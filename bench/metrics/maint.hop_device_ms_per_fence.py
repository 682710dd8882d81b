"""Device time of the unfused maintenance hops (modules
jit__hop_segment*) per fence, from the trace."""


def read(run):
    if run.trace is None or run.fences == 0:
        return None
    s = run.trace.prefix_s("jit__hop_segment")
    return None if s is None else s / run.fences * 1e3
