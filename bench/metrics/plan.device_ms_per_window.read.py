"""Device time of the fused plan programs (modules jit__program*) per
read window, from the trace."""


def read(run):
    if run.trace is None or run.read_windows == 0:
        return None
    s = run.trace.prefix_s("jit__program")
    return None if s is None else s / run.read_windows * 1e3
