"""Device time per fence of every module outside the fused read programs
(jit__program*): the hops, scatter-adds, gathers and small element-wise
programs that apply_writes and view maintenance launch, from the trace."""


def read(run):
    if run.trace is None or run.fences == 0:
        return None
    t = run.trace
    total = sum(t.module_s.values()) / t.devices
    s = total - (t.prefix_s("jit__program") or 0.0)
    return s / run.fences * 1e3 if s > 0 else None
