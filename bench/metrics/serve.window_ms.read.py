"""Harness span around every ServeEngine.step() that ran a read window,
per read window."""


def read(run):
    total, n = run.steps.get("read", (0.0, 0))
    return total / n * 1e3 if n else None
