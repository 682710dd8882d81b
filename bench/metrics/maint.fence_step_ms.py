"""Harness span around every ServeEngine.step() that applied a write
fence, per fence."""


def read(run):
    total, n = run.steps.get("fence", (0.0, 0))
    return total / n * 1e3 if n else None
