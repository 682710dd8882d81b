"""JAX tracing and compile events inside the window (should be 0)."""


def read(run):
    return run.compiles_in_window
