"""ServeStats read tickets answered per read window, in the window."""


def read(run):
    if run.read_windows == 0:
        return None
    return run.read_tickets / run.read_windows
