"""Read tickets completed in the window per window second."""


def read(run):
    if run.read_lat_s.size == 0:
        return None
    return run.read_lat_s.size / run.window_s
