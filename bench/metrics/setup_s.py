"""Process start to the first timed request: graph load, views, warm-up."""


def read(run):
    return run.setup_s
