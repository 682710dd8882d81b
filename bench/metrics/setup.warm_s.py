"""Wall time of the warm-up phase of set-up."""


def read(run):
    return run.warm_s
