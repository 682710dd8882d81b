"""Wall time of the configuration's create_view calls in set-up."""


def read(run):
    return run.views_build_s
