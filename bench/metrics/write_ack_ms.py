"""Window seconds per write batch acknowledged, for the one closed-loop
writer: its mean time from submit_writes to the fence applied with the
views maintained."""


def read(run):
    if run.writes_acked == 0:
        return None
    return run.window_s / run.writes_acked * 1e3
