"""One of the run's counts over the window's seconds."""


def read(run, count: str):
    if count not in run.counts or run.window_s <= 0:
        return None
    return run.counts[count] / run.window_s
