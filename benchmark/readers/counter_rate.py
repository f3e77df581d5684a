"""The growth of the program's counters ``counters`` over the window, summed,
over the window's seconds: real tokens a second."""


def read(run, counters: list):
    if run.window_s <= 0 or not all(k in run.counters_end for k in counters):
        return None
    return sum(run.grown(k) for k in counters) / run.window_s
