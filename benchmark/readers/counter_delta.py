"""By how much the program's counters whose names start with one of
``prefixes`` grew over the window, all of them summed."""


def read(run, prefixes: list):
    keys = [k for k in run.counters_end if any(k.startswith(p) for p in prefixes)]
    return sum(run.grown(k) for k in keys) if keys else None
