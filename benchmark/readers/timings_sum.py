"""A sum of the program's own barrier-closed stage clocks of one train
(``ops/als.als_train(..., timings=)``), optionally over another of its
entries (seconds of all sweeps over the iterations)."""


def read(run, keys: list, per: str | None = None):
    if run.timings is None or not all(k in run.timings for k in keys):
        return None
    total = sum(float(run.timings[k]) for k in keys)
    return total / float(run.timings[per]) if per else total
