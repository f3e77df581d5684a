"""A ratio of the growth of two groups of the program's counters over the
window, times ``scale``: queries over batches dispatched, cache hits over
look-ups."""


def read(run, numerator: list, denominator: list, scale: float = 1.0):
    if not all(k in run.counters_end for k in numerator + denominator):
        return None
    below = sum(run.grown(k) for k in denominator)
    return scale * sum(run.grown(k) for k in numerator) / below if below > 0 else None
