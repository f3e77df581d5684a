"""A percentile of one of the run's series of readings (latencies of the
window's requests, walls of its trains): ``q`` 50 is the median."""

import numpy as np


def read(run, series: str, q: float):
    values = run.series.get(series)
    if values is None or len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
