"""Mean milliseconds of the program's own ``pio:`` spans
(``predictionio_tpu/obs/jaxprof.annotate``) that begin inside the traced
slice and bear one of ``spans`` as their whole name, on any host thread:
the host's time in one launch of the session scorer, say. None where the
slice holds no such span (a program that does not write it, an engine that
never runs it) and for an untraced run."""

from benchmark.readers import _slice


def read(run, spans: list):
    profile = _slice.load(run)
    if profile is None:
        return None
    took = [
        b - a for name, a, b in profile.spans if name in spans and profile.start_ns <= a < profile.end_ns
    ]
    return 1e-6 * sum(took) / len(took) if took else None
