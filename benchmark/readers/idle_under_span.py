"""The share of the traced slice in which the device was idle WHILE the host
was inside one of the program's own spans, in percent of the slice. Idle is
what ``trace_reduce`` calls idle: the holes in the union of the first chip's
``XLA Ops`` intervals inside the slice. ``spans`` are prefixes of span names
(``pio:dispatch`` takes ``pio:dispatch.decode`` too) on any host thread;
none means any time at all. ``unless`` are prefixes of spans that come
first: idle time under one of them is theirs. So metrics whose ``unless``
lists every span named before them share the idle time out with no
overlap, and with a last one that names no span they sum to the device's
idle share. A slice with no such span gives 0, and all the idle time to
the metric that names none."""

from benchmark.readers import _slice


def _open(profile, prefixes) -> list:
    named = [(a, b) for name, a, b in profile.spans if name.startswith(tuple(prefixes))]
    return _slice.union(_slice.clip(named, profile.start_ns, profile.end_ns))


def read(run, spans: list, unless: list):
    profile = _slice.load(run)
    if profile is None:
        return None
    start, end = profile.start_ns, profile.end_ns
    busy = _slice.union(_slice.clip([op[:2] for op in profile.ops], start, end))
    idle = _slice.complement(busy, start, end)
    if spans:
        idle = _slice.intersect(idle, _open(profile, spans))
    if unless:
        idle = _slice.intersect(idle, _slice.complement(_open(profile, unless), start, end))
    return 100.0 * sum(b - a for a, b in idle) / (end - start)
