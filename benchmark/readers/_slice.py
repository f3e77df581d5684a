"""The traced slice's profile, read once more for what ``run.trace`` (a
``trace_reduce.TraceSummary``) does not carry: the program's own host spans
(``pio:...``, ``predictionio_tpu/obs/jaxprof.annotate``) and, for every
device operation, the ``op_name`` that ``jax.named_scope`` puts into it.
Shared by ``scope_mean_ms`` and ``idle_under_span``; not a reader itself.

Where the profile is: a reader gets no path, but the slice's ``.xplane.pb``
still stands when readers run (``harness.open_cell`` removes its
``benchmark-run-*`` directory only afterwards, trace under ``trace/``), and
the holder of the chip is alone on it, so the newest one under
``tempfile.gettempdir()`` is this run's. It is loaded once a process.

Which stat bears the ``op_name`` (looked at on a TPU v5 lite, jax 0.9.0,
PR 24): ``tf_op``, a stat of the event's METADATA on the ``XLA Ops`` line
(``jit(_serve_by_index_batch)/score/dot_general:``), beside
``hlo_category`` and ``source``. ``jax.profiler.ProfileData`` shows an
event's own stats only (``device_offset_ps``, ``device_duration_ps``), so
the metadata is read from the file's bytes: the few fields of the protobuf
wire format that lead to it, nothing else decoded. Events, their times and
the slice's ends come from ``trace_reduce.load``, as ``trace_reduce.reduce``
takes them, so the two agree to the nanosecond.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import os
import tempfile

from benchmark import trace_reduce

SPAN_PREFIX = "pio:"
OP_NAME_STAT = "tf_op"


@dataclasses.dataclass
class SliceProfile:
    start_ns: float  # benchmark:slice_start
    end_ns: float  # benchmark:slice_end
    spans: list  # [(name, start_ns, end_ns)] of the program's pio: spans
    ops: list  # [(start_ns, end_ns, hlo text, op_name)] of the first chip


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an
    int, a length-delimited field as a memoryview, fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_names(data: bytes) -> dict[str, set]:
    """``{HLO text: {op_name, ...}}`` over the device planes of a serialized
    ``XSpace``: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (a string kept once, as a stat metadata's name)."""
    out: dict[str, set] = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_map_value(v))
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        for event in events:
            hlo, found = "", None
            for f, v in _fields(event):
                if f == 2:
                    hlo = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                        found = (
                            bytes(stat[5]).decode() if 5 in stat else stat_names.get(stat.get(7), "")
                        )
            if found:
                # "name:type"; XLA leaves the type empty
                out.setdefault(hlo, set()).add(found.rsplit(":", 1)[0])
    return out


@functools.lru_cache(maxsize=1)
def read(path: str) -> SliceProfile | None:
    """One ``.xplane.pb`` (gzipped or not) as a ``SliceProfile``; None where
    it lacks the slice's annotations or a device plane."""
    profile = trace_reduce.load(path)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        names = op_names(f.read())
    start = end = None
    spans, chips = [], {}
    for plane in profile.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            chips[int(m.group(1))] = {line.name: line for line in plane.lines}
        elif plane.name == trace_reduce.HOST_PLANE:
            # a thread's line bears the process's name: look in every line
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace_reduce.SLICE_START and start is None:
                        start = e.start_ns
                    elif e.name == trace_reduce.SLICE_END:
                        end = e.start_ns
                    elif e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    if not chips or start is None or end is None or end <= start:
        return None
    line = chips[min(chips)].get(trace_reduce.OPS_LINE)
    ops = [
        (e.start_ns, e.start_ns + e.duration_ns, e.name, names.get(e.name, frozenset()))
        for e in (line.events if line is not None else ())
    ]
    return SliceProfile(start, end, spans, ops)


def load(run) -> SliceProfile | None:
    """This run's traced slice; None for a run that traced nothing."""
    if run.trace is None:
        return None
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "benchmark-run-*", "trace"))
    if not dirs:
        return None
    try:
        return read(trace_reduce.find_xplane(max(dirs, key=os.path.getmtime)))
    except FileNotFoundError:
        return None


def union(intervals) -> list:
    return trace_reduce._union([[a, b] for a, b in intervals if b > a])


def intersect(xs: list, ys: list) -> list:
    """Two sorted lists of disjoint intervals, intersected."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(xs: list, start, end) -> list:
    """The holes of a sorted list of disjoint intervals inside [start, end]."""
    out, cursor = [], start
    for a, b in xs:
        if a > cursor:
            out.append([cursor, min(a, end)])
        cursor = max(cursor, b)
    if cursor < end:
        out.append([cursor, end])
    return [[a, b] for a, b in out if b > a]


def clip(intervals, start, end) -> list:
    return [(max(a, start), min(b, end)) for a, b in intervals]
