"""From a profiler trace (``.xplane.pb``) to the device's busy and idle time,
each program's device time, the operations that took most time and the
longest idle gaps. The yardstick's own reduction: every PR computes these
numbers the same way, and none that claims a gain can change how.

What a TPU trace holds (looked at by hand on a v5e, jax 0.9.0, PR 23): one
plane ``/device:TPU:<n>`` per chip with a line ``XLA Modules`` (one event per
execution of a compiled program, named ``jit_<function>(<fingerprint>)``) and
a line ``XLA Ops`` (one event per HLO operation, named by its HLO text);
a plane ``/host:CPU`` with one line per host thread, named after the thread,
among them the process's ``jax.profiler.TraceAnnotation`` spans. All events share one clock, in
nanoseconds from the start of the trace (device and host agreed within
0.1 ms).

The traced slice runs from the ``benchmark:slice_start`` annotation to the
``benchmark:slice_end`` one. Busy time is the union of the ``XLA Ops``
intervals inside the slice, averaged over the chips used; an idle gap is a
hole in that union on the first chip.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
import time
from typing import Callable

SLICE_START = "benchmark:slice_start"
SLICE_END = "benchmark:slice_end"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
TOP_OPS = 10
TOP_GAPS = 5

_LAYOUT = re.compile(r"\{[^{}]*\}")
_FINGERPRINT = re.compile(r"\(\d+\)$")
_CONTAINER = re.compile(r"[ )](while|conditional|call)\(")


@dataclasses.dataclass
class TraceSummary:
    window_s: float  # length of the traced slice
    busy_s: float  # seconds an operation ran on the device, mean over chips
    device_ops: list  # [[name, seconds]], most time first, all chips summed
    programs: dict  # function name -> {"count", "seconds"} (first chip)
    gaps: list  # [[name, seconds, start_s in the slice]], longest first
    gap_totals: dict  # name -> idle seconds under that name (first chip)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        """The ``breakdown`` of a ``--trace 1`` line: the operations that
        took most device time, and the longest idle gaps by what the host
        was doing, followed by the idle seconds under each name."""
        gaps = [[name, seconds] for name, seconds, _ in self.gaps[:TOP_GAPS]]
        totals = sorted(self.gap_totals.items(), key=lambda kv: -kv[1])
        gaps += [[f"sum:{name}", seconds] for name, seconds in totals[: 10 - len(gaps)]]
        return {"device_ops": self.device_ops[:TOP_OPS], "idle_gaps": gaps}


def op_label(hlo: str) -> str:
    """An HLO operation's text without its layouts, cut to 96 characters:
    ``%custom-call = (f32[128,16], s32[128,16]) custom-call(f32[128,5700000] ...``"""
    return _LAYOUT.sub("", hlo)[:96].strip()


def program_label(module: str) -> str:
    """``jit__serve_by_index_batch(5519314190413188012)`` without the
    fingerprint, which differs from bucket to bucket and build to build."""
    return _FINGERPRINT.sub("", module)


def load(path: str):
    """A ``ProfileData`` from an ``.xplane.pb``, gzipped or not."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list) -> list:
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def reduce(
    profile,
    chips: int = 1,
    name_gap: Callable[[float, float], str] | None = None,
) -> TraceSummary:
    """Reduce one trace. ``name_gap(start_s, end_s)`` names an idle gap from
    its place in the slice (seconds from ``benchmark:slice_start``); without
    it every gap is ``unnamed``."""
    device_lines: dict[int, dict] = {}
    slice_start = slice_end = None
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            device_lines[int(m.group(1))] = {line.name: line for line in plane.lines}
        elif plane.name == HOST_PLANE:
            # a thread's line bears the thread's name ("python", "python3",
            # the server's worker): the annotations are looked for in all
            for line in plane.lines:
                for event in line.events:
                    if event.name == SLICE_START and slice_start is None:
                        slice_start = event.start_ns
                    elif event.name == SLICE_END:
                        slice_end = event.start_ns
    used = sorted(device_lines)[:chips]
    if not used:
        raise ValueError("the trace holds no /device:TPU:<n> plane")

    per_chip_ops = {}
    for chip in used:
        line = device_lines[chip].get(OPS_LINE)
        per_chip_ops[chip] = (
            [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
            if line is not None
            else []
        )
    everything = [iv for ops in per_chip_ops.values() for iv in ops]
    if not everything:
        raise ValueError("no operation ran on the device inside the trace")
    if slice_start is None or slice_end is None or slice_end <= slice_start:
        raise ValueError(f"the trace lacks the {SLICE_START} and {SLICE_END} annotations")
    window_ns = slice_end - slice_start

    op_seconds: dict[str, float] = {}
    busy_ns = 0.0
    merged_first: list = []
    for chip in used:
        clipped = []
        for start, end, name in per_chip_ops[chip]:
            start, end = max(start, slice_start), min(end, slice_end)
            if end <= start:
                continue
            clipped.append((start, end))
            if _CONTAINER.search(name):
                continue  # a loop's own event spans its body's: not ranked beside them
            label = op_label(name)
            op_seconds[label] = op_seconds.get(label, 0.0) + (end - start) * 1e-9
        merged = _union(clipped)
        busy_ns += sum(end - start for start, end in merged)
        if chip == used[0]:
            merged_first = merged

    programs: dict[str, dict] = {}
    modules = device_lines[used[0]].get(MODULES_LINE)
    for event in modules.events if modules is not None else ():
        if event.start_ns < slice_start or event.start_ns >= slice_end:
            continue
        row = programs.setdefault(program_label(event.name), {"count": 0, "seconds": 0.0})
        row["count"] += 1
        row["seconds"] += event.duration_ns * 1e-9

    gaps = []
    cursor = slice_start
    for start, end in merged_first + [[slice_end, slice_end]]:
        if start > cursor:
            a, b = (cursor - slice_start) * 1e-9, (start - slice_start) * 1e-9
            gaps.append([name_gap(a, b) if name_gap else "unnamed", b - a, a])
        cursor = max(cursor, end)
    gap_totals: dict[str, float] = {}
    for name, seconds, _ in gaps:
        gap_totals[name] = gap_totals.get(name, 0.0) + seconds
    gaps.sort(key=lambda g: -g[1])

    return TraceSummary(
        window_s=window_ns * 1e-9,
        busy_s=busy_ns * 1e-9 / len(used),
        device_ops=[[k, v] for k, v in sorted(op_seconds.items(), key=lambda kv: -kv[1])],
        programs=programs,
        gaps=gaps[:64],
        gap_totals=gap_totals,
    )


class Slice:
    """Trace a slice of a run: ``with Slice(trace_dir) as s: ...``, then
    ``s.reduce(chips, name_gap)``. The annotations at its ends are what
    ``reduce`` cuts by; ``start_monotonic`` is the host's clock at the first,
    so a driver can place its own log (requests in flight, a train's stages)
    on the slice's seconds."""

    def __init__(self, trace_dir: str):
        self.trace_dir = str(trace_dir)
        self.start_monotonic = None

    def __enter__(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # annotations only, no call tracing
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.start_monotonic = time.monotonic()
        with jax.profiler.TraceAnnotation(SLICE_START):
            pass
        return self

    def __exit__(self, *exc):
        import jax

        with jax.profiler.TraceAnnotation(SLICE_END):
            pass
        jax.profiler.stop_trace()
        return False

    def reduce(self, chips: int = 1, name_gap=None) -> TraceSummary:
        return reduce(load(find_xplane(self.trace_dir)), chips, name_gap)
