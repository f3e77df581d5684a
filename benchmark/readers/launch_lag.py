"""A launch's wait in the device's queue, from the traced slice: the host's
``DoEnqueueProgram`` event of an execution and the execution's event on the
first chip's ``XLA Modules`` line carry the same ``run_id`` stat (looked at
on a TPU v5 lite, jax 0.9.0, in the slice PR 24 recorded: 28 of each), on the
one clock host and device share (``trace_reduce``'s docstring), so every
execution is paired with its own enqueue and nothing is guessed from order.

An execution counts when it BEGINS inside the slice and its enqueue begins
inside one of the program's spans named by ``under`` (prefixes, on any
thread, by time: ``pio:dispatch`` keeps the serving path's launches and
leaves out a check's or a warm-up's). An execution whose enqueue the trace
does not hold (it came before the trace began) is left out. A chip runs its
programs in the order they were enqueued, so those stand at the slice's
FRONT, as many as stood in the queue when the trace began, and the share
that pairs is taken from the first execution on whose enqueue the trace
holds. What is kept is then every launch enqueued between the trace's start
and the enqueue of the slice's last execution: a window by the time of the
ENQUEUE, which takes no side between long waits and short ones.

``measure="lag_ms"``: the mean, over those pairs, of device start minus
enqueue start, in milliseconds. The two clocks agree to about a millisecond:
in the recorded slice a launch that met an idle device reads -0.42 to -0.48
(the device's event BEFORE the host's), and is counted as it reads, not cut
at 0. ``measure="queued"``: the mean, over the same enqueues, of the
executions enqueued earlier and not yet ended on the first chip at that
moment (the depth of the queue a launch meets, whoever launched what stands
in it). Only what the first chip is seen to run stands in that queue, from
its enqueue (an execution without one: from before the trace) to its end,
and beside it what was enqueued after everything seen running and is still
waiting when the trace ends; an enqueue for another chip, or one whose
execution is never seen, adds nothing, so the depth stays the first chip's.

None, with one line on stderr, where under half of those executions pair or
the trace bears no ``run_id`` on either side; the value, with one line on
stderr, where under 95% of the slice's executions pair, the front counted;
None in silence for an untraced run and for a slice in which nothing was
launched under ``under``.
"""

import bisect
import functools
import glob
import os
import tempfile

from benchmark import trace_reduce
from benchmark.readers import _slice
from benchmark.readers.scope_mean_ms import _say_once

ENQUEUE_EVENT = "DoEnqueueProgram"
RUN_ID_STAT = "run_id"


def slice_path(run) -> str | None:
    """The traced slice's file, found as ``_slice.load`` finds it."""
    if run.trace is None:
        return None
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "benchmark-run-*", "trace"))
    try:
        return trace_reduce.find_xplane(max(dirs, key=os.path.getmtime)) if dirs else None
    except FileNotFoundError:
        return None


@functools.lru_cache(maxsize=1)
def run_ids(path: str) -> tuple[dict, dict]:
    """``({run_id: enqueue start}, {run_id: (device start, device end)})``
    of one ``.xplane.pb``, nanoseconds on the trace's clock: the host's
    enqueue events on any thread, the first chip's executions."""
    enqueues, chips = {}, {}
    for plane in trace_reduce.load(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            chips[int(m.group(1))] = {line.name: line for line in plane.lines}
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == ENQUEUE_EVENT:
                        run_id = dict(e.stats).get(RUN_ID_STAT)
                        if run_id is not None:
                            enqueues[run_id] = e.start_ns
    modules = chips[min(chips)].get(trace_reduce.MODULES_LINE) if chips else None
    executions = {}
    for e in modules.events if modules is not None else ():
        run_id = dict(e.stats).get(RUN_ID_STAT)
        if run_id is not None:
            executions[run_id] = (e.start_ns, e.start_ns + e.duration_ns)
    return enqueues, executions


def pairs(profile, enqueues: dict, executions: dict, under: list) -> list | None:
    """``[(enqueue start, device start)]`` of the executions that begin
    inside the slice and were enqueued under one of ``under``; None where
    too few of the slice's executions pair."""
    inside = sorted(
        (v[0], k) for k, v in executions.items() if profile.start_ns <= v[0] < profile.end_ns
    )
    held = [k in enqueues for _, k in inside]
    front = held.index(True) if True in held else len(inside)
    paired = [(enqueues[k], start) for (start, k), ok in zip(inside, held) if ok]
    said = (
        f"benchmark: {len(paired)} of the slice's {len(inside)} executions pair with a "
        f"{ENQUEUE_EVENT} event by {RUN_ID_STAT} ({len(enqueues)} such events; the first "
        f"{front} were enqueued before the trace began)"
    )
    if not paired or len(paired) < 0.5 * (len(inside) - front):
        _say_once(said + ": no launch lag")
        return None
    if len(paired) < 0.95 * len(inside):
        _say_once(said)
    named = [(a, b) for name, a, b in profile.spans if name.startswith(tuple(under))]
    open_ = _slice.union(named)
    begins = [a for a, _ in open_]

    def is_under(t):
        i = bisect.bisect_right(begins, t) - 1
        return i >= 0 and t < open_[i][1]

    return [p for p in paired if is_under(p[0])]


def queue(enqueues: dict, executions: dict) -> tuple[list, list]:
    """``(sorted times something joined the first chip's queue, sorted times
    something left it)``: every execution seen, from its enqueue (from before
    the trace where the trace holds none) to its end, and the enqueues after
    the newest one seen running, which wait beyond the trace's end."""
    joined = [enqueues.get(k, float("-inf")) for k in executions]
    running = [t for k, t in enqueues.items() if k in executions]
    newest = max(running, default=float("inf"))
    joined += [t for k, t in enqueues.items() if k not in executions and t > newest]
    return sorted(joined), sorted(end for _, end in executions.values())


def read(run, measure: str, under: list):
    path = slice_path(run)
    profile = _slice.read(path) if path else None
    if profile is None:
        return None
    enqueues, executions = run_ids(path)
    kept = pairs(profile, enqueues, executions, under)
    if not kept:
        return None
    if measure == "lag_ms":
        return 1e-6 * sum(device - enqueued for enqueued, device in kept) / len(kept)
    if measure == "queued":
        joined, left = queue(enqueues, executions)
        return sum(
            bisect.bisect_left(joined, t) - bisect.bisect_right(left, t) for t, _ in kept
        ) / len(kept)
    raise ValueError(f"launch_lag: no measure {measure!r}")
