"""The benchmark's harness: one cell, one run, one result line.

Driven by data. A cell of ``BENCHMARK.json`` names a configuration and a
traffic mix; everything else is found by those names:

- ``benchmark/configs/<config>.json`` (the ``file`` of the configuration's
  entry) holds the sizes as run and names its ``engine``, a module under
  ``benchmark/engines/`` that builds the system under test;
- ``benchmark/traffic/<traffic>.json`` holds the mix's parameters and names
  its ``kind``, a module under ``benchmark/drivers/`` that offers the load;
  ``benchmark/cells/<cell>.json``, where it exists, holds what belongs to
  this cell alone (its fixed rate) and is laid over the traffic file;
- ``benchmark/end_to_end/<metric>.json`` and
  ``benchmark/layer_metrics/<metric>.json`` name the ``reader`` (a module
  under ``benchmark/readers/`` with ``read(run, **args)``) that takes the
  metric from the run; a reader that finds nothing to read returns None and
  the metric is left out of the line.

So a later PR adds a configuration, a mix, a cell or a metric by adding files
and entries; no file here holds a cell's, a configuration's or a metric's
name.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

class Refused(Exception):
    """The run cannot be made here (no such cell, wrong platform, too few
    chips): exit non-zero, print no result."""


@dataclasses.dataclass
class Context:
    root: Path
    workload: str
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    process_start: float  # time.monotonic() when the process started
    workdir: Path  # scratch for this run, removed when it ends
    compile_events: Any = None  # callable: compile-or-cache-load events so far
    platform: str = "tpu"


@dataclasses.dataclass
class Run:
    """What a driver hands back; the readers take every metric from it."""

    setup_seconds: float
    window_s: float
    attempted: int
    failed: int
    correct: bool
    series: dict = dataclasses.field(default_factory=dict)  # name -> list of readings
    counts: dict = dataclasses.field(default_factory=dict)  # name -> count in the window
    counters_start: dict = dataclasses.field(default_factory=dict)
    counters_end: dict = dataclasses.field(default_factory=dict)
    timings: dict | None = None  # the program's own stage clocks (traced run)
    shapes: dict = dataclasses.field(default_factory=dict)
    trace: Any = None  # trace_reduce.TraceSummary of the traced slice
    peak: dict | None = None  # this device's row of peaks.json
    notes: dict = dataclasses.field(default_factory=dict)  # printed to stderr

    def grown(self, counter: str) -> float:
        """By how much one of the program's counters grew over the window."""
        return self.counters_end.get(counter, 0.0) - self.counters_start.get(counter, 0.0)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str):
    """``(bench, cell, config, traffic)`` for one cell's name."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no cell {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load_json(root / entry["file"])
    traffic = _load_json(root / "benchmark" / "traffic" / f"{cell['traffic']}.json")
    own = root / "benchmark" / "cells" / f"{workload}.json"
    if own.exists():
        traffic = {**traffic, **_load_json(own)}
    return bench, cell, config, traffic


def metrics_of(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The entries this run reports: the cell's end-to-end metrics, or with
    ``--trace 1`` its per-layer metrics. An entry without ``workloads`` is
    every cell's."""
    entries = bench["per_layer" if trace else "end_to_end"]
    return [e for e in entries if workload in e.get("workloads", [workload])]


def read_metric(root: Path, trace: bool, name: str, run: Run):
    spec = _load_json(
        root / "benchmark" / ("layer_metrics" if trace else "end_to_end") / f"{name}.json"
    )
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(run, **spec.get("args", {}))


def device_report(devices, run: Run) -> dict:
    # the runtime books live arrays under bytes_in_use and the compiled
    # programs' temporaries under bytes_reserved: the chip holds both
    stats = [d.memory_stats() or {} for d in devices]
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(
            int(s.get("peak_bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0))
            for s in stats
        ),
    }
    if run.trace is not None:
        report["busy_s"] = run.trace.busy_s
        report["window_s"] = run.trace.window_s
    return report


@contextlib.contextmanager
def open_cell(
    root: Path,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    process_start: float,
    platform: str = "tpu",
):
    """Everything one cell's name leads to, ready to drive: yields ``(bench,
    ctx, engine, driver, devices)``. ``platform`` is what the run insists on
    finding; the command line always insists on the chip, and only the tests'
    rehearsals ask for anything else."""
    root = Path(root)
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    if not (root / "predictionio_tpu").is_dir():
        raise Refused(f"{root} holds the benchmark but not the program it measures")
    bench, cell, config, traffic = load_cell(root, workload)

    from predictionio_tpu.utils.platform import configure_jax

    configure_jax()  # platform and compile cache, before JAX is imported
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise Refused(f"found platform {devices[0].platform!r}, the cell runs on {platform!r}")
    if len(devices) < int(cell["chips"]):
        raise Refused(f"found {len(devices)} chip(s), the cell asks for {cell['chips']}")

    events = [0]

    def on_duration(event, duration_secs, **_):
        # one per program compiled or loaded from the persistent cache
        if str(event).endswith("/backend_compile_duration"):
            events[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    engine = importlib.import_module(f"benchmark.engines.{config['engine']}")
    driver = importlib.import_module(f"benchmark.drivers.{traffic['kind']}")
    workdir = Path(tempfile.mkdtemp(prefix="benchmark-run-"))
    try:
        ctx = Context(
            root, workload, cell, config, traffic, int(seed), float(seconds),
            bool(trace), process_start, workdir, lambda: events[0], platform,
        )
        yield bench, ctx, engine, driver, devices[: int(cell["chips"])]
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def run_cell(root: Path, workload: str, seed, seconds, trace, process_start, platform="tpu") -> dict:
    """Run one cell once and return the result line's object."""
    with open_cell(root, workload, seed, seconds, trace, process_start, platform) as opened:
        bench, ctx, engine, driver, devices = opened
        peak = None
        if ctx.trace and platform == "tpu":
            peaks = _load_json(ctx.root / "benchmark" / "peaks.json")
            if devices[0].device_kind not in peaks:
                raise Refused(f"no peaks for device kind {devices[0].device_kind!r} in peaks.json")
            peak = peaks[devices[0].device_kind]
        run = driver.run(ctx, engine)
        run.peak = peak
        metrics = {}
        for entry in metrics_of(bench, workload, ctx.trace):
            value = read_metric(ctx.root, ctx.trace, entry["name"], run)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
        line = {
            "correct": bool(run.correct),
            "attempted": int(run.attempted),
            "failed": int(run.failed),
            "metrics": metrics,
            "device": device_report(devices, run),
        }
        if run.trace is not None:
            line["breakdown"] = run.trace.breakdown()
        print(json.dumps({"notes": run.notes}, default=str), file=sys.stderr, flush=True)
        return line


def reduce_slice(ctx: Context, tracer, name_gap):
    """The traced slice's summary. On the chip a trace in which nothing ran
    on the device is an error; a rehearsal on another platform has no device
    plane to read and carries no trace."""
    try:
        return tracer.reduce(int(ctx.cell["chips"]), name_gap)
    except ValueError:
        if ctx.platform == "tpu":
            raise
        return None


def sleep_until(deadline: float) -> None:
    wait = deadline - time.monotonic()
    if wait > 0:
        time.sleep(wait)
