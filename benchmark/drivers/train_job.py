"""Traffic kind ``train_job``: whole trains back to back. A new train starts
while the window is open and the one in flight always finishes; the
end-to-end metric is the median wall of a train. The traced run makes one
train through the program's own stage clocks, under the profiler.
"""

from __future__ import annotations

import time
import types

import jax

from benchmark import harness, trace_reduce


def stage_namer(timings: dict, train_start_s: float):
    """Name an idle gap of the device by the train's stage that holds its
    middle: the stages run one after another, so their boundaries follow
    from the train's start (in the slice's seconds) and their lengths."""
    edges, t = [], train_start_s
    for stage, key in (
        ("pack", "pack_s"), ("upload", "upload_s"), ("build", "build_s"), ("sweep", "device_s"),
    ):
        edges.append((stage, t, t + float(timings.get(key, 0.0))))
        t = edges[-1][2]

    def name(start_s: float, end_s: float) -> str:
        middle = 0.5 * (start_s + end_s)
        for stage, lo, hi in edges:
            if lo <= middle < hi:
                return stage
        return "before_train" if middle < train_start_s else "after_sweep"

    return name


def run(ctx, engine):
    job = engine.training(ctx)
    job.warm(instrumented=ctx.trace)
    notes = {"setup_parts": job.parts}
    t0 = time.monotonic()
    events_start = ctx.compile_events()
    walls, timings, tracer, model = [], None, None, None
    if ctx.trace:
        tracer = trace_reduce.Slice(ctx.workdir / "trace")
        with tracer:
            train_start_s = time.monotonic() - tracer.start_monotonic
            with jax.profiler.TraceAnnotation("benchmark:train"):
                timings, factors = job.instrumented()
        model = types.SimpleNamespace(user_factors=factors[0], item_factors=factors[1])
        walls.append(timings["wall_s"])
    else:
        while time.monotonic() - t0 < ctx.seconds:
            t = time.monotonic()
            model = job.train()
            walls.append(time.monotonic() - t)
    window_s = time.monotonic() - t0
    events_end = ctx.compile_events()
    ok, detail = job.check(model)
    notes.update(detail, trains=len(walls))
    run = harness.Run(
        setup_seconds=t0 - ctx.process_start,
        window_s=window_s,
        attempted=len(walls),
        failed=0,
        correct=ok,
        series={"train_s": walls},
        counts={"trains": len(walls)},
        counters_start={"benchmark.compile_events": events_start},
        counters_end={"benchmark.compile_events": events_end},
        timings=timings,
        shapes=job.shapes(),
        notes=notes,
    )
    if tracer is not None:
        run.trace = harness.reduce_slice(ctx, tracer, stage_namer(timings, train_start_s))
    return run
