"""What the two HTTP drivers share: deploy, start the load generators as
processes of their own, hold the window, read the program's counters at its
ends, trace a slice of it, gather the generators' logs and check answers.

The process that calls this holds the chip and runs the server (as ``pio
deploy`` would, on its own event loop and thread). The generators
(``benchmark/loadgen.py``) never import JAX.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time

import numpy as np

from benchmark import harness, trace_reduce

LEAD_S = 1.5  # from the generators' start to their first request


def _spawn(ctx, specs: list[dict]) -> list[subprocess.Popen]:
    procs = []
    for p, spec in enumerate(specs):
        spec_path = ctx.workdir / f"generator-{p}.json"
        spec["result"] = str(ctx.workdir / f"generator-{p}.result.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        procs.append(
            subprocess.Popen(
                [sys.executable, str(ctx.root / "benchmark" / "loadgen.py"), str(spec_path)],
                stdin=subprocess.DEVNULL,
            )
        )
    return procs


def _gather(procs, specs, deadline_s: float) -> dict[str, np.ndarray]:
    """Wait for every generator and merge their logs (seconds from t0)."""
    try:
        for proc in procs:
            proc.wait(timeout=max(1.0, deadline_s - time.monotonic()))
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    columns: dict[str, list] = {k: [] for k in ("due", "sent", "done", "ok")}
    kept, connects, errors = {}, 0, []
    for proc, spec in zip(procs, specs):
        if proc.returncode != 0:
            raise RuntimeError(f"load generator exited with {proc.returncode}")
        with open(spec["result"]) as f:
            result = json.load(f)
        users = spec["users"]
        n = len(users)
        for key in ("due", "sent", "done", "ok"):
            columns[key] += result[key]
        kept.update({users[int(i) % n]: body for i, body in result["kept"].items()})
        connects += result["connects"]
        errors += result["errors"]
    log = {k: np.asarray(v) for k, v in columns.items()}
    log["ok"] = log["ok"].astype(bool)
    return {"log": log, "kept": kept, "connects": connects, "errors": errors}


def in_flight_namer(log: dict[str, np.ndarray], slice_start_s: float):
    """Name an idle gap of the device by whether a request was in flight at
    its middle, from the generators' log. ``slice_start_s`` is the traced
    slice's start in the log's seconds."""
    sent = np.sort(log["sent"])
    done = np.sort(log["done"])

    def name(start_s: float, end_s: float) -> str:
        middle = slice_start_s + 0.5 * (start_s + end_s)
        flying = np.searchsorted(sent, middle, "right") - np.searchsorted(done, middle, "right")
        return "request_in_flight" if flying > 0 else "no_request"

    return name


def run(ctx, engine, measure) -> harness.Run:
    """One serving run: deploy, ``measure(ctx, engine, deployment)``, stop."""
    deployment = engine.serving(ctx)
    try:
        return measure(ctx, engine, deployment)
    finally:
        deployment.stop()


def measure(ctx, engine, deployment, mode: str, users, due=None) -> harness.Run:
    """Offer one window of load to a deployment that stands, and read it.
    ``mode`` is the generators' loop ("open" or "closed"), ``users`` the
    users asked for, in order, and for an open loop ``due`` their due times
    in seconds from the window's start."""
    traffic = ctx.traffic
    n_procs = int(traffic["generator_processes"])
    rng = np.random.default_rng([ctx.seed, 3])
    specs = []
    for p in range(n_procs):
        spec = {
            "mode": mode,
            "port": deployment.port,
            "path": deployment.path,
            "body_format": deployment.body_format,
            "item_marker": deployment.item_marker,
            "items_expected": deployment.items_expected,
            "timeout_s": float(traffic["timeout_s"]),
            "connections": int(traffic["connections"]) // n_procs,
            "users": users[p::n_procs].tolist(),
        }
        if mode == "open":
            mine = due[p::n_procs]
            spec["due"] = mine.tolist()
            # answers kept for the reference: requests due inside the window
            inside = np.flatnonzero((mine >= 0) & (mine < ctx.seconds))
        else:
            spec["start_s"] = -float(traffic["ramp_s"])
            spec["stop_s"] = ctx.seconds
            # a closed loop has no schedule: its first requests are kept,
            # past the ramp's (each connection's first are the ramp's)
            inside = np.arange(spec["connections"] * 8, spec["connections"] * 40)
        share = engine.CHECKED_QUERIES // n_procs
        spec["keep"] = rng.choice(inside, min(share, len(inside)), replace=False).tolist()
        specs.append(spec)

    t0 = time.monotonic() + LEAD_S + float(traffic["ramp_s"])
    for spec in specs:
        spec["t0"] = t0
    procs = _spawn(ctx, specs)
    notes = {"setup_parts": deployment.parts}
    tracer = None
    try:
        harness.sleep_until(t0)
        counters_start = deployment.counters()
        counters_start["benchmark.compile_events"] = ctx.compile_events()
        if ctx.trace:
            harness.sleep_until(t0 + float(traffic["trace_offset_s"]))
            tracer = trace_reduce.Slice(ctx.workdir / "trace")
            with tracer:
                time.sleep(float(traffic["trace_slice_s"]))
        harness.sleep_until(t0 + ctx.seconds)
        counters_end = deployment.counters()
        counters_end["benchmark.compile_events"] = ctx.compile_events()
    except BaseException:
        for proc in procs:
            proc.kill()
        raise
    gathered = _gather(procs, specs, t0 + ctx.seconds + 4 * float(traffic["timeout_s"]) + 30)
    log = gathered["log"]

    # the window: an open loop's requests DUE in it, a closed loop's
    # replies RECEIVED in it
    at = log["due"] if mode == "open" else log["done"]
    inside = (at >= 0) & (at < ctx.seconds)
    ok = log["ok"][inside]
    timeout_ms = 1e3 * float(traffic["timeout_s"])
    latency_ms = 1e3 * (log["done"] - log["due"])[inside]
    latency_ms = np.where(ok, latency_ms, np.maximum(latency_ms, timeout_ms))
    late_ms = 1e3 * (log["sent"] - log["due"])[inside]

    checked, wrong, worst = deployment.check(gathered["kept"])
    answered_at = log["done"][inside & log["ok"]]
    notes.update(
        # replies received in each whole second of the window: shows a stall
        answered_per_s=np.bincount(answered_at.astype(int), minlength=int(ctx.seconds)).tolist(),
        checked=checked,
        wrong=wrong,
        worst_score_error=worst,
        connects=gathered["connects"],
        generator_errors=gathered["errors"][:8],
        ramp_requests=int((~inside).sum()),
    )
    failed = int((~ok).sum())
    run = harness.Run(
        setup_seconds=t0 - ctx.process_start,
        window_s=ctx.seconds,
        attempted=int(inside.sum()),
        failed=failed,
        correct=failed == 0 and wrong == 0 and checked >= engine.CHECKED_QUERIES // 2,
        series={"latency_ms": latency_ms, "late_ms": late_ms},
        counts={
            "answered": int(ok.sum()),
            # sent in time for the window's end and not answered by it
            "unanswered_at_end": int(((log["due"] < ctx.seconds) & (log["done"] > ctx.seconds)).sum()),
            "sent_by_end": int((log["due"] < ctx.seconds).sum()),
        },
        counters_start=counters_start,
        counters_end=counters_end,
        shapes=deployment.shapes(),
        notes=notes,
    )
    if tracer is not None:
        namer = in_flight_namer(log, tracer.start_monotonic - t0)
        run.trace = harness.reduce_slice(ctx, tracer, namer)
    if mode == "open" and len(latency_ms):
        p50, late99 = np.percentile(latency_ms, 50), np.percentile(late_ms, 99)
        if late99 > p50 / 5:
            print(
                f"benchmark: the generator starved: it sent {late99:.2f} ms late at its "
                f"99th percentile, over a fifth of the median latency {p50:.2f} ms",
                file=sys.stderr,
            )
    return run
