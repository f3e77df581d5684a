"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric (BASELINE.md): ALS recommendation train wall-clock at
MovieLens-20M scale plus serving latency/qps of the deployed top-k predict.
The reference publishes no numbers (BASELINE.json ``published: {}``), so
``vs_baseline`` is reported against the north-star serving target of
10 ms p50 (value < 1.0 means better than target).

Phase-isolated architecture: every phase (als, serving, twotower,
secondary, ...) runs in its OWN subprocess, one at a time, so exactly one
process holds the chip at any moment:
  - a device fault kills only that phase's process, never the harness
    (the parent imports no jax at all);
  - each phase checkpoints partial results to its output file as it goes,
    so a crash after the timed region still records the timing;
  - a failed phase is retried once in a fresh process (fresh TPU client),
    then recorded as ``<phase>_error`` in the final line;
  - the final line is ALWAYS printed; exit code is 0 iff at least one
    phase shipped numbers AND every quality gate that ran passed (the
    ``*_gate_ok`` booleans — a healthy-looking wall-clock over junk
    factors must not return success).

Serving is reported three ways, all printed:
  - ``serving_e2e_*``: concurrent HTTP POSTs from separate load-generator
    processes through the real ``QueryServer`` (micro-batch dispatcher,
    batched device kernels) — the number a user of ``pio deploy``
    experiences under load, and what ``vs_baseline`` uses.
  - ``serving_device_p50_ms``: per-query time of the compiled serve kernel
    alone (slope method, fixed dispatch overhead cancels).
  - ``serving_seq_*``: one blocking request at a time — what a *serial*
    client pays per call.
The host has ``bench_host_cores`` CPU cores; server and load generators
share them.

A device phase needs the chip: without one it fails, and the run's exit
code is non-zero. ``--cpu-only`` skips the device phases (CI smokes).

Scale selection: full ML-20M shape on TPU; a reduced ML-100K shape for the
CPU-pinned phases or when PIO_BENCH_SCALE=ml100k.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile
import time

# factor handoff als-phase -> serving-phase; unique per orchestrator run
# (a fixed name would let two concurrent bench runs clobber each other),
# inherited by the phase subprocesses through the environment
FACTORS_PATH = os.environ.setdefault(
    "PIO_BENCH_FACTORS",
    os.path.join(tempfile.gettempdir(), f"pio_bench_factors_{os.getpid()}.npz"),
)

# (phase, timeout_s) — order matters: serving reuses the als phase's factors
PHASES: list[tuple[str, int]] = [
    ("als", 900),
    ("serving", 900),
    ("serving_local", 600),
    # offline mega-batch inference over the same factors (CPU backend,
    # like serving_local): must land AFTER serving_local so the orchestrator
    # can gate offline qps >= 5x the online qps measured in the same round
    ("batchpredict", 600),
    ("twotower", 900),
    ("ann", 600),
    # the evaluation grid vs the sequential MetricEvaluator (CPU backend
    # like serving_local: the speedup compares two host-orchestrated
    # paths, so both sides must share a backend) — ISSUE 15 acceptance
    ("evalgrid", 600),
    ("secondary", 600),
    # diurnal/spike trace against a real self-sizing fleet (CPU workers;
    # never needs the device) — ISSUE 13 acceptance evidence
    ("elastic", 600),
    # device-free roofline (obs/costmodel): XLA cost_analysis flops/bytes
    # for every registered jit bucket family + the host sampler's
    # self-measured overhead — CPU backend, never needs the device
    ("roofline", 600),
    # session/next-item serving + bandit hot-path overhead (CPU backend,
    # never needs the device) — ISSUE 20 acceptance evidence
    ("sequential", 600),
]

# phases that need the accelerator (the others pin JAX_PLATFORMS=cpu
# themselves). Without a chip they fail; ``--cpu-only`` skips them.
_DEVICE_PHASES = {"als", "serving", "twotower", "ann", "secondary"}


# ---------------------------------------------------------------------------
# Shared helpers (phase-process side)
# ---------------------------------------------------------------------------


_NO_ACCELERATOR = "no accelerator: "  # first words of a device phase's refusal


def _jax_setup(device_phase: bool = False):
    """Import jax; returns (jax, platform). A device phase refuses the CPU:
    a number measured there must never be filed under a device field. The
    refusal is the process's last stderr line, which the orchestrator
    reads as the phase's error and turns into a non-zero exit code."""
    from predictionio_tpu.utils.platform import configure_jax

    configure_jax()
    import jax

    try:
        platform = jax.devices()[0].platform
    except RuntimeError as exc:  # JAX_PLATFORMS names a backend that is absent
        if device_phase:
            raise SystemExit(_NO_ACCELERATOR + str(exc).splitlines()[0]) from exc
        raise
    if device_phase and platform == "cpu":
        raise SystemExit(
            f"{_NO_ACCELERATOR}this phase measures the device and JAX found "
            f"platform 'cpu' (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            "--cpu-only skips the device phases"
        )
    return jax, platform


def _scale_params(platform: str):
    scale = os.environ.get(
        "PIO_BENCH_SCALE", "ml20m" if platform == "tpu" else "ml100k"
    )
    if scale == "ml20m":
        return scale, 138_000, 27_000, 20_000_000, 32, 10
    if scale == "ml1m":
        return scale, 6_040, 3_700, 1_000_000, 32, 10
    return scale, 943, 1_682, 100_000, 32, 10


def synthesize_ratings(n_users: int, n_items: int, n_ratings: int, seed: int = 0):
    """Synthetic low-rank + noise ratings with a realistic popularity skew,
    quantized to half-star steps like the actual MovieLens scales the bench
    names (real ML ratings are 0.5..5.0 in 0.5 increments — which also
    means the uint8 dictionary ratings wire engages exactly as it would on
    the real dataset). Quantization adds ~0.02 RMSE over the 0.3 noise
    floor; the 0.45 gate absorbs it."""
    import numpy as np

    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_users, n_ratings).astype(np.int32)
    # zipf-ish item popularity
    raw = rng.zipf(1.3, n_ratings).astype(np.int64) % n_items
    items = raw.astype(np.int32)
    k = 8
    U = rng.normal(size=(n_users, k)) / np.sqrt(k)
    V = rng.normal(size=(n_items, k)) / np.sqrt(k)
    vals = np.clip(
        np.sum(U[users] * V[items], axis=1) + 3.0 + 0.3 * rng.normal(size=n_ratings),
        1.0,
        5.0,
    ).astype(np.float32)
    vals = (np.round(vals * 2.0) / 2.0).astype(np.float32)
    return users, items, vals


class _Checkpoint:
    """Progressive result writer: every ``save`` rewrites the phase output
    file, so a device fault after the timed region still ships the timing."""

    def __init__(self, path: str):
        self.path = path
        self.data: dict = {}

    def save(self, **fields) -> None:
        self.data.update(fields)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.data, f)
        os.replace(tmp, self.path)


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _heldout_rmse(uf, vf, users, items, vals, mask) -> float:
    """RMSE of factor-model predictions on the held-out mask (host numpy);
    the quality pairing every latency/wall-clock headline ships with."""
    import numpy as np

    pred = np.sum(uf[users[mask]] * vf[items[mask]], axis=1)
    return float(np.sqrt(np.mean((pred - vals[mask]) ** 2)))


def _free_port() -> int:
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


# ---------------------------------------------------------------------------
# Phase: als — headline train wall-clock + held-out RMSE + FLOP/MFU accounting
# ---------------------------------------------------------------------------


def phase_als(ck: _Checkpoint) -> None:
    import numpy as np

    jax, platform = _jax_setup(device_phase=True)
    scale, n_users, n_items, n_ratings, rank, iterations = _scale_params(platform)
    from predictionio_tpu.ops.als import (
        ALSConfig,
        als_train,
        fetch_barrier,
        solver_hbm_bytes_per_iter,
    )

    users, items, vals = synthesize_ratings(n_users, n_items, n_ratings)
    # 2% held-out split: wall-clock numbers without a quality gate can be
    # silently gamed by under-iterating, so the bench *records and gates*
    # held-out RMSE on the factors it timed (VERDICT r1 weak #3)
    split_rng = np.random.default_rng(42)
    test_mask = split_rng.random(n_ratings) < 0.02
    users_tr, items_tr, vals_tr = (
        users[~test_mask],
        items[~test_mask],
        vals[~test_mask],
    )
    config = ALSConfig(rank=rank, iterations=iterations, reg=0.05, chunk=65536)
    ck.save(
        platform=platform,
        scale={
            "n_users": n_users,
            "n_items": n_items,
            "n_ratings": n_ratings,
            "rank": rank,
            "iterations": iterations,
        },
        scale_name=scale,
    )

    # The timed runs are INSTRUMENTED (ops/als.py ``timings``): the train
    # itself inserts barriers (post-upload, post-build, post-last-iteration),
    # so the decomposition sums to the wall clock it ships with, by
    # construction.
    # first run pays the XLA compile (shapes are full-size, so a small
    # warm-up would compile a different program and warm nothing)
    t_cold: dict = {}
    t0 = time.perf_counter()
    uf, vf = als_train(
        users_tr, items_tr, vals_tr, n_users, n_items, config, timings=t_cold
    )
    cold_wall = time.perf_counter() - t0
    ck.save(als_cold_wall_s=round(cold_wall, 3))

    t_warm: dict = {}
    t0 = time.perf_counter()
    uf, vf = als_train(
        users_tr, items_tr, vals_tr, n_users, n_items, config, timings=t_warm
    )
    instr_wall = time.perf_counter() - t0
    device_per_iter = t_warm["device_s"] / iterations

    # a separate PROFILED warm run (obs/xray) produces the train_step_*
    # evidence. Deliberately NOT merged with the timings run above: the
    # profiler adds a per-iteration device barrier + live-array walk
    # inside the window timings records as device_s, which would inflate
    # the long-gated als_device_s_per_iter against pre-profiler baselines
    # (and dilute the hbm_util roofline). One extra warm train buys
    # uncontaminated comparability; this run measures what a default
    # (PIO_XRAY=1) `pio train` actually pays.
    from predictionio_tpu.obs import xray

    train_prof = xray.TrainProfile("als-bench")
    with xray.use_profile(train_prof), train_prof.measure():
        als_train(users_tr, items_tr, vals_tr, n_users, n_items, config)
    prof_json = train_prof.finish().to_json_dict()
    ck.save(
        **{
            f"train_step_{name}_ms": round(stats["meanS"] * 1e3, 3)
            for name, stats in prof_json["phases"].items()
        },
        train_device_time_frac=prof_json["deviceTimeFrac"],
        train_peak_bytes_per_device=prof_json["memory"]["peakBytesPerDevice"],
    )

    # THE HEADLINE: a warm UNINSTRUMENTED run. The timings barriers above
    # serialize pack -> upload -> build -> solve to cut the decomposition,
    # but the plain path (what `pio train` runs) keeps dispatch fully
    # async, so H2D transfer overlaps the device-side table build. The
    # ending fetch_barrier makes it a completion wall, not a dispatch ack.
    t0 = time.perf_counter()
    uf, vf = als_train(users_tr, items_tr, vals_tr, n_users, n_items, config)
    fetch_barrier(uf, vf)
    train_wall = time.perf_counter() - t0
    ck.save(
        als_train_wall_s=round(train_wall, 3),
        # the barrier-instrumented wall the decomposition below was cut
        # from (>= headline: its stage barriers forbid the pipeline
        # overlap the plain path gets)
        als_instrumented_wall_s=round(instr_wall, 3),
        # warm-run decomposition: host group-by / H2D upload of the wire
        # arrays / device-side block-table build / solver iterations (each
        # phase barrier-confirmed)
        als_pack_s=round(t_warm["pack_s"], 3),
        als_upload_s=round(t_warm["upload_s"], 3),
        als_build_s=round(t_warm["build_s"], 3),
        als_device_s=round(t_warm["device_s"], 3),
        als_device_s_per_iter=round(device_per_iter, 3),
        # decomposition completeness: the phases vs the instrumented wall
        # they were cut from (should be ~1.0; <1 means untimed overhead)
        als_decomposition_coverage=round(
            (
                t_warm["pack_s"]
                + t_warm["upload_s"]
                + t_warm["build_s"]
                + t_warm["device_s"]
            )
            / instr_wall,
            3,
        ),
    )

    # analytic FLOP accounting (VERDICT r2 weak #5): per iteration, both
    # half-solves stream all nnz ratings — each contributes a rank-1 f x f
    # Gram update (2f^2 FLOPs: f^2 mults + f^2 adds) and a 2f b-update —
    # plus per-entity batched solve (~f^3/3 + 2f^2).
    f = rank
    nnz = int((~test_mask).sum())
    per_iter = 2 * nnz * (2 * f * f + 4 * f) + (n_users + n_items) * (
        f**3 / 3 + 2 * f * f
    )
    als_flops = per_iter * iterations
    # peak: TPU v5e ~197 TFLOP/s bf16 / ~98 fp32 (MXU); CPU runs get no MFU
    peak = 98e12 if platform == "tpu" else None
    device_mfu = als_flops / t_warm["device_s"] / peak if peak else None
    # HBM roofline (round-4 verdict task #3): the solver is gather-bound,
    # so the honest device-efficiency metric is bandwidth utilization, not
    # MFU. bytes/iter comes from the formulation's mandatory-traffic model
    # (ops/als.py solver_hbm_bytes_per_iter, block shapes recorded by the
    # instrumented train); v5e HBM peak = 819 GB/s. util > 1 = broken
    # probe (fail loudly, like the MFU gate); util << 0.5 = the gather
    # loop, not the memory system, is the bottleneck.
    if platform == "tpu" and "nb_u" in t_warm:
        hbm_bytes = solver_hbm_bytes_per_iter(
            t_warm["nb_u"], t_warm["nb_i"], t_warm["d"], rank,
            n_users, n_items,
            gather_dtype=config.gather_dtype, solver=config.solver,
            implicit=config.implicit,
        )
        hbm_util = hbm_bytes / device_per_iter / 819e9
        ck.save(
            als_hbm_bytes_per_iter=float(f"{hbm_bytes:.3e}"),
            als_hbm_util=round(hbm_util, 4),
            als_hbm_util_gate_ok=bool(0.0 < hbm_util <= 1.0),
        )

    ck.save(
        als_compile_s=round(max(0.0, cold_wall - train_wall), 1),
        als_flops=float(f"{als_flops:.3e}"),
        # wall-clock MFU includes host block-packing + H2D upload (what a
        # user's `pio train` pays); device MFU isolates the compute
        als_tflops_per_s=round(als_flops / train_wall / 1e12, 2),
        als_mfu=(round(als_flops / train_wall / peak, 4) if peak else None),
        als_device_mfu=round(device_mfu, 4) if device_mfu else None,
        # a device MFU outside (0, 1] means the probe is broken, not that
        # the chip is fast — fail loudly instead of publishing it again
        als_device_mfu_gate_ok=(
            bool(0.0 < device_mfu <= 1.0) if device_mfu is not None else True
        ),
    )

    # extra datapoints (not the headline), each with its own RMSE so a
    # quality cost would be visible. A failure here fails the phase: an
    # option that cannot run on the chip is a finding, not a footnote.
    if platform == "tpu":
        # the bf16-gather solver variant (ALSConfig.gather_dtype — halves
        # the gather-bound loop's row bytes)
        t_bf16: dict = {}
        cfg16 = ALSConfig(
            rank=rank, iterations=iterations, reg=0.05, chunk=65536,
            gather_dtype="bf16",
        )
        t0 = time.perf_counter()
        uf16, vf16 = als_train(
            users_tr, items_tr, vals_tr, n_users, n_items, cfg16,
            timings=t_bf16,
        )
        bf16_wall = time.perf_counter() - t0
        ck.save(
            # wall includes this variant's own compile (shapes differ
            # from the f32 program); device_s is the comparable number
            als_bf16_wall_s=round(bf16_wall, 3),
            als_bf16_device_s=round(t_bf16["device_s"], 3),
            als_bf16_heldout_rmse=round(
                _heldout_rmse(
                    np.asarray(uf16), np.asarray(vf16),
                    users, items, vals, test_mask,
                ),
                4,
            ),
        )

    # held-out quality gate (the wall-clock above is already checkpointed
    # if the readback faults)
    uf_host, vf_host = np.asarray(uf), np.asarray(vf)
    als_rmse = _heldout_rmse(uf_host, vf_host, users, items, vals, test_mask)
    # synthetic ratings = low-rank + N(0, 0.3) noise clipped to [1,5] then
    # half-star quantized like real MovieLens (r5); a healthy fit lands
    # near the combined noise floor (0.338 continuous at ML-20M in r3/r4;
    # 0.385 quantized at the CPU scale). The 0.45 gate still fails a real
    # regression (under-iteration, precision loss, packing bug) — r1's
    # broken run measured 0.52+ (VERDICT r3 weak #5)
    ck.save(
        als_heldout_rmse=round(als_rmse, 4),
        als_rmse_gate_ok=bool(als_rmse < 0.45),
    )
    # hand the factors to the serving phase (separate process)
    np.savez(FACTORS_PATH, uf=uf_host, vf=vf_host)


# ---------------------------------------------------------------------------
# Phase: serving — device kernel floor, sequential, batched, and e2e HTTP
# ---------------------------------------------------------------------------


def phase_serving(ck: _Checkpoint) -> None:
    import functools

    import numpy as np

    jax, platform = _jax_setup(device_phase=True)
    import jax.numpy as jnp
    from jax import lax

    _, n_users, n_items, _, rank, _ = _scale_params(platform)
    from predictionio_tpu.ops.als import ServingIndex

    # factors from the als phase when it survived; random otherwise (serving
    # latency is shape-dependent, not value-dependent)
    if os.path.exists(FACTORS_PATH):
        z = np.load(FACTORS_PATH)
        uf, vf = z["uf"], z["vf"]
        ck.save(serving_factors="als")
    else:
        rng0 = np.random.default_rng(0)
        uf = rng0.normal(size=(n_users, rank)).astype(np.float32)
        vf = rng0.normal(size=(n_items, rank)).astype(np.float32)
        ck.save(serving_factors="random_fallback")

    k = 10
    index = ServingIndex(uf, vf)
    index.warmup(k)
    rng = np.random.default_rng(1)

    # Device-side per-query latency: time a jitted scan of K back-to-back
    # serves at two different K and take the slope — the fixed dispatch
    # overhead cancels, so noise cannot clamp the result to a fake 0.
    def serve_many_fn(K):
        @functools.partial(jax.jit, static_argnames=("kk",))
        def serve_many(idxs, u, v, kk):
            def body(carry, uidx):
                s, i = lax.top_k(v @ u[uidx], kk)
                return carry + s[0], i[0]

            return lax.scan(body, 0.0, idxs)

        idxs = jnp.asarray(rng.integers(0, n_users, K).astype(np.int32))

        def run():
            # fetching the scalar carry closes the timed region
            carry, _ = serve_many(idxs, index.user_factors, index.item_factors, k)
            np.asarray(carry)

        run()
        return min(_timed(run) for _ in range(3))

    k_lo, k_hi = 64, 320
    t_lo, t_hi = serve_many_fn(k_lo), serve_many_fn(k_hi)
    slope_ms = (t_hi - t_lo) * 1000.0 / (k_hi - k_lo)
    # negative slope = measurement noise swamped the device work; fall back
    # to the conservative upper bound (total time / K) rather than claiming 0
    device_p50_ms = slope_ms if slope_ms > 0 else t_hi * 1000.0 / k_hi
    ck.save(serving_device_p50_ms=round(device_p50_ms, 4))

    # end-to-end blocking per-call latency + measured sequential throughput.
    # Kept for comparison with the concurrent server numbers below — this
    # is what a *serial* client experiences.
    latencies = []
    q_users = rng.integers(0, n_users, 30)
    t_all0 = time.perf_counter()
    for q in q_users:
        t0 = time.perf_counter()
        index.serve(int(q), k)
        latencies.append(time.perf_counter() - t0)
    seq_qps = len(q_users) / (time.perf_counter() - t_all0)
    seq_p50_ms = float(np.percentile(np.array(latencies) * 1000.0, 50))
    ck.save(
        serving_seq_p50_ms=round(seq_p50_ms, 3), serving_seq_qps=round(seq_qps, 1)
    )

    # micro-batched sustained throughput: dispatch every batch up front (an
    # async query server never blocks per batch), then fetch every result to
    # host — dispatches overlap the fetch stream, so this is what the
    # server actually sustains
    index.serve_batch(rng.integers(0, n_users, 64), k)  # warm [B]-shaped program
    n_batches = 20
    # distinct indices per batch, as distinct requests would bring
    didxs = [
        jnp.asarray(rng.integers(0, n_users, 64).astype(np.int32))
        for _ in range(n_batches)
    ]
    jax.block_until_ready(didxs)
    t0 = time.perf_counter()
    outs = [index.serve_batch_async(d, k) for d in didxs]
    results = [index.unpack_batch(np.asarray(o)) for o in outs]
    batch_qps = 64 * n_batches / (time.perf_counter() - t0)
    assert len(results) == n_batches
    ck.save(serving_batched_qps=round(batch_qps, 1))

    # THE e2e number: concurrent HTTP requests through the real QueryServer
    # (aiohttp + micro-batch dispatcher coalescing into batched device calls).
    # This is what a user of `pio deploy` experiences under load.
    server_stats = _bench_server_e2e(uf, vf, k)
    ck.save(
        **{
            kk: (vv if isinstance(vv, bool) else round(vv, 3))
            for kk, vv in server_stats.items()
        }
    )

    ec_p50, ec_reads = _bench_ecommerce_serving()
    ck.save(
        ecommerce_p50_ms=round(ec_p50, 3),
        # storage round trips per warm predict — the TTL cache target is 0
        ecommerce_storage_reads_per_predict=round(ec_reads, 4),
    )


def phase_serving_local(ck: _Checkpoint) -> None:
    """The QueryServer stack (aiohttp + micro-batch dispatcher + compiled
    top-k kernels) against the in-process CPU backend over loopback HTTP
    with real concurrent load-generator processes: the host-side
    framework overhead, with no accelerator in the picture. A CPU number;
    the benchmark PR (ROADMAP S1) moves this stack onto the chip."""
    # must happen before any jax import in this phase process
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    _jax_setup()
    _, n_users, n_items, n_ratings, rank, _ = _scale_params("cpu")
    if os.path.exists(FACTORS_PATH):
        z = np.load(FACTORS_PATH)
        uf, vf = z["uf"], z["vf"]
        ck.save(serving_local_factors="als")
    else:
        # the device ALS phase didn't run — train real factors
        # on the CPU backend at the CPU scale rather than serving random
        # ones: latency must always be paired with quality (r4 verdict
        # weak #2 — the r4 local p50 was measured over random factors)
        try:
            from predictionio_tpu.ops.als import ALSConfig, als_train

            users, items, vals = synthesize_ratings(n_users, n_items, n_ratings)
            split_rng = np.random.default_rng(42)
            test_mask = split_rng.random(n_ratings) < 0.02
            cfg = ALSConfig(rank=rank, iterations=5, reg=0.05, chunk=65536)
            uf_d, vf_d = als_train(
                users[~test_mask], items[~test_mask], vals[~test_mask],
                n_users, n_items, cfg,
            )
            uf, vf = np.asarray(uf_d), np.asarray(vf_d)
            ck.save(
                serving_local_factors="cpu_als",
                serving_local_heldout_rmse=round(
                    _heldout_rmse(uf, vf, users, items, vals, test_mask), 4
                ),
            )
        except Exception as exc:  # noqa: BLE001 - latency still worth shipping
            ck.save(
                serving_local_factors="random_fallback",
                serving_local_factors_error=str(exc)[:200],
            )
            rng0 = np.random.default_rng(0)
            uf = rng0.normal(size=(n_users, rank)).astype(np.float32)
            vf = rng0.normal(size=(n_items, rank)).astype(np.float32)
    stats = _bench_server_e2e(uf, vf, k=10)
    ck.save(
        **{
            kk.replace("serving_", "serving_local_"): (
                vv if isinstance(vv, bool) else round(vv, 3)
            )
            for kk, vv in stats.items()
        }
    )


def _bench_ecommerce_serving(
    n_users: int = 20_000, n_items: int = 10_000, n_queries: int = 30
) -> tuple[float, float]:
    """E-commerce predict path (BASELINE workload 4): device matvec + masked
    top-k + TTL-cached business-rule lookups (seen/unavailable items).
    Reports warm p50 and measured storage reads per warm predict."""
    import numpy as np

    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.models.ecommerce.engine import (
        ECommAlgorithm,
        ECommAlgorithmParams,
        ECommModel,
        Query,
    )
    from predictionio_tpu.workflow.context import WorkflowContext

    storage = Storage(
        env={
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        }
    )
    app_id = storage.get_meta_data_apps().insert(App(0, "ecombench"))
    levents = storage.get_l_events()
    rng = np.random.default_rng(3)
    levents.insert_batch(
        [
            Event(
                event="buy",
                entity_type="user",
                entity_id="u7",
                target_entity_type="item",
                target_entity_id=f"i{int(i)}",
            )
            for i in rng.integers(0, n_items, 20)
        ]
        + [
            Event(
                event="$set",
                entity_type="constraint",
                entity_id="unavailableItems",
                properties=DataMap({"items": [f"i{int(i)}" for i in rng.integers(0, n_items, 50)]}),
            )
        ],
        app_id,
    )
    model = ECommModel(
        rng.normal(size=(n_users, 16)).astype(np.float32),
        rng.normal(size=(n_items, 16)).astype(np.float32),
        rng.random(n_items).astype(np.float32),
        [f"u{i}" for i in range(n_users)],
        [f"i{i}" for i in range(n_items)],
        [None] * n_items,
    )
    # cache_ttl_s is the operator OPT-IN (default 0 = reference's always-live
    # reads); the bench measures the opted-in warm path, and the
    # storage_reads_per_predict metric proves it hits zero
    algo = ECommAlgorithm(
        ECommAlgorithmParams(app_name="ecombench", unseen_only=True, cache_ttl_s=5.0)
    )
    c = WorkflowContext(mode="serving", _storage=storage, app_name="ecombench")
    store = c.l_event_store()
    reads = {"n": 0}
    orig = store.find_by_entity

    def counted(*a, **kw):
        reads["n"] += 1
        return orig(*a, **kw)

    store.find_by_entity = counted
    c.l_event_store = lambda: store
    algo.predict_with_context(c, model, Query(user="u7", num=10))  # warm + compile
    reads["n"] = 0
    lat = []
    for _ in range(n_queries):
        t0 = time.perf_counter()
        algo.predict_with_context(c, model, Query(user="u7", num=10))
        lat.append(time.perf_counter() - t0)
    return (
        float(np.percentile(np.asarray(lat) * 1000.0, 50)),
        reads["n"] / n_queries,
    )


def _bench_server_e2e(
    uf,
    vf,
    k: int,
    latency_concurrency: int = 8,
    throughput_concurrency: int = 64,
    n_requests: int = 512,
) -> dict[str, float]:
    """Measure the deploy surface end-to-end: the real ``QueryServer``
    (aiohttp + micro-batch dispatcher) on localhost, hit with concurrent
    POST /queries.json from separate load-generator processes.

    Two passes against the same warm server: a moderate-concurrency pass
    for per-request latency (p50/p95 — at saturation the measured latency
    is queueing by Little's law, not service time, so a saturating pass
    cannot test a latency target), then a high-concurrency pass for
    sustained qps and the average device batch the dispatcher achieved."""
    import asyncio

    import numpy as np

    from predictionio_tpu.data.storage.memory import MemoryStorageClient  # noqa: F401
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.models.recommendation import engine_factory
    from predictionio_tpu.models.recommendation.engine import ALSModel
    from predictionio_tpu.workflow.create_server import QueryServer, ServerConfig
    from predictionio_tpu.workflow.engine_loader import EngineManifest

    n_users, n_items = uf.shape[0], vf.shape[0]
    model = ALSModel(
        np.asarray(uf),
        np.asarray(vf),
        [f"u{i}" for i in range(n_users)],
        [f"i{i}" for i in range(n_items)],
    )
    # (QueryServer.start() pre-compiles the pow2 batch buckets via the
    # algorithm's warmup_serving hook — same as a real deploy)
    engine = engine_factory()
    ep = engine.engine_params_from_variant(
        {
            "datasource": {"params": {"appName": "bench"}},
            "algorithms": [{"name": "als", "params": {}}],
        }
    )
    storage = Storage(
        env={
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        }
    )
    # the server gets its own event loop + real TCP socket in a background
    # thread; clients are real threads with persistent HTTP connections.
    # (sharing one asyncio loop between bench client and server caps the
    # measurement at the loop's own request-processing rate, not the
    # framework's)
    import http.client
    import threading

    port = _free_port()
    loop = asyncio.new_event_loop()
    server_box: dict = {}

    def serve() -> None:
        asyncio.set_event_loop(loop)

        async def boot():
            server = QueryServer(
                engine=engine,
                engine_params=ep,
                models=[model],
                manifest=EngineManifest(
                    engine_id="bench",
                    version="1",
                    variant="engine.json",
                    engine_factory="predictionio_tpu.models.recommendation.engine_factory",
                ),
                instance_id="bench",
                storage=storage,
                # result cache sized for the bench's zipf-free uniform user
                # draw: repeats within a pass hit; the dedicated hit pass
                # below measures the cached path in isolation
                config=ServerConfig(
                    ip="127.0.0.1",
                    port=port,
                    max_batch_size=32,
                    result_cache_size=4096,
                ),
            )
            await server.start()
            server_box["server"] = server

        loop.run_until_complete(boot())
        loop.run_forever()

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    for _ in range(200):  # wait for bind
        if "server" in server_box:
            break
        time.sleep(0.05)
    else:
        raise RuntimeError("bench query server failed to start")

    rng = np.random.default_rng(7)
    users = [f"u{int(u)}" for u in rng.integers(0, n_users, n_requests)]

    import socket as _socket

    def _post_one(conn, u: str) -> None:
        body = json.dumps({"user": u, "num": k})
        conn.request(
            "POST", "/queries.json", body, {"Content-Type": "application/json"}
        )
        resp = conn.getresponse()
        resp.read()
        if resp.status != 200:
            raise RuntimeError(f"serving bench request failed ({resp.status})")

    # warm the [B]-shaped programs the dispatcher will hit; the warm conn
    # also pins TCP_NODELAY on the query socket (the client half — aiohttp
    # applies it to every accepted server connection) and records it
    warm_conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    warm_conn.connect()
    warm_conn.sock.setsockopt(
        _socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1
    )
    tcp_nodelay = bool(
        warm_conn.sock.getsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY)
    )
    for u in users[:4]:
        _post_one(warm_conn, u)
    warm_conn.close()

    # cold-connection pass: a fresh TCP connection per request, so
    # transport wins (keep-alive) are attributed separately from kernel or
    # host-glue wins instead of conflated into one e2e number. Starts from
    # a flushed cache — a sampled-with-replacement duplicate answering
    # from the cache would under-price the full-dispatch cost this field
    # exists to attribute
    _cold_cache = server_box["server"]._result_cache
    if _cold_cache is not None:
        _cold_cache.clear()
    cold_lat = []
    for u in users[-32:]:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        t0 = time.perf_counter()
        _post_one(conn, u)
        cold_lat.append(time.perf_counter() - t0)
        conn.close()

    # load generators are separate *processes* (an in-process client would
    # share the GIL/event loop with the server and measure itself instead).
    # The client itself is deliberately thin — threaded raw-socket HTTP/1.1
    # over persistent keep-alive connections, ONE sendall and a minimal
    # recv-parse per request: an async-framework client costs multiple ms
    # of CPU and several syscalls per request on a small host, which
    # saturates the GENERATOR and reports its own queueing as server
    # latency. Blocking sockets release the GIL, so `conc` threads overlap.
    client_src = r"""
import json, socket, sys, threading, time

port, conc, k = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
users = sys.stdin.read().split()

lat, errors, conns = [], 0, 0
lock = threading.Lock()

REQ = (
    "POST /queries.json HTTP/1.1\r\nHost: 127.0.0.1\r\n"
    "Content-Type: application/json\r\nContent-Length: %d\r\n\r\n"
)


def _connect():
    s = socket.create_connection(("127.0.0.1", port), timeout=60)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def _one(sock, wire: bytes) -> int:
    sock.sendall(wire)  # headers+body in one syscall (and one packet)
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            raise OSError("connection closed")
        buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    status = int(head.split(None, 2)[1])
    clen = 0
    for line in head.split(b"\r\n")[1:]:
        name, _, value = line.partition(b":")
        if name.strip().lower() == b"content-length":
            clen = int(value)
            break
    while len(rest) < clen:
        chunk = sock.recv(65536)
        if not chunk:
            raise OSError("connection closed")
        rest += chunk
    return status


def worker(chunk):
    # one persistent connection per worker; a server-side close shows up
    # as a reconnect in `conns` (keep-alive regressions become visible)
    global errors, conns
    my_lat, my_errors, my_conns = [], 0, 1
    sock = _connect()
    try:
        for u in chunk:
            body = json.dumps({"user": u, "num": k}).encode()
            wire = (REQ % len(body)).encode() + body
            t0 = time.perf_counter()
            for attempt in (0, 1):
                try:
                    if _one(sock, wire) != 200:
                        my_errors += 1
                    break
                except OSError:
                    # stale keep-alive connection: reconnect once, retry
                    sock.close()
                    sock = _connect()
                    my_conns += 1
                    if attempt:
                        my_errors += 1
            my_lat.append(time.perf_counter() - t0)
    finally:
        sock.close()
    with lock:
        lat.extend(my_lat)
        errors += my_errors
        conns += my_conns


chunks = [users[i::conc] for i in range(conc)]
threads = [
    threading.Thread(target=worker, args=(ch,)) for ch in chunks if ch
]
t0 = time.perf_counter()
for t in threads:
    t.start()
for t in threads:
    t.join()
elapsed = time.perf_counter() - t0
print(json.dumps(
    {"elapsed": elapsed, "lat": lat, "errors": errors, "conns": conns}
))
"""
    def run_load(
        load_users: list[str], concurrency: int
    ) -> tuple[list[float], float, int]:
        n_procs = 2
        per_proc_conc = max(1, concurrency // n_procs)
        chunks = [load_users[i::n_procs] for i in range(n_procs)]
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", client_src, str(port), str(per_proc_conc), str(k)],
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                env={**os.environ, "JAX_PLATFORMS": ""},
            )
            for _ in range(n_procs)
        ]
        # feed every stdin first so all generators run concurrently; each
        # child times its own request stream (excluding interpreter startup)
        for p, chunk in zip(procs, chunks):
            p.stdin.write(" ".join(chunk).encode())
            p.stdin.close()
        outs = [p.stdout.read() for p in procs]
        for p in procs:
            p.wait(timeout=300)
        lat: list[float] = []
        n_errors = 0
        elapsed = 0.0
        conns = 0
        for out in outs:
            stats = json.loads(out)
            lat.extend(stats["lat"])
            n_errors += stats["errors"]
            elapsed = max(elapsed, stats["elapsed"])
            conns += stats.get("conns", 0)
        if n_errors:
            raise RuntimeError(f"serving bench saw {n_errors} non-200 responses")
        return lat, elapsed, conns

    # each timed pass gets an INDEPENDENT user sample and starts from a
    # flushed result cache: repeats *within* a pass hit (representative of
    # the sampled query distribution), but the latency pass must not
    # pre-populate the cache for the throughput pass — a cache-inflated
    # qps could hide a dispatch-path regression from the --compare gate
    _cache = server_box["server"]._result_cache
    if _cache is not None:
        _cache.clear()
    lat_pass, _, lat_conns = run_load(users[: n_requests // 2], latency_concurrency)
    # snapshot counters so avg_batch reflects the throughput pass only (the
    # latency pass batches at its concurrency, by design)
    _b2 = server_box["server"]._batcher
    warm_queries, warm_batches = _b2.queries_dispatched, _b2.batches_dispatched
    tput_users = [f"u{int(u)}" for u in rng.integers(0, n_users, n_requests)]
    if _cache is not None:
        _cache.clear()
    tput_pass, tput_elapsed, tput_conns = run_load(tput_users, throughput_concurrency)
    # keep-alive attribution: with connection reuse each generator holds at
    # most its concurrency in the pool; anything near one-conn-per-request
    # means the transport win is NOT being measured
    keepalive = bool(
        lat_conns <= 2 * latency_concurrency
        and tput_conns <= 2 * throughput_concurrency
    )

    # snapshot the cache counters NOW, while they reflect only the timed
    # load passes: the synthetic 64-hit pass below would inflate the
    # recorded hit ratio far past the sampled query mix's real one
    cache = server_box["server"]._result_cache
    cache_stats = cache.stats() if cache is not None else {}
    cache_lookups = cache_stats.get("hits", 0.0) + cache_stats.get("misses", 0.0)

    # cached-hit pass: one already-answered query repeated on a warm
    # keep-alive connection — the pure result-cache path (never enters the
    # micro-batch queue); sequential so each sample is one clean RTT
    hit_conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    hit_conn.connect()
    hit_conn.sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
    _post_one(hit_conn, users[0])  # prime the entry
    hit_lat = []
    for _ in range(64):
        t0 = time.perf_counter()
        _post_one(hit_conn, users[0])
        hit_lat.append(time.perf_counter() - t0)
    hit_conn.close()

    # fleet gateway hop (ISSUE 9): the SAME cached query through a
    # one-replica fleet Gateway on loopback — two hops where the direct
    # pass paid one. The p50 delta is the pure proxy overhead a fleet
    # deploy adds per request; --compare gates it (<1 ms contract,
    # serving_gateway_hop_p50_ms in the baseline fixture)
    gw_stats = _bench_gateway_hop(
        port, users[0], k, float(np.percentile(np.asarray(hit_lat) * 1e3, 50))
    )

    batcher = server_box["server"]._batcher
    # snapshot the server's own metrics registry before shutdown: the
    # BENCH_*.json perf trajectory carries the server-side latency
    # distribution (p50/p95/p99 as /metrics reports them) and the jit
    # recompile count, so a perf regression caused by a compile storm is
    # visible in the evidence itself, not just in wall-clock drift
    obs = _registry_serving_summary(server_box["server"])
    # graceful shutdown ON the server loop (stopping a loop with the
    # micro-batcher task still pending spews 'Event loop is closed' noise
    # at interpreter exit and can mask the phase's real exit status)
    stop_fut = asyncio.run_coroutine_threadsafe(server_box["server"].stop(), loop)
    try:
        stop_fut.result(timeout=10)
    except Exception:
        pass
    loop.call_soon_threadsafe(loop.stop)
    thread.join(timeout=10)
    lat_ms = np.asarray(lat_pass) * 1000.0
    cold_ms = np.asarray(cold_lat) * 1000.0
    hit_ms = np.asarray(hit_lat) * 1000.0
    return {
        "serving_e2e_p50_ms": float(np.percentile(lat_ms, 50)),
        "serving_e2e_p95_ms": float(np.percentile(lat_ms, 95)),
        "serving_e2e_qps": len(tput_pass) / tput_elapsed,
        "serving_avg_batch": (
            (batcher.queries_dispatched - warm_queries)
            / max(1, batcher.batches_dispatched - warm_batches)
        ),
        # transport attribution (ISSUE 8): keep-alive verified by counting
        # real TCP connects in the load generators; the cold-connection
        # pair is the per-request price of NOT reusing connections
        "serving_keepalive": keepalive,
        "serving_tcp_nodelay": tcp_nodelay,
        "serving_cold_conn_p50_ms": float(np.percentile(cold_ms, 50)),
        "serving_cold_conn_p95_ms": float(np.percentile(cold_ms, 95)),
        # version-keyed result cache: hit ratio over the whole run + the
        # e2e latency of the pure cached path (one repeated query)
        "serving_cache_hit_ratio": (
            float(cache_stats.get("hits", 0.0) / cache_lookups)
            if cache_lookups
            else 0.0
        ),
        "serving_cache_hit_p50_ms": float(np.percentile(hit_ms, 50)),
        **gw_stats,
        **obs,
    }


def _bench_gateway_hop(
    server_port: int, user: str, k: int, direct_p50_ms: float, n: int = 64
) -> dict:
    """Measure the fleet gateway's per-request overhead: a one-replica
    :class:`~predictionio_tpu.fleet.gateway.Gateway` in front of the
    already-running bench server, hit sequentially with the same cached
    query the direct pass timed. Records the replica count of the
    measured topology, the through-gateway p50, and the hop delta
    (clamped at 0 — scheduling jitter must not record a negative cost)."""
    import asyncio
    import http.client
    import socket as _socket
    import threading

    import numpy as np

    from predictionio_tpu.fleet.gateway import Gateway, GatewayConfig

    gw_port = _free_port()
    loop = asyncio.new_event_loop()
    box: dict = {}

    def serve() -> None:
        asyncio.set_event_loop(loop)

        async def boot():
            gw = Gateway(
                GatewayConfig(
                    ip="127.0.0.1",
                    port=gw_port,
                    replica_urls=(f"http://127.0.0.1:{server_port}",),
                    probe_interval_s=5.0,
                )
            )
            await gw.start()
            box["gw"] = gw

        loop.run_until_complete(boot())
        loop.run_forever()

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    started = False
    for _ in range(100):
        if "gw" in box:
            started = True
            break
        time.sleep(0.05)
    try:
        if not started:
            raise RuntimeError("gateway failed to start")
        conn = http.client.HTTPConnection("127.0.0.1", gw_port, timeout=60)
        conn.connect()
        conn.sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        body = json.dumps({"user": user, "num": k})

        def post_once() -> None:
            conn.request(
                "POST",
                "/queries.json",
                body,
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            resp.read()
            if resp.status != 200:
                raise RuntimeError(f"gateway bench request failed ({resp.status})")

        for _ in range(4):  # warm the gateway->replica keep-alive session
            post_once()
        lat = []
        for _ in range(n):
            t0 = time.perf_counter()
            post_once()
            lat.append(time.perf_counter() - t0)
        conn.close()
        gw_p50 = float(np.percentile(np.asarray(lat) * 1e3, 50))
        # pooled-upstream attribution: the warmed requests above ran
        # through the gateway's keep-alive TCPConnector — record that the
        # pool was live (per-host cap + keepalive window configured) so a
        # hop-p50 regression can be told apart from a pooling regression
        session = getattr(box["gw"], "_session", None)
        connector = getattr(session, "connector", None)
        pooled = float(
            connector is not None
            and getattr(connector, "limit_per_host", 0) > 0
            and getattr(connector, "keepalive_timeout", 0) > 0
        )
        return {
            "serving_fleet_replicas": 1.0,
            "serving_gateway_p50_ms": gw_p50,
            "serving_gateway_hop_p50_ms": max(0.0, gw_p50 - direct_p50_ms),
            "serving_gateway_pooled": pooled,
        }
    except Exception as exc:  # noqa: BLE001 - missing hop evidence, never fatal
        # no string fields in the stats dict: every non-bool value is
        # round()ed on save, so the failure is reported, not recorded
        print(f"[bench] gateway hop probe failed: {exc}", file=sys.stderr)
        return {}
    finally:
        gw = box.get("gw")
        if gw is not None:
            try:
                asyncio.run_coroutine_threadsafe(gw.stop(), loop).result(10)
            except Exception:
                pass
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)


def _registry_serving_summary(server) -> dict[str, float]:
    """Server-side observability snapshot for the bench evidence chain:
    request-latency percentiles from the obs registry histogram, the full
    per-phase waterfall (ingress parse .. respond — the attribution the
    transport-gap work lands against), and the serving-time jit recompile
    count (0 on a healthy pow2-bucketed run)."""
    try:
        summary = server._m_latency.summary(endpoint="/queries.json")
        server.compile_watcher.sample()  # fold in compiles since last scrape
        recompiles = server.compile_watcher.total_misses()
        out = {
            "serving_metrics_recompile_count": float(recompiles),
            "serving_metrics_count": float(summary.get("count", 0)),
        }
        for q in ("p50", "p95", "p99"):
            if q in summary:
                out[f"serving_metrics_{q}_ms"] = round(summary[q] * 1000.0, 3)
        # the phase waterfall: per-phase p50/p95/mean in ms, flat keys so
        # --compare diffs them field by field like any other percentile
        for phase, info in server.waterfall.snapshot().items():
            for stat in ("p50", "p95", "mean"):
                if stat in info:
                    out[f"serving_phase_{phase}_{stat}_ms"] = round(
                        info[stat] * 1000.0, 3
                    )
        return out
    except Exception as exc:  # noqa: BLE001 - obs must never sink the bench
        return {"serving_metrics_error": str(exc)}


# ---------------------------------------------------------------------------
# Phase: twotower — train-step throughput + retrieval quality gate
# ---------------------------------------------------------------------------


def phase_twotower(ck: _Checkpoint) -> None:
    _, platform = _jax_setup(device_phase=True)
    _, n_users, n_items, _, _, _ = _scale_params(platform)
    ck.save(twotower_examples_per_s=round(_bench_twotower(n_users, n_items), 1))
    # two-tower retrieval quality gate: recall@10 on held-out positives of a
    # clustered synthetic dataset (random baseline ~0.01; r3 measured 0.177
    # with the pre-fix loss, r4's corrected loss + 16 epochs measures 0.485
    # on the CPU backend — gate at 0.4 per the round-4 verdict (#7) so a
    # regression of the duplicate-collision masking / loss fixes fails the
    # bench rather than sliding back to the 0.177 era unnoticed)
    recall10, first_loss, last_loss = _bench_twotower_recall()
    ck.save(
        twotower_recall_at_10=round(recall10, 4),
        twotower_recall_gate_ok=bool(recall10 > 0.4),
        twotower_first_epoch_loss=round(first_loss, 4),
        twotower_last_epoch_loss=round(last_loss, 4),
        # training must actually optimize: final epoch loss below the first
        twotower_loss_gate_ok=bool(last_loss < first_loss),
    )
    if platform == "tpu":
        pallas_ms, ref_ms, err = _bench_attention()
        ck.save(
            attention_pallas_ms=round(pallas_ms, 3),
            attention_ref_ms=round(ref_ms, 3),
            attention_max_abs_err=float(f"{err:.2e}"),
            # both sides multiply in bf16 (kernel: explicit bf16 dots with
            # f32 accumulation; reference: TPU default f32->bf16 passes), so
            # the gate bounds |pallas - ref| by bf16 rounding at these shapes
            attention_gate_ok=bool(err < 2e-2),
            # the default path must be the faster one at the encoder's shape
            # (VERDICT r3 weak #4: a custom kernel slower than what it
            # replaces is negative value)
            attention_faster_gate_ok=bool(pallas_ms < ref_ms),
        )
        # long-sequence point: where the dense reference's [L, L] score
        # materialization falls over and the flash tiling pays off
        pallas4k, ref4k, _ = _bench_attention(L=4096)
        ck.save(
            attention_pallas_l4k_ms=round(pallas4k, 3),
            attention_ref_l4k_ms=round(ref4k, 3),
        )
        # the ENCODER's real head shape (H=2 heads of 32, from embed_dim 64
        # — not the generic 8x64 sweep shape): round-4 verdict task #6
        enc_p, enc_r, enc_err = _bench_attention(B=8, H=2, L=2048, D=32)
        ck.save(
            attention_encshape_pallas_ms=round(enc_p, 3),
            attention_encshape_ref_ms=round(enc_r, 3),
            attention_encshape_max_abs_err=float(f"{enc_err:.2e}"),
        )
        # full history-encoder forward, plain vs sharded-with-sp=1 (a 1x1
        # device mesh): bounds the sharded code path's dispatch overhead on
        # hardware without needing more chips (round-4 verdict task #6)
        try:
            fwd_ms = _bench_encoder_forward(sp=False)
            sp1_ms = _bench_encoder_forward(sp=True)
            ck.save(
                encoder_fwd_ms=round(fwd_ms, 3),
                encoder_sp1_fwd_ms=round(sp1_ms, 3),
                encoder_sp1_overhead=round(sp1_ms / fwd_ms, 3)
                if fwd_ms > 0
                else None,
            )
        except Exception as exc:  # noqa: BLE001 - extra datapoint only
            ck.save(encoder_bench_error=str(exc)[:200])


def _bench_attention(B: int = 4, H: int = 8, L: int = 2048, D: int = 64):
    """Pallas fused attention vs the jnp reference on TPU: wall-clock of the
    two-tower history encoder's kernel (ops/attention.py) and their max
    absolute output difference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.ops.attention import attention_reference, fused_attention

    from jax import lax

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, H, L, D)).astype(np.float32)) for _ in range(3)
    )
    pallas_fn = jax.jit(lambda q, k, v: fused_attention(q, k, v, causal=True))
    ref_fn = jax.jit(lambda q, k, v: attention_reference(q, k, v, causal=True))
    out_p = np.asarray(pallas_fn(q, k, v))  # compile + warm
    out_r = np.asarray(ref_fn(q, k, v))
    err = float(np.max(np.abs(out_p - out_r)))

    def chained(fn, n):
        # n sequential applications chained through q: one dispatch + one
        # fetch regardless of n, so the per-iteration slope cancels the
        # fixed dispatch and fetch cost
        @jax.jit
        def run(q, k, v):
            def body(c, _):
                return fn(c, k, v), ()

            out, _ = lax.scan(body, q, None, length=n)
            return out

        return run

    def timed(fn):
        # wide spread (2 vs 34 iterations) so the slope dwarfs per-fetch
        # jitter; min-of-8 against noise spikes
        lo, hi = chained(fn, 2), chained(fn, 34)
        for f in (lo, hi):
            np.asarray(f(q, k, v)[0, 0, :1])  # compile + warm
        t_lo = min(
            _timed(lambda: np.asarray(lo(q, k, v)[0, 0, :1])) for _ in range(8)
        )
        t_hi = min(
            _timed(lambda: np.asarray(hi(q, k, v)[0, 0, :1])) for _ in range(8)
        )
        return max(t_hi - t_lo, 1e-9) / 32 * 1000.0

    return timed(pallas_fn), timed(ref_fn), err


def _bench_encoder_forward(
    sp: bool, B: int = 256, T: int = 256, vocab: int = 27_000
) -> float:
    """Per-forward latency of the two-tower history encoder (embed +
    causal attention + masked mean-pool) at a production-ish shape.

    ``sp=True`` runs the IDENTICAL encoder with a 1x1 ``(data, model)``
    mesh attached — the sequence-parallel code path (shard_map + ring
    collectives degenerating to P=1) on a single chip, so the difference
    vs ``sp=False`` is pure sharded-path dispatch/compile overhead: the
    number that bounds what sp>1 costs beyond its collectives.

    Slope-timed like ``_bench_attention`` (chained scan, 2 vs 10
    applications, input perturbed per step so XLA cannot hoist the call
    out of the loop)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh

    from predictionio_tpu.models.twotower.model import SeqEncoder

    mesh = (
        Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        if sp
        else None
    )
    enc = SeqEncoder(
        vocab=vocab, embed_dim=64, n_heads=2, max_len=T, sp_mesh=mesh
    )
    rng = np.random.default_rng(0)
    hist = jnp.asarray(rng.integers(0, vocab, (B, T)).astype(np.int32))
    params = enc.init(jax.random.PRNGKey(0), hist)

    def chained(n):
        @jax.jit
        def run(hist):
            def body(c, i):
                out = enc.apply(params, (hist + i) % vocab)
                return c + out.sum(), ()

            tot, _ = lax.scan(body, jnp.float32(0), jnp.arange(n))
            return tot

        return run

    lo, hi = chained(2), chained(10)
    for f in (lo, hi):
        np.asarray(f(hist))  # compile + warm
    t_lo = min(_timed(lambda: np.asarray(lo(hist))) for _ in range(5))
    t_hi = min(_timed(lambda: np.asarray(hi(hist))) for _ in range(5))
    return max(t_hi - t_lo, 1e-9) / 8 * 1000.0


def _bench_twotower(n_users: int, n_items: int, batch: int = 8192, steps: int = 20) -> float:
    """Two-tower retrieval train-step throughput (BASELINE workload 5).
    Pipelined dispatch: steps chain via donated params, one block at end."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from predictionio_tpu.models.twotower.model import (
        TwoTower,
        TwoTowerConfig,
        make_train_step,
    )

    config = TwoTowerConfig(
        n_users=n_users, n_items=n_items, embed_dim=64, hidden=(128,), out_dim=32
    )
    model = TwoTower(config)
    rng = jax.random.PRNGKey(0)
    users0 = jnp.zeros((batch,), jnp.int32)
    params = model.init(rng, users0, users0)["params"]
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)
    step = jax.jit(
        make_train_step(model, tx, config.temperature), donate_argnums=(0, 1)
    )
    np_rng = np.random.default_rng(0)
    ub = [
        jnp.asarray(np_rng.integers(0, n_users, batch).astype(np.int32))
        for _ in range(steps)
    ]
    ib = [
        jnp.asarray(np_rng.integers(0, n_items, batch).astype(np.int32))
        for _ in range(steps)
    ]
    params, opt_state, loss = step(params, opt_state, ub[0], ib[0])  # compile
    np.asarray(loss)  # true completion barrier (see als phase note)
    t0 = time.perf_counter()
    for s in range(steps):
        params, opt_state, loss = step(params, opt_state, ub[s], ib[s])
    np.asarray(loss)
    return batch * steps / (time.perf_counter() - t0)


def _bench_twotower_recall(
    n_users: int = 2000,
    n_items: int = 1000,
    n_clusters: int = 20,
    pos_per_user: int = 30,
    seed: int = 0,
) -> tuple[float, float, float]:
    """Two-tower retrieval quality: train on clustered synthetic positives
    (90% of a user's interactions land in the user's cluster), hold out one
    positive per user, report recall@10 over the full item catalog. A
    random ranker scores ~10/n_items = 0.01; a model that learns the
    cluster structure scores an order of magnitude higher."""
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.models.twotower.model import (
        TwoTower,
        TwoTowerConfig,
        train_two_tower,
        user_embedding,
    )

    rng = np.random.default_rng(seed)
    user_cluster = rng.integers(0, n_clusters, n_users)
    item_cluster = rng.integers(0, n_clusters, n_items)
    items_by_cluster = [np.flatnonzero(item_cluster == c) for c in range(n_clusters)]
    all_items = np.arange(n_items)
    train_u, train_i, test_u, test_i = [], [], [], []
    for u in range(n_users):
        own = items_by_cluster[user_cluster[u]]
        if len(own) < 2:
            continue
        # sample WITHOUT replacement so the held-out item (pos[0]) cannot
        # leak into the training pairs — otherwise the gate would partly
        # measure memorization instead of generalization
        n_in = min(int(round(pos_per_user * 0.9)), len(own))
        in_cluster = rng.choice(own, n_in, replace=False)
        tail = rng.choice(all_items, pos_per_user - n_in, replace=False)
        pos = np.concatenate([in_cluster, tail[tail != in_cluster[0]]])
        # hold out an *in-cluster* positive (pos[0]): the model can only
        # retrieve it by learning the cluster structure, whereas the random
        # 10% tail is unpredictable by construction
        train_u.extend([u] * (len(pos) - 1))
        train_i.extend(pos[1:])
        test_u.append(u)
        test_i.append(pos[0])
    config = TwoTowerConfig(
        n_users=n_users,
        n_items=n_items,
        embed_dim=32,
        hidden=(64,),
        out_dim=16,
        batch_size=1024,
        # with the corrected in-batch loss (duplicate-collision masking +
        # log-Q debiasing) the model keeps improving well past 8 epochs:
        # 16 measured 0.485 recall@10 vs 0.19 at 8
        epochs=16,
        seed=seed,
    )
    res = train_two_tower(
        np.asarray(train_u, np.int32), np.asarray(train_i, np.int32), config
    )
    model = TwoTower(config)
    u_emb = np.asarray(
        user_embedding(model, res.params, jnp.asarray(np.asarray(test_u, np.int32)))
    )
    scores = u_emb @ res.item_embeddings.T  # [n_test, n_items]
    # standard leave-one-out protocol: mask each user's *train* positives so
    # memorized items don't crowd the held-out one out of the top-10
    train_by_user: dict[int, list[int]] = {}
    for u, i in zip(train_u, train_i):
        train_by_user.setdefault(u, []).append(i)
    for row, u in enumerate(test_u):
        seen = [i for i in train_by_user.get(u, ()) if i != test_i[row]]
        scores[row, seen] = -np.inf
    top10 = np.argpartition(-scores, 10, axis=1)[:, :10]
    hits = sum(1 for row, ti in zip(top10, test_i) if ti in row)
    return hits / len(test_i), res.losses[0], res.losses[-1]


# ---------------------------------------------------------------------------
# Phase: ann — clustered MIPS retrieval vs exact at >=100k items
# ---------------------------------------------------------------------------


def phase_ann(ck: _Checkpoint) -> None:
    """The million-item-retrieval evidence (ISSUE 10 / ROADMAP item 4b):
    on a >=100k-item clustered synthetic corpus, measure (1) recall@10 of
    the IVF index vs exact brute force, (2) the real candidate fraction
    scored per query (must stay <=10% of the corpus), and (3) the
    device+fetch p50 of the ANN path vs the exact path at the SAME corpus
    size — the acceptance is a measured crossover, not a claim. Queries
    are drawn from the corpus distribution (user embeddings live near the
    item clusters they were trained against), batch 64, pow2-bucketed
    like the serving dispatch. ``PIO_ANN_BENCH_ITEMS`` scales the corpus
    (CI smoke uses a smaller one)."""
    jax, platform = _jax_setup(device_phase=True)
    import numpy as np

    from predictionio_tpu.ann import AnnConfig, build_index
    from predictionio_tpu.ann.search import AnnSearcher
    from predictionio_tpu.ops import topk

    n = int(os.environ.get("PIO_ANN_BENCH_ITEMS", "100000"))
    f = 32
    modes_n = max(32, n // 512)
    rng = np.random.default_rng(7)
    modes = rng.normal(size=(modes_n, f))
    modes /= np.linalg.norm(modes, axis=1, keepdims=True)
    vecs = (
        modes[rng.integers(0, modes_n, n)]
        + 0.15 * rng.normal(size=(n, f))
    ).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    ck.save(serving_ann_corpus_items=n, ann_platform=platform)

    t0 = time.perf_counter()
    index = build_index(vecs, AnnConfig(min_items=0), model_version="bench")
    ck.save(
        serving_ann_build_s=round(time.perf_counter() - t0, 3),
        serving_ann_clusters=index.clusters,
        serving_ann_bucket_cap=index.bucket_cap,
        serving_ann_nprobe=index.nprobe,
        serving_ann_hbm_bytes=index.hbm_bytes(),
    )
    searcher = AnnSearcher(index)

    import jax.numpy as jnp

    table = jnp.asarray(vecs)
    B, k, batches = 64, 10, 40
    kk = topk.next_pow2(k)
    queries = (
        modes[rng.integers(0, modes_n, (batches, B))]
        + 0.15 * rng.normal(size=(batches, B, f))
    ).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=2, keepdims=True)

    # warm both paths, then time per-batch dispatch->fetch round trips
    topk.fetch_topk(topk.dot_top_k_async(table, queries[0].copy(), None, kk))
    AnnSearcher.fetch(searcher.search_async(queries[0].copy(), kk))

    exact_ms, ann_ms = [], []
    exact_idx_all, ann_idx_all, counts_all = [], [], []
    for i in range(batches):
        t = time.perf_counter()
        _, eidx = topk.fetch_topk(
            topk.dot_top_k_async(table, queries[i].copy(), None, kk)
        )
        exact_ms.append((time.perf_counter() - t) * 1e3)
        exact_idx_all.append(eidx)
    for i in range(batches):
        t = time.perf_counter()
        _, aidx, counts = AnnSearcher.fetch(
            searcher.search_async(queries[i].copy(), kk)
        )
        ann_ms.append((time.perf_counter() - t) * 1e3)
        ann_idx_all.append(aidx)
        counts_all.append(counts)
    hits = sum(
        len(set(a[r, :k]) & set(e[r, :k]))
        for a, e in zip(ann_idx_all, exact_idx_all)
        for r in range(B)
    )
    recall = hits / float(batches * B * k)
    cand_frac = float(np.concatenate(counts_all).mean()) / n
    ck.save(
        serving_ann_recall_at_10=round(recall, 4),
        serving_ann_candidates_frac=round(cand_frac, 4),
        serving_ann_p50_ms=round(float(np.percentile(ann_ms, 50)), 3),
        serving_ann_p95_ms=round(float(np.percentile(ann_ms, 95)), 3),
        serving_ann_exact_p50_ms=round(float(np.percentile(exact_ms, 50)), 3),
        # the measured crossover the acceptance asks for: ANN device+fetch
        # p50 at or below exact at the same corpus size
        serving_ann_speedup=round(
            float(np.percentile(exact_ms, 50))
            / max(1e-9, float(np.percentile(ann_ms, 50))),
            3,
        ),
    )


# ---------------------------------------------------------------------------
# Phase: batchpredict — offline mega-batch throughput (ISSUE 14)
# ---------------------------------------------------------------------------


def phase_batchpredict(ck: _Checkpoint) -> None:
    """Device-saturating offline inference: the `pio batchpredict`
    mega-batch pipeline (streaming source -> double-buffered fused-kernel
    dispatch -> atomic file writeback) over the same factors the serving
    phases use. Records offline qps / users-per-s, the per-phase p50s of
    the read->assemble->dispatch->fetch->write timeline, and the tiling
    ratio (phases must cover the run wall clock within 10% — the same
    evidence contract as the serving waterfall and the train profiler).
    Runs on the CPU backend like serving_local: the number the acceptance
    gate compares against is the same-host online serving qps, so both
    sides must share a backend."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    _jax_setup()
    _, n_users, n_items, n_ratings, rank, _ = _scale_params("cpu")
    if os.path.exists(FACTORS_PATH):
        z = np.load(FACTORS_PATH)
        uf, vf = z["uf"], z["vf"]
        ck.save(batchpredict_factors="als")
    else:
        # same provenance rule as serving_local: throughput pairs with
        # real factors when obtainable, labeled random fallback otherwise
        try:
            from predictionio_tpu.ops.als import ALSConfig, als_train

            users, items, vals = synthesize_ratings(n_users, n_items, n_ratings)
            cfg = ALSConfig(rank=rank, iterations=3, reg=0.05, chunk=65536)
            uf_d, vf_d = als_train(users, items, vals, n_users, n_items, cfg)
            uf, vf = np.asarray(uf_d), np.asarray(vf_d)
            ck.save(batchpredict_factors="cpu_als")
        except Exception as exc:  # noqa: BLE001 - throughput still worth shipping
            ck.save(
                batchpredict_factors="random_fallback",
                batchpredict_factors_error=str(exc)[:200],
            )
            rng0 = np.random.default_rng(0)
            uf = rng0.normal(size=(n_users, rank)).astype(np.float32)
            vf = rng0.normal(size=(n_items, rank)).astype(np.float32)

    from predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
        Serving,
    )
    from predictionio_tpu.models.recommendation import engine_factory
    from predictionio_tpu.workflow.batch_predict import (
        BatchPredictInstruments,
        FileSink,
        StatusFile,
        run_pipeline,
    )

    engine = engine_factory()
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=uf.shape[1]))
    batch = int(os.environ.get("PIO_BENCH_BP_BATCH", "512"))
    n_queries = int(os.environ.get("PIO_BENCH_BP_QUERIES", "20000"))
    # the true nightly shape is ONE query per DISTINCT user (what
    # --from-events produces); tile the user factor table up to the query
    # count so users_per_s measures real distinct-user throughput instead
    # of cycling a small vocab
    if uf.shape[0] < n_queries:
        reps = -(-n_queries // uf.shape[0])
        uf = np.tile(np.asarray(uf, np.float32), (reps, 1))[:n_queries]
    model = ALSModel(
        np.asarray(uf, np.float32),
        np.asarray(vf, np.float32),
        [f"u{i}" for i in range(uf.shape[0])],
        [f"i{i}" for i in range(vf.shape[0])],
    )
    components = (None, None, [algo], Serving())

    def source():
        for i in range(n_queries):
            yield i + 1, {"user": f"u{i}", "num": 10}

    out_path = os.path.join(
        tempfile.gettempdir(), f"pio_bench_bp_{os.getpid()}.jsonl"
    )
    status_path = os.path.join(
        tempfile.gettempdir(), f"pio_bench_bp_{os.getpid()}.status.json"
    )
    status = StatusFile(status_path)
    status.update(force=True, engineId="recommendation", source="synthetic")
    report = run_pipeline(
        engine,
        components,
        [model],
        source(),
        [FileSink(out_path)],
        batch_size=batch,
        instruments=BatchPredictInstruments(),
        status=status,
    )
    with open(out_path) as fh:
        written = sum(1 for _ in fh)
    os.unlink(out_path)
    assert written == n_queries, (written, n_queries)
    tiling_ok = bool(0.9 <= report.tiling_ratio <= 1.001)
    ck.save(
        batchpredict_offline_qps=round(report.qps, 1),
        # one query = one user's nightly precompute; engines fanning
        # several queries per user would make these diverge
        batchpredict_offline_users_per_s=round(report.users_per_s, 1),
        batchpredict_queries=report.queries,
        batchpredict_errors=report.errors,
        batchpredict_batch=batch,
        batchpredict_wall_s=report.wall_s,
        batchpredict_warmup_s=report.warmup_s,
        batchpredict_tiling_ratio=report.tiling_ratio,
        batchpredict_tiling_gate_ok=tiling_ok,
        batchpredict_status_file=status_path,
        **{
            f"batchpredict_phase_{name}_p50_ms": v
            for name, v in report.phase_p50_ms.items()
        },
    )


# ---------------------------------------------------------------------------
# Phase: evalgrid — the evaluation grid vs the sequential MetricEvaluator
# ---------------------------------------------------------------------------

# Module-level DASE pieces: spawn-mode grid workers rebuild the evaluation
# by unpickling these from bench.py's __main__, and the synthetic data is
# a pure function of the params — every worker derives identical folds
# with nothing shipped but a few integers.


def _evalgrid_sizes() -> tuple[int, int, int, int]:
    return (
        int(os.environ.get("PIO_BENCH_EG_USERS", "24000")),
        int(os.environ.get("PIO_BENCH_EG_ITEMS", "400")),
        int(os.environ.get("PIO_BENCH_EG_RATINGS", "96000")),
        int(os.environ.get("PIO_BENCH_EG_FOLDS", "2")),
    )


class _EvalGridDataSource:
    """Synthetic-ratings data source with recommendation-template k-fold
    read_eval (fold membership by rating index modulo k). Duck-typed
    against BaseDataSource with lazy imports so plain
    `python bench.py --compare` never pays the jax import."""

    def __init__(self, params=None):
        self.params = params
        n_users, n_items, n_ratings, self.k = _evalgrid_sizes()
        u, i, r = synthesize_ratings(n_users, n_items, n_ratings, seed=7)
        self._u, self._i, self._r = u, i, r
        self._user_vocab = [f"u{x}" for x in range(n_users)]
        self._item_vocab = [f"i{x}" for x in range(n_items)]

    def read_training(self, ctx):
        from predictionio_tpu.models.recommendation.engine import TrainingData

        return TrainingData(
            self._u, self._i, self._r, self._user_vocab, self._item_vocab
        )

    def read_eval(self, ctx):
        import numpy as np

        from predictionio_tpu.models.recommendation.engine import (
            ActualResult,
            Query,
            Rating,
            TrainingData,
        )

        idx = np.arange(len(self._u))
        folds = []
        for fold in range(self.k):
            test = idx % self.k == fold
            td = TrainingData(
                self._u[~test],
                self._i[~test],
                self._r[~test],
                self._user_vocab,
                self._item_vocab,
            )
            qa = []
            tu, ti = self._u[test], self._i[test]
            order = np.argsort(tu, kind="stable")
            bounds = np.flatnonzero(
                np.diff(tu[order], prepend=-1)
            ).tolist() + [len(order)]
            for s, e in zip(bounds[:-1], bounds[1:]):
                rows = order[s:e]
                user = self._user_vocab[int(tu[rows[0]])]
                ratings = tuple(
                    Rating(user, self._item_vocab[int(x)], 1.0)
                    for x in ti[rows]
                )
                qa.append((Query(user, 10), ActualResult(ratings)))
            folds.append((td, {"fold": fold}, qa))
        return folds


def _evalgrid_evaluation():
    """2 ranks x 4 regularizations over the synthetic corpus — the grid
    the phase searches AND the sequential baseline scores."""
    from predictionio_tpu.controller import Engine, EngineParams
    from predictionio_tpu.eval import Evaluation
    from predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        Preparator,
        Query,
        Serving,
    )
    from predictionio_tpu.tuning.metrics import PrecisionAtK

    params_list = [
        EngineParams(
            data_source=("", None),
            preparator=("", None),
            algorithms=[
                (
                    "als",
                    ALSAlgorithmParams(
                        rank=rank, num_iterations=2, lambda_=lam, seed=3
                    ),
                )
            ],
            serving=("", None),
        )
        for rank in (4, 8)
        for lam in (0.02, 0.05, 0.2, 0.5)
    ]
    return Evaluation(
        engine=Engine(
            _EvalGridDataSource,
            Preparator,
            {"als": ALSAlgorithm},
            Serving,
            query_class=Query,
        ),
        metric=PrecisionAtK(10),
        engine_params_generator=params_list,
    )


def phase_evalgrid(ck: _Checkpoint) -> None:
    """The evaluation grid (ISSUE 15, docs/evaluation.md): the SAME
    fold×params search run two ways on the CPU backend —

    1. the seed-parity sequential ``MetricEvaluator`` (one EngineParams at
       a time through ``Engine.eval``: re-read/re-prepare per params, one
       per-query device round-trip per held-out query), and
    2. the grid runner (parallel workers, FastEval prefix caching, scoring
       through ``Engine.dispatch_batch`` mega-batches into the fused
       kernels, durable ledger)

    and records cells/hour, the measured speedup (the acceptance target is
    >= 2x on the 4-worker CPU sandbox; on a 1-core box the win is the
    batched scoring + prefix caching, on real hardware the workers stack
    on top), and the winner's score — all ``--compare``-gated."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    _jax_setup()
    import tempfile as _tempfile
    import time as _time

    from predictionio_tpu.eval import MetricEvaluator
    from predictionio_tpu.tuning import run_grid
    from predictionio_tpu.workflow.context import WorkflowContext

    n_users, n_items, n_ratings, k = _evalgrid_sizes()
    ev = _evalgrid_evaluation()
    params_list = list(ev.params_list())
    ctx = WorkflowContext(mode="evaluation")

    # --- sequential baseline: the path PR 15 replaces ----------------------
    t0 = _time.perf_counter()
    seq = MetricEvaluator(ev.metric).evaluate_base(ctx, ev.engine, params_list)
    seq_s = _time.perf_counter() - t0

    # --- the grid ----------------------------------------------------------
    workers = int(
        os.environ.get(
            "PIO_BENCH_EVALGRID_WORKERS", str(min(4, os.cpu_count() or 1))
        )
    )
    workdir = _tempfile.mkdtemp(prefix="pio_bench_evalgrid_")
    status_path = os.path.join(workdir, "status.json")
    t0 = _time.perf_counter()
    report = run_grid(
        _evalgrid_evaluation,
        workdir=workdir,
        workers=workers,
        status_path=status_path,
        env={
            "JAX_PLATFORMS": "cpu",
            **{
                key: os.environ[key]
                for key in os.environ
                if key.startswith("PIO_BENCH_EG_")
            },
        },
    )
    grid_s = _time.perf_counter() - t0

    # both paths must agree on the winner — the speedup is only evidence
    # if the answer is the same answer. Exact equality holds here because
    # precision@k counts every ratable query and this corpus makes every
    # held-out query ratable: the grid's query-weighted fold mean IS the
    # pooled metric (see tuning.runner.params_score_of for when it isn't)
    assert report.best_params_index == seq.best_index, (
        report.best_params_index,
        seq.best_index,
    )
    assert abs(report.best_score - seq.best_score) < 1e-6, (
        report.best_score,
        seq.best_score,
    )
    speedup = seq_s / grid_s if grid_s > 0 else 0.0
    ck.save(
        evalgrid_params=len(params_list),
        evalgrid_folds=report.folds,
        evalgrid_cells=report.cells_total,
        evalgrid_workers=workers,
        evalgrid_corpus=f"{n_users}x{n_items}x{n_ratings}",
        evalgrid_queries=sum(s["queries"] for s in report.scores),
        evalgrid_wall_s=round(grid_s, 3),
        evalgrid_seq_wall_s=round(seq_s, 3),
        evalgrid_cells_per_hour=report.cells_per_hour,
        evalgrid_speedup_x=round(speedup, 2),
        # acceptance rail (ISSUE 15): >= 2x the sequential MetricEvaluator
        evalgrid_speedup_gate_ok=bool(speedup >= 2.0),
        evalgrid_winner_score=round(report.best_score, 6),
        evalgrid_winner_params_index=report.best_params_index,
    )


# ---------------------------------------------------------------------------
# Phase: secondary — remaining BASELINE workloads, one measurement each
# ---------------------------------------------------------------------------


def phase_secondary(ck: _Checkpoint) -> None:
    _jax_setup(device_phase=True)
    ck.save(naive_bayes_train_ms=round(_bench_naive_bayes(), 2))
    cooccur_ms = _bench_cooccurrence()
    ck.save(
        cooccurrence_build_ms=round(cooccur_ms, 1),
        # the ML-1M similar-product build target (round-4 verdict #8); the
        # native kernel runs it ~150ms on the dev host vs 945ms host-side
        # in r3
        cooccurrence_build_gate_ok=bool(cooccur_ms < 300.0),
    )
    cold, warm = _bench_snapshot_ingest()
    ck.save(
        snapshot_ingest_cold_s=round(cold, 3),
        snapshot_ingest_warm_s=round(warm, 3),
        # the point of the snapshot cache: a second train's ingest reads
        # columnar shards, not the row store (target: warm < 10% of cold)
        snapshot_ingest_ratio=round(warm / cold, 4) if cold else None,
    )
    eps, p50 = _bench_event_ingest()
    ck.save(
        # ingestion surface (the reference's other hot path): batched POSTs
        # of 50 events/request (the contract cap) through the real aiohttp
        # event server over loopback, auth + validation + storage included
        event_ingest_eps=round(eps, 1),
        event_ingest_batch_p50_ms=round(p50, 3),
    )


def _bench_event_ingest(
    n_batches: int = 40, batch_size: int = 50
) -> tuple[float, float]:
    """Event-server ingest throughput: real HTTP batch POSTs (50/request,
    the reference's hard cap, EventServer.scala:70) against the in-memory
    store over loopback. Returns (events/s, per-batch p50 ms)."""
    import asyncio
    import http.client
    import threading

    import numpy as np

    from predictionio_tpu.data.api.event_server import (
        EventServer,
        EventServerConfig,
    )
    from predictionio_tpu.data.storage.base import AccessKey, App
    from predictionio_tpu.data.storage.registry import Storage

    storage = Storage(
        env={
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        }
    )
    app_id = storage.get_meta_data_apps().insert(App(0, "ingestbench"))
    storage.get_meta_data_access_keys().insert(AccessKey("ingestkey", app_id, ()))

    port = _free_port()
    loop = asyncio.new_event_loop()
    ready = threading.Event()

    server_box: dict = {}

    def serve() -> None:
        asyncio.set_event_loop(loop)
        server = EventServer(
            storage=storage, config=EventServerConfig(ip="127.0.0.1", port=port)
        )
        loop.run_until_complete(server.start())
        server_box["server"] = server
        ready.set()
        loop.run_forever()

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    if not ready.wait(timeout=30):
        raise RuntimeError("event server failed to start for the ingest bench")

    rng = np.random.default_rng(9)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    path = "/batch/events.json?accessKey=ingestkey"

    def post_batch() -> None:
        body = json.dumps(
            [
                {
                    "event": "rate",
                    "entityType": "user",
                    "entityId": f"u{int(u)}",
                    "targetEntityType": "item",
                    "targetEntityId": f"i{int(i)}",
                    "properties": {"rating": float(i % 5 + 1)},
                }
                for u, i in zip(
                    rng.integers(0, 5000, batch_size),
                    rng.integers(0, 2000, batch_size),
                )
            ]
        )
        conn.request("POST", path, body, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"ingest bench batch failed: {resp.status} {payload[:200]}")

    post_batch()  # warm (routes, json codecs, first insert)
    lat = []
    t0 = time.perf_counter()
    for _ in range(n_batches):
        t1 = time.perf_counter()
        post_batch()
        lat.append(time.perf_counter() - t1)
    elapsed = time.perf_counter() - t0
    conn.close()
    # graceful aiohttp runner cleanup ON its loop, then stop it (a bare
    # loop.stop leaves the keep-alive handler task pending and noisy)
    stop_fut = asyncio.run_coroutine_threadsafe(server_box["server"].stop(), loop)
    try:
        stop_fut.result(timeout=10)
    except Exception:
        pass
    loop.call_soon_threadsafe(loop.stop)
    thread.join(timeout=10)
    return (
        n_batches * batch_size / elapsed,
        float(np.percentile(np.asarray(lat) * 1000.0, 50)),
    )


def _bench_snapshot_ingest(n_events: int = 200_000) -> tuple[float, float]:
    """Train-path ingest through the sharded snapshot cache: cold = full
    row-store scan + dictionary encode + shard write; warm = shard read.
    This is what every template DataSource pays at the top of `pio train`."""
    import shutil
    import tempfile as _tf

    import numpy as np

    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.storage.base import AccessKey, App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.data.store.event_store import PEventStore

    root = _tf.mkdtemp(prefix="pio_bench_snapshot_")
    try:
        storage = Storage(
            env={
                "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
                "PIO_STORAGE_SOURCES_SQL_PATH": os.path.join(root, "ev.db"),
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
            }
        )
        app_id = storage.get_meta_data_apps().insert(App(0, "snapbench"))
        storage.get_meta_data_access_keys().insert(AccessKey("k", app_id, ()))
        rng = np.random.default_rng(0)
        users = rng.integers(0, 5000, n_events)
        items = rng.integers(0, 2000, n_events)
        p = storage.get_p_events()
        p.write(
            (
                Event(
                    event="rate",
                    entity_type="user",
                    entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{i}",
                    properties=DataMap({"rating": float(u % 5 + 1)}),
                )
                for u, i in zip(users, items)
            ),
            app_id,
        )
        store = PEventStore(storage)
        snap = os.path.join(root, "snapshots")
        kwargs = dict(
            app_name="snapbench",
            snapshot_dir=snap,
            event_names=["rate"],
            entity_type="user",
            target_entity_type="item",
            rating_key="rating",
        )
        t0 = time.perf_counter()
        cold_cols = store.to_columnar_cached(**kwargs)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm_cols = store.to_columnar_cached(**kwargs)
        warm = time.perf_counter() - t0
        assert len(warm_cols) == len(cold_cols) == n_events
        return cold, warm
    finally:
        shutil.rmtree(root, ignore_errors=True)


def phase_elastic(ck: _Checkpoint) -> None:
    """SLO-driven elasticity under a synthetic diurnal/spike load trace
    (ISSUE 13): a REAL fleet — worker processes under the supervisor,
    gateway in front, telemetry ring + autoscaler attached — driven
    through steady -> spike -> decay. The autoscaler must track the
    trace (scale out during the spike, drain back in during the decay)
    with ZERO client-visible 5xx and bounded over-provisioning.

    Recorded evidence (``--compare`` gates the starred fields):
      fleet_trace_p95_ms*      p95 across the whole trace (spike included)
      fleet_peak_replicas*     most replicas the fleet grew to (bounded
                               over-provisioning: more is worse)
      fleet_shed_total         gateway sheds + worker load sheds (target 0)
      fleet_trace_5xx          client-visible 5xx count (target 0)
      fleet_steady_replicas    replicas after the decay (the scale-in proof)
      fleet_scale_outs/ins     decisions applied, from the telemetry ring
    """
    os.environ["JAX_PLATFORMS"] = "cpu"  # fleet parent: no device needed
    import asyncio

    result = asyncio.run(_elastic_trace())
    ck.save(**result)


async def _elastic_trace() -> dict:
    import asyncio
    import tempfile as _tempfile

    import aiohttp
    import numpy as np

    from predictionio_tpu.fleet.autoscaler import (
        Autoscaler,
        AutoscalerConfig,
        ScalingPolicy,
    )
    from predictionio_tpu.fleet.gateway import Gateway, GatewayConfig
    from predictionio_tpu.fleet.launch import build_obs_plane
    from predictionio_tpu.fleet.supervisor import (
        Supervisor,
        SupervisorConfig,
        WorkerSpec,
    )
    from predictionio_tpu.fleet.worklog import spawn_with_log
    from predictionio_tpu.obs.metrics import MetricsRegistry

    worker_script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts", "fleet_smoke.py"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    ports = [_free_port() for _ in range(8)]
    next_slot = [1]

    def spec_factory(worker_class: str) -> WorkerSpec:
        i = next_slot[0]
        next_slot[0] += 1
        return WorkerSpec(
            name=f"w{i}", port=ports[i], worker_class=worker_class
        )

    obs_dir = _tempfile.mkdtemp(prefix="pio_bench_elastic_obs_")
    metrics = MetricsRegistry()
    obs = build_obs_plane(obs_dir, metrics)

    def spawn(spec: WorkerSpec):
        return spawn_with_log(
            [sys.executable, worker_script, "--worker", str(spec.port)],
            obs["logbook"],
            spec.name,
            env=env,
        )

    sup = Supervisor(
        spawn,
        [WorkerSpec(name="w0", port=ports[0])],
        SupervisorConfig(poll_interval_s=0.1, term_grace_s=10.0),
        metrics=metrics,
        logbook=obs["logbook"],
        on_crash=obs["on_crash"],
    )
    gw = Gateway(
        GatewayConfig(
            ip="127.0.0.1",
            port=_free_port(),
            replica_urls=(WorkerSpec("w0", ports[0]).url,),
            probe_interval_s=0.2,
            probe_timeout_s=2.0,
            request_timeout_s=15.0,
            telemetry_interval_s=0.25,
            # short burn windows so post-spike burn decays inside the
            # trace (the SRE 300s default would pin the idle detector)
            slo_windows=((10.0, 10.0), (30.0, 5.0)),
        ),
        metrics=metrics,
        telemetry=obs["telemetry"],
        incidents=obs["incidents"],
    )
    auto = Autoscaler(
        ScalingPolicy(
            AutoscalerConfig(
                min_replicas=1,
                max_replicas=3,
                tick_interval_s=0.5,
                lookback_s=120.0,
                burn_threshold=1.0,
                queue_depth_high=2.0,
                inflight_high_per_replica=6.0,
                confirm_s=2.0,
                idle_sustain_s=6.0,
                queue_depth_low=1.0,
                idle_inflight_per_replica=2.0,
                idle_burn_max=0.5,
                scale_out_cooldown_s=6.0,
                scale_in_cooldown_s=8.0,
            )
        ),
        sup,
        gw,
        spec_factory,
        ring=obs["telemetry"],
        metrics=metrics,
        incidents=obs["incidents"],
    )
    statuses: list[int] = []
    lat_s: list[float] = []
    replica_timeline: list[int] = []
    sup.start()
    sup_task = asyncio.ensure_future(sup.run())
    auto_task = asyncio.ensure_future(auto.run())
    await gw.start()
    gw_url = f"http://127.0.0.1:{gw.config.port}"
    session = aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=20))

    async def one_query(i: int) -> None:
        t0 = time.perf_counter()
        try:
            async with session.post(
                f"{gw_url}/queries.json",
                json={"user": f"u{i % 500}", "num": 5},
            ) as resp:
                await resp.read()
                statuses.append(resp.status)
        except Exception:
            statuses.append(599)  # transport failure = client-visible 5xx
        lat_s.append(time.perf_counter() - t0)

    async def load(duration_s: float, concurrency: int, rps: float | None):
        """Closed-loop when rps is None; paced open-ish loop otherwise."""
        stop_at = time.monotonic() + duration_s
        i = [0]

        async def worker_loop():
            while time.monotonic() < stop_at:
                i[0] += 1
                await one_query(i[0])
                if rps is not None:
                    await asyncio.sleep(concurrency / rps)
                replica_timeline.append(len(sup.live_specs()))

        await asyncio.gather(*(worker_loop() for _ in range(concurrency)))

    try:
        # worker 0 up (pays the jax import once)
        deadline = time.monotonic() + 120.0
        while True:
            try:
                async with session.get(f"{gw_url}/healthz") as resp:
                    if (await resp.json()).get("replicasHealthy", 0) >= 1:
                        break
            except Exception:
                pass
            if time.monotonic() > deadline:
                raise RuntimeError("elastic bench: worker never became ready")
            await asyncio.sleep(0.25)
        trace_t0 = time.perf_counter()
        await load(6.0, 2, rps=10.0)  # steady morning
        await load(30.0, 24, rps=None)  # spike: closed-loop flood
        await load(30.0, 1, rps=4.0)  # decay back to idle
        trace_s = time.perf_counter() - trace_t0
        # let the last drain finish before reading the final shape
        deadline = time.monotonic() + 30.0
        while len(sup.snapshot()) > len(sup.live_specs()):
            if time.monotonic() > deadline:
                break
            await asyncio.sleep(0.25)
        fivexx = sum(1 for s in statuses if s >= 500)
        ring = obs["telemetry"]
        # sheds = gateway no-replica 503s PLUS the workers' own
        # admission-control sheds (federated pio_load_shed_total) — the
        # last fleet snapshot already carries both summed
        fleet_recs = [r for r in ring.records() if r.get("kind") == "fleet"]
        sheds = metrics.get("pio_fleet_no_replica_total").total()
        if fleet_recs:
            counters = fleet_recs[-1].get("counters") or {}
            sheds = float(counters.get("no_replica", sheds)) + float(
                counters.get("load_shed", 0.0)
            )
        scaling = [
            r for r in ring.records() if r.get("kind") == "scaling"
        ]
        outs = sum(
            1 for r in scaling if r["decision"]["action"] == "scale-out"
        )
        ins = sum(
            1 for r in scaling if r["decision"]["action"] == "scale-in"
        )
        lat_ms = np.asarray(lat_s) * 1000.0
        return {
            "fleet_trace_requests": len(statuses),
            "fleet_trace_s": round(trace_s, 1),
            "fleet_trace_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "fleet_trace_p95_ms": round(float(np.percentile(lat_ms, 95)), 3),
            "fleet_trace_5xx": fivexx,
            "fleet_shed_total": float(sheds),
            "fleet_zero_5xx": bool(fivexx == 0 and sheds == 0),
            "fleet_peak_replicas": max(replica_timeline) if replica_timeline else 1,
            "fleet_steady_replicas": len(sup.live_specs()),
            "fleet_scale_outs": outs,
            "fleet_scale_ins": ins,
        }
    finally:
        for task in (auto_task, sup_task):
            task.cancel()
        await asyncio.gather(auto_task, sup_task, return_exceptions=True)
        await session.close()
        await gw.stop()
        await asyncio.get_running_loop().run_in_executor(None, sup.stop)
        obs["telemetry"].close()


def _bench_naive_bayes(n: int = 200_000, f: int = 64, classes: int = 8) -> float:
    """Classification template training wall-clock (BASELINE workload 1)."""
    import numpy as np

    from predictionio_tpu.ops.classify import train_naive_bayes

    rng = np.random.default_rng(0)
    labels = rng.integers(0, classes, n).astype(np.float64)
    feats = rng.poisson(2.0, size=(n, f)).astype(np.float64)
    t0 = time.perf_counter()
    train_naive_bayes(labels, feats, 1.0)
    return (time.perf_counter() - t0) * 1000.0


def _bench_cooccurrence(n_users: int = 6040, n_items: int = 3700, nnz: int = 1_000_000) -> float:
    """Similar-product cooccurrence build at ML-1M scale (BASELINE workload 3).

    Min-of-3 with a warm native library: the build is a pure host+native
    measurement (r5 moved the pair counting into ``pio_cooccur_topn``) and
    single-shot timings on the 1-core bench host carry multi-hundred-ms
    scheduler noise."""
    import numpy as np

    from predictionio_tpu.ops.cooccurrence import cooccurrence_top_n

    rng = np.random.default_rng(0)
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = (rng.zipf(1.3, nnz) % n_items).astype(np.int32)
    cooccurrence_top_n(u[:1000], i[:1000], n_items, 20)  # build/load the lib
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        cooccurrence_top_n(u, i, n_items, 20)
        best = min(best, (time.perf_counter() - t0) * 1000.0)
    return best


# ---------------------------------------------------------------------------
# Perf-regression gate: --compare (ROADMAP item 5 — the trajectory is gated,
# not asserted: every later scaling PR lands with its perf delta recorded)
# ---------------------------------------------------------------------------

# fields where smaller is better (latencies, wall-clocks); "value" is the
# headline train wall-clock after main() pops als_train_wall_s into it
_COMPARE_LOWER_IS_BETTER = frozenset(
    {
        "value",
        "serving_e2e_p50_ms",
        "serving_e2e_p95_ms",
        "serving_local_e2e_p50_ms",
        "serving_local_e2e_p95_ms",
        "serving_metrics_p50_ms",
        "serving_metrics_p95_ms",
        "serving_metrics_p99_ms",
        "serving_local_metrics_p50_ms",
        "serving_local_metrics_p95_ms",
        "serving_local_metrics_p99_ms",
        "serving_device_p50_ms",
        "serving_seq_p50_ms",
        # fleet gateway proxy overhead (ISSUE 9): regression-gated against
        # the checked-in baseline (the sandbox HTTP floor is ~2 ms, so the
        # paper's <1 ms production hop target is held as no-worse-than-
        # baseline here, not as an absolute bound)
        "serving_gateway_hop_p50_ms",
        "serving_local_gateway_hop_p50_ms",
        "als_device_s_per_iter",
        "ecommerce_p50_ms",
        "naive_bayes_train_ms",
        "cooccurrence_build_ms",
        "event_ingest_batch_p50_ms",
        # the measured training memory peak gates like a latency — a
        # quietly-fatter train is a regression too (obs/xray profiler)
        "train_peak_bytes_per_device",
        # the ANN path's device+fetch p50 and candidate fraction (ISSUE
        # 10): candidate generation creeping back toward O(corpus) — more
        # candidates scored per query — is a regression even when the
        # wall clock hides it on fast hardware
        "serving_ann_p50_ms",
        "serving_ann_candidates_frac",
        # elasticity trace (ISSUE 13): the fleet must keep tracking the
        # spike within latency (p95 over the WHOLE trace, spike included),
        # without shedding or erroring, and without over-provisioning
        # (peak replicas growing across rounds = the policy got greedier)
        "fleet_trace_p95_ms",
        "fleet_trace_5xx",
        "fleet_shed_total",
        "fleet_peak_replicas",
        # the profiling plane (ISSUE 18): the analytic device cost per 1k
        # queries must not silently grow, and the always-on host sampler
        # must stay inside its <1% budget
        "roofline_topk_cost_per_1k_usd",
        "roofline_ann_cost_per_1k_usd",
        "roofline_als_cost_per_1k_usd",
        "roofline_twotower_cost_per_1k_usd",
        "sampler_overhead_frac",
        # session/next-item engine + bandit hot-path cost (ISSUE 20): the
        # attention scorer silently degrading to host scoring, or bandit
        # impression accounting growing a lock hotspot, must trip the gate
        "serving_sequential_p50_ms",
        "serving_sequential_p95_ms",
        "bandit_pick_overhead_ms",
    }
)
# the per-phase waterfall percentiles ride the same gate, whatever phases
# the run exported; train_step_{phase}_ms are the training waterfall's
# twins (obs/xray step profiler)
_COMPARE_LOWER_RE = re.compile(
    r"^(serving(_local)?_phase_[a-z_]+_(p50|p95|mean)_ms"
    r"|train_step_[a-z_]+_ms"
    # the offline pipeline's read->assemble->dispatch->fetch->write p50s
    # (ISSUE 14): a host-side regression in any phase is a throughput
    # regression even before it shows in the headline qps
    r"|batchpredict_phase_[a-z_]+_p50_ms)$"
)
_COMPARE_HIGHER_IS_BETTER = frozenset(
    {
        "serving_e2e_qps",
        "serving_local_e2e_qps",
        "serving_batched_qps",
        "serving_seq_qps",
        "twotower_examples_per_s",
        "event_ingest_eps",
        # measured ANN quality: recall@10 vs exact must not silently decay
        "serving_ann_recall_at_10",
        # offline mega-batch throughput (ISSUE 14): the whole point of the
        # dedicated offline path — its qps regressing means the nightly
        # precompute window silently grows
        "batchpredict_offline_qps",
        "batchpredict_offline_users_per_s",
        # the evaluation grid (ISSUE 15): search throughput (cells/hour),
        # the measured advantage over the sequential MetricEvaluator, and
        # the winner's score — a quality decay in the searched optimum is
        # a regression even when the wall clock improves
        "evalgrid_cells_per_hour",
        "evalgrid_speedup_x",
        "evalgrid_winner_score",
        # arithmetic intensity per bucket family (obs/costmodel): a drop
        # means the kernel does less compute per byte moved — it got more
        # memory-bound, the wrong direction on any accelerator
        "roofline_topk_ai",
        "roofline_ann_ai",
        "roofline_als_ai",
        "roofline_twotower_ai",
    }
)


def _compare_direction(field: str) -> int:
    """+1 = higher is worse (latency), -1 = lower is worse (throughput),
    0 = not a gated field."""
    if field in _COMPARE_LOWER_IS_BETTER or _COMPARE_LOWER_RE.match(field):
        return 1
    if field in _COMPARE_HIGHER_IS_BETTER:
        return -1
    return 0


def compare_bench(
    current: dict,
    priors: list[dict],
    tolerance: float = 0.25,
    min_abs_ms: float = 0.5,
) -> dict:
    """Diff the gated percentile/throughput fields of ``current`` against
    the BEST value any prior round achieved (min for latencies, max for
    throughputs). A field regresses when it is worse than best-prior by
    more than ``tolerance`` (relative) AND, for millisecond fields, by
    more than ``min_abs_ms`` absolute — sub-millisecond phases jitter by
    large ratios on shared CI hosts and must not trip the gate on noise.

    Returns the flat ``compare_*`` verdict fields recorded into the bench
    JSON; ``compare_ok`` is the gate."""
    regressions: list[dict] = []
    improvements = 0
    compared = 0
    for field, cur in sorted(current.items()):
        direction = _compare_direction(field)
        if direction == 0 or not isinstance(cur, (int, float)) or cur is None:
            continue
        prior_vals = [
            p[field]
            for p in priors
            if isinstance(p.get(field), (int, float))
        ]
        if not prior_vals:
            continue
        best = min(prior_vals) if direction > 0 else max(prior_vals)
        compared += 1
        if best <= 0:
            continue  # degenerate prior; a ratio against it is meaningless
        ratio = cur / best
        if direction > 0:
            regressed = ratio > 1.0 + tolerance and (
                not field.endswith("_ms") or (cur - best) > min_abs_ms
            )
            improved = ratio < 1.0
        else:
            regressed = ratio < 1.0 - tolerance
            improved = ratio > 1.0
        if regressed:
            regressions.append(
                {
                    "field": field,
                    "current": cur,
                    "best_prior": best,
                    "ratio": round(ratio, 4),
                }
            )
        elif improved:
            improvements += 1
    return {
        "compare_ok": not regressions,
        "compare_tolerance": tolerance,
        "compare_fields": compared,
        "compare_improvements": improvements,
        "compare_regressions": regressions,
    }


def _load_bench_json(path: str) -> dict:
    """A bench evidence file: either a bare JSON object or the last JSON
    line of a captured bench stdout."""
    with open(path) as fh:
        text = fh.read().strip()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        for line in reversed(text.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
        raise


def phase_sequential(ck: _Checkpoint) -> None:
    """The session/next-item engine + bandit overhead (ISSUE 20): train
    the sequential engine's attention scorer on synthetic sessions (CPU
    backend), serve next-item batches through ``Engine.dispatch_batch``
    into the shared ops/topk pack format, and measure

    - ``serving_sequential_p50_ms`` — per-dispatch next-item latency, and
    - ``bandit_pick_overhead_ms`` — the per-request cost the bandit adds
      to the hot path (sticky lane pick + impression accounting),

    both ``--compare``-gated: the attention path quietly falling back to
    host scoring, or bandit accounting growing a lock hotspot, is a
    regression even on fast hardware."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    _jax_setup()
    import numpy as np

    from predictionio_tpu.bandit import BanditLoop
    from predictionio_tpu.models.sequential import (
        Query,
        SequentialModel,
        engine_factory,
    )
    from predictionio_tpu.models.sequential.engine import (
        AttentionAlgorithmParams,
        TrainingData,
    )
    from predictionio_tpu.registry.router import RolloutPlan, choose_lane
    from predictionio_tpu.controller.engine import EngineParams

    n_items = int(os.environ.get("PIO_BENCH_SEQ_ITEMS", "2000"))
    n_users = int(os.environ.get("PIO_BENCH_SEQ_USERS", "1500"))
    sess_len = 12
    rng = np.random.default_rng(0)
    # markov-flavored synthetic sessions: each item strongly transitions
    # to (i + small hop), with noise — gives the scorers real structure
    sequences = []
    for _ in range(n_users):
        s = [int(rng.integers(n_items))]
        for _ in range(sess_len - 1):
            if rng.random() < 0.7:
                s.append((s[-1] + int(rng.integers(1, 4))) % n_items)
            else:
                s.append(int(rng.integers(n_items)))
        sequences.append(np.asarray(s, np.int32))
    vocab = [f"i{j}" for j in range(n_items)]
    td = TrainingData(
        users=[f"u{k}" for k in range(n_users)],
        sequences=sequences,
        item_vocab=vocab,
    )

    engine = engine_factory()
    ep = EngineParams(
        data_source=("", None),
        preparator=("", None),
        algorithms=[
            (
                "attention",
                AttentionAlgorithmParams(rank=32, num_iterations=3, context=8),
            )
        ],
        serving=("", None),
    )
    _, _, algorithms, serving = engine.make_components(ep)
    from predictionio_tpu.workflow.context import WorkflowContext

    ctx = WorkflowContext(mode="training")
    t0 = time.perf_counter()
    model: SequentialModel = algorithms[0].train(ctx, td)
    ck.save(
        sequential_train_wall_s=round(time.perf_counter() - t0, 3),
        sequential_items=n_items,
        sequential_sessions=n_users,
    )
    algorithms[0].warmup_serving(model, 8)
    batch = 8
    rounds = int(os.environ.get("PIO_BENCH_SEQ_ROUNDS", "60"))
    queries = [
        Query(
            user=f"u{k}",
            recent_items=tuple(
                vocab[int(j)] for j in sequences[k % n_users][-4:]
            ),
            num=10,
        )
        for k in range(batch)
    ]
    lat = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        fin = engine.dispatch_batch(algorithms, serving, [model], queries)
        results = fin()
        lat.append((time.perf_counter() - t0) * 1000.0 / batch)
        assert len(results) == batch and results[0].item_scores
    lat.sort()
    ck.save(
        serving_sequential_p50_ms=round(lat[len(lat) // 2], 4),
        serving_sequential_p95_ms=round(lat[int(len(lat) * 0.95)], 4),
        sequential_rounds=rounds,
        sequential_batch=batch,
    )

    # bandit pick overhead: the ONLY work the bandit adds per served
    # request — the sticky lane pick it shares with the plain canary plus
    # its own impression accounting (lock + bounded trace log + counter)
    loop = BanditLoop("thompson", seed=0)

    class _Tailer:  # poll is never driven here; begin() just needs a slot
        def poll(self, impressions):
            return [], 0

    loop.begin("v1", "v2", _Tailer())
    plan = RolloutPlan("canary", 0.5, "v2")
    picks = int(os.environ.get("PIO_BENCH_BANDIT_PICKS", "5000"))
    t0 = time.perf_counter()
    for k in range(picks):
        lane = choose_lane(plan, f"u{k}")
        loop.record_impression(
            f"tr-{k}", "candidate" if lane == "candidate" else "stable",
            "v2" if lane == "candidate" else "v1",
        )
    wall_ms = (time.perf_counter() - t0) * 1000.0
    ck.save(
        bandit_pick_overhead_ms=round(wall_ms / picks, 6),
        bandit_picks=picks,
    )


def phase_roofline(ck: _Checkpoint) -> None:
    """The analytic device anchor (ISSUE 18): lower+compile the registered
    jit bucket families on the CPU backend and record XLA's own
    ``cost_analysis()`` flops/bytes as ``roofline_*`` fields — per-family
    arithmetic intensity and the priced device cost per 1k queries — plus
    the always-on host sampler's self-measured overhead fraction under a
    planted busy thread. All numbers ride the ``--compare`` gate: AI
    decaying or cost-per-1k / sampler overhead growing is a regression
    even though no device ever ran."""
    # must happen before any jax import in this phase process
    os.environ["JAX_PLATFORMS"] = "cpu"
    _jax_setup()
    from predictionio_tpu.obs import costmodel

    fields = costmodel.bench_fields(
        ["topk", "ann", "als", "twotower"], device=costmodel.DEFAULT_DEVICE
    )
    ck.save(**{k: v for k, v in fields.items() if v is not None})

    # sampler overhead at the DEFAULT period against a real busy thread:
    # the <1% always-on claim, measured in the bench so --compare catches
    # the sampler itself getting more expensive
    import threading

    from predictionio_tpu.obs.sampler import HostSampler

    stop = threading.Event()

    def _busy() -> None:
        while not stop.is_set():
            sum(i * i for i in range(2000))

    worker = threading.Thread(target=_busy, name="pio-dispatch-bench", daemon=True)
    worker.start()
    sampler = HostSampler()
    sampler.start()
    try:
        time.sleep(3.0)
    finally:
        sampler.stop()
        stop.set()
        worker.join(timeout=2.0)
    ck.save(
        sampler_overhead_frac=round(sampler.overhead_frac(), 6),
        sampler_samples=int(sampler.snapshot()["samples"]),
    )


_PHASE_FNS = {
    "als": phase_als,
    "serving": phase_serving,
    "serving_local": phase_serving_local,
    "batchpredict": phase_batchpredict,
    "twotower": phase_twotower,
    "ann": phase_ann,
    "evalgrid": phase_evalgrid,
    "secondary": phase_secondary,
    "elastic": phase_elastic,
    "roofline": phase_roofline,
    "sequential": phase_sequential,
}


# ---------------------------------------------------------------------------
# Orchestrator (parent process — NO jax import anywhere on this path)
# ---------------------------------------------------------------------------


def _run_phase(
    name: str, timeout_s: int, retries: int = 1
) -> tuple[dict, str | None]:
    """Run one phase in a subprocess; returns (partial_results, error).
    Partial results survive crashes (the phase checkpoints its output file
    after every milestone); each attempt is a fresh process, and the chip
    is held by one phase process at a time."""
    last_err = None
    merged: dict = {}
    for attempt in range(retries + 1):
        out = os.path.join(
            tempfile.gettempdir(), f"pio_bench_{name}_{os.getpid()}_{attempt}.json"
        )
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--phase", name, "--out", out],
                capture_output=True,
                timeout=timeout_s,
            )
            rc = proc.returncode
            tail = proc.stderr.decode(errors="replace")[-600:]
        except subprocess.TimeoutExpired:
            rc, tail = -1, f"phase timed out after {timeout_s}s"
        partial = {}
        if os.path.exists(out):
            try:
                with open(out) as fh:
                    partial = json.load(fh)
            except (OSError, json.JSONDecodeError):
                pass
            os.unlink(out)
        # the most recent attempt wins for overlapping keys (a clean retry's
        # measurements must not be shadowed by the crashed attempt's partial
        # checkpoint); earlier values survive only for fields the retry
        # never reached
        merged = {**merged, **partial}
        if rc == 0:
            return merged, None
        last_err = tail.strip().splitlines()[-1] if tail.strip() else f"rc={rc}"
        print(
            f"[bench] phase {name} attempt {attempt + 1} failed: {last_err}",
            file=sys.stderr,
        )
    return merged, last_err


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--phase", choices=sorted(_PHASE_FNS))
    parser.add_argument("--out")
    parser.add_argument(
        "--only", help="comma-separated phase subset (orchestrator mode)"
    )
    parser.add_argument(
        "--cpu-only",
        action="store_true",
        help="skip the device phases (CI smokes of the CPU-pinned phases)",
    )
    parser.add_argument(
        "--compare",
        nargs="+",
        metavar="PRIOR_JSON",
        help="perf-regression gate: diff this run's e2e/phase percentiles "
        "against the best value across the given prior BENCH_r*.json "
        "round(s); exits nonzero on regression beyond the tolerance, with "
        "the verdict recorded in the JSON line",
    )
    parser.add_argument(
        "--current",
        metavar="CURRENT_JSON",
        help="with --compare: run no phases, just gate an existing bench "
        "JSON against the prior(s) (CI fixture mode)",
    )
    parser.add_argument(
        "--compare-tolerance",
        type=float,
        default=0.25,
        help="relative regression tolerance for --compare (default 0.25)",
    )
    parser.add_argument(
        "--no-compare",
        action="store_true",
        help="skip the automatic perf-regression gate against the "
        "checked-in BENCH_r*.json rounds",
    )
    args = parser.parse_args()

    if args.current and not args.compare:
        # --current is CI fixture mode: the caller must name its baseline
        # explicitly — the checked-in-rounds auto-default below is only for
        # full measurement runs
        parser.error("--current requires --compare")

    if not args.compare and not args.no_compare:
        # default gate: every full run is compared against the checked-in
        # prior rounds, so the perf trajectory is held (not just recorded)
        # even when the orchestrator invokes a bare `python bench.py`
        auto_priors = sorted(
            glob.glob(os.path.join(os.path.dirname(__file__) or ".", "BENCH_r*.json"))
        )
        if auto_priors:
            args.compare = auto_priors

    if args.compare and args.current:
        # pure compare mode: no phases, no jax — gate file against file(s)
        current = _load_bench_json(args.current)
        priors = [_load_bench_json(p) for p in args.compare]
        verdict = compare_bench(
            current, priors, tolerance=args.compare_tolerance
        )
        print(
            json.dumps(
                {
                    "metric": "bench_compare",
                    "compare_current": args.current,
                    "compare_baselines": list(args.compare),
                    **verdict,
                }
            )
        )
        return 0 if verdict["compare_ok"] else 1

    if args.phase:  # child mode
        out = args.out or os.path.join(
            tempfile.gettempdir(), f"pio_bench_{args.phase}_{os.getpid()}.json"
        )
        ck = _Checkpoint(out)
        _PHASE_FNS[args.phase](ck)
        if not args.out:
            print(json.dumps(ck.data))
        return 0

    if os.path.exists(FACTORS_PATH):
        os.unlink(FACTORS_PATH)  # never serve stale factors from a prior run
    selected = (
        [p for p in PHASES if p[0] in set(args.only.split(","))]
        if args.only
        else PHASES
    )
    fields: dict = {}
    errors: dict[str, str] = {}

    if args.cpu_only:
        fields["bench_cpu_only"] = True
    for name, timeout_s in selected:
        if args.cpu_only and name in _DEVICE_PHASES:
            errors[f"{name}_error"] = "skipped: --cpu-only"
            continue
        res, err = _run_phase(name, timeout_s)
        fields.update(res)
        if err:
            errors[f"{name}_error"] = err

    # offline-vs-online acceptance (ISSUE 14): the dedicated offline path
    # exists because the online path can never saturate the device — hold
    # that by measurement whenever both ran in this round, on the same CPU
    # backend over the same factors. 5x is the floor; BENCH_r01 measured
    # ~66x headroom (973 batched vs 14.6 sequential).
    off_qps = fields.get("batchpredict_offline_qps")
    on_qps = fields.get("serving_local_e2e_qps")
    if off_qps is not None and on_qps:
        fields["batchpredict_vs_online_x"] = round(off_qps / on_qps, 2)
        fields["batchpredict_speedup_gate_ok"] = bool(off_qps >= 5.0 * on_qps)

    scale_name = fields.pop("scale_name", os.environ.get("PIO_BENCH_SCALE", "ml100k"))
    train_wall = fields.pop("als_train_wall_s", None)
    # vs_baseline = e2e p50 through the real server under concurrency vs the
    # 10ms north-star target (the CPU loopback number when that phase ran,
    # else the device phase's; ROADMAP S1 replaces this pairing).
    e2e_p50 = fields.get("serving_local_e2e_p50_ms", fields.get("serving_e2e_p50_ms"))
    result = {
        "metric": f"als_{scale_name}_train_wall_clock",
        "value": train_wall,
        "unit": "s",
        **fields,
        **errors,
        "bench_host_cores": os.cpu_count(),
    }
    # evidence semantics (ROADMAP item 5): vs_baseline is OMITTED — never
    # null-paired — when the serving headline it rates is absent. A reader
    # of BENCH_r*.json must never see a ratio standing next to a missing
    # measurement and wonder which run produced it. Same contract for the
    # gateway-hop fields: _bench_gateway_hop returns {} on failure, and
    # the scrub below guarantees no None ever rides a serving_gateway_*
    # key even if a future path pairs one.
    if e2e_p50 is not None:
        result["vs_baseline"] = round(e2e_p50 / 10.0, 4)
    for key in list(result):
        if key.startswith("serving_gateway_") and result[key] is None:
            del result[key]
    compare_ok = True
    if args.compare:
        # the perf-regression gate: this run vs the best prior round(s);
        # the verdict rides in the evidence line itself
        try:
            priors = [_load_bench_json(p) for p in args.compare]
            verdict = compare_bench(
                result, priors, tolerance=args.compare_tolerance
            )
        except (OSError, json.JSONDecodeError) as exc:
            verdict = {
                "compare_ok": False,
                "compare_error": f"unreadable prior: {exc}",
            }
        result.update(compare_baselines=list(args.compare), **verdict)
        compare_ok = bool(verdict["compare_ok"])
    print(json.dumps(result))
    # Exit code: 0 = shipped numbers AND every quality gate that ran passed.
    # The gates are load-bearing (9ec18f4): a wall-clock headline with junk
    # factors must NOT look healthy to automation, so a failed gate is a
    # failed bench even though the JSON (with the gate booleans) still
    # prints for forensics. An entirely empty run is also a failure.
    gates_ok = all(v for k, v in fields.items() if k.endswith("_gate_ok"))
    # a headline metric without its paired quality gate means the phase
    # crashed between checkpointing the timing and computing the gate — the
    # exact "healthy-looking wall-clock over unvalidated factors" this exit
    # code exists to catch, so it fails the bench even though the JSON
    # above still ships the partial numbers for forensics
    gate_pairs = {
        "als_train_wall_s": "als_rmse_gate_ok",
        "twotower_examples_per_s": "twotower_recall_gate_ok",
    }
    all_fields = {**fields, "als_train_wall_s": train_wall}
    pairs_ok = all(
        gate in fields
        for headline, gate in gate_pairs.items()
        if all_fields.get(headline) is not None
    )
    # "shipped" means actual measurements — phase metadata (platform, scale,
    # factor provenance) is written before any timed region and must not
    # make a fully-crashed run look healthy
    meta_keys = {
        "platform",
        "scale",
        "serving_factors",
        "bench_cpu_only",
    }
    shipped = any(k not in meta_keys for k in fields)
    # a device phase that found no chip fails the run, whatever the CPU
    # phases shipped (skipping the device phases is --cpu-only's job)
    device_ok = not any(v.startswith(_NO_ACCELERATOR) for v in errors.values())
    return (
        0
        if (shipped and gates_ok and pairs_ok and device_ok and compare_ok)
        else 1
    )


if __name__ == "__main__":
    sys.exit(main())
