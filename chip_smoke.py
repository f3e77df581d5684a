"""Bring-up smoke: the framework's main path on one attached TPU chip.

Runs, through the CLI a user would type and one child process at a time,

    kernels -> pio app new -> pio import -> pio train -> pio models show
            -> pio deploy + POST /queries.json + SIGTERM -> check

on the recommendation template at the repository's headline shape
(138,000 users x 27,000 items, rank 32, 10 iterations; the ML-20M shape
BASELINE.md names). Ratings are scale, not width: they are synthesized from
``--seed`` with the distribution ``synthesize_ratings`` states and cut to what
``pio import`` loads in about a minute; every user and item is rated at
least once, so the factor tables, the [138k, 32, 32] normal-equation
workspace and the serving index keep their full width.

This process imports no JAX (a process that has touched JAX holds the
chip and its children could not have it); ``kernels`` and ``check`` are
this file run again as a child. Nothing is retried, probed or skipped: the
first step that fails ends the run with a non-zero exit code. A train or a
server that did not run on ``REQUIRED_PLATFORM`` is a failure, whatever
else succeeded. The last line of a successful run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reported it; the line before it, ``SHAPE {...}``, names
the shape that ran. The widths are constants of this file; only the ratings
count (scale) can be set from outside.

``python chip_smoke.py`` needs no arguments; see PERF.md for what a run
established.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
REQUIRED_PLATFORM = "tpu"

# the headline shape (BASELINE.md: ML-20M's): widths are never cut
N_USERS, N_ITEMS, RANK, ITERATIONS = 138_000, 27_000, 32, 10
FULL_RATINGS = 20_000_000
# the cut: `pio import` loads about a million events a minute into SQLite
DEFAULT_RATINGS = 1_000_000
APP_NAME = "chipsmoke"
ENGINE_ID = "chip-smoke"
# a served score may differ from the float64 one by this share of
# sum_j |u_j v_j|. f32 products accumulated in f32 stay under 2^-18 of it
# (the chip showed 2^-23, PERF.md); one bf16 pass on the MXU would be 2^-8
# of it and fails here on purpose: XLA computes this [f] x [f, n_items]
# product in f32 today, and serving at a lower precision is a change of
# results that whoever makes it has to state, in this constant too
SCORE_TOLERANCE_FACTOR = 2.0**-16


# ---------------------------------------------------------------------------
# data: made from the seed, the same bytes every time
# ---------------------------------------------------------------------------


def synthesize_ratings(seed: int, n_users: int, n_items: int, n_ratings: int):
    """Uniform users, zipf(1.3) items, a rank-8 structure + 3.0 + N(0, 0.3)
    clipped to [1, 5] and quantized to half stars, with the first ratings
    re-pointed so that every user and every item occurs at least once."""
    if n_ratings < max(n_users, n_items):
        raise ValueError("fewer ratings than entities: the tables would not be full width")
    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_users, n_ratings).astype(np.int32)
    items = (rng.zipf(1.3, n_ratings).astype(np.int64) % n_items).astype(np.int32)
    users[:n_users] = rng.permutation(n_users)
    items[:n_items] = rng.permutation(n_items)
    k = 8
    U = rng.normal(size=(n_users, k)) / np.sqrt(k)
    V = rng.normal(size=(n_items, k)) / np.sqrt(k)
    vals = np.sum(U[users] * V[items], axis=1) + 3.0 + 0.3 * rng.normal(size=n_ratings)
    vals = np.round(np.clip(vals, 1.0, 5.0) * 2.0) / 2.0
    return users, items, vals.astype(np.float32)


def write_events(path: str, seed: int, n_users: int, n_items: int, n_ratings: int) -> None:
    """The ratings as `pio import` JSON lines (one "rate" event each)."""
    users, items, vals = synthesize_ratings(seed, n_users, n_items, n_ratings)
    line = (
        '{"event":"rate","entityType":"user","entityId":"u%d",'
        '"targetEntityType":"item","targetEntityId":"i%d",'
        '"properties":{"rating":%.1f},'
        '"eventTime":"2020-01-%02dT%02d:%02d:%02d.000Z"}\n'
    )
    with open(path, "w") as f:
        for start in range(0, n_ratings, 100_000):
            stop = min(start + 100_000, n_ratings)
            f.write(
                "".join(
                    line % (u, i, v, 1 + t // 86400, t // 3600 % 24, t // 60 % 60, t % 60)
                    for u, i, v, t in zip(
                        users[start:stop].tolist(),
                        items[start:stop].tolist(),
                        vals[start:stop].tolist(),
                        range(start, stop),
                    )
                )
            )


# ---------------------------------------------------------------------------
# plan: every child's command line, fixed before anything runs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Plan:
    workdir: str
    env: dict[str, str]
    steps: dict[str, list[str]]
    queries: list[dict]

    @property
    def events_path(self) -> str:
        return os.path.join(self.workdir, "events.jsonl")

    @property
    def engine_dir(self) -> str:
        return os.path.join(self.workdir, "engine")

    @property
    def served_path(self) -> str:
        return os.path.join(self.workdir, "served.json")


def build_plan(args: argparse.Namespace, workdir: str) -> Plan:
    from predictionio_tpu.utils.platform import configure_jax

    configure_jax()  # settles JAX_PLATFORMS and the cache directory for every child
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # a store of its own: the native library, the SQLite store, snapshots
    # and the model blob are all made by this run
    env["PIO_FS_BASEDIR"] = os.path.join(workdir, "store")
    for name in [n for n in env if n.startswith(("PIO_STORAGE_", "PIO_SNAPSHOT_"))]:
        del env[name]
    plan = Plan(workdir, env, {}, [
        {"user": "u0", "num": 4},
        {"user": "u%d" % (N_USERS // 2), "num": 10},
        {"user": "u%d" % (N_USERS - 1), "num": 10},
        {"user": "u7", "num": 4},
        {"user": "nobody-by-this-name", "num": 4},
    ])
    registry = ["--registry-dir", os.path.join(workdir, "registry")]
    engine = ["--engine-dir", plan.engine_dir]
    pio = [sys.executable, "-m", "predictionio_tpu.tools.cli"]
    me = [sys.executable, os.path.abspath(__file__)]
    plan.steps.update({
        "kernels": me + ["--child", "kernels"],
        "app_new": pio + ["app", "new", APP_NAME],
        "import": pio + ["import", "--appname", APP_NAME, "--input", plan.events_path],
        "train": pio + ["train"] + engine + registry,
        "models_show": pio + ["models", "show", "--engine-id", ENGINE_ID] + registry,
        "deploy": pio + ["deploy"] + engine + ["--ip", "127.0.0.1", "--port", str(args.port)],
        "check": me + [
            "--child", "check", "--workdir", workdir,
            "--seed", str(args.seed), "--ratings", str(args.ratings),
        ],
    })
    return plan


def write_variant(plan: Plan, args: argparse.Namespace) -> None:
    os.makedirs(plan.engine_dir, exist_ok=True)
    variant = {
        "id": ENGINE_ID,
        "description": "chip_smoke: recommendation template at the headline shape",
        "engineFactory": "predictionio_tpu.models.recommendation.engine_factory",
        "datasource": {"params": {"appName": APP_NAME}},
        "algorithms": [
            {
                "name": "als",
                "params": {
                    "rank": RANK,
                    "numIterations": ITERATIONS,
                    "lambda": 0.05,
                    "seed": args.seed,
                },
            }
        ],
    }
    with open(os.path.join(plan.engine_dir, "engine.json"), "w") as f:
        json.dump(variant, f, indent=2)


# ---------------------------------------------------------------------------
# parent: one child at a time
# ---------------------------------------------------------------------------


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def run_step(plan: Plan, name: str, echo: bool = True) -> tuple[str, float]:
    """Run one child to its end; its failure is this run's failure."""
    say(f"--- {name}: {' '.join(plan.steps[name])}")
    t0 = time.perf_counter()
    proc = subprocess.run(
        plan.steps[name], env=plan.env, cwd=plan.workdir,
        stdout=subprocess.PIPE, stderr=None, text=True,
    )
    wall = time.perf_counter() - t0
    if echo or proc.returncode != 0:
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
    if proc.returncode != 0:
        raise SystemExit(f"chip_smoke: step {name} failed with exit code {proc.returncode}")
    say(f"{name}: {wall:.1f} s wall")
    return proc.stdout, wall


def marked_json(stdout: str, marker: str):
    """The JSON after ``marker`` on the one stdout line that starts with it."""
    lines = [ln for ln in stdout.splitlines() if ln.startswith(marker)]
    if len(lines) != 1:
        raise SystemExit(f"chip_smoke: expected one {marker!r} line, found {len(lines)}")
    return json.loads(lines[0][len(marker):])


def require_platform(what: str, device: dict | None) -> dict:
    """Fail unless ``device`` (read by the child from the arrays it used)
    names the required platform, a device kind and a device count."""
    if (
        not device
        or device.get("platform") != REQUIRED_PLATFORM
        or not device.get("deviceKind")
        or not device.get("deviceCount")
    ):
        found = device.get("platform") if device else None
        raise SystemExit(
            f"chip_smoke: {what} must run on platform {REQUIRED_PLATFORM!r} "
            f"and say which device; it reported platform {found!r} ({device})"
        )
    say(f"{what} ran on platform {device['platform']}, device_kind "
        f"{device['deviceKind']!r}, {device['deviceCount']} of "
        f"{device['visibleDevices']} device(s)")
    return device


def http_json(url: str, payload: dict | None = None, timeout: float = 30.0):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def cache_entries(cache_dir: str | None) -> int:
    return len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0


def serve_and_query(plan: Plan, port: int, start_timeout_s: float = 600.0) -> dict:
    """`pio deploy`, a few queries, SIGTERM; the server must drain and exit
    0. Returns the device the server reported."""
    say(f"--- deploy: {' '.join(plan.steps['deploy'])}")
    base = f"http://127.0.0.1:{port}"
    t0 = time.perf_counter()
    server = subprocess.Popen(plan.steps["deploy"], env=plan.env, cwd=plan.workdir)
    try:
        status = None
        while status is None:
            if server.poll() is not None:
                raise SystemExit(f"chip_smoke: pio deploy exited with code {server.returncode} before serving")
            if time.perf_counter() - t0 > start_timeout_s:
                raise SystemExit(f"chip_smoke: pio deploy not serving after {start_timeout_s:.0f} s")
            try:
                status = http_json(base + "/", timeout=5.0)
            except (urllib.error.URLError, ConnectionError, TimeoutError):
                time.sleep(0.5)
        say(f"deploy: serving after {time.perf_counter() - t0:.1f} s (model load + warmup compiles)")
        device = require_platform("pio deploy", status.get("device"))
        served = []
        for query in plan.queries:
            t1 = time.perf_counter()
            answer = http_json(base + "/queries.json", query)
            served.append({"query": query, "answer": answer})
            say(f"query {json.dumps(query)} -> {len(answer['itemScores'])} items "
                f"in {1e3 * (time.perf_counter() - t1):.1f} ms")
        with open(plan.served_path, "w") as f:
            json.dump(served, f)
        server.send_signal(signal.SIGTERM)
        code = server.wait(timeout=120)
        if code != 0:
            raise SystemExit(f"chip_smoke: pio deploy exited with code {code} after SIGTERM")
        say("deploy: drained and exited 0 after SIGTERM (chip released)")
        return device
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()


def main(argv: list[str] | None = None) -> int:
    args = parse_args(argv)
    if args.child == "kernels":
        return child_kernels()
    if args.child == "check":
        return child_check(args)

    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="pio_chip_smoke_")
    try:
        plan = build_plan(args, workdir)
        cache_dir = plan.env.get("JAX_COMPILATION_CACHE_DIR")  # none on a CPU run
        entries_before = cache_entries(cache_dir)
        say(f"workdir {workdir}; JAX_PLATFORMS={plan.env['JAX_PLATFORMS']}")
        say(f"compile cache {cache_dir}: {entries_before} entries before")
        say(f"shape {N_USERS:,} users x {N_ITEMS:,} items, rank {RANK}, "
            f"{ITERATIONS} iterations; ratings cut to {args.ratings:,} of the "
            f"{FULL_RATINGS:,} the ml20m shape names ({100 * args.ratings / FULL_RATINGS:.1f}%), "
            f"seed {args.seed}")

        kernels_out, _ = run_step(plan, "kernels")
        found = marked_json(kernels_out, "KERNELS ")
        run_step(plan, "app_new")
        t0 = time.perf_counter()
        write_events(plan.events_path, args.seed, N_USERS, N_ITEMS, args.ratings)
        say(f"events: {args.ratings:,} written in {time.perf_counter() - t0:.1f} s")
        write_variant(plan, args)
        run_step(plan, "import")

        train_out, _ = run_step(plan, "train")
        train_device = require_platform("pio train", marked_json(train_out, "Trained on: "))
        shown, _ = run_step(plan, "models_show", echo=False)
        profile = json.loads(shown)["manifest"]["train_profile"]
        require_platform("the train profile", profile["device"])
        say(f"train: profiled {profile['wallClockS']:.1f} s, of which "
            f"tracing, lowering and XLA compile (or cache load) {profile['xlaCompileS']:.1f} s "
            f"over {profile['xlaCompiles']} programs; phases "
            + ", ".join(f"{k} {v['wallS']:.1f} s" for k, v in profile["phases"].items()))

        serve_device = serve_and_query(plan, args.port)
        run_step(plan, "check")

        entries_after = cache_entries(cache_dir)
        say(f"compile cache {cache_dir}: {entries_before} entries before, {entries_after} after")
        for what, device in (("pio train", train_device), ("pio deploy", serve_device)):
            if (device["platform"], device["deviceKind"]) != (found["platform"], found["kind"]):
                raise SystemExit(f"chip_smoke: {what} reported {device}, the kernels child {found}")
        say(f"all steps passed in {time.perf_counter() - t_start:.1f} s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    # the shape that ran goes on a line of its own: the last line holds the
    # keys "ok" and "device" and no others (the chip check's contract)
    print("SHAPE " + json.dumps({"users": N_USERS, "items": N_ITEMS, "rank": RANK,
                                 "iterations": ITERATIONS, "ratings": args.ratings}))
    print(json.dumps(result_line(found)), flush=True)
    return 0


def result_line(found: dict) -> dict:
    """The run's last line of standard output, for a run that passed."""
    return {
        "ok": True,
        "device": {"platform": str(found["platform"]), "kind": str(found["kind"]),
                   "count": int(found["count"])},
    }


def parse_args(argv: list[str] | None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ratings", type=int, default=DEFAULT_RATINGS,
                   help="events to import (scale; the widths are fixed)")
    p.add_argument("--port", type=int, default=18765)
    p.add_argument("--child", choices=["kernels", "check"], help=argparse.SUPPRESS)
    p.add_argument("--workdir", help=argparse.SUPPRESS)
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# child: kernels — every Pallas kernel, compiled, at its consumers' shapes
# ---------------------------------------------------------------------------


def child_kernels() -> int:
    import importlib.metadata

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.attention import attention_reference, fused_attention
    from predictionio_tpu.ops.spd_solve import _cg_lanes, batched_spd_solve_auto

    dev = jax.devices()[0]
    found = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print("versions: python %s, jax %s, jaxlib %s, libtpu %s" % (
        sys.version.split()[0], jax.__version__,
        importlib.metadata.version("jaxlib"), importlib.metadata.version("libtpu")))
    print(f"devices: platform {found['platform']}, device_kind {found['kind']!r}, count {found['count']}")
    if found["platform"] != REQUIRED_PLATFORM:
        raise SystemExit(
            f"chip_smoke: needs platform {REQUIRED_PLATFORM!r}; JAX found platform "
            f"{found['platform']!r} ({found['kind']}, JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')})")

    def first_call(fn, *xs):
        """(result, seconds of the first call: compile, or cache load, and
        one run). A set-up fact; no kernel is timed here."""
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*xs))
        return out, time.perf_counter() - t0

    def holds_compiled_kernel(fn, *xs) -> None:
        if "tpu_custom_call" not in fn.lower(*xs).as_text():
            raise SystemExit("chip_smoke: the program holds no Mosaic kernel (interpret mode?)")

    rng = np.random.default_rng(0)
    # (B, H, L, D): the single-block kernel at the two-tower history
    # encoder's and the sequential scorer's shapes, the flash kernel at the
    # long-sequence shape (4 x 8 x 2048 x 64). The kernels multiply in bf16 with f32
    # accumulation (2e-2, the repo's own test tolerance); the reference
    # runs in f32 so that only the kernel's rounding is in the difference.
    for shape in ((8, 2, 8, 32), (8, 1, 8, 16), (4, 8, 2048, 64)):
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3))
        for causal in (False, True):
            kern = jax.jit(lambda q, k, v, c=causal: fused_attention(q, k, v, causal=c))
            holds_compiled_kernel(kern, q, k, v)
            out, first_s = first_call(kern, q, k, v)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda q, k, v, c=causal: attention_reference(q, k, v, causal=c))(q, k, v)
            err = float(jnp.max(jnp.abs(out - ref)))
            print(f"kernel attention {shape} causal={causal}: max|kernel - attention_reference| "
                  f"{err:.2e}; first call {first_s:.2f} s")
            if not err < 2e-2:
                raise SystemExit(f"chip_smoke: attention kernel differs from its reference at {shape}")

    # the CG's tile kernel through the jitted entry, at the smoke's rank
    # over the user table's systems and at the template's default rank over
    # the item table's, against the same body as plain XLA; systems shaped
    # like regularized ALS normal equations
    for n, f in ((N_USERS + 1, RANK), (N_ITEMS + 1, 10)):
        g = jnp.asarray(rng.normal(size=(n, f, 2 * f)), jnp.float32)
        A = jnp.einsum("nfd,ngd->nfg", g, g, precision="highest") / (2 * f) + 0.5 * jnp.eye(f)
        b = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
        holds_compiled_kernel(batched_spd_solve_auto, A, b)
        x, first_s = first_call(batched_spd_solve_auto, A, b)
        ref = jax.jit(lambda A, b: _cg_lanes(jnp.transpose(A, (2, 1, 0)), b.T).T)(A, b)
        resid = float(jnp.max(jnp.abs(jnp.einsum("nfg,ng->nf", A, x, precision="highest") - b)))
        err = float(jnp.max(jnp.abs(x - ref)))
        print(f"kernel cg n={n} f={f}: max|kernel - plain body| {err:.2e}, max residual "
              f"{resid:.2e}; first call {first_s:.2f} s")
        # same body, f32 multiply-and-add on both sides: float rounding only
        if not (err < 1e-4 and resid < 1e-4):
            raise SystemExit(f"chip_smoke: the CG tile kernel differs from its plain body at f={f}")
    print("KERNELS " + json.dumps(found))
    return 0


# ---------------------------------------------------------------------------
# child: check — the persisted factors and the served answers, in NumPy
# ---------------------------------------------------------------------------


def child_check(args: argparse.Namespace) -> int:
    # pinned to the CPU before anything imports JAX: unpickling the model
    # imports the engine module, and the reference must not take the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.utils import native
    from predictionio_tpu.workflow import model_io

    storage = Storage.instance()
    instances = storage.get_meta_data_engine_instances().get_all()
    done = [i for i in instances if i.engine_id == ENGINE_ID and i.status == "COMPLETED"]
    if len(done) != 1:
        raise SystemExit(f"chip_smoke: expected one COMPLETED instance, found {len(done)}")
    blob = storage.get_model_data_models().get(done[0].id).models
    (model,) = model_io.deserialize_models(blob)
    uf = np.asarray(model.user_factors, np.float64)
    vf = np.asarray(model.item_factors, np.float64)
    print(f"check: persisted factors {uf.shape} x {vf.shape}, blob {len(blob):,} bytes; "
          f"native library loaded: {native.get_library() is not None}")
    if uf.shape != (N_USERS, RANK) or vf.shape != (N_ITEMS, RANK):
        raise SystemExit("chip_smoke: the persisted tables are not full width")
    if not (np.isfinite(uf).all() and np.isfinite(vf).all()):
        raise SystemExit("chip_smoke: non-finite factors")

    users, items, vals = synthesize_ratings(args.seed, N_USERS, N_ITEMS, args.ratings)
    u_of = {name: i for i, name in enumerate(model.user_vocab)}
    i_of = {name: i for i, name in enumerate(model.item_vocab)}
    u_row = np.fromiter((u_of["u%d" % u] for u in range(N_USERS)), np.int64, N_USERS)
    i_row = np.fromiter((i_of["i%d" % i] for i in range(N_ITEMS)), np.int64, N_ITEMS)
    pred = np.einsum("nf,nf->n", uf[u_row[users]], vf[i_row[items]])
    rmse = float(np.sqrt(np.mean((pred - vals) ** 2)))
    rmse_mean = float(np.std(vals))
    print(f"check: training-set RMSE {rmse:.4f} (global-mean predictor {rmse_mean:.4f})")
    if not rmse < rmse_mean:
        raise SystemExit("chip_smoke: the factors fit the training set no better than its mean")

    with open(os.path.join(args.workdir, "served.json")) as f:
        served = json.load(f)
    for entry in served:
        query, got = entry["query"], entry["answer"]["itemScores"]
        if query["user"] not in u_of:
            if got:
                raise SystemExit(f"chip_smoke: unknown user {query['user']!r} got an answer")
            print(f"check: {json.dumps(query)} -> empty, as an unknown user should")
            continue
        u = uf[u_of[query["user"]]]
        scores = vf @ u
        tol = SCORE_TOLERANCE_FACTOR * float(np.max(np.abs(vf) @ np.abs(u)))
        best = np.sort(scores)[::-1][: query["num"]]
        rows = [i_of[s["item"]] for s in got]
        at_rows = scores[rows]
        worst_score = float(np.max(np.abs(np.array([s["score"] for s in got]) - at_rows)))
        worst_rank = float(np.max(np.abs(at_rows - best))) if len(rows) == len(best) else np.inf
        exact = rows == np.argsort(-scores, kind="stable")[: query["num"]].tolist()
        print(f"check: {json.dumps(query)} -> {len(rows)} items, ids "
              f"{'equal' if exact else 'equal up to near-ties'}; max|score - numpy| {worst_score:.2e}, "
              f"max rank-wise gap {worst_rank:.2e}, tolerance {tol:.2e}")
        if len(set(rows)) != query["num"] or worst_score > tol or worst_rank > tol:
            raise SystemExit(f"chip_smoke: served answer for {query} differs from the NumPy top-k")
    print("check: every served answer equals the NumPy top-k over the persisted factors")
    return 0


if __name__ == "__main__":
    sys.exit(main())
