"""The recommendation template (``models/recommendation``: ``ALSAlgorithm``,
``ServingIndex``) as a system under test: what a configuration file with
``"engine": "recommendation_als"`` is built and driven through.

``serving(ctx)`` deploys seeded factor tables behind the program's own
``QueryServer`` (in this process, on its own event loop and thread, over real
TCP: the process that holds the chip is the server's) with ``pio deploy``'s
``ServerConfig`` defaults. ``training(ctx)`` hands seeded ratings as
``TrainingData`` to the engine's own ``Preparator`` and ``ALSAlgorithm.train``;
the event store is bypassed (``pio import`` loads 6.7 k events a second, 50
minutes for 20 M ratings: PERF.md, PR 22).
"""

from __future__ import annotations

import asyncio
import functools
import json
import re
import socket
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import ratings, reference

ENGINE_FACTORY = "predictionio_tpu.models.recommendation.engine_factory"
MEMORY_STORAGE = {
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
}
# serving answers recomputed against the plain reference after the window
CHECKED_QUERIES = 64
# items whose last half-step is solved again in float64 after a train
CHECKED_ITEMS = 256
HELDOUT_RMSE_GATE = 0.45  # bench.py's gate for this distribution
# ‖A y_dev − b‖ ÷ ‖b‖ allowed on an item's returned factors, A and b formed
# in float64 (benchmark/reference.py). The chip reaches 0.84e-3 to 1.47e-3
# over twelve seeds (my chip runs, PR 23): the block-Gram einsum runs with
# bf16 operands, XLA's default for a float32 dot here. 2^-8 = 3.9e-3 is what
# one bf16 pass guarantees and sits under three times the worst seen, so
# fewer CG steps, a looser solve or fp8 operands fail it and have to say so.
ITEM_RESIDUAL_TOLERANCE = 2.0**-8


def _engine_and_params(config: dict):
    from predictionio_tpu.models.recommendation import engine_factory

    engine = engine_factory()
    return engine, engine.engine_params_from_variant(config["variant"])


@functools.partial(jax.jit, static_argnames=("n_users", "n_items", "rank"))
def _factor_tables(key, *, n_users: int, n_items: int, rank: int):
    k_u, k_v = jax.random.split(key)
    scale = 1.0 / np.sqrt(rank)
    return (
        jax.random.normal(k_u, (n_users, rank), jnp.float32) * scale,
        jax.random.normal(k_v, (n_items, rank), jnp.float32) * scale,
    )


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


_METRIC_LINE = re.compile(r"^(\w+)(?:\{(.*)\})?\s+(\S+)$")


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text to ``{name{labels}: value}``."""
    out = {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        m = _METRIC_LINE.match(line.strip())
        if m:
            try:
                out[f"{m.group(1)}{{{m.group(2) or ''}}}"] = float(m.group(3))
            except ValueError:
                pass
    return out


class Serving:
    """One deployment: tables from the seed, the program's server in front."""

    path = "/queries.json"
    item_marker = '"item"'

    def __init__(self, ctx):
        from predictionio_tpu.data.storage.registry import Storage
        from predictionio_tpu.models.recommendation.engine import ALSModel
        from predictionio_tpu.workflow.create_server import QueryServer, ServerConfig
        from predictionio_tpu.workflow.engine_loader import EngineManifest

        config = ctx.config
        self.n_users, self.n_items = int(config["n_users"]), int(config["n_items"])
        engine, engine_params = _engine_and_params(config)
        self.rank = int(engine_params.algorithms[0][1].rank)
        self.num = int(ctx.traffic["num"])
        self.items_expected = self.num
        self.body_format = '{"user":"u%%d","num":%d}' % self.num
        self.parts = {}

        t = time.monotonic()
        self.user_factors, self.item_factors = jax.block_until_ready(
            _factor_tables(
                ratings.device_key(ctx.seed),
                n_users=self.n_users,
                n_items=self.n_items,
                rank=self.rank,
            )
        )
        self.parts["tables_s"] = time.monotonic() - t

        # ALSModel wants both vocabularies as Python lists of strings and
        # looks users up in a dict it builds from them: set-up that only the
        # program can shorten (PERF.md)
        t = time.monotonic()
        model = ALSModel(
            self.user_factors,
            self.item_factors,
            list(map("u%d".__mod__, range(self.n_users))),
            list(map("i%d".__mod__, range(self.n_items))),
        )
        model.user_index("u0")
        self.parts["vocab_s"] = time.monotonic() - t

        # pio deploy's defaults, but for the address and what the
        # configuration file states of the deployment
        server_config = ServerConfig(
            ip="127.0.0.1", port=_free_port(), **config.get("server_config", {})
        )
        self.port = server_config.port
        self.loop = asyncio.new_event_loop()
        self.server = QueryServer(
            engine=engine,
            engine_params=engine_params,
            models=[model],
            manifest=EngineManifest(
                engine_id="benchmark",
                version="1",
                variant="engine.json",
                engine_factory=ENGINE_FACTORY,
            ),
            instance_id="benchmark",
            storage=Storage(env=MEMORY_STORAGE),
            config=server_config,
        )
        started = threading.Event()
        failure = []

        def serve():
            asyncio.set_event_loop(self.loop)
            try:
                # start() warms the single-query program and every
                # power-of-two bucket up to max_batch_size, as a deploy does
                self.loop.run_until_complete(self.server.start())
            except BaseException as exc:  # surfaced to the caller below
                failure.append(exc)
                started.set()
                return
            started.set()
            self.loop.run_forever()

        t = time.monotonic()
        self.thread = threading.Thread(target=serve, daemon=True)
        self.thread.start()
        started.wait()
        if failure:
            raise failure[0]
        self.parts["server_start_s"] = time.monotonic() - t

    def shapes(self) -> dict:
        return {"n_items": self.n_items, "rank": self.rank}

    def counters(self) -> dict[str, float]:
        """The program's own counters, now: ``/metrics`` as a client would
        scrape it, the micro-batcher's dispatch counts and the result
        cache's ``stats()``."""
        with urllib.request.urlopen(
            f"http://127.0.0.1:{self.port}/metrics", timeout=10
        ) as resp:
            out = parse_metrics(resp.read().decode())
        batcher = self.server._batcher
        out["batcher.queries_dispatched"] = float(batcher.queries_dispatched)
        out["batcher.batches_dispatched"] = float(batcher.batches_dispatched)
        cache = self.server._result_cache
        for key, value in (cache.stats() if cache is not None else {}).items():
            out[f"result_cache.{key}"] = float(value)
        return out

    def check(self, kept: dict[int, str]):
        """``kept`` maps a user index to the reply body it was sent:
        ``(checked, wrong, worst |Δscore| ÷ Σ|u·v|)`` against the plain
        reference, from the same tables."""
        wrong, worst = 0, 0.0
        for uidx, body in kept.items():
            answer = json.loads(body)["itemScores"]
            ids = [int(row["item"][1:]) for row in answer]
            scores = [row["score"] for row in answer]
            ok, rel = reference.check_topk(
                self.user_factors, self.item_factors, uidx, ids, scores
            )
            wrong += not ok
            worst = max(worst, rel)
        return len(kept), wrong, worst

    def stop(self) -> None:
        future = asyncio.run_coroutine_threadsafe(self.server.stop(), self.loop)
        try:
            future.result(timeout=20)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(timeout=20)


def serving(ctx) -> Serving:
    return Serving(ctx)


class Training:
    """Seeded ratings, the engine's own Preparator and ALSAlgorithm."""

    def __init__(self, ctx):
        from predictionio_tpu.models.recommendation.engine import TrainingData
        from predictionio_tpu.workflow.context import WorkflowContext

        config = ctx.config
        self.n_users, self.n_items = int(config["n_users"]), int(config["n_items"])
        self.parts = {}
        t = time.monotonic()
        users, items, vals, heldout = ratings.synthesize_ratings(
            int(config["structure_seed"]),
            ctx.seed,
            self.n_users,
            self.n_items,
            int(config["n_ratings"]),
            float(config["heldout_share"]),
        )
        self.heldout = (users[heldout], items[heldout], vals[heldout])
        self.train_ratings = (users[~heldout], items[~heldout], vals[~heldout])
        self.parts["ratings_s"] = time.monotonic() - t

        t = time.monotonic()
        engine, self.engine_params = _engine_and_params(config)
        _, preparator, (self.algorithm,), _ = engine.make_components(self.engine_params)
        self.ctx = WorkflowContext(mode="training")
        self.prepared = preparator.prepare(
            self.ctx,
            TrainingData(
                *self.train_ratings,
                list(map("u%d".__mod__, range(self.n_users))),
                list(map("i%d".__mod__, range(self.n_items))),
            ),
        )
        self.prepared.sanity_check()
        self.parts["prepare_s"] = time.monotonic() - t
        self.seed = ctx.seed

    def warm(self, instrumented: bool = False) -> None:
        """One one-iteration train at the full shape, by the path the window
        will take: every program a train runs, compiled or loaded from the
        cache (the instrumented path adds its barrier programs)."""
        import dataclasses

        t = time.monotonic()
        if instrumented:
            self.instrumented(iterations=1)
        else:
            algorithm = type(self.algorithm)(
                dataclasses.replace(self.algorithm.params, num_iterations=1)
            )
            algorithm.train(self.ctx, self.prepared)
        self.parts["warm_train_s"] = time.monotonic() - t

    def train(self):
        """The plain path: no timings, no profile. The model's factors are
        host arrays, so the fetch is inside."""
        return self.algorithm.train(self.ctx, self.prepared)

    def als_config(self, iterations: int | None = None):
        """The ``ALSConfig`` that ``ALSAlgorithm.train`` builds."""
        from predictionio_tpu.ops.als import ALSConfig

        p = self.algorithm.params
        return ALSConfig(
            rank=p.rank,
            iterations=iterations or p.num_iterations,
            reg=p.lambda_,
            implicit=p.implicit_prefs,
            alpha=p.alpha,
            seed=p.seed if p.seed is not None else 0,
            gather_dtype=p.gather_dtype,
            solver=p.solver,
        )

    def instrumented(self, iterations: int | None = None):
        """One train through ``ops/als.als_train(..., timings=)``, with the
        ``ALSConfig`` that ``ALSAlgorithm.train`` builds: the program's own
        barrier-closed stage clocks. Its barriers forbid the overlap the
        plain path has, so it is run in the traced run only."""
        from predictionio_tpu.ops.als import als_train

        timings: dict = {}
        pd = self.prepared
        t = time.monotonic()
        uf, vf = als_train(
            pd.user_idx, pd.item_idx, pd.ratings, self.n_users, self.n_items,
            self.als_config(iterations), timings=timings,
        )
        factors = (np.asarray(uf), np.asarray(vf))
        timings["wall_s"] = time.monotonic() - t
        timings["iterations"] = iterations or self.algorithm.params.num_iterations
        return timings, factors

    def shapes(self) -> dict:
        p = self.algorithm.params
        return {
            "n_users": self.n_users,
            "n_items": self.n_items,
            "rank": p.rank,
            "solver": p.solver,
            "gather_dtype": p.gather_dtype,
            "implicit": p.implicit_prefs,
        }

    def check(self, model):
        """``(ok, detail)``: held-out RMSE under the gate, and the last
        half-step of seeded items against a float64 solve."""
        uf, vf = np.asarray(model.user_factors), np.asarray(model.item_factors)
        rmse = reference.heldout_rmse(uf, vf, *self.heldout)
        rng = np.random.default_rng([self.seed, 2])
        users, items, vals = self.train_ratings
        # the head item always: its system sums the most ratings
        head = int(np.bincount(items, minlength=self.n_items).argmax())
        chosen = np.unique(
            np.concatenate([[head], rng.integers(0, self.n_items, CHECKED_ITEMS - 1)])
        )
        residual, distance = reference.item_half_step_check(
            uf, vf, users, items, vals, chosen, float(self.algorithm.params.lambda_)
        )
        finite = bool(np.isfinite(uf).all() and np.isfinite(vf).all())
        ok = finite and rmse <= HELDOUT_RMSE_GATE and residual <= ITEM_RESIDUAL_TOLERANCE
        return ok, {
            "heldout_rmse": rmse,
            "item_residual": residual,
            "item_distance_to_f64": distance,
            "items_checked": int(len(chosen)),
        }


def training(ctx) -> Training:
    return Training(ctx)
