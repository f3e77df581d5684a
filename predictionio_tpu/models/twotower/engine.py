"""Two-tower retrieval engine (DASE components).

Wire contract mirrors the recommendation template (Query {user, num} ->
PredictedResult {itemScores}) so the two are drop-in interchangeable behind
the same query server.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import numpy as np

from predictionio_tpu.controller import (
    BaseDataSource,
    BasePreparator,
    BaseServing,
    Engine,
    JaxAlgorithm,
    Params,
    SanityCheck,
)
from predictionio_tpu.models.twotower.model import (
    TwoTower,
    TwoTowerConfig,
    train_two_tower,
)
from predictionio_tpu.workflow.context import WorkflowContext


@dataclasses.dataclass(frozen=True)
class Query:
    user: str
    num: int = 10

    @staticmethod
    def from_json_dict(d: dict[str, Any]) -> "Query":
        return Query(user=str(d["user"]), num=int(d.get("num", 10)))


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...]

    def to_json_dict(self) -> dict[str, Any]:
        return {
            "itemScores": [{"item": s.item, "score": s.score} for s in self.item_scores]
        }


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    event_names: tuple[str, ...] = ("rate", "buy", "view")


@dataclasses.dataclass
class TrainingData(SanityCheck):
    user_idx: np.ndarray
    item_idx: np.ndarray
    user_vocab: list[str]
    item_vocab: list[str]
    timestamps: np.ndarray | None = None  # event times for history ordering

    def sanity_check(self) -> None:
        if len(self.user_idx) == 0:
            raise ValueError("no interaction events found; check app data")


class DataSource(BaseDataSource):
    params_class = DataSourceParams
    params: DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        col = ctx.p_event_store().to_columnar_cached(
            app_name=self.params.app_name or ctx.app_name,
            channel_name=ctx.channel_name,
            event_names=list(self.params.event_names),
            entity_type="user",
            target_entity_type="item",
        )
        valid = (col.entity_ids >= 0) & (col.target_ids >= 0)
        return TrainingData(
            col.entity_ids[valid],
            col.target_ids[valid],
            col.entity_vocab,
            col.target_vocab,
            timestamps=col.timestamps[valid],
        )


class Preparator(BasePreparator):
    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> TrainingData:
        return td


@dataclasses.dataclass(frozen=True)
class TwoTowerAlgorithmParams(Params):
    embed_dim: int = 64
    hidden: tuple[int, ...] = (128,)
    out_dim: int = 32
    temperature: float = 0.05
    learning_rate: float = 1e-3
    batch_size: int = 4096
    epochs: int = 5
    seed: int = 0
    mesh: str = ""  # e.g. "data=-1,model=2"; empty = all devices on data
    # sequence encoder over each user's recent item history (consumes the
    # pallas fused-attention kernel on TPU, ops/attention.py); 0 disables.
    # On the chip a length of 1024 or more must be a multiple of 256
    # (fused_attention refuses it otherwise)
    history_len: int = 0
    n_heads: int = 2
    # sequence/context parallelism for the encoder: shard the history axis
    # over the mesh's `model` axis (ring or ulysses attention over ICI,
    # composed with `data`-axis batch sharding). Requires history_len > 0,
    # history_len % model-axis == 0, and a mesh with model > 1; serving is
    # unaffected (attention has no parameters, models load mesh-less).
    context_parallel: bool = False
    sp_impl: str = "ring"  # "ring" | "ulysses"


@dataclasses.dataclass
class TwoTowerModelState(SanityCheck):
    config: TwoTowerConfig
    params: Any  # host numpy pytree
    item_embeddings: np.ndarray
    user_vocab: list[str]
    item_vocab: list[str]
    losses: list[float]
    history: np.ndarray | None = None  # [n_users, T] when the encoder is on

    def __post_init__(self):
        self._user_index: dict[str, int] | None = None
        self._device_items = None
        self._device_params = None
        self._serve_fn = None
        self._embed_fn = None
        self._model: TwoTower | None = None

    def sanity_check(self) -> None:
        if not np.all(np.isfinite(self.item_embeddings)):
            raise ValueError("two-tower training produced non-finite embeddings")

    def user_index(self, user: str) -> int | None:
        if self._user_index is None:
            self._user_index = {u: i for i, u in enumerate(self.user_vocab)}
        return self._user_index.get(user)

    def model(self) -> TwoTower:
        if self._model is None:
            self._model = TwoTower(self.config)
        return self._model

    def device_items(self):
        if self._device_items is None:
            import jax.numpy as jnp

            self._device_items = jnp.asarray(self.item_embeddings)
        return self._device_items

    def device_params(self):
        """Tower params re-landed on device once (the checkpoint form is
        host numpy); serving must never re-upload them per query."""
        if self._device_params is None:
            import jax
            import jax.numpy as jnp

            self._device_params = jax.tree_util.tree_map(
                jnp.asarray, self.params
            )
        return self._device_params

    def serve_topk(self, uidx, hist, k: int):
        """Dispatch the fused user-tower -> dot-products -> top-k program
        for a [B] batch of user indices ([B,T] histories when the sequence
        encoder is on). One compiled program per (B, k) bucket; returns
        the packed [B,2,k] handle (decode with ``ops.topk.fetch_topk``)."""
        if self._serve_fn is None:
            import functools

            import jax

            from predictionio_tpu.models.twotower.model import TwoTower as _TT
            from predictionio_tpu.ops.topk import select_top_k

            mdl = self.model()

            @functools.partial(
                jax.jit, static_argnames=("k",), donate_argnums=(2, 3)
            )
            def _serve(params, items, uidx, hist, k: int):
                u = mdl.apply(
                    {"params": params}, uidx, hist, method=_TT.embed_users
                )
                with jax.named_scope("score"):
                    scores = u @ items.T  # [B, n_items] on the MXU
                return select_top_k(scores, k)

            self._serve_fn = _serve
        from predictionio_tpu.ops.topk import upload

        # upload() COPIES: uidx/hist live in reusable scratch buffers the
        # dispatcher overwrites for the next batch while this one is in
        # flight (jnp.asarray would alias them on the CPU backend)
        return self._serve_fn(
            self.device_params(),
            self.device_items(),
            upload(uidx),
            upload(hist),
            k,
        )

    def embed_users_async(self, uidx, hist):
        """Dispatch the user-tower forward alone: the [B, out_dim] device
        embedding handle the ANN search composes with (tower -> probe ->
        bucket scoring stay on device, no host round-trip in between)."""
        if self._embed_fn is None:
            import functools

            import jax

            from predictionio_tpu.models.twotower.model import TwoTower as _TT

            mdl = self.model()

            @functools.partial(jax.jit, donate_argnums=(1, 2))
            def _embed(params, uidx, hist):
                return mdl.apply(
                    {"params": params}, uidx, hist, method=_TT.embed_users
                )

            self._embed_fn = _embed
        from predictionio_tpu.ops.topk import upload

        return self._embed_fn(self.device_params(), upload(uidx), upload(hist))

    def __getstate__(self):
        return {
            "config": self.config,
            "params": self.params,
            "item_embeddings": self.item_embeddings,
            "user_vocab": self.user_vocab,
            "item_vocab": self.item_vocab,
            "losses": self.losses,
            "history": self.history,
        }

    def __setstate__(self, state):
        self.__dict__.update(state)
        self.__dict__.setdefault("history", None)  # pre-encoder blobs
        self._user_index = None
        self._device_items = None
        self._device_params = None
        self._serve_fn = None
        self._embed_fn = None
        self._model = None


class TwoTowerAlgorithm(JaxAlgorithm):
    params_class = TwoTowerAlgorithmParams
    params: TwoTowerAlgorithmParams

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> TwoTowerModelState:
        config = TwoTowerConfig(
            n_users=max(len(pd.user_vocab), 1),
            n_items=max(len(pd.item_vocab), 1),
            embed_dim=self.params.embed_dim,
            hidden=tuple(self.params.hidden),
            out_dim=self.params.out_dim,
            temperature=self.params.temperature,
            learning_rate=self.params.learning_rate,
            batch_size=self.params.batch_size,
            epochs=self.params.epochs,
            seed=self.params.seed,
            history_len=self.params.history_len,
            n_heads=self.params.n_heads,
            context_parallel=self.params.context_parallel,
            sp_impl=self.params.sp_impl,
        )
        mesh = None
        if self.params.mesh:
            from predictionio_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(self.params.mesh)
        history = None
        if config.history_len > 0:
            from predictionio_tpu.models.twotower.model import build_history_matrix

            history = build_history_matrix(
                pd.user_idx,
                pd.item_idx,
                pd.timestamps,
                config.n_users,
                config.history_len,
            )
        result = train_two_tower(
            pd.user_idx, pd.item_idx, config, mesh=mesh, history=history
        )
        return TwoTowerModelState(
            config=config,
            params=result.params,
            item_embeddings=result.item_embeddings,
            user_vocab=pd.user_vocab,
            item_vocab=pd.item_vocab,
            losses=result.losses,
            history=history,
        )

    def predict(self, model: TwoTowerModelState, query: Query) -> PredictedResult:
        return self.predict_batch(model, [query])[0]

    def predict_batch(
        self, model: TwoTowerModelState, queries: Sequence[Query]
    ) -> list[PredictedResult]:
        return self.predict_batch_dispatch(model, queries)()

    def predict_batch_dispatch(
        self, model: TwoTowerModelState, queries: Sequence[Query]
    ):
        """Serving micro-batch as ONE fused device program: user-tower
        forward -> dot products against the resident item table -> top-k,
        with user indices (and histories) assembled into reusable staging
        buffers and only [B, k] results fetched in the finalize. Unknown
        users answer empty without touching the device.

        When the deployed version pins an ANN index (docs/ann.md), the
        dot-products stage routes through it instead: the user embedding
        handle feeds the two-stage clustered search and only nprobe
        buckets are scored — O(batch * nprobe * cap), not O(batch *
        corpus). Exact scoring remains the fallback (no index, or k wider
        than the probe pool); sampled batches ALSO run exact as a shadow
        to measure the live recall proxy."""
        from predictionio_tpu.ann.lifecycle import ATTR as _ANN_ATTR
        from predictionio_tpu.obs.jaxprof import annotate
        from predictionio_tpu.ops import topk

        n = len(model.item_vocab)
        results: list[PredictedResult | None] = [None] * len(queries)
        rows: list[int] = []
        uidxs: list[int] = []
        max_num = 1
        for i, q in enumerate(queries):
            uidx = model.user_index(q.user)
            if uidx is None or q.num <= 0:
                results[i] = PredictedResult(())
                continue
            rows.append(i)
            uidxs.append(uidx)
            max_num = max(max_num, q.num)
        handle = None
        ann = None
        exact_handle = None
        kk = 0
        if rows:
            b = topk.batch_bucket(len(rows))
            pool = topk.scratch()
            uidx_buf = pool.zeros("twotower.uidx", (b,), np.int32)
            uidx_buf[: len(rows)] = uidxs  # pad rows serve user 0, dropped
            hist_buf = None
            if model.history is not None:
                hist_buf = pool.get(
                    "twotower.hist", (b, model.history.shape[1]),
                    model.history.dtype,
                )
                np.take(model.history, uidx_buf, axis=0, out=hist_buf)
            kk = min(topk.next_pow2(max_num), n)
            ann = getattr(model, _ANN_ATTR, None)
            if ann is not None and not ann.supports(kk):
                ann.count_fallback(len(rows))
                ann = None
            if ann is not None:
                vec_handle = model.embed_users_async(uidx_buf, hist_buf)
                handle = ann.search_async(vec_handle, kk)
                if ann.take_recall_sample():
                    exact_handle = model.serve_topk(uidx_buf, hist_buf, kk)
            else:
                handle = model.serve_topk(uidx_buf, hist_buf, kk)

        def finalize() -> list[PredictedResult]:
            if handle is not None:
                from predictionio_tpu.ops.topk import fetch_topk

                if ann is not None:
                    scores, idx = ann.fetch(handle, rows=len(rows))
                    if exact_handle is not None:
                        _, exact_idx = fetch_topk(exact_handle)
                        ann.record_recall(idx, exact_idx, rows=len(rows))
                else:
                    scores, idx = fetch_topk(handle)
                with annotate("pio:fetch.unpack"):
                    for row, i in enumerate(rows):
                        num = min(queries[i].num, kk)
                        results[i] = PredictedResult(
                            tuple(
                                ItemScore(model.item_vocab[int(it)], float(s))
                                for s, it in zip(scores[row, :num], idx[row, :num])
                                if np.isfinite(s)
                            )
                        )
            return results  # type: ignore[return-value]

        return finalize

    def warmup_serving(self, model: TwoTowerModelState, max_batch: int) -> None:
        """Pre-compile the fused tower->score->top-k program for every
        pow2 batch bucket at the default k — and, when an ANN index is
        pinned, the tower->probe->bucket-search composition the dispatch
        path actually runs (plus exact, which stays the shadow/fallback)."""
        from predictionio_tpu.ann.lifecycle import ATTR as _ANN_ATTR
        from predictionio_tpu.ops import topk

        n = len(model.item_vocab)
        kk = min(topk.next_pow2(10), n)
        ann = getattr(model, _ANN_ATTR, None)
        if ann is not None and not ann.supports(kk):
            ann = None

        def dispatch(b: int):
            hist = (
                np.zeros((b, model.history.shape[1]), model.history.dtype)
                if model.history is not None
                else None
            )
            if ann is not None:
                packed, _counts = ann.search_async(
                    model.embed_users_async(np.zeros(b, np.int32), hist), kk
                )
                return packed
            return model.serve_topk(np.zeros(b, np.int32), hist, kk)

        topk.warmup_pow2_buckets(max_batch, dispatch)
        if ann is not None:
            # the exact program stays warm at every bucket too: it is the
            # recall shadow (sampled at arbitrary batch sizes) and the
            # automatic fallback — a shadow must never pay a serving-time
            # compile the watcher would alarm on
            def dispatch_exact(b: int):
                hist = (
                    np.zeros((b, model.history.shape[1]), model.history.dtype)
                    if model.history is not None
                    else None
                )
                return model.serve_topk(np.zeros(b, np.int32), hist, kk)

            topk.warmup_pow2_buckets(max_batch, dispatch_exact)


class Serving(BaseServing):
    def serve(self, query: Query, predictions: Sequence[PredictedResult]) -> PredictedResult:
        return predictions[0]


def engine_factory() -> Engine:
    return Engine(
        DataSource,
        Preparator,
        {"twotower": TwoTowerAlgorithm},
        Serving,
        query_class=Query,
    )
