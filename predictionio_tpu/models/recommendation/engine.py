"""ALS recommendation engine (DASE components).

Reference parity (behavioral, re-designed for TPU):
  - Query {"user", "num"} / PredictedResult {"itemScores": [{item, score}]}
    — ``recommendation-engine/src/main/scala/Engine.scala:22-39``.
  - DataSource reads "rate" and "buy" events of user->item, mapping buy to
    rating 4.0; k-fold readEval grouping eval queries per user —
    ``DataSource.scala:45-104``.
  - ALSAlgorithm params rank/numIterations/lambda/seed —
    ``ALSAlgorithm.scala:39-90`` (MLlib ALS there; ops.als here).
  - Serving returns the first algorithm's result — ``Serving.scala``.

TPU design: training data is columnar (dense int32 user/item ids + float32
ratings) from one event-store scan; the model holds host-numpy factor tables
plus id vocabularies; serving re-lands factors on device once and answers
queries with a resident jitted dot-product + ``lax.top_k``.
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
from predictionio_tpu.data.storage.base import ColumnarEvents
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.workflow.context import WorkflowContext


# ---------------------------------------------------------------------------
# Wire types
# ---------------------------------------------------------------------------


DEFAULT_QUERY_NUM = 10


@dataclasses.dataclass(frozen=True)
class Query:
    """``blackList`` mirrors the blacklist-items variant
    (``examples/scala-parallel-recommendation/blacklist-items/src/main/scala/
    Engine.scala:23-27``); None means no filtering."""

    user: str
    num: int = DEFAULT_QUERY_NUM
    black_list: frozenset[str] | None = None

    @staticmethod
    def from_json_dict(d: dict[str, Any]) -> "Query":
        bl = d.get("blackList")
        return Query(
            user=str(d["user"]),
            num=int(d.get("num", DEFAULT_QUERY_NUM)),
            black_list=frozenset(str(x) for x in bl) if bl is not None else None,
        )


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...]

    def to_json_dict(self) -> dict[str, Any]:
        return {
            "itemScores": [
                {"item": s.item, "score": s.score} for s in self.item_scores
            ]
        }


@dataclasses.dataclass(frozen=True)
class Rating:
    user: str
    item: str
    rating: float


@dataclasses.dataclass(frozen=True)
class ActualResult:
    ratings: tuple[Rating, ...]


# ---------------------------------------------------------------------------
# DataSource
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EvalParams(Params):
    k_fold: int = 2
    query_num: int = 10


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    """``rating_map`` generalises the reading-custom-events variant
    (``reading-custom-events/src/main/scala/DataSource.scala:50-61``: like->4.0,
    dislike->1.0) and train-with-view-event (view->1.0 + implicit ALS): each
    listed event name is assigned a fixed rating value, overriding any
    per-event "rating" property."""

    app_name: str = ""
    event_names: tuple[str, ...] = ("rate", "buy")
    buy_rating: float = 4.0  # ref: map buy event to rating 4
    rating_map: dict[str, float] | None = None
    eval_params: EvalParams | None = None


@dataclasses.dataclass
class TrainingData(SanityCheck):
    """Columnar ratings + vocabularies."""

    user_idx: np.ndarray
    item_idx: np.ndarray
    ratings: np.ndarray
    user_vocab: list[str]
    item_vocab: list[str]

    def sanity_check(self) -> None:
        if len(self.user_idx) == 0:
            raise ValueError(
                "no rating events found; check app data (ref: empty RDD check)"
            )
        if not np.all(np.isfinite(self.ratings)):
            raise ValueError("non-finite rating values present")


def _columnar_to_ratings(
    col: ColumnarEvents,
    buy_rating: float,
    rating_map: dict[str, float] | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    ratings = col.ratings.copy()
    if rating_map:
        names = np.asarray(col.event_names)
        for event_name, value in rating_map.items():
            ratings[names == event_name] = float(value)
    else:
        buys = np.asarray([n == "buy" for n in col.event_names], dtype=bool)
        ratings[buys] = buy_rating
    valid = np.isfinite(ratings) & (col.entity_ids >= 0) & (col.target_ids >= 0)
    return col.entity_ids[valid], col.target_ids[valid], ratings[valid]


class DataSource(BaseDataSource):
    params_class = DataSourceParams
    params: DataSourceParams

    def _read_columnar(self, ctx: WorkflowContext) -> ColumnarEvents:
        store = ctx.p_event_store()
        return store.to_columnar_cached(
            app_name=self.params.app_name or ctx.app_name,
            channel_name=ctx.channel_name,
            event_names=list(self.params.event_names),
            entity_type="user",
            target_entity_type="item",
            rating_key="rating",
        )

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        col = self._read_columnar(ctx)
        u, i, r = _columnar_to_ratings(
            col, self.params.buy_rating, self.params.rating_map
        )
        return TrainingData(u, i, r, col.entity_vocab, col.target_vocab)

    def read_eval(self, ctx: WorkflowContext):
        """k-fold split by rating index (ref DataSource.scala:81-104)."""
        if self.params.eval_params is None:
            raise ValueError("Must specify evalParams for evaluation")
        ep = self.params.eval_params
        col = self._read_columnar(ctx)
        u, i, r = _columnar_to_ratings(
            col, self.params.buy_rating, self.params.rating_map
        )
        idx = np.arange(len(u))
        folds = []
        for fold in range(ep.k_fold):
            test_mask = idx % ep.k_fold == fold
            td = TrainingData(
                u[~test_mask], i[~test_mask], r[~test_mask],
                col.entity_vocab, col.target_vocab,
            )
            # group test ratings per user -> one query per user
            qa: list[tuple[Query, ActualResult]] = []
            test_u, test_i, test_r = u[test_mask], i[test_mask], r[test_mask]
            for user_id in np.unique(test_u):
                sel = test_u == user_id
                ratings = tuple(
                    Rating(
                        col.entity_vocab[int(user_id)],
                        col.target_vocab[int(ti)],
                        float(tr),
                    )
                    for ti, tr in zip(test_i[sel], test_r[sel])
                )
                qa.append(
                    (
                        Query(col.entity_vocab[int(user_id)], ep.query_num),
                        ActualResult(ratings),
                    )
                )
            folds.append((td, {}, qa))
        return folds


class Preparator(BasePreparator):
    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> TrainingData:
        return td


@dataclasses.dataclass(frozen=True)
class CustomPreparatorParams(Params):
    filepath: str


class CustomPreparator(BasePreparator):
    """customize-data-prep variant (ref ``customize-data-prep/src/main/scala/
    Preparator.scala:29-44``): drop ratings whose item appears in the
    exclusion file (one item id per line)."""

    params_class = CustomPreparatorParams
    params: CustomPreparatorParams

    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> TrainingData:
        with open(self.params.filepath) as fh:
            no_train_items = {line.strip() for line in fh if line.strip()}
        if not no_train_items:
            return td
        excluded = np.asarray(
            [item in no_train_items for item in td.item_vocab], dtype=bool
        )
        # drop the items from the vocab too, not just their ratings:
        # rating-less items would get all-zero factors and could still be
        # served at score 0.0 (MLlib never materialises factors for them)
        new_of_old = np.cumsum(~excluded) - 1
        keep = ~excluded[td.item_idx]
        return TrainingData(
            td.user_idx[keep],
            new_of_old[td.item_idx[keep]].astype(td.item_idx.dtype),
            td.ratings[keep],
            td.user_vocab,
            [it for it, ex in zip(td.item_vocab, excluded) if not ex],
        )


# ---------------------------------------------------------------------------
# Algorithm
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.1
    seed: int | None = 3
    implicit_prefs: bool = False
    alpha: float = 1.0
    # train with the ALX-style mesh-sharded solver (ops/als_sharded.py)
    # across all visible devices; single-device falls back transparently
    distributed: bool = False
    # "f32" | "bf16": gather the fixed factor side in bf16 during the
    # solver's Gram accumulation (halves the gather-bound loop's row bytes;
    # accumulators and solves stay f32 — see ops/als.ALSConfig.gather_dtype)
    gather_dtype: str = "f32"
    # "cg" | "cholesky": per-entity SPD solver. "cg" holds the systems
    # batch-last and, on a TPU, solves a tile of them to the end in VMEM;
    # "cg_fused" is read as "cg" (see ops/als.ALSConfig.solver)
    solver: str = "cg"


@dataclasses.dataclass
class ALSModel(SanityCheck):
    user_factors: np.ndarray  # [n_users, f] host numpy (checkpoint form)
    item_factors: np.ndarray  # [n_items, f]
    user_vocab: list[str]
    item_vocab: list[str]

    def __post_init__(self):
        self._user_index: dict[str, int] | None = None
        self._item_index: dict[str, int] | None = None
        self._serving_index = None

    def sanity_check(self) -> None:
        if not (
            np.all(np.isfinite(self.user_factors))
            and np.all(np.isfinite(self.item_factors))
        ):
            raise ValueError("ALS produced non-finite factors")

    # -- serving-side helpers ------------------------------------------------
    def user_index(self, user: str) -> int | None:
        if self._user_index is None:
            self._user_index = {u: i for i, u in enumerate(self.user_vocab)}
        return self._user_index.get(user)

    def item_index(self, item: str) -> int | None:
        if self._item_index is None:
            self._item_index = {it: i for i, it in enumerate(self.item_vocab)}
        return self._item_index.get(item)

    def serving_index(self):
        """Both factor tables resident on device; index-addressed top-k
        with one upload + one fetch per batch (ops.topk.ServingIndex)."""
        if self._serving_index is None:
            from predictionio_tpu.ops.topk import ServingIndex

            self._serving_index = ServingIndex(self.user_factors, self.item_factors)
        return self._serving_index

    def __getstate__(self):
        return {
            "user_factors": self.user_factors,
            "item_factors": self.item_factors,
            "user_vocab": self.user_vocab,
            "item_vocab": self.item_vocab,
        }

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._user_index = None
        self._item_index = None
        self._serving_index = None


class ALSAlgorithm(JaxAlgorithm):
    params_class = ALSAlgorithmParams
    params: ALSAlgorithmParams

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> ALSModel:
        cfg = ALSConfig(
            rank=self.params.rank,
            iterations=self.params.num_iterations,
            reg=self.params.lambda_,
            implicit=self.params.implicit_prefs,
            alpha=self.params.alpha,
            seed=self.params.seed if self.params.seed is not None else 0,
            gather_dtype=self.params.gather_dtype,
            solver=self.params.solver,
        )
        from predictionio_tpu.obs import xray

        prof = xray.current_profile()
        if prof is not None:
            # capacity planner prediction recorded BEFORE the allocation
            # happens; the profile's live-memory samples are the runtime
            # cross-check (pio doctor --capacity answers this preflight)
            import jax

            prof.set_estimate(
                xray.estimate_factors(
                    len(pd.user_vocab),
                    len(pd.item_vocab),
                    self.params.rank,
                    mesh=jax.device_count() if self.params.distributed else 1,
                    nnz=int(pd.user_idx.shape[0]),
                    gather_dtype=self.params.gather_dtype,
                )
            )
        if self.params.distributed:
            from predictionio_tpu.ops.als_sharded import als_train_sharded

            uf, vf = als_train_sharded(
                pd.user_idx,
                pd.item_idx,
                pd.ratings,
                len(pd.user_vocab),
                len(pd.item_vocab),
                cfg,
            )
        else:
            uf, vf = als_train(
                pd.user_idx,
                pd.item_idx,
                pd.ratings,
                len(pd.user_vocab),
                len(pd.item_vocab),
                cfg,
            )
        return ALSModel(
            np.asarray(uf), np.asarray(vf), pd.user_vocab, pd.item_vocab
        )

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        uidx = model.user_index(query.user)
        if uidx is None:
            return PredictedResult(())  # unknown user -> empty result
        mask = None
        if query.black_list:
            # blacklist-items variant (ref blacklist-items/ALSAlgorithm.scala:
            # 95-111 recommendProductsWithFilter): device-side mask, so
            # excluded items never reach the top-k
            mask = np.ones(len(model.item_vocab), dtype=bool)
            for item in query.black_list:
                iidx = model.item_index(item)
                if iidx is not None:
                    mask[iidx] = False
        # a batch of one at the k bucket the deploy warmed, the first num kept
        scores, idx = model.serving_index().serve(uidx, query.num, mask=mask)
        return PredictedResult(
            tuple(
                ItemScore(model.item_vocab[int(i)], float(s))
                for s, i in zip(scores, idx)
                if np.isfinite(s)
            )
        )

    def warmup_serving(self, model: ALSModel, max_batch: int) -> None:
        """Pre-compile every pow2 batch bucket (bucket 1 is the single
        query's) for the default result size, so the first request burst
        after deploy or /reload pays no XLA compiles."""
        model.serving_index().warmup_buckets(
            min(DEFAULT_QUERY_NUM, len(model.item_vocab)), max_batch
        )

    def predict_batch(
        self, model: ALSModel, queries: Sequence[Query]
    ) -> list[PredictedResult]:
        """Serving micro-batch: all mask-free known-user queries become ONE
        batched top-k kernel ([B] indices -> [B,2,k] packed result); unknown
        users answer empty and blacklist queries (per-query device mask) go
        one by one, as batches of one. This is what lets the query server
        sustain batched-kernel throughput end-to-end instead of one device
        round-trip per request."""
        return self.predict_batch_dispatch(model, queries)()

    def predict_batch_dispatch(
        self, model: ALSModel, queries: Sequence[Query]
    ):
        """Pipelined serving: dispatch the batched top-k kernel now, fetch in
        the returned finalize — the query server overlaps batch n's transport
        with batch n+1's dispatch (ops.topk.ServingIndex.serve_batch_async).
        User indices are assembled into a reusable staging buffer
        (ops.topk.scratch) and only the packed [B,2,k] result is fetched."""
        from predictionio_tpu.obs.jaxprof import annotate
        from predictionio_tpu.ops import topk

        results: list[PredictedResult | None] = [None] * len(queries)
        batch_pos: list[int] = []
        batch_idx: list[int] = []
        masked_pos: list[int] = []
        for i, q in enumerate(queries):
            uidx = model.user_index(q.user)
            if uidx is None:
                results[i] = PredictedResult(())
            elif q.black_list:
                # per-query device mask: a batch of one, deferred to
                # finalize — a blocking predict here would stall the shared
                # dispatch thread for a full device round-trip
                masked_pos.append(i)
            else:
                batch_pos.append(i)
                batch_idx.append(uidx)
        n_items = len(model.item_vocab)
        handle = None
        if batch_pos:
            # bucket B and k to powers of two: every distinct shape compiles
            # its own XLA program, and ragged request arrivals would
            # otherwise trigger a compile storm; buckets cap the universe at
            # ~log2(max_batch) programs, pre-warmed via
            # ServingIndex.warmup_buckets
            k = min(max(queries[i].num for i in batch_pos), n_items)
            kk = min(topk.next_pow2(k), n_items)
            bucket = topk.batch_bucket(len(batch_pos))
            # pad rows serve user 0, dropped on unpack
            idxs = topk.scratch().zeros("rec.uidx", (bucket,), np.int32)
            idxs[: len(batch_pos)] = batch_idx
            handle = model.serving_index().serve_batch_async(idxs, kk)

        def finalize() -> list[PredictedResult]:
            for i in masked_pos:
                results[i] = self.predict(model, queries[i])
            if handle is not None:
                scores, idx = topk.fetch_topk(handle)
                with annotate("pio:fetch.unpack"):
                    for row, i in enumerate(batch_pos):
                        num = min(queries[i].num, n_items)
                        results[i] = PredictedResult(
                            tuple(
                                ItemScore(model.item_vocab[int(it)], float(s))
                                for s, it in zip(scores[row, :num], idx[row, :num])
                                if np.isfinite(s)
                            )
                        )
            return results  # type: ignore[return-value]

        return finalize


class Serving(BaseServing):
    def serve(self, query: Query, predictions: Sequence[PredictedResult]) -> PredictedResult:
        return predictions[0]


@dataclasses.dataclass(frozen=True)
class ServingParams(Params):
    filepath: str


class FilterServing(BaseServing):
    """customize-serving variant (ref ``customize-serving/src/main/scala/
    Serving.scala:26-43``): re-read the disabled-items file on every request
    (ops can edit it live, no redeploy) and drop those items from the
    first algorithm's result."""

    params_class = ServingParams
    params: ServingParams

    def serve(
        self, query: Query, predictions: Sequence[PredictedResult]
    ) -> PredictedResult:
        with open(self.params.filepath) as fh:
            disabled = {line.strip() for line in fh if line.strip()}
        return PredictedResult(
            tuple(
                s for s in predictions[0].item_scores if s.item not in disabled
            )
        )


def engine_factory() -> Engine:
    return Engine(
        DataSource,
        {"": Preparator, "custom": CustomPreparator},
        {"als": ALSAlgorithm},
        {"": Serving, "filter": FilterServing},
        query_class=Query,
    )
