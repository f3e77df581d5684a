"""Similar-product engine (DASE components).

Reference parity (behavioral):
  - Query {items, num, categories?, categoryBlackList?, whiteList?,
    blackList?} -> PredictedResult {itemScores} —
    ``multi-events-multi-algos/src/main/scala/Engine.scala:23-41``.
  - DataSource reads user/item entities (item ``categories`` property) and
    view + like events — ``DataSource.scala``.
  - ALSAlgorithm: implicit ALS on view counts; predict scores every item by
    cosine similarity to each query item's factor, summed —
    ``ALSAlgorithm.scala:136-230``.
  - LikeAlgorithm: same scoring on like events — ``LikeAlgorithm.scala``.
  - CooccurrenceAlgorithm: top-N ordered-pair counts —
    ``CooccurrenceAlgorithm.scala:30-90``.
  - isCandidateItem filters: whitelist, blacklist, query-item exclusion,
    category overlap, category blacklist — ``ALSAlgorithm.scala:236-260``.

TPU design: cosine scoring, candidate masking and selection are ONE fused
jitted program (ops/topk.gather_sum_top_k_async) over the resident
normalized item-factor table; a micro-batch of queries is one device call
and only the (k scores, k indices) pairs ever cross the wire.
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
    LocalAlgorithm,
    Params,
    SanityCheck,
)
from predictionio_tpu.obs.jaxprof import annotate
from predictionio_tpu.ops import topk
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.ops.cooccurrence import cooccurrence_top_n, score_by_cooccurrence
from predictionio_tpu.workflow.context import WorkflowContext


@dataclasses.dataclass(frozen=True)
class Query:
    items: tuple[str, ...]
    num: int = 10
    categories: frozenset[str] | None = None
    category_black_list: frozenset[str] | None = None
    white_list: frozenset[str] | None = None
    black_list: frozenset[str] | None = None

    @staticmethod
    def from_json_dict(d: dict[str, Any]) -> "Query":
        def fset(key):
            v = d.get(key)
            return frozenset(v) if v is not None else None

        return Query(
            items=tuple(d["items"]),
            num=int(d.get("num", 10)),
            categories=fset("categories"),
            category_black_list=fset("categoryBlackList"),
            white_list=fset("whiteList"),
            black_list=fset("blackList"),
        )


@dataclasses.dataclass(frozen=True)
class ItemScore:
    """``properties`` carries returned item attributes for the
    return-item-properties variant (ref ``return-item-properties/src/main/
    scala/Engine.scala:38-45`` adds title/date/imdbUrl fields); they are
    flattened into the wire dict exactly like the reference's named fields."""

    item: str
    score: float
    properties: dict[str, Any] | None = None

    def to_json_dict(self) -> dict[str, Any]:
        out: dict[str, Any] = dict(self.properties or {})
        out["item"] = self.item
        out["score"] = self.score
        return out


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...]

    def to_json_dict(self) -> dict[str, Any]:
        return {"itemScores": [s.to_json_dict() for s in self.item_scores]}


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    """``item_property_names`` enables return-item-properties
    (ref ``return-item-properties/DataSource.scala:60-75``: collect
    title/date/imdbUrl per item); ``rate_event`` adds a rated-interaction
    table for train-with-rate-event (ref ``train-with-rate-event/
    DataSource.scala``: rate events with a rating property, latest rating
    per (user,item) wins)."""

    app_name: str = ""
    item_property_names: tuple[str, ...] = ()
    rate_event: str | None = None


@dataclasses.dataclass
class TrainingData(SanityCheck):
    user_vocab: list[str]
    item_vocab: list[str]
    item_categories: list[frozenset[str] | None]  # aligned with item_vocab
    view_user_idx: np.ndarray
    view_item_idx: np.ndarray
    like_user_idx: np.ndarray
    like_item_idx: np.ndarray
    # return-item-properties: per-item property dicts aligned with item_vocab
    item_properties: list[dict[str, Any] | None] | None = None
    # train-with-rate-event: latest rating per (user, item)
    rate_user_idx: np.ndarray | None = None
    rate_item_idx: np.ndarray | None = None
    rate_values: np.ndarray | None = None

    def sanity_check(self) -> None:
        n_rates = 0 if self.rate_user_idx is None else len(self.rate_user_idx)
        if len(self.view_user_idx) == 0 and len(self.like_user_idx) == 0 and n_rates == 0:
            raise ValueError("no view/like/rate events found; check app data")


class DataSource(BaseDataSource):
    params_class = DataSourceParams
    params: DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        store = ctx.p_event_store()
        app_name = self.params.app_name or ctx.app_name
        event_names = ["view", "like"]
        if self.params.rate_event:
            event_names.append(self.params.rate_event)
        col = store.to_columnar_cached(
            app_name=app_name,
            channel_name=ctx.channel_name,
            event_names=event_names,
            entity_type="user",
            target_entity_type="item",
            rating_key="rating",
        )
        item_vocab = list(col.target_vocab)
        item_index = {v: i for i, v in enumerate(item_vocab)}
        # item categories (+ optional returned properties) from $set
        # properties of item entities
        item_props = store.aggregate_properties(
            app_name=app_name, entity_type="item", channel_name=ctx.channel_name
        )
        categories: list[frozenset[str] | None] = [None] * len(item_vocab)
        wanted = self.params.item_property_names
        properties: list[dict[str, Any] | None] | None = (
            [None] * len(item_vocab) if wanted else None
        )
        for entity_id, pm in item_props.items():
            idx = item_index.get(entity_id)
            if idx is None:
                item_index[entity_id] = len(item_vocab)
                item_vocab.append(entity_id)
                categories.append(None)
                if properties is not None:
                    properties.append(None)
                idx = item_index[entity_id]
            cats = pm.get_opt("categories")
            if cats is not None:
                categories[idx] = frozenset(cats)
            if properties is not None:
                properties[idx] = {
                    name: pm.get_opt(name)
                    for name in wanted
                    if pm.get_opt(name) is not None
                }
        views = np.asarray([n == "view" for n in col.event_names], bool)
        likes = np.asarray([n == "like" for n in col.event_names], bool)
        valid = (col.entity_ids >= 0) & (col.target_ids >= 0)
        rate_u = rate_i = rate_v = None
        if self.params.rate_event:
            rates = np.asarray(
                [n == self.params.rate_event for n in col.event_names], bool
            )
            sel = rates & valid & np.isfinite(col.ratings)
            # latest rating per (user, item) wins (ref train-with-rate-event/
            # ALSAlgorithm.scala:101-117 reduceByKey on timestamp)
            order = np.argsort(col.timestamps[sel], kind="stable")
            u, i, v = (
                col.entity_ids[sel][order],
                col.target_ids[sel][order],
                col.ratings[sel][order],
            )
            pairs = np.stack([u, i], 1)
            # np.unique keeps the FIRST occurrence; reverse so first == latest
            _, first = np.unique(pairs[::-1], axis=0, return_index=True)
            keep = len(u) - 1 - first
            rate_u, rate_i, rate_v = u[keep], i[keep], v[keep].astype(np.float32)
        return TrainingData(
            user_vocab=col.entity_vocab,
            item_vocab=item_vocab,
            item_categories=categories,
            view_user_idx=col.entity_ids[views & valid],
            view_item_idx=col.target_ids[views & valid],
            like_user_idx=col.entity_ids[likes & valid],
            like_item_idx=col.target_ids[likes & valid],
            item_properties=properties,
            rate_user_idx=rate_u,
            rate_item_idx=rate_i,
            rate_values=rate_v,
        )


class Preparator(BasePreparator):
    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> TrainingData:
        return td


# ---------------------------------------------------------------------------
# Shared model + filtering
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SimilarModel(SanityCheck):
    item_factors: np.ndarray  # [n_items, f], L2-normalized rows
    item_vocab: list[str]
    item_categories: list[frozenset[str] | None]
    item_properties: list[dict[str, Any] | None] | None = None

    def __post_init__(self):
        self._index: dict[str, int] | None = None
        self._device_factors = None

    def properties_of(self, i: int) -> dict[str, Any] | None:
        if self.item_properties is None:
            return None
        return self.item_properties[i]

    def sanity_check(self) -> None:
        if not np.all(np.isfinite(self.item_factors)):
            raise ValueError("non-finite item factors")

    def item_index(self, item: str) -> int | None:
        if self._index is None:
            self._index = {v: i for i, v in enumerate(self.item_vocab)}
        return self._index.get(item)

    def device_factors(self):
        if self._device_factors is None:
            import jax.numpy as jnp

            self._device_factors = jnp.asarray(self.item_factors)
        return self._device_factors

    def __getstate__(self):
        return {
            "item_factors": self.item_factors,
            "item_vocab": self.item_vocab,
            "item_categories": self.item_categories,
            "item_properties": self.item_properties,
        }

    def __setstate__(self, state):
        state.setdefault("item_properties", None)
        self.__dict__.update(state)
        self._index = None
        self._device_factors = None


def candidate_mask(
    model: SimilarModel,
    query: Query,
    query_idx: list[int],
    out: np.ndarray | None = None,
) -> np.ndarray:
    """ref isCandidateItem (ALSAlgorithm.scala:236-260). ``out`` writes the
    mask into a preallocated row (the batch path assembles query masks
    directly into its reusable [B, n] staging buffer)."""
    n = len(model.item_vocab)
    if out is None:
        mask = np.ones(n, bool)
    else:
        mask = out
        mask[...] = True
    mask[query_idx] = False  # exclude query items
    if query.white_list is not None:
        wl = np.zeros(n, bool)
        for it in query.white_list:
            idx = model.item_index(it)
            if idx is not None:
                wl[idx] = True
        mask &= wl
    if query.black_list is not None:
        for it in query.black_list:
            idx = model.item_index(it)
            if idx is not None:
                mask[idx] = False
    if query.categories is not None:
        for i in range(n):
            cats = model.item_categories[i]
            # items without categories are discarded when filtering by category
            if cats is None or not (cats & query.categories):
                mask[i] = False
    if query.category_black_list is not None:
        for i in range(n):
            cats = model.item_categories[i]
            if cats is not None and (cats & query.category_black_list):
                mask[i] = False
    return mask


# ---------------------------------------------------------------------------
# Algorithms
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int | None = 3
    # "cg" | "cholesky"; "cg_fused" is read as "cg" (see ops/als.ALSConfig.solver)
    solver: str = "cg"


class _ALSBase(JaxAlgorithm):
    params_class = ALSAlgorithmParams
    params: ALSAlgorithmParams

    event_kind = "view"

    def _interactions(self, pd: TrainingData) -> tuple[np.ndarray, np.ndarray]:
        if self.event_kind == "view":
            return pd.view_user_idx, pd.view_item_idx
        return pd.like_user_idx, pd.like_item_idx

    @staticmethod
    def _build_model(item_factors, pd: TrainingData) -> SimilarModel:
        """L2-normalise for cosine scoring and package with vocab/metadata."""
        vf = np.asarray(item_factors)
        norms = np.linalg.norm(vf, axis=1, keepdims=True)
        vf = vf / np.where(norms == 0, 1.0, norms)
        return SimilarModel(
            vf,
            list(pd.item_vocab),
            list(pd.item_categories),
            pd.item_properties,
        )

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> SimilarModel:
        users, items = self._interactions(pd)
        if len(users) == 0:
            raise ValueError(f"no {self.event_kind} events to train on")
        # count interactions as implicit ratings (ref trainImplicit on counts)
        pair, counts = np.unique(
            np.stack([users, items], 1), axis=0, return_counts=True
        )
        cfg = ALSConfig(
            rank=self.params.rank,
            iterations=self.params.num_iterations,
            reg=self.params.lambda_,
            implicit=True,
            alpha=self.params.alpha,
            seed=self.params.seed if self.params.seed is not None else 0,
            solver=self.params.solver,
        )
        _, item_factors = als_train(
            pair[:, 0],
            pair[:, 1],
            counts.astype(np.float32),
            len(pd.user_vocab),
            len(pd.item_vocab),
            cfg,
        )
        return self._build_model(item_factors, pd)

    def predict(self, model: SimilarModel, query: Query) -> PredictedResult:
        return self.predict_batch(model, [query])[0]

    def predict_batch(
        self, model: SimilarModel, queries: Sequence[Query]
    ) -> list[PredictedResult]:
        return self.predict_batch_dispatch(model, queries)()

    @staticmethod
    def _has_filters(q: Query) -> bool:
        return (
            q.categories is not None
            or q.category_black_list is not None
            or q.white_list is not None
            or q.black_list is not None
        )

    def predict_batch_dispatch(self, model: SimilarModel, queries: Sequence[Query]):
        """One fused device call for the whole micro-batch: query-item
        indices and per-query candidate masks are assembled directly into
        reusable staging buffers, the gather->sum-cosine->mask->top-k runs
        as one jitted program, and only [B, k] score/index pairs are
        fetched (in the returned finalize, so the query server overlaps
        transport with the next batch's dispatch).

        With an ANN index pinned (docs/ann.md), scoring routes through
        the clustered search instead: the summed query vector (sum of
        cosines == dot with the summed factor vector) probes nprobe
        buckets, so the corpus-wide matmul disappears. Filter-less
        batches exclude the query's own items inside the kernel by id;
        filtered batches hand their candidate mask to the masked search
        variant. Exact stays the fallback and the sampled recall shadow."""
        from predictionio_tpu.ann.lifecycle import ATTR as _ANN_ATTR

        n = len(model.item_vocab)
        results: list[PredictedResult | None] = [None] * len(queries)
        rows: list[int] = []
        row_qidx: list[list[int]] = []
        max_q = 1
        max_num = 1
        filtered = False
        for i, q in enumerate(queries):
            qidx = [
                j for it in q.items if (j := model.item_index(it)) is not None
            ]
            if not qidx or q.num <= 0:
                results[i] = PredictedResult(())
                continue
            rows.append(i)
            row_qidx.append(qidx)
            max_q = max(max_q, len(qidx))
            max_num = max(max_num, q.num)
            filtered = filtered or self._has_filters(q)
        handle = None
        ann = None
        exact_handle = None
        kk = 0
        if rows:
            # pow2 buckets on batch/query-width/k keep the compile universe
            # at ~log^3 programs (same discipline as ServingIndex.warmup_buckets)
            b = topk.batch_bucket(len(rows))
            qcap = topk.next_pow2(max_q)
            pool = topk.scratch()
            qidx_buf = pool.zeros("similar.qidx", (b, qcap), np.int32)
            qw_buf = pool.zeros("similar.qw", (b, qcap), np.float32)
            for row, qidx in enumerate(row_qidx):
                qidx_buf[row, : len(qidx)] = qidx
                qw_buf[row, : len(qidx)] = 1.0
            kk = min(topk.next_pow2(max_num), n)
            ann = getattr(model, _ANN_ATTR, None)
            if ann is not None and not ann.supports(kk, filtered=filtered):
                ann.count_fallback(len(rows))
                ann = None
            mask_buf = None
            sample = ann is not None and ann.take_recall_sample()
            if ann is None or filtered or sample:
                # the exact kernels (and the masked ANN variant) consume
                # the full candidate mask; the filter-less pure-ANN path
                # skips this O(B*n) host assembly entirely
                mask_buf = pool.get("similar.mask", (b, n), np.bool_)
                mask_buf[len(rows):] = True  # pad rows: harmless full mask
                for row, (i, qidx) in enumerate(zip(rows, row_qidx)):
                    candidate_mask(model, queries[i], qidx, out=mask_buf[row])
            if ann is not None:
                qvec_buf = pool.zeros(
                    "similar.qvec", (b, model.item_factors.shape[1]), np.float32
                )
                for row, qidx in enumerate(row_qidx):
                    # sum of per-item cosines == one dot with the summed
                    # normalized factors — the IVF probe sees one vector
                    np.sum(model.item_factors[qidx], axis=0, out=qvec_buf[row])
                if filtered:
                    handle = ann.search_async(qvec_buf, kk, mask=mask_buf)
                else:
                    excl_buf = pool.full(
                        "similar.excl", (b, qcap), np.int32, -1
                    )
                    for row, qidx in enumerate(row_qidx):
                        excl_buf[row, : len(qidx)] = qidx
                    handle = ann.search_async(qvec_buf, kk, exclude=excl_buf)
                if sample:
                    exact_handle = topk.gather_sum_top_k_async(
                        model.device_factors(), qidx_buf, qw_buf, mask_buf, kk
                    )
            else:
                handle = topk.gather_sum_top_k_async(
                    model.device_factors(), qidx_buf, qw_buf, mask_buf, kk
                )

        def finalize() -> list[PredictedResult]:
            if handle is not None:
                if ann is not None:
                    scores, idx = ann.fetch(handle, rows=len(rows))
                    if exact_handle is not None:
                        _, exact_idx = topk.fetch_topk(exact_handle)
                        ann.record_recall(idx, exact_idx, rows=len(rows))
                else:
                    scores, idx = topk.fetch_topk(handle)
                with annotate("pio:fetch.unpack"):
                    for row, i in enumerate(rows):
                        num = min(queries[i].num, kk)
                        results[i] = PredictedResult(
                            tuple(
                                ItemScore(
                                    model.item_vocab[int(it)],
                                    float(s),
                                    model.properties_of(int(it)),
                                )
                                for s, it in zip(scores[row, :num], idx[row, :num])
                                if np.isfinite(s)
                            )
                        )
            return results  # type: ignore[return-value]

        return finalize

    def warmup_serving(self, model: SimilarModel, max_batch: int) -> None:
        """Pre-compile the single-item-query program for every pow2 batch
        bucket at the default k, so the first burst after deploy/reload
        pays no XLA compiles on the common shape. The exact program warms
        even with an ANN index pinned (it stays the recall shadow and the
        fallback); the index's own buckets warm via AnnServing.warmup."""
        from predictionio_tpu.ann.lifecycle import ATTR as _ANN_ATTR

        n = len(model.item_vocab)
        kk = min(topk.next_pow2(10), n)
        topk.warmup_pow2_buckets(
            max_batch,
            lambda b: topk.gather_sum_top_k_async(
                model.device_factors(),
                np.zeros((b, 1), np.int32),
                np.zeros((b, 1), np.float32),
                np.ones((b, n), bool),
                kk,
            ),
        )
        ann = getattr(model, _ANN_ATTR, None)
        if ann is not None and ann.supports(kk):
            # the filter-less dispatch shape (id exclusion) is the hot one
            topk.warmup_pow2_buckets(
                max_batch,
                lambda b: ann.search_async(
                    np.zeros((b, model.item_factors.shape[1]), np.float32),
                    kk,
                    exclude=np.full((b, 1), -1, np.int32),
                )[0],
            )


class ALSAlgorithm(_ALSBase):
    event_kind = "view"


class RateALSAlgorithm(_ALSBase):
    """train-with-rate-event variant (ref ``train-with-rate-event/
    ALSAlgorithm.scala:66-129``): explicit ALS on the latest rating per
    (user, item) instead of implicit ALS on view counts."""

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> SimilarModel:
        if pd.rate_user_idx is None or len(pd.rate_user_idx) == 0:
            raise ValueError(
                "no rate events to train on; set DataSourceParams.rate_event"
            )
        cfg = ALSConfig(
            rank=self.params.rank,
            iterations=self.params.num_iterations,
            reg=self.params.lambda_,
            implicit=False,
            seed=self.params.seed if self.params.seed is not None else 0,
            solver=self.params.solver,
        )
        _, item_factors = als_train(
            pd.rate_user_idx,
            pd.rate_item_idx,
            pd.rate_values,
            len(pd.user_vocab),
            len(pd.item_vocab),
            cfg,
        )
        return self._build_model(item_factors, pd)


class LikeAlgorithm(_ALSBase):
    """ref LikeAlgorithm.scala — same scoring trained on like events."""

    event_kind = "like"


@dataclasses.dataclass(frozen=True)
class CooccurrenceParams(Params):
    n: int = 20  # top-N cooccurring items kept per item


@dataclasses.dataclass
class CooccurrenceModel:
    top_map: dict[int, list[tuple[int, int]]]
    item_vocab: list[str]
    item_categories: list[frozenset[str] | None]
    item_properties: list[dict[str, Any] | None] | None = None

    def __post_init__(self):
        self._index = {v: i for i, v in enumerate(self.item_vocab)}

    def item_index(self, item: str) -> int | None:
        return self._index.get(item)

    def properties_of(self, i: int) -> dict[str, Any] | None:
        if self.item_properties is None:
            return None
        return self.item_properties[i]

    def __getstate__(self):
        return {
            "top_map": self.top_map,
            "item_vocab": self.item_vocab,
            "item_categories": self.item_categories,
            "item_properties": self.item_properties,
        }

    def __setstate__(self, state):
        state.setdefault("item_properties", None)
        self.__dict__.update(state)
        self._index = {v: i for i, v in enumerate(self.item_vocab)}


class CooccurrenceAlgorithm(LocalAlgorithm):
    params_class = CooccurrenceParams
    params: CooccurrenceParams

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> CooccurrenceModel:
        top_map = cooccurrence_top_n(
            pd.view_user_idx, pd.view_item_idx, len(pd.item_vocab), self.params.n
        )
        return CooccurrenceModel(
            top_map,
            list(pd.item_vocab),
            list(pd.item_categories),
            pd.item_properties,
        )

    def predict(self, model: CooccurrenceModel, query: Query) -> PredictedResult:
        query_idx = [
            i for it in query.items if (i := model.item_index(it)) is not None
        ]
        score_map = score_by_cooccurrence(model.top_map, query_idx)
        shim = SimilarModel(
            np.zeros((len(model.item_vocab), 1), np.float32),
            model.item_vocab,
            model.item_categories,
        )
        mask = candidate_mask(shim, query, query_idx)
        scores = np.full(len(model.item_vocab), -np.inf)
        for i, s in score_map.items():
            scores[i] = s
        # cooccurrence scores are host-born (a sparse count map) — the
        # sanctioned host ending lives in the fused-top-k helper
        sk, si = topk.host_top_k(scores, mask, query.num)
        return PredictedResult(
            tuple(
                ItemScore(model.item_vocab[int(i)], float(s), model.properties_of(int(i)))
                for s, i in zip(sk, si)
            )
        )


class Serving(BaseServing):
    def serve(self, query: Query, predictions: Sequence[PredictedResult]) -> PredictedResult:
        return predictions[0]


def engine_factory() -> Engine:
    return Engine(
        DataSource,
        Preparator,
        {
            "als": ALSAlgorithm,
            "cooccurrence": CooccurrenceAlgorithm,
            "likealgo": LikeAlgorithm,
            "rateals": RateALSAlgorithm,
        },
        Serving,
        query_class=Query,
    )
