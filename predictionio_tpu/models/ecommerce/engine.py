"""E-commerce recommendation engine (DASE components).

Reference parity (behavioral), all from
``train-with-rate-event/src/main/scala/``:
  - Query {user, num, categories?, whiteList?, blackList?} ->
    PredictedResult {itemScores} — ``Engine.scala:23-38``.
  - ECommAlgorithmParams {appName, unseenOnly, seenEvents, similarEvents,
    rank, numIterations, lambda, seed} — ``ECommAlgorithm.scala:38-47``.
  - Train: implicit ALS on rate events (weighted by rating), popularity
    counts from buy events for the cold fallback — ``ECommAlgorithm.scala:
    76-158, 211-240``.
  - Predict (``:243-330``): known user -> dot(userFactor, itemFactors);
    unknown/cold user -> summed similarity of items to the user's recent
    ``similarEvents`` (live LEventStore lookup, last 10), falling back to
    popularity counts when no recent items; ``unseenOnly`` excludes items
    from the user's live ``seenEvents``; the ``unavailableItems`` constraint
    entity ($set on entityType "constraint") is re-read per query.

TPU design: factor tables live on device; scoring, business-rule masking
and selection run as ONE fused jitted program (ops/topk) with only the
(k scores, k indices) pairs fetched; the live lookups stay host-side
(row-store reads) and a micro-batch of known-user queries is a single
batched device call.
"""

from __future__ import annotations

import dataclasses
import logging
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
from predictionio_tpu.obs.jaxprof import annotate
from predictionio_tpu.ops import topk
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.workflow.context import WorkflowContext

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class Query:
    user: str
    num: int = 10
    categories: frozenset[str] | None = None
    white_list: frozenset[str] | None = None
    black_list: frozenset[str] | None = None

    @staticmethod
    def from_json_dict(d: dict[str, Any]) -> "Query":
        def fset(key):
            v = d.get(key)
            return frozenset(v) if v is not None else None

        return Query(
            user=str(d["user"]),
            num=int(d.get("num", 10)),
            categories=fset("categories"),
            white_list=fset("whiteList"),
            black_list=fset("blackList"),
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
            "itemScores": [{"item": s.item, "score": s.score} for s in self.item_scores]
        }


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""


@dataclasses.dataclass
class TrainingData(SanityCheck):
    user_vocab: list[str]
    item_vocab: list[str]
    item_categories: list[frozenset[str] | None]
    rate_user_idx: np.ndarray
    rate_item_idx: np.ndarray
    rate_values: np.ndarray
    buy_user_idx: np.ndarray
    buy_item_idx: np.ndarray

    def sanity_check(self) -> None:
        if len(self.rate_user_idx) == 0:
            raise ValueError("no rate events found; check app data")


class DataSource(BaseDataSource):
    params_class = DataSourceParams
    params: DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        store = ctx.p_event_store()
        app_name = self.params.app_name or ctx.app_name
        col = store.to_columnar_cached(
            app_name=app_name,
            channel_name=ctx.channel_name,
            event_names=["rate", "buy"],
            entity_type="user",
            target_entity_type="item",
            rating_key="rating",
        )
        item_vocab = list(col.target_vocab)
        item_index = {v: i for i, v in enumerate(item_vocab)}
        item_props = store.aggregate_properties(
            app_name=app_name, entity_type="item", channel_name=ctx.channel_name
        )
        categories: list[frozenset[str] | None] = [None] * len(item_vocab)
        for entity_id, pm in item_props.items():
            idx = item_index.get(entity_id)
            if idx is None:
                continue
            cats = pm.get_opt("categories")
            if cats is not None:
                categories[idx] = frozenset(cats)
        rates = np.asarray([n == "rate" for n in col.event_names], bool)
        buys = np.asarray([n == "buy" for n in col.event_names], bool)
        valid = (col.entity_ids >= 0) & (col.target_ids >= 0)
        rate_mask = rates & valid & np.isfinite(col.ratings)
        buy_mask = buys & valid
        return TrainingData(
            user_vocab=col.entity_vocab,
            item_vocab=item_vocab,
            item_categories=categories,
            rate_user_idx=col.entity_ids[rate_mask],
            rate_item_idx=col.target_ids[rate_mask],
            rate_values=col.ratings[rate_mask],
            buy_user_idx=col.entity_ids[buy_mask],
            buy_item_idx=col.target_ids[buy_mask],
        )


class Preparator(BasePreparator):
    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> TrainingData:
        return td


@dataclasses.dataclass(frozen=True)
class ECommAlgorithmParams(Params):
    app_name: str = ""
    unseen_only: bool = False
    seen_events: tuple[str, ...] = ("buy", "view")
    similar_events: tuple[str, ...] = ("view",)
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int | None = 3
    # "cg" | "cholesky"; "cg_fused" is read as "cg" (see ops/als.ALSConfig.solver)
    solver: str = "cg"
    # adjust-score variant: enable the per-request weightedItems constraint
    # lookup (off by default — it costs one event-store query per predict)
    adjust_score: bool = False
    # TTL for serving-time storage lookups (seen/recent items per user,
    # unavailable-items + weightedItems constraints). The DEFAULT is 0 =
    # always-live per-query reads, matching the reference's semantics
    # (ECommAlgorithm.scala:252-300): a `$set` of unavailableItems or a new
    # seen/buy event affects the very next prediction. Operators opt into a
    # positive TTL (e.g. 5.0) to trade freshness (lag bounded by the TTL)
    # for a p50 with ZERO storage round trips once the cache is warm.
    cache_ttl_s: float = 0.0


@dataclasses.dataclass
class ECommModel(SanityCheck):
    user_factors: np.ndarray  # [n_users, f]
    item_factors: np.ndarray  # [n_items, f]
    popular_counts: np.ndarray  # [n_items] buy counts
    user_vocab: list[str]
    item_vocab: list[str]
    item_categories: list[frozenset[str] | None]

    def __post_init__(self):
        import uuid

        self._user_index: dict[str, int] | None = None
        self._item_index: dict[str, int] | None = None
        self._device_items = None
        # identity token for serving-side caches: values derived FROM this
        # model (index arrays, weight vectors) must never be served to a
        # different (e.g. hot-swapped) model
        self._cache_token = uuid.uuid4().hex

    def sanity_check(self) -> None:
        if not (
            np.all(np.isfinite(self.user_factors))
            and np.all(np.isfinite(self.item_factors))
        ):
            raise ValueError("non-finite ALS factors")

    def user_index(self, user: str) -> int | None:
        if self._user_index is None:
            self._user_index = {u: i for i, u in enumerate(self.user_vocab)}
        return self._user_index.get(user)

    def item_index(self, item: str) -> int | None:
        if self._item_index is None:
            self._item_index = {v: i for i, v in enumerate(self.item_vocab)}
        return self._item_index.get(item)

    def device_items(self):
        if self._device_items is None:
            import jax.numpy as jnp

            self._device_items = jnp.asarray(self.item_factors)
        return self._device_items

    def __getstate__(self):
        return {
            "user_factors": self.user_factors,
            "item_factors": self.item_factors,
            "popular_counts": self.popular_counts,
            "user_vocab": self.user_vocab,
            "item_vocab": self.item_vocab,
            "item_categories": self.item_categories,
        }

    def __setstate__(self, state):
        import uuid

        self.__dict__.update(state)
        self._user_index = None
        self._item_index = None
        self._device_items = None
        self._cache_token = uuid.uuid4().hex


class ECommAlgorithm(JaxAlgorithm):
    params_class = ECommAlgorithmParams
    params: ECommAlgorithmParams

    @property
    def _lookup_cache(self):
        """Lazy per-instance TTL cache for the serving-time storage reads
        (one shared cache: keys are namespaced tuples)."""
        cache = getattr(self, "_lookup_cache_obj", None)
        if cache is None:
            from predictionio_tpu.utils.ttl_cache import TTLCache

            cache = TTLCache(ttl_s=self.params.cache_ttl_s)
            self._lookup_cache_obj = cache
        return cache

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> ECommModel:
        cfg = ALSConfig(
            rank=self.params.rank,
            iterations=self.params.num_iterations,
            reg=self.params.lambda_,
            implicit=True,
            alpha=self.params.alpha,
            seed=self.params.seed if self.params.seed is not None else 0,
            solver=self.params.solver,
        )
        uf, vf = als_train(
            pd.rate_user_idx,
            pd.rate_item_idx,
            pd.rate_values,
            len(pd.user_vocab),
            len(pd.item_vocab),
            cfg,
        )
        popular = np.bincount(
            pd.buy_item_idx, minlength=len(pd.item_vocab)
        ).astype(np.float32)
        return ECommModel(
            np.asarray(uf),
            np.asarray(vf),
            popular,
            list(pd.user_vocab),
            list(pd.item_vocab),
            list(pd.item_categories),
        )

    # -- live lookups (ref ECommAlgorithm.scala:252-300), TTL-cached so the
    # steady-state predict path does zero storage round trips ---------------
    # NOTE failure handling: the _live loaders RAISE on storage errors and
    # the degraded fallback is applied OUTSIDE get_or_load — caching the
    # fallback would silently disable a business filter for a whole TTL
    # window after one storage blip; this way only successful reads cache
    # and the next query retries the store.
    def _seen_items(self, ctx: WorkflowContext, user: str) -> set[str]:
        try:
            return self._lookup_cache.get_or_load(
                ("seen", user), lambda: self._seen_items_live(ctx, user)
            )
        except Exception:
            logger.exception("seen-items lookup failed; serving without filter")
            return set()

    def _seen_items_live(self, ctx: WorkflowContext, user: str) -> set[str]:
        events = ctx.l_event_store().find_by_entity(
            app_name=self.params.app_name or ctx.app_name,
            entity_type="user",
            entity_id=user,
            event_names=list(self.params.seen_events),
            limit=None,
        )
        return {e.target_entity_id for e in events if e.target_entity_id is not None}

    def _unavailable_items(self, ctx: WorkflowContext) -> set[str]:
        try:
            return self._lookup_cache.get_or_load(
                ("unavailable",), lambda: self._unavailable_items_live(ctx)
            )
        except Exception:
            logger.exception("unavailable-items lookup failed; assuming none")
            return set()

    def _unavailable_items_live(self, ctx: WorkflowContext) -> set[str]:
        """$set events on (constraint, unavailableItems), latest wins
        (ref :268-284)."""
        events = list(
            ctx.l_event_store().find_by_entity(
                app_name=self.params.app_name or ctx.app_name,
                entity_type="constraint",
                entity_id="unavailableItems",
                event_names=["$set"],
                limit=1,
            )
        )
        if events:
            return set(events[0].properties.get_or_else("items", []))
        return set()

    def _item_weights(self, ctx: WorkflowContext, model: ECommModel) -> np.ndarray | None:
        try:
            # keyed by model identity: the weight vector is sized/indexed
            # against THIS model's item vocab
            return self._lookup_cache.get_or_load(
                ("weights", model._cache_token),
                lambda: self._item_weights_live(ctx, model),
            )
        except Exception:
            logger.exception("weightedItems lookup failed; weights ignored")
            return None

    def _item_weights_live(self, ctx: WorkflowContext, model: ECommModel) -> np.ndarray | None:
        """adjust-score variant (ref adjust-score/ECommAlgorithm.scala:56-58,
        256-263,400-430): latest $set on (constraint, weightedItems) carries
        ``weights``: [{"items": [...], "weight": w}]; scores of listed items
        are multiplied by w, everything else by 1.0. Returns None when no
        constraint is set so the multiply can be skipped entirely."""
        events = list(
            ctx.l_event_store().find_by_entity(
                app_name=self.params.app_name or ctx.app_name,
                entity_type="constraint",
                entity_id="weightedItems",
                event_names=["$set"],
                limit=1,
            )
        )
        if not events:
            return None
        groups = events[0].properties.get_or_else("weights", [])
        if not groups:
            return None
        weights = np.ones(len(model.item_vocab), np.float64)
        for group in groups:
            w = float(group.get("weight", 1.0))
            for it in group.get("items", []):
                idx = model.item_index(str(it))
                if idx is not None:
                    weights[idx] = w
        return weights

    def _recent_item_indices(self, ctx: WorkflowContext, model: ECommModel, user: str) -> list[int]:
        try:
            # keyed by model identity: returns indices INTO this model's
            # item table
            return self._lookup_cache.get_or_load(
                ("recent", model._cache_token, user),
                lambda: self._recent_item_indices_live(ctx, model, user),
            )
        except Exception:
            logger.exception("recent-items lookup failed")
            return []

    def _recent_item_indices_live(self, ctx: WorkflowContext, model: ECommModel, user: str) -> list[int]:
        """Last 10 similar-event items (ref :302-320)."""
        events = ctx.l_event_store().find_by_entity(
            app_name=self.params.app_name or ctx.app_name,
            entity_type="user",
            entity_id=user,
            event_names=list(self.params.similar_events),
            limit=10,
        )
        out = []
        for e in events:
            if e.target_entity_id is not None:
                idx = model.item_index(e.target_entity_id)
                if idx is not None:
                    out.append(idx)
        return out

    def _candidate_mask(
        self,
        ctx: WorkflowContext,
        model: ECommModel,
        query: Query,
        out: np.ndarray,
    ) -> None:
        """Business-rule + query filters written into a preallocated [n]
        mask row (seen items, unavailable constraint, white/black lists,
        category overlap — ref ECommAlgorithm.scala:243-330)."""
        n = len(model.item_vocab)
        out[...] = True
        if self.params.unseen_only:
            for it in self._seen_items(ctx, query.user):
                idx = model.item_index(it)
                if idx is not None:
                    out[idx] = False
        for it in self._unavailable_items(ctx):
            idx = model.item_index(it)
            if idx is not None:
                out[idx] = False
        if query.white_list is not None:
            wl = np.zeros(n, bool)
            for it in query.white_list:
                idx = model.item_index(it)
                if idx is not None:
                    wl[idx] = True
            out &= wl
        if query.black_list is not None:
            for it in query.black_list:
                idx = model.item_index(it)
                if idx is not None:
                    out[idx] = False
        if query.categories is not None:
            for i in range(n):
                cats = model.item_categories[i]
                if cats is None or not (cats & query.categories):
                    out[i] = False

    def _weights(self, ctx: WorkflowContext, model: ECommModel):
        if not self.params.adjust_score:
            return None
        return self._item_weights(ctx, model)

    @staticmethod
    def _result_rows(
        model: ECommModel, scores: np.ndarray, idx: np.ndarray, num: int
    ) -> PredictedResult:
        return PredictedResult(
            tuple(
                ItemScore(model.item_vocab[int(i)], float(s))
                for s, i in zip(scores[:num], idx[:num])
                if np.isfinite(s)
            )
        )

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        return self.predict_with_context(
            WorkflowContext(mode="serving"), model, query
        )

    def predict_with_context(
        self, ctx: WorkflowContext, model: ECommModel, query: Query
    ) -> PredictedResult:
        n = len(model.item_vocab)
        pool = topk.scratch()
        mask = pool.get("ecomm.mask1", (1, n), np.bool_)
        self._candidate_mask(ctx, model, query, mask[0])
        weights = self._weights(ctx, model)
        kk = min(topk.next_pow2(min(query.num, n)), n)
        uidx = model.user_index(query.user)
        if uidx is not None:
            handle = topk.dot_top_k_async(
                model.device_items(),
                model.user_factors[uidx][None],
                mask,
                kk,
                weights=weights,
            )
        else:
            recent = self._recent_item_indices(ctx, model, query.user)
            if recent:
                handle = topk.gather_sum_top_k_async(
                    model.device_items(),
                    np.asarray(recent, np.int32)[None],
                    np.ones((1, len(recent)), np.float32),
                    mask,
                    kk,
                    weights=weights,
                )
            else:
                # popularity fallback: the scores are host-born counts —
                # nothing device-resident to fuse with, so this is the
                # sanctioned host ending (ops/topk.host_top_k)
                scores = model.popular_counts.astype(np.float64)
                if weights is not None:
                    scores = scores * weights
                sk, si = topk.host_top_k(scores, mask[0], min(query.num, n))
                return self._result_rows(model, sk, si, len(si))
        scores, idx = topk.fetch_topk(handle)
        return self._result_rows(
            model, scores[0], idx[0], min(query.num, kk)
        )

    def predict_batch(
        self, model: ECommModel, queries: Sequence[Query]
    ) -> list[PredictedResult]:
        return self.predict_batch_dispatch(model, queries)()

    def predict_batch_dispatch(self, model: ECommModel, queries: Sequence[Query]):
        """Micro-batch path: every known-user query rides ONE fused
        batched matvec+mask+top-k (user vectors and mask rows assembled
        into reusable staging buffers); cold users (recent-similarity or
        popularity fallback) answer per query in the finalize."""
        ctx = WorkflowContext(mode="serving")
        n = len(model.item_vocab)
        results: list[PredictedResult | None] = [None] * len(queries)
        rows: list[int] = []
        row_uidx: list[int] = []
        cold: list[int] = []
        max_num = 1
        for i, q in enumerate(queries):
            if q.num <= 0:
                results[i] = PredictedResult(())
                continue
            uidx = model.user_index(q.user)
            if uidx is None:
                cold.append(i)
                continue
            rows.append(i)
            row_uidx.append(uidx)
            max_num = max(max_num, q.num)
        handle = None
        kk = 0
        if rows:
            weights = self._weights(ctx, model)
            f = model.user_factors.shape[1]
            b = topk.batch_bucket(len(rows))
            pool = topk.scratch()
            vec_buf = pool.zeros("ecomm.vecs", (b, f), np.float32)
            np.take(
                model.user_factors, np.asarray(row_uidx, np.int64), axis=0,
                out=vec_buf[: len(rows)],
            )
            mask_buf = pool.get("ecomm.mask", (b, n), np.bool_)
            mask_buf[len(rows):] = True
            for row, i in enumerate(rows):
                self._candidate_mask(ctx, model, queries[i], mask_buf[row])
            kk = min(topk.next_pow2(max_num), n)
            handle = topk.dot_top_k_async(
                model.device_items(), vec_buf, mask_buf, kk, weights=weights
            )

        def finalize() -> list[PredictedResult]:
            for i in cold:
                results[i] = self.predict_with_context(ctx, model, queries[i])
            if handle is not None:
                scores, idx = topk.fetch_topk(handle)
                with annotate("pio:fetch.unpack"):
                    for row, i in enumerate(rows):
                        results[i] = self._result_rows(
                            model, scores[row], idx[row], min(queries[i].num, kk)
                        )
            return results  # type: ignore[return-value]

        return finalize

    def warmup_serving(self, model: ECommModel, max_batch: int) -> None:
        n = len(model.item_vocab)
        f = model.user_factors.shape[1]
        kk = min(topk.next_pow2(10), n)
        # with adjust_score the serving path passes weights (and runs the
        # program specialised on them) only while a weightedItems constraint
        # is actually set (a live event-store lookup — unknowable here), so
        # warm BOTH: whichever one serves, its programs are compiled
        variants: list[np.ndarray | None] = [None]
        if self.params.adjust_score:
            variants.append(np.ones(n, np.float32))
        for weights in variants:
            topk.warmup_pow2_buckets(
                max_batch,
                lambda b: topk.dot_top_k_async(
                    model.device_items(),
                    np.zeros((b, f), np.float32),
                    np.ones((b, n), bool),
                    kk,
                    weights=weights,
                ),
            )


class Serving(BaseServing):
    def serve(self, query: Query, predictions: Sequence[PredictedResult]) -> PredictedResult:
        return predictions[0]


def engine_factory() -> Engine:
    return Engine(
        DataSource,
        Preparator,
        {"ecomm": ECommAlgorithm},
        Serving,
        query_class=Query,
    )
