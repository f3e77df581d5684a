"""Session / next-item engine (DASE components).

Reference parity (behavioral):
  - the e2 MarkovChain (``e2/.../engine/MarkovChain.scala:26-55``) finally
    gets a template consumer: the transition-matrix scorer below is
    EXACTLY ``e2.markov_chain.train_markov_chain`` over consecutive-pair
    coordinates — a parity unit test holds the two outputs equal.
  - ordered per-user reads ride the PR-5 ``find_after`` contract (strict
    ``(creation_time_us, event_id)`` total order, bounded pages), so the
    session order the trainer sees is the ingest order, not scan luck.

TPU design: the optional attention scorer is a serving consumer of
``ops/attention.fused_attention``: session items gather their input
embeddings, one causal single-head attention pass over the short context
window produces the session vector, and scoring+masking+selection is the
shared fused ``ops/topk.dot_top_k_async`` program over the resident output
table — only the packed (k scores, k indices) result ever crosses the
wire. When an ANN index is pinned to the lane the session vector handle
feeds ``ann.search_async`` zero-copy, same as the two-tower engine.

The ``olmoe`` scorer puts a real backbone in the same place: the session's
items are the tokens of OLMoE-1B-7B (``olmoe.py``: 16 heads of 128 with
q/k norms and RoPE, 64 sparse experts with 8 a token), one causal prefill
a query, the final-normed hidden state at the session's last position
scored against ``lm_head`` through the same ``ops/topk.dot_top_k_async``.
The ``kimi_linear`` scorer is a second backbone behind the same staging and
launch (``BackboneAlgorithm``): Kimi-Linear-48B-A3B's block
(``kimi_linear.py``: layers of four kinds, a chunked gated-delta-rule scan
beside latent attention, a shared expert and the chip's share of 256
sigmoid-routed experts). The ``sdar`` algorithm is a third, and the first
that GENERATES: SDAR-30B-A3B-Chat's block (``sdar.py``: grouped queries
under a block-causal mask, 128 softmax-routed experts) answers with ``num``
items in order, produced block by block by masked diffusion over a cache of
keys and values that lives for the batch. The ``lfm2`` scorer is a fourth, and
the first whose program holds a model's WHOLE depth: LFM2-8B-A1B (``lfm2.py``:
24 layers, gated short convolutions in 18 and grouped-query attention at a
head width of 64 in 6, two dense feed-forwards and then the chip's share of 32
sigmoid-routed experts). Every backbone's weights are drawn
from a seed, not fitted: fitting a backbone is not this engine's work yet
(ROADMAP R7).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any, Iterator, Sequence

import numpy as np

from predictionio_tpu.controller import (
    BaseDataSource,
    BasePreparator,
    BaseServing,
    Engine,
    JaxAlgorithm,
    LocalAlgorithm,
    Params,
    PersistentModel,
    SanityCheck,
)
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.store.event_store import resolve_app
from predictionio_tpu.e2.markov_chain import MarkovChainModel, train_markov_chain
from predictionio_tpu.models.sequential.metrics import BackboneInstruments
from predictionio_tpu.obs.jaxprof import annotate
from predictionio_tpu.ops import topk
from predictionio_tpu.workflow.context import WorkflowContext

# ---------------------------------------------------------------------------
# Query / result
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Query:
    """``recentItems`` is the caller-supplied session tail (most recent
    LAST); when absent, the model's stored last-item for ``user`` answers
    (ref e-commerce template's recent-event lookup)."""

    user: str | None = None
    recent_items: tuple[str, ...] = ()
    num: int = 10

    @staticmethod
    def from_json_dict(d: dict[str, Any]) -> "Query":
        return Query(
            user=d.get("user"),
            recent_items=tuple(d.get("recentItems") or ()),
            num=int(d.get("num", 10)),
        )


@dataclasses.dataclass(frozen=True)
class ItemScore:
    """``step``, where an answer was GENERATED (``sdar``): the 0-based
    denoise pass of the item's block that fixed it; ``score`` is then the
    log-probability it was fixed at."""

    item: str
    score: float
    step: int | None = None

    def to_json_dict(self) -> dict[str, Any]:
        out = {"item": self.item, "score": self.score}
        return out if self.step is None else {**out, "step": self.step}


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...]

    def to_json_dict(self) -> dict[str, Any]:
        return {"itemScores": [s.to_json_dict() for s in self.item_scores]}


@dataclasses.dataclass(frozen=True)
class ActualResult:
    """The user's true continuation (ordered) for eval folds."""

    items: tuple[str, ...]


# ---------------------------------------------------------------------------
# DataSource
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EvalParams(Params):
    k_fold: int = 3
    query_num: int = 10
    # how many trailing items of each held-out user's session become the
    # actual continuation (the prefix becomes the query's recentItems)
    holdout_tail: int = 2


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str
    channel_name: str | None = None
    event_names: tuple[str, ...] = ("view",)
    entity_type: str = "user"
    target_entity_type: str = "item"
    # find_after page size and total-event bound for one training read
    page: int = 2048
    max_events: int = 500_000
    eval_params: EvalParams | None = None


@dataclasses.dataclass
class TrainingData(SanityCheck):
    """Ordered per-user sessions, dictionary-encoded: ``sequences[i]`` is
    user ``users[i]``'s item-index sequence in event order."""

    users: list[str]
    sequences: list[np.ndarray]
    item_vocab: list[str]

    def sanity_check(self) -> None:
        if len(self.users) != len(self.sequences):
            raise ValueError("users/sequences length mismatch")
        if not any(len(s) >= 2 for s in self.sequences):
            raise ValueError(
                "no session with >= 2 events — nothing to learn transitions from"
            )


def transition_coordinates(
    sequences: Sequence[np.ndarray],
) -> list[tuple[int, int, float]]:
    """Consecutive-pair (from, to, 1.0) coordinates — the exact coordinate
    form ``e2.markov_chain.train_markov_chain`` consumes (it sums the
    duplicates itself; emitting raw pairs keeps the parity trivially
    auditable)."""
    coords: list[tuple[int, int, float]] = []
    for seq in sequences:
        for a, b in zip(seq[:-1], seq[1:]):
            coords.append((int(a), int(b), 1.0))
    return coords


def sequences_from_events(
    events: Iterator[Event],
    *,
    event_names: Sequence[str],
    entity_type: str,
    target_entity_type: str,
    vocab: dict[str, int] | None = None,
) -> tuple[dict[str, list[int]], list[str]]:
    """Fold an ORDERED event iterator into per-user item-index sequences.
    The iterator's order IS the session order — callers must feed a
    ``find_after``-ordered stream (see ``_iter_ordered``)."""
    names = set(event_names)
    index: dict[str, int] = dict(vocab) if vocab else {}
    item_vocab: list[str] = [None] * len(index)  # type: ignore[list-item]
    for item, i in index.items():
        item_vocab[i] = item
    per_user: dict[str, list[int]] = {}
    for e in events:
        if e.event not in names or e.entity_type != entity_type:
            continue
        if e.target_entity_type != target_entity_type or e.target_entity_id is None:
            continue
        idx = index.get(e.target_entity_id)
        if idx is None:
            idx = len(item_vocab)
            index[e.target_entity_id] = idx
            item_vocab.append(e.target_entity_id)
        per_user.setdefault(e.entity_id, []).append(idx)
    return per_user, item_vocab


def _iter_ordered(
    levents, app_id: int, channel_id: int | None, page: int, max_events: int
) -> Iterator[Event]:
    """Bounded ordered scan: ``find_after`` pages in ``(creation_time_us,
    event_id)`` order up to the head observed at entry, so a live ingest
    stream cannot keep the read open forever."""
    head = levents.seq_head(app_id, channel_id)
    if head is None:
        return
    from predictionio_tpu.data.storage.base import event_seq_key

    cursor = None
    seen = 0
    while seen < max_events:
        batch = list(
            levents.find_after(
                app_id, channel_id, cursor, min(page, max_events - seen)
            )
        )
        if not batch:
            return
        for e in batch:
            key = event_seq_key(e)
            if key > head:
                return
            cursor = key
            seen += 1
            yield e
        if len(batch) < page:
            return


class DataSource(BaseDataSource):
    params_class = DataSourceParams
    params: DataSourceParams

    def _ordered_events(self, ctx: WorkflowContext) -> Iterator[Event]:
        app_id, channel_id = resolve_app(
            ctx.storage, self.params.app_name, self.params.channel_name
        )
        levents = ctx.storage.get_l_events()
        return _iter_ordered(
            levents, app_id, channel_id, self.params.page, self.params.max_events
        )

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        per_user, vocab = sequences_from_events(
            self._ordered_events(ctx),
            event_names=self.params.event_names,
            entity_type=self.params.entity_type,
            target_entity_type=self.params.target_entity_type,
        )
        users = sorted(per_user)
        return TrainingData(
            users,
            [np.asarray(per_user[u], np.int32) for u in users],
            vocab,
        )

    def read_eval(self, ctx: WorkflowContext):
        """k-fold by USER through the tuning grid's ``EventStoreSplitter``
        (the PR-14 follow-up): fold assignment is the splitter's sticky
        sha256 bucket, so eval-grid cells across processes and hosts agree
        on which users are held out without exchanging state."""
        if self.params.eval_params is None:
            raise ValueError("Must specify evalParams for evaluation")
        ep = self.params.eval_params
        from predictionio_tpu.tuning.grid import EventStoreSplitter

        app_id, channel_id = resolve_app(
            ctx.storage, self.params.app_name, self.params.channel_name
        )
        splitter = EventStoreSplitter(
            ctx.storage.get_l_events(),
            app_id,
            ep.k_fold,
            channel_id,
            num=ep.query_num,
            entity_type=self.params.entity_type,
            event_names=self.params.event_names,
            page=self.params.page,
        )
        per_user, vocab = sequences_from_events(
            splitter.iter_ordered(),
            event_names=self.params.event_names,
            entity_type=self.params.entity_type,
            target_entity_type=self.params.target_entity_type,
        )
        folds = []
        for fold in range(ep.k_fold):
            keep = splitter.keep_for_training(fold)
            users = sorted(u for u in per_user if keep(u))
            td = TrainingData(
                users,
                [np.asarray(per_user[u], np.int32) for u in users],
                vocab,
            )
            qa: list[tuple[Query, ActualResult]] = []
            for u in sorted(per_user):
                if keep(u):
                    continue
                seq = per_user[u]
                if len(seq) < 2:
                    continue
                tail = min(ep.holdout_tail, len(seq) - 1)
                qa.append(
                    (
                        Query(
                            user=u,
                            recent_items=tuple(
                                vocab[i] for i in seq[:-tail]
                            ),
                            num=ep.query_num,
                        ),
                        ActualResult(tuple(vocab[i] for i in seq[-tail:])),
                    )
                )
            folds.append((td, {}, qa))
        return folds


class Preparator(BasePreparator):
    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> TrainingData:
        return td


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SequentialModel(SanityCheck):
    """One model type serves both scorers: the Markov fields are always
    present (the stream trainer folds into them live); the attention
    fields are present when the attention algorithm trained. ``item_out``
    doubles as ``item_factors`` so the ANN lifecycle's
    ``item_vectors_of`` picks the table up unchanged."""

    item_vocab: list[str]
    markov: MarkovChainModel | None = None
    # raw summed pair counts — what the streaming trainer merges into;
    # the markov model is always rebuilt from these (exact e2 math)
    pair_counts: dict[tuple[int, int], float] = dataclasses.field(
        default_factory=dict
    )
    user_last: dict[str, int] = dataclasses.field(default_factory=dict)
    top_n: int = 10
    # attention scorer state (None for markov-only models)
    item_in: np.ndarray | None = None  # [n, f] session-side embeddings
    item_out: np.ndarray | None = None  # [n, f] scoring table
    context: int = 8

    def __post_init__(self):
        self._lock = threading.Lock()
        self._dev_in = None
        self._dev_out = None
        self._index: dict[str, int] | None = None

    @property
    def item_factors(self) -> np.ndarray | None:
        return self.item_out

    def item_index(self) -> dict[str, int]:
        idx = self._index
        if idx is None or len(idx) != len(self.item_vocab):
            idx = self._index = {v: i for i, v in enumerate(self.item_vocab)}
        return idx

    def device_in(self):
        import jax.numpy as jnp

        with self._lock:
            if self._dev_in is None and self.item_in is not None:
                self._dev_in = jnp.asarray(self.item_in, jnp.float32)
            return self._dev_in

    def device_out(self):
        import jax.numpy as jnp

        with self._lock:
            if self._dev_out is None and self.item_out is not None:
                self._dev_out = jnp.asarray(self.item_out, jnp.float32)
            return self._dev_out

    def __getstate__(self):
        state = dict(self.__dict__)
        for k in ("_lock", "_dev_in", "_dev_out", "_index"):
            state.pop(k, None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._dev_in = None
        self._dev_out = None
        self._index = None

    def sanity_check(self) -> None:
        if not self.item_vocab:
            raise ValueError("empty item vocab")

    def session_indices(self, query: Query) -> list[int]:
        """Resolve the query's session tail to item indices: explicit
        ``recentItems`` win; a bare ``user`` falls back to the stored
        last item of their training/stream history."""
        idx = self.item_index()
        session = [
            idx[i] for i in query.recent_items if i in idx
        ]
        if not session and query.user is not None:
            last = self.user_last.get(query.user)
            if last is not None:
                session = [last]
        return session


def build_markov(
    sequences: Sequence[np.ndarray], n_states: int, top_n: int
) -> tuple[MarkovChainModel, dict[tuple[int, int], float]]:
    """Train the transition model through the REAL e2 entry point — the
    parity test holds this against a direct ``train_markov_chain`` call on
    the same events. Returns the summed pair counts too (the streaming
    trainer's merge substrate; ``train_markov_chain`` keeps only top-N
    probabilities, which is lossy)."""
    coords = transition_coordinates(sequences)
    counts: dict[tuple[int, int], float] = {}
    for i, j, c in coords:
        counts[(i, j)] = counts.get((i, j), 0.0) + c
    return train_markov_chain(coords, n_states, top_n), counts


def markov_from_counts(
    counts: dict[tuple[int, int], float], n_states: int, top_n: int
) -> MarkovChainModel:
    return train_markov_chain(
        [(i, j, c) for (i, j), c in counts.items()], n_states, top_n
    )


def last_items(sequences: Sequence[np.ndarray], users: Sequence[str]) -> dict[str, int]:
    return {
        u: int(seq[-1]) for u, seq in zip(users, sequences) if len(seq)
    }


# ---------------------------------------------------------------------------
# Markov algorithm (host-born sparse scores -> sanctioned host ending)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MarkovAlgorithmParams(Params):
    top_n: int = 10


class MarkovAlgorithm(LocalAlgorithm):
    """Transition-matrix next-item scorer. The scores are host-born sparse
    transition probabilities (<= top_n of them) — ``topk.host_top_k`` is
    the sanctioned ending, same as the cooccurrence algorithm."""

    params_class = MarkovAlgorithmParams
    params: MarkovAlgorithmParams

    def train(self, ctx: WorkflowContext, td: TrainingData) -> SequentialModel:
        markov, counts = build_markov(
            td.sequences, len(td.item_vocab), self.params.top_n
        )
        return SequentialModel(
            item_vocab=list(td.item_vocab),
            markov=markov,
            pair_counts=counts,
            user_last=last_items(td.sequences, td.users),
            top_n=self.params.top_n,
        )

    def predict(self, model: SequentialModel, query: Query) -> PredictedResult:
        session = model.session_indices(query)
        if not session or model.markov is None:
            return PredictedResult(())
        n = len(model.item_vocab)
        scores = np.zeros(n, np.float64)
        for j, p in model.markov.transition_probs(session[-1]):
            if j < n:
                scores[j] = p
        mask = np.ones(n, bool)
        mask[np.asarray(session, np.int64)] = False
        mask &= scores > 0.0
        s, idx = topk.host_top_k(scores, mask, query.num)
        return PredictedResult(
            tuple(
                ItemScore(model.item_vocab[int(i)], float(v))
                for v, i in zip(s, idx)
            )
        )


# ---------------------------------------------------------------------------
# Attention algorithm (fused_attention encode -> fused top-k / ANN)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionAlgorithmParams(Params):
    rank: int = 32
    num_iterations: int = 10
    lambda_: float = 0.1
    seed: int = 3
    # session window the attention encoder attends over; short by design
    # (the pallas kernel's single-block path covers it on TPU). On the
    # chip a context of 1024 or more must be a multiple of 256
    # (ops/attention.fused_attention refuses it otherwise). This is the
    # `attention` scorer's window only: `olmoe` takes a session's last
    # `max_position_embeddings` items, packed into token streams
    context: int = 8
    top_n: int = 10


class AttentionAlgorithm(JaxAlgorithm):
    """Short-context attention next-item scorer.

    Train: implicit ALS over the transition-pair matrix factorizes
    transitions into an input table (session side) and an output table
    (scoring side) — attention over the input embeddings of the session
    window produces the session vector; the output table scores it.
    Markov is the window=1 special case of this program.

    Serve: gather -> causal single-head ``fused_attention`` -> last
    position = session vector (device-resident) -> shared
    ``topk.dot_top_k_async`` (or ``ann.search_async`` when a lane index is
    pinned). No host argsort anywhere on this path — the packed [B,2,k]
    result is the only fetch."""

    params_class = AttentionAlgorithmParams
    params: AttentionAlgorithmParams

    def train(self, ctx: WorkflowContext, td: TrainingData) -> SequentialModel:
        from predictionio_tpu.ops.als import ALSConfig, als_train

        n = len(td.item_vocab)
        markov, counts = build_markov(td.sequences, n, self.params.top_n)
        if counts:
            from_idx = np.asarray([i for i, _ in counts], np.int32)
            to_idx = np.asarray([j for _, j in counts], np.int32)
            weight = np.asarray(list(counts.values()), np.float32)
        else:
            from_idx = np.empty(0, np.int32)
            to_idx = np.empty(0, np.int32)
            weight = np.empty(0, np.float32)
        cfg = ALSConfig(
            rank=self.params.rank,
            iterations=self.params.num_iterations,
            reg=self.params.lambda_,
            implicit=True,
            seed=self.params.seed,
        )
        item_in, item_out = als_train(from_idx, to_idx, weight, n, n, cfg)
        item_in = np.asarray(item_in, np.float32)
        item_out = np.asarray(item_out, np.float32)
        return SequentialModel(
            item_vocab=list(td.item_vocab),
            markov=markov,
            pair_counts=counts,
            user_last=last_items(td.sequences, td.users),
            top_n=self.params.top_n,
            item_in=item_in,
            item_out=item_out,
            context=self.params.context,
        )

    # ------------------------------------------------------------- serving
    @staticmethod
    def _encode(table, hist):
        """Jit-compiled per (B, L) bucket by the jax cache: gather the
        window's input embeddings and run one causal single-head
        attention pass; the last position's output is the session
        vector. Left-pad slots repeat the window's oldest item — a
        documented smoothing bias that keeps the program shape static.
        ``fused_attention`` has a segment mask since the backbones pack
        their sessions into streams (``segment=``); this scorer passes
        none, and its program is what it was. True of this scorer only:
        the backbones pad BEHIND a session, where causal attention keeps
        the padding out of every real position, and are exact."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.attention import fused_attention

        e = table[hist]  # [B, L, f]
        x = e[:, None, :, :]  # [B, H=1, L, f]
        out = fused_attention(x, x, x, causal=True)
        return jnp.asarray(out[:, 0, -1, :])  # [B, f]

    _encode_jit = None

    @classmethod
    def _encoder(cls):
        if cls._encode_jit is None:
            import jax

            cls._encode_jit = jax.jit(cls._encode)
        return cls._encode_jit

    def _stage_batch(
        self, model: SequentialModel, queries: Sequence[Query]
    ):
        """Host staging: resolve sessions, right-align into a [B, L]
        window buffer (left-padded with each row's oldest in-window item),
        and build the candidate mask excluding session items."""
        pool = topk.scratch()
        b = len(queries)
        bb = topk.next_pow2(b)
        L = max(1, self.params.context)
        n = len(model.item_vocab)
        hist = pool.zeros("seq_hist", (bb, L), np.int32)
        mask = pool.full("seq_mask", (bb, n), bool, True)
        mask[b:, :] = False
        sessions: list[list[int]] = []
        for q_i, q in enumerate(queries):
            session = model.session_indices(q)
            sessions.append(session)
            window = session[-L:] if session else []
            if window:
                hist[q_i, :] = window[0]
                hist[q_i, L - len(window):] = window
                mask[q_i, np.asarray(session, np.int64)] = False
            else:
                mask[q_i, :] = False
        return hist, mask, sessions, bb

    def predict_batch_dispatch(
        self, model: SequentialModel, queries: Sequence[Query]
    ):
        from predictionio_tpu.ann.lifecycle import ATTR as _ANN_ATTR

        table_in = model.device_in()
        table_out = model.device_out()
        if table_in is None or table_out is None:
            # markov-only model answering on the attention lane: map the
            # host scorer (still no device work to fuse with)
            alg = MarkovAlgorithm(MarkovAlgorithmParams(top_n=model.top_n))
            results = [alg.predict(model, q) for q in queries]
            return lambda: results
        hist, mask, sessions, bb = self._stage_batch(model, queries)
        n = len(model.item_vocab)
        kk = min(topk.next_pow2(max(1, max(q.num for q in queries))), n)
        ctx_vec = self._encoder()(table_in, topk.upload(hist, np.int32))
        ann = getattr(model, _ANN_ATTR, None)
        if ann is not None and not ann.supports(kk):
            ann.count_fallback(len(queries))
            ann = None
        if ann is not None:
            # exclusion of session items happens in the fused ANN gather
            handle = ann.search_async(
                ctx_vec, kk, exclude=self._exclude_rows(sessions, bb)
            )
        else:
            handle = topk.dot_top_k_async(table_out, ctx_vec, mask, kk)

        def finalize() -> list[PredictedResult]:
            if ann is not None:
                scores, idx = ann.fetch(handle, rows=len(queries))
            else:
                scores, idx = topk.fetch_topk(handle)
            out: list[PredictedResult] = []
            for q_i, q in enumerate(queries):
                banned = set(sessions[q_i])
                picks: list[ItemScore] = []
                for v, i in zip(scores[q_i], idx[q_i]):
                    i = int(i)
                    if not np.isfinite(v) or i < 0 or i in banned:
                        continue
                    picks.append(ItemScore(model.item_vocab[i], float(v)))
                    if len(picks) >= q.num:
                        break
                out.append(PredictedResult(tuple(picks)))
            return out

        return finalize

    @staticmethod
    def _exclude_rows(sessions: list[list[int]], bb: int) -> np.ndarray:
        width = max(1, max((len(s) for s in sessions), default=1))
        ex = np.full((bb, width), -1, np.int32)
        for r, s in enumerate(sessions):
            if s:
                ex[r, : len(s)] = s
        return ex

    def predict_batch(
        self, model: SequentialModel, queries: Sequence[Query]
    ) -> list[PredictedResult]:
        return self.predict_batch_dispatch(model, queries)()

    def predict(self, model: SequentialModel, query: Query) -> PredictedResult:
        return self.predict_batch(model, [query])[0]

    def warmup_serving(self, model: SequentialModel, max_batch: int) -> None:
        """Pre-compile the encode+topk program per pow2 batch bucket (and
        the ANN composition when pinned) so the first burst after
        deploy/reload pays no XLA compiles."""
        if model.device_in() is None:
            return
        vocab = model.item_vocab
        if not vocab:
            return
        probe = Query(recent_items=(vocab[0],), num=min(10, len(vocab)))

        def dispatch(b: int):
            fin = self.predict_batch_dispatch(model, [probe] * b)
            return fin() if callable(fin) else fin

        topk.warmup_pow2_buckets(max_batch, dispatch)


# ---------------------------------------------------------------------------
# Backbone algorithms (one prefill through a language model's block -> fused
# top-k): `olmoe`, `kimi_linear` and `lfm2` share the model, its storage, the staging
# and the launch; the backbone's module and its parameters are what differs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OlmoeAlgorithmParams(Params):
    """The published ``config.json`` of allenai/OLMoE-1B-7B-0125-Instruct,
    key for key (a variant file carries them verbatim), and the seed the
    weights are drawn from. The keys the program has one answer for
    (``attention_bias`` false, ``clip_qkv`` null, ``hidden_act`` silu,
    ``norm_topk_prob`` false, ``rope_scaling`` null, untied embeddings, as
    many key-value heads as heads) are refused at any other value rather
    than ignored."""

    hidden_size: int = 2048
    intermediate_size: int = 1024
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: dict | None = None
    attention_bias: bool = False
    clip_qkv: float | None = None
    tie_word_embeddings: bool = False
    vocab_size: int = 50304
    max_position_embeddings: int = 4096
    model_type: str = "olmoe"
    seed: int = 3

    def config(self):
        from predictionio_tpu.models.sequential.olmoe import OlmoeConfig

        one_answer = {
            "model_type": "olmoe", "hidden_act": "silu", "norm_topk_prob": False,
            "rope_scaling": None, "attention_bias": False, "clip_qkv": None,
            "tie_word_embeddings": False, "num_key_value_heads": self.num_attention_heads,
        }
        for key, value in one_answer.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"olmoe: {key}={getattr(self, key)!r} is not implemented (only {value!r})"
                )
        return OlmoeConfig(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_experts=self.num_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            vocab_size=self.vocab_size,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps,
            rope_theta=self.rope_theta,
        )


@dataclasses.dataclass(frozen=True)
class KimiLinearAlgorithmParams(Params):
    """The published ``config.json`` of moonshotai/Kimi-Linear-48B-A3B-Instruct,
    key for key (a variant file carries them verbatim), the seed the weights
    are drawn from, and the chip's share of a stated deployment:
    ``experts_held`` ``[first, count]`` of the router's ``num_experts`` (all
    of them by default) and ``vocab_slice`` ``[first, count]`` of
    ``vocab_size`` (items are the slice's tokens). ``num_hidden_layers`` may
    be fewer than published: layers 1 to that, as ``linear_attn_config``
    numbers them. The keys the program has one answer for are refused at any
    other value rather than ignored."""

    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 72
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    linear_attn_config: dict = dataclasses.field(
        default_factory=lambda: {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
            "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
            "num_heads": 32,
            "short_conv_kernel_size": 4,
        }
    )
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    use_grouped_topk: bool = True
    num_expert_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 0
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: dict | None = None
    tie_word_embeddings: bool = False
    vocab_size: int = 163840
    model_max_length: int = 1048576
    model_type: str = "kimi_linear"
    experts_held: tuple | None = None
    vocab_slice: tuple | None = None
    seed: int = 3

    def config(self):
        from predictionio_tpu.models.sequential.kimi_linear import KimiLinearConfig

        one_answer = {
            "model_type": "kimi_linear", "hidden_act": "silu", "mla_use_nope": True,
            "q_lora_rank": None, "moe_layer_freq": 1, "moe_renormalize": True,
            "moe_router_activation_func": "sigmoid", "num_expert_group": 1, "topk_group": 1,
            "num_nextn_predict_layers": 0, "rope_scaling": None, "tie_word_embeddings": False,
            "num_key_value_heads": self.num_attention_heads,
        }
        for key, value in one_answer.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"kimi_linear: {key}={getattr(self, key)!r} is not implemented (only {value!r})"
                )
        linear = self.linear_attn_config
        return KimiLinearConfig(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            moe_intermediate_size=self.moe_intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim,
            kda_num_heads=linear["num_heads"],
            kda_head_dim=linear["head_dim"],
            short_conv_kernel_size=linear["short_conv_kernel_size"],
            kda_layers=tuple(linear["kda_layers"]),
            full_attn_layers=tuple(linear["full_attn_layers"]),
            first_k_dense_replace=self.first_k_dense_replace,
            num_experts=self.num_experts,
            num_experts_per_token=self.num_experts_per_token,
            num_shared_experts=self.num_shared_experts,
            routed_scaling_factor=self.routed_scaling_factor,
            rms_norm_eps=self.rms_norm_eps,
            experts_held=tuple(self.experts_held or (0, self.num_experts)),
            vocab_slice=tuple(self.vocab_slice or (0, self.vocab_size)),
            model_max_length=self.model_max_length,
        )


@dataclasses.dataclass(frozen=True)
class SdarAlgorithmParams(Params):
    """The published ``config.json`` of JetLM/SDAR-30B-A3B-Chat, key for key
    (a variant file carries them verbatim), the seed the weights are drawn
    from, and what the generation needs and ``config.json`` has no key for:
    ``block_length``, ``denoising_steps`` (denoise passes a block) and
    ``mask_token_id`` (the vocabulary's last id by default; never an item).
    ``intermediate_size`` names a dense feed-forward that no layer has
    (``mlp_only_layers`` []): stated, not built. The keys the program has
    one answer for are refused at any other value rather than ignored."""

    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: dict | None = None
    attention_bias: bool = False
    sliding_window: int | None = None
    use_sliding_window: bool = False
    max_window_layers: int = 48
    tie_word_embeddings: bool = False
    vocab_size: int = 151936
    max_position_embeddings: int = 32768
    model_type: str = "sdar_moe"
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int | None = None
    seed: int = 3

    def config(self):
        from predictionio_tpu.models.sequential.sdar import SdarConfig

        one_answer = {
            "model_type": "sdar_moe", "hidden_act": "silu", "norm_topk_prob": True,
            "decoder_sparse_step": 1, "mlp_only_layers": (), "rope_scaling": None,
            "attention_bias": False, "sliding_window": None, "use_sliding_window": False,
            "tie_word_embeddings": False,
        }
        for key, value in one_answer.items():
            mine = getattr(self, key)
            if (tuple(mine) if isinstance(mine, list) else mine) != value:
                raise ValueError(f"sdar: {key}={mine!r} is not implemented (only {value!r})")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("sdar: the key/value heads do not divide the heads")
        return SdarConfig(
            hidden_size=self.hidden_size,
            moe_intermediate_size=self.moe_intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            head_dim=self.head_dim,
            num_experts=self.num_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            vocab_size=self.vocab_size,
            rms_norm_eps=self.rms_norm_eps,
            rope_theta=self.rope_theta,
            block_length=self.block_length,
            denoising_steps=self.denoising_steps,
            mask_token_id=self.vocab_size - 1 if self.mask_token_id is None else self.mask_token_id,
        )


@dataclasses.dataclass(frozen=True)
class Lfm2AlgorithmParams(Params):
    """The published ``config.json`` of LiquidAI/LFM2-8B-A1B, key for key (a
    variant file carries them verbatim), the seed the weights are drawn from,
    and the chip's share of a stated deployment: ``experts_held`` ``[first,
    count]`` of the router's ``num_experts`` (all of them by default).
    ``layer_types`` is the published LIST, a mixer's kind a layer. The keys
    the program has one answer for are refused at any other value rather than
    ignored."""

    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: tuple = ("conv", "conv", "full_attention", "conv") * 5 + (
        "conv", "full_attention", "conv", "conv",
    )
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    vocab_size: int = 65536
    max_position_embeddings: int = 128000
    model_type: str = "lfm2_moe"
    experts_held: tuple | None = None
    seed: int = 3

    def config(self):
        from predictionio_tpu.models.sequential.lfm2 import Lfm2Config

        one_answer = {
            "model_type": "lfm2_moe", "conv_bias": False, "norm_topk_prob": True,
            "use_expert_bias": True,
        }
        for key, value in one_answer.items():
            if getattr(self, key) != value:
                raise ValueError(f"lfm2: {key}={getattr(self, key)!r} is not implemented (only {value!r})")
        return Lfm2Config(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            moe_intermediate_size=self.moe_intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            layer_types=tuple(self.layer_types),
            conv_L_cache=self.conv_L_cache,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            num_dense_layers=self.num_dense_layers,
            num_experts=self.num_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            routed_scaling_factor=float(self.routed_scaling_factor),
            norm_eps=self.norm_eps,
            rope_theta=float(self.rope_theta),
            vocab_size=self.vocab_size,
            max_position_embeddings=self.max_position_embeddings,
            experts_held=tuple(self.experts_held or (0, self.num_experts)),
        )


@dataclasses.dataclass(frozen=True)
class KananaAlgorithmParams(Params):
    """The published ``config.json`` of kakaocorp/kanana-2-30b-a3b-instruct-2601
    (``model_type: deepseek_v3``), key for key (a variant file carries them
    verbatim), and the seed the weights are drawn from. ``head_dim`` (64, the
    rotary width) and ``qk_head_dim`` (128 + 64) restate other keys and are
    held to them. The keys the program has one answer for are refused at any
    other value rather than ignored: no low-rank queries (``q_lora_rank``
    null), a sigmoid router whose group limit is the identity (``n_group`` 1,
    ``topk_group`` 1), the chosen weights renormalised, interleaved RoPE
    without scaling."""

    attention_bias: bool = False
    first_k_dense_replace: int = 1
    head_dim: int = 64
    hidden_act: str = "silu"
    hidden_size: int = 2048
    intermediate_size: int = 6144
    kv_lora_rank: int = 512
    max_position_embeddings: int = 32768
    model_type: str = "deepseek_v3"
    moe_intermediate_size: int = 768
    moe_layer_freq: int = 1
    n_group: int = 1
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts_per_tok: int = 6
    num_hidden_layers: int = 48
    num_key_value_heads: int = 32
    q_lora_rank: int | None = None
    qk_head_dim: int = 192
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    rms_norm_eps: float = 1e-6
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    rope_theta: float = 1000000.0
    routed_scaling_factor: float = 2.448
    scoring_func: str = "sigmoid"
    tie_word_embeddings: bool = False
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    v_head_dim: int = 128
    vocab_size: int = 128256
    seed: int = 3

    def config(self):
        from predictionio_tpu.models.sequential.kanana import KananaConfig

        one_answer = {
            "model_type": "deepseek_v3", "hidden_act": "silu", "attention_bias": False,
            "q_lora_rank": None, "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "rope_interleave": True, "rope_scaling": None, "tie_word_embeddings": False,
            "num_key_value_heads": self.num_attention_heads, "head_dim": self.qk_rope_head_dim,
            "qk_head_dim": self.qk_nope_head_dim + self.qk_rope_head_dim,
        }
        for key, value in one_answer.items():
            if getattr(self, key) != value:
                raise ValueError(f"kanana: {key}={getattr(self, key)!r} is not implemented (only {value!r})")
        return KananaConfig(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            moe_intermediate_size=self.moe_intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim,
            first_k_dense_replace=self.first_k_dense_replace,
            n_routed_experts=self.n_routed_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            n_shared_experts=self.n_shared_experts,
            routed_scaling_factor=float(self.routed_scaling_factor),
            rms_norm_eps=self.rms_norm_eps,
            rope_theta=float(self.rope_theta),
            vocab_size=self.vocab_size,
            max_position_embeddings=self.max_position_embeddings,
        )


class BackboneModel(PersistentModel, SanityCheck):
    """A backbone's weight tree on the device, the item vocabulary (item
    ``i`` is token ``i``) and every user's session tail: the last
    ``config.max_session`` items, all users' in ONE int32 array with
    offsets (``tails[offsets[u]:offsets[u + 1]]``), not Python lists.

    It keeps its own storage (``save`` / ``load``): one raw file an array,
    read back array by array onto the device, so 7 GB of weights never
    pass through ``workflow/model_io``'s one pickled blob.

    ``program()`` is the module that holds the backbone's program
    (``Config``, ``session_vectors``, ``init_weights``,
    ``SESSION_ALIGN``), imported when first asked for; a subclass a backbone
    keeps a stored model's class path telling which."""

    @staticmethod
    def program():
        raise NotImplementedError

    def __init__(self, config, item_vocab, users, tails, offsets, weights):
        self.config = config
        self.item_vocab = list(item_vocab)
        self.users = list(users)
        self.tails = np.asarray(tails, np.int32)
        self.offsets = np.asarray(offsets, np.int64)
        self.weights = weights  # {name: device array}, layers stacked
        self._item_index: dict[str, int] | None = None
        self._user_index: dict[str, int] | None = None
        self._head = None

    def sanity_check(self) -> None:
        if not self.item_vocab:
            raise ValueError("empty item vocab")
        if len(self.item_vocab) > self.config.table_rows:
            raise ValueError(
                f"{len(self.item_vocab)} items do not fit a vocabulary of "
                f"{self.config.table_rows}"
            )

    def item_index(self) -> dict[str, int]:
        if self._item_index is None:
            self._item_index = {v: i for i, v in enumerate(self.item_vocab)}
        return self._item_index

    def user_index(self) -> dict[str, int]:
        if self._user_index is None:
            self._user_index = {u: i for i, u in enumerate(self.users)}
        return self._user_index

    def head(self):
        """``lm_head`` (``embed`` where the head is tied to it and the tree
        holds none) as ``ops/topk`` scores against it: float32 on the device
        (the bf16 values, exactly), one conversion a model."""
        if self._head is None:
            import jax.numpy as jnp

            table = self.weights.get("lm_head", self.weights["embed"])
            self._head = table.astype(jnp.float32)
        return self._head

    def session_tokens(self, query: Query) -> np.ndarray:
        """The query's session as token ids, oldest first, at most
        ``config.max_session`` of them: explicit ``recentItems`` win
        (unknown items dropped), a bare ``user`` gets their stored tail."""
        top = self.config.max_session
        if query.recent_items:
            index = self.item_index()
            known = [index[i] for i in query.recent_items if i in index]
            return np.asarray(known[-top:], np.int32)
        u = self.user_index().get(query.user) if query.user is not None else None
        if u is None:
            return np.empty(0, np.int32)
        return self.tails[self.offsets[u] : self.offsets[u + 1]]

    # -------------------------------------------------------- persistence
    def save(self, instance_id: str, params: Any, base_dir: str) -> bool:
        from predictionio_tpu.models.sequential import olmoe

        header = {
            "config": dataclasses.asdict(self.config),
            "item_vocab": self.item_vocab,
            "users": self.users,
        }
        arrays = {**self.weights, "tails": self.tails, "offsets": self.offsets}
        olmoe.save_arrays(os.path.join(base_dir, instance_id), header, arrays)
        return True

    @classmethod
    def load(cls, instance_id: str, params: Any, base_dir: str) -> "BackboneModel":
        import jax

        from predictionio_tpu.models.sequential import olmoe

        directory = os.path.join(base_dir, instance_id)
        header = olmoe.load_header(directory)
        arrays = {}
        for name, spec in header["arrays"].items():
            host = olmoe.load_array(directory, name, spec)
            # a weight goes to the device and leaves the host at once
            arrays[name] = host if name in ("tails", "offsets") else jax.device_put(host)
        return cls(
            cls.program().Config(**header["config"]),
            header["item_vocab"],
            header["users"],
            arrays.pop("tails"),
            arrays.pop("offsets"),
            arrays,
        )


def session_tails(sequences: Sequence[np.ndarray], keep: int):
    """``(tails, offsets)``: every sequence's last ``keep`` items, laid end
    to end in one int32 array."""
    lengths = np.fromiter((min(len(s), keep) for s in sequences), np.int64, len(sequences))
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    tails = np.empty(int(offsets[-1]), np.int32)
    for seq, start, n in zip(sequences, offsets, lengths):
        tails[start : start + n] = seq[len(seq) - n :]
    return tails, offsets


def _stream_limits(model: BackboneModel) -> tuple[int, int]:
    """``(the multiple a session starts on in its token stream, the sessions
    a stream holds)``: the backbone's ``SESSION_ALIGN``, and its budget's
    worth of them."""
    align = model.program().SESSION_ALIGN
    return align, model.config.stream_shapes()[0] // align


class BackboneAlgorithm(JaxAlgorithm):
    """A query answered through a language model's block: what the ``olmoe``,
    ``kimi_linear``, ``lfm2``, ``sdar`` and ``kanana`` algorithms share, which is everything but
    the backbone's module (``model_class.program()``), its parameters and
    HOW a staged batch is answered (``_answer``, the one hook): next-item
    scoring by one prefill (here; ``olmoe``, ``kimi_linear``, ``lfm2``) or a generation
    over the batch's cache (``GroupedAlgorithm``: ``SdarAlgorithm`` block by
    block, ``KananaAlgorithm`` token by token).

    Train: builds the item vocabulary (item ``i`` is token ``i``) and every
    user's session tail from the ordered events, and DRAWS the weights from
    ``seed`` in bfloat16. Fitting the backbone is not this engine's work yet
    (ROADMAP R7): the scores are those of a random network, and what is
    exact is that they are THIS network's, which the reference holds.

    Serve: ``predict_batch_dispatch`` does not treat a batch as B equal
    rows. It PACKS the batch's sessions into token streams (``_plan``:
    longest first, first fit, into streams of the backbone's
    ``TOKEN_BUDGET`` tokens, of ``config.max_session`` where a session is
    longer; every session from a multiple of ``SESSION_ALIGN``, at most
    ``TOKEN_BUDGET // SESSION_ALIGN`` a stream) and lays each out as
    ``[1, T]`` tokens with each token's ``segment`` and ``position``
    (``_stage``). ``_answer`` then launches: here the staged streams go
    ``STACKED_ROWS`` at a time as the ROWS of one program (``_programs``,
    ``_stack``), the backbone's ``session_vectors`` a program, then
    ``topk.dot_top_k_async`` over the program's sessions (their items
    masked). ONE finalize answers in the queries' order. The programs'
    shapes are a closed set (``[STACKED_ROWS, budget]`` and a single row of
    each of ``config.stream_shapes``) and ``warmup_serving`` compiles all of
    it. A single query is one session in a stream in a program of one row.
    What it launched is counted in ``instruments``, the algorithm's own until
    a query server hands over its registry."""

    model_class: type[BackboneModel]

    def __init__(self, params: Params | None = None):
        super().__init__(params)
        self.instruments = BackboneInstruments()

    def register_metrics(self, registry) -> None:
        self.instruments = BackboneInstruments(registry)

    def train(self, ctx: WorkflowContext, td: TrainingData) -> BackboneModel:
        config = self.params.config()
        tails, offsets = session_tails(td.sequences, config.max_session)
        model = self.model_class(
            config, td.item_vocab, td.users, tails, offsets,
            self.model_class.program().init_weights(config, self.params.seed),
        )
        model.sanity_check()
        return model

    # ------------------------------------------------------------- serving
    @staticmethod
    def _plan(model: BackboneModel, queries: Sequence[Query]):
        """Look-up and packing: ``(sessions, streams)``, a stream being
        ``(length, [(query index, where its session starts), ...])``. The
        sessions go longest first into the first stream that has room for
        their items rounded up to whole ``SESSION_ALIGN``s and holds fewer
        than ``TOKEN_BUDGET // SESSION_ALIGN`` of them; a new stream is of
        ``TOKEN_BUDGET`` tokens, or of the longest session's where the
        session does not fit that. Queries with no session are in no stream."""
        align, most = _stream_limits(model)
        budget, *longer = model.config.stream_shapes()
        sessions = [model.session_tokens(q) for q in queries]
        streams: list[list] = []  # [length, tokens free at its end, members]
        for i in sorted(range(len(sessions)), key=lambda i: -len(sessions[i])):
            room = -(-len(sessions[i]) // align) * align
            if not room:
                continue
            stream = next((s for s in streams if room <= s[1] and len(s[2]) < most), None)
            if stream is None:
                length = budget if room <= budget else longer[0]
                stream = [length, length, []]
                streams.append(stream)
            stream[2].append((i, stream[0] - stream[1]))
            stream[1] -= room
        return sessions, [(length, members) for length, _, members in streams]

    @staticmethod
    def _stage(model: BackboneModel, sessions, stream):
        """One stream's host arrays: ``tokens`` [1, T] (token 0 behind a
        session's last item: any token would do, no real position sees it),
        ``segment`` [1, T] (the session's index in the stream, -1 for the
        padding), ``position`` [1, T] (the index inside the session),
        ``last`` [S] (each session's last position in the stream, -1 for
        none) and the candidate mask [S, table rows] without the session's
        items, the vocabulary's unused rows and the rows of no session."""
        length, members = stream
        _, most = _stream_limits(model)
        tokens = np.zeros((1, length), np.int32)
        segment = np.full((1, length), -1, np.int32)
        position = np.zeros((1, length), np.int32)
        last = np.full(most, -1, np.int32)
        mask = np.zeros((most, model.config.table_rows), bool)
        mask[: len(members), : len(model.item_vocab)] = True
        for row, (i, start) in enumerate(members):
            session = sessions[i]
            end = start + len(session)
            tokens[0, start:end] = session
            segment[0, start:end] = row
            position[0, start:end] = np.arange(len(session))
            last[row] = end - 1
            mask[row, session] = False
        return tokens, segment, position, last, mask

    def predict_batch_dispatch(self, model: BackboneModel, queries: Sequence[Query]):
        t0 = time.perf_counter()
        sessions, streams = self._plan(model, queries)
        with annotate("pio:seq.stage", batch=len(queries), streams=len(streams)):
            staged = [self._stage(model, sessions, stream) for stream in streams]
        self.instruments.on_stage(time.perf_counter() - t0)
        return self._answer(model, queries, sessions, streams, staged)

    @staticmethod
    def _programs(model: BackboneModel, streams) -> list[list[int]]:
        """The staged streams' indices as PROGRAMS, a program's streams being
        its rows: the streams of the budget's length go ``STACKED_ROWS`` at a
        time (the backbone module's constant) and what is left of them, like
        every longer stream, one by one. The shapes are a closed set:
        ``[STACKED_ROWS, budget]`` and ``[1, T]`` for every ``T`` of
        ``config.stream_shapes()``."""
        budget = model.config.stream_shapes()[0]
        height = model.program().STACKED_ROWS
        short = [i for i, (length, _) in enumerate(streams) if length == budget]
        whole = len(short) - len(short) % height
        stacks = [short[at : at + height] for at in range(0, whole, height)]
        return stacks + [[i] for i in sorted(set(range(len(streams))) - set(short[:whole]))]

    @staticmethod
    def _stack(staged):
        """Staged streams of one length as the rows of ONE program:
        ``tokens``, ``segment``, ``position`` [R, T], ``last`` [R, S] and the
        mask [R * S, table rows], row by row. A row keeps its own segment
        ids: no kernel looks across rows."""
        tokens, segment, position, last, mask = zip(*staged)
        return (
            np.concatenate(tokens), np.concatenate(segment), np.concatenate(position),
            np.stack(last), np.concatenate(mask),
        )

    def _answer(self, model: BackboneModel, queries, sessions, streams, staged):
        """The staged streams launched, and the ``finalize`` that answers the
        queries in their order: one prefill a PROGRAM (``_programs``: up to
        ``STACKED_ROWS`` streams as its rows, so that a layer's experts meet
        all their tokens at once) and one fused top-k over its sessions'
        vectors."""
        config = model.config
        session_vectors = model.program().session_vectors
        n = len(model.item_vocab)
        kk = min(topk.next_pow2(max(1, max(q.num for q in queries))), n)
        _, most = _stream_limits(model)
        launched = []
        for rows in self._programs(model, streams):
            length = streams[rows[0]][0]
            # (the query, where its vector and its answer lie among the program's)
            places = [
                (i, r * most + k) for r, row in enumerate(rows) for k, (i, _) in enumerate(streams[row][1])
            ]
            real = sum(len(sessions[i]) for i, _ in places)
            # (`bucket` is a row's length and `rows` the streams stacked: the
            # names the counters' readers know a program's shape by)
            with annotate("pio:seq.launch", bucket=length, rows=len(rows), tokens=real):
                *arrays, mask = self._stack([staged[row] for row in rows])
                vectors, counted = session_vectors(
                    model.weights, *(topk.upload(a, np.int32) for a in arrays), config=config
                )
                handle = topk.dot_top_k_async(model.head(), vectors, mask, kk)
            self.instruments.on_launch(length, len(rows), real, len(places))
            launched.append((places, handle, counted, real))

        def finalize() -> list[PredictedResult]:
            out: list[PredictedResult] = [PredictedResult(())] * len(queries)
            for places, handle, counted, real in launched:
                scores, idx = topk.fetch_topk(handle)
                # up to three integers a program ride back with its answer:
                # the busiest expert's copies and, where the chip holds a
                # share of the experts, the copies routed to a held one and
                # the sparse layers whose held copies took more than one round
                counted = np.atleast_1d(np.asarray(counted, np.int64))
                routed = config.routed_copies(real)
                held = int(counted[1]) if counted.size > 1 else routed
                self.instruments.on_expert_load(int(counted[0]), config.even_expert_load(real))
                self.instruments.on_copies(held, routed - held)
                if counted.size > 2:
                    more = int(counted[2])
                    self.instruments.on_held_blocks(config.sparse_layers - more, more)
                for i, place in places:
                    picks = [
                        ItemScore(model.item_vocab[int(item)], float(score))
                        for score, item in zip(scores[place], idx[place])
                        if np.isfinite(score)
                    ]
                    out[i] = PredictedResult(tuple(picks[: queries[i].num]))
            return out

        return finalize

    def predict_batch(
        self, model: BackboneModel, queries: Sequence[Query]
    ) -> list[PredictedResult]:
        return self.predict_batch_dispatch(model, queries)()

    def predict(self, model: BackboneModel, query: Query) -> PredictedResult:
        return self.predict_batch(model, [query])[0]

    def warmup_serving(self, model: BackboneModel, max_batch: int) -> None:
        """Compile every program shape there is, by the path serving takes
        (the staging copies, ``session_vectors`` and the top-k): a query
        whose session is the longest there is, then batches of 1 to
        ``STACKED_ROWS`` streams' worth of sessions that each fill a stream of
        the budget: whatever a batch leaves behind its whole stacks is
        compiled then too.
        ``max_batch`` bounds nothing here: a batch of any size is packed
        into these shapes."""
        n = len(model.item_vocab)
        align, _ = _stream_limits(model)
        budget, *longer = model.config.stream_shapes()

        def filling(length: int) -> list[Query]:
            """Sessions that leave a stream of ``length`` no room for another
            of them: the longest there are, as many as fit."""
            items = min(length, model.config.max_session)
            recent = tuple(model.item_vocab[i % n] for i in range(items))
            room = -(-items // align) * align
            return [Query(recent_items=recent, num=min(10, n))] * (length // room)

        for length in longer:
            self.predict_batch(model, filling(length))
        stream = filling(budget)
        for streams in range(1, model.program().STACKED_ROWS + 1):
            self.predict_batch(model, stream * streams)


class OlmoeModel(BackboneModel):
    @staticmethod
    def program():
        from predictionio_tpu.models.sequential import olmoe

        return olmoe


class OlmoeAlgorithm(BackboneAlgorithm):
    """``olmoe``: OLMoE-1B-7B (``olmoe.py``)."""

    params_class = OlmoeAlgorithmParams
    params: OlmoeAlgorithmParams
    model_class = OlmoeModel


class KimiLinearModel(BackboneModel):
    @staticmethod
    def program():
        from predictionio_tpu.models.sequential import kimi_linear

        return kimi_linear


class KimiLinearAlgorithm(BackboneAlgorithm):
    """``kimi_linear``: Kimi-Linear-48B-A3B's block (``kimi_linear.py``)."""

    params_class = KimiLinearAlgorithmParams
    params: KimiLinearAlgorithmParams
    model_class = KimiLinearModel


class Lfm2Model(BackboneModel):
    @staticmethod
    def program():
        from predictionio_tpu.models.sequential import lfm2

        return lfm2


class Lfm2Algorithm(BackboneAlgorithm):
    """``lfm2``: LFM2-8B-A1B at its whole depth (``lfm2.py``)."""

    params_class = Lfm2AlgorithmParams
    params: Lfm2AlgorithmParams
    model_class = Lfm2Model


class SdarModel(BackboneModel):
    @staticmethod
    def program():
        from predictionio_tpu.models.sequential import sdar

        return sdar

    def sanity_check(self) -> None:
        super().sanity_check()
        if len(self.item_vocab) > self.config.mask_token_id:
            raise ValueError(
                f"{len(self.item_vocab)} items reach the mask's id {self.config.mask_token_id}: "
                "the mask is no item"
            )


class GroupedAlgorithm(BackboneAlgorithm):
    """What an algorithm whose answer is GENERATED over a per-batch cache
    shares (``sdar``, ``kanana``): the staged streams are put into GROUPS,
    each of at most the backbone's ``SESSIONS`` sessions and
    ``config.cache_tokens`` stream tokens (a batch is one group but for a
    rare long one), and a group is launched by the algorithm's own
    ``_launch_group``."""

    def batch_limit(self) -> int:
        """The sessions ONE group holds: a batch of more is answered in a
        second group, by passes or steps of its own that cost what the
        first's do however few sessions they carry."""
        return self.model_class.program().SESSIONS

    @staticmethod
    def _groups(model: BackboneModel, streams) -> list[list[int]]:
        """The streams' indices, in order, cut where a group would pass the
        cache's tokens or the group's sessions."""
        most, room = model.program().SESSIONS, model.config.cache_tokens
        groups: list[list[int]] = []
        tokens = held = 0
        for i, (length, members) in enumerate(streams):
            if not groups or tokens + length > room or held + len(members) > most:
                groups.append([])
                tokens = held = 0
            groups[-1].append(i)
            tokens, held = tokens + length, held + len(members)
        return groups

    def _launched(self, model: BackboneModel, queries, sessions, streams, staged) -> list:
        return [
            self._launch_group(
                model, queries, sessions, [streams[i] for i in group], [staged[i] for i in group]
            )
            for group in self._groups(model, streams)
        ]


class SdarAlgorithm(GroupedAlgorithm):
    """``sdar``: SDAR-30B-A3B-Chat's block (``sdar.py``), and the one
    backbone whose answer is GENERATED: ``num`` items in order, each chosen
    given the ones before it, by masked diffusion block by block.

    ``_answer``: the staged streams are put into GROUPS, each of at most
    ``sdar.SESSIONS`` sessions and ``config.cache_tokens`` stream tokens (a
    batch is one group but for a rare long one). A group is: its state and
    an empty cache (``sdar.new_state``: the first device state of this
    engine that outlives a program; its bytes are counted and it is freed
    when the group's last pass has run); a PREFILL a stream, which writes the
    stream's keys and values into the cache where the stream lies; then as
    many ``sdar.denoise_pass`` as the slowest session's schedule has, all
    sessions in each, nothing fetched between them; ``finalize`` fetches the
    items, log-probabilities and steps of a group in one transfer."""

    params_class = SdarAlgorithmParams
    params: SdarAlgorithmParams
    model_class = SdarModel

    def _launch_group(self, model: BackboneModel, queries, sessions, streams, staged):
        """One group's programs: ``(members [(query, row of the state)],
        the answer's handle, the busiest experts' counts, the copies of real
        rows the routers sent out, the experts the passes' real rows reached
        (a handle) of those the passes could have)``."""
        config, program = model.config, model.program()
        t0 = time.perf_counter()
        block, slots = config.block_length, config.generated_slots
        seg = np.full(config.cache_tokens, -1, np.int32)
        commits = np.full((config.most_passes, config.chunk), -1, np.int32)
        tokens = np.full((program.SESSIONS, slots), config.mask_token_id, np.int32)
        step = np.full((program.SESSIONS, slots), -2, np.int32)
        blocks, reach, start = (np.zeros(program.SESSIONS, np.int32) for _ in range(3))
        allowed = np.zeros((program.SESSIONS, config.table_rows), bool)
        members, schedules, offsets, offset = [], [], [], 0
        for (length, packed), (*_, mask) in zip(streams, staged):
            offsets.append(offset)
            for row, (i, at) in enumerate(packed):
                session, s = sessions[i], len(members)
                r = len(session) % block
                num = config.fit(len(session), queries[i].num)
                # whole blocks are the cache's; the rest opens the first generated block
                seg[offset + at : offset + at + len(session) - r] = s
                tokens[s, :r] = session[len(session) - r :]
                step[s, r : r + num] = -1
                blocks[s] = -(-(num + r) // block) if num else 0
                reach[s], start[s] = r + num, len(session) - r
                allowed[s] = mask[row]
                members.append((i, s))
                schedules.append(config.schedule(len(session), num))
                # the passes whose chunk holds one of this session's clean blocks
                for t in (t for t, kind in enumerate(schedules[-1]) if kind == "c"):
                    commits[t, s * block : (s + 1) * block] = s
            offset += length
        allowed[:, config.mask_token_id] = False
        # the host's part ends here: what follows are launches, and a launch
        # waits in the device's queue behind the other batch's programs
        self.instruments.stage_seconds.inc(time.perf_counter() - t0)
        state = program.new_state(
            model.weights, config, seg, commits, tokens, step, blocks, reach, start, allowed
        )
        # the prefill's last layer makes keys and values only: no router there
        cache, counted, layers = state.pop("cache"), [], config.num_hidden_layers
        routed = 0
        for (length, packed), (*stream, _, _), at in zip(streams, staged, offsets):
            real = sum(len(sessions[i]) for i, _ in packed)
            with annotate("pio:seq.launch", bucket=length, rows=1, tokens=real):
                cache, busiest = program.session_vectors(
                    model.weights, cache, *(topk.upload(a, np.int32) for a in stream),
                    np.int32(at), config=config,
                )
            self.instruments.on_launch(length, 1, real, len(packed))
            counted.append(busiest)
            routed += (layers - 1) * real * config.num_experts_per_tok
        state["cache"] = cache
        passes = max(map(len, schedules), default=0)
        kinds = ["d" if any(s[t : t + 1] == "d" for s in schedules) else "c" for t in range(passes)]
        with annotate(
            "pio:seq.denoise", batch=len(queries), sessions=len(members),
            blocks=int(blocks.sum()), passes=passes,
        ):
            for _ in range(passes):
                state = program.denoise_pass(model.weights, state, config=config)
            answer = program.answer_of(state)
        counted.append(state["busiest"])
        # a pass takes a session's whole current block through every layer,
        # whether it denoises or commits
        for s, schedule in enumerate(schedules):
            made = schedule.split("c")
            for b, denoises in enumerate(made):
                rows = min(block, int(reach[s]) - b * block)
                routed += layers * config.num_experts_per_tok * rows * (len(denoises) + (b < len(made) - 1))
        self.instruments.on_generation(
            denoise=kinds.count("d"), commit=kinds.count("c"), blocks=int(blocks.sum()),
            items=int((step == -1).sum()),
            cache_bytes=config.cache_bytes(offset + passes * config.chunk),
        )
        offered = passes * layers * config.num_experts
        return members, answer, counted, routed, (state["reached"], offered)

    def _answer(self, model: BackboneModel, queries, sessions, streams, staged):
        config = model.config
        launched = self._launched(model, queries, sessions, streams, staged)

        def finalize() -> list[PredictedResult]:
            out: list[PredictedResult] = [PredictedResult(())] * len(queries)
            for members, answer, counted, routed, (reached, offered) in launched:
                with annotate("pio:fetch.block"):  # the host blocked on the device
                    packed = np.asarray(answer, np.int32)
                busiest = sum(int(np.asarray(c, np.int64)) for c in counted)
                self.instruments.on_expert_load(busiest, routed / config.num_experts)
                self.instruments.on_copies(routed, 0)
                self.instruments.on_experts_reached(int(np.asarray(reached, np.int64)), offered)
                items, steps = packed[:, 0, :], packed[:, 2, :]
                logp = np.ascontiguousarray(packed[:, 1, :]).view(np.float32)
                for i, s in members:
                    # (a session that holds every item leaves no candidate: the answer ends there)
                    made = np.flatnonzero(steps[s] >= 0)
                    finite = np.isfinite(logp[s, made])
                    made = made[: len(made) if finite.all() else int(np.argmin(finite))]
                    out[i] = PredictedResult(tuple(
                        ItemScore(model.item_vocab[int(items[s, g])], float(logp[s, g]), int(steps[s, g]))
                        for g in made
                    ))
            return out

        return finalize


class KananaModel(BackboneModel):
    @staticmethod
    def program():
        from predictionio_tpu.models.sequential import kanana

        return kanana


class KananaAlgorithm(GroupedAlgorithm):
    """``kanana``: kanana-2-30b-a3b's block (``kanana.py``), the backbone
    whose answer is generated TOKEN BY TOKEN: ``num`` items in order, each the
    likeliest candidate given the session and the items before it, one
    position a step against a latent cache.

    A group (``GroupedAlgorithm``) is: its state and an empty cache
    (``kanana.new_state``); a PREFILL a stream (the expanded form), which
    writes the stream's latents into the cache where the stream lies and each
    session's last hidden state beside them; ``kanana.first_pick`` (the first
    item, from those); then ``max(num) - 1`` times ``kanana.decode_step`` (the
    absorbed form), all sessions in each, nothing fetched between them;
    ``finalize`` fetches a group's items and log-probabilities in one
    transfer. An item's place in the answer is its step, so ``ItemScore.step``
    stays None."""

    params_class = KananaAlgorithmParams
    params: KananaAlgorithmParams
    model_class = KananaModel

    def _launch_group(self, model: BackboneModel, queries, sessions, streams, staged):
        """One group's programs: ``(members [(query, row of the state, its
        items)], the answer's handle, the busiest experts' counts, the copies
        of real rows the routers sent out, the experts the steps' real rows
        reached (a handle) of those the steps could have)``."""
        config, program = model.config, model.program()
        t0 = time.perf_counter()
        seg = np.full(config.cache_tokens, -1, np.int32)
        length, num = (np.zeros(program.SESSIONS, np.int32) for _ in range(2))
        allowed = np.zeros((program.SESSIONS, config.table_rows), bool)
        members, places, offset = [], [], 0
        for (stream_length, packed), (*_, mask) in zip(streams, staged):
            places.append((offset, len(members)))
            for row, (i, at) in enumerate(packed):
                s = len(members)
                seg[offset + at : offset + at + len(sessions[i])] = s
                length[s], num[s] = len(sessions[i]), config.fit(queries[i].num)
                allowed[s] = mask[row]
                members.append((i, s, int(num[s])))
            offset += stream_length
        # the host's part ends here: what follows are launches
        self.instruments.stage_seconds.inc(time.perf_counter() - t0)
        state = program.new_state(model.weights, config, seg, length, num, allowed)
        cache, counted, routed = state.pop("cache"), [], 0
        # a sparse last layer routes each session's last position alone
        spared = 0 if config.is_dense(config.num_hidden_layers - 1) else config.num_experts_per_tok
        for (stream_length, packed), (*stream, last, _), (at, first) in zip(streams, staged, places):
            real = sum(len(sessions[i]) for i, _ in packed)
            with annotate("pio:seq.launch", bucket=stream_length, rows=1, tokens=real):
                cache, busiest = program.session_vectors(
                    model.weights, cache, *(topk.upload(a, np.int32) for a in (*stream, last[None])),
                    np.int32(at), np.int32(first), config=config,
                )
            self.instruments.on_launch(stream_length, 1, real, len(packed))
            counted.append(busiest)
            routed += config.routed_copies(real) - spared * (real - len(packed))
        state["cache"] = cache
        steps = max(int(num.max()) - 1, 0)
        with annotate("pio:seq.decode", batch=len(queries), sessions=len(members), steps=steps):
            state = program.first_pick(model.weights, state, config=config)
            for _ in range(steps):
                state = program.decode_step(model.weights, state, config=config)
            answer = program.answer_of(state)
        counted.append(state["busiest"])
        stepped = int(np.maximum(num - 1, 0).sum())  # real rows of the steps, and the positions they cached
        routed += config.routed_copies(stepped)
        self.instruments.on_generation(
            decode=steps, items=int(num.sum()), cache_bytes=config.cache_bytes(int(length.sum()) + stepped)
        )
        offered = steps * config.sparse_layers * config.n_routed_experts
        return members, answer, counted, routed, (state["reached"], offered)

    def _answer(self, model: BackboneModel, queries, sessions, streams, staged):
        config = model.config
        launched = self._launched(model, queries, sessions, streams, staged)

        def finalize() -> list[PredictedResult]:
            out: list[PredictedResult] = [PredictedResult(())] * len(queries)
            for members, answer, counted, routed, (reached, offered) in launched:
                with annotate("pio:fetch.block"):  # the host blocked on the device
                    packed = np.asarray(answer, np.int32)
                busiest = sum(int(np.asarray(c, np.int64)) for c in counted)
                self.instruments.on_expert_load(busiest, routed / config.n_routed_experts)
                self.instruments.on_copies(routed, 0)
                self.instruments.on_experts_reached(int(np.asarray(reached, np.int64)), offered)
                items = packed[:, 0, :]
                logp = np.ascontiguousarray(packed[:, 1, :]).view(np.float32)
                for i, s, made in members:
                    # (a session that holds every item leaves no candidate: the answer ends there)
                    finite = np.isfinite(logp[s, :made])
                    made = made if finite.all() else int(np.argmin(finite))
                    out[i] = PredictedResult(tuple(
                        ItemScore(model.item_vocab[int(items[s, g])], float(logp[s, g])) for g in range(made)
                    ))
            return out

        return finalize


# ---------------------------------------------------------------------------
# Serving / factory
# ---------------------------------------------------------------------------


class Serving(BaseServing):
    def serve(self, query: Query, predictions: Sequence[PredictedResult]):
        return predictions[0]


def engine_factory() -> Engine:
    return Engine(
        DataSource,
        Preparator,
        {
            "markov": MarkovAlgorithm,
            "attention": AttentionAlgorithm,
            "olmoe": OlmoeAlgorithm,
            "kimi_linear": KimiLinearAlgorithm,
            "sdar": SdarAlgorithm,
            "lfm2": Lfm2Algorithm,
            "kanana": KananaAlgorithm,
        },
        Serving,
        query_class=Query,
    )
