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

A BACKBONE puts a language model's block in the same place. Their host side
is ``backbone.py``, below this module, whose table ``BACKBONES`` names them;
``engine_factory`` spreads it, and nothing here knows one from another. This
module is the top of the package: every name the package had before
``backbone.py`` and ``records.py`` were cut out of it is importable from here.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Iterator, Sequence

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
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.store.event_store import resolve_app
from predictionio_tpu.e2.markov_chain import MarkovChainModel, train_markov_chain
# what lies below this module and is read from it, the top of the package, by
# `__init__`, stored models' class paths, the benchmark and the tests
from predictionio_tpu.models.sequential.backbone import (  # noqa: F401
    BACKBONES, BackboneAlgorithm, BackboneModel, GraniteAlgorithm, GraniteModel, GroupedAlgorithm, KananaAlgorithm,
    KananaModel,
    KimiLinearAlgorithm, KimiLinearModel, Lfm2Algorithm, Lfm2Model, OlmoeAlgorithm, OlmoeModel,
    SdarAlgorithm, SdarModel, session_tails,
)
from predictionio_tpu.models.sequential.granite import GraniteAlgorithmParams  # noqa: F401
from predictionio_tpu.models.sequential.kanana import KananaAlgorithmParams  # noqa: F401
from predictionio_tpu.models.sequential.kimi_linear import KimiLinearAlgorithmParams  # noqa: F401
from predictionio_tpu.models.sequential.lfm2 import Lfm2AlgorithmParams  # noqa: F401
from predictionio_tpu.models.sequential.olmoe import OlmoeAlgorithmParams  # noqa: F401
from predictionio_tpu.models.sequential.records import (  # noqa: F401
    ActualResult, BackboneParams, ItemScore, PredictedResult, Query, TrainingData,
)
from predictionio_tpu.models.sequential.sdar import SdarAlgorithmParams  # noqa: F401
from predictionio_tpu.ops import topk
from predictionio_tpu.workflow.context import WorkflowContext

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
    # `attention` scorer's window only: a backbone takes a session's last
    # `config.max_session` items, packed into token streams
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
# Serving / factory
# ---------------------------------------------------------------------------


class Serving(BaseServing):
    def serve(self, query: Query, predictions: Sequence[PredictedResult]):
        return predictions[0]


def engine_factory() -> Engine:
    return Engine(
        DataSource,
        Preparator,
        {"markov": MarkovAlgorithm, "attention": AttentionAlgorithm, **BACKBONES},
        Serving,
        query_class=Query,
    )
