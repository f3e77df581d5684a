"""The host side every backbone of the sequential engine shares.

A backbone puts a language model's block where the ``attention`` scorer has
its one attention pass: the session's items are the tokens, and the answer is
the next items scored by one prefill (``BackboneAlgorithm``) or ``num`` items
GENERATED in order over a cache that lives for the batch
(``GroupedAlgorithm``). Nothing of ONE backbone is here: its device programs,
its ``Config`` and its published-key parameters are its module's, below this
file, and the classes that tie a module to this host side stand at the foot,
under ``BACKBONES``, the one table that names them. Every backbone's weights
are drawn from a seed, not fitted (ROADMAP R7).

A backbone's module provides ``Config`` (frozen: the programs' static
argument) with ``table_rows``, ``max_session``, ``stream_shapes()``,
``routed_copies()`` and ``even_expert_load()``; its parameters
(``records.BackboneParams``); ``session_vectors`` (the prefill),
``init_weights``, ``SESSION_ALIGN``, ``TOKEN_BUDGET``, ``STACKED_ROWS``; and,
a GENERATING one, ``SESSIONS``, ``new_state``, ``answer_of``,
``config.cache_tokens`` and the programs its driver launches in between.
Adding one: docs/sequential.md, "Adding a backbone".
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Sequence

import numpy as np

from predictionio_tpu.controller import JaxAlgorithm, Params, PersistentModel, SanityCheck
from predictionio_tpu.models.sequential import granite, kanana, kimi_linear, lfm2, olmoe, sdar
from predictionio_tpu.models.sequential.metrics import BackboneInstruments
from predictionio_tpu.models.sequential.olmoe import load_array, load_header, save_arrays
from predictionio_tpu.models.sequential.records import ItemScore, PredictedResult, Query, TrainingData
from predictionio_tpu.obs.jaxprof import annotate
from predictionio_tpu.ops import topk
from predictionio_tpu.workflow.context import WorkflowContext


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
    ``SESSION_ALIGN``): the subclass's ``module``. A subclass a backbone
    keeps a stored model's class path telling which."""

    module = None  # a subclass's: the backbone's module

    @classmethod
    def program(cls):
        return cls.module

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
        header = {
            "config": dataclasses.asdict(self.config),
            "item_vocab": self.item_vocab,
            "users": self.users,
        }
        arrays = {**self.weights, "tails": self.tails, "offsets": self.offsets}
        save_arrays(os.path.join(base_dir, instance_id), header, arrays)
        return True

    @classmethod
    def load(cls, instance_id: str, params: Any, base_dir: str) -> "BackboneModel":
        import jax

        directory = os.path.join(base_dir, instance_id)
        header = load_header(directory)
        arrays = {}
        for name, spec in header["arrays"].items():
            host = load_array(directory, name, spec)
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
    """A query answered through a language model's block: what every
    backbone's algorithm shares, which is everything but the backbone's module
    (``model_class.program()``: this file's docstring says what it must
    provide), its parameters (``params_class``) and HOW a staged batch is
    answered (``_answer``, the one hook): next-item scoring by one prefill
    (here) or a generation over the batch's cache (``GroupedAlgorithm``).

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


class GroupedAlgorithm(BackboneAlgorithm):
    """What an algorithm whose answer is GENERATED over a per-batch cache
    shares: the staged streams are put into GROUPS, each of at most the
    backbone's ``SESSIONS`` sessions and ``config.cache_tokens`` stream tokens
    (a batch is one group but for a rare long one). A group is: its state and
    an empty cache (the module's ``new_state``: device state that outlives a
    program; its bytes are counted and it is freed when the group's last
    program has run); a PREFILL a stream, which writes into the cache where
    the stream lies; then the driver's own programs, all sessions in each,
    nothing fetched between them. ``finalize`` fetches a group's answer in
    one transfer.

    A driver gives ``_launch_group``, which returns ``(members [(query, row
    of the state, ...)], the answer's handle, the busiest experts' counts,
    (the copies of real rows the routers sent out, the experts they are
    spread over), (the experts its programs' real rows reached (a handle),
    those they could have))``, and ``_made``: a member's rows of the packed
    answer read as ``(its items' places, each one's step or None)``."""

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

    @staticmethod
    def _walk(model: BackboneModel, sessions, streams, staged, block: int = 1):
        """A group's streams as they lie end to end in its cache: ``(seg
        [cache tokens]: the session a slot's item is of, whole ``block``s of a
        session only, -1 elsewhere; allowed [SESSIONS, table rows]: the staged
        masks, a session a row; members [(query, row)]; a stream's (offset in
        the cache, first session's row); the offset they end at)``."""
        seg = np.full(model.config.cache_tokens, -1, np.int32)
        allowed = np.zeros((model.program().SESSIONS, model.config.table_rows), bool)
        members, places, offset = [], [], 0
        for (length, packed), (*_, mask) in zip(streams, staged):
            places.append((offset, len(members)))
            for row, (i, at) in enumerate(packed):
                s = len(members)
                seg[offset + at : offset + at + len(sessions[i]) // block * block] = s
                allowed[s] = mask[row]
                members.append((i, s))
            offset += length
        return seg, allowed, members, places, offset

    def _prefill(self, model: BackboneModel, sessions, streams, staged, places, state, beyond):
        """One prefill a stream into ``state["cache"]``; ``beyond(last, offset,
        first row)`` gives what the backbone's ``session_vectors`` takes
        behind the stream's tokens, segments and positions: ``(arrays,
        scalars)``. Returns ``(the busiest experts' counts, a stream's (real
        tokens, sessions))``."""
        config, session_vectors = model.config, model.program().session_vectors
        cache, counted, reals = state.pop("cache"), [], []
        for (length, packed), (*stream, last, _), place in zip(streams, staged, places):
            real = sum(len(sessions[i]) for i, _ in packed)
            with annotate("pio:seq.launch", bucket=length, rows=1, tokens=real):
                arrays, scalars = beyond(last, *place)
                cache, busiest = session_vectors(
                    model.weights, cache, *(topk.upload(a, np.int32) for a in (*stream, *arrays)),
                    *scalars, config=config,
                )
            self.instruments.on_launch(length, 1, real, len(packed))
            counted.append(busiest)
            reals.append((real, len(packed)))
        state["cache"] = cache
        return counted, reals

    def _answer(self, model: BackboneModel, queries, sessions, streams, staged):
        launched = [
            self._launch_group(
                model, queries, sessions, [streams[i] for i in group], [staged[i] for i in group]
            )
            for group in self._groups(model, streams)
        ]

        def finalize() -> list[PredictedResult]:
            out: list[PredictedResult] = [PredictedResult(())] * len(queries)
            for members, answer, counted, (routed, experts), (reached, offered) in launched:
                with annotate("pio:fetch.block"):  # the host blocked on the device
                    packed = np.asarray(answer, np.int32)
                busiest = sum(int(np.asarray(c, np.int64)) for c in counted)
                self.instruments.on_expert_load(busiest, routed / experts)
                self.instruments.on_copies(routed, 0)
                self.instruments.on_experts_reached(int(np.asarray(reached, np.int64)), offered)
                items = packed[:, 0, :]
                logp = np.ascontiguousarray(packed[:, 1, :]).view(np.float32)
                for i, s, *kept in members:
                    made, steps = self._made(packed[s], *kept)
                    # (a session that holds every item leaves no candidate: the answer ends there)
                    finite = np.isfinite(logp[s, made])
                    made = made[: len(made) if finite.all() else int(np.argmin(finite))]
                    out[i] = PredictedResult(tuple(
                        ItemScore(model.item_vocab[int(items[s, g])], float(logp[s, g]), step)
                        for g, step in zip(made, steps)
                    ))
            return out

        return finalize


# The backbones: a module's model class (a stored model's class path names
# it), its algorithm class and, last, the table. Nothing above names one.


class OlmoeModel(BackboneModel):
    module = olmoe


class OlmoeAlgorithm(BackboneAlgorithm):
    """``olmoe``: OLMoE-1B-7B (``olmoe.py``)."""

    params_class = olmoe.OlmoeAlgorithmParams
    params: olmoe.OlmoeAlgorithmParams
    model_class = OlmoeModel


class KimiLinearModel(BackboneModel):
    module = kimi_linear


class KimiLinearAlgorithm(BackboneAlgorithm):
    """``kimi_linear``: Kimi-Linear-48B-A3B's block (``kimi_linear.py``)."""

    params_class = kimi_linear.KimiLinearAlgorithmParams
    params: kimi_linear.KimiLinearAlgorithmParams
    model_class = KimiLinearModel


class Lfm2Model(BackboneModel):
    module = lfm2


class Lfm2Algorithm(BackboneAlgorithm):
    """``lfm2``: LFM2-8B-A1B at its whole depth (``lfm2.py``)."""

    params_class = lfm2.Lfm2AlgorithmParams
    params: lfm2.Lfm2AlgorithmParams
    model_class = Lfm2Model


class SdarModel(BackboneModel):
    module = sdar

    def sanity_check(self) -> None:
        super().sanity_check()
        if len(self.item_vocab) > self.config.mask_token_id:
            raise ValueError(
                f"{len(self.item_vocab)} items reach the mask's id {self.config.mask_token_id}: "
                "the mask is no item"
            )


class SdarAlgorithm(GroupedAlgorithm):
    """``sdar``: SDAR-30B-A3B-Chat's block (``sdar.py``: what a batch is on
    the device), the backbone whose answer is generated BLOCK BY BLOCK by
    masked diffusion: behind a group's prefills, as many ``sdar.denoise_pass``
    as the slowest session's schedule has."""

    params_class = sdar.SdarAlgorithmParams
    params: sdar.SdarAlgorithmParams
    model_class = SdarModel

    @staticmethod
    def _made(packed):
        made = np.flatnonzero(packed[2] >= 0)
        return made, packed[2][made].tolist()

    def _launch_group(self, model: BackboneModel, queries, sessions, streams, staged):
        config, program = model.config, model.program()
        t0 = time.perf_counter()
        block, slots = config.block_length, config.generated_slots
        commits = np.full((config.most_passes, config.chunk), -1, np.int32)
        tokens = np.full((program.SESSIONS, slots), config.mask_token_id, np.int32)
        step = np.full((program.SESSIONS, slots), -2, np.int32)
        blocks, reach, start = (np.zeros(program.SESSIONS, np.int32) for _ in range(3))
        # whole blocks are the cache's; the rest opens the first generated block
        seg, allowed, members, places, offset = self._walk(model, sessions, streams, staged, block)
        allowed[:, config.mask_token_id] = False
        schedules = []
        for i, s in members:
            session = sessions[i]
            r = len(session) % block
            num = config.fit(len(session), queries[i].num)
            tokens[s, :r] = session[len(session) - r :]
            step[s, r : r + num] = -1
            blocks[s] = -(-(num + r) // block) if num else 0
            reach[s], start[s] = r + num, len(session) - r
            schedules.append(config.schedule(len(session), num))
            # the passes whose chunk holds one of this session's clean blocks
            for t in (t for t, kind in enumerate(schedules[-1]) if kind == "c"):
                commits[t, s * block : (s + 1) * block] = s
        # the host's part ends here: what follows are launches, and a launch
        # waits in the device's queue behind the other batch's programs
        self.instruments.stage_seconds.inc(time.perf_counter() - t0)
        state = program.new_state(
            model.weights, config, seg, commits, tokens, step, blocks, reach, start, allowed
        )
        counted, reals = self._prefill(
            model, sessions, streams, staged, places, state, lambda last, at, first: ((), (np.int32(at),))
        )
        # the prefill's last layer makes keys and values only: no router there
        layers = config.num_hidden_layers
        routed = sum((layers - 1) * real * config.num_experts_per_tok for real, _ in reals)
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
        return members, answer, counted, (routed, config.num_experts), (state["reached"], offered)


class KananaModel(BackboneModel):
    module = kanana


class KananaAlgorithm(GroupedAlgorithm):
    """``kanana``: kanana-2-30b-a3b's block (``kanana.py``: what a batch is
    on the device), the backbone whose answer is generated TOKEN BY TOKEN
    against a latent cache: behind a group's prefills, ``kanana.first_pick``
    and ``max(num) - 1`` times ``kanana.decode_step``. An item's place in the
    answer is its step, so ``ItemScore.step`` stays None."""

    params_class = kanana.KananaAlgorithmParams
    params: kanana.KananaAlgorithmParams
    model_class = KananaModel

    @staticmethod
    def _made(packed, num):
        return np.arange(num), [None] * num

    def _launch_group(self, model: BackboneModel, queries, sessions, streams, staged):
        config, program = model.config, model.program()
        t0 = time.perf_counter()
        length, num = (np.zeros(program.SESSIONS, np.int32) for _ in range(2))
        seg, allowed, members, places, _ = self._walk(model, sessions, streams, staged)
        for i, s in members:
            length[s], num[s] = len(sessions[i]), config.fit(queries[i].num)
        members = [(i, s, int(num[s])) for i, s in members]
        # the host's part ends here: what follows are launches
        self.instruments.stage_seconds.inc(time.perf_counter() - t0)
        state = program.new_state(model.weights, config, seg, length, num, allowed)
        counted, reals = self._prefill(
            model, sessions, streams, staged, places, state,
            lambda last, at, first: ((last[None],), (np.int32(at), np.int32(first))),
        )
        # a sparse last layer routes each session's last position alone
        spared = 0 if config.is_dense(config.num_hidden_layers - 1) else config.num_experts_per_tok
        routed = sum(config.routed_copies(real) - spared * (real - held) for real, held in reals)
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
        return members, answer, counted, (routed, config.n_routed_experts), (state["reached"], offered)


class GraniteModel(BackboneModel):
    module = granite


class GraniteAlgorithm(BackboneAlgorithm):
    """``granite``: granite-4.0-h-small's block (``granite.py``)."""

    params_class = granite.GraniteAlgorithmParams
    params: granite.GraniteAlgorithmParams
    model_class = GraniteModel


BACKBONES = {
    "olmoe": OlmoeAlgorithm,
    "kimi_linear": KimiLinearAlgorithm,
    "sdar": SdarAlgorithm,
    "lfm2": Lfm2Algorithm,
    "kanana": KananaAlgorithm,
    "granite": GraniteAlgorithm,
}

# a stored model names its class as ``<module>.<name>``
# (``controller.make_persistent_model``), and every store written before this
# file was names the engine's module, the top of the package, which imports
# these classes. They go on giving that name, so that a store written on
# either side of the move loads on the other.
for _algorithm in BACKBONES.values():
    _algorithm.model_class.__module__ = "predictionio_tpu.models.sequential.engine"
