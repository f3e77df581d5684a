"""The sequential template's ``olmoe`` scorer (``models/sequential``:
``OlmoeAlgorithm``, ``OlmoeModel``, ``olmoe.session_vectors``, ``ops/moe``)
as a system under test: what a configuration file with ``"engine":
"sequential_olmoe"`` is built and driven through.

``serving(ctx)`` deploys a seeded weight tree and 400,000 users' seeded
session tails behind the program's own ``QueryServer`` (in this process, on
its own event loop and thread, over real TCP), with ``pio deploy``'s
``ServerConfig`` defaults but for what the configuration file states. The
event store and ``pio train`` are bypassed (the model is built as
``OlmoeAlgorithm.train`` builds it, from arrays instead of events); PERF.md
records one ``pio train`` → ``pio deploy`` → query at these widths.

``check`` holds the served answers to the plain reference
(``benchmark/reference_olmoe.py``), outside the window, on the SAME bf16
weights upcast to float32, layer by layer (one layer's experts in float32
are 1.6 GB; the 8 layers' would not fit beside the served model), one session
at a time at its true length.

The program's names are imported at the top: a checkout that lacks them (the
parent of the PR that added this cell) fails at once, with no result line.
"""

from __future__ import annotations

import asyncio
import json
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_olmoe as reference
from benchmark import schedule
from benchmark.engines import recommendation_als
from benchmark.engines.recommendation_als import MEMORY_STORAGE, _free_port
from predictionio_tpu.models.sequential import olmoe
from predictionio_tpu.models.sequential.engine import OlmoeModel

ENGINE_FACTORY = "predictionio_tpu.models.sequential.engine_factory"
# serving answers recomputed against the plain reference after the window
CHECKED_QUERIES = 16
# the keys of the published config.json, as the configuration file runs them
PUBLISHED = (
    "attention_bias", "clip_qkv", "hidden_act", "hidden_size", "intermediate_size",
    "max_position_embeddings", "model_type", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "rms_norm_eps", "rope_scaling", "rope_theta", "tie_word_embeddings", "vocab_size",
)
# How far a served score may lie from the reference's logit for that item,
# logits being of unit order (the weights' scaling). What runs on the chip:
# products with bf16 operands and float32 accumulation (2^-9 an operand)
# through 8 layers of attention and experts, against float32 at `highest` on
# the same weights. That error is SMALL and everywhere: over the builder's
# 10 runs of 17 answers the median answer's worst score is off by 0.0057 to
# 0.0086 (PERF.md, PR 26). And it is LARGE and rare: 4.3% of (token, layer)
# pairs have their router's 8th and 9th weight within 1e-4, a stream off by
# 1e-2 tips some of them, and a token that takes another expert than the
# reference's moves by one expert's output; an answer in nine is off by
# 0.025 to 0.10 that way. One number cannot hold both (fp8 expert weights
# put the WORST answer at 0.06 to 0.09, inside the flips' range), so there
# are two: the MEDIAN answer within SCORE_TOLERANCE, under twice the worst
# median seen, which fp8 experts (median 0.030, 0.031) and 7 experts a
# token (0.071, 0.076) fail by a factor of two and five; and EVERY answer
# within FLIP_TOLERANCE, two and a half times the worst seen (0.1024). The
# second limit tells no precision from another (7 experts and fp8 pass it):
# it guards against GROSS faults, another session's or another row's answer,
# which are off by the logits' own order (the tests plant one).
SCORE_TOLERANCE = 0.016
FLIP_TOLERANCE = 0.25
# a router weight margin under this counts as a tie that bf16 inputs decide:
# the weights are of order 1/64, and a stream off by 1e-2 moves them by 1e-4
ROUTER_TIE = 1e-4
# lengths are dealt so that every so many users, in the order the traffic
# first asks for them, hold the population's mix of lengths
DEALT_BLOCK = 256


def variant_of(config: dict, seed: int) -> dict:
    """The engine variant: the configuration file's published keys, verbatim,
    are the algorithm's parameters; ``--seed`` draws the weights."""
    variant = json.loads(json.dumps(config["variant"]))
    params = variant["algorithms"][0]["params"]
    params.update({key: config[key] for key in PUBLISHED})
    params["seed"] = int(seed) % (2**31)
    return variant


def session_lengths(config: dict) -> np.ndarray:
    """Every user's session length, sorted: the multiset the configuration
    fixes (``structure_seed``), whatever ``--seed`` is."""
    spec = config["session_length"]
    rng = np.random.default_rng(int(config["structure_seed"]))
    draws = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], int(config["n_users"])))
    return np.sort(np.clip(np.rint(draws), spec["min"], spec["max"]).astype(np.int64))


def stream_of(ctx, n_users: int):
    """The users the cell's driver will ask for, in order: the driver's own
    draw (``benchmark/schedule``), by the traffic's kind. A kind this does not
    know gets none, and lengths dealt at random."""
    traffic = ctx.traffic
    if traffic["kind"] == "closed_loop_http":
        return schedule.closed_loop_users(ctx.seed, n_users, traffic, int(traffic["users_drawn"]))
    if traffic["kind"] == "open_loop_http":
        return schedule.open_loop_schedule(ctx.seed, n_users, traffic, ctx.seconds)[1]
    print(f"benchmark: no stream known for {traffic['kind']}: lengths dealt at random", file=sys.stderr)
    return np.empty(0, np.int64)


def sessions_of(config: dict, seed: int, asked=()):
    """``(tails, offsets)`` of all users: ``--seed`` draws the items, uniform
    over the vocabulary, and says which user has which length.

    The stream is STRATIFIED, and that is a property of this configuration's
    traffic. ``asked`` are the users the window's generators will ask for, in
    order (``stream_of``). Every ``DEALT_BLOCK`` users in the order they are
    first asked for hold the population's mix of lengths (a low-discrepancy
    sequence over the SORTED multiset), in random order inside the block: a
    batch of 32 is all but an independent sample, so long sessions cluster
    and follow each other as they would, while a window of 4,000 answers
    holds the same work whatever the seed. Dealt at random over all users, a
    window's answers are an independent sample of a distribution whose
    standard deviation is 1.2 times its mean: answers a second then spread
    by 3.9% between seeds at a token rate steady to 1% (PERF.md, PR 26),
    which is the yardstick's sampling noise, not the system's. The multiset,
    the users' popularity and what a user is asked are untouched; the users
    never asked for get the rest at random."""
    rng = np.random.default_rng([int(seed), 1])
    lengths_sorted = session_lengths(config)
    n = len(lengths_sorted)
    # a Kronecker sequence from a seeded start: every stretch of it spreads
    # evenly over [0, 1); its ranks turn that into a permutation of the multiset
    keys = (rng.random() + np.arange(n) * 0.6180339887498949) % 1.0
    dealt = lengths_sorted[np.argsort(np.argsort(keys))]
    dealt = dealt[np.lexsort((rng.random(n), np.arange(n) // DEALT_BLOCK))]
    asked = np.asarray(asked, np.int64)
    _, first = np.unique(asked, return_index=True)
    in_order = asked[np.sort(first)]
    others = np.setdiff1d(np.arange(n), in_order, assume_unique=True)
    lengths = np.empty(n, np.int64)
    lengths[np.concatenate([in_order, rng.permutation(others)])] = dealt
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    tails = rng.integers(0, int(config["vocab_size"]), int(offsets[-1]), dtype=np.int32)
    return tails, offsets


def reference_logits(weights: dict, config: dict, sessions: list) -> tuple[list, float]:
    """The reference's logits at each session's last position, and the share
    of (token, layer) pairs whose router leaves its k-th and (k+1)-th expert
    within ``ROUTER_TIE``. Layer by layer: one layer's weights are upcast to
    float32, every session goes through it alone, at its own length."""
    # the reference compiles a program for every session length: one-offs that
    # would push the served programs out of the persistent compile cache
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    @jax.jit
    def step(x, layer):
        h = reference.attention_block(x, layer, config)
        n2 = reference.rms_norm(h, layer["w_post"], config["rms_norm_eps"])
        margin = reference.router_margin(reference.router_probs(n2, layer), config["num_experts_per_tok"])
        return reference.moe_block(h, layer, config), jnp.sum(margin < ROUTER_TIE)

    states = [reference.embed(weights, tokens) for tokens in sessions]
    ties = total = 0
    for i in range(int(config["num_hidden_layers"])):
        layer = jax.tree.map(lambda a: a.astype(jnp.float32), olmoe.layer_of(weights, i))
        for s, x in enumerate(states):
            states[s], tied = step(x, layer)
            ties += int(tied)
            total += int(x.shape[0])
        del layer
    logits = [np.asarray(reference.head(weights, config, x[-1])) for x in states]
    return logits, ties / max(total, 1)


def check_answer(logits: np.ndarray, session: np.ndarray, ids, scores, n_items: int):
    """One served answer against the reference's logits: ``(ids_ok, by_set,
    error)``. ``error`` is the largest |served score − reference logit| over
    its items. The ids are the reference's top-k (its session's items left
    out) in its order, except where the reference scores the two candidates
    for a place within twice ``SCORE_TOLERANCE``. An answer that fails that
    and is itself off by more (a tipped router moved its scores) falls back
    to the SET: each served item within twice the answer's own error,
    ``FLIP_TOLERANCE`` at most, of the reference's k-th; ``by_set`` flags it."""
    ids = np.asarray(ids, np.int64)
    error = float(np.abs(np.asarray(scores, np.float64) - logits[ids]).max()) if len(ids) else np.inf
    allowed = np.ones(len(logits), bool)
    allowed[n_items:] = False
    allowed[session] = False
    order = np.argsort(-np.where(allowed, logits, -np.inf), kind="stable")[: len(ids)]
    if not allowed[ids].all() or len(set(ids.tolist())) != len(ids):
        return False, False, error
    if (np.abs(logits[ids] - logits[order]) <= 2 * SCORE_TOLERANCE)[ids != order].all():
        return True, False, error
    by_set = SCORE_TOLERANCE < error <= FLIP_TOLERANCE and bool(
        (logits[ids] >= logits[order[-1]] - 2 * error).all()
    )
    return by_set, by_set, error


def count_wrong(errors: list, ids_ok: list) -> int:
    """How many of a run's checked answers count as wrong: those with other
    ids than the reference's or a score beyond ``FLIP_TOLERANCE``; and, where
    the MEDIAN answer is beyond ``SCORE_TOLERANCE`` (the arithmetic is not
    what the configuration states), every answer beyond it."""
    wrong = sum(1 for error, ok in zip(errors, ids_ok) if not ok or error > FLIP_TOLERANCE)
    if errors and float(np.median(errors)) > SCORE_TOLERANCE:
        wrong = max(wrong, sum(1 for error in errors if error > SCORE_TOLERANCE))
    return wrong


class Serving(recommendation_als.Serving):
    """One deployment: weights and sessions from the seed, the program's
    server in front. ``counters`` and ``stop`` (and the path and the reply's
    item marker) are the recommendation deployment's: the same server."""

    def __init__(self, ctx):
        from predictionio_tpu.data.storage.registry import Storage
        from predictionio_tpu.models.sequential import engine_factory
        from predictionio_tpu.workflow.create_server import QueryServer, ServerConfig
        from predictionio_tpu.workflow.engine_loader import EngineManifest

        config = ctx.config
        self.config = config
        self.n_users = int(config["n_users"])
        self.num = int(ctx.traffic["num"])
        self.items_expected = self.num
        self.body_format = '{"user":"u%%d","num":%d}' % self.num
        self.parts = {}
        engine = engine_factory()
        engine_params = engine.engine_params_from_variant(variant_of(config, ctx.seed))
        params = engine_params.algorithms[0][1]
        self.model_config = params.config()

        t = time.monotonic()
        weights = jax.block_until_ready(olmoe.init_weights(self.model_config, params.seed))
        self.parts["weights_s"] = time.monotonic() - t

        t = time.monotonic()
        asked = stream_of(ctx, self.n_users)
        # whom the generators ask while they keep replies for the check
        self.asked_early = set(asked[: len(asked) // 10].tolist())
        tails, offsets = sessions_of(config, ctx.seed, asked)
        n_items = int(config["vocab_size"])
        self.model = OlmoeModel(
            self.model_config,
            list(map("i%d".__mod__, range(n_items))),
            list(map("u%d".__mod__, range(self.n_users))),
            tails,
            offsets,
            weights,
        )
        self.model.user_index()
        self.parts["sessions_s"] = time.monotonic() - t

        server_config = ServerConfig(
            ip="127.0.0.1", port=_free_port(), **config.get("server_config", {})
        )
        self.port = server_config.port
        self.loop = asyncio.new_event_loop()
        self.server = QueryServer(
            engine=engine,
            engine_params=engine_params,
            models=[self.model],
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
                # start() warms every program shape, as a deploy does
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
        return {key: self.config[key] for key in PUBLISHED}

    def ask(self, user: int) -> str:
        """One query over the served HTTP path, as a generator sends it."""
        request = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{self.path}",
            (self.body_format % user).encode(),
            {"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request, timeout=60) as resp:
            return resp.read().decode()

    def check(self, kept: dict[int, str]):
        """``kept`` maps a user index to the reply body it was sent:
        ``(checked, wrong, worst |Δscore|)`` against the plain reference on
        the same weights. The generators draw which replies are kept, so one
        user of the longest bucket is asked here as well, over the same HTTP
        path, after the window."""
        strangers = sorted(set(kept) - self.asked_early) if self.asked_early else []
        if strangers:
            raise RuntimeError(
                f"the generators asked for users {strangers[:8]}, whom `stream_of` did not "
                "expect in the window: the lengths were not dealt along the stream that ran"
            )
        kept = dict(kept)
        model = self.model
        lengths = np.diff(model.offsets)
        longest = np.flatnonzero(lengths > self.model_config.buckets()[-2])
        if len(longest):
            user = int(longest[0])
            kept.setdefault(user, self.ask(user))
        users = sorted(kept)
        sessions = [model.tails[model.offsets[u] : model.offsets[u + 1]] for u in users]
        logits, tie_share = reference_logits(model.weights, self.shapes(), sessions)
        errors, ids_ok, by_set = [], [], 0
        for user, session, ref in zip(users, sessions, logits):
            answer = json.loads(kept[user])["itemScores"]
            ids = [int(row["item"][1:]) for row in answer]
            ok, fell_back, error = check_answer(
                ref, session, ids, [row["score"] for row in answer], len(model.item_vocab)
            )
            errors.append(error)
            ids_ok.append(ok and len(ids) == self.num)
            by_set += fell_back
            if not ids_ok[-1] or error > FLIP_TOLERANCE:
                print(
                    f"benchmark: user {user} (session of {len(session)}): served {ids}, "
                    f"off the reference by {error:.4f}",
                    file=sys.stderr,
                )
        wrong, worst = count_wrong(errors, ids_ok), max(errors)
        print(
            f"benchmark: checked {len(users)} answers (sessions of {min(map(len, sessions))} to "
            f"{max(map(len, sessions))} items), worst |served - reference| by answer: median "
            f"{np.median(errors):.4f} of {SCORE_TOLERANCE}, largest {worst:.4f} of {FLIP_TOLERANCE} "
            f"({sorted(round(e, 4) for e in errors)}), {by_set} with the reference's ids only "
            f"as a set; {100 * tie_share:.3f}% of (token, layer) "
            f"pairs have their router's 8th and 9th weight within {ROUTER_TIE}",
            file=sys.stderr,
        )
        return len(users), wrong, worst


def serving(ctx) -> Serving:
    return Serving(ctx)
