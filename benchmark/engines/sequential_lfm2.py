"""The sequential template's ``lfm2`` scorer (``models/sequential``:
``Lfm2Algorithm``, ``Lfm2Model``, ``lfm2.session_vectors``,
``ops/linear_attention.short_conv``, ``ops/attention``, ``ops/moe``) as a
system under test: what a configuration file with ``"engine":
"sequential_lfm2"`` is built and driven through.

The deployment is ``sequential_olmoe.Serving``'s with another backbone: the
same server, users, session lengths, stratified stream and check of the
served answers, so this module holds only what differs: how the
configuration file's keys become the algorithm's parameters (the file gives
the chip's SHARE under ``num_experts`` and the published count beside it),
which model is built, and the reference (``benchmark/reference_lfm2.py``)
with the limits measured for it.

The program's names are imported at the top: a checkout that lacks them (the
parent of the PR that added this cell) fails at once, with no result line.
"""

from __future__ import annotations

import asyncio
import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_lfm2 as reference
from benchmark.engines import sequential_olmoe
from benchmark.engines.recommendation_als import MEMORY_STORAGE, _free_port
from benchmark.engines.sequential_olmoe import ENGINE_FACTORY, sessions_of, stream_of
from predictionio_tpu.models.sequential import lfm2
from predictionio_tpu.models.sequential.engine import Lfm2Model
from predictionio_tpu.ops import moe

# serving answers recomputed against the plain reference after the window
CHECKED_QUERIES = 64
# the keys of the published config.json, as the configuration file runs them
PUBLISHED = (
    "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size", "layer_types",
    "max_position_embeddings", "model_type", "moe_intermediate_size", "norm_eps", "norm_topk_prob",
    "num_attention_heads", "num_dense_layers", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "rope_theta", "routed_scaling_factor",
    "use_expert_bias", "vocab_size",
)
# How far a served score may lie from the reference's logit for that item,
# logits being of unit order. The two limits of ``sequential_olmoe`` (the
# MEDIAN answer tight, EVERY answer loosely) at this backbone's own readings
# (PERF.md, PR 41, "The check"), which are Kimi-Linear's in size and for its
# reason: a sigmoid router whose 32 scores lie so close that in 3.4% of all
# (token, sparse layer) pairs the 4th and the 5th of score + bias are within
# 1e-3, over 22 sparse layers; a stream that bf16 products put off by 1e-2
# tips them, and a tipped token swaps one expert of weight about a quarter for
# another. Over the builder's nineteen sound checks of 65 answers the median
# answer's worst score is off by 0.140 to 0.200 and the largest by 0.57 to
# 0.90. The MEDIAN within SCORE_TOLERANCE, one and a half times the worst
# median seen: every matrix rounded to float8's three mantissa bits reads 1.78
# (largest 3.16) and a router that chooses without its bias 0.505. It does NOT tell bfloat16 gates (0.207), a
# one-pass router product (0.169) nor a convolution without its session mask
# (0.250: the mask's absence spoils a session's first two positions, which is
# much of a session of 16 to 42 items, 0.82 to 1.59, and little of the median
# session's 256): the PROBES below hold those. EVERY answer within
# FLIP_TOLERANCE, twice the largest seen: it guards against gross faults,
# another session's or another row's answer, which are off by the logits' own
# order (3 and more).
SCORE_TOLERANCE = 0.3
FLIP_TOLERANCE = 1.8
# a margin of the router's 4th over its 5th of (score + bias) under this counts
# as a tie that bf16 inputs decide: the scores are sigmoids of unit-order
# logits, and a stream off by 1e-2 moves one by up to 2.5e-3
ROUTER_TIE = 1e-3
# Two PROBES, which neither the router's ties nor the projections' bf16
# operands reach: a function the served program calls, on the chip, given the
# reference's own float32 inputs for a checked session (right-padded, as the
# program pads it, to one of two lengths), against the reference on the same
# inputs.
# The gates and the taps: ``lfm2.gated_conv`` on the reference's float32
# ``in_proj`` output of the first convolution layer (one function serves all
# eighteen), the session laid TWICE in one row with its ``position`` as two
# sessions of a packed stream, against the reference's three shifted products,
# ``|| difference || / || output ||`` over both copies' real positions. As
# configured it reads 0 (the same float32 operations in the same order); with
# ``B * u`` and ``C * conv`` in bfloat16 0.00413; with the taps' session mask
# dropped the second copy's first two positions are the first copy's tail's:
# 0.177. EVERY session within GATE_TOLERANCE.
# The router: ``ops/moe.route_sigmoid(eps=1e-6)`` on the reference's float32
# input of each sparse layer against ``reference.router_choice``, the largest
# difference of a weight over the tokens whose margin is no tie. As configured
# 8.9e-8; its product in one bfloat16 pass 0.249 (a score moves by more than
# the tie's margin and a token takes another expert), a choice without the
# bias 0.262. EVERY session within ROUTER_TOLERANCE.
# ``benchmark/controls_lfm2.py`` plants each of these in the deployed cell and
# has the check refuse it.
GATE_TOLERANCE = 1e-4
ROUTER_TOLERANCE = 1e-3


def variant_of(config: dict, seed: int) -> dict:
    """The engine variant: the published keys are the algorithm's parameters.
    The file states the chip's SHARE under ``num_experts`` (and lists it in
    ``reduced``); the algorithm takes the PUBLISHED count there and the share
    as ``experts_held``. ``--seed`` draws the weights."""
    variant = json.loads(json.dumps(config["variant"]))
    params = variant["algorithms"][0]["params"]
    params.update({key: config[key] for key in PUBLISHED})
    params["num_experts"] = config["published"]["num_experts"]
    params["experts_held"] = config["experts_held"]
    params["seed"] = int(seed) % (2**31)
    return variant


def kind_of(config: dict, i: int) -> tuple[bool, bool]:
    return reference.is_conv(config, i), reference.is_dense(config, i)


def layer_step(config: dict):
    """The check's one jitted function: ``step(x, layer, real, like=, probed=)``
    takes one session's float32 stream ``x`` [L, hidden] through one layer of
    the reference, of the kind of layer ``like``, and returns ``(x, ties, gate
    error, router error)`` over its first ``real`` positions (the rest is
    padding). ``probed`` puts the PROGRAM's gated convolution, over the
    session laid twice in one row as two sessions, beside the reference's
    three shifted products."""
    k, scale = int(config["num_experts_per_tok"]), float(config["routed_scaling_factor"])

    def step(x, layer, real, like, probed):
        eps = float(config["norm_eps"])
        live = jnp.arange(x.shape[0]) < real
        gate_error = router_error = jnp.zeros((), jnp.float32)
        tied = jnp.zeros((), jnp.int32)
        if probed:
            # the PROGRAM's gates and taps (the function its mixers call) on
            # the reference's own float32 projection, against the reference
            n = reference.rms_norm(x, layer["operator_norm"], eps)
            with jax.default_matmul_precision("highest"):
                projected = n @ jnp.asarray(layer["in_proj"], jnp.float32)
            b, c, u = jnp.split(projected, 3, axis=-1)
            ours = c * reference.short_conv(b * u, layer["conv"])
            # ... TWICE in one row, as two sessions of a packed stream: the
            # second has the first in front of it and has to come out the same
            at = jnp.arange(x.shape[0], dtype=jnp.int32)
            theirs = lfm2.gated_conv(
                jnp.concatenate([projected, projected])[None], layer["conv"], jnp.concatenate([at, at])[None]
            )[0].reshape(2, *ours.shape)
            off = jnp.where(live[None, :, None], theirs - ours, 0.0)
            gate_error = jnp.sqrt(jnp.sum(off * off) / (2 * jnp.sum(jnp.where(live[:, None], ours * ours, 0.0))))
        h = reference.mixer_block(x, layer, config, like)
        if not reference.is_dense(config, like):
            # the PROGRAM's router on the reference's own float32 input: where
            # the reference's margin is no tie, the same experts at the same weights
            m = reference.rms_norm(h, layer["ffn_norm"], eps)
            scores = reference.router_scores(m, layer)
            margin = reference.router_margin(scores, layer["expert_bias"], k)
            tied = jnp.sum((margin < ROUTER_TIE) & live)
            weights, experts = moe.route_sigmoid(
                m, layer["router"], layer["expert_bias"], k, scale, eps=reference.ROUTER_EPS
            )
            theirs = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], experts].add(weights)
            off = jnp.abs(theirs - reference.router_choice(scores, layer["expert_bias"], k, scale))
            router_error = jnp.max(jnp.where(((margin >= ROUTER_TIE) & live)[:, None], off, 0.0))
        return reference.ffn_block(h, layer, config, like), tied, gate_error, router_error

    return jax.jit(step, static_argnames=("like", "probed"))


def reference_logits(weights: dict, config: dict, sessions: list, lengths=None):
    """``(logits, tie share, gate errors, router errors)``: the reference's
    logits at each session's last position; the share of (token, sparse
    layer) pairs whose router leaves its k-th and (k+1)-th of score + bias
    within ``ROUTER_TIE``; and each session's two PROBES (the module's head:
    ``GATE_TOLERANCE``, the gated convolution in the first convolution layer;
    ``ROUTER_TOLERANCE``, the router's largest over the sparse layers).
    Layer by layer, every session alone, as
    ``sequential_kimi_linear.reference_logits`` goes: a layer's arrays go in
    as they are served, in bfloat16, and the reference upcasts each where it
    uses it (an expert at a time, a head at a time).

    ``lengths``, where given, are the lengths the sessions are right-padded
    to (with token 0) before they go through: every layer is causal, so a
    session's own positions come out as they do at its true length, and the
    reference compiles one program a KIND of layer (three) and a padded
    length. The padding is left out of the ties' count."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    n_layers = int(config["num_hidden_layers"])
    first_of_kind = {}
    for i in reversed(range(n_layers)):
        first_of_kind[kind_of(config, i)] = i

    step = layer_step(config)
    # the gated convolution is one function for every conv layer: probed in the first
    first_conv = min(i for i in range(n_layers) if reference.is_conv(config, i))
    true = [len(tokens) for tokens in sessions]
    padded = true if lengths is None else lengths
    states = [
        reference.embed(weights, np.concatenate([tokens, np.zeros(n - len(tokens), np.int32)]))
        for tokens, n in zip(sessions, padded)
    ]
    ties = total = 0
    gate_errors, router_errors = np.zeros(len(sessions)), np.zeros(len(sessions))
    for i in range(n_layers):
        layer = lfm2.layer_of(weights, i)
        like = first_of_kind[kind_of(config, i)]
        results = [step(x, layer, true[s], like=like, probed=i == first_conv) for s, x in enumerate(states)]
        for s, (x, tied, gate_error, router_error) in enumerate(results):
            states[s] = x
            ties += int(tied)
            total += 0 if reference.is_dense(config, i) else true[s]
            gate_errors[s] = max(gate_errors[s], float(gate_error))
            router_errors[s] = max(router_errors[s], float(router_error))
    logits = [np.asarray(reference.head(weights, config, x[n - 1])) for x, n in zip(states, true)]
    step.clear_cache()  # the reference's programs leave the device with the check
    return logits, ties / max(total, 1), gate_errors.tolist(), router_errors.tolist()


def check_answer(logits: np.ndarray, session: np.ndarray, ids, scores, n_items: int):
    """One served answer against the reference's logits: ``(ids_ok, by_set,
    error)``. ``error`` is the largest |served score − reference logit| over
    its items. The ids are the reference's top-k (its session's items left
    out) in its order, or (``by_set``) each served item scores, by the
    reference, within a MARGIN of the reference's item at its place and of
    the reference's k-th. The margin is twice what a score may be off: the
    answer's own ``error`` or ``SCORE_TOLERANCE``, whichever is larger
    (``FLIP_TOLERANCE`` at most). The answer's own error alone will not do
    here: it is read off the TEN served items, while the item that a served
    one displaced is one of the dozens of candidates within 0.3 of the k-th
    of 65,536 and was scored with an error of its own that no reply shows;
    two of 520 sound answers of the builder's first eight runs, themselves
    off by 0.061 and 0.076, held an item whose rival the served program had
    put further off than twice that (PERF.md, PR 41, "The check"). Another
    session's answer is off the k-th by the logits' own order (3 and more)."""
    ids = np.asarray(ids, np.int64)
    error = float(np.abs(np.asarray(scores, np.float64) - logits[ids]).max()) if len(ids) else np.inf
    allowed = np.ones(len(logits), bool)
    allowed[n_items:] = False
    allowed[session] = False
    order = np.argsort(-np.where(allowed, logits, -np.inf), kind="stable")[: len(ids)]
    if not allowed[ids].all() or len(set(ids.tolist())) != len(ids):
        return False, False, error
    if (ids == order).all():
        return True, False, error
    margin = 2 * max(error, SCORE_TOLERANCE)
    by_set = error <= FLIP_TOLERANCE and bool(
        (np.abs(logits[ids] - logits[order]) <= margin)[ids != order].all()
        and (logits[ids] >= logits[order[-1]] - margin).all()
    )
    return by_set, by_set, error


def count_wrong(errors: list, ids_ok: list, gate_errors=(), router_errors=()) -> int:
    """``sequential_olmoe.count_wrong`` under this backbone's limits; and
    every session whose gate probe is beyond ``GATE_TOLERANCE`` or whose
    router probe is beyond ``ROUTER_TOLERANCE``. A reading that is no number
    counts as beyond."""
    wrong = sum(1 for error, ok in zip(errors, ids_ok) if not ok or error > FLIP_TOLERANCE)
    if errors and float(np.median(errors)) > SCORE_TOLERANCE:
        wrong = max(wrong, sum(1 for error in errors if error > SCORE_TOLERANCE))
    wrong = max(wrong, sum(1 for error in gate_errors if not error <= GATE_TOLERANCE))
    return max(wrong, sum(1 for error in router_errors if not error <= ROUTER_TOLERANCE))


class Serving(sequential_olmoe.Serving):
    """``sequential_olmoe.Serving`` with the ``lfm2`` algorithm's parameters,
    model and reference; ``ask``, ``counters`` and ``stop`` are inherited."""

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
        weights = jax.block_until_ready(lfm2.init_weights(self.model_config, params.seed))
        self.parts["weights_s"] = time.monotonic() - t

        t = time.monotonic()
        asked = stream_of(ctx, self.n_users)
        # whom the generators ask while they keep replies for the check
        self.asked_early = set(asked[: len(asked) // 10].tolist())
        self.stream = asked
        tails, offsets = sessions_of(config, ctx.seed, asked)
        self.model = Lfm2Model(
            self.model_config,
            list(map("i%d".__mod__, range(self.model_config.table_rows))),
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
                engine_id="benchmark", version="1", variant="engine.json",
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
        """The configuration file's keys the reference and the roofline
        arithmetic read: the published ones as run, the share, and the
        published count beside it."""
        keys = PUBLISHED + ("experts_held", "published")
        return {key: self.config[key] for key in keys}

    def check(self, kept: dict[int, str]):
        """As ``sequential_kimi_linear.Serving.check``: ``(checked, wrong,
        worst |Δscore|)`` of the kept replies and one user of the longest
        bucket against the plain reference on the same weights. The replies
        the generators did not bring back of their ``CHECKED_QUERIES`` are
        asked for here, after the window, over the same HTTP path: the users
        the generators asked first."""
        # (what an earlier check of this deployment asked for itself is no stranger)
        own = set(getattr(self, "checked_replies", ()))
        strangers = sorted(set(kept) - self.asked_early - own) if self.asked_early else []
        if strangers:
            raise RuntimeError(
                f"the generators asked for users {strangers[:8]}, whom `stream_of` did not "
                "expect in the window: the lengths were not dealt along the stream that ran"
            )
        kept, from_window = dict(kept), len(kept)
        model = self.model
        lengths = np.diff(model.offsets)
        longest = np.flatnonzero(lengths > self.model_config.buckets()[-2])
        if len(longest) and int(longest[0]) not in kept:
            kept[int(longest[0])] = self.ask(int(longest[0]))
        for user in self.stream.tolist():
            if len(kept) > CHECKED_QUERIES:
                break
            if user not in kept:
                kept[user] = self.ask(user)
        self.checked_replies = kept  # a second check of this deployment asks for none again
        users = sorted(kept)
        sessions = [model.tails[model.offsets[u] : model.offsets[u + 1]] for u in users]
        buckets = self.model_config.buckets()
        # two padded lengths, the fourth bucket from the top and the top one
        ladder = (buckets[max(0, len(buckets) - 4)], buckets[-1])
        t = time.monotonic()
        logits, tie_share, gate_errors, router_errors = reference_logits(
            model.weights, self.shapes(), sessions,
            [lfm2.bucket_of(len(session), ladder) for session in sessions],
        )
        reference_s = time.monotonic() - t
        memory = jax.local_devices()[0].memory_stats() or {}
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
        wrong, worst = count_wrong(errors, ids_ok, gate_errors, router_errors), max(errors)
        # what `benchmark/controls_lfm2.py` prints beside each control
        self.readings = {
            "median_score_error": float(np.median(errors)),
            "largest_score_error": worst,
            "errors_by_items": sorted((len(s), round(e, 4)) for s, e in zip(sessions, errors)),
            "largest_gate_error": max(gate_errors),
            "largest_router_error": max(router_errors),
            "tie_share": tie_share,
        }
        print(
            f"benchmark: checked {len(users)} answers (sessions of {min(map(len, sessions))} to "
            f"{max(map(len, sessions))} items), worst |served - reference| by answer: median "
            f"{np.median(errors):.4f} of {SCORE_TOLERANCE}, largest {worst:.4f} of {FLIP_TOLERANCE} "
            f"({sorted(round(e, 4) for e in errors)}), {by_set} with the reference's ids only "
            f"as a set; {100 * tie_share:.3f}% of (token, sparse layer) pairs have their "
            f"router's 4th and 5th of score + bias within {ROUTER_TIE}; the program's gated "
            f"convolution on the reference's projection off its three shifted products by at most "
            f"{max(gate_errors):.3g} of {GATE_TOLERANCE} of their size, its router's weights off the "
            f"reference's by at most {max(router_errors):.3g} of {ROUTER_TOLERANCE}; {wrong} wrong; "
            f"{from_window} of the replies are the window's; the reference and its probes took "
            f"{reference_s:.0f} s; the device's fullest so far "
            f"{memory.get('peak_bytes_in_use', 0) / 1e9:.2f} GB in use of "
            f"{memory.get('bytes_limit', 0) / 1e9:.2f}",
            file=sys.stderr,
        )
        return len(users), wrong, worst


def serving(ctx) -> Serving:
    return Serving(ctx)
