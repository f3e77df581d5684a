"""The sequential template's ``kimi_linear`` scorer (``models/sequential``:
``KimiLinearAlgorithm``, ``KimiLinearModel``, ``kimi_linear.session_vectors``,
``ops/linear_attention``, ``ops/moe``) as a system under test: what a
configuration file with ``"engine": "sequential_kimi_linear"`` is built and
driven through.

The deployment is ``sequential_olmoe.Serving``'s with another backbone: the
same server, users, session lengths, stratified stream and check of the
served answers, so this module holds only what differs: how the
configuration file's keys become the algorithm's parameters (the file gives
the chip's SHARE under the published keys and the published counts beside
them), which model is built, and the reference
(``benchmark/reference_kimi_linear.py``) with the limits measured for it.

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

from benchmark import reference_kimi_linear as reference
from benchmark.engines import sequential_olmoe
from benchmark.engines.recommendation_als import MEMORY_STORAGE, _free_port
from benchmark.engines.sequential_olmoe import (  # noqa: F401  (the driver reads CHECKED_QUERIES)
    CHECKED_QUERIES, ENGINE_FACTORY, sessions_of, stream_of,
)
from predictionio_tpu.models.sequential import kimi_linear
from predictionio_tpu.models.sequential.engine import KimiLinearModel
from predictionio_tpu.ops import linear_attention, moe

# the keys of the published config.json, as the configuration file runs them
PUBLISHED = (
    "first_k_dense_replace", "head_dim", "hidden_act", "hidden_size", "intermediate_size",
    "kv_lora_rank", "linear_attn_config", "mla_use_nope", "model_max_length", "model_type",
    "moe_intermediate_size", "moe_layer_freq", "moe_renormalize", "moe_router_activation_func",
    "num_attention_heads", "num_expert_group", "num_experts", "num_experts_per_token",
    "num_hidden_layers", "num_key_value_heads", "num_nextn_predict_layers", "num_shared_experts",
    "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_scaling",
    "rope_theta", "routed_scaling_factor", "tie_word_embeddings", "topk_group",
    "use_grouped_topk", "v_head_dim", "vocab_size",
)
# How far a served score may lie from the reference's logit for that item,
# logits being of unit order. The two limits of ``sequential_olmoe`` (the
# MEDIAN answer tight, EVERY answer loosely), with this backbone's own
# readings (PERF.md, PR 31, "The check"), which are ten times OLMoE's for one
# reason: the router. Its 256 sigmoid scores lie so close that in 14% of all
# (token, sparse layer) pairs the 8th and the 9th of score + bias are within
# 1e-3; a stream that bf16 products put off by 1e-2 tips them, and a tipped
# token swaps one expert of weight 2.446 / 8 for another (OLMoE's softmax
# weights are of order 1 / 64). So EVERY answer carries tipped tokens: over
# the builder's twelve runs of 10 to 17 answers the median answer's worst
# score is off by 0.056 to 0.154 and the largest by 0.21 to 0.52 (and over
# eight later runs of 17 answers each by 0.072 to 0.162 and 0.28 to 0.74,
# with the limits as they stood); a fifth of
# all answers are off by over 0.2 and one in eleven by over 0.3. The MEDIAN
# within SCORE_TOLERANCE, twice the worst median seen then (by the answers' own
# spread a run of 11 passes 0.2 ninety-nine times in a hundred, too few for a
# driver that makes sixteen, and 0.3 all but once in seven thousand): 7
# experts a token (0.44; 13 of its 17 answers over 0.3), a router that
# selects without its bias (0.59) and the held experts' weights in fp8 (0.55)
# FAIL it. It does NOT tell a KDA state kept in bfloat16 (0.140, 0.142: above
# all but one median of the configured arithmetic, and inside the limit) nor
# a log decay in bfloat16 (0.064): the tipped tokens are a floor of noise
# under which a thousandth of the scan's output is lost, and so is the bf16
# projections' own error (the first layer's output, KDA and dense and no
# router, is off the reference's by 2.0e-3 to 2.3e-3 of its size as
# configured and by 2.0e-3 to 2.4e-3 with a bfloat16 state: sandbox, PR 31).
# What the scores cannot see the PROBES below hold.
# EVERY answer within FLIP_TOLERANCE, two and a half times the largest seen
# when it was set (1.7 times the largest since):
# it guards against gross faults, another session's or another row's answer
# or the mixers' weights in fp8 (median 2.5), which are off by the logits' own
# order.
SCORE_TOLERANCE = 0.3
FLIP_TOLERANCE = 1.25
# a margin of the router's 8th over its 9th of (score + bias) under this counts
# as a tie that bf16 inputs decide: the scores are sigmoids of unit-order
# logits, and a stream off by 1e-2 moves one by up to 2.5e-3
ROUTER_TIE = 1e-3
# Two PROBES, which neither the router's ties nor the projections' bf16
# operands reach: a function the served program calls, on the chip, given the
# reference's own float32 inputs for a checked session (right-padded, as the
# program pads it, to one of two lengths), against the reference on the same
# inputs.
# The scan: ``ops/linear_attention.kda`` on ``reference.kda_inputs`` against
# ``reference.kda_recurrence``, ``|| difference || / || output ||`` over the
# session's real positions, in the first KDA layer (one function serves all
# six). As configured (float32 state and decays, products in three bf16
# passes) it grows with the session, 3.0e-5 at 119 items to 1.8e-4 at 2,784,
# and the MEDIAN session of a run reads 6.0e-5 (the cell's traced run; 5.1e-5
# over all six layers in an earlier one; 4.9e-5 to 7.7e-5 over eight later
# runs of 17 sessions); with the state rounded to bfloat16
# where a chunk hands it on, 6.7e-4 at 108 items to 1.7e-3 at 2,784, the
# median 1.18e-3, and as configured on a session of one chunk (my chip runs,
# PR 31, seed 3100000312; PERF.md has them all). The MEDIAN session within
# SCAN_TOLERANCE, over three times the one and a sixth of the other (a
# reading grows with its session and a run's sessions differ in length, so
# the median is the steadier number). In the sandbox (the published widths,
# the three passes emulated) one-pass products read 3e-3 to 4e-3 and FAIL; a
# bfloat16 LOG DECAY reads 5e-5 to 7e-5 and is NOT told: the three passes
# themselves do as much to a session of 250 items.
# The router: ``ops/moe.route_sigmoid`` on the reference's float32 input of
# each sparse layer against ``reference.router_choice``, the largest
# difference of a weight over the tokens whose margin is no tie. As
# configured 9e-8 to 1.2e-7 (the chip); 7 experts a token or a choice without
# the bias 0.31 to 0.32 (sandbox, the published widths). EVERY session within
# ROUTER_TOLERANCE.
# ``benchmark/controls_kimi_linear.py`` plants each of these in the deployed
# cell and has the check refuse it.
SCAN_TOLERANCE = 2e-4
ROUTER_TOLERANCE = 1e-3


def variant_of(config: dict, seed: int) -> dict:
    """The engine variant: the published keys are the algorithm's parameters.
    The file states the chip's SHARE under ``num_experts`` and ``vocab_size``
    (and lists both in ``reduced``); the algorithm takes the PUBLISHED counts
    there, the share as ``experts_held`` and ``vocab_slice``, and
    ``num_hidden_layers`` as run. ``--seed`` draws the weights."""
    variant = json.loads(json.dumps(config["variant"]))
    params = variant["algorithms"][0]["params"]
    params.update({key: config[key] for key in PUBLISHED})
    params.update({key: config["published"][key] for key in ("num_experts", "vocab_size")})
    params.update({key: config[key] for key in ("experts_held", "vocab_slice")})
    params["seed"] = int(seed) % (2**31)
    return variant


def kind_of(config: dict, i: int) -> tuple[bool, bool]:
    return reference.is_kda(config, i), reference.is_dense(config, i)


def layer_step(config: dict):
    """The check's one jitted function: ``step(x, layer, real, like=, probed=)``
    takes one session's float32 stream ``x`` [L, hidden] through one layer of
    the reference, of the kind of layer ``like``, and returns ``(x, ties, scan
    error, router error)`` over its first ``real`` positions (the rest is
    padding). ``probed`` puts the PROGRAM's scan beside the recurrence."""
    k, scale = int(config["num_experts_per_token"]), float(config["routed_scaling_factor"])

    def step(x, layer, real, like, probed):
        eps = config["rms_norm_eps"]
        live = jnp.arange(x.shape[0]) < real
        scan_error = router_error = jnp.zeros((), jnp.float32)
        tied = jnp.zeros((), jnp.int32)
        if probed:
            # the PROGRAM's scan (the function its mixers call) on the
            # reference's own float32 inputs, against the recurrence on them
            n = reference.rms_norm(x, layer["w_in"], eps)
            inputs = reference.kda_inputs(n, layer, config)
            o = reference.kda_recurrence(*inputs)
            theirs, _ = linear_attention.kda(*(a[None] for a in inputs))
            off = jnp.where(live[:, None, None], theirs[0] - o, 0.0)
            scan_error = jnp.sqrt(jnp.sum(off * off) / jnp.sum(jnp.where(live[:, None, None], o * o, 0.0)))
            h = x + reference.kda_output(o, n, layer, config)
        else:
            h = reference.mixer_block(x, layer, config, like)
        if not reference.is_dense(config, like):
            # the PROGRAM's router on the reference's own float32 input: where
            # the reference's margin is no tie, the same experts at the same weights
            n2 = reference.rms_norm(h, layer["w_post"], eps)
            scores = reference.router_scores(n2, layer)
            margin = reference.router_margin(scores, layer["router_bias"], k)
            tied = jnp.sum((margin < ROUTER_TIE) & live)
            weights, experts = moe.route_sigmoid(n2, layer["router"], layer["router_bias"], k, scale)
            theirs = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], experts].add(weights)
            off = jnp.abs(theirs - reference.router_choice(scores, layer["router_bias"], k, scale))
            router_error = jnp.max(jnp.where(((margin >= ROUTER_TIE) & live)[:, None], off, 0.0))
        return reference.ffn_block(h, layer, config, like), tied, scan_error, router_error

    return jax.jit(step, static_argnames=("like", "probed"))


def reference_logits(weights: dict, config: dict, sessions: list, lengths=None):
    """``(logits, tie share, scan errors, router errors)``: the reference's
    logits at each session's last position; the share of (token, sparse
    layer) pairs whose router leaves its k-th and (k+1)-th of score + bias
    within ``ROUTER_TIE``; and each session's two PROBES (the module's head:
    ``SCAN_TOLERANCE``, the scan in the first KDA layer; ``ROUTER_TOLERANCE``,
    the router's largest over the sparse layers).
    Layer by layer, every session alone. A layer's arrays go in as they are
    served, in bfloat16: the reference upcasts each to float32 where it uses
    it (an expert at a time, a head at a time), so the check holds nothing
    beside the served model but one session's temporaries, 0.4 GB at 4,096
    items. (It upcast a whole layer first, 2.0 GB, and took all 32 heads'
    [L, L] scores at once, 2.45 GB of temporaries at 4,096 items: 12.4 GB
    with the served model, on a chip of 16.9 whose free memory the window
    leaves in pieces. The sandbox's compile for v5e gives both numbers.)

    ``lengths``, where given, are the lengths the sessions are right-padded
    to (with token 0) before they go through: every layer is causal, so a
    session's own positions come out as they do at its true length, and the
    reference then compiles one program a KIND of layer and a padded length
    (three kinds; ``Serving.check`` pads to one of two lengths) where the
    true lengths of 17 sessions cost 51 compiles, most of a check's six
    minutes, and the seven buckets' 21 most of three (PERF.md, PR 31). The
    padding is left out of the ties' count."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    n_layers = int(config["num_hidden_layers"])
    first_of_kind = {}
    for i in range(n_layers, 0, -1):
        first_of_kind[kind_of(config, i)] = i

    step = layer_step(config)
    # the scan is one function for every KDA layer: probed in the first
    first_kda = min(i for i in range(1, n_layers + 1) if reference.is_kda(config, i))
    true = [len(tokens) for tokens in sessions]
    padded = true if lengths is None else lengths
    states = [
        reference.embed(weights, np.concatenate([tokens, np.zeros(n - len(tokens), np.int32)]))
        for tokens, n in zip(sessions, padded)
    ]
    ties = total = 0
    scan_errors, router_errors = np.zeros(len(sessions)), np.zeros(len(sessions))
    for i in range(1, n_layers + 1):
        layer = kimi_linear.layer_of(weights, i)
        like = first_of_kind[kind_of(config, i)]
        for s, x in enumerate(states):
            states[s], tied, scan_error, router_error = step(
                x, layer, true[s], like=like, probed=i == first_kda
            )
            ties += int(tied)
            total += 0 if reference.is_dense(config, i) else true[s]
            scan_errors[s] = max(scan_errors[s], float(scan_error))
            router_errors[s] = max(router_errors[s], float(router_error))
    logits = [np.asarray(reference.head(weights, config, x[n - 1])) for x, n in zip(states, true)]
    step.clear_cache()  # the reference's programs leave the device with the check
    return logits, ties / max(total, 1), scan_errors.tolist(), router_errors.tolist()


def check_answer(logits: np.ndarray, session: np.ndarray, ids, scores, n_items: int):
    """``sequential_olmoe.check_answer`` under this backbone's limits: one
    served answer against the reference's logits, ``(ids_ok, by_set, error)``.
    ``error`` is the largest |served score − reference logit| over its items.
    The ids are the reference's top-k (its session's items left out) in its
    order, except where the reference scores the two candidates for a place
    within twice the answer's own error (every answer carries tipped tokens
    here: the module's head); then ``by_set`` flags it and each served item
    has to be within twice that error, ``FLIP_TOLERANCE`` at most, of the
    reference's k-th."""
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
    by_set = error <= FLIP_TOLERANCE and bool(
        (np.abs(logits[ids] - logits[order]) <= 2 * error)[ids != order].all()
        and (logits[ids] >= logits[order[-1]] - 2 * error).all()
    )
    return by_set, by_set, error


def count_wrong(errors: list, ids_ok: list, scan_errors=(), router_errors=()) -> int:
    """``sequential_olmoe.count_wrong`` under this backbone's limits; and,
    where the MEDIAN session's scan probe is beyond ``SCAN_TOLERANCE``, every
    session beyond it; and every session whose router probe is beyond
    ``ROUTER_TOLERANCE``. A reading that is no number counts as beyond."""
    wrong = sum(1 for error, ok in zip(errors, ids_ok) if not ok or error > FLIP_TOLERANCE)
    if errors and float(np.median(errors)) > SCORE_TOLERANCE:
        wrong = max(wrong, sum(1 for error in errors if error > SCORE_TOLERANCE))
    if len(scan_errors) and not float(np.median(scan_errors)) <= SCAN_TOLERANCE:
        wrong = max(wrong, sum(1 for error in scan_errors if not error <= SCAN_TOLERANCE))
    return max(wrong, sum(1 for error in router_errors if not error <= ROUTER_TOLERANCE))


class Serving(sequential_olmoe.Serving):
    """``sequential_olmoe.Serving`` with the ``kimi_linear`` algorithm's
    parameters, model and reference; ``ask``, ``counters`` and ``stop`` are
    inherited."""

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
        weights = jax.block_until_ready(kimi_linear.init_weights(self.model_config, params.seed))
        self.parts["weights_s"] = time.monotonic() - t

        t = time.monotonic()
        asked = stream_of(ctx, self.n_users)
        # whom the generators ask while they keep replies for the check
        self.asked_early = set(asked[: len(asked) // 10].tolist())
        self.stream = asked
        tails, offsets = sessions_of(config, ctx.seed, asked)
        self.model = KimiLinearModel(
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
        published counts beside them."""
        keys = PUBLISHED + ("experts_held", "vocab_slice", "published")
        return {key: self.config[key] for key in keys}

    def check(self, kept: dict[int, str]):
        """As ``sequential_olmoe.Serving.check``: ``(checked, wrong, worst
        |Δscore|)`` of the kept replies and one user of the longest bucket
        against the plain reference on the same weights.

        The generators draw which ``CHECKED_QUERIES`` of their requests 256
        to 1,279 they keep, and at this backbone's 36 answers a second a
        generator sends about 980 in a window: 10 to 13 of the 16 come back
        (my chip runs, PR 31), and one run in 140 would bring under the 7 the
        driver asks for. The rest are asked for here, after the window, over
        the same HTTP path as the longest bucket's user: the users the
        generators asked first."""
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
        logits, tie_share, scan_errors, router_errors = reference_logits(
            model.weights, self.shapes(), sessions,
            [kimi_linear.bucket_of(len(session), ladder) for session in sessions],
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
        wrong, worst = count_wrong(errors, ids_ok, scan_errors, router_errors), max(errors)
        # what `benchmark/controls_kimi_linear.py` prints beside each control
        self.readings = {
            "median_score_error": float(np.median(errors)),
            "median_scan_error": float(np.median(scan_errors)),
            "scan_errors_by_items": sorted((len(s), e) for s, e in zip(sessions, scan_errors)),
            "largest_router_error": max(router_errors),
        }
        print(
            f"benchmark: checked {len(users)} answers (sessions of {min(map(len, sessions))} to "
            f"{max(map(len, sessions))} items), worst |served - reference| by answer: median "
            f"{np.median(errors):.4f} of {SCORE_TOLERANCE}, largest {worst:.4f} of {FLIP_TOLERANCE} "
            f"({sorted(round(e, 4) for e in errors)}), {by_set} with the reference's ids only "
            f"as a set; {100 * tie_share:.3f}% of (token, sparse layer) pairs have their "
            f"router's 8th and 9th of score + bias within {ROUTER_TIE}; the program's scan on "
            f"the reference's inputs off the recurrence by a median {np.median(scan_errors):.3g} "
            f"of {SCAN_TOLERANCE} of its size (by items: "
            f"{sorted((len(s), float(f'{e:.3g}')) for s, e in zip(sessions, scan_errors))}), "
            f"its router's weights off the reference's by at most {max(router_errors):.3g} of "
            f"{ROUTER_TOLERANCE}; {wrong} wrong; {from_window} of the replies are the window's; "
            f"the reference and its probes took {reference_s:.0f} s; the device's fullest so far "
            f"{memory.get('peak_bytes_in_use', 0) / 1e9:.2f} GB in use of "
            f"{memory.get('bytes_limit', 0) / 1e9:.2f}",
            file=sys.stderr,
        )
        return len(users), wrong, worst


def serving(ctx) -> Serving:
    return Serving(ctx)
