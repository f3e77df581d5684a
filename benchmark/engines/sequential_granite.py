"""The sequential template's ``granite`` scorer (``models/sequential``:
``GraniteAlgorithm``, ``GraniteModel``, ``granite.session_vectors``,
``ops/linear_attention.ssd`` and ``short_conv``, ``ops/attention``,
``ops/moe``) as a system under test: what a configuration file with
``"engine": "sequential_granite"`` is built and driven through.

The deployment is ``sequential_olmoe.Serving``'s with another backbone: the
same server, users, session lengths, stratified stream and check of the served
answers, so this module holds only what differs: how the configuration file's
keys become the algorithm's parameters (the file gives the chip's SHARE under
``num_local_experts`` and ``vocab_size`` and the published counts beside
them), which model is built, and the reference
(``benchmark/reference_granite.py``) with the limits measured for it.

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

from benchmark import reference_granite as reference
from benchmark.engines import sequential_olmoe
from benchmark.engines.recommendation_als import MEMORY_STORAGE, _free_port
from benchmark.engines.sequential_olmoe import ENGINE_FACTORY, sessions_of, stream_of
from predictionio_tpu.models.sequential import granite
from predictionio_tpu.models.sequential.engine import GraniteModel
from predictionio_tpu.ops import moe

# serving answers recomputed against the plain reference after the window
CHECKED_QUERIES = 64
# the keys of the published config.json, as the configuration file runs them
PUBLISHED = (
    "attention_bias", "attention_multiplier", "embedding_multiplier", "hidden_act", "hidden_size",
    "intermediate_size", "layer_types", "logits_scaling", "mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv",
    "mamba_d_head", "mamba_d_state", "mamba_expand", "mamba_n_groups", "mamba_n_heads", "mamba_proj_bias",
    "max_position_embeddings", "model_type", "normalization_function", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads", "num_local_experts",
    "position_embedding_type", "residual_multiplier", "rms_norm_eps", "rope_scaling", "rope_theta",
    "shared_intermediate_size", "tie_word_embeddings", "vocab_size",
)
# How far a served score may lie from the reference's logit for that item,
# logits being of unit order. The two limits of ``sequential_olmoe`` (the
# MEDIAN answer tight, EVERY answer loosely) at this backbone's own readings
# (PERF.md, PR 49, "The check"), which are a TENTH of Kimi-Linear's and LFM2's
# for two reasons: a token's ten experts weigh a tenth each and a layer's
# output joins a stream of deviation 3 (12 x the embedding's 0.25) at 0.22, so
# a tipped router moves a logit by thousandths; and the head's product rounds
# the vector to bfloat16, 3e-3 of a unit-order logit, which is most of what is
# left. Sandbox, PR 49 (the CPU, the published widths, FOUR of the ten layers,
# ten sessions of 16 to 1,100 items, the head's vectors rounded to bfloat16 as
# the chip's default product rounds them): the median answer's worst score off
# by 0.0036 as configured (largest 0.0047; 1.5% of (token, layer) pairs tied),
# 0.057 with every matrix in float8 (least 0.033), 1.04 with
# ``residual_multiplier`` left out, 0.0116 with the scores scaled by
# ``128 ** -0.5`` and 0.0176 with the scan's session reset dropped (0.03 to
# 0.056 in sessions of 16 to 150 items, nothing in long ones): the last two
# the PROBES hold. At the first SIX layers (the published order, the attention
# layer among them) 0.0032 as configured (largest 0.0054) and 0.059 in float8
# (least 0.048): the depth hardly moves either, the head's rounding does most. The MEDIAN within SCORE_TOLERANCE, EVERY answer within
# FLIP_TOLERANCE (another session's answer is off by the logits' own order, 1
# and more). On the CHIP (my chip runs, PR 49, checks of 65 answers on
# eleven seeds, all ten layers): the median 0.0033 to 0.0036, the largest 0.0055
# to 0.0111, 1.58% tied: the sandbox's readings to the digit; with every
# matrix in float8 the median 0.060 (least 0.040), without the residual's
# multiplier 1.91, without the router's renormalisation 0.035. The limits are
# eight times the configured median and half of float8's, fourteen times the
# largest seen and a seventh of another session's answer.
SCORE_TOLERANCE = 0.03
FLIP_TOLERANCE = 0.15
# a margin of the router's 10th logit over its 11th under this counts as a tie
# that bf16 inputs decide: the logits are of unit order, and a stream off by
# 1e-2 moves one by as much
ROUTER_TIE = 1e-3
# Four PROBES, which neither the router's ties nor the projections' bf16
# operands reach: a function the served program calls, on the chip, given the
# reference's own float32 inputs for a checked session (right-padded, as the
# program pads it, to one of two lengths), against the reference on the same
# inputs; each ``|| difference || / || output ||`` over the session's real
# positions, in the first layer of its kind (one function serves all of them).
# The convolution and the scan see the session laid TWICE in one row as two
# sessions of a packed stream, the second from a multiple of
# ``granite.SESSION_ALIGN`` that is INSIDE a chunk of the scan, so that a tap
# or a state that reaches over a session's start shows in the second copy.
# The convolution: ``short_conv(position=, bias=)`` on the reference's float32
# ``xBC`` against the reference's four shifted products. EVERY session within
# CONV_TOLERANCE.
# The scan: ``ops/linear_attention.ssd(segment=)`` at ``granite.SSD_CHUNK`` on
# ``reference.ssd_inputs`` against ``reference.ssd_recurrence``. The MEDIAN
# session within SCAN_TOLERANCE (a reading grows with its session).
# The attention: ``granite.attention_mixer`` (bf16 operands, the served
# arrays) on the reference's float32 normed stream against
# ``reference.attention_mixer``. EVERY session within ATTN_TOLERANCE.
# The router: ``ops/moe.route(renormalise=True)`` on the reference's float32
# input of each layer against ``reference.router_choice``, the largest
# difference of a weight over the tokens whose margin is no tie. EVERY session
# within ROUTER_TOLERANCE.
# ``benchmark/controls_granite.py`` plants a fault for each in the deployed
# cell and has the check refuse it. Readings (sandbox, PR 49, the published
# widths; the scan's products emulated as the chip's three bfloat16 passes):
# the convolution 2.5e-8 as configured (the same float32 operations in the
# same order; bfloat16 taps would read 4e-3, a dropped mask the session in
# front); the scan 2.5e-6 as configured at 120, 300 and 1,500 items (3.9e-7
# in plain float32), 8.6e-5 / 1.35e-4 / 1.8e-4 with the state rounded to
# bfloat16 where a chunk hands it on, 1.3e-3 with one-pass products; the
# attention 0.0028 (bf16 operands against float32); the router 1.5e-7. On the
# CHIP (my chip runs, PR 49, eleven seeds): the convolution 0, the scan
# 1.22e-5 to 1.51e-5 at the median session as configured (five times the
# emulation's: the chip's own exponentials and sums), the attention 0.0030 to
# 0.0031, the router 1.8e-7 to 2.4e-7; planted in the deployed cell, each
# control moves its own probe and no other: a bfloat16 state 1.09e-4 and
# 1.15e-4 (38 and 41 of 65 wrong), the scan without its session reset 0.294
# and 0.132 (44 and 47), the convolution without its mask 0.160 (47), the
# scores scaled by ``128 ** -0.5`` 0.942 at the attention (47), the router
# without renormalisation 0.306 (65). SCAN_TOLERANCE lies 3.3 times over the
# largest configured reading and 2.2 times under the bfloat16 state's. The
# mixers' three are made for the sessions padded to the SHORTER length alone
# (``reference_logits``: why), the router's for all.
CONV_TOLERANCE = 1e-4
SCAN_TOLERANCE = 5e-5
ATTN_TOLERANCE = 0.03
ROUTER_TOLERANCE = 1e-3
PROBES = ("conv", "scan", "attn", "router")
# of each probe, every session's reading or the median session's is held to its limit
LIMITS = {
    "conv": (CONV_TOLERANCE, max), "scan": (SCAN_TOLERANCE, np.median),
    "attn": (ATTN_TOLERANCE, max), "router": (ROUTER_TOLERANCE, max),
}


def variant_of(config: dict, seed: int) -> dict:
    """The engine variant: the published keys are the algorithm's parameters.
    The file states the chip's SHARE under ``num_local_experts`` and
    ``vocab_size`` (and lists both in ``reduced``); the algorithm takes the
    PUBLISHED counts there, the share as ``experts_held`` and ``vocab_slice``,
    and ``num_hidden_layers`` as run. ``--seed`` draws the weights."""
    variant = json.loads(json.dumps(config["variant"]))
    params = variant["algorithms"][0]["params"]
    params.update({key: config[key] for key in PUBLISHED})
    params.update({key: config["published"][key] for key in ("num_local_experts", "vocab_size")})
    params.update({key: config[key] for key in ("experts_held", "vocab_slice")})
    params["seed"] = int(seed) % (2**31)
    return variant


def _relative(theirs, ours, live):
    """``|| theirs - ours || / || ours ||`` over the positions ``live`` marks
    (``live`` broadcast from the left)."""
    live = live.reshape(live.shape + (1,) * (ours.ndim - live.ndim))
    off = jnp.where(live, theirs - ours, 0.0)
    return jnp.sqrt(jnp.sum(off * off) / jnp.sum(jnp.where(live, ours * ours, 0.0)))


def twice(x, gap: int):
    """``x`` [L, ...] laid twice in one row with ``gap`` positions between
    the copies, [1, 2 L + gap, ...]: the gap holds the session's own first
    values (no zeros: what reaches over the second copy's start has to show)."""
    return jnp.concatenate([x, x[jnp.arange(gap) % x.shape[0]], x])[None]


def mamba_probes(n, layer, config: dict, real):
    """The PROGRAM's convolution and scan (the functions its mixers call) on
    the reference's own float32 inputs, the session laid twice in one row as
    two sessions of a packed stream: ``(conv error, scan error)``."""
    length, gap = n.shape[0], granite.SESSION_ALIGN
    at = jnp.arange(length, dtype=jnp.int32)
    live = at < real
    both = jnp.stack([live, live])
    # each position's index inside its session and its session's id, as the engine stages them
    position = jnp.concatenate([jnp.where(live, at, 0), jnp.zeros(gap, jnp.int32), jnp.where(live, at, 0)])[None]
    segment = jnp.concatenate([jnp.where(live, 0, -1), jnp.full(gap, -1), jnp.where(live, 1, -1)])[None]
    heads, p, state = int(config["mamba_n_heads"]), int(config["mamba_d_head"]), int(config["mamba_d_state"])
    inner = heads * p
    with jax.default_matmul_precision("highest"):
        projected = n @ jnp.asarray(layer["in_proj"], jnp.float32)
    xbc = projected[:, inner : 2 * inner + 2 * state]
    ours = reference.short_conv(xbc, layer["conv"], layer["conv_bias"])
    theirs, _ = granite.short_conv(twice(xbc, gap), layer["conv"], position=position, bias=layer["conv_bias"])
    copies = jnp.stack([theirs[0, :length], theirs[0, length + gap :]])
    conv_error = _relative(copies, jnp.stack([ours, ours]), both)
    (x, step, a, b, c, d), _ = reference.ssd_inputs(n, layer, config)
    y = reference.ssd_recurrence(x, step, a, b, c, d)
    theirs, _ = granite.ssd(
        twice(x, gap), twice(step, gap), a, twice(b, gap), twice(c, gap), d, segment=segment, chunk=granite.SSD_CHUNK
    )
    copies = jnp.stack([theirs[0, :length], theirs[0, length + gap :]])
    return conv_error, _relative(copies, jnp.stack([y, y]), both)


def layer_step(config: dict, model_config):
    """The check's one jitted function: ``step(x, layer, real, like=, probed=)``
    takes one session's float32 stream ``x`` [L, hidden] through one layer of
    the reference, of the kind of layer ``like``, and returns ``(x, ties,
    [conv, scan, attn, router] errors)`` over its first ``real`` positions
    (the rest is padding). ``probed`` puts the PROGRAM's functions beside the
    reference's (the module's head)."""
    k = int(config["num_experts_per_tok"])

    def step(x, layer, real, like, probed):
        eps = float(config["rms_norm_eps"])
        live = jnp.arange(x.shape[0]) < real
        errors = dict.fromkeys(PROBES, jnp.zeros((), jnp.float32))
        if probed:
            n = reference.rms_norm(x, layer["w_in"], eps)
            if reference.is_mamba(config, like):
                errors["conv"], errors["scan"] = mamba_probes(n, layer, config, real)
            else:
                ours = reference.attention_mixer(n, layer, config)
                theirs = granite.attention_mixer(n[None], jnp.where(live, 0, -1)[None], layer, model_config)[0]
                errors["attn"] = _relative(theirs, ours, live)
        h = reference.mixer_block(x, layer, config, like)
        # the PROGRAM's router on the reference's own float32 input: where the
        # reference's margin is no tie, the same experts at the same weights
        m = reference.rms_norm(h, layer["w_post"], eps)
        logits = reference.router_logits(m, layer)
        margin = reference.router_margin(logits, k)
        tied = jnp.sum((margin < ROUTER_TIE) & live)
        weights, experts = moe.route(m, layer["router"], k, renormalise=True)
        theirs = jnp.zeros_like(logits).at[jnp.arange(x.shape[0])[:, None], experts].add(weights)
        off = jnp.abs(theirs - reference.router_choice(logits, k))
        errors["router"] = jnp.max(jnp.where(((margin >= ROUTER_TIE) & live)[:, None], off, 0.0))
        return reference.ffn_block(h, layer, config), tied, jnp.stack([errors[name] for name in PROBES])

    return jax.jit(step, static_argnames=("like", "probed"))


# rows of the table the reference's head takes at a time: the whole table in
# float32 is 0.8 GB, and as much again transposed, beside a served model that
# leaves the chip 5 GB; 8,192 rows are 0.13 GB
HEAD_ROWS = 8192


def head_in_blocks(weights: dict, config: dict, x) -> np.ndarray:
    """``reference.head`` over ``HEAD_ROWS`` rows of the table at a time."""
    table = weights["embed"]
    blocks = [
        reference.head({"embed": table[at : at + HEAD_ROWS], "final_norm": weights["final_norm"]}, config, x)
        for at in range(0, table.shape[0], HEAD_ROWS)
    ]
    return np.concatenate([np.asarray(block) for block in blocks])


def reference_logits(weights: dict, config: dict, model_config, sessions: list, lengths=None):
    """``(logits, tie share, {probe: every probed session's reading})``: the
    reference's logits at each session's last position; the share of (token,
    layer) pairs whose router leaves its k-th and (k+1)-th logit within
    ``ROUTER_TIE``; and each session's PROBES (the module's head), a probe the
    largest over the layers it is made in. SESSION BY SESSION, each through
    the layers alone and fetched before the next begins: a layer's arrays go
    in as they are served, in bfloat16, and the reference upcasts each where
    it uses it (an expert at a time, a head at a time, ``HEAD_ROWS`` of the
    table at a time). So the check holds ONE session's stream on the device
    (67 MB at 4,096 items) beside one layer's temporaries (0.8 GB). Layer by
    layer over all the sessions, as ``sequential_lfm2.reference_logits`` goes,
    holds every session's stream twice (3 GB of 65 answers, more where more of
    them are long) and with the table upcast whole read 15.4 to 16.2 GB in use
    of 16.9 by the seed (my chip runs, PR 49): beside this model that order
    does not fit on every seed.

    ``lengths``, where given, are the lengths the sessions are right-padded
    to (with token 0) before they go through: every layer is causal, so a
    session's own positions come out as they do at its true length, and the
    reference compiles one program a KIND of layer (two, and their probed
    forms) and a padded length. The padding is left out of the ties' count."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    n_layers = int(config["num_hidden_layers"])
    first_of_kind = {}
    for i in reversed(range(n_layers)):
        first_of_kind[reference.is_mamba(config, i)] = i
    layers = [granite.layer_of(weights, i) for i in range(n_layers)]

    step = layer_step(config, model_config)
    true = [len(tokens) for tokens in sessions]
    padded = true if lengths is None else lengths
    # the mixers' probes are made at the SHORTER padded length alone (at every length where none is given): the session laid twice at 4,096 items
    # is 2.5 GB of the scan's temporaries beside a served model that leaves the chip 5; the router's in all
    short = [s for s, n in enumerate(padded) if lengths is None or n == min(padded)]
    ties = 0
    errors = np.zeros((len(sessions), len(PROBES)))
    logits = []
    for s, tokens in enumerate(sessions):
        x = reference.embed(weights, config, np.concatenate([tokens, np.zeros(padded[s] - true[s], np.int32)]))
        tied, probed = [], []
        for i, layer in enumerate(layers):
            like = first_of_kind[reference.is_mamba(config, i)]
            x, *counted = step(x, layer, true[s], like=like, probed=i == like and s in short)
            tied.append(counted[0])
            probed.append(counted[1])
        logits.append(head_in_blocks(weights, config, x[true[s] - 1]))  # fetched: the next session begins after this one
        ties += int(sum(map(int, tied)))
        errors[s] = np.max(np.asarray(probed), axis=0)
    step.clear_cache()  # the reference's programs leave the device with the check
    readings = {name: errors[short if name != "router" else slice(None), at].tolist() for at, name in enumerate(PROBES)}
    return logits, ties / max(n_layers * sum(true), 1), readings


def check_answer(logits: np.ndarray, session: np.ndarray, ids, scores, n_items: int):
    """``sequential_lfm2.check_answer`` under this backbone's limits: one
    served answer against the reference's logits, ``(ids_ok, by_set, error)``.
    ``error`` is the largest |served score − reference logit| over its items.
    The ids are the reference's top-k (its session's items left out) in its
    order, or (``by_set``) each served item scores, by the reference, within
    a MARGIN of the reference's item at its place and of the reference's
    k-th: twice what a score may be off, the answer's own ``error`` or
    ``SCORE_TOLERANCE``, whichever is larger (``FLIP_TOLERANCE`` at most; the
    item a served one displaced was scored with an error of its own that no
    reply shows). Another session's answer is off the k-th by the logits'
    own order."""
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


def count_wrong(errors: list, ids_ok: list, probes: dict | None = None) -> int:
    """``sequential_olmoe.count_wrong`` under this backbone's limits; and,
    for every probe that is beyond its limit (``LIMITS``: every session's
    reading or the median session's), the sessions whose reading is. A
    reading that is no number counts as beyond."""
    wrong = sum(1 for error, ok in zip(errors, ids_ok) if not ok or error > FLIP_TOLERANCE)
    if errors and float(np.median(errors)) > SCORE_TOLERANCE:
        wrong = max(wrong, sum(1 for error in errors if error > SCORE_TOLERANCE))
    for name, readings in (probes or {}).items():
        limit, over = LIMITS[name]
        if readings and not over(readings) <= limit:
            wrong = max(wrong, sum(1 for reading in readings if not reading <= limit))
    return wrong


class Serving(sequential_olmoe.Serving):
    """``sequential_olmoe.Serving`` with the ``granite`` algorithm's
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
        weights = jax.block_until_ready(granite.init_weights(self.model_config, params.seed))
        self.parts["weights_s"] = time.monotonic() - t

        t = time.monotonic()
        asked = stream_of(ctx, self.n_users)
        # whom the generators ask while they keep replies for the check
        self.asked_early = set(asked[: len(asked) // 10].tolist())
        self.stream = asked
        tails, offsets = sessions_of(config, ctx.seed, asked)
        self.model = GraniteModel(
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
        published counts beside it."""
        keys = PUBLISHED + ("experts_held", "vocab_slice", "published")
        return {key: self.config[key] for key in keys}

    def check(self, kept: dict[int, str]):
        """As ``sequential_kimi_linear.Serving.check``: ``(checked, wrong, worst
        |Δscore|)`` of the kept replies and one user of the longest bucket
        against the plain reference on the same weights. The replies the
        generators did not bring back of their ``CHECKED_QUERIES`` are asked
        for here, after the window, over the same HTTP path: the users the
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
        before = (jax.local_devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
        t = time.monotonic()
        logits, tie_share, probes = reference_logits(
            model.weights, self.shapes(), self.model_config, sessions,
            [granite.bucket_of(len(session), ladder) for session in sessions],
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
        wrong, worst = count_wrong(errors, ids_ok, probes), max(errors)
        # what `benchmark/controls_granite.py` prints beside each control
        self.readings = {
            "median_score_error": float(np.median(errors)),
            "largest_score_error": worst,
            "errors_by_items": sorted((len(s), round(e, 4)) for s, e in zip(sessions, errors)),
            **{f"{name}_error": float(LIMITS[name][1](readings)) for name, readings in probes.items()},
            "tie_share": tie_share,
        }
        said = ", ".join(
            f"{name} {LIMITS[name][1](readings):.3g} of {LIMITS[name][0]}"
            f" ({'the median session' if LIMITS[name][1] is np.median else 'every session'})"
            for name, readings in probes.items()
        )
        print(
            f"benchmark: checked {len(users)} answers (sessions of {min(map(len, sessions))} to "
            f"{max(map(len, sessions))} items), worst |served - reference| by answer: median "
            f"{np.median(errors):.4f} of {SCORE_TOLERANCE}, largest {worst:.4f} of {FLIP_TOLERANCE} "
            f"({sorted(round(e, 4) for e in errors)}), {by_set} with the reference's ids only "
            f"as a set; {100 * tie_share:.3f}% of (token, layer) pairs have their router's 10th "
            f"and 11th logit within {ROUTER_TIE}; the program's functions on the reference's inputs "
            f"off the reference's by: {said}; {wrong} wrong; {from_window} of the replies are the "
            f"window's; the reference and its probes took {reference_s:.0f} s; the device's fullest "
            f"before them {before / 1e9:.2f} GB in use, after them {memory.get('peak_bytes_in_use', 0) / 1e9:.2f} "
            f"of {memory.get('bytes_limit', 0) / 1e9:.2f}",
            file=sys.stderr,
        )
        return len(users), wrong, worst


def serving(ctx) -> Serving:
    return Serving(ctx)
