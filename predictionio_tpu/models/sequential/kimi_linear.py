"""Kimi-Linear-48B-A3B's block as a session encoder: the device side of the
sequential engine's ``kimi_linear`` algorithm (``backbone.KimiLinearAlgorithm``).

As ``olmoe.py`` is for OLMoE: a session's items are the tokens, one causal
forward pass over the session (``session_vectors``, the SAME name, arguments
and results as OLMoE's, so that the engine's launch and the benchmark's
readers serve both) gives the final-normed hidden state at its last real
position, and ``lm_head`` scores it in the engine. Layer equations:
``kimi_linear_reference.py``, which the tests and the benchmark hold this to.

Layers are of four kinds and are unrolled, each with its own arrays (a flat
tree, layer ``i``'s as ``"<i>.<name>"``, numbered from 1 as the published
``linear_attn_config`` numbers them): the token mixer is KDA
(``ops/linear_attention``: a short convolution, a gated delta rule scanned
chunk by chunk) or latent attention without rotary embedding (expanded, as a
prefill runs it: ``ops/attention.fused_attention`` with keys of 192 and
values of 128); the feed-forward is dense (the leading layers) or sparse
(``ops/moe``: a sigmoid router over ALL ``num_experts``, the grouped products
over the experts HELD here, and one shared expert). No ``lax.scan`` over
layers: no two periods are alike (the first layer's feed-forward is dense),
and a layer's own arrays are read where they lie.

What a chip holds is a share of a stated deployment (``experts_held``,
``vocab_slice``): the router keeps its published width and its experts per
token, the held experts' part of the result goes on to the next layer, and
the embedding, ``lm_head`` and the scores are over the slice.

What runs: weights in bfloat16 (products with bf16 operands and float32
accumulation); the residual stream, the norms, the convolution, the decays,
the gates, the KDA state and everything in its scan, the router and the
softmax in float32. A float32 weight tree (the CPU parity tests) computes in
float32.

A program is ``[R, T]`` tokens, ``R`` token streams of several sessions each
as its rows, as ``olmoe.py``'s is (``segment``, ``position``): latent
attention sees a key only from inside its own row and segment, the KDA state
is zeroed where a chunk begins a session (sessions start on multiples of
``SESSION_ALIGN``, which is the scan's ``CHUNK``) and the short convolution
reaches no further back than a session's first item, so a session's positions
come out as they would alone; neither kind of mixer lets a real position see
what follows it, and both work row by row. The experts and the shared expert
take the tokens of all rows at once.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu.models.sequential.olmoe import (
    LENGTH_BUCKETS, SESSION_ALIGN, _at_last, _normal, _project, _rms, bucket_of, stream_shapes,
)
from predictionio_tpu.models.sequential.records import BackboneParams
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.attention import fused_attention
from predictionio_tpu.ops.linear_attention import CHUNK, kda, short_conv

__all__ = [
    "KimiLinearConfig", "KimiLinearAlgorithmParams", "TOKEN_BUDGET", "MAX_SESSION", "SESSION_ALIGN", "bucket_of",
    "weight_shapes", "init_weights", "layer_of", "session_vectors", "all_logits",
]

# tokens a stream holds (``MAX_SESSION`` where a session is longer)
TOKEN_BUDGET = 2048
# streams that ride as the rows of one program (``olmoe.STACKED_ROWS``: why
# and how). ONE here, from the chip (PERF.md, PR 38; ms a stream, the top-k
# included): [1, 2048] 76.4, [2, 2048] 82.5, [4, 2048] 85.4, two streams end
# to end as [1, 4096] 77.1. The experts do gain by rows (a sparse layer's
# router and experts 4.38 ms a stream alone, 2.86 in a four), but the KDA
# mixer LOSES more (6.95 ms a layer and stream alone, 9.11 in a four: the
# scan 5.22 -> 6.83, the projections and convolutions 0.96 -> 1.43). With
# the mixer mapped row by row (``lax.map``) a four takes 70.5 ms a stream and
# a batch of 32 sessions 509 ms for 537, but only beside a single-row program
# of each length, and a THIRD compiled shape costs this unrolled program 3.7
# to 4.9 s of a 38 s set-up (the benchmark's bound is 10%); with two shapes
# (what a batch leaves behind its stacks riding a half-empty 4,096 row) every
# height lost 11 to 13% a batch. So the program takes [R, T] and the engine
# sends it one row; a scan over the alike layers (ROADMAP S9 (g)) is what
# would pay for the third shape
STACKED_ROWS = 1
# items of a session the engine keeps, and so the longest program: the
# traffic's bound (the model's own is ``model_max_length``, 1,048,576 as
# published)
MAX_SESSION = 4096


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The keys of the published ``config.json`` that shape the program
    (``linear_attn_config``'s flattened), and the chip's share."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kda_num_heads: int
    kda_head_dim: int
    short_conv_kernel_size: int
    kda_layers: tuple[int, ...]
    full_attn_layers: tuple[int, ...]
    first_k_dense_replace: int
    num_experts: int  # the router's width
    num_experts_per_token: int
    num_shared_experts: int
    routed_scaling_factor: float
    rms_norm_eps: float
    experts_held: tuple[int, int]  # (first, count) of the router's experts
    vocab_slice: tuple[int, int]  # (first, count) of the published vocabulary
    model_max_length: int

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers", "experts_held", "vocab_slice"):
            object.__setattr__(self, name, tuple(int(v) for v in getattr(self, name)))
        for i in range(1, self.num_hidden_layers + 1):
            if (i in self.kda_layers) == (i in self.full_attn_layers):
                raise ValueError(f"layer {i} is in neither or both of kda_layers and full_attn_layers")
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} is no block of {self.num_experts} experts")

    @property
    def max_session(self) -> int:
        """Items of a session the engine keeps."""
        return min(MAX_SESSION, self.model_max_length)

    @property
    def table_rows(self) -> int:
        """Rows of ``embed`` and ``lm_head``: the items a session may hold."""
        return self.vocab_slice[1]

    def is_kda(self, i: int) -> bool:
        return i in self.kda_layers

    def is_dense(self, i: int) -> bool:
        return i <= self.first_k_dense_replace

    @property
    def sparse_layers(self) -> int:
        return sum(not self.is_dense(i) for i in range(1, self.num_hidden_layers + 1))

    def even_expert_load(self, real_tokens: float) -> float:
        """Copies of ``real_tokens`` an even split gives each expert, summed
        over the sparse layers."""
        return self.sparse_layers * real_tokens * self.num_experts_per_token / self.num_experts

    def routed_copies(self, real_tokens: int) -> int:
        """Copies of ``real_tokens`` the routers send out, over all layers."""
        return self.sparse_layers * real_tokens * self.num_experts_per_token

    def buckets(self) -> tuple[int, ...]:
        top = self.max_session
        return tuple(b for b in LENGTH_BUCKETS if b < top) + (top,)

    def stream_shapes(self) -> tuple[int, ...]:
        return stream_shapes(TOKEN_BUDGET, self.max_session)

    def program_shapes(self) -> tuple[tuple[int, int], ...]:
        """``(budget // bucket, bucket)`` up the ladder of ``buckets()``, to
        which the benchmark's check pads its references: the ``[rows,
        bucket]`` programs of before the streams. NOTHING compiles them any
        more (``stream_shapes`` are the compiled ones); a test of the
        benchmark's pins this method, and it goes with that pin."""
        return tuple((max(1, TOKEN_BUDGET // bucket), bucket) for bucket in self.buckets())


Config = KimiLinearConfig


@dataclasses.dataclass(frozen=True)
class KimiLinearAlgorithmParams(BackboneParams):
    """The published ``config.json`` of moonshotai/Kimi-Linear-48B-A3B-Instruct
    and the chip's share of a stated deployment: ``experts_held`` ``[first,
    count]`` of the router's ``num_experts`` (all of them by default) and
    ``vocab_slice`` ``[first, count]`` of ``vocab_size`` (items are the
    slice's tokens). ``num_hidden_layers`` may be fewer than published: layers
    1 to that, as ``linear_attn_config`` numbers them."""

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

    ONE_ANSWER = {
        "model_type": "kimi_linear", "hidden_act": "silu", "mla_use_nope": True,
        "q_lora_rank": None, "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_expert_group": 1, "topk_group": 1,
        "num_nextn_predict_layers": 0, "rope_scaling": None, "tie_word_embeddings": False,
        "num_key_value_heads": lambda p: p.num_attention_heads,
    }

    def derived(self) -> dict:
        linear = self.linear_attn_config
        return {
            "kda_num_heads": linear["num_heads"],
            "kda_head_dim": linear["head_dim"],
            "short_conv_kernel_size": linear["short_conv_kernel_size"],
            "kda_layers": tuple(linear["kda_layers"]),
            "full_attn_layers": tuple(linear["full_attn_layers"]),
            "experts_held": tuple(self.experts_held or (0, self.num_experts)),
            "vocab_slice": tuple(self.vocab_slice or (0, self.vocab_size)),
        }

if SESSION_ALIGN % CHUNK:
    raise ImportError(f"sessions aligned to {SESSION_ALIGN} do not start on the scan's chunks of {CHUNK}")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _layer_shapes(config: KimiLinearConfig, i: int) -> dict[str, tuple[tuple[int, ...], int | None]]:
    """``name -> (shape, fan-in)`` of layer ``i``'s arrays; a fan-in of None
    marks an array that is no projection (drawn by its own rule)."""
    h = config.hidden_size
    shapes: dict = {"w_in": ((h,), None), "w_post": ((h,), None)}
    if config.is_kda(i):
        heads, d, taps = config.kda_num_heads, config.kda_head_dim, config.short_conv_kernel_size
        wide = heads * d
        shapes.update({
            "wq": ((h, wide), h), "wk": ((h, wide), h), "wv": ((h, wide), h), "wo": ((wide, h), wide),
            "conv_q": ((taps, wide), taps), "conv_k": ((taps, wide), taps), "conv_v": ((taps, wide), taps),
            "w_fa": ((h, d), h), "w_fb": ((d, wide), d), "A_log": ((heads,), None),
            "dt_bias": ((wide,), None), "w_b": ((h, heads), h),
            "w_ga": ((h, d), h), "w_gb": ((d, wide), d), "o_norm": ((d,), None),
        })
    else:
        heads = config.num_attention_heads
        qk = config.qk_nope_head_dim + config.qk_rope_head_dim
        rank = config.kv_lora_rank
        shapes.update({
            "wq": ((h, heads * qk), h), "w_kva": ((h, rank + config.qk_rope_head_dim), h),
            "kv_norm": ((rank,), None),
            "w_kvb": ((rank, heads * (config.qk_nope_head_dim + config.v_head_dim)), rank),
            "wo": ((heads * config.v_head_dim, h), heads * config.v_head_dim),
        })
    if config.is_dense(i):
        w = config.intermediate_size
        shapes.update({"dense_gate": ((h, w), h), "dense_up": ((h, w), h), "dense_down": ((w, h), w)})
    else:
        w, held = config.moe_intermediate_size, config.experts_held[1]
        shared = w * config.num_shared_experts
        shapes.update({
            "router": ((h, config.num_experts), h), "router_bias": ((config.num_experts,), None),
            "gate": ((held, h, w), h), "up": ((held, h, w), h), "down": ((held, w, h), w),
            "shared_gate": ((h, shared), h), "shared_up": ((h, shared), h),
            "shared_down": ((shared, h), shared),
        })
    return shapes


def weight_shapes(config: KimiLinearConfig) -> dict[str, tuple[int, ...]]:
    h, rows = config.hidden_size, config.table_rows
    shapes = {"embed": (rows, h), "final_norm": (h,), "lm_head": (rows, h)}
    for i in range(1, config.num_hidden_layers + 1):
        shapes.update({f"{i}.{name}": shape for name, (shape, _) in _layer_shapes(config, i).items()})
    return shapes


@functools.partial(jax.jit, static_argnames=("shape", "low", "high", "dtype"))
def _log_uniform(key, shape, low, high, dtype):
    return jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(low), np.log(high))).astype(dtype)


def init_weights(config: KimiLinearConfig, seed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights on the device: a projection normal over
    ``sqrt(fan-in)`` so that every layer keeps the stream's scale and the
    logits come out of unit order; a norm's weight near one. ``A_log`` (a
    head) and ``dt_bias`` (a channel) are drawn so that the decays
    ``exp(-exp(A_log) softplus(. + dt_bias))`` spread over about (0.9,
    0.9999), where a decay that is dropped or applied a chunk late shows;
    the router's selection bias small and not zero, so that the choice by
    ``s + bias`` is another than the choice by ``s``."""
    fan_in: dict = {"embed": 1, "final_norm": None, "lm_head": config.hidden_size}
    for i in range(1, config.num_hidden_layers + 1):
        fan_in.update({f"{i}.{name}": f for name, (_, f) in _layer_shapes(config, i).items()})
    shapes = weight_shapes(config)
    keys = jax.random.split(jax.random.key(seed, impl="rbg"), len(shapes))
    weights = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        kind = name.rsplit(".", 1)[-1]
        if fan_in[name] is not None:
            weights[name] = _normal(key, shape, 1.0 / float(np.sqrt(fan_in[name])), 0.0, dtype)
        elif kind == "A_log":
            # exp(A_log) in (0.002, 0.1): a decay of 0.9 to 0.9999 at softplus 1 to 0.05
            weights[name] = jnp.log(_log_uniform(key, shape, 0.002, 0.1, jnp.float32)).astype(dtype)
        elif kind == "dt_bias":
            # softplus(dt_bias) in (0.05, 1)
            sp = _log_uniform(key, shape, 0.05, 1.0, jnp.float32)
            weights[name] = jnp.log(jnp.expm1(sp)).astype(dtype)
        elif kind == "router_bias":
            # a bias of 0.1 made one held expert ten times as busy as an even
            # split (my chip run, PR 31): a fitted bias EVENS the load
            weights[name] = _normal(key, shape, 0.02, 0.0, dtype)
        else:  # a norm's weight
            weights[name] = _normal(key, shape, 0.1, 1.0, dtype)
    return weights


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays (numbered from 1) under their own names."""
    prefix = f"{i}."
    return {name[len(prefix) :]: a for name, a in weights.items() if name.startswith(prefix)}


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_mixer(n, position, layer, config: KimiLinearConfig):
    """``n`` [B, L, hidden] float32 -> the mixer's output [B, L, hidden].
    ``position`` [B, L] (None: every row one session) is each token's index
    inside its session; a session begins on a chunk's first position."""
    rows, length, _ = n.shape
    heads, d = config.kda_num_heads, config.kda_head_dim
    split = (rows, length, heads, d)
    with jax.named_scope("conv"):
        q, _ = short_conv(_project(n, layer["wq"]), layer["conv_q"], position=position)
        k, _ = short_conv(_project(n, layer["wk"]), layer["conv_k"], position=position)
        v, _ = short_conv(_project(n, layer["wv"]), layer["conv_v"], position=position)
        q, k, v = _l2(q.reshape(split)) * d**-0.5, _l2(k.reshape(split)), v.reshape(split)
    with jax.named_scope("gates"):
        rate = _project(_project(n, layer["w_fa"]), layer["w_fb"]) + layer["dt_bias"].astype(jnp.float32)
        g = -jnp.exp(layer["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(rate).reshape(split)
        b = jax.nn.sigmoid(_project(n, layer["w_b"]))
        gate = jax.nn.sigmoid(_project(_project(n, layer["w_ga"]), layer["w_gb"])).reshape(split)
    with jax.named_scope("scan"):
        o, _ = kda(q, k, v, g, b, starts=None if position is None else position[:, ::CHUNK] == 0)
    o = _rms(o, layer["o_norm"], config.rms_norm_eps) * gate
    return _project(o.reshape(rows, length, heads * d), layer["wo"])


def _mla_mixer(n, segment, layer, config: KimiLinearConfig):
    """Latent attention with the latent expanded, no rotary embedding;
    inside ``segment`` [B, L] where rows are shared."""
    rows, length, _ = n.shape
    heads, nope, rope = config.num_attention_heads, config.qk_nope_head_dim, config.qk_rope_head_dim
    d_v, rank = config.v_head_dim, config.kv_lora_rank
    operand = layer["wq"].dtype
    q = _project(n, layer["wq"]).reshape(rows, length, heads, nope + rope)
    latent = _project(n, layer["w_kva"])
    c, k_r = latent[..., :rank], latent[..., rank:]
    expanded = _project(_rms(c, layer["kv_norm"], config.rms_norm_eps), layer["w_kvb"])
    expanded = expanded.reshape(rows, length, heads, nope + d_v)
    k_r = jnp.broadcast_to(k_r[:, :, None, :], (rows, length, heads, rope))
    k = jnp.concatenate([expanded[..., :nope], k_r], axis=-1)
    q, k, v = (t.transpose(0, 2, 1, 3).astype(operand) for t in (q, k, expanded[..., nope:]))
    out = fused_attention(q, k, v, causal=True, segment=segment)
    out = out.transpose(0, 2, 1, 3).reshape(rows, length, heads * d_v)
    return _project(out, layer["wo"])


def _layer(x, segment, position, layer, i: int, config: KimiLinearConfig):
    """Decoder layer ``i`` over ``x`` [B, L, hidden] float32: ``(x', [busiest
    held expert's copies, copies routed to a held expert, 1 if they overflowed
    the held block (``ops/moe.held_expert_ffn`` took more than one round)])``
    of REAL tokens (``segment`` not -1; the padding's copies get no row);
    zeros for a dense layer. ``segment`` and ``position`` None: every row one
    session."""
    rows, length, hidden = x.shape
    eps = config.rms_norm_eps
    kind = "kda" if config.is_kda(i) else "mla"
    with jax.named_scope(kind):
        n1 = _rms(x, layer["w_in"], eps)
        if kind == "kda":
            h = x + _kda_mixer(n1, position, layer, config)
        else:
            h = x + _mla_mixer(n1, segment, layer, config)
    # the feed-forward's pre-norm stands under its first reader's scope and
    # the residual sum under its last writer's (XLA names a fusion by its
    # root), so that the scopes' times add up to the program's
    if config.is_dense(i):
        with jax.named_scope("dense"):
            n2 = _rms(h, layer["w_post"], eps).reshape(rows * length, hidden)
            y = moe.gated_mlp(n2, layer["dense_gate"], layer["dense_up"], layer["dense_down"])
            out = h + y.reshape(rows, length, hidden)
        return out, jnp.zeros(3, jnp.int32)
    first, count = config.experts_held
    with jax.named_scope("router"):
        n2 = _rms(h, layer["w_post"], eps).reshape(rows * length, hidden)
        weights, experts = moe.route_sigmoid(
            n2, layer["router"], layer["router_bias"], config.num_experts_per_token,
            config.routed_scaling_factor,
        )
        real = None if segment is None else (segment >= 0).reshape(-1)
        load = moe.expert_load(experts - first, count, real)
    with jax.named_scope("experts"):
        y, rounds = moe.held_expert_ffn(
            n2, weights, experts, layer["gate"], layer["up"], layer["down"],
            held=(first, count, config.num_experts), counted=real,
        )
    with jax.named_scope("shared"):
        y = y + moe.gated_mlp(n2, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
        out = h + y.reshape(rows, length, hidden)
    return out, jnp.stack([jnp.max(load), jnp.sum(load), (rounds > 1).astype(jnp.int32)])


def _layers(weights, x, segment, position, config: KimiLinearConfig):
    counts = jnp.zeros(3, jnp.int32)
    for i in range(1, config.num_hidden_layers + 1):
        x, counted = _layer(x, segment, position, layer_of(weights, i), i, config)
        counts = counts + counted
    return x, counts


@functools.partial(jax.jit, static_argnames=("config",))
def session_vectors(weights, tokens, segment, position, last, *, config: KimiLinearConfig):
    """``R`` token streams as the rows of one program, as
    ``olmoe.session_vectors`` takes them: ``tokens``, ``segment`` and
    ``position`` [R, T] int32; ``last`` [R, S] int32, each session's last
    position IN ITS STREAM, -1 where a stream holds fewer than S. Returns
    the session vectors [R * S, hidden] float32, row by row (``rms(x_L;
    w_final)`` at ``last``; one at -1 is to be thrown away) and three counts
    summed over the sparse layers: the copies of REAL tokens the program's
    busiest held expert got, those all the held experts got, and the layers
    where they overflowed the held block."""
    with jax.named_scope("embed"):
        x = weights["embed"][tokens].astype(jnp.float32)
    x, counts = _layers(weights, x, segment, position, config)
    with jax.named_scope("head"):
        out = _rms(_at_last(x, last), weights["final_norm"], config.rms_norm_eps)
    return out, counts


@functools.partial(jax.jit, static_argnames=("config",))
def all_logits(weights, tokens, *, config: KimiLinearConfig):
    """Logits of EVERY position of ``tokens`` [B, L], every row one session,
    [B, L, vocabulary's slice]: what the parity tests compare with the
    reference's ``forward``; serving never runs it."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x, _ = _layers(weights, x, None, None, config)
    out = _rms(x, weights["final_norm"], config.rms_norm_eps)
    return jnp.dot(out, weights["lm_head"].astype(jnp.float32).T, precision=lax.Precision.HIGHEST)
