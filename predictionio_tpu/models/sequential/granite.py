"""granite-4.0-h-small's block as a session encoder: the device side of the
sequential engine's ``granite`` algorithm (``backbone.GraniteAlgorithm``).

As ``olmoe.py``, ``kimi_linear.py`` and ``lfm2.py`` are for their backbones: a
session's items are the tokens, one causal forward pass over the session
(``session_vectors``, the SAME name, arguments and results, so that the
engine's launch and the benchmark's readers serve all of them) gives the
final-normed hidden state at its last real position, and the engine scores it
against the embedding (the head is TIED to it: ``backbone.BackboneModel.head``).
Layer equations: ``granite_reference.py``, which the tests and the benchmark
hold this to.

Layers are of two kinds, read from the published ``layer_types`` LIST (its
first ``num_hidden_layers``: nine ``mamba`` to one ``attention``), unrolled,
each with its own arrays (a flat tree, layer ``i``'s as ``"<i>.<name>"``,
numbered from 0 as the list numbers them). The token mixer is MAMBA-2
(``mamba``: one projection 16,768 wide cut three ways ``[z | xBC | dt]``, a
causal depthwise convolution of four taps with a bias under SiLU
(``ops/linear_attention.short_conv(bias=)``), the state-space scan
(``ops/linear_attention.ssd``: 128 heads of 64 over a state of 128, ``B`` and
``C`` of ONE group, the step ``softplus(dt + dt_bias)``, the skip ``D``; on
the chip ONE Pallas kernel a layer, ``ssd_kernel``, whose grid walks the
chunks in order with a block of heads' state in VMEM, off it the XLA form)
and an RMSNorm over all 8,192 channels of ``y * silu(z)``) or grouped-query
attention WITHOUT positions (``attention``: 32 query heads over 8 key/value
heads of 128, no rotary embedding, no norm a head,
``ops/attention.fused_attention``). Every layer's feed-forward is sparse
(``ops/moe``: a softmax router over ALL ``num_local_experts``, the chosen ten
renormalised, the grouped products over the experts HELD here) beside one
shared expert. Four scalars of the configuration change the paths between
them: the embedding is multiplied by ``embedding_multiplier``, a mixer's and a
feed-forward's output by ``residual_multiplier`` before it joins the stream,
the attention's scores by ``attention_multiplier`` (NOT ``head_dim ** -0.5``)
and the logits are divided by ``logits_scaling``.

What a chip holds is a share of a stated deployment (``experts_held``,
``vocab_slice``): the router keeps its published width and its experts per
token, the held experts' part of the result goes on to the next layer, and
the embedding (which is the head) and the scores are over the slice.

What runs: weights in bfloat16 (products with bf16 operands and float32
accumulation); the residual stream, the norms, the convolution, the step, the
decays, the scan and its state, the gate, the router and every softmax in
float32. A float32 weight tree (the CPU parity tests) computes in float32.

A program is ``[R, T]`` tokens, ``R`` token streams of several sessions each
as its rows, as ``olmoe.py``'s is (``segment``, ``position``): attention sees
a key only from inside its own row and segment, the scan's state reaches a
position only from its own session (``ssd(segment=)``: a chunk of ``SSD_CHUNK``
positions may hold several sessions, which start on multiples of
``SESSION_ALIGN``; the kernel takes the ids into the chunk's mask and XLA, in
front of it, into what the state reaches and what reaches the state) and the
convolution reaches no further back than a
session's first item, so a session's positions come out as they would alone.
The experts and the shared expert take the tokens of all rows at once.
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
from predictionio_tpu.ops.linear_attention import short_conv, ssd

__all__ = [
    "GraniteConfig", "GraniteAlgorithmParams", "TOKEN_BUDGET", "STACKED_ROWS", "MAX_SESSION", "SESSION_ALIGN",
    "SSD_CHUNK", "bucket_of", "weight_shapes", "init_weights", "layer_of", "attention_mixer", "session_vectors",
    "all_logits",
]

# tokens a stream holds (``MAX_SESSION`` where a session is longer)
TOKEN_BUDGET = 2048
# streams that ride as the rows of one program (``olmoe.STACKED_ROWS``: why
# and how). ONE here, from the chip (PERF.md, PR 49; the bare program, ms a
# STREAM of 2,048 tokens): [1, 2048] 126.3, [2, 2048] 125.6, [4, 2048] 123.0,
# two streams end to end as [1, 4096] 129.3. Rows gain 2.6% at four, which is
# what ``kimi_linear`` and ``lfm2`` then LOST in their cells to the streams a
# batch leaves behind its stacks and to a third compiled shape; the cell was
# not run by fours here
STACKED_ROWS = 1
# items of a session the engine keeps, and so the longest program: the
# traffic's bound (the model's own is ``max_position_embeddings``, 131,072)
MAX_SESSION = 4096
# positions the scan evaluates at once. The published ``mamba_chunk_size`` 256
# is the published kernels' tile, not arithmetic (``ssd`` is the same function
# at any width: a test holds 64, 128 and 256 to the recurrence). From the chip
# (PERF.md, PR 49; the whole program at [1, 2048], ms): 64: 145.7, **128:
# 126.3**, 256: 127.1 (the triangles' bytes grow with the chunk, the states
# handed on fall with it). The kernel that serves since PR 51 (PERF.md, PR
# 51; the bare scan at [1, 2048] / [1, 4096], ms a call by the wall clock of
# nine chained calls, a pass over ``y`` between them in it): **128: 0.45 /
# 1.07**, 256: 0.52 / 1.20 (a head's triangle is twice the elements a token;
# the kernel's own events in the cell's trace at 128: 0.34 / 0.65)
SSD_CHUNK = 128

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteConfig:
    """The keys of the published ``config.json`` that shape the program, and
    the chip's share."""

    hidden_size: int
    intermediate_size: int  # ONE routed expert's width
    shared_intermediate_size: int
    num_hidden_layers: int
    layer_types: tuple[str, ...]  # a mixer's kind a layer, from 0; the first ``num_hidden_layers`` run
    num_attention_heads: int
    num_key_value_heads: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int
    num_local_experts: int  # the router's width
    num_experts_per_tok: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    rms_norm_eps: float
    max_position_embeddings: int
    experts_held: tuple[int, int]  # (first, count) of the router's experts
    vocab_slice: tuple[int, int]  # (first, count) of the published vocabulary

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(str(kind) for kind in self.layer_types))
        for name in ("experts_held", "vocab_slice"):
            object.__setattr__(self, name, tuple(int(v) for v in getattr(self, name)))
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, num_hidden_layers {self.num_hidden_layers}"
            )
        unknown = set(self.layer_types) - {MAMBA, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}: only {MAMBA!r} and {ATTENTION!r} are built")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the key/value heads do not divide the heads")
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.num_local_experts):
            raise ValueError(f"experts_held {self.experts_held} is no block of {self.num_local_experts} experts")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        """Channels of the scan's ``x``, of ``z`` and of the gated norm."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def max_session(self) -> int:
        """Items of a session the engine keeps."""
        return min(MAX_SESSION, self.max_position_embeddings)

    @property
    def table_rows(self) -> int:
        """Rows of ``embed``, which is the head too: the items a session may hold."""
        return self.vocab_slice[1]

    def is_mamba(self, i: int) -> bool:
        return self.layer_types[i] == MAMBA

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers

    def even_expert_load(self, real_tokens: float) -> float:
        """Copies of ``real_tokens`` an even split gives each expert, summed
        over the layers."""
        return self.sparse_layers * real_tokens * self.num_experts_per_tok / self.num_local_experts

    def routed_copies(self, real_tokens: int) -> int:
        """Copies of ``real_tokens`` the routers send out, over all layers."""
        return self.sparse_layers * real_tokens * self.num_experts_per_tok

    def buckets(self) -> tuple[int, ...]:
        """The ladder the benchmark's check pads its references by."""
        top = self.max_session
        return tuple(b for b in LENGTH_BUCKETS if b < top) + (top,)

    def stream_shapes(self) -> tuple[int, ...]:
        return stream_shapes(TOKEN_BUDGET, self.max_session)


Config = GraniteConfig


@dataclasses.dataclass(frozen=True)
class GraniteAlgorithmParams(BackboneParams):
    """The published ``config.json`` of ibm-granite/granite-4.0-h-small and the
    chip's share of a stated deployment: ``experts_held`` ``[first, count]`` of
    the router's ``num_local_experts`` (all of them by default) and
    ``vocab_slice`` ``[first, count]`` of ``vocab_size`` (items are the slice's
    tokens). ``num_hidden_layers`` may be fewer than published: layers 0 to
    that, as ``layer_types`` numbers them. ``mamba_chunk_size`` is the
    published kernels' tile and shapes nothing here (``granite.SSD_CHUNK``)."""

    hidden_size: int = 4096
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: tuple = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_bias: bool = False
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_n_heads: int = 128
    mamba_proj_bias: bool = False
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    normalization_function: str = "rmsnorm"
    position_embedding_type: str = "nope"
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: dict | None = None
    tie_word_embeddings: bool = True
    vocab_size: int = 100352
    max_position_embeddings: int = 131072
    model_type: str = "granitemoehybrid"
    experts_held: tuple | None = None
    vocab_slice: tuple | None = None
    seed: int = 3

    ONE_ANSWER = {
        "model_type": "granitemoehybrid", "hidden_act": "silu", "attention_bias": False,
        "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_n_groups": 1,
        "normalization_function": "rmsnorm", "position_embedding_type": "nope", "rope_scaling": None,
        "tie_word_embeddings": True,
        # the scan's inner width is the heads' (the one the program reads) and the expansion's alike
        "mamba_expand": lambda p: p.mamba_n_heads * p.mamba_d_head // p.hidden_size,
    }

    def derived(self) -> dict:
        return {
            "attention_multiplier": float(self.attention_multiplier),
            "embedding_multiplier": float(self.embedding_multiplier),
            "residual_multiplier": float(self.residual_multiplier),
            "logits_scaling": float(self.logits_scaling),
            "experts_held": tuple(self.experts_held or (0, self.num_local_experts)),
            "vocab_slice": tuple(self.vocab_slice or (0, self.vocab_size)),
        }


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _layer_shapes(config: GraniteConfig, i: int) -> dict[str, tuple[tuple[int, ...], int | None]]:
    """``name -> (shape, fan-in)`` of layer ``i``'s arrays (a projection kept
    ``[in, out]``); a fan-in of None marks an array that is no projection
    (drawn by its own rule)."""
    h = config.hidden_size
    shapes: dict = {"w_in": ((h,), None), "w_post": ((h,), None)}
    if config.is_mamba(i):
        heads, inner, taps = config.mamba_n_heads, config.mamba_inner, config.mamba_d_conv
        conv = inner + 2 * config.mamba_d_state  # x, B and C go through the convolution
        shapes.update({
            "in_proj": ((h, inner + conv + heads), h), "conv": ((taps, conv), taps), "conv_bias": ((conv,), None),
            "dt_bias": ((heads,), None), "A_log": ((heads,), None), "D": ((heads,), None),
            "gate_norm": ((inner,), None), "out_proj": ((inner, h), inner),
        })
    else:
        heads, kv, d = config.num_attention_heads, config.num_key_value_heads, config.head_dim
        shapes.update({
            "wq": ((h, heads * d), h), "wk": ((h, kv * d), h), "wv": ((h, kv * d), h),
            "wo": ((heads * d, h), heads * d),
        })
    w, held, shared = config.intermediate_size, config.experts_held[1], config.shared_intermediate_size
    shapes.update({
        "router": ((h, config.num_local_experts), h),
        "gate": ((held, h, w), h), "up": ((held, h, w), h), "down": ((held, w, h), w),
        "shared_gate": ((h, shared), h), "shared_up": ((h, shared), h), "shared_down": ((shared, h), shared),
    })
    return shapes


def weight_shapes(config: GraniteConfig) -> dict[str, tuple[int, ...]]:
    shapes = {"embed": (config.table_rows, config.hidden_size), "final_norm": (config.hidden_size,)}
    for i in range(config.num_hidden_layers):
        shapes.update({f"{i}.{name}": shape for name, (shape, _) in _layer_shapes(config, i).items()})
    return shapes


@functools.partial(jax.jit, static_argnames=("shape", "low", "high"))
def _log_uniform(key, shape, low, high):
    return jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(low), np.log(high)))


def init_weights(config: GraniteConfig, seed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights on the device: a projection normal over
    ``sqrt(fan-in)`` so that every layer keeps the stream's scale; ``embed``
    over ``sqrt(hidden) / logits_scaling``, because it is the head too and the
    logits, divided by ``logits_scaling``, are to come out of unit order; a
    norm's weight near one. The scan's own are drawn as Mamba-2 initialises
    them, so that seeded decays have a trained model's strength: ``A_log =
    log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly from (0.001, 0.1), ``D`` ones; the convolution's bias small
    and not zero, so that leaving it out is another function."""
    # (``embed`` as a fan-in: a deviation of ``logits_scaling / sqrt(hidden)``)
    fan_in: dict = {"embed": config.hidden_size / config.logits_scaling**2, "final_norm": None}
    for i in range(config.num_hidden_layers):
        fan_in.update({f"{i}.{name}": f for name, (_, f) in _layer_shapes(config, i).items()})
    shapes = weight_shapes(config)
    keys = jax.random.split(jax.random.key(seed, impl="rbg"), len(shapes))
    weights = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        kind = name.rsplit(".", 1)[-1]
        if fan_in[name] is not None:
            weights[name] = _normal(key, shape, 1.0 / float(np.sqrt(fan_in[name])), 0.0, dtype)
        elif kind == "A_log":
            weights[name] = jnp.log(_log_uniform(key, shape, 1.0, 16.0)).astype(dtype)
        elif kind == "dt_bias":
            weights[name] = jnp.log(jnp.expm1(_log_uniform(key, shape, 0.001, 0.1))).astype(dtype)
        elif kind == "D":
            weights[name] = jnp.ones(shape, dtype)
        elif kind == "conv_bias":
            weights[name] = _normal(key, shape, 0.1, 0.0, dtype)
        else:  # a norm's weight
            weights[name] = _normal(key, shape, 0.1, 1.0, dtype)
    return weights


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays (numbered from 0) under their own names."""
    prefix = f"{i}."
    return {name[len(prefix) :]: a for name, a in weights.items() if name.startswith(prefix)}


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _mamba_mixer(n, segment, position, layer, config: GraniteConfig):
    """``n`` [B, L, hidden] float32 -> the Mamba-2 mixer's output [B, L,
    hidden]. ``segment`` and ``position`` [B, L] (None: every row one
    session): each token's session and its index inside it."""
    rows, length, _ = n.shape
    heads, p, state, inner = config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state, config.mamba_inner
    with jax.named_scope("in_proj"):
        z, xbc, dt = jnp.split(_project(n, layer["in_proj"]), [inner, 2 * (inner + state)], axis=-1)
    with jax.named_scope("conv"):
        xbc, _ = short_conv(xbc, layer["conv"], position=position, bias=layer["conv_bias"])
        x, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
    with jax.named_scope("ssd"):
        step = jax.nn.softplus(dt + layer["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(layer["A_log"].astype(jnp.float32))
        y, _ = ssd(x.reshape(rows, length, heads, p), step, a, b, c, layer["D"], segment=segment, chunk=SSD_CHUNK)
    with jax.named_scope("gate_norm"):
        # the norm AFTER the gate, over all the channels at once (one group)
        y = _rms(y.reshape(rows, length, inner) * jax.nn.silu(z), layer["gate_norm"], config.rms_norm_eps)
    with jax.named_scope("out_proj"):
        return _project(y, layer["out_proj"])


def attention_mixer(n, segment, layer, config: GraniteConfig):
    """Grouped-query attention with no positional encoding and no norm a
    head; inside ``segment`` [B, L] where rows are shared. The scores are
    scaled by ``attention_multiplier``, which ``fused_attention`` does not
    take (it scales by ``head_dim ** -0.5``, and its kernels are every other
    backbone's): ``q`` is multiplied by the quotient of the two, in float32,
    before its cast to the operands' type."""
    rows, length, _ = n.shape
    heads, kv, d = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    q = _project(n, layer["wq"]).reshape(rows, length, heads, d) * (config.attention_multiplier * d**0.5)
    k = _project(n, layer["wk"]).reshape(rows, length, kv, d)
    v = _project(n, layer["wv"]).reshape(rows, length, kv, d)
    operand = layer["wq"].dtype
    q, k, v = (t.transpose(0, 2, 1, 3).astype(operand) for t in (q, k, v))
    out = fused_attention(q, k, v, causal=True, segment=segment)
    return _project(out.transpose(0, 2, 1, 3).reshape(rows, length, heads * d), layer["wo"])


def _layer(x, segment, position, layer, i: int, config: GraniteConfig):
    """Decoder layer ``i`` over ``x`` [B, L, hidden] float32: ``(x', [busiest
    held expert's copies, copies routed to a held expert, 1 if they overflowed
    the held block (``ops/moe.held_expert_ffn``: at half the experts held, 36
    of 72, all the copies' rows, every held group from a tile's edge)])`` of
    REAL tokens (``segment`` not -1; the padding's copies count for nothing
    and get no row). ``segment`` and ``position`` None: every row one
    session."""
    rows, length, hidden = x.shape
    eps, residual = config.rms_norm_eps, config.residual_multiplier
    if config.is_mamba(i):
        with jax.named_scope("mamba"):
            h = x + residual * _mamba_mixer(_rms(x, layer["w_in"], eps), segment, position, layer, config)
    else:
        with jax.named_scope("attn"):
            h = x + residual * attention_mixer(_rms(x, layer["w_in"], eps), segment, layer, config)
    # the feed-forward's pre-norm stands under its first reader's scope and
    # the residual sum under its last writer's (``kimi_linear._layer``: why)
    first, count = config.experts_held
    with jax.named_scope("router"):
        n2 = _rms(h, layer["w_post"], eps).reshape(rows * length, hidden)
        # a softmax over the ten chosen logits IS the softmax over all, its top ten over their sum
        weights, experts = moe.route(n2, layer["router"], config.num_experts_per_tok, renormalise=True)
        real = None if segment is None else (segment >= 0).reshape(-1)
        load = moe.expert_load(experts - first, count, real)
    with jax.named_scope("experts"):
        # the way out on an overflow: ten layers unrolled take the loop, as
        # ``kimi_linear``'s seven (the readings stand beside ``moe.HELD_ROOM``)
        y, rounds = moe.held_expert_ffn(
            n2, weights, experts, layer["gate"], layer["up"], layer["down"],
            held=(first, count, config.num_local_experts), counted=real, overflow="rounds",
        )
    with jax.named_scope("shared"):
        y = y + moe.gated_mlp(n2, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
        out = h + residual * y.reshape(rows, length, hidden)
    return out, jnp.stack([jnp.max(load), jnp.sum(load), (rounds > 1).astype(jnp.int32)])


def _layers(weights, x, segment, position, config: GraniteConfig):
    counts = jnp.zeros(3, jnp.int32)
    for i in range(config.num_hidden_layers):
        x, counted = _layer(x, segment, position, layer_of(weights, i), i, config)
        counts = counts + counted
    return x, counts


def _embed(weights, tokens, config: GraniteConfig):
    return config.embedding_multiplier * weights["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("config",))
def session_vectors(weights, tokens, segment, position, last, *, config: GraniteConfig):
    """``R`` token streams as the rows of one program, as
    ``olmoe.session_vectors`` takes them: ``tokens``, ``segment`` and
    ``position`` [R, T] int32; ``last`` [R, S] int32, each session's last
    position IN ITS STREAM, -1 where a stream holds fewer than S. Returns
    the session vectors [R * S, hidden] float32, row by row (``rms(x_L;
    final_norm) / logits_scaling`` at ``last``, so that the engine's product
    with the embedding IS the scaled logits; one at -1 is to be thrown away)
    and three counts summed over the layers, as ``kimi_linear``'s: the copies
    of REAL tokens the program's busiest held expert got, those all the held
    experts got, and the layers where they overflowed the held block."""
    with jax.named_scope("embed"):
        x = _embed(weights, tokens, config)
    x, counts = _layers(weights, x, segment, position, config)
    with jax.named_scope("head"):
        out = _rms(_at_last(x, last), weights["final_norm"], config.rms_norm_eps) / config.logits_scaling
    return out, counts


@functools.partial(jax.jit, static_argnames=("config",))
def all_logits(weights, tokens, *, config: GraniteConfig):
    """Logits of EVERY position of ``tokens`` [B, L], every row one session,
    [B, L, vocabulary's slice]: what the parity tests compare with the
    reference's ``forward``; serving never runs it."""
    x, _ = _layers(weights, _embed(weights, tokens, config), None, None, config)
    out = _rms(x, weights["final_norm"], config.rms_norm_eps) / config.logits_scaling
    return jnp.dot(out, weights["embed"].astype(jnp.float32).T, precision=lax.Precision.HIGHEST)
