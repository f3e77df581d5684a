"""OLMoE-1B-7B as a session encoder: the device side of the sequential
engine's ``olmoe`` algorithm (``backbone.OlmoeAlgorithm``).

A session's items are the tokens. One causal forward pass over the session
(``session_vectors``) and the final-normed hidden state at its LAST real
position is the session vector; ``lm_head`` scores it through
``ops/topk.dot_top_k_async`` in the engine. Layer equations:
``olmoe_reference.py`` (the published ``modeling_olmoe.py``), which the
tests and the benchmark hold this to.

What runs: weights in bfloat16 (products with bf16 operands and float32
accumulation; the residual stream, the norms, RoPE, the router and every
softmax in float32), the layers under ``lax.scan`` over stacked weights so
that a program compiles one layer, attention through
``ops/attention.fused_attention`` at ``[B, heads, L, 128]``, the experts
through ``ops/moe``. The operands' type follows the weights': a float32
weight tree (the CPU parity tests) computes in float32.

A program is ``[R, T]`` tokens: ``R`` TOKEN STREAMS as its rows, each of
``T`` tokens, ``T`` one of ``config.stream_shapes()`` (2,048, or 4,096 where
a session is longer than that). The engine packs several sessions into a
stream, each from a multiple of ``SESSION_ALIGN`` and right-padded to the
next, and hands over every token's ``segment`` (its session's index in its
stream, -1 for padding) and ``position`` (its index inside its session).
Attention sees a key only from inside its own row and segment
(``fused_attention(segment=)`` works row by row) and RoPE turns by
``position``, so a session's positions come out as they would alone;
everything else in a layer is a token's own, and the experts' grouped
products take the tokens of ALL rows at once (``STACKED_ROWS``: why). The
padding is computed and thrown away (``pio_seq_tokens_total{kind}`` counts
it).

The weights are drawn from a seed, not fitted: fitting the backbone is not
this engine's work yet (ROADMAP R7).

``save_arrays`` / ``load_array`` at the end are the storage of every
backbone's model (``backbone.BackboneModel``), not OLMoE's alone.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu.models.sequential.records import BackboneParams
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.attention import fused_attention

# the ladder the benchmark's check pads its references by (``buckets()``)
LENGTH_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096)
# a session starts on a multiple of this in its stream (the ladder's first
# step, and ``ops/linear_attention.CHUNK``: one rule for every backbone)
SESSION_ALIGN = LENGTH_BUCKETS[0]
# tokens a stream holds (``max_session`` where a session is longer), and
# ``TOKEN_BUDGET // SESSION_ALIGN`` the sessions it may hold. From the chip
# (PERF.md, PR 26): a program takes about 20 ms and 17 ms a thousand tokens,
# so taller is cheaper a token, but a batch of 32 sessions did not fill
# 8,192 tokens when programs were cut by length bucket (43 answers a second
# against 80 at 2,048); packed streams do, and ride as ROWS (below), which
# beat the same streams end to end in a taller row
TOKEN_BUDGET = 2048
# streams of ``TOKEN_BUDGET`` tokens that ride as the ROWS of one program:
# the engine stacks a batch's streams this many at a time and sends what is
# left one by one, so the compiled shapes are the closed set [STACKED_ROWS,
# TOKEN_BUDGET] and [1, T] for T of ``stream_shapes()``. A layer's experts
# then meet all the rows' tokens at once: 1,024 rows an expert, not 256 (one
# tile of ``ops/moe.TILING`` and a bit for the busiest), and the layer's
# matrices are read once for the rows. From the chip (PERF.md, PR 38; ms a
# STREAM, the top-k included): [1, 2048] 37.5, [2, 2048] 32.7, [4, 2048]
# 31.6, [8, 2048] 31.4, two streams end to end as [1, 4096] 32.8, four as
# [1, 8192] 33.1. A batch of 32 sessions is 6 or 7 streams, so fours leave
# fewer behind than eights: batches a second 4.35 by fours, 4.06 by eights,
# 4.33 by twos, 3.88 one by one
STACKED_ROWS = 4

LAYER_ARRAYS = (
    "w_in", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "w_post", "router", "gate", "up", "down",
)
EXPERT_ARRAYS = ("gate", "up", "down")
TOP_ARRAYS = ("embed", "final_norm", "lm_head")


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    """The keys of the published ``config.json`` that shape the program."""

    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_experts: int
    num_experts_per_tok: int
    vocab_size: int
    max_position_embeddings: int
    rms_norm_eps: float
    rope_theta: float

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    # what the engine asks of any backbone's configuration
    @property
    def table_rows(self) -> int:
        """Rows of ``embed`` and ``lm_head``: the items a session may hold."""
        return self.vocab_size

    @property
    def max_session(self) -> int:
        """Items of a session the engine keeps."""
        return self.max_position_embeddings

    def routed_copies(self, real_tokens: int) -> int:
        """Copies of ``real_tokens`` the routers send out, over all layers."""
        return self.num_hidden_layers * real_tokens * self.num_experts_per_tok

    def even_expert_load(self, real_tokens: float) -> float:
        """Copies of ``real_tokens`` an even split gives each expert, summed
        over the layers."""
        return self.num_hidden_layers * real_tokens * self.num_experts_per_tok / self.num_experts

    def buckets(self) -> tuple[int, ...]:
        """The length buckets up to the longest session the model takes."""
        top = self.max_position_embeddings
        return tuple(b for b in LENGTH_BUCKETS if b < top) + (top,)

    def stream_shapes(self) -> tuple[int, ...]:
        return stream_shapes(TOKEN_BUDGET, self.max_session)


Config = OlmoeConfig


@dataclasses.dataclass(frozen=True)
class OlmoeAlgorithmParams(BackboneParams):
    """The published ``config.json`` of allenai/OLMoE-1B-7B-0125-Instruct."""

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

    ONE_ANSWER = {
        "model_type": "olmoe", "hidden_act": "silu", "norm_topk_prob": False,
        "rope_scaling": None, "attention_bias": False, "clip_qkv": None,
        "tie_word_embeddings": False, "num_key_value_heads": lambda p: p.num_attention_heads,
    }


def stream_shapes(budget: int, max_session: int) -> tuple[int, ...]:
    """The lengths a token stream is compiled at, the closed set ``warmup``
    compiles: the budget and, where a session may be longer, the longest
    session's (whole ``SESSION_ALIGN``s)."""
    longest = -(-max_session // SESSION_ALIGN) * SESSION_ALIGN
    return (budget,) + ((longest,) if longest > budget else ())


def bucket_of(length: int, buckets: tuple[int, ...]) -> int:
    return next(b for b in buckets if b >= length)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_shapes(config: OlmoeConfig) -> dict[str, tuple[int, ...]]:
    h, w, e = config.hidden_size, config.intermediate_size, config.num_experts
    n, v = config.num_hidden_layers, config.vocab_size
    return {
        "embed": (v, h), "final_norm": (h,), "lm_head": (v, h),
        "w_in": (n, h), "wq": (n, h, h), "wk": (n, h, h), "wv": (n, h, h), "wo": (n, h, h),
        "q_norm": (n, h), "k_norm": (n, h), "w_post": (n, h), "router": (n, h, e),
        "gate": (n, e, h, w), "up": (n, e, h, w), "down": (n, e, w, h),
    }


def init_weights(config: OlmoeConfig, seed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights on the device, ``[layers, ...]`` stacked: a projection
    normal over ``sqrt(fan-in)`` so that every layer keeps the stream's
    scale and the logits come out of unit order; a norm's weight near one."""
    shapes = weight_shapes(config)
    fan_in = {
        "wq": config.hidden_size, "wk": config.hidden_size, "wv": config.hidden_size,
        "wo": config.hidden_size, "router": config.hidden_size, "gate": config.hidden_size,
        "up": config.hidden_size, "down": config.intermediate_size, "lm_head": config.hidden_size,
        "embed": 1,
    }
    keys = jax.random.split(jax.random.key(seed, impl="rbg"), len(shapes))
    weights = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name in fan_in:
            scale = 1.0 / float(np.sqrt(fan_in[name]))
            weights[name] = _normal(key, shape, scale, 0.0, dtype)
        else:  # a norm's weight
            weights[name] = _normal(key, shape, 0.1, 1.0, dtype)
    return weights


@functools.partial(jax.jit, static_argnames=("shape", "scale", "mean", "dtype"))
def _normal(key, shape, scale, mean, dtype):
    return (mean + scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays, unstacked (the reference's layer form)."""
    return {name: weights[name][i] for name in LAYER_ARRAYS}


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def _rms(x, weight, eps: float):
    x = x.astype(jnp.float32)
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return weight.astype(jnp.float32) * (x * lax.rsqrt(variance + eps))


def _rope(x, position, theta: float):
    """``x`` [B, L, heads, d] float32 at ``position`` [B, L], rotate-half."""
    d = x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = position.astype(jnp.float32)[:, :, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    half = d // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _project(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _layer(x, segment, position, layer, experts_of_all_layers, index, config: OlmoeConfig):
    """Decoder layer ``index`` over ``x`` [B, L, hidden] float32: ``layer``
    holds its own arrays but the experts', which are read in place out of
    every layer's, stacked ``[layers * experts, ...]``. ``segment`` [B, L]
    (None: every row one session) keeps attention inside a session and
    marks the sessions' own positions: the padding (-1) is computed like
    them and left out of the busiest expert's count."""
    rows, length, hidden = x.shape
    heads, d = config.num_attention_heads, config.head_dim
    eps = config.rms_norm_eps
    with jax.named_scope("attn"):
        n1 = _rms(x, layer["w_in"], eps)
        q = _rms(_project(n1, layer["wq"]), layer["q_norm"], eps)
        k = _rms(_project(n1, layer["wk"]), layer["k_norm"], eps)
        v = _project(n1, layer["wv"])
        with jax.named_scope("rope"):
            q = _rope(q.reshape(rows, length, heads, d), position, config.rope_theta)
            k = _rope(k.reshape(rows, length, heads, d), position, config.rope_theta)
        operand = layer["wq"].dtype
        q, k, v = (
            t.reshape(rows, length, heads, d).transpose(0, 2, 1, 3).astype(operand)
            for t in (q, k, v)
        )
        out = fused_attention(q, k, v, causal=True, segment=segment)
        out = out.transpose(0, 2, 1, 3).reshape(rows, length, hidden)
        h = x + _project(out, layer["wo"])
    n2 = _rms(h, layer["w_post"], eps).reshape(rows * length, hidden)
    with jax.named_scope("router"):
        weights, experts = moe.route(n2, layer["router"], config.num_experts_per_tok)
        real = None if segment is None else (segment >= 0).reshape(-1)
        busiest = jnp.max(moe.expert_load(experts, config.num_experts, real))
    with jax.named_scope("experts"):
        y = moe.expert_ffn(
            n2, weights, experts, *experts_of_all_layers,
            n_experts=config.num_experts, first_group=index * config.num_experts,
        )
    return h + y.reshape(rows, length, hidden), busiest


def _layers(weights, x, segment, position, config: OlmoeConfig):
    """Every layer over ``x``, under ``lax.scan`` so that a program compiles
    one: ``(x, [layers] copies of real tokens each layer's busiest expert
    got)``. The scan slices the small arrays; the experts' stay whole."""
    stacked = tuple(
        weights[name].reshape((-1,) + weights[name].shape[2:]) for name in EXPERT_ARRAYS
    )
    sliced = {name: weights[name] for name in LAYER_ARRAYS if name not in EXPERT_ARRAYS}

    def step(x, scanned):
        index, layer = scanned
        return _layer(x, segment, position, layer, stacked, index, config)

    return lax.scan(step, x, (jnp.arange(config.num_hidden_layers), sliced))


def _at_last(x, last):
    """``x`` [R, T, hidden] at ``last`` [R, S], a row's positions from its
    own row: [R * S, hidden], row by row."""
    rows = jnp.arange(x.shape[0])[:, None]
    return x[rows, jnp.maximum(last, 0)].reshape(-1, x.shape[2])


@functools.partial(jax.jit, static_argnames=("config",))
def session_vectors(weights, tokens, segment, position, last, *, config: OlmoeConfig):
    """``R`` token streams as the rows of one program: ``tokens``,
    ``segment`` and ``position`` [R, T] int32 (the module's docstring);
    ``last`` [R, S] int32, each session's last position IN ITS STREAM, -1
    where a stream holds fewer than S. Returns the session vectors [R * S,
    hidden] float32, row by row (``rms(x_L; w_final)`` at ``last``; one at
    -1 is to be thrown away) and, summed over the layers, the number of
    copies of REAL tokens the busiest expert of the PROGRAM got."""
    with jax.named_scope("embed"):
        x = weights["embed"][tokens].astype(jnp.float32)

    x, busiest = _layers(weights, x, segment, position, config)
    with jax.named_scope("head"):
        out = _rms(_at_last(x, last), weights["final_norm"], config.rms_norm_eps)
    return out, jnp.sum(busiest)


@functools.partial(jax.jit, static_argnames=("config",))
def all_logits(weights, tokens, *, config: OlmoeConfig):
    """Logits of EVERY position of ``tokens`` [B, L], every row one session:
    what the parity tests compare with the reference's ``forward``; serving
    never runs it."""
    x = weights["embed"][tokens].astype(jnp.float32)
    position = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x, _ = _layers(weights, x, None, position, config)
    out = _rms(x, weights["final_norm"], config.rms_norm_eps)
    return jnp.dot(
        out, weights["lm_head"].astype(jnp.float32).T, precision=lax.Precision.HIGHEST
    )


# ---------------------------------------------------------------------------
# arrays on disk: one raw file an array, a JSON header beside them
# ---------------------------------------------------------------------------

HEADER = "olmoe.json"


def save_arrays(directory: str, header: dict, arrays: dict) -> None:
    """``arrays`` (name -> device or host array) as ``<name>.bin`` of raw
    little-endian bytes, one at a time, and ``header`` with their types and
    shapes as ``olmoe.json``, written last: a directory without it is not a
    model."""
    os.makedirs(directory, exist_ok=True)
    specs = {}
    for name, array in arrays.items():
        # one array on the host at a time; the chip hands some back strided
        host = np.ascontiguousarray(array)
        specs[name] = {"dtype": str(host.dtype), "shape": list(host.shape)}
        host.tofile(os.path.join(directory, f"{name}.bin"))
        del host
    tmp = os.path.join(directory, HEADER + ".tmp")
    with open(tmp, "w") as f:
        json.dump({**header, "arrays": specs}, f)
    os.replace(tmp, os.path.join(directory, HEADER))


def load_header(directory: str) -> dict:
    with open(os.path.join(directory, HEADER)) as f:
        return json.load(f)


def load_array(directory: str, name: str, spec: dict) -> np.ndarray:
    dtype = jnp.dtype(spec["dtype"])
    raw = np.fromfile(os.path.join(directory, f"{name}.bin"), dtype=np.uint8)
    expected = int(np.prod(spec["shape"], dtype=np.int64)) * dtype.itemsize
    if raw.size != expected:
        raise ValueError(
            f"{name}.bin holds {raw.size} bytes, its header says {expected}: truncated model"
        )
    return raw.view(dtype).reshape(spec["shape"])
