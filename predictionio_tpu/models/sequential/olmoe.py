"""OLMoE-1B-7B as a session encoder: the device side of the sequential
engine's ``olmoe`` algorithm (``engine.OlmoeAlgorithm``).

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

Sessions are RIGHT-padded to their length bucket: under causal attention a
real position never sees the padding behind it, so no key mask is needed and
a real position's output is exact; the padding's rows are computed and thrown
away (``pio_seq_tokens_total{kind}`` counts them).

The weights are drawn from a seed, not fitted: fitting the backbone is not
this engine's work yet (ROADMAP R7).

``save_arrays`` / ``load_array`` at the end are the storage of every
backbone's model (``engine.BackboneModel``), not OLMoE's alone.
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

from predictionio_tpu.ops import moe
from predictionio_tpu.ops.attention import fused_attention

# a session is padded to the first of these that holds it
LENGTH_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096)
# padded tokens a program holds (one row where a session is longer). From the
# chip (PERF.md, PR 26): a program takes about 20 ms and 17 ms a thousand
# tokens, so taller is cheaper a token, but a batch of 32 sessions spreads
# over six buckets and fills no tall program: with 8,192 tokens a program the
# cell answers 43 queries a second, with 2,048 it answers 80, and a second
# height beside that (8,192 for a group that fills it) wins 1 to 3%
TOKEN_BUDGET = 2048

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

    def program_shapes(self) -> tuple[tuple[int, int], ...]:
        """Every ``(rows, bucket)`` a program is launched at: the closed set
        ``warmup`` compiles."""
        return tuple((program_rows(bucket), bucket) for bucket in self.buckets())


Config = OlmoeConfig


def program_rows(bucket: int) -> int:
    """The height of a bucket's programs."""
    return max(1, TOKEN_BUDGET // bucket)


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


def _rope(x, theta: float):
    """``x`` [B, L, heads, d] float32, positions 0..L-1, rotate-half."""
    length, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    half = d // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _project(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _layer(x, real, layer, experts_of_all_layers, index, config: OlmoeConfig):
    """Decoder layer ``index`` over ``x`` [B, L, hidden] float32: ``layer``
    holds its own arrays but the experts', which are read in place out of
    every layer's, stacked ``[layers * experts, ...]``. ``real`` [B, L]
    marks the sessions' own positions: the padding is computed like them
    and left out of the busiest expert's count."""
    rows, length, hidden = x.shape
    heads, d = config.num_attention_heads, config.head_dim
    eps = config.rms_norm_eps
    with jax.named_scope("attn"):
        n1 = _rms(x, layer["w_in"], eps)
        q = _rms(_project(n1, layer["wq"]), layer["q_norm"], eps)
        k = _rms(_project(n1, layer["wk"]), layer["k_norm"], eps)
        v = _project(n1, layer["wv"])
        with jax.named_scope("rope"):
            q = _rope(q.reshape(rows, length, heads, d), config.rope_theta)
            k = _rope(k.reshape(rows, length, heads, d), config.rope_theta)
        operand = layer["wq"].dtype
        q, k, v = (
            t.reshape(rows, length, heads, d).transpose(0, 2, 1, 3).astype(operand)
            for t in (q, k, v)
        )
        out = fused_attention(q, k, v, causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(rows, length, hidden)
        h = x + _project(out, layer["wo"])
    n2 = _rms(h, layer["w_post"], eps).reshape(rows * length, hidden)
    with jax.named_scope("router"):
        weights, experts = moe.route(n2, layer["router"], config.num_experts_per_tok)
        busiest = jnp.max(moe.expert_load(experts, config.num_experts, real.reshape(-1)))
    with jax.named_scope("experts"):
        y = moe.expert_ffn(
            n2, weights, experts, *experts_of_all_layers,
            n_experts=config.num_experts, first_group=index * config.num_experts,
        )
    return h + y.reshape(rows, length, hidden), busiest


def _layers(weights, x, real, config: OlmoeConfig):
    """Every layer over ``x``, under ``lax.scan`` so that a program compiles
    one: ``(x, [layers] copies of real tokens each layer's busiest expert
    got)``. The scan slices the small arrays; the experts' stay whole."""
    stacked = tuple(
        weights[name].reshape((-1,) + weights[name].shape[2:]) for name in EXPERT_ARRAYS
    )
    sliced = {name: weights[name] for name in LAYER_ARRAYS if name not in EXPERT_ARRAYS}

    def step(x, scanned):
        index, layer = scanned
        return _layer(x, real, layer, stacked, index, config)

    return lax.scan(step, x, (jnp.arange(config.num_hidden_layers), sliced))


@functools.partial(jax.jit, static_argnames=("config",))
def session_vectors(weights, tokens, last, *, config: OlmoeConfig):
    """``tokens`` [B, L] int32, right-padded; ``last`` [B] int32, each
    session's last real position, -1 for a padding row. Returns the session
    vectors [B, hidden] float32 (``rms(x_L; w_final)`` at ``last``; a
    padding row's is to be thrown away) and, summed over the layers, the
    number of copies of REAL tokens the busiest expert got."""
    real = jnp.arange(tokens.shape[1])[None, :] <= last[:, None]
    with jax.named_scope("embed"):
        x = weights["embed"][tokens].astype(jnp.float32)

    x, busiest = _layers(weights, x, real, config)
    with jax.named_scope("head"):
        at_last = x[jnp.arange(tokens.shape[0]), jnp.maximum(last, 0)]
        out = _rms(at_last, weights["final_norm"], config.rms_norm_eps)
    return out, jnp.sum(busiest)


@functools.partial(jax.jit, static_argnames=("config",))
def all_logits(weights, tokens, *, config: OlmoeConfig):
    """Logits of EVERY position, [B, L, vocabulary]: what the parity tests
    compare with the reference's ``forward``; serving never runs it."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x, _ = _layers(weights, x, jnp.ones(tokens.shape, bool), config)
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
