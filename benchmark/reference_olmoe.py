"""The plain reference that decides ``correct`` in the ``seq-olmoe`` cells: a
copy of ``predictionio_tpu/models/sequential/olmoe_reference.py``, function
for function (``tests/benchmark_harness/test_benchmark_seq_olmoe.py`` holds
the two equal), kept with the benchmark so that a change to the program's
copy cannot move the yardstick. The forward pass of ``modeling_olmoe.py``
(allenai/OLMoE-1B-7B-0125-Instruct) in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, one session at a time, every
expert computed densely and masked by the router's choice, nothing imported
from the program. The layer equations and each departure from the published
code are in the original's docstring.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = "highest"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, weight, eps: float):
    x = _f32(x)
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return _f32(weight) * (x * jax.lax.rsqrt(variance + eps))


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta: float):
    """``x`` [L, heads, d], positions 0..L-1."""
    length, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [L, d]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    return x * cos + rotate_half(x) * sin


def attention(n1, layer, config):
    """Causal self-attention of one session, ``n1`` [L, hidden]."""
    heads = int(config["num_attention_heads"])
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    length, hidden = n1.shape
    d = hidden // heads
    with jax.default_matmul_precision(_HIGHEST):
        q = rms_norm(n1 @ _f32(layer["wq"]), layer["q_norm"], eps)
        k = rms_norm(n1 @ _f32(layer["wk"]), layer["k_norm"], eps)
        v = n1 @ _f32(layer["wv"])
        q = rope(q.reshape(length, heads, d), theta)
        k = rope(k.reshape(length, heads, d), theta)
        v = v.reshape(length, heads, d)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hqk,khd->qhd", weights, v).reshape(length, hidden)
        return out @ _f32(layer["wo"])


def router_probs(n2, layer):
    """softmax over the experts of the router's logits, float32: [L, E]."""
    with jax.default_matmul_precision(_HIGHEST):
        return jax.nn.softmax(n2 @ _f32(layer["router"]), axis=-1)


def router_choice(probs, k: int):
    """``[L, E]`` weights: the router's probability for a token's top-k
    experts, 0 for the others; not renormalised."""
    _, ids = jax.lax.top_k(probs, k)
    chosen = jnp.zeros_like(probs, dtype=bool).at[jnp.arange(probs.shape[0])[:, None], ids].set(True)
    return jnp.where(chosen, probs, 0.0)


def router_margin(probs, k: int):
    """By how much the k-th weight of a token leads its (k+1)-th: where this
    is within rounding, another precision may choose another expert."""
    top, _ = jax.lax.top_k(probs, k + 1)
    return top[:, k - 1] - top[:, k]


def experts(n2, weights, layer):
    """``sum_e weights[:, e] * down_e(silu(gate_e n2) * (up_e n2))``, every
    expert computed for every token, one expert at a time."""
    n_experts = weights.shape[1]

    def one(acc, e):
        with jax.default_matmul_precision(_HIGHEST):
            gate = n2 @ _f32(layer["gate"][e])
            up = n2 @ _f32(layer["up"][e])
            out = (jax.nn.silu(gate) * up) @ _f32(layer["down"][e])
        return acc + weights[:, e, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n2), jnp.arange(n_experts))
    return acc


def moe(n2, layer, config):
    probs = router_probs(n2, layer)
    return experts(n2, router_choice(probs, int(config["num_experts_per_tok"])), layer)


def attention_block(x, layer, config):
    """``h = x + attention(rms(x; w_in))``."""
    return x + attention(rms_norm(x, layer["w_in"], float(config["rms_norm_eps"])), layer, config)


def moe_block(h, layer, config):
    """``y = h + moe(rms(h; w_post))``."""
    return h + moe(rms_norm(h, layer["w_post"], float(config["rms_norm_eps"])), layer, config)


def layer_forward(x, layer, config):
    """One decoder layer over one session, ``x`` [L, hidden] float32."""
    return moe_block(attention_block(x, layer, config), layer, config)


def embed(weights, tokens):
    return _f32(weights["embed"])[jnp.asarray(tokens, jnp.int32)]


def head(weights, config, x):
    """``lm_head · rms(x; w_final)`` for hidden states ``x`` [..., hidden]."""
    out = rms_norm(x, weights["final_norm"], float(config["rms_norm_eps"]))
    with jax.default_matmul_precision(_HIGHEST):
        return out @ _f32(weights["lm_head"]).T


def forward(weights, config, tokens):
    """Logits of every position of one session: [L, vocabulary]."""
    x = embed(weights, tokens)
    for layer in weights["layers"]:
        x = layer_forward(x, layer, config)
    return head(weights, config, x)


def next_item_logits(weights, config, tokens):
    """What a query is scored by: the logits at the session's last
    position, [vocabulary]."""
    x = embed(weights, tokens)
    for layer in weights["layers"]:
        x = layer_forward(x, layer, config)
    return head(weights, config, x[-1])
