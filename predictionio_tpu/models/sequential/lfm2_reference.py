"""The plain reference of LFM2-8B-A1B: what ``models/sequential``'s ``lfm2``
algorithm is held to.

The forward pass of LiquidAI/LFM2-8B-A1B (``model_type`` ``lfm2_moe``) in
``jax.numpy``, float32, under ``jax.default_matmul_precision("highest")``,
one session at a time: the short convolution as three shifted products,
attention under a full causal mask with keys and values REPEATED per query
head, the held experts one at a time; no kernel, no packing, no cache, no
batch, nothing imported from ``ops/``. Layers are numbered from 0 as the
published ``layer_types`` list numbers them, and a layer's kind is read from
that list. With ``n = rms(x; operator_norm)`` and ``m = rms(h; ffn_norm)``
the pre-norms of a layer's two halves (``norm_eps``)::

    x0 = embed[tokens]
    conv layer (layer_types[i] == "conv"):
      [B, C, u] = W_in n                       thirds of one projection, no bias
      y_t = C_t * sum_{j < 3} w[j] * (B u)_{t-2+j}     depthwise, causal, zeros before position 0,
                                                       no bias, NO activation
      h = x + W_out y
    attention layer ("full_attention"), 32 query heads over 8 key/value heads of 64:
      q = W_q n, k = W_k n, v = W_v n
      q, k = rope(rms_head(q; q_layernorm)), rope(rms_head(k; k_layernorm))   theta 1e6, rotate-half
      h = x + W_o causal_softmax(q k^T * 64^-0.5) v          query head j reads key/value head j // 4
    dense feed-forward (layers 0 .. num_dense_layers - 1): y = h + W2 (silu(W1 m) * W3 m)
    sparse feed-forward: s = sigmoid(W_r m) over ALL num_experts; chosen = top 4 of s + expert_bias;
      weights = s at the chosen / (their sum + 1e-6) * routed_scaling_factor
      y = h + sum_{e chosen and HELD} weight_e ffn_e(m)
    out = rms(x_L; embedding_norm);  logits = embed out          (the head is tied to the embedding)

``experts_held`` ``[first, count]`` names the routed experts this chip holds;
the router scores and chooses over all of them, what the absent ones would
add is left out and the partial result goes on to the next layer. With all of
them held this is the uncut layer.

What ``config.json`` has no key for is the modeling code's (the benchmark's
configuration file lists each under ``assumed``): the tied head, the
``embedding_norm`` before it, the norms over a head's 64 and the rotate-half
convention, ``expert_bias`` steering the choice only, the ``1e-6``.

Departures from the published code, none of which changes a value: a
projection is kept ``[in, out]`` and applied as ``x @ W``; the depthwise
``Conv1d`` (left-padded by 2 and cut to the length) is written as its three
taps, ``w[j]`` the kernel's ``[:, 0, j]``; the experts' weights are stacked
(``gate``, ``up``, ``down`` are their ``w1``, ``w3``, ``w2``); one session, no
batch axis, no cache.

Weights: a flat ``{name: array}`` with ``embed``, ``embedding_norm`` and layer
``i``'s arrays as ``"<i>.<name>"`` (``layer_of`` cuts one layer out); any
float type, upcast here. ``config`` holds the published ``config.json`` keys
and ``experts_held``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = "highest"
ROUTER_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, weight, eps: float):
    x = _f32(x)
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return _f32(weight) * (x * jax.lax.rsqrt(variance + eps))


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays (numbered from 0) under their own names."""
    prefix = f"{i}."
    return {name[len(prefix) :]: a for name, a in weights.items() if name.startswith(prefix)}


def is_conv(config, i: int) -> bool:
    return config["layer_types"][i] == "conv"


def is_dense(config, i: int) -> bool:
    return i < int(config["num_dense_layers"])


def short_conv(x, w):
    """The causal depthwise convolution, no bias, no activation: ``x`` [L, D],
    ``w`` [taps, D], ``w[-1]`` on the position itself, zeros before position
    0; a sum of ``taps`` shifted products."""
    taps, length = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(_f32(w[j]) * padded[j : j + length] for j in range(taps))


def conv_mixer(n, layer, config):
    """The gated short convolution of one session, ``n`` [L, hidden]."""
    with jax.default_matmul_precision(_HIGHEST):
        b, c, u = jnp.split(n @ _f32(layer["in_proj"]), 3, axis=-1)
        return (c * short_conv(b * u, layer["conv"])) @ _f32(layer["out_proj"])


def rope(x, theta: float):
    """``x`` [L, heads, d] at positions 0 .. L-1, rotate-half over all ``d``."""
    length, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def attention_mixer(n, layer, config):
    """Grouped-query attention of one session under a full causal mask."""
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    d = int(config["hidden_size"]) // heads
    eps, theta = float(config["norm_eps"]), float(config["rope_theta"])
    length = n.shape[0]
    with jax.default_matmul_precision(_HIGHEST):
        q = rms_norm((n @ _f32(layer["q_proj"])).reshape(length, heads, d), layer["q_layernorm"], eps)
        k = rms_norm((n @ _f32(layer["k_proj"])).reshape(length, kv, d), layer["k_layernorm"], eps)
        v = (n @ _f32(layer["v_proj"])).reshape(length, kv, d)
        q, k = rope(q, theta), rope(k, theta)
        # every query head its own copy of its group's keys and values
        k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]

        def one(head):
            # one head at a time: all 32 heads' [L, L] scores of a session of
            # 4,096 items are 2.1 GB in float32, and as much again masked
            q_h, k_h, v_h = head
            scores = jnp.where(causal, (q_h @ k_h.T) * d**-0.5, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        out = jax.lax.map(one, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))
        return out.swapaxes(0, 1).reshape(length, heads * d) @ _f32(layer["out_proj"])


def gated_mlp(m, gate, up, down):
    with jax.default_matmul_precision(_HIGHEST):
        return (jax.nn.silu(m @ _f32(gate)) * (m @ _f32(up))) @ _f32(down)


def router_scores(m, layer):
    """sigmoid of the router's logits over ALL experts, float32: [L, E]."""
    with jax.default_matmul_precision(_HIGHEST):
        return jax.nn.sigmoid(m @ _f32(layer["router"]))


def router_choice(scores, bias, k: int, scale: float):
    """``[L, E]`` weights: the top ``k`` of ``scores + bias`` are chosen; a
    chosen expert weighs ``scores`` (without the bias) over the chosen's sum
    plus ``ROUTER_EPS``, times ``scale``; the others 0."""
    _, ids = jax.lax.top_k(scores + _f32(bias), k)
    chosen = jnp.zeros_like(scores, dtype=bool).at[jnp.arange(scores.shape[0])[:, None], ids].set(True)
    kept = jnp.where(chosen, scores, 0.0)
    return scale * kept / (jnp.sum(kept, axis=-1, keepdims=True) + ROUTER_EPS)


def router_margin(scores, bias, k: int):
    """By how much the k-th of ``scores + bias`` leads the (k+1)-th: where
    this is within rounding, another precision may choose another expert."""
    top, _ = jax.lax.top_k(scores + _f32(bias), k + 1)
    return top[:, k - 1] - top[:, k]


def experts(m, weights, layer, held):
    """``sum_e weights[:, e] * ffn_e(m)`` over the experts HELD, ``held``
    ``[first, count]``: ``layer``'s ``gate``, ``up`` and ``down`` hold those
    ``count``, one expert at a time."""
    first, count = int(held[0]), int(held[1])

    def one(acc, e):
        out = gated_mlp(m, layer["gate"][e], layer["up"][e], layer["down"][e])
        return acc + weights[:, first + e, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(count))
    return acc


def sparse_ffn(m, layer, config):
    weights = router_choice(
        router_scores(m, layer), layer["expert_bias"], int(config["num_experts_per_tok"]),
        float(config["routed_scaling_factor"]),
    )
    return experts(m, weights, layer, config["experts_held"])


def mixer_block(x, layer, config, i: int):
    """``h = x + mixer(rms(x; operator_norm))``, layer ``i``'s kind of mixer."""
    n = rms_norm(x, layer["operator_norm"], float(config["norm_eps"]))
    return x + (conv_mixer if is_conv(config, i) else attention_mixer)(n, layer, config)


def ffn_block(h, layer, config, i: int):
    """``y = h + ffn(rms(h; ffn_norm))``, layer ``i``'s kind of feed-forward."""
    m = rms_norm(h, layer["ffn_norm"], float(config["norm_eps"]))
    if is_dense(config, i):
        return h + gated_mlp(m, layer["w1"], layer["w3"], layer["w2"])
    return h + sparse_ffn(m, layer, config)


def layer_forward(x, layer, config, i: int):
    """Decoder layer ``i`` over one session, ``x`` [L, hidden] float32."""
    return ffn_block(mixer_block(x, layer, config, i), layer, config, i)


def embed(weights, tokens):
    return _f32(weights["embed"])[jnp.asarray(tokens, jnp.int32)]


def head(weights, config, x):
    """``embed · rms(x; embedding_norm)`` for hidden states ``x`` [..., hidden]."""
    out = rms_norm(x, weights["embedding_norm"], float(config["norm_eps"]))
    with jax.default_matmul_precision(_HIGHEST):
        return out @ _f32(weights["embed"]).T


def hidden_states(weights, config, tokens):
    x = embed(weights, tokens)
    for i in range(int(config["num_hidden_layers"])):
        x = layer_forward(x, layer_of(weights, i), config, i)
    return x


def forward(weights, config, tokens):
    """Logits of every position of one session: [L, vocabulary]."""
    return head(weights, config, hidden_states(weights, config, tokens))


def next_item_logits(weights, config, tokens):
    """What a query is scored by: the logits at the session's last
    position, [vocabulary]."""
    return head(weights, config, hidden_states(weights, config, tokens)[-1])
