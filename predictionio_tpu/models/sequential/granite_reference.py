"""The plain reference of granite-4.0-h-small: what ``models/sequential``'s
``granite`` algorithm is held to.

The forward pass of ibm-granite/granite-4.0-h-small (``model_type``
``granitemoehybrid``) in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, one session at a time: the
state-space scan as its RECURRENCE, one position a step (no chunk, no
triangle), the convolution as four shifted products, attention under a full
causal mask with keys and values REPEATED per query head, the held experts
one at a time; no kernel, no packing, no cache, no batch, nothing imported
from ``ops/``. Layers are numbered from 0 as the published ``layer_types``
list numbers them, and a layer's kind is read from that list (its first
``num_hidden_layers``). With ``n = rms(h; w_in)`` and ``m = rms(h; w_post)``
the pre-norms of a layer's two halves (``rms_norm_eps``)::

    h_0 = embedding_multiplier * embed[tokens]                                   (12)
    h = h + residual_multiplier * mixer(n)                                       (0.22)
    h = h + residual_multiplier * ffn(m)
    mamba layer (layer_types[i] == "mamba"), 128 heads of 64 over a state of 128, ONE group:
      [z | xBC | dt] = W_in n                           8192 | 8448 | 128, no bias
      xBC = silu(conv4(xBC) + b_conv)                   depthwise, causal, 4 taps, zeros before position 0
      x [L, 128, 64], B [L, 128], C [L, 128]            every head reads the same B and C
      step_t = softplus(dt_t + dt_bias);  A = -exp(A_log)          a head each
      S_t = exp(step_t A) S_{t-1} + step_t x_t B_t^T               a head's state [64, 128], S_{-1} = 0
      y_t = S_t C_t + D x_t
      mixer = W_out rms(y * silu(z); w_gate_norm)        the norm AFTER the gate, over all 8192
    attention layer ("attention"), 32 query heads over 8 key/value heads of 128, NO positions:
      q = W_q n, k = W_k n, v = W_v n                    no bias, no rotary embedding, no norm a head
      mixer = W_o causal_softmax(q k^T * attention_multiplier) v     (1/128, not 128^-0.5);
                                                         query head j reads key/value head j // 4
    ffn(m) = sum_{e chosen and HELD} g_e W_down,e (silu(W_gate,e m) * W_up,e m)      width 768
             + W_out (silu(W_g m) * W_u m)                                           shared, width 1536
      logits_r = W_r m over ALL num_local_experts (72); chosen = top 10 by logit;
      g = softmax over the 10 chosen logits
    scores = embed rms(h_L; final_norm) / logits_scaling                         (16; the head is tied)

``experts_held`` ``[first, count]`` names the routed experts this chip holds
and ``vocab_slice`` ``[first, count]`` its rows of the vocabulary; the router
scores and chooses over all its experts, what the absent ones would add is
left out and the partial result goes on to the next layer. With all of them
held this is the uncut layer.

What ``config.json`` has no key for is the modeling code's (the benchmark's
configuration file lists each under ``assumed``): the router's softmax over
the CHOSEN logits (top-k gating), the gated norm after the gate and over all
the channels as one group, no limit on the step, ``head_dim`` 128.

Departures from the published code, none of which changes a value: a
projection is kept ``[in, out]`` and applied as ``x @ W``; the depthwise
``Conv1d`` (left-padded by 3 and cut to the length) is written as its four
taps, ``w[j]`` the kernel's ``[:, 0, j]``; the experts' fused ``input_linear``
is kept as its halves ``gate`` and ``up``, stacked over the experts, and
``output_linear`` as ``down`` (the shared expert's alike); the scan is the
recurrence itself and not the published chunked kernels (``mamba_chunk_size``
is their tile: it changes no value); one session, no batch axis, no cache.

Weights: a flat ``{name: array}`` with ``embed``, ``final_norm`` and layer
``i``'s arrays as ``"<i>.<name>"`` (``layer_of`` cuts one layer out); any
float type, upcast here. ``config`` holds the published ``config.json`` keys
and ``experts_held``.
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


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays (numbered from 0) under their own names."""
    prefix = f"{i}."
    return {name[len(prefix) :]: a for name, a in weights.items() if name.startswith(prefix)}


def is_mamba(config, i: int) -> bool:
    return config["layer_types"][i] == "mamba"


def short_conv(x, w, bias):
    """``silu(conv(x) + bias)``, the causal depthwise convolution: ``x``
    [L, D], ``w`` [taps, D], ``w[-1]`` on the position itself, zeros before
    position 0; a sum of ``taps`` shifted products."""
    taps, length = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(sum(_f32(w[j]) * padded[j : j + length] for j in range(taps)) + _f32(bias))


def ssd_inputs(n, layer, config):
    """What the scan takes, from the pre-normed stream ``n`` [L, hidden]:
    ``(x [L, heads, p], step [L, heads], A [heads], B [L, state], C [L,
    state], D [heads])`` and the gate ``z`` [L, heads * p] beside them."""
    heads, p, state = int(config["mamba_n_heads"]), int(config["mamba_d_head"]), int(config["mamba_d_state"])
    inner = heads * p
    with jax.default_matmul_precision(_HIGHEST):
        projected = n @ _f32(layer["in_proj"])
    z, xbc, dt = projected[:, :inner], projected[:, inner : 2 * inner + 2 * state], projected[:, 2 * inner + 2 * state :]
    xbc = short_conv(xbc, layer["conv"], layer["conv_bias"])
    x, b, c = xbc[:, :inner], xbc[:, inner : inner + state], xbc[:, inner + state :]
    step = jax.nn.softplus(dt + _f32(layer["dt_bias"]))
    return (x.reshape(-1, heads, p), step, -jnp.exp(_f32(layer["A_log"])), b, c, _f32(layer["D"])), z


def ssd_recurrence(x, step, a, b, c, d):
    """The state-space recurrence, one position a step: ``y`` [L, heads, p]."""

    def one(s, at):
        x_t, step_t, b_t, c_t = at
        s = jnp.exp(step_t * a)[:, None, None] * s + (step_t[:, None] * x_t)[:, :, None] * b_t
        return s, jnp.sum(s * c_t, axis=-1) + d[:, None] * x_t

    zero = jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32)
    return jax.lax.scan(one, zero, (x, step, b, c))[1]


def ssd_output(y, z, layer, config):
    """``W_out rms(y * silu(z); gate_norm)``: the norm after the gate, over
    all the channels."""
    gated = y.reshape(z.shape) * jax.nn.silu(z)
    with jax.default_matmul_precision(_HIGHEST):
        return rms_norm(gated, layer["gate_norm"], float(config["rms_norm_eps"])) @ _f32(layer["out_proj"])


def mamba_mixer(n, layer, config):
    """The Mamba-2 mixer of one session, ``n`` [L, hidden]."""
    inputs, z = ssd_inputs(n, layer, config)
    return ssd_output(ssd_recurrence(*inputs), z, layer, config)


def attention_mixer(n, layer, config):
    """Grouped-query attention of one session under a full causal mask, no
    positional encoding, scores times ``attention_multiplier``."""
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    d, scale = int(config["hidden_size"]) // heads, float(config["attention_multiplier"])
    length = n.shape[0]
    with jax.default_matmul_precision(_HIGHEST):
        q = (n @ _f32(layer["wq"])).reshape(length, heads, d)
        k = (n @ _f32(layer["wk"])).reshape(length, kv, d)
        v = (n @ _f32(layer["wv"])).reshape(length, kv, d)
        # every query head its own copy of its group's keys and values
        k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]

        def one(head):
            # one head at a time: all 32 heads' [L, L] scores of a session of
            # 4,096 items are 2.1 GB in float32, and as much again masked
            q_h, k_h, v_h = head
            scores = jnp.where(causal, (q_h @ k_h.T) * scale, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        out = jax.lax.map(one, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))
        return out.swapaxes(0, 1).reshape(length, heads * d) @ _f32(layer["wo"])


def gated_mlp(m, gate, up, down):
    with jax.default_matmul_precision(_HIGHEST):
        return (jax.nn.silu(m @ _f32(gate)) * (m @ _f32(up))) @ _f32(down)


def router_logits(m, layer):
    """The router's logits over ALL experts, float32: [L, E]."""
    with jax.default_matmul_precision(_HIGHEST):
        return m @ _f32(layer["router"])


def router_choice(logits, k: int):
    """``[L, E]`` weights: the top ``k`` logits are chosen and weigh the
    softmax over THEM; the others 0."""
    top, ids = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)
    return jnp.zeros_like(logits).at[jnp.arange(logits.shape[0])[:, None], ids].set(gates)


def router_margin(logits, k: int):
    """By how much the k-th logit leads the (k+1)-th: where this is within
    rounding, another precision may choose another expert."""
    top, _ = jax.lax.top_k(logits, k + 1)
    return top[:, k - 1] - top[:, k]


def experts(m, weights, layer, held):
    """``sum_e weights[:, e] * ffn_e(m)`` over the experts HELD, ``held``
    ``[first, count]``: ``layer``'s ``gate``, ``up`` and ``down`` hold those
    ``count``, one expert at a time."""
    first, count = int(held[0]), int(held[1])

    def one(acc, e):
        out = gated_mlp(m, layer["gate"][e], layer["up"][e], layer["down"][e])
        return acc + jax.lax.dynamic_index_in_dim(weights, first + e, axis=1) * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(count))
    return acc


def sparse_ffn(m, layer, config):
    """The held experts' part and the shared expert, before the residual's
    multiplier."""
    weights = router_choice(router_logits(m, layer), int(config["num_experts_per_tok"]))
    shared = gated_mlp(m, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    return experts(m, weights, layer, config["experts_held"]) + shared


def mixer_block(x, layer, config, i: int):
    """``h = x + residual_multiplier * mixer(rms(x; w_in))``, layer ``i``'s kind of mixer."""
    n = rms_norm(x, layer["w_in"], float(config["rms_norm_eps"]))
    mixer = (mamba_mixer if is_mamba(config, i) else attention_mixer)(n, layer, config)
    return x + float(config["residual_multiplier"]) * mixer


def ffn_block(h, layer, config):
    """``y = h + residual_multiplier * ffn(rms(h; w_post))``."""
    m = rms_norm(h, layer["w_post"], float(config["rms_norm_eps"]))
    return h + float(config["residual_multiplier"]) * sparse_ffn(m, layer, config)


def layer_forward(x, layer, config, i: int):
    """Decoder layer ``i`` over one session, ``x`` [L, hidden] float32."""
    return ffn_block(mixer_block(x, layer, config, i), layer, config)


def embed(weights, config, tokens):
    # the rows are taken before they are upcast: the whole table in float32 is 0.8 GB at the published widths
    return float(config["embedding_multiplier"]) * _f32(weights["embed"][jnp.asarray(tokens, jnp.int32)])


def head(weights, config, x):
    """``embed · rms(x; final_norm) / logits_scaling`` for hidden states ``x`` [..., hidden]."""
    out = rms_norm(x, weights["final_norm"], float(config["rms_norm_eps"]))
    with jax.default_matmul_precision(_HIGHEST):
        return (out @ _f32(weights["embed"]).T) / float(config["logits_scaling"])


def hidden_states(weights, config, tokens):
    x = embed(weights, config, tokens)
    for i in range(int(config["num_hidden_layers"])):
        x = layer_forward(x, layer_of(weights, i), config, i)
    return x


def forward(weights, config, tokens):
    """Logits of every position of one session: [L, vocabulary's slice]."""
    return head(weights, config, hidden_states(weights, config, tokens))


def next_item_logits(weights, config, tokens):
    """What a query is scored by: the logits at the session's last
    position, [vocabulary's slice]."""
    return head(weights, config, hidden_states(weights, config, tokens)[-1])
