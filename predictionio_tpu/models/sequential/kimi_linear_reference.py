"""The plain reference of the Kimi-Linear block: what ``models/sequential``'s
``kimi_linear`` algorithm is held to.

The forward pass of moonshotai/Kimi-Linear-48B-A3B-Instruct in ``jax.numpy``,
float32, under ``jax.default_matmul_precision("highest")``, one session at a
time: linear attention as the RECURRENCE, one position at a time; latent
attention expanded, under a full causal mask; the experts one at a time; no
kernel, no chunk, no cache, no batch, nothing imported from ``ops/``. Layers
are numbered from 1 as ``linear_attn_config`` numbers them. With ``n = rms(x;
w)`` the pre-norm of each half of a layer::

    x0 = embed[tokens]
    KDA layer (linear_attn_config.kda_layers), c(.) = silu(causal depthwise conv, 4 taps):
      q = c(Wq n), k = c(Wk n), v = c(Wv n), split into heads of 128
      q = l2(q) * 128^-0.5, k = l2(k)
      g = -exp(A_log) * softplus(W_fb (W_fa n) + dt_bias)   a log decay a channel
      b = sigmoid(W_b n)                                    a step size a head
      S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
      h = x + Wo [ rms_head(o; w_o_norm) * sigmoid(W_gb (W_ga n)) ]
    MLA layer (linear_attn_config.full_attn_layers), NoPE (mla_use_nope): no rotary embedding
      q = Wq n as heads of 128 + 64;  [c, k_r] = W_kva n, c of 512, k_r of 64 for all heads
      [k_n, v] = W_kvb rms(c; w_kv_norm) as heads of 128 + 128;  k = [k_n, k_r]
      h = x + Wo causal_softmax(q k^T * 192^-0.5) v
    dense feed-forward (layers up to first_k_dense_replace): y = h + down(silu(gate n) * up n)
    sparse feed-forward: s = sigmoid(W_r n) over ALL num_experts; chosen = top-k of s + bias;
      weights = s at the chosen / their sum * routed_scaling_factor
      y = h + sum_{e chosen and HELD} weight_e ffn_e(n) + shared(n)
    out = rms(x_L; w_final);  logits = lm_head out   (over the vocabulary's slice)

``experts_held`` ``[first, count]`` names the routed experts this chip holds;
the router scores and chooses over all of them, what the absent ones would
add is left out and the partial result goes on to the next layer. With all of
them held this is the uncut layer.

What ``config.json`` has no key for is the modeling code's (the benchmark's
configuration file lists each under ``assumed``): the low-rank gates' inner
width (the KDA head width), SiLU after the convolution, sigmoid in the
output gate, the L2 normalisation and the scale of ``q``, the shapes of
``A_log`` (a head) and ``dt_bias`` (a channel), the router's selection bias
(DeepSeek-V3's ``e_score_correction_bias``).

Departures from the published code, none of which changes a value: a
projection is kept ``[in, out]`` and applied as ``x @ W``; the experts'
weights are stacked; one session, no batch axis, no cache.

Weights: a flat ``{name: array}`` with ``embed``, ``final_norm``, ``lm_head``
and layer ``i``'s arrays as ``"<i>.<name>"`` (``layer_of`` cuts one layer
out); any float type, upcast here. ``config`` holds the published
``config.json`` keys, ``experts_held`` and ``vocab_slice``.
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


def l2_norm(x):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays (numbered from 1) under their own names."""
    prefix = f"{i}."
    return {name[len(prefix) :]: a for name, a in weights.items() if name.startswith(prefix)}


def is_kda(config, i: int) -> bool:
    return i in config["linear_attn_config"]["kda_layers"]


def is_dense(config, i: int) -> bool:
    return i <= int(config["first_k_dense_replace"])


def short_conv(x, w):
    """``silu`` of the causal depthwise convolution: ``x`` [L, D], ``w``
    [taps, D], ``w[-1]`` on the position itself, zeros before position 0."""
    taps, length = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    y = sum(_f32(w[j]) * padded[j : j + length] for j in range(taps))
    return jax.nn.silu(y)


def kda_recurrence(q, k, v, g, b):
    """The gated delta rule, one position at a time: ``q``, ``k``, ``g``
    [L, heads, d_k], ``v`` [L, heads, d_v], ``b`` [L, heads]; the state
    [heads, d_k, d_v] starts at zero. Returns ``o`` [L, heads, d_v]."""

    def one(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        with jax.default_matmul_precision(_HIGHEST):
            state = jnp.exp(g_t)[:, :, None] * state
            seen = jnp.einsum("hc,hcv->hv", k_t, state)
            state = state + jnp.einsum("hc,hv->hcv", k_t, b_t[:, None] * (v_t - seen))
            return state, jnp.einsum("hc,hcv->hv", q_t, state)

    zero = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), jnp.float32)
    _, o = jax.lax.scan(one, zero, (q, k, v, g, b))
    return o


def kda_inputs(n, layer, config):
    """What the recurrence is given for one session, ``n`` [L, hidden]:
    ``(q, k, v, g, b)``, ``q`` normalised and scaled, ``k`` normalised, ``g``
    the log decay a channel, ``b`` the step size a head."""
    spec = config["linear_attn_config"]
    heads, d = int(spec["num_heads"]), int(spec["head_dim"])
    length = n.shape[0]
    with jax.default_matmul_precision(_HIGHEST):
        q = short_conv(n @ _f32(layer["wq"]), layer["conv_q"]).reshape(length, heads, d)
        k = short_conv(n @ _f32(layer["wk"]), layer["conv_k"]).reshape(length, heads, d)
        v = short_conv(n @ _f32(layer["wv"]), layer["conv_v"]).reshape(length, heads, d)
        rate = (n @ _f32(layer["w_fa"])) @ _f32(layer["w_fb"]) + _f32(layer["dt_bias"])
        g = -jnp.exp(_f32(layer["A_log"]))[None, :, None] * jax.nn.softplus(rate).reshape(length, heads, d)
        return l2_norm(q) * d**-0.5, l2_norm(k), v, g, jax.nn.sigmoid(n @ _f32(layer["w_b"]))


def kda_output(o, n, layer, config):
    """The mixer's output from what the recurrence read, ``o`` [L, heads,
    d_v]: a norm a head, the output gate, the output projection."""
    length, heads, d = o.shape
    with jax.default_matmul_precision(_HIGHEST):
        gate = jax.nn.sigmoid((n @ _f32(layer["w_ga"])) @ _f32(layer["w_gb"])).reshape(length, heads, d)
        o = rms_norm(o, layer["o_norm"], float(config["rms_norm_eps"])) * gate
        return o.reshape(length, heads * d) @ _f32(layer["wo"])


def kda_mixer(n, layer, config):
    """The KDA token mixer of one session, ``n`` [L, hidden]."""
    return kda_output(kda_recurrence(*kda_inputs(n, layer, config)), n, layer, config)


def mla_mixer(n, layer, config):
    """Latent attention without rotary embedding, expanded, one session."""
    heads = int(config["num_attention_heads"])
    nope, rope, d_v = (int(config[key]) for key in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rank = int(config["kv_lora_rank"])
    length = n.shape[0]
    with jax.default_matmul_precision(_HIGHEST):
        q = (n @ _f32(layer["wq"])).reshape(length, heads, nope + rope)
        latent = n @ _f32(layer["w_kva"])
        c, k_r = latent[:, :rank], latent[:, rank:]
        expanded = rms_norm(c, layer["kv_norm"], float(config["rms_norm_eps"])) @ _f32(layer["w_kvb"])
        expanded = expanded.reshape(length, heads, nope + d_v)
        k_n, v = expanded[..., :nope], expanded[..., nope:]
        k = jnp.concatenate([k_n, jnp.broadcast_to(k_r[:, None, :], (length, heads, rope))], axis=-1)
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]

        def one(head):
            # one head at a time: all 32 heads' [L, L] scores of a session of
            # 4,096 items are 2.1 GB in float32, and as much again masked
            q_h, k_h, v_h = head
            scores = jnp.where(causal, (q_h @ k_h.T) * (nope + rope) ** -0.5, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        out = jax.lax.map(one, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))
        return out.swapaxes(0, 1).reshape(length, heads * d_v) @ _f32(layer["wo"])


def gated_mlp(n, gate, up, down):
    with jax.default_matmul_precision(_HIGHEST):
        return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def router_scores(n2, layer):
    """sigmoid of the router's logits over ALL experts, float32: [L, E]."""
    with jax.default_matmul_precision(_HIGHEST):
        return jax.nn.sigmoid(n2 @ _f32(layer["router"]))


def router_choice(scores, bias, k: int, scale: float):
    """``[L, E]`` weights: the top ``k`` of ``scores + bias`` are chosen;
    a chosen expert weighs ``scores`` (without the bias) over the chosen's
    sum, times ``scale``; the others 0."""
    _, ids = jax.lax.top_k(scores + _f32(bias), k)
    chosen = jnp.zeros_like(scores, dtype=bool).at[jnp.arange(scores.shape[0])[:, None], ids].set(True)
    kept = jnp.where(chosen, scores, 0.0)
    return scale * kept / jnp.sum(kept, axis=-1, keepdims=True)


def router_margin(scores, bias, k: int):
    """By how much the k-th of ``scores + bias`` leads the (k+1)-th: where
    this is within rounding, another precision may choose another expert."""
    top, _ = jax.lax.top_k(scores + _f32(bias), k + 1)
    return top[:, k - 1] - top[:, k]


def experts(n2, weights, layer, held):
    """``sum_e weights[:, e] * ffn_e(n2)`` over the experts HELD, ``held``
    ``[first, count]``: ``layer``'s ``gate``, ``up`` and ``down`` hold those
    ``count``, one expert at a time."""
    first, count = int(held[0]), int(held[1])

    def one(acc, e):
        out = gated_mlp(n2, layer["gate"][e], layer["up"][e], layer["down"][e])
        return acc + weights[:, first + e, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n2), jnp.arange(count))
    return acc


def sparse_ffn(n2, layer, config):
    scores = router_scores(n2, layer)
    weights = router_choice(
        scores, layer["router_bias"], int(config["num_experts_per_token"]),
        float(config["routed_scaling_factor"]),
    )
    shared = gated_mlp(n2, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    return experts(n2, weights, layer, config["experts_held"]) + shared


def mixer_block(x, layer, config, i: int):
    """``h = x + mixer(rms(x; w_in))``, layer ``i``'s kind of mixer."""
    n = rms_norm(x, layer["w_in"], float(config["rms_norm_eps"]))
    return x + (kda_mixer if is_kda(config, i) else mla_mixer)(n, layer, config)


def ffn_block(h, layer, config, i: int):
    """``y = h + ffn(rms(h; w_post))``, layer ``i``'s kind of feed-forward."""
    n2 = rms_norm(h, layer["w_post"], float(config["rms_norm_eps"]))
    if is_dense(config, i):
        return h + gated_mlp(n2, layer["dense_gate"], layer["dense_up"], layer["dense_down"])
    return h + sparse_ffn(n2, layer, config)


def layer_forward(x, layer, config, i: int):
    """Decoder layer ``i`` over one session, ``x`` [L, hidden] float32."""
    return ffn_block(mixer_block(x, layer, config, i), layer, config, i)


def embed(weights, tokens):
    return _f32(weights["embed"])[jnp.asarray(tokens, jnp.int32)]


def head(weights, config, x):
    """``lm_head · rms(x; w_final)`` for hidden states ``x`` [..., hidden]."""
    out = rms_norm(x, weights["final_norm"], float(config["rms_norm_eps"]))
    with jax.default_matmul_precision(_HIGHEST):
        return out @ _f32(weights["lm_head"]).T


def hidden_states(weights, config, tokens):
    x = embed(weights, tokens)
    for i in range(1, int(config["num_hidden_layers"]) + 1):
        x = layer_forward(x, layer_of(weights, i), config, i)
    return x


def forward(weights, config, tokens):
    """Logits of every position of one session: [L, vocabulary's slice]."""
    return head(weights, config, hidden_states(weights, config, tokens))


def next_item_logits(weights, config, tokens):
    """What a query is scored by: the logits at the session's last
    position, [vocabulary's slice]."""
    return head(weights, config, hidden_states(weights, config, tokens)[-1])
