"""The benchmark's own copy of kanana-2-30b-a3b's plain reference: what
``benchmark/engines/sequential_kanana.py`` holds the served answers to.

Function for function ``predictionio_tpu/models/sequential/
kanana_reference.py`` (a test holds the two equal), kept here so that the
yardstick imports nothing from the program it measures: ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``, one sequence at a time;
latent attention in its EXPANDED form only, under a full causal mask, one head
at a time, interleaved RoPE on the 64 rotary dimensions (the key's part turned
once for all heads); a sigmoid router over all the experts (chosen by score
plus bias by a plain sort, weighed by score over the chosen's sum times the
scaling factor), the experts one at a time, two shared experts as one product;
no kernel, no cache, no batch. A generation is the plain loop: every item a
forward of the whole sequence so far. The layer equations and the departures
from DeepSeek-V3's modeling code (none changes a value) are written out in the
program's copy.

Weights: a flat ``{name: array}`` with ``embed``, ``final_norm``, ``lm_head``
and layer ``i``'s arrays as ``"<i>.<name>"`` (layers numbered from 0);
``config`` holds the published ``config.json`` keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = "highest"
# what the published router adds to the chosen scores' sum before it divides
ROUTER_EPS = 1e-20


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


def is_dense(config, i: int) -> bool:
    return i < int(config["first_k_dense_replace"])


def deinterleave(x):
    """The last axis's even dimensions, then its odd ones (the published
    ``view(d // 2, 2).transpose``)."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta: float, position=None):
    """``x`` [L, ..., d] turned at ``position`` [L] (0 to L - 1 by default),
    ``rope_interleave``: dimensions ``2j`` and ``2j + 1`` turn together by
    ``position * theta ** (-2j / d)``; the result lies de-interleaved."""
    d = x.shape[-1]
    position = jnp.arange(x.shape[0]) if position is None else position
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = _f32(position)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1).reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    x = deinterleave(_f32(x))
    return x * jnp.cos(angles) + rotate_half(x) * jnp.sin(angles)


def latent(n, layer, config, position=None):
    """What latent attention keeps of a token: ``(c [L, 512], k_r [L, 64])``,
    the NORMALISED latent and the TURNED rotary key, of normed rows ``n``."""
    rank = int(config["kv_lora_rank"])
    with jax.default_matmul_precision(_HIGHEST):
        both = n @ _f32(layer["w_kva"])
    c = rms_norm(both[:, :rank], layer["kv_norm"], float(config["rms_norm_eps"]))
    return c, rope(both[:, rank:], float(config["rope_theta"]), position)


def mla_mixer(n, layer, config):
    """Latent attention expanded, one sequence: ``n`` [L, hidden]."""
    heads = int(config["num_attention_heads"])
    nope, rot, d_v = (int(config[key]) for key in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    length = n.shape[0]
    c, k_r = latent(n, layer, config)
    with jax.default_matmul_precision(_HIGHEST):
        q = (n @ _f32(layer["wq"])).reshape(length, heads, nope + rot)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], float(config["rope_theta"]))], axis=-1)
        expanded = (c @ _f32(layer["w_kvb"])).reshape(length, heads, nope + d_v)
        k = jnp.concatenate(
            [expanded[..., :nope], jnp.broadcast_to(k_r[:, None, :], (length, heads, rot))], axis=-1
        )
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]

        def one(head):
            # one head at a time: 32 heads' [L, L] scores of 4,127 positions are 2.2 GB
            q_h, k_h, v_h = head
            scores = jnp.where(causal, (q_h @ k_h.T) * (nope + rot) ** -0.5, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        out = jax.lax.map(one, (q.swapaxes(0, 1), k.swapaxes(0, 1), expanded[..., nope:].swapaxes(0, 1)))
        return out.swapaxes(0, 1).reshape(length, heads * d_v) @ _f32(layer["wo"])


def gated_mlp(n, gate, up, down):
    with jax.default_matmul_precision(_HIGHEST):
        return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def router_scores(n2, layer):
    """sigmoid of the router's logits over ALL experts, float32: [L, E]."""
    with jax.default_matmul_precision(_HIGHEST):
        return jax.nn.sigmoid(n2 @ _f32(layer["router"]))


def router_choice(scores, bias, k: int, scale: float):
    """``[L, E]`` weights by a plain sort: the ``k`` largest of ``scores +
    bias`` are chosen (ties: the lower id); a chosen expert weighs ``scores``
    (without the bias) over the chosen's sum plus ``ROUTER_EPS``, times
    ``scale``; the others 0."""
    order = jnp.argsort(-(scores + _f32(bias)), axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros_like(scores, dtype=bool).at[jnp.arange(scores.shape[0])[:, None], order].set(True)
    kept = jnp.where(chosen, scores, 0.0)
    return scale * kept / (jnp.sum(kept, axis=-1, keepdims=True) + ROUTER_EPS)


def router_margin(scores, bias, k: int):
    """By how much the k-th of ``scores + bias`` leads the (k+1)-th: where
    this is within rounding, another precision may choose another expert."""
    ranked = -jnp.sort(-(scores + _f32(bias)), axis=-1)
    return ranked[:, k - 1] - ranked[:, k]


def experts(n2, weights, layer):
    """``sum_e weights[:, e] * ffn_e(n2)``, one expert at a time."""

    def one(acc, e):
        out = gated_mlp(n2, layer["gate"][e], layer["up"][e], layer["down"][e])
        return acc + weights[:, e, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n2), jnp.arange(layer["gate"].shape[0]))
    return acc


def sparse_ffn(n2, layer, config):
    weights = router_choice(
        router_scores(n2, layer), layer["router_bias"], int(config["num_experts_per_tok"]),
        float(config["routed_scaling_factor"]),
    )
    shared = gated_mlp(n2, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    return experts(n2, weights, layer) + shared


def mixer_block(x, layer, config):
    """``h = x + attention(rms(x; w_in))``."""
    return x + mla_mixer(rms_norm(x, layer["w_in"], float(config["rms_norm_eps"])), layer, config)


def ffn_block(h, layer, config, i: int):
    """``y = h + ffn(rms(h; w_post))``, layer ``i``'s kind of feed-forward."""
    n2 = rms_norm(h, layer["w_post"], float(config["rms_norm_eps"]))
    if is_dense(config, i):
        return h + gated_mlp(n2, layer["dense_gate"], layer["dense_up"], layer["dense_down"])
    return h + sparse_ffn(n2, layer, config)


def layer_forward(x, layer, config, i: int):
    """Decoder layer ``i`` over one sequence, ``x`` [L, hidden] float32."""
    return ffn_block(mixer_block(x, layer, config), layer, config, i)


def embed(weights, tokens):
    return _f32(weights["embed"])[jnp.asarray(tokens, jnp.int32)]


def head(weights, config, x):
    """``lm_head · rms(x; w_final)`` for hidden states ``x`` [..., hidden]."""
    out = rms_norm(x, weights["final_norm"], float(config["rms_norm_eps"]))
    with jax.default_matmul_precision(_HIGHEST):
        return out @ _f32(weights["lm_head"]).T


def hidden_states(weights, config, tokens):
    x = embed(weights, tokens)
    for i in range(int(config["num_hidden_layers"])):
        x = layer_forward(x, layer_of(weights, i), config, i)
    return x


def forward(weights, config, tokens):
    """Logits of every position of one sequence: [L, vocabulary]. The logits
    at position ``i`` score the token AT ``i + 1``."""
    return head(weights, config, hidden_states(weights, config, tokens))


def log_probabilities(logits, allowed):
    """``log softmax`` of ``logits`` [V] over the candidates ``allowed`` [V]
    leaves, float32; -inf at the others."""
    z = np.where(allowed, np.asarray(logits, np.float32), -np.inf)
    top = z.max()
    return z - (top + np.log(np.exp(z - top).sum()))


def candidates(config, session, n_items: int):
    """[V] bool: what an answer to ``session`` may hold at its start: an
    item (ids under ``n_items``) that the session does not hold."""
    allowed = np.zeros(int(config["vocab_size"]), bool)
    allowed[:n_items] = True
    allowed[np.asarray(session, np.int64)] = False
    return allowed


def generate(weights, config, session, num: int, n_items: int):
    """``[(item, log-probability), ...]``: ``num`` items after ``session``,
    greedy at temperature 0, each the likeliest allowed candidate given the
    session and the items before it (ties: the lower id), by the plain loop:
    every item a forward of the whole sequence so far. An item chosen is no
    candidate again."""
    tokens = list(np.asarray(session, np.int64))
    allowed = candidates(config, session, n_items)
    out = []
    for _ in range(num):
        logp = log_probabilities(np.asarray(forward(weights, config, np.asarray(tokens)))[-1], allowed)
        item = int(np.argmax(logp))
        out.append((item, float(logp[item])))
        allowed[item] = False
        tokens.append(item)
    return out
