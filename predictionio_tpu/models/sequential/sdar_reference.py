"""The plain reference of the SDAR backbone: what ``models/sequential``'s
``sdar`` algorithm is held to.

SDAR-30B-A3B-Chat (JetLM; ``model_type: sdar_moe``) is Qwen3-MoE's block
under a BLOCK-causal mask, and it answers by masked diffusion over blocks.
Here: its forward pass in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, one session at a time, every
expert computed densely and masked by the router's choice, no kernel, no
cache, no batching, nothing imported from ``ops/``; and the generation loop,
which recomputes the whole sequence at every step. Layer equations, for the
residual stream ``x`` [T, hidden] and positions ``p_t`` counted from the
session's start::

    x0 = embed[tokens]
    n1 = rms(x; w_in)
    q  = rope(rms_head(split(Wq n1); q_norm));  k = rope(rms_head(split(Wk n1); k_norm))
    h  = x + Wo concat_h softmax_f32(q_h k_{h // 8}^T / sqrt(128) + mask) split(Wv n1)_{h // 8}
    n2 = rms(h; w_post);  s = softmax_f32(W_r n2) over the 128 experts
    y  = h + sum_{e in top-8(s)} (s_e / sum_{top-8} s) D_e(silu(G_e n2) * (U_e n2))
    out = rms(x_L; w_final);  logits = lm_head out

``rms_head`` is an RMSNorm over each head's OWN 128 values with one learned
weight [128] for the queries and one for the keys (Qwen3's; OLMoE norms the
whole projection); 32 query heads read 4 key/value heads, head ``h`` reads
``h // 8``; RoPE rotate-half over all 128 values, theta 1e6; no bias
anywhere. ``mask``: key ``j`` is seen by query ``i`` where
``p_j // B <= p_i // B`` (``B`` the block length): two-way inside a block,
causal across blocks. The chosen experts' weights are divided by their sum
(``norm_topk_prob`` true). Logits at position ``i`` predict the token AT
position ``i`` (masked prediction in place, no shift). ``intermediate_size``
names a dense feed-forward no layer has (``mlp_only_layers`` []): not built.

Generation (``generate``) of ``num`` items after a session of ``L`` items,
``steps`` denoise steps a block, greedy: the sequence is cut into blocks of
``B`` from position 0. The blocks the session fills are clean context. The
block that holds its last ``r = L mod B`` items (if any) starts with those
fixed and ``B - r`` positions holding the mask id; each later block starts
all mask; the sequence ENDS with the answer's ``num``-th position, so the
last block may be short. For the current block, until none of its positions
is masked: one forward of the whole sequence so far (earlier blocks clean,
the current one as it stands); ``fix`` then fixes ``ceil(m / steps left)``
of its ``m`` masked positions. The answer is the ``num`` generated positions
in order, each with the log-probability at which it was fixed and the step
(0-based forward of its block) that fixed it: a reply alone lets this
module replay the trajectory (``state_at``).

Departures from the published code and sampler, each also a line of the
benchmark configuration's ``assumed``:

- a projection is kept ``[in, out]`` and applied as ``x @ W``; the experts'
  weights are stacked and every expert is computed for every token, then
  weighted by the router's choice; one session, no batch axis, no cache
  (none of these changes a value);
- the candidates: never the mask id, never an item of the session, never an
  item this answer already holds (the engine's "never what the session
  holds" rule; the published sampler draws from the whole vocabulary). The
  probability of a candidate is its softmax over the candidates allowed when
  the forward was made;
- the last block ends with the answer (the published sampler generates
  whole blocks and cuts the text afterwards): what a reply does not hold was
  never part of the sequence, so every state can be replayed from a reply;
- static low-confidence remasking, greedy (temperature 0): the most
  confident positions are fixed, ties to the lower position; positions fixed
  by ONE forward take their items in order of confidence, each its best
  candidate that a more confident one has not taken. The published
  confidence-threshold variant is not built (it never fires on seeded
  weights).

Weights: ``{"embed", "final_norm", "lm_head", "layers": [layer, ...]}``, a
layer ``{"w_in", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "w_post",
"router", "gate", "up", "down"}``; any float type, upcast here. ``config``
holds the published ``config.json`` keys and ``block_length``,
``denoising_steps``, ``mask_token_id``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

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


def keys_and_values(n1, layer, config):
    """``(k, v)`` [L, kv heads, d] of one session: the keys normed a head and
    turned by RoPE, as every later block reads them."""
    kv_heads, d = int(config["num_key_value_heads"]), int(config["head_dim"])
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    length = n1.shape[0]
    with jax.default_matmul_precision(_HIGHEST):
        k = (n1 @ _f32(layer["wk"])).reshape(length, kv_heads, d)
        v = (n1 @ _f32(layer["wv"])).reshape(length, kv_heads, d)
    return rope(rms_norm(k, layer["k_norm"], eps), theta), v


def attention(n1, layer, config, length=None):
    """Block-causal grouped-query self-attention of one session, ``n1``
    [L, hidden], one head at a time. ``length``, where given, says how many
    of the L positions are the sequence's: the rest is padding behind it,
    which no position sees (a short last block has padding INSIDE it)."""
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    d, block = int(config["head_dim"]), int(config["block_length"])
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    rows = n1.shape[0]
    k, v = keys_and_values(n1, layer, config)
    at = jnp.arange(rows) // block
    seen = at[:, None] >= at[None, :]
    if length is not None:
        seen = seen & (jnp.arange(rows) < length)[None, :]
    with jax.default_matmul_precision(_HIGHEST):
        q = (n1 @ _f32(layer["wq"])).reshape(rows, heads, d)
        q = rope(rms_norm(q, layer["q_norm"], eps), theta)

        def one(h):
            # query head h reads key/value head h // (heads / kv_heads)
            k_h, v_h = k[:, h // (heads // kv_heads)], v[:, h // (heads // kv_heads)]
            scores = jnp.where(seen, (q[:, h] @ k_h.T) / jnp.sqrt(jnp.float32(d)), -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        out = jnp.moveaxis(jax.lax.map(one, jnp.arange(heads)), 0, 1).reshape(rows, heads * d)
        return out @ _f32(layer["wo"])


def router_probs(n2, layer):
    """softmax over the experts of the router's logits, float32: [L, E]."""
    with jax.default_matmul_precision(_HIGHEST):
        return jax.nn.softmax(n2 @ _f32(layer["router"]), axis=-1)


def router_choice(probs, k: int):
    """``[L, E]`` weights: the router's probability for a token's top-k
    experts over their sum (renormalised), 0 for the others."""
    _, ids = jax.lax.top_k(probs, k)
    chosen = jnp.zeros_like(probs, dtype=bool).at[jnp.arange(probs.shape[0])[:, None], ids].set(True)
    kept = jnp.where(chosen, probs, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True)


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


def attention_block(x, layer, config, length=None):
    """``h = x + attention(rms(x; w_in))``."""
    n1 = rms_norm(x, layer["w_in"], float(config["rms_norm_eps"]))
    return x + attention(n1, layer, config, length)


def moe_block(h, layer, config):
    """``y = h + moe(rms(h; w_post))``."""
    return h + moe(rms_norm(h, layer["w_post"], float(config["rms_norm_eps"])), layer, config)


def layer_forward(x, layer, config, length=None):
    """One decoder layer over one session, ``x`` [L, hidden] float32."""
    return moe_block(attention_block(x, layer, config, length), layer, config)


def embed(weights, tokens):
    return _f32(jnp.asarray(weights["embed"])[jnp.asarray(tokens, jnp.int32)])


def head(weights, config, x):
    """``lm_head · rms(x; w_final)`` for hidden states ``x`` [..., hidden]."""
    out = rms_norm(x, weights["final_norm"], float(config["rms_norm_eps"]))
    with jax.default_matmul_precision(_HIGHEST):
        return out @ _f32(weights["lm_head"]).T


def forward(weights, config, tokens):
    """Logits of every position of one sequence (the session, then the
    generated blocks as they stand): [L, vocabulary]."""
    x = embed(weights, tokens)
    for layer in weights["layers"]:
        x = layer_forward(x, layer, config)
    return head(weights, config, x)


def blocks_of(length: int, num: int, block: int) -> tuple[int, int, int]:
    """``(r, first, blocks)`` for ``num`` items after a session of
    ``length``: the items its last, partial block holds, the position that
    block starts at, and how many blocks are generated (that one first)."""
    r = length % block
    return r, length - r, -(-(num + r) // block)


def block_span(length: int, num: int, block: int, index: int) -> tuple[int, int]:
    """Positions ``[low, high)`` of generated block ``index``: the last one
    ends with the answer."""
    first = length - length % block
    return first + index * block, min(first + (index + 1) * block, length + num)


def log_probabilities(logits, allowed):
    """``log softmax`` of ``logits`` [B, V] over the candidates ``allowed``
    [V] leaves, float32; -inf at the others."""
    z = np.where(allowed[None, :], np.asarray(logits, np.float32), -np.inf)
    top = z.max(axis=1, keepdims=True)
    return z - (top + np.log(np.exp(z - top).sum(axis=1, keepdims=True)))


def fix(logits, masked, allowed, steps_left: int):
    """One forward's fixing: ``[(place in the block, item, log-probability),
    ...]`` in the order they are fixed. ``logits`` [B, V] of the block's
    positions, ``masked`` [B] those still masked, ``allowed`` [V] the
    candidates. ``ceil(m / steps_left)`` of the ``m`` masked positions are
    fixed: the most confident by their best candidate's probability over the
    allowed candidates (ties: the lower position), each in that order taking
    its best candidate that none before it took in this forward."""
    logp = log_probabilities(logits, allowed)
    places = np.flatnonzero(masked)
    count = -(-len(places) // max(steps_left, 1))
    confident = sorted(places.tolist(), key=lambda p: (-float(logp[p].max()), p))[:count]
    taken, fixed = [], []
    for place in confident:
        row = logp[place].copy()
        row[taken] = -np.inf
        item = int(np.argmax(row))  # (the lowest id of equals)
        taken.append(item)
        fixed.append((place, item, float(row[item])))
    return fixed


def candidates(config, session, n_items: int):
    """[V] bool: what an answer to ``session`` may hold at its start: an
    item (ids under ``n_items``), not the mask id, not one of the session."""
    allowed = np.zeros(int(config["vocab_size"]), bool)
    allowed[:n_items] = True
    allowed[int(config["mask_token_id"])] = False
    allowed[np.asarray(session, np.int64)] = False
    return allowed


def generate(weights, config, session, num: int, n_items: int):
    """``[(item, log-probability, step), ...]``: ``num`` items after
    ``session``, by the plain loop of the module's docstring: every step a
    forward of the whole sequence so far."""
    block, steps = int(config["block_length"]), int(config["denoising_steps"])
    mask_id = int(config["mask_token_id"])
    blocks = blocks_of(len(session), num, block)[2]
    tokens = np.concatenate([np.asarray(session, np.int64), np.full(num, mask_id)])
    allowed = candidates(config, session, n_items)
    record = {}
    for b in range(blocks):
        low, high = block_span(len(session), num, block, b)
        step = 0
        while (masked := tokens[low:high] == mask_id).any():
            logits = np.asarray(forward(weights, config, tokens[:high]))[low:high]
            for place, item, logp in fix(logits, masked, allowed, steps - step):
                tokens[low + place] = item
                allowed[item] = False
                record[low + place] = (item, logp, step)
            step += 1
    return [record[p] for p in range(len(session), len(session) + num)]


def state_at(config, session, items, steps, block_index: int, step: int):
    """The sequence as it stood when forward ``step`` of generated block
    ``block_index`` was made, replayed from a reply's own trajectory
    (``items`` and the ``steps`` that fixed them, in the answer's order):
    ``(tokens up to that block's end, the block's first position, masked
    (of the block's positions), the items the answer held by then)``."""
    block, mask_id = int(config["block_length"]), int(config["mask_token_id"])
    first = len(session) - len(session) % block
    low, high = block_span(len(session), len(items), block, block_index)
    tokens = np.concatenate([np.asarray(session, np.int64), np.full(high - len(session), mask_id)])
    held = []
    for at, (item, fixed_at) in enumerate(zip(items, steps)):
        position = len(session) + at
        of_block = (position - first) // block
        if of_block < block_index or (of_block == block_index and fixed_at < step):
            tokens[position] = item
            held.append(item)
    return tokens, low, tokens[low:high] == mask_id, held
