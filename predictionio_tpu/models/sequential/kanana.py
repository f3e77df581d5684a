"""kanana-2-30b-a3b (``model_type: deepseek_v3``) as a session continuer: the
device side of the sequential engine's ``kanana`` algorithm
(``backbone.KananaAlgorithm``).

A session's items are the tokens, as in ``olmoe.py``; the ANSWER is a
generation, as ``sdar.py``'s is, but TOKEN BY TOKEN: ``num`` items in order,
each the likeliest allowed candidate given the session and the items before
it (greedy, temperature 0), one position a step against a cache
(``kanana_reference.py`` has the layer equations and the plain loop, and the
tests and the benchmark hold this to it). The block is DeepSeek-V3's: latent
attention (32 heads of 128 + 64 | 128 over a latent of 512, interleaved RoPE on
the 64, the key's rotary part shared by all heads), a dense feed-forward in
layer 0, then 128 sigmoid-routed experts of width 768 with 6 a token (chosen
by score plus bias, renormalised, times 2.448) beside two shared experts as
one product 1,536 wide.

Latent attention has two forms, which are the same function (a test holds
them together), and a batch runs BOTH over one weight tree:

1. PREFILL, ``session_vectors``: one packed token stream a program, as the
   other backbones' (``fused_attention(segment=)``), in the EXPANDED form: a
   layer makes every token's normalised latent ``c`` (512) and turned rotary
   key ``k_r`` (64), expands ``c`` through ``W_kvb`` to every head's keys and
   values (192 / 128) and attends causally inside a session. It writes ``[c |
   k_r]``, 576 values a token and layer in bfloat16, into the batch's CACHE
   at the stream's offset, as they lie; the last layer makes queries, the
   feed-forward and the head's input for each session's LAST position only.
2. The CACHE, ``new_state``: a layer's ``[slots, 576]``, the slots being
   ``config.cache_tokens`` for the streams, end to end, then
   ``generated_slots`` a session for the positions the steps add, session by
   session (one tile of the attention kernel's keys at the shipped sizes).
   Its capacity is fixed, so a step is ONE compiled shape; a batch whose
   streams do not fit is answered in more than one group (``backbone.GroupedAlgorithm``).
3. The first item, ``first_pick``: ``lm_head`` over the prefills' vectors and
   the choice (``ops/topk.select_top_k`` beside a log-sum-exp, under the
   session's mask: never one of its own items nor one already chosen).
4. STEPS, ``decode_step``: all of the group's sessions in one program, each
   session's newest item as ONE row. A layer writes the row's 576 values into
   the session's next slot, then attends in the ABSORBED form: ``W_uk`` (the
   keys' half of ``W_kvb``) is multiplied into the query, so that a head's
   query is 512 + 64 wide and meets the cache as it lies; the 32 heads of a
   session are the ROWS of one head (``fused_attention(segment=(row ->
   session, slot -> session), value_width=512)``: one key/value head whose
   values are the keys' own first 512 columns, read once for all heads, the
   tiles of other sessions neither read nor multiplied); ``W_uv`` is applied
   to the sum. Then the feed-forward (about 1.5 rows an expert), ``lm_head``
   and the choice, appended on the device. A session whose ``num`` is reached
   rides along masked. Nothing is fetched between steps.

Scopes: ``embed``, ``mla`` (with ``rope``, ``expand``), ``dense``, ``router``,
``experts`` (``sort``, ``gmm``, ``combine``), ``shared``, ``cache``, ``head``
in the prefill; ``embed``, ``cache``, ``mla_absorbed`` (with ``absorb``,
``rope``, ``attn``, ``unabsorb``), ``dense``, ``router``, ``experts``,
``shared``, ``head``, ``pick`` in a step. Layers are unrolled, each with its
own arrays (a flat tree, layer ``i``'s as ``"<i>.<name>"``, numbered from 0 as
``first_k_dense_replace`` counts them). The weights are drawn from a seed,
not fitted (ROADMAP R7).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu.models.sequential.olmoe import (
    LENGTH_BUCKETS, SESSION_ALIGN, TOKEN_BUDGET, _at_last, _normal, _project, _rms, _rope, stream_shapes,
)
from predictionio_tpu.models.sequential.records import BackboneParams
from predictionio_tpu.ops import moe, topk
from predictionio_tpu.ops.attention import fused_attention

__all__ = [
    "KananaConfig", "KananaAlgorithmParams", "SESSIONS", "MAX_SESSION", "SESSION_ALIGN", "TOKEN_BUDGET", "weight_shapes",
    "init_weights", "layer_of", "session_vectors", "new_state", "first_pick", "decode_step", "answer_of",
]

# items of a session the engine keeps, and so the longest stream: the
# traffic's bound (the model's own is ``max_position_embeddings``, 32,768)
MAX_SESSION = 4096
# sessions a group of steps holds: a stream's most, a short group is padded.
# A step is bound by the weights it reads, so more rows a step are nearly
# free; 64 would need batches of 64 from the server's two slots of 32
SESSIONS = TOKEN_BUDGET // SESSION_ALIGN
# streams a prefill takes (``olmoe.STACKED_ROWS``): one, written into the
# group's cache where the stream lies
STACKED_ROWS = 1
# places a session's generated items have in the state, and the slots of the
# cache behind the streams that hold their positions (the last item chosen is
# never embedded, so an answer of 32 uses 31 of them)
GENERATED_SLOTS = 32
# slots of the cache that hold streams: fifteen and a half streams of the
# budget (a batch of 32 sessions packs into about seven); with the generated
# positions 32,768 slots in all, 32 of the attention kernel's tiles of 1,024
# keys, 0.23 GB at the published widths. A slot is 576 values wide as the
# model makes them, 4.5 lane tiles: the kernels take it whole (the tile of
# keys is the array's full width), neither padded to 640 nor split 512 + 64
CACHE_TOKENS = 16 * TOKEN_BUDGET - SESSIONS * GENERATED_SLOTS
# what the published router adds to the chosen scores' sum before it divides
ROUTER_EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class KananaConfig:
    """The keys of the published ``config.json`` that shape the program, and
    what it has no key for: the cache's sizes."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    first_k_dense_replace: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    vocab_size: int
    max_position_embeddings: int
    cache_tokens: int = CACHE_TOKENS
    generated_slots: int = GENERATED_SLOTS

    # what the engine asks of any backbone's configuration
    @property
    def table_rows(self) -> int:
        return self.vocab_size

    @property
    def max_session(self) -> int:
        return min(MAX_SESSION, self.max_position_embeddings - self.generated_slots)

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace

    @property
    def sparse_layers(self) -> int:
        return sum(not self.is_dense(i) for i in range(self.num_hidden_layers))

    def routed_copies(self, real_tokens: int) -> int:
        return self.sparse_layers * real_tokens * self.num_experts_per_tok

    def even_expert_load(self, real_tokens: float) -> float:
        return self.sparse_layers * real_tokens * self.num_experts_per_tok / self.n_routed_experts

    def buckets(self) -> tuple[int, ...]:
        top = self.max_session
        return tuple(b for b in LENGTH_BUCKETS if b < top) + (top,)

    def stream_shapes(self) -> tuple[int, ...]:
        return stream_shapes(TOKEN_BUDGET, self.max_session)

    # the generation
    @property
    def latent_width(self) -> int:
        """Values a token leaves a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_slots(self) -> int:
        return self.cache_tokens + SESSIONS * self.generated_slots

    def fit(self, num: int) -> int:
        """``num`` cut to the places an answer has in the state."""
        return max(0, min(num, self.generated_slots))

    def cache_bytes(self, slots: int) -> int:
        """Bytes ``slots`` token slots hold over the layers (bfloat16)."""
        return slots * self.num_hidden_layers * self.latent_width * 2


Config = KananaConfig


@dataclasses.dataclass(frozen=True)
class KananaAlgorithmParams(BackboneParams):
    """The published ``config.json`` of kakaocorp/kanana-2-30b-a3b-instruct-2601
    (``model_type: deepseek_v3``). ``head_dim`` (64, the rotary width) and
    ``qk_head_dim`` (128 + 64) restate other keys and are held to them. The
    one answers say: no low-rank queries (``q_lora_rank`` null), a sigmoid
    router whose group limit is the identity (``n_group`` 1, ``topk_group``
    1), the chosen weights renormalised, interleaved RoPE without scaling."""

    attention_bias: bool = False
    first_k_dense_replace: int = 1
    head_dim: int = 64
    hidden_act: str = "silu"
    hidden_size: int = 2048
    intermediate_size: int = 6144
    kv_lora_rank: int = 512
    max_position_embeddings: int = 32768
    model_type: str = "deepseek_v3"
    moe_intermediate_size: int = 768
    moe_layer_freq: int = 1
    n_group: int = 1
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts_per_tok: int = 6
    num_hidden_layers: int = 48
    num_key_value_heads: int = 32
    q_lora_rank: int | None = None
    qk_head_dim: int = 192
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    rms_norm_eps: float = 1e-6
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    rope_theta: float = 1000000.0
    routed_scaling_factor: float = 2.448
    scoring_func: str = "sigmoid"
    tie_word_embeddings: bool = False
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    v_head_dim: int = 128
    vocab_size: int = 128256
    seed: int = 3

    ONE_ANSWER = {
        "model_type": "deepseek_v3", "hidden_act": "silu", "attention_bias": False,
        "q_lora_rank": None, "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "rope_interleave": True, "rope_scaling": None, "tie_word_embeddings": False,
        "num_key_value_heads": lambda p: p.num_attention_heads,
        "head_dim": lambda p: p.qk_rope_head_dim,
        "qk_head_dim": lambda p: p.qk_nope_head_dim + p.qk_rope_head_dim,
    }

    def derived(self) -> dict:
        return {
            "routed_scaling_factor": float(self.routed_scaling_factor),
            "rope_theta": float(self.rope_theta),
        }


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _layer_shapes(config: KananaConfig, i: int) -> dict[str, tuple[tuple[int, ...], int | None]]:
    """``name -> (shape, fan-in)`` of layer ``i``'s arrays; a fan-in of None
    marks an array that is no projection (drawn by its own rule)."""
    h, heads, rank = config.hidden_size, config.num_attention_heads, config.kv_lora_rank
    nope, rot, d_v = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    shapes: dict = {
        "w_in": ((h,), None), "w_post": ((h,), None),
        "wq": ((h, heads * (nope + rot)), h), "w_kva": ((h, rank + rot), h), "kv_norm": ((rank,), None),
        "w_kvb": ((rank, heads * (nope + d_v)), rank), "wo": ((heads * d_v, h), heads * d_v),
    }
    if config.is_dense(i):
        w = config.intermediate_size
        shapes.update({"dense_gate": ((h, w), h), "dense_up": ((h, w), h), "dense_down": ((w, h), w)})
    else:
        w, e = config.moe_intermediate_size, config.n_routed_experts
        shared = w * config.n_shared_experts
        shapes.update({
            "router": ((h, e), h), "router_bias": ((e,), None),
            "gate": ((e, h, w), h), "up": ((e, h, w), h), "down": ((e, w, h), w),
            "shared_gate": ((h, shared), h), "shared_up": ((h, shared), h),
            "shared_down": ((shared, h), shared),
        })
    return shapes


def weight_shapes(config: KananaConfig) -> dict[str, tuple[int, ...]]:
    h, v = config.hidden_size, config.vocab_size
    shapes = {"embed": (v, h), "final_norm": (h,), "lm_head": (v, h)}
    for i in range(config.num_hidden_layers):
        shapes.update({f"{i}.{name}": shape for name, (shape, _) in _layer_shapes(config, i).items()})
    return shapes


def init_weights(config: KananaConfig, seed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights on the device, as ``kimi_linear.init_weights`` draws
    them: a projection normal over ``sqrt(fan-in)`` so that logits come out
    of unit order, a norm's weight near one, the router's selection bias
    normal at 0.02 (small and not zero: the choice by ``s + bias`` is another
    than the choice by ``s``; at 0.1 one expert ran ten times an even share,
    PERF.md PR 31)."""
    fan_in: dict = {"embed": 1, "final_norm": None, "lm_head": config.hidden_size}
    for i in range(config.num_hidden_layers):
        fan_in.update({f"{i}.{name}": f for name, (_, f) in _layer_shapes(config, i).items()})
    shapes = weight_shapes(config)
    keys = jax.random.split(jax.random.key(seed, impl="rbg"), len(shapes))
    weights = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if fan_in[name] is not None:
            weights[name] = _normal(key, shape, 1.0 / float(np.sqrt(fan_in[name])), 0.0, dtype)
        elif name.endswith("router_bias"):
            weights[name] = _normal(key, shape, 0.02, 0.0, dtype)
        else:  # a norm's weight
            weights[name] = _normal(key, shape, 0.1, 1.0, dtype)
    return weights


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays (numbered from 0) under their own names."""
    prefix = f"{i}."
    return {name[len(prefix) :]: a for name, a in weights.items() if name.startswith(prefix)}


# ---------------------------------------------------------------------------
# a layer's parts, shared by the prefill and the step
# ---------------------------------------------------------------------------


def _rope_interleaved(x, position, theta: float):
    """``x`` [B, L, heads, d] float32 at ``position`` [B, L], the published
    ``rope_interleave``: dimensions ``2j`` and ``2j + 1`` turn together. They
    are de-interleaved (the even ones, then the odd) and turned by halves,
    and left in that order, queries and keys alike."""
    return _rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1), position, theta)


def _latent(n1, position, layer, config: KananaConfig):
    """What a token leaves in the cache, ``[c | k_r]`` [B, L, 576] float32 of
    normed rows ``n1`` [B, L, hidden]: the latent NORMALISED and the rotary
    key TURNED, once for all heads."""
    rank = config.kv_lora_rank
    both = _project(n1, layer["w_kva"])
    c = _rms(both[..., :rank], layer["kv_norm"], config.rms_norm_eps)
    with jax.named_scope("rope"):
        k_r = _rope_interleaved(both[..., None, rank:], position, config.rope_theta)[..., 0, :]
    return jnp.concatenate([c, k_r], axis=-1)


def _queries(n1, position, layer, config: KananaConfig):
    """``(q_nope [B, L, heads, 128], q_rope [B, L, heads, 64])`` float32, the
    rotary part turned a head."""
    nope, rot = config.qk_nope_head_dim, config.qk_rope_head_dim
    q = _project(n1, layer["wq"]).reshape(n1.shape[:2] + (config.num_attention_heads, nope + rot))
    with jax.named_scope("rope"):
        return q[..., :nope], _rope_interleaved(q[..., nope:], position, config.rope_theta)


def _expanded(kept, layer, config: KananaConfig):
    """Every head's keys and values of the cached ``kept`` [B, L, 576] (in the
    operands' type, as the cache holds them): ``(k [B, heads, L, 192], v [B,
    heads, L, 128])``."""
    heads, nope, rank = config.num_attention_heads, config.qk_nope_head_dim, config.kv_lora_rank
    rows, length, _ = kept.shape
    with jax.named_scope("expand"):
        expanded = _project(kept[..., :rank], layer["w_kvb"]).astype(kept.dtype)
        expanded = expanded.reshape(rows, length, heads, nope + config.v_head_dim)
        k_r = jnp.broadcast_to(kept[:, :, None, rank:], (rows, length, heads, config.qk_rope_head_dim))
        k = jnp.concatenate([expanded[..., :nope], k_r], axis=-1)
    return k.transpose(0, 2, 1, 3), expanded[..., nope:].transpose(0, 2, 1, 3)


def _feed_forward(h, layer, i: int, real, config: KananaConfig):
    """``(h + ffn(rms(h; w_post)), [copies of ``real`` rows the busiest expert
    got, experts that got one])`` for rows ``h`` [T, hidden]; zeros for the
    dense layer. The pre-norm stands under its first reader's scope and the
    residual sum under its last writer's (``kimi_linear._layer``: why)."""
    eps = config.rms_norm_eps
    if config.is_dense(i):
        with jax.named_scope("dense"):
            n2 = _rms(h, layer["w_post"], eps)
            out = h + moe.gated_mlp(n2, layer["dense_gate"], layer["dense_up"], layer["dense_down"])
        return out, jnp.zeros(2, jnp.int32)
    with jax.named_scope("router"):
        n2 = _rms(h, layer["w_post"], eps)
        # (n_group 1 and topk_group 1: the group limit is the identity)
        weights, experts = moe.route_sigmoid(
            n2, layer["router"], layer["router_bias"], config.num_experts_per_tok,
            config.routed_scaling_factor, eps=ROUTER_EPS,
        )
        load = moe.expert_load(experts, config.n_routed_experts, real)
    with jax.named_scope("experts"):
        y = moe.expert_ffn(n2, weights, experts, layer["gate"], layer["up"], layer["down"])
    with jax.named_scope("shared"):
        y = y + moe.gated_mlp(n2, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
        out = h + y
    return out, jnp.stack([jnp.max(load), jnp.sum(load > 0, dtype=jnp.int32)])


# ---------------------------------------------------------------------------
# the prefill
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def session_vectors(weights, cache, tokens, segment, position, last, offset, first, *, config: KananaConfig):
    """One token stream's PREFILL: ``tokens``, ``segment`` and ``position``
    [1, T] and ``last`` [1, S] int32 as ``olmoe.session_vectors`` takes them.
    ``cache`` is the batch's ``(a layer's [slots, 576] each, vectors [2 *
    SESSIONS, hidden])``, donated: every layer's ``[c | k_r]`` of the stream
    is written at slots ``offset`` to ``offset + T``, as it lies, and the
    final-normed hidden state at each session's last position (the head's
    input) at rows ``first`` to ``first + S`` of the vectors (a later
    stream's rows overwrite what a stream of fewer than ``S`` sessions leaves
    behind its own). Returns ``(cache, copies of real tokens the busiest
    expert got, summed over the layers)``. (The name is the one every
    backbone's program a stream carries.)"""
    latents, vectors = cache
    operand = weights["0.wq"].dtype
    heads, d_v = config.num_attention_heads, config.v_head_dim
    rows, length = tokens.shape
    sessions = last.shape[1]
    with jax.named_scope("embed"):
        x = weights["embed"][tokens].astype(jnp.float32)
    real = (segment >= 0).reshape(-1)
    latents, busiest, n = list(latents), jnp.zeros((), jnp.int32), config.num_hidden_layers
    for i in range(n):
        layer = layer_of(weights, i)
        with jax.named_scope("mla"):
            n1 = _rms(x, layer["w_in"], config.rms_norm_eps)
            kept = _latent(n1, position, layer, config).astype(operand)
        with jax.named_scope("cache"):
            latents[i] = lax.dynamic_update_slice(latents[i], kept[0], (offset, 0))
        if i == n - 1:
            # the last layer's output is read at each session's last position
            # alone: its queries, feed-forward and head input are made there
            ids = jnp.where(last >= 0, jnp.arange(sessions, dtype=jnp.int32)[None], -1)
            x, n1 = _at_last(x, last)[None], _at_last(n1, last)[None]
            position = jnp.take_along_axis(position, jnp.maximum(last, 0), axis=1)
            real, length = ids.reshape(-1) >= 0, sessions
        with jax.named_scope("mla"):
            q = jnp.concatenate(_queries(n1, position, layer, config), axis=-1)
            k, v = _expanded(kept, layer, config)
            q = q.transpose(0, 2, 1, 3).astype(operand)
            if i == n - 1:
                out = fused_attention(q, k, v, segment=(ids, segment))
            else:
                out = fused_attention(q, k, v, causal=True, segment=segment)
            out = out.transpose(0, 2, 1, 3).reshape(rows, length, heads * d_v)
            h = x + _project(out, layer["wo"])
        y, counted = _feed_forward(h.reshape(rows * length, -1), layer, i, real, config)
        x, busiest = y.reshape(h.shape), busiest + counted[0]
    with jax.named_scope("head"):
        out = _rms(x[0], weights["final_norm"], config.rms_norm_eps)
        vectors = lax.dynamic_update_slice(vectors, out, (first, 0))
    return (tuple(latents), vectors), busiest


# ---------------------------------------------------------------------------
# the group's state and a step over it
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config", "dtype"))
def _empty_cache(config: KananaConfig, dtype):
    # zeros, not whatever the memory held: a slot no one sees still meets a
    # probability of 0, and 0 times a NaN is a NaN
    latents = tuple(
        jnp.zeros((config.cache_slots, config.latent_width), dtype) for _ in range(config.num_hidden_layers)
    )
    return latents, jnp.zeros((2 * SESSIONS, config.hidden_size), jnp.float32)


def new_state(weights, config: KananaConfig, seg, length, num, allowed):
    """A group's state on the device, from the host's arrays: ``seg``
    [cache_tokens] (which session a stream slot's latent is of, -1 for none),
    ``length`` [SESSIONS] each session's items, ``num`` [SESSIONS] the items
    it is to be answered with (0: no session), ``allowed`` [SESSIONS,
    vocabulary] its candidates. ``made`` counts the items chosen so far,
    ``items`` and ``logp`` [SESSIONS, generated_slots] hold them. The cache is
    empty: the prefills fill it."""
    places = (SESSIONS, config.generated_slots)
    return {
        "cache": _empty_cache(config, weights["0.wq"].dtype),
        "seg": topk.upload(seg, np.int32),
        "length": topk.upload(length, np.int32),
        "num": topk.upload(num, np.int32),
        "made": jnp.zeros(SESSIONS, jnp.int32),
        "items": jnp.full(places, -1, jnp.int32),
        "logp": jnp.zeros(places, jnp.float32),
        "allowed": topk.upload(allowed),
        "busiest": jnp.zeros((), jnp.int32),
        "reached": jnp.zeros((), jnp.int32),
    }


def _slot_ids(state, config: KananaConfig):
    """``[cache_slots]``: the session whose latent a slot holds, -1 for none:
    the stream slots as the host marked them, then every session's generated
    positions up to its newest (which this step writes before it attends)."""
    at = jnp.arange(config.generated_slots, dtype=jnp.int32)[None, :]
    seen = at < state["made"][:, None]
    generated = jnp.where(seen, jnp.arange(SESSIONS, dtype=jnp.int32)[:, None], -1)
    return jnp.concatenate([state["seg"], generated.reshape(-1)])


def _mla_absorbed(x, position, ids_q, ids_k, slot, kept, layer, config: KananaConfig):
    """Latent attention ABSORBED for one new position a session: ``x``
    [S, hidden] at ``position`` [S]; ``kept`` [slots, 576] is the layer's
    cache, into which the new positions' ``[c | k_r]`` go at ``slot`` [S]
    before the sessions attend over what they hold (``ids_q`` [S], ``ids_k``
    [slots]). Returns ``(x + attention, kept)``."""
    heads, nope, rank = config.num_attention_heads, config.qk_nope_head_dim, config.kv_lora_rank
    operand, sessions = layer["wq"].dtype, x.shape[0]
    with jax.named_scope("mla_absorbed"):
        n1 = _rms(x, layer["w_in"], config.rms_norm_eps)[None]
        new = _latent(n1, position[None], layer, config)[0].astype(operand)
        q_nope, q_rope = _queries(n1, position[None], layer, config)
    with jax.named_scope("cache"):
        kept = kept.at[slot].set(new)
    with jax.named_scope("mla_absorbed"):
        w_kvb = layer["w_kvb"].reshape(rank, heads, nope + config.v_head_dim)
        with jax.named_scope("absorb"):
            # W_uk into the query: a head's query in the latent's own space
            q_lat = jnp.einsum(
                "shd,chd->shc", q_nope[0].astype(operand), w_kvb[..., :nope],
                preferred_element_type=jnp.float32,
            )
            # (the kernel scales by the width it sees; the model's is 128 + 64)
            width = nope + config.qk_rope_head_dim
            q = jnp.concatenate([q_lat, q_rope[0]], axis=-1) * (config.latent_width / width) ** 0.5
        with jax.named_scope("attn"):
            # a session's heads are the rows of ONE head: they read the same
            # keys, and a tile of queries holds few sessions
            out = fused_attention(
                q.reshape(1, 1, sessions * heads, config.latent_width).astype(operand), kept[None, None], None,
                segment=(jnp.repeat(ids_q, heads)[None], ids_k[None]), value_width=rank,
            )
        with jax.named_scope("unabsorb"):
            out = jnp.einsum(
                "shc,chd->shd", out.reshape(sessions, heads, rank), w_kvb[..., nope:],
                preferred_element_type=jnp.float32,
            )
        h = x + _project(out.reshape(sessions, heads * config.v_head_dim), layer["wo"])
    return h, kept


def _forward(weights, state, config: KananaConfig):
    """Every session's newest item through the layers against the cache:
    ``(the head's input [S, hidden], the state with the cache one position a
    session on and its counts)``."""
    row = jnp.arange(SESSIONS, dtype=jnp.int32)
    made, live = state["made"], state["made"] < state["num"]
    newest = jnp.maximum(made - 1, 0)
    with jax.named_scope("embed"):
        x = weights["embed"][state["items"][row, newest]].astype(jnp.float32)
    with jax.named_scope("cache"):
        position = state["length"] + newest
        slot = config.cache_tokens + row * config.generated_slots + newest
        ids_q, ids_k = jnp.where(live, row, -1), _slot_ids(state, config)
    latents, vectors = state["cache"]
    latents, busiest, reached = list(latents), state["busiest"], state["reached"]
    for i in range(config.num_hidden_layers):
        layer = layer_of(weights, i)
        h, latents[i] = _mla_absorbed(x, position, ids_q, ids_k, slot, latents[i], layer, config)
        x, counted = _feed_forward(h, layer, i, live, config)
        busiest, reached = busiest + counted[0], reached + counted[1]
    with jax.named_scope("head"):
        out = _rms(x, weights["final_norm"], config.rms_norm_eps)
    return out, {**state, "cache": (tuple(latents), vectors), "busiest": busiest, "reached": reached}


def _pick(weights, state, out, config: KananaConfig):
    """``(logits [S, vocabulary], the state one item a live session on)``:
    ``lm_head`` over the head's inputs ``out`` [S, hidden], the likeliest
    allowed candidate a session and its log-probability among the allowed,
    appended; the item is no candidate again."""
    row = jnp.arange(SESSIONS)
    with jax.named_scope("head"):
        table = weights["lm_head"]
        logits = jnp.dot(out.astype(table.dtype), table.T, preferred_element_type=jnp.float32)
    with jax.named_scope("pick"):
        live = state["made"] < state["num"]
        packed, total = topk.select_top_k(logits, 1, mask=state["allowed"], log_sum_exp=True)
        item = packed[:, 1, 0]
        logp = lax.bitcast_convert_type(packed[:, 0, 0], jnp.float32) - total
        where = jnp.where(live, state["made"], config.generated_slots)  # past the end: dropped
        taken = jnp.where(live, item, state["allowed"].shape[1])
        state = {
            **state,
            "items": state["items"].at[row, where].set(item, mode="drop"),
            "logp": state["logp"].at[row, where].set(logp, mode="drop"),
            "allowed": state["allowed"].at[row, taken].set(False, mode="drop"),
            "made": state["made"] + live,
        }
    return logits, state


def _first(weights, state, config: KananaConfig):
    """The first item's ``(logits, state)``, from the prefills' vectors."""
    return _pick(weights, state, state["cache"][1][:SESSIONS], config)


def _step(weights, state, config: KananaConfig):
    """One step's ``(logits, state)``; the parity tests compare the logits
    with the reference's whole-sequence ``forward``."""
    out, state = _forward(weights, state, config)
    return _pick(weights, state, out, config)


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("state",))
def first_pick(weights, state, *, config: KananaConfig):
    """Every session's FIRST item, chosen at the prefills' last positions."""
    return _first(weights, state, config)[1]


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("state",))
def decode_step(weights, state, *, config: KananaConfig):
    """ONE step over all of a group's sessions (the module's docstring, 4):
    ``state`` (``new_state``'s, donated) comes back one item a live session on."""
    return _step(weights, state, config)[1]


@jax.jit
def answer_of(state):
    """What ``finalize`` fetches, in ONE array: ``[SESSIONS, 2, generated
    slots]`` int32, the items and the bits of their log-probabilities."""
    return jnp.stack([state["items"], lax.bitcast_convert_type(state["logp"], jnp.int32)], axis=1)
