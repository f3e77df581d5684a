"""SDAR-30B-A3B-Chat as a session continuer: the device side of the
sequential engine's ``sdar`` algorithm (``backbone.SdarAlgorithm``).

A session's items are the tokens, as in ``olmoe.py``; the ANSWER is not one
scoring but a generation: ``num`` items in order, produced block by block by
masked diffusion (``sdar_reference.py`` has the layer equations and the
sampler, and the tests and the benchmark hold this to it). The block is
Qwen3-MoE's: 32 query heads reading 4 key/value heads of 128 (head ``h``
reads ``h // 8``), q and k normed a head, RoPE, a BLOCK-causal mask (a key is
seen where ``position_k // B <= position_q // B``), 128 experts of width 768
with 8 a token, softmax router, weights renormalised.

What a batch is, on the device (the engine drives it):

1. PREFILL, ``session_vectors``: one packed token stream a program, as the
   other backbones' (``fused_attention(segment=, block=B)``, grouped
   queries). It returns no vector: it writes every layer's keys and values
   (bfloat16, normed, turned) of the stream into the batch's CACHE at the
   stream's offset, as they lie. The last layer computes nothing but its
   keys and values. A session's last ``L mod B`` items are in the stream but
   not visible in the cache (``state["seg"]``): they belong to the first
   generated block.
2. The CACHE, ``new_state``: a layer's keys and values ``[kv heads, slots,
   128]``, the slots being ``config.cache_tokens`` for the streams, end to
   end, then ``config.most_passes`` CHUNKS of ``SESSIONS * B``: pass ``t``
   writes every session's block there, one contiguous piece. Its capacity
   is fixed, so the pass below is ONE compiled shape; a batch whose streams
   do not fit is answered in more than one group (``backbone.GroupedAlgorithm``).
3. PASSES, ``denoise_pass``: all of the group's sessions in one program.
   Each session's CURRENT block goes in as its ``B`` tokens (mask id where
   masked) at its positions; a layer writes the blocks' keys and values into
   the pass's chunk and attends over the session's cached keys and the block
   itself (``fused_attention(segment=(ids of the block positions, ids of the
   slots))``: every key a session may see is of an earlier block or of this
   one, so "same session" is the whole mask); then the router, the experts
   (8 rows an expert), ``lm_head``, and the choice a position through
   ``ops/topk.select_top_k`` beside a log-sum-exp, and the fixing rule, all
   on the device. A block with no masked position left gets one more pass
   that fixes nothing: its COMMIT. Only a commit's chunk is ever seen again
   (``state["commits"]``: the host knows every session's schedule): what a
   denoise pass made of a block with masks in it is read by that pass alone.
   A session's last block needs no commit.

Scopes: ``embed``, ``attn`` (with ``rope``), ``router``, ``experts`` in the
prefill; ``cache``, ``attn``, ``router``, ``experts``, ``head``, ``unmask``
in a pass. The weights are drawn from a seed, not fitted (ROADMAP R7).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu.models.sequential.olmoe import (
    LENGTH_BUCKETS, SESSION_ALIGN, TOKEN_BUDGET, _normal, _project, _rms, _rope, stream_shapes,
)
from predictionio_tpu.models.sequential.records import BackboneParams
from predictionio_tpu.ops import moe, topk
from predictionio_tpu.ops.attention import fused_attention

__all__ = [
    "SdarConfig", "SdarAlgorithmParams", "SESSIONS", "MAX_SESSION", "SESSION_ALIGN", "TOKEN_BUDGET", "weight_shapes",
    "init_weights", "layer_of", "session_vectors", "new_state", "denoise_pass", "answer_of",
]

# items of a session the engine keeps, and so the longest stream: the
# traffic's bound (the model's own is ``max_position_embeddings``, 32,768)
MAX_SESSION = 4096
# sessions a group of passes holds: a stream's most, a short group is padded
SESSIONS = TOKEN_BUDGET // SESSION_ALIGN
# streams a prefill takes (``olmoe.STACKED_ROWS``): one, written into the
# group's cache where the stream lies; stacking them is ROADMAP S9's follow-up
STACKED_ROWS = 1
# passes a group may make, each with a chunk of the cache for its blocks'
# keys and values: 24 take an answer of 16 items (19 or 20 passes), and with
# the streams' slots below the keys are 32 of the attention kernel's tiles
# of 1,024
MOST_PASSES = 24
# slots of the cache that hold streams: fourteen and a half streams of the
# budget (a batch of 32 sessions packs into about seven); 32,768 slots in
# all, 0.4 GB at the published widths
CACHE_TOKENS = 16 * TOKEN_BUDGET - MOST_PASSES * SESSIONS * 4
# places a session's generated blocks have in the state (its partial block's
# items and its answer's)
GENERATED_SLOTS = 24

LAYER_ARRAYS = (
    "w_in", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "w_post", "router", "gate", "up", "down",
)
EXPERT_ARRAYS = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The keys of the published ``config.json`` that shape the program, and
    what it has no key for: the block, the steps, the mask's id, the cache."""

    hidden_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    block_length: int
    denoising_steps: int
    mask_token_id: int
    cache_tokens: int = CACHE_TOKENS
    most_passes: int = MOST_PASSES
    generated_slots: int = GENERATED_SLOTS

    # what the engine asks of any backbone's configuration
    @property
    def table_rows(self) -> int:
        return self.vocab_size

    @property
    def max_session(self) -> int:
        return MAX_SESSION

    def routed_copies(self, real_tokens: int) -> int:
        return self.num_hidden_layers * real_tokens * self.num_experts_per_tok

    def even_expert_load(self, real_tokens: float) -> float:
        return self.num_hidden_layers * real_tokens * self.num_experts_per_tok / self.num_experts

    def buckets(self) -> tuple[int, ...]:
        return tuple(b for b in LENGTH_BUCKETS if b < MAX_SESSION) + (MAX_SESSION,)

    def stream_shapes(self) -> tuple[int, ...]:
        return stream_shapes(TOKEN_BUDGET, MAX_SESSION)

    # the generation
    @property
    def chunk(self) -> int:
        """Slots one pass writes: every session's block."""
        return SESSIONS * self.block_length

    @property
    def cache_slots(self) -> int:
        return self.cache_tokens + self.most_passes * self.chunk

    @property
    def choices(self) -> int:
        """Positions one pass may fix in a block, and so the candidates a
        position needs: ``ceil(block / steps)``."""
        return -(-self.block_length // self.denoising_steps)

    def fit(self, length: int, num: int) -> int:
        """``num`` cut to what a session of ``length`` items can be answered
        with: places in the state and passes in the cache."""
        r = length % self.block_length
        num = max(0, min(num, self.generated_slots - r))
        while num and len(self.schedule(length, num)) > self.most_passes:
            num -= 1
        return num

    def cache_bytes(self, slots: int) -> int:
        """Bytes of keys and values ``slots`` token slots hold (bfloat16)."""
        return slots * self.num_hidden_layers * 2 * self.num_key_value_heads * self.head_dim * 2

    def schedule(self, length: int, num: int) -> str:
        """One session's passes, a letter each: ``d`` a denoise pass, ``c``
        a commit. A block of ``m`` masked positions takes ``min(m, steps)``
        denoise passes; every block but the last is committed."""
        block, steps = self.block_length, self.denoising_steps
        r = length % block
        out, left = [], num
        while left > 0:
            m = min(block - r, left)
            out.append("d" * min(m, steps))
            left, r = left - m, 0
        return "c".join(out)


Config = SdarConfig


@dataclasses.dataclass(frozen=True)
class SdarAlgorithmParams(BackboneParams):
    """The published ``config.json`` of JetLM/SDAR-30B-A3B-Chat, and what the
    generation needs and ``config.json`` has no key for: ``block_length``,
    ``denoising_steps`` (denoise passes a block) and ``mask_token_id`` (the
    vocabulary's last id by default; never an item). ``intermediate_size``
    names a dense feed-forward that no layer has (``mlp_only_layers`` []):
    stated, not built."""

    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: dict | None = None
    attention_bias: bool = False
    sliding_window: int | None = None
    use_sliding_window: bool = False
    max_window_layers: int = 48
    tie_word_embeddings: bool = False
    vocab_size: int = 151936
    max_position_embeddings: int = 32768
    model_type: str = "sdar_moe"
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int | None = None
    seed: int = 3

    ONE_ANSWER = {
        "model_type": "sdar_moe", "hidden_act": "silu", "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": (), "rope_scaling": None,
        "attention_bias": False, "sliding_window": None, "use_sliding_window": False,
        "tie_word_embeddings": False,
    }

    def derived(self) -> dict:
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("sdar: the key/value heads do not divide the heads")
        return {"mask_token_id": self.vocab_size - 1 if self.mask_token_id is None else self.mask_token_id}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_shapes(config: SdarConfig) -> dict[str, tuple[int, ...]]:
    h, w, e = config.hidden_size, config.moe_intermediate_size, config.num_experts
    n, v, d = config.num_hidden_layers, config.vocab_size, config.head_dim
    wide, narrow = config.num_attention_heads * d, config.num_key_value_heads * d
    return {
        "embed": (v, h), "final_norm": (h,), "lm_head": (v, h),
        "w_in": (n, h), "wq": (n, h, wide), "wk": (n, h, narrow), "wv": (n, h, narrow),
        "wo": (n, wide, h), "q_norm": (n, d), "k_norm": (n, d), "w_post": (n, h),
        "router": (n, h, e), "gate": (n, e, h, w), "up": (n, e, h, w), "down": (n, e, w, h),
    }


def init_weights(config: SdarConfig, seed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights on the device, ``[layers, ...]`` stacked, scaled as
    ``olmoe.init_weights`` scales them (a projection normal over
    ``sqrt(fan-in)``, a norm's weight near one): logits of unit order."""
    shapes = weight_shapes(config)
    h = config.hidden_size
    fan_in = {
        "wq": h, "wk": h, "wv": h, "wo": config.num_attention_heads * config.head_dim,
        "router": h, "gate": h, "up": h, "down": config.moe_intermediate_size, "lm_head": h,
        "embed": 1,
    }
    keys = jax.random.split(jax.random.key(seed, impl="rbg"), len(shapes))
    weights = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name in fan_in:
            weights[name] = _normal(key, shape, 1.0 / float(np.sqrt(fan_in[name])), 0.0, dtype)
        else:  # a norm's weight
            weights[name] = _normal(key, shape, 0.1, 1.0, dtype)
    return weights


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays, unstacked (the reference's layer form)."""
    return {name: weights[name][i] for name in LAYER_ARRAYS}


# ---------------------------------------------------------------------------
# a layer's parts, shared by the prefill and the pass
# ---------------------------------------------------------------------------


def _heads(x, width: int):
    return x.reshape(x.shape[:-1] + (x.shape[-1] // width, width))


def _queries_keys_values(n1, position, layer, config: SdarConfig):
    """``(q [R, T, heads, d], k, v [R, T, kv heads, d])`` float32 of normed
    rows ``n1`` [R, T, hidden] at ``position`` [R, T]: q and k normed a head
    and turned."""
    d, eps = config.head_dim, config.rms_norm_eps
    k = _rms(_heads(_project(n1, layer["wk"]), d), layer["k_norm"], eps)
    v = _heads(_project(n1, layer["wv"]), d)
    q = _rms(_heads(_project(n1, layer["wq"]), d), layer["q_norm"], eps)
    with jax.named_scope("rope"):
        q, k = _rope(q, position, config.rope_theta), _rope(k, position, config.rope_theta)
    return q, k, v


def _sparse(h, layer, experts_of_all_layers, index, real, config: SdarConfig):
    """``(h + moe(rms(h)), copies of ``real`` rows the busiest expert got,
    experts that got one)`` for rows ``h`` [T, hidden]."""
    n2 = _rms(h, layer["w_post"], config.rms_norm_eps)
    with jax.named_scope("router"):
        weights, experts = moe.route(
            n2, layer["router"], config.num_experts_per_tok, renormalise=True
        )
        load = moe.expert_load(experts, config.num_experts, real)
    with jax.named_scope("experts"):
        y = moe.expert_ffn(
            n2, weights, experts, *experts_of_all_layers, n_experts=config.num_experts,
            first_group=index * config.num_experts,
        )
    return h + y, jnp.max(load), jnp.sum(load > 0, dtype=jnp.int32)


def _stacked_experts(weights):
    """Every layer's experts, ``[layers * experts, ...]``: read in place."""
    return tuple(weights[name].reshape((-1,) + weights[name].shape[2:]) for name in EXPERT_ARRAYS)


# ---------------------------------------------------------------------------
# the prefill
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def session_vectors(weights, cache, tokens, segment, position, offset, *, config: SdarConfig):
    """One token stream's PREFILL: ``tokens``, ``segment`` and ``position``
    [1, T] int32 as ``olmoe.session_vectors`` takes them. ``cache`` is the
    batch's ``(keys, values)``, a tuple of ``[kv heads, slots, d]`` a layer
    each, donated: every layer's keys and values of the stream are written
    at slots ``offset`` to ``offset + T``, as they lie. Returns ``(cache,
    copies of real tokens the busiest expert got, summed over the layers)``.
    (The name is the one every backbone's program a stream carries.)"""
    operand = weights["wq"].dtype
    heads = config.num_attention_heads
    rows, length = tokens.shape
    with jax.named_scope("embed"):
        x = weights["embed"][tokens].astype(jnp.float32)
    stacked = _stacked_experts(weights)
    real = (segment >= 0).reshape(-1)
    small = {name: weights[name] for name in LAYER_ARRAYS if name not in EXPERT_ARRAYS}

    def keys_values(x, layer):
        n1 = _rms(x, layer["w_in"], config.rms_norm_eps)
        q, k, v = _queries_keys_values(n1, position, layer, config)
        return tuple(t.transpose(0, 2, 1, 3).astype(operand) for t in (q, k, v))

    def step(x, index):
        layer = jax.tree.map(lambda a: a[index], small)
        with jax.named_scope("attn"):
            q, k, v = keys_values(x, layer)
            out = fused_attention(q, k, v, causal=True, segment=segment, block=config.block_length)
            out = out.transpose(0, 2, 1, 3).reshape(rows, length, heads * config.head_dim)
            h = x + _project(out, layer["wo"])
        y, busiest, _ = _sparse(
            h.reshape(rows * length, -1), layer, stacked, index, real, config
        )
        return y.reshape(x.shape), (k[0], v[0], busiest)

    n = config.num_hidden_layers
    # the last layer's output is no one's input: its keys and values alone
    x, (keys, values, busiest) = lax.scan(step, x, jnp.arange(n - 1))
    with jax.named_scope("attn"):
        _, k_last, v_last = keys_values(x, jax.tree.map(lambda a: a[n - 1], small))
    with jax.named_scope("cache"):

        def written(side, scanned, last):
            return tuple(
                lax.dynamic_update_slice(c, scanned[i] if i < n - 1 else last[0], (0, offset, 0))
                for i, c in enumerate(side)
            )

        cache = written(cache[0], keys, k_last), written(cache[1], values, v_last)
    return cache, jnp.sum(busiest)


# ---------------------------------------------------------------------------
# the batch's state and a pass over it
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config", "dtype"))
def _empty_cache(config: SdarConfig, dtype):
    shape = (config.num_key_value_heads, config.cache_slots, config.head_dim)
    # zeros, not whatever the memory held: a slot no one sees still meets a
    # probability of 0, and 0 times a NaN is a NaN
    return tuple(tuple(jnp.zeros(shape, dtype) for _ in range(config.num_hidden_layers)) for _ in "kv")


def new_state(weights, config: SdarConfig, seg, commits, tokens, step, blocks, reach, start, allowed):
    """A group's state on the device, from the host's arrays: ``seg``
    [cache_tokens] (which session a stream slot's keys are of, -1 for none:
    padding, and a session's items past its last whole block), ``commits``
    [most_passes, SESSIONS * B] (whose clean block a pass's chunk holds once
    the pass has run: the session where the pass is its commit, -1
    elsewhere), ``tokens`` and
    ``step`` [SESSIONS, generated_slots] (a session's generated blocks as
    they start: its partial block's items, then the mask id; ``step`` -1
    where a position is to be generated and -2 elsewhere), ``blocks``
    [SESSIONS] the blocks each generates (0: no session), ``reach`` how many
    of its generated slots are positions of its sequence (the partial
    block's items and the answer's), ``start`` the position of a session's
    first generated block, ``allowed`` [SESSIONS,
    vocabulary] its candidates. The cache is empty: the prefill fills it."""
    sessions = tokens.shape[0]
    return {
        "cache": _empty_cache(config, weights["wk"].dtype),
        "seg": topk.upload(seg, np.int32),
        "commits": topk.upload(commits, np.int32),
        "pass": jnp.zeros((), jnp.int32),
        "tokens": topk.upload(tokens, np.int32),
        "step": topk.upload(step, np.int32),
        "logp": jnp.zeros(tokens.shape, jnp.float32),
        "block": jnp.zeros(sessions, jnp.int32),
        "tick": jnp.zeros(sessions, jnp.int32),
        "blocks": topk.upload(blocks, np.int32),
        "reach": topk.upload(reach, np.int32),
        "start": topk.upload(start, np.int32),
        "allowed": topk.upload(allowed),
        "busiest": jnp.zeros((), jnp.int32),
        "reached": jnp.zeros((), jnp.int32),
    }


def _block_view(state, config: SdarConfig):
    """Every session's current block: ``(at [S, B] its places in the
    generated slots, active [S])``."""
    block = config.block_length
    most = config.generated_slots // block - 1
    at = jnp.minimum(state["block"], most)[:, None] * block + jnp.arange(block, dtype=jnp.int32)
    return at, state["block"] < state["blocks"]


def _forward(weights, state, config: SdarConfig):
    """The sessions' current blocks through the layers: ``(logits [S * B,
    vocabulary] float32, cache, busiest, reached)``: the last two are the
    state's counts a pass on (the copies of real rows each layer's busiest
    expert got, and the experts of each layer that got one: what a pass has
    to read of them)."""
    operand = weights["wq"].dtype
    block, heads, kv_heads = config.block_length, config.num_attention_heads, config.num_key_value_heads
    group, d = heads // kv_heads, config.head_dim
    sessions = state["tokens"].shape[0]
    rows = sessions * block
    at, active = _block_view(state, config)
    # a place the answer does not reach (a short last block) is no position
    real = (active[:, None] & (at < state["reach"][:, None])).reshape(rows)
    tokens = jnp.take_along_axis(state["tokens"], at, axis=1).reshape(1, rows)
    position = (state["start"][:, None] + at).reshape(1, rows)
    of_session = jnp.repeat(jnp.arange(sessions, dtype=jnp.int32), block)
    ids_q = jnp.where(real, of_session, -1)
    with jax.named_scope("cache"):
        # the stream slots as the host marked them; of the passes' chunks the
        # earlier ones where they were commits, and this pass's own
        now = state["pass"]
        earlier = jnp.arange(config.most_passes, dtype=jnp.int32)[:, None] - now
        ids_chunks = jnp.where(earlier < 0, state["commits"], jnp.where(earlier == 0, ids_q[None, :], -1))
        ids_k = jnp.concatenate([state["seg"], ids_chunks.reshape(-1)])[None]
        chunk_at = (0, config.cache_tokens + now * rows, 0)
    with jax.named_scope("embed"):
        x = weights["embed"][tokens].astype(jnp.float32)
    stacked = _stacked_experts(weights)
    cache_k, cache_v = list(state["cache"][0]), list(state["cache"][1])
    busiest, reached = state["busiest"], state["reached"]
    for i in range(config.num_hidden_layers):
        layer = {name: weights[name][i] for name in LAYER_ARRAYS if name not in EXPERT_ARRAYS}
        with jax.named_scope("attn"):
            n1 = _rms(x, layer["w_in"], config.rms_norm_eps)
            q, k, v = _queries_keys_values(n1, position, layer, config)
        with jax.named_scope("cache"):
            cache_k[i] = lax.dynamic_update_slice(
                cache_k[i], k[0].transpose(1, 0, 2).astype(operand), chunk_at
            )
            cache_v[i] = lax.dynamic_update_slice(
                cache_v[i], v[0].transpose(1, 0, 2).astype(operand), chunk_at
            )
        with jax.named_scope("attn"):
            # the eight query heads of a key/value head lie beside each other
            # as rows of ONE head: a tile of queries then holds few sessions,
            # and the keys of the others are neither read nor multiplied
            folded = q[0].reshape(rows, kv_heads, group, d).transpose(1, 0, 2, 3)
            folded = folded.reshape(1, kv_heads, rows * group, d).astype(operand)
            out = fused_attention(
                folded, cache_k[i][None], cache_v[i][None],
                segment=(jnp.repeat(ids_q, group)[None], ids_k),
            )
            out = out.reshape(kv_heads, rows, group, d).transpose(1, 0, 2, 3)
            h = x + _project(out.reshape(1, rows, heads * d), layer["wo"])
        y, most, got = _sparse(h[0], layer, stacked, i, real, config)
        x, busiest, reached = y[None], busiest + most, reached + got
    with jax.named_scope("head"):
        out = _rms(x[0], weights["final_norm"], config.rms_norm_eps)
        logits = jnp.dot(
            out.astype(operand), weights["lm_head"].T, preferred_element_type=jnp.float32
        )
    return logits, (tuple(cache_k), tuple(cache_v)), busiest, reached


def _unmask(state, logits, config: SdarConfig):
    """The fixing rule (``sdar_reference.fix``) for every session at once,
    and each session's move to its next pass."""
    block, steps, k = config.block_length, config.denoising_steps, config.choices
    sessions = state["tokens"].shape[0]
    row = jnp.arange(sessions)
    at, active = _block_view(state, config)
    masked = (jnp.take_along_axis(state["step"], at, axis=1) == -1) & active[:, None]
    allowed = jnp.broadcast_to(state["allowed"][:, None, :], (sessions, block, logits.shape[-1]))
    packed, total = topk.select_top_k(
        logits, k, mask=allowed.reshape(sessions * block, -1), log_sum_exp=True
    )
    scores = lax.bitcast_convert_type(packed[:, 0, :], jnp.float32).reshape(sessions, block, k)
    items = packed[:, 1, :].reshape(sessions, block, k)
    total = total.reshape(sessions, block)
    m = jnp.sum(masked, axis=1)
    count = jnp.where(m > 0, -(-m // jnp.maximum(steps - state["tick"], 1)), 0)
    confidence = jnp.where(masked, scores[:, :, 0] - total, -jnp.inf)
    order = jnp.argsort(-confidence, axis=1, stable=True)  # ties: the lower position
    tokens, step, logp, ok = state["tokens"], state["step"], state["logp"], state["allowed"]
    taken = jnp.full((sessions, k), -1, jnp.int32)
    for j in range(k):
        place = order[:, j]
        fixes = j < count
        offered, worth = items[row, place], scores[row, place]  # [S, k]
        free = ~jnp.any(offered[:, :, None] == taken[:, None, :], axis=-1)
        first = jnp.argmax(free, axis=1)
        item = offered[row, first]
        where = jnp.where(fixes, at[row, place], config.generated_slots)  # past the end: dropped
        tokens = tokens.at[row, where].set(item, mode="drop")
        step = step.at[row, where].set(state["tick"], mode="drop")
        logp = logp.at[row, where].set(worth[row, first] - total[row, place], mode="drop")
        ok = ok.at[row, jnp.where(fixes, item, ok.shape[1])].set(False, mode="drop")
        taken = taken.at[:, j].set(jnp.where(fixes, item, -1))
    denoised = m > 0
    full = m - count == 0
    last = state["block"] == state["blocks"] - 1
    moved = jnp.where(
        denoised & full & last, state["blocks"],  # done: its last block needs no commit
        jnp.where(~denoised & active, state["block"] + 1, state["block"]),  # committed
    )
    tick = jnp.where(denoised, state["tick"] + 1, 0)
    return {
        **state, "tokens": tokens, "step": step, "logp": logp, "allowed": ok, "block": moved,
        "tick": tick, "pass": state["pass"] + 1,
    }


def _pass(weights, state, config: SdarConfig):
    """``(the blocks' logits [SESSIONS * B, vocabulary], the state one pass
    on)``; the parity tests compare the logits with the reference's
    whole-sequence ``forward``."""
    logits, cache, busiest, reached = _forward(weights, state, config)
    counted = {"cache": cache, "busiest": busiest, "reached": reached}
    with jax.named_scope("unmask"):
        return logits, _unmask({**state, **counted}, logits, config)


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("state",))
def denoise_pass(weights, state, *, config: SdarConfig):
    """ONE pass over all of a group's sessions (the module's docstring, 3):
    ``state`` (``new_state``'s, donated) comes back one pass on."""
    return _pass(weights, state, config)[1]


@jax.jit
def answer_of(state):
    """What ``finalize`` fetches, in ONE array: ``[SESSIONS, 3, generated
    slots]`` int32, the items, the bits of their log-probabilities and the
    steps that fixed them."""
    return jnp.stack(
        [state["tokens"], lax.bitcast_convert_type(state["logp"], jnp.int32), state["step"]], axis=1
    )
