"""LFM2-8B-A1B as a session encoder: the device side of the sequential
engine's ``lfm2`` algorithm (``backbone.Lfm2Algorithm``).

As ``olmoe.py`` and ``kimi_linear.py`` are for their backbones: a session's
items are the tokens, one causal forward pass over the session
(``session_vectors``, the SAME name, arguments and results, so that the
engine's launch and the benchmark's readers serve all of them) gives the
final-normed hidden state at its last real position, and the engine scores it
against the embedding (the head is TIED to it: ``backbone.BackboneModel.head``).
Layer equations: ``lfm2_reference.py``, which the tests and the benchmark hold
this to.

The model's WHOLE depth is one program of 24 layers, numbered from 0 as the
published ``layer_types`` LIST numbers them (the kinds are read from the list,
its last period is irregular). Sparse layers that REPEAT a pattern of mixers
are one ``lax.scan`` whose body is the pattern's layers (``scan_plan``: for
the published list the two dense layers unrolled, a scan of 4 over
``[attention, conv, conv, conv]`` and a scan of 2 over ``[attention, conv,
conv]``: 7 sparse bodies for 22 layers), so that what a sparse body holds
twice (the held experts' compact block and its way out on an overflow,
below) is compiled, loaded and kept 7 times a program and not 22; a layer
in no repeat stays unrolled. The weight tree is flat: an unrolled layer
``i``'s arrays as ``"<i>.<name>"``, a scan's as ``"<start>+<period>x<repeats>.
<slot>.<name>"`` with the repeats stacked in front (stacked ONCE, in
``init_weights``); ``layer_of`` gives any layer's arrays under their
published names. The token mixer is a GATED SHORT CONVOLUTION
(``conv``: ``C * conv3(B * u)`` with ``B, C, u`` one projection's thirds, a
causal depthwise convolution of ``conv_L_cache`` taps with no bias and no
activation, ``ops/linear_attention.short_conv``) or grouped-query attention
(``full_attention``: 32 query heads over 8 key/value heads of 64, a norm a
head on q and k, RoPE, ``ops/attention.fused_attention``); the feed-forward is
dense (the first ``num_dense_layers``) or sparse (``ops/moe``: a sigmoid
router over ALL ``num_experts``, chosen by score plus ``expert_bias``, and the
grouped products over the experts HELD here, ``ops/moe.held_expert_ffn``:
only the copies routed to a held expert are laid out, in a compact block;
a routing that overflows it takes the whole path, ``expert_ffn(held=)``, as
the other branch of one ``lax.cond``, which a scanned body can afford: the
readings stand beside ``ops/moe.HELD_ROOM``). In a scan the small arrays ride
as its ``xs``; the experts' matrices stay WHOLE, a scan's repeats stacked,
and reach the kernel through ``first_group`` (a slice in front of a Pallas
call is a copy of the matrices).

What a chip holds is a share of a stated deployment (``experts_held``): the
router keeps its published width and its experts per token, and the held
experts' part of the result goes on to the next layer.

What runs: weights in bfloat16 (products with bf16 operands and float32
accumulation); the RESIDUAL STREAM, the norms, the convolution's two gates
and its taps, RoPE, the router and every softmax in float32. A float32 weight
tree (the CPU parity tests) computes in float32.

A program is ``[R, T]`` tokens, ``R`` token streams of several sessions each
as its rows, as ``olmoe.py``'s is (``segment``, ``position``): attention sees
a key only from inside its own row and segment, RoPE turns by ``position``,
and a convolution's tap reaches no further back than its own session's first
item (``short_conv(position=)``), so a session's positions come out as they
would alone. The experts take the tokens of all rows at once.
"""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu.models.sequential.olmoe import (
    LENGTH_BUCKETS, SESSION_ALIGN, _at_last, _normal, _project, _rms, _rope, bucket_of, stream_shapes,
)
from predictionio_tpu.models.sequential.records import BackboneParams
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.attention import fused_attention
from predictionio_tpu.ops.linear_attention import short_conv

__all__ = [
    "Lfm2Config", "Lfm2AlgorithmParams", "TOKEN_BUDGET", "STACKED_ROWS", "MAX_SESSION", "SESSION_ALIGN", "ROUTER_EPS",
    "bucket_of", "weight_shapes", "init_weights", "layer_of", "gated_conv", "session_vectors",
    "all_logits",
]

# tokens a stream holds (``MAX_SESSION`` where a session is longer)
TOKEN_BUDGET = 2048
# streams that ride as the rows of one program (``olmoe.STACKED_ROWS``: why
# and how). ONE here, from the chip (PERF.md, PR 41; ms a STREAM of 2,048
# tokens through all 24 layers): [1, 2048] **45.4**, [2, 2048] 46.5, [4, 2048]
# 49.1, [8, 2048] 50.4, two streams end to end as [1, 4096] 49.9. Three
# quarters of the layers' mixers are a token's own (two projections and
# float32 gates and taps), which gain nothing by rows, and a held expert has
# its 256 rows (a whole tile of ``ops/moe.TILING``) at ONE stream already.
# The cell's answers a second agree (one seed, 4100000101): **93.2** one by
# one, 88.9 by twos, 87.0 by fours. The closed set is then
# [1, 2048] and [1, 4096]; unrolled (PR 41 to 46) each compiled in 13 to 27 s
# on the chip's host and the server's cold start with an EMPTY compile cache
# was 57.0 s (a set-up of about 100 s with 27.0 s of drawing weights) against
# Kimi-Linear's 128 to 138 s. The sparse layers are scanned bodies since PR 47
# (``scan_plan``), not for that compile time but for the SECOND path a sparse
# body then affords (``ops/moe.HELD_ROOM``: the readings)
STACKED_ROWS = 1
# items of a session the engine keeps, and so the longest program: the
# traffic's bound (the model's own is ``max_position_embeddings``, 128,000)
MAX_SESSION = 4096
# in the renormalisation of the chosen experts' scores (the published
# ``routing_weights / (routing_weights.sum(-1) + 1e-6)``)
ROUTER_EPS = 1e-6

CONV, ATTENTION = "conv", "full_attention"
# the arrays a scan does not slice (``_layers``)
EXPERT_ARRAYS = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The keys of the published ``config.json`` that shape the program, and
    the chip's share."""

    hidden_size: int
    intermediate_size: int  # the dense layers' feed-forward
    moe_intermediate_size: int
    num_hidden_layers: int
    layer_types: tuple[str, ...]  # a mixer's kind a layer, from 0
    conv_L_cache: int  # the convolution's taps
    num_attention_heads: int
    num_key_value_heads: int
    num_dense_layers: int
    num_experts: int  # the router's width
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_eps: float
    rope_theta: float
    vocab_size: int
    max_position_embeddings: int
    experts_held: tuple[int, int]  # (first, count) of the router's experts

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(str(kind) for kind in self.layer_types))
        object.__setattr__(self, "experts_held", tuple(int(v) for v in self.experts_held))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, num_hidden_layers {self.num_hidden_layers}"
            )
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}: only {CONV!r} and {ATTENTION!r} are built")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the key/value heads do not divide the heads")
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} is no block of {self.num_experts} experts")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def max_session(self) -> int:
        """Items of a session the engine keeps."""
        return min(MAX_SESSION, self.max_position_embeddings)

    @property
    def table_rows(self) -> int:
        """Rows of ``embed``, which is the head too: the items a session may hold."""
        return self.vocab_size

    def is_conv(self, i: int) -> bool:
        return self.layer_types[i] == CONV

    def is_dense(self, i: int) -> bool:
        return i < self.num_dense_layers

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - min(self.num_dense_layers, self.num_hidden_layers)

    def even_expert_load(self, real_tokens: float) -> float:
        """Copies of ``real_tokens`` an even split gives each expert, summed
        over the sparse layers."""
        return self.sparse_layers * real_tokens * self.num_experts_per_tok / self.num_experts

    def routed_copies(self, real_tokens: int) -> int:
        """Copies of ``real_tokens`` the routers send out, over all layers."""
        return self.sparse_layers * real_tokens * self.num_experts_per_tok

    def buckets(self) -> tuple[int, ...]:
        """The ladder the benchmark's check pads its references by."""
        top = self.max_session
        return tuple(b for b in LENGTH_BUCKETS if b < top) + (top,)

    def stream_shapes(self) -> tuple[int, ...]:
        return stream_shapes(TOKEN_BUDGET, self.max_session)


Config = Lfm2Config


@dataclasses.dataclass(frozen=True)
class Lfm2AlgorithmParams(BackboneParams):
    """The published ``config.json`` of LiquidAI/LFM2-8B-A1B and the chip's
    share of a stated deployment: ``experts_held`` ``[first, count]`` of the
    router's ``num_experts`` (all of them by default). ``layer_types`` is the
    published LIST, a mixer's kind a layer."""

    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: tuple = ("conv", "conv", "full_attention", "conv") * 5 + (
        "conv", "full_attention", "conv", "conv",
    )
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    vocab_size: int = 65536
    max_position_embeddings: int = 128000
    model_type: str = "lfm2_moe"
    experts_held: tuple | None = None
    seed: int = 3

    ONE_ANSWER = {
        "model_type": "lfm2_moe", "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
    }

    def derived(self) -> dict:
        return {
            "routed_scaling_factor": float(self.routed_scaling_factor),
            "rope_theta": float(self.rope_theta),
            "experts_held": tuple(self.experts_held or (0, self.num_experts)),
        }


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _layer_shapes(config: Lfm2Config, i: int) -> dict[str, tuple[tuple[int, ...], int | None]]:
    """``name -> (shape, fan-in)`` of layer ``i``'s arrays, under the
    published modules' names (a projection kept ``[in, out]``); a fan-in of
    None marks an array that is no projection (drawn by its own rule)."""
    h = config.hidden_size
    shapes: dict = {"operator_norm": ((h,), None), "ffn_norm": ((h,), None)}
    if config.is_conv(i):
        taps = config.conv_L_cache
        shapes.update({"in_proj": ((h, 3 * h), h), "conv": ((taps, h), taps), "out_proj": ((h, h), h)})
    else:
        heads, kv, d = config.num_attention_heads, config.num_key_value_heads, config.head_dim
        shapes.update({
            "q_proj": ((h, heads * d), h), "k_proj": ((h, kv * d), h), "v_proj": ((h, kv * d), h),
            "out_proj": ((heads * d, h), heads * d),
            "q_layernorm": ((d,), None), "k_layernorm": ((d,), None),
        })
    if config.is_dense(i):
        w = config.intermediate_size
        shapes.update({"w1": ((h, w), h), "w3": ((h, w), h), "w2": ((w, h), w)})
    else:
        w, held = config.moe_intermediate_size, config.experts_held[1]
        shapes.update({
            "router": ((h, config.num_experts), h), "expert_bias": ((config.num_experts,), None),
            "gate": ((held, h, w), h), "up": ((held, h, w), h), "down": ((held, w, h), w),
        })
    return shapes


def scan_plan(config: Lfm2Config) -> tuple[tuple[int, int, int], ...]:
    """The program's runs of layers, ``(start, period, repeats)`` each, from
    ``layer_types`` and ``num_dense_layers`` alone: ``repeats`` over 1 is ONE
    ``lax.scan`` of that many steps whose body is layers ``start`` to
    ``start + period - 1``; ``(i, 1, 1)`` is layer ``i`` unrolled. The dense
    layers stay unrolled (the first few, they hold no expert block). Among
    the sparse ones, from the left: the repeat of a pattern of mixers that
    covers most layers from here (at least twice; of two that cover as many
    the shorter pattern), or, where nothing repeats from here, this layer
    alone."""
    runs, n = [], config.num_hidden_layers
    i = 0
    while i < n:
        best = (1, 1)
        for period in range(1, 0 if config.is_dense(i) else (n - i) // 2 + 1):
            pattern, repeats = config.layer_types[i : i + period], 1
            while config.layer_types[i + repeats * period : i + (repeats + 1) * period] == pattern:
                repeats += 1
            if repeats > 1 and period * repeats > best[0] * best[1]:
                best = (period, repeats)
        runs.append((i, *best))
        i += best[0] * best[1]
    return tuple(runs)


def _stacked_name(start: int, period: int, repeats: int, slot: int, name: str) -> str:
    return f"{start}+{period}x{repeats}.{slot}.{name}"


_STACKED = re.compile(r"(\d+)\+(\d+)x(\d+)\.(\d+)\.(.+)")


def _tree(config: Lfm2Config) -> dict[str, tuple[tuple[str, ...], tuple[int, ...], int | None]]:
    """The weight tree as it is kept: ``name -> (the published arrays
    ``"<i>.<name>"`` it holds, in order; ONE such array's shape; its
    fan-in)``. A scan's array holds its steps' stacked in front."""
    h = config.hidden_size
    tree = {"embed": (("embed",), (config.vocab_size, h), h), "embedding_norm": (("embedding_norm",), (h,), None)}
    for start, period, repeats in scan_plan(config):
        for slot in range(period):
            for name, spec in _layer_shapes(config, start + slot).items():
                members = tuple(f"{start + slot + r * period}.{name}" for r in range(repeats))
                kept = members[0] if repeats == 1 else _stacked_name(start, period, repeats, slot, name)
                tree[kept] = (members, *spec)
    return tree


def weight_shapes(config: Lfm2Config) -> dict[str, tuple[int, ...]]:
    """``name -> shape`` of the tree ``init_weights`` gives."""
    return {
        name: shape if len(members) == 1 else (len(members), *shape)
        for name, (members, shape, _) in _tree(config).items()
    }


def init_weights(config: Lfm2Config, seed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights on the device: a projection normal over
    ``sqrt(fan-in)`` so that every layer keeps the stream's scale; ``embed``
    over ``sqrt(hidden)``, because it is the head too and the logits are to
    come out of unit order (a session's first stream is that small; the
    first norm takes it to one); a norm's weight near one; the router's
    selection bias small and not zero, so that the choice by ``s + bias`` is
    another than the choice by ``s``.

    Every PUBLISHED array (``"<i>.<name>"``, ``embed``, ``embedding_norm``)
    has one key of the seed's, in the order of the sorted names, whatever
    ``scan_plan`` makes of the layers: ``layer_of`` gives the same numbers
    for a seed however the tree is stacked. A scan's arrays are stacked
    here, once, one at a time (its members leave the device as it is made),
    and never inside a program."""
    tree = _tree(config)
    published = sorted(member for members, _, _ in tree.values() for member in members)
    keys = dict(zip(published, jax.random.split(jax.random.key(seed, impl="rbg"), len(published))))

    def draw(member, shape, fan_in):
        if fan_in is not None:
            return _normal(keys[member], shape, 1.0 / float(np.sqrt(fan_in)), 0.0, dtype)
        if member.endswith(".expert_bias"):
            # ``kimi_linear.init_weights``: at 0.1 one held expert was ten
            # times as busy as an even split (PERF.md, PR 31)
            return _normal(keys[member], shape, 0.02, 0.0, dtype)
        return _normal(keys[member], shape, 0.1, 1.0, dtype)  # a norm's weight

    weights = {}
    for name, (members, shape, fan_in) in tree.items():
        drawn = [draw(member, shape, fan_in) for member in members]
        weights[name] = drawn[0] if len(drawn) == 1 else jnp.stack(drawn)
    return weights


def layer_of(weights: dict, i: int) -> dict:
    """Layer ``i``'s arrays (numbered from 0) under their published names,
    whether the tree keeps them alone or as one step of a scan's."""
    prefix = f"{i}."
    layer = {name[len(prefix) :]: a for name, a in weights.items() if name.startswith(prefix)}
    for name, a in weights.items():
        stacked = _STACKED.fullmatch(name)
        if stacked:
            start, period, repeats, slot = map(int, stacked.groups()[:4])
            step, at = divmod(i - start, period)
            if 0 <= step < repeats and at == slot:
                layer[stacked.group(5)] = a[step]
    return layer


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def gated_conv(projected, taps, position=None):
    """``C * conv(B * u)`` for ``projected`` [B, L, 3 * hidden] float32, the
    thirds ``B, C, u`` in the published order, ``taps`` [taps, hidden]: both
    gates and the taps in float32, no bias, no activation. ``position``
    [B, L] (None: every row one session) is each token's index inside its
    session: a tap reaches no further back than index 0."""
    with jax.named_scope("gate"):
        b, c, u = jnp.split(projected, 3, axis=-1)
        gated = b * u
    with jax.named_scope("taps"):
        y, _ = short_conv(gated, taps, position=position, activation=None)
    with jax.named_scope("gate"):
        return c * y


def _conv_mixer(n, position, layer):
    """``n`` [B, L, hidden] float32 -> ``out_proj(gated_conv(in_proj(n)))``."""
    with jax.named_scope("in_proj"):
        projected = _project(n, layer["in_proj"])
    y = gated_conv(projected, layer["conv"], position)
    with jax.named_scope("out_proj"):
        return _project(y, layer["out_proj"])


def _attention_mixer(n, segment, position, layer, config: Lfm2Config):
    """Grouped-query attention, a norm a head on q and k, RoPE by
    ``position``; inside ``segment`` [B, L] where rows are shared."""
    rows, length, _ = n.shape
    heads, kv, d = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    eps = config.norm_eps
    q = _rms(_project(n, layer["q_proj"]).reshape(rows, length, heads, d), layer["q_layernorm"], eps)
    k = _rms(_project(n, layer["k_proj"]).reshape(rows, length, kv, d), layer["k_layernorm"], eps)
    v = _project(n, layer["v_proj"]).reshape(rows, length, kv, d)
    with jax.named_scope("rope"):
        q, k = _rope(q, position, config.rope_theta), _rope(k, position, config.rope_theta)
    operand = layer["q_proj"].dtype
    q, k, v = (t.transpose(0, 2, 1, 3).astype(operand) for t in (q, k, v))
    out = fused_attention(q, k, v, causal=True, segment=segment)
    return _project(out.transpose(0, 2, 1, 3).reshape(rows, length, heads * d), layer["out_proj"])


def _layer(x, segment, position, layer, conv: bool, dense: bool, config: Lfm2Config, first_group=0):
    """One decoder layer over ``x`` [B, L, hidden] float32, its mixer a
    convolution or attention and its feed-forward dense or sparse: ``(x',
    [busiest held expert's copies, copies routed to a held expert, 1 if they
    overflowed the held block (``ops/moe.held_expert_ffn``)])`` of REAL
    tokens (``segment`` not -1; the padding's copies get no row); zeros for
    a dense layer. ``segment`` None: every row one session. ``layer``'s
    experts may be several layers' stacked, this one's from ``first_group``."""
    rows, length, hidden = x.shape
    eps = config.norm_eps
    if conv:
        with jax.named_scope("conv"):
            h = x + _conv_mixer(_rms(x, layer["operator_norm"], eps), position, layer)
    else:
        with jax.named_scope("attn"):
            h = x + _attention_mixer(_rms(x, layer["operator_norm"], eps), segment, position, layer, config)
    # the feed-forward's pre-norm stands under its first reader's scope and
    # the residual sum under its last writer's (``kimi_linear._layer``: why)
    if dense:
        with jax.named_scope("dense"):
            n2 = _rms(h, layer["ffn_norm"], eps).reshape(rows * length, hidden)
            y = moe.gated_mlp(n2, layer["w1"], layer["w3"], layer["w2"])
            out = h + y.reshape(rows, length, hidden)
        return out, jnp.zeros(3, jnp.int32)
    first, count = config.experts_held
    with jax.named_scope("router"):
        n2 = _rms(h, layer["ffn_norm"], eps).reshape(rows * length, hidden)
        weights, experts = moe.route_sigmoid(
            n2, layer["router"], layer["expert_bias"], config.num_experts_per_tok,
            config.routed_scaling_factor, eps=ROUTER_EPS,
        )
        real = None if segment is None else (segment >= 0).reshape(-1)
        load = moe.expert_load(experts - first, count, real)
    with jax.named_scope("experts"):
        # the overflow's way out is the whole path behind a `cond`: this
        # program's sparse bodies are few (``scan_plan``), and the rounds'
        # loop costs every execution its carry (``ops/moe.HELD_ROOM``)
        y, rounds = moe.held_expert_ffn(
            n2, weights, experts, layer["gate"], layer["up"], layer["down"],
            held=(first, count, config.num_experts), first_group=first_group, counted=real,
            overflow="whole",
        )
        out = h + y.reshape(rows, length, hidden)
    return out, jnp.stack([jnp.max(load), jnp.sum(load), (rounds > 1).astype(jnp.int32)])


def _layers(weights, x, segment, position, config: Lfm2Config):
    """Every layer over ``x`` as ``scan_plan`` lays them out: ``(x, the
    layers' three counts summed)``. A scan slices its small arrays; its
    experts' stay whole, ``[repeats * held, ...]``, a step's from
    ``step * held``."""
    counts = jnp.zeros(3, jnp.int32)
    held = config.experts_held[1]
    for start, period, repeats in scan_plan(config):
        kinds = [(config.is_conv(start + slot), config.is_dense(start + slot)) for slot in range(period)]
        if repeats == 1:
            x, counted = _layer(x, segment, position, layer_of(weights, start), *kinds[0], config)
            counts = counts + counted
            continue
        small = [
            {
                name: weights[_stacked_name(start, period, repeats, slot, name)]
                for name in _layer_shapes(config, start + slot)
            }
            for slot in range(period)
        ]
        # (a scan's layers are sparse; merging the two leading axes moves nothing)
        whole = [
            {name: (a := arrays.pop(name)).reshape(-1, *a.shape[2:]) for name in EXPERT_ARRAYS} for arrays in small
        ]

        def body(carry, scanned):
            (x, counts), (step, sliced) = carry, scanned
            for (conv, dense), arrays, experts in zip(kinds, sliced, whole):
                x, counted = _layer(
                    x, segment, position, {**arrays, **experts}, conv, dense, config, first_group=step * held
                )
                counts = counts + counted
            return (x, counts), None

        (x, counts), _ = lax.scan(body, (x, counts), (jnp.arange(repeats, dtype=jnp.int32), small))
    return x, counts


@functools.partial(jax.jit, static_argnames=("config",))
def session_vectors(weights, tokens, segment, position, last, *, config: Lfm2Config):
    """``R`` token streams as the rows of one program, as
    ``olmoe.session_vectors`` takes them: ``tokens``, ``segment`` and
    ``position`` [R, T] int32; ``last`` [R, S] int32, each session's last
    position IN ITS STREAM, -1 where a stream holds fewer than S. Returns
    the session vectors [R * S, hidden] float32, row by row (``rms(x_L;
    embedding_norm)`` at ``last``; one at -1 is to be thrown away) and three
    counts summed over the sparse layers, as ``kimi_linear``'s: the copies of
    REAL tokens the program's busiest held expert got, those all the held
    experts got, and the layers where they overflowed the held block."""
    with jax.named_scope("embed"):
        x = weights["embed"][tokens].astype(jnp.float32)
    x, counts = _layers(weights, x, segment, position, config)
    with jax.named_scope("head"):
        out = _rms(_at_last(x, last), weights["embedding_norm"], config.norm_eps)
    return out, counts


@functools.partial(jax.jit, static_argnames=("config",))
def all_logits(weights, tokens, *, config: Lfm2Config):
    """Logits of EVERY position of ``tokens`` [B, L], every row one session,
    [B, L, vocabulary]: what the parity tests compare with the reference's
    ``forward``; serving never runs it."""
    x = weights["embed"][tokens].astype(jnp.float32)
    position = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x, _ = _layers(weights, x, None, position, config)
    out = _rms(x, weights["embedding_norm"], config.norm_eps)
    return jnp.dot(out, weights["embed"].astype(jnp.float32).T, precision=lax.Precision.HIGHEST)
