"""Sparse experts: router, grouping of tokens by expert, grouped products.

A mixture-of-experts feed-forward: the router scores every token against
every expert in float32 and a token goes to its ``k`` best, in one of two
published forms: :func:`route` (softmax over the experts, the chosen weights
as they are or, ``renormalise``, over their sum) and :func:`route_sigmoid` (sigmoid scores, chosen by score plus
a selection bias, weighed by the scores over their sum times a scale). Each
expert is a gated MLP ``down(silu(gate(x)) * up(x))`` (:func:`gated_mlp`,
which is also a shared expert and a dense feed-forward). The experts' work is
three GROUPED products: the tokens' copies are sorted by expert, and rows
``offsets[e]:offsets[e+1]`` of the sorted block meet expert ``e``'s matrix.
No token is dropped and there is no capacity factor: a group is as long as
the router made it, whatever the imbalance.

A chip may hold a SHARE of the experts its router knows: it then computes
the held experts' part of the result for the tokens routed to them, in one of
two layouts that the model's layer chooses between. ``expert_ffn(held=)``
sorts all ``T * k`` copies, the absent behind the held groups.
:func:`held_expert_ffn`, told the router's width, lays out the copies it
holds and no others, every held group from a multiple of the grouped
product's row tile, so that no tile of rows meets two experts, and sorts,
gathers and multiplies a COMPACT block of ``held_block`` rows (a static part
of the ``T * k``: twice the copies the held experts are expected to get,
half of all at a quarter of the experts; at half of them all the rows, and
what the layout saves is what the rows cost); a copy routed to an absent
expert, or a padding token's, gets no row, meets no matrix and adds nothing.
The block is a window on that layout: a routing whose held copies do not fit
it (every token may send all its ``k`` to held experts) has one of two ways
out, exact for any routing,
since no copy is dropped, and chosen by the CALLER (``overflow=``): the same
body over the next window too, under one ``lax.while_loop`` (one round but
for an overflow), or all of ``expert_ffn(held=)`` as the other branch of one
``lax.cond``. The loop costs a program by the sparse layers it executes, the
second path by the sparse bodies it compiles, so the choice is the layer's,
which knows its program's depth (the readings stand beside ``HELD_ROOM``).
No code stands in for the chips that hold the other experts or for the
exchange with them.

Scopes (``jax.named_scope``; the benchmark's per-layer metrics read them):
``router`` (the caller wraps :func:`route` in it), and inside ``experts``:
``sort`` (each copy's place by expert: an argsort, or the compact block's
running sums; the gather of the copies' rows, group sizes), ``gmm``
(the three grouped products and the gate) and ``combine`` (un-sort and the
weighted sum over a token's ``k`` experts).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import gmm

__all__ = [
    "route", "route_sigmoid", "expert_load", "grouped_matmul", "expert_ffn", "gated_mlp",
    "held_block", "held_expert_ffn",
]

# (rows, contraction, columns) of the grouped product's tiles, from the chip
# (PERF.md, PR 26): 256 rows by 1,024 columns with the contraction WHOLE (so
# that an output tile is written once) was the best of twelve at [16 k and
# 131 k, 2048] x [64, 2048, 1024] and at [.., 1024] x [64, 1024, 2048]
TILING = (256, 2048, 1024)
# A tile of rows is multiplied whole once for every group with a row in it,
# so groups of FEW rows want a shorter one, until the steps it adds cost
# more. Under this many rows a live group the tile is halved. From the chip
# (PERF.md, PR 34 and PR 31): at 8 rows a group (a denoise pass of ``sdar``,
# [1024, 2048] x 128 live groups) a pass took 16.0 ms at 256 rows, **15.3**
# at 128, 15.5 at 64, 16.0 at 32; at 64 rows a group no tile won.
SHORT_GROUP = 64
# Columns of a tile for matrices whose width ``TILING``'s 1,024 do not divide
# and that were measured: LFM2's experts are 1,792 wide (7 x 256 = 2 x 896),
# and at 1,024 the second tile of two is a quarter beyond the matrix. From the
# chip (PERF.md, PR 41; ``lfm2``'s whole program of 22 sparse layers, 8 experts
# held, ms at [1, 2048] and at [4, 2048] tokens): **45.3 / 195.5** at 896,
# 46.0 / 201.0 at 256, 45.5 / 200.3 at 1,024 (this table's default rule);
# 1,792 whole is refused by Mosaic (16.96 MB of scoped VMEM of 16). A width
# with no entry keeps the rule below, so OLMoE's and Kimi-Linear's 1,024 and
# SDAR's, kanana's and Granite's 768 (whole lanes: one tile of columns, 768
# wide) compile to the tiles they had
COLUMN_TILES = {1792: 896}
# The compact block of a chip that holds a SHARE of its router's experts
# (``held_block``): room for ``HELD_ROOM`` times the copies the held experts
# are expected to get (half of all ``T * k`` at a quarter of the experts; all
# the copies' rows at half of them or more), in row tiles of the power of two
# at or under the rows a held group is expected to have, from 8 (a vector register's sublanes: the kernel takes no less) to
# ``TILING``'s own. From the chip (PERF.md, PR 42; the half share PR 50).
# The tile and the room, by the WHOLE served program at [1, 2048] / [1, 4096]
# tokens (ms; the models' own routers, four seeded streams; "all" lays out
# all the copies, as ``expert_ffn(held=)`` does; the block's overflow as the
# other branch of a `cond`):
#   ``kimi_linear`` (64 of 256 held, 8 a token, 64 / 128 rows a group, seven
#   sparse layers): all 75.5 / 153.4; **66.4 / 151.0** at this rule (tiles of
#   64 / 128, half the copies); 65.6 / 152.9 at tiles of 128 / 256 and three
#   quarters; tiles of 128 / 256 in half the copies OVERFLOW in every layer
#   (64 groups x half a tile of padding beside 4,096 held copies): 76.3 / 161.5;
#   ``lfm2`` (8 of 32, 4 a token, 256 / 512 rows a group, 22 sparse layers):
#   all 45.2 / 100.8; **39.8 / 84.6** at this rule (tiles of 256, half the
#   copies); 40.3 / 84.9 at tiles of 128;
#   ``granite`` (36 of 72, 10 a token, 284 / 568 rows a group, ten sparse
#   layers; the overflow in rounds; with the combine's re-layout still in
#   it): all 125.6 / 258.2; three quarters of the copies in tiles of 64
#   137.0, of 128 125.3 / 263.4, of 256 **118.7 / 252.8** (the products alone
#   27.7 -> 38.1, 26.3, 19.7 ms a program: at 128 rows and fewer a visit is
#   bound by the matrix it loads); the block's SIZE moves nothing the clock
#   shows, and seven eighths of the copies overflowed in 1 layer of 10 once
#   no token was padding (a seeded router's held half gets up to 58% of a
#   layer's copies): a half share takes all the copies' rows. With them, in
#   tiles of 256, and the combine gathered copy by copy: **103.2 / 223.2**
#   (no token padding 110.7, no overflow; seven eighths again 102.9).
# The overflow, by the cells (`answered_qps` / cached `setup_s`, parent ->
# change, pairs sharing a seed): as the other branch of a `lax.cond` (twice
# the kernels a sparse layer) ``kimi_linear`` 56.5 -> 63.5 / 37.2 -> 40.7 and
# ``lfm2`` 93.9 -> 106.7 / 43.6 -> 55.9: 12 s more to load 132 kernels' worth
# of program, over the set-up's bound; as further ROUNDS of one body under a
# `lax.while_loop` (the default here) ``kimi_linear`` **57.1 -> 64.1 / 38.9 ->
# 39.8** and ``lfm2`` 93.6 -> 96.2 / 42.6 -> 47.1. The block itself is worth
# as much at 256 rows a group as at 64; what differs is the DEPTH: a loop a
# sparse layer costs a program of 22 unrolled layers most of what the block
# saves and its set-up 10%, and one of 7 nothing; a second path costs by the
# bodies that are COMPILED. With ``lfm2``'s 22 sparse layers as 7 scanned
# bodies (PERF.md, PR 47; 93.84 answers a second and 42.1 s as the parent
# had it, all the copies laid out): the `cond` **112.0 / 38.1** (44 kernels
# a program for 72), the rounds 108.6 / 39.7: a scan does not take the
# loop's carry away, 22 EXECUTIONS a program still pay it. So ``lfm2``'s layer
# names ``overflow="whole"`` and ``kimi_linear``'s, unrolled, keeps the rounds;
# ``granite``'s ten unrolled layers (PERF.md, PR 50; two seeds each, the
# parent 32.9 to 34.8 / 31.5 to 35.7): the rounds 39.92 and 39.37 / 35.8 and
# 37.5, the `cond` 39.24 and 39.41 / 43.3 (108 s where it compiled; 34.06 and
# 32.92 / 42.7 against the rounds' 34.06 and 33.59 / 36.4 in the block's first
# form): as many answers and 6 s of set-up more, so it names the rounds
HELD_ROOM = 2


def route(x, router_w, k: int, renormalise: bool = False):
    """``(weights [T, k] float32, experts [T, k] int32)`` of tokens ``x``
    [T, hidden]: softmax over ALL experts in float32 (the product at
    ``highest`` too: a choice between the k-th and the (k+1)-th expert must
    not hang on a bf16 rounding), then the top k. Their weights are the
    softmax's as they are (OLMoE's ``norm_topk_prob`` false) or,
    ``renormalise``, divided by their sum (Qwen3-MoE's and SDAR's true)."""
    logits = jnp.dot(
        x.astype(jnp.float32),
        router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


def route_sigmoid(x, router_w, bias, k: int, scale: float, eps: float = 0.0):
    """``(weights [T, k] float32, experts [T, k] int32)`` in the sigmoid
    form: ``s = sigmoid(x @ router_w)`` over ALL experts (float32, the
    product at ``highest``, as in :func:`route`); the ``k`` chosen are the
    top ``k`` of ``s + bias`` (the bias steers the choice only); their
    weights are ``s`` at the chosen over their sum plus ``eps`` (LFM2's
    published ``1e-6``; Kimi-Linear's has none), times ``scale``."""
    logits = jnp.dot(
        x.astype(jnp.float32),
        router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    # (no `+ 0.0` in Kimi-Linear's program: it compiles to what it did)
    return scale * chosen / (total + eps if eps else total), experts


def expert_load(experts, n_experts: int, counted=None):
    """How many token copies each expert got, [n_experts] int32, over the
    tokens ``counted`` [T] marks (all of them by default). A comparison and
    a sum, no scatter."""
    hits = experts[..., None] == jnp.arange(n_experts, dtype=experts.dtype)
    if counted is not None:
        hits = hits & counted[:, None, None]
    return jnp.sum(hits, axis=(0, 1), dtype=jnp.int32)


def grouped_matmul(lhs, rhs, group_sizes, out_dtype, live=None, tile=None):
    """``lhs[offsets[g]:offsets[g+1]] @ rhs[g]`` for every group ``g``:
    ``lhs`` [M, K] sorted by group, ``rhs`` [G, K, N], ``group_sizes`` [G]
    int32 summing to M (a multiple of 8); float32 accumulation, rounded to
    ``out_dtype``. An empty group costs nothing.

    On the chip the megablox Pallas kernel that jax ships (of the two
    candidates timed there it won: ``lax.ragged_dot`` took 1.3 times as
    long, PERF.md PR 26). Off the chip the kernel runs only interpreted,
    its grid through host callbacks: a served program of a test's size takes
    a fifth of a second, and the CPU rehearsal of the benchmark's cell
    answers 5 queries a second where it has to answer 32 in its 6 s
    (sandbox, PR 26). So there XLA's own ``ragged_dot`` stands in, as
    ``ops/attention.fused_attention`` takes its jnp path off the chip; the
    tests run the kernel interpreted against it, alone and through a whole
    program.

    ``live`` is how many of ``rhs``'s groups ``group_sizes`` can fill, where
    that is not all of them (every layer's experts lie stacked and one
    layer's are live): the kernel takes its tile of rows from the rows a
    live group has (``SHORT_GROUP``). ``tile`` names the tile of rows instead,
    where the caller has laid every group out from a multiple of it."""
    if jax.default_backend() != "tpu":
        out = lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32)
        return out.astype(out_dtype)
    return grouped_matmul_kernel(lhs, rhs, group_sizes, out_dtype, live=live, tile=tile)


def grouped_matmul_kernel(
    lhs, rhs, group_sizes, out_dtype, interpret: bool = False, live=None, tile=None
):
    """The kernel itself (``interpret`` is how a test runs it off the chip)."""
    rows, contraction, columns = TILING
    if tile is not None:
        rows = tile
    elif lhs.shape[0] < SHORT_GROUP * (live or rhs.shape[0]):
        rows //= 2
    # a contraction tile past the operand's own is masked on every step: it
    # took 1.5 times as long at [.., 1024] x [64, 1024, 2048]; a tile of
    # columns past the matrices' own whole lanes (experts 768 wide) is
    # multiplied and thrown away
    width = rhs.shape[2]
    tiling = (
        math.gcd(rows, lhs.shape[0]),
        min(contraction, lhs.shape[1]),
        COLUMN_TILES.get(width) or (columns if width % 128 else min(columns, width)),
    )
    return gmm(
        lhs, rhs, group_sizes, preferred_element_type=out_dtype, tiling=tiling, interpret=interpret
    )


def gated_mlp(x, gate, up, down):
    """``down(silu(gate(x)) * up(x))`` for ``x`` [T, hidden]: a shared
    expert or a dense feed-forward. Operands in the weights' type, float32
    accumulation; returns float32."""
    xs = x.astype(gate.dtype)
    g = jnp.dot(xs, gate, preferred_element_type=jnp.float32)
    u = jnp.dot(xs, up, preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(down.dtype), down, preferred_element_type=jnp.float32)


def held_block(tokens: int, k: int, count: int, n_experts: int):
    """``(rows, tile)`` of the compact block that ``count`` held experts of a
    router ``n_experts`` wide get for ``tokens`` tokens at ``k`` copies each,
    from static shapes alone: the row tile is the power of two at or under
    the rows a held group is EXPECTED to have (``tokens * k / n_experts``),
    from 8 to ``TILING``'s own, and the block holds ``HELD_ROOM`` times the
    copies the held experts are expected to get, or all the copies' rows
    where that is less (more than ``1 / HELD_ROOM`` of the experts held), a
    whole number of tiles. What the layout needs of it is the expected copies
    and a tile a held group beside them (half a tile a group is what rounding
    the groups up costs on average, the rest is the router's imbalance's):
    None where that does not fit (all or nearly all the experts held: the
    block would overflow on every routing)."""
    copies = tokens * k
    group = max(copies // n_experts, 1)
    tile = min(TILING[0], max(8, 1 << (group.bit_length() - 1)))
    expected = -(-copies * count // n_experts)
    rows = min(-(-HELD_ROOM * copies * count // (n_experts * tile)), copies // tile) * tile
    return (rows, tile) if expected + count * tile <= rows else None


def _held_layout(experts, first: int, count: int, tile: int, counted=None):
    """Where each copy of ``experts`` [T, k] lies among the held copies laid
    out by expert, every group from a multiple of ``tile``: ``(place [T, k],
    held [T, k] bool, start [count], padded [count])``. Held group ``e`` takes
    rows ``start[e]`` to ``start[e] + padded[e]``, its size rounded up to
    ``tile`` and ``start`` the running sum; a held copy's place is
    ``start[e]`` plus its rank among the group's copies in token order (an
    absent copy's means nothing). A token that ``counted`` [T] leaves out
    holds no copy. A comparison, a running sum over the tokens and sums: no
    sort."""
    hits = (experts - first)[..., None] == jnp.arange(count, dtype=experts.dtype)  # [T, k, count]
    if counted is not None:
        hits = hits & counted[:, None, None]
    each = jnp.sum(hits, axis=1, dtype=jnp.int32)  # a token's copies by held expert
    before = jnp.cumsum(each, axis=0) - each  # ... and the earlier tokens'
    padded = (before[-1] + each[-1] + tile - 1) // tile * tile
    start = jnp.cumsum(padded) - padded
    # a top-k names an expert once a token; a routing that names one twice
    # keeps both copies: the later lies behind the earlier
    earlier = jnp.tril(experts[:, :, None] == experts[:, None, :], -1)
    place = jnp.sum(jnp.where(hits, (start + before)[:, None, :], 0), axis=2) + jnp.sum(earlier, axis=2)
    return place, jnp.any(hits, axis=2), start, padded


def _group_sizes(load, groups: int, first_group):
    return lax.dynamic_update_slice(
        jnp.zeros(groups, jnp.int32), load, (jnp.asarray(first_group, jnp.int32),)
    )


def _gated_products(xs, gate, up, down, sizes, live, tile=None):
    """The three grouped products of rows ``xs`` laid out by group."""
    # gate and up leave the kernel in the operands' type: `down` takes
    # them in it anyway, and float32 would double what the gate moves
    g = grouped_matmul(xs, gate, sizes, gate.dtype, live=live, tile=tile).astype(jnp.float32)
    u = grouped_matmul(xs, up, sizes, gate.dtype, live=live, tile=tile).astype(jnp.float32)
    return grouped_matmul((jax.nn.silu(g) * u).astype(down.dtype), down, sizes, jnp.float32, live=live, tile=tile)


def expert_ffn(x, weights, experts, gate, up, down, n_experts=None, first_group=0, held=None):
    """``sum_j weights[t, j] * ffn_{experts[t, j]}(x[t])`` for tokens ``x``
    [T, hidden]: ``gate`` and ``up`` [G, hidden, width], ``down``
    [G, width, hidden], expert ``e``'s matrices at ``first_group + e``.

    ``G`` may be more than the router's ``n_experts``: every layer's experts
    stacked, ``first_group`` (traced or not) the layer's first. The kernel
    then reads the layer's matrices where they lie; a slice taken in front
    of it is a copy of all of them (0.8 GB a layer at OLMoE's widths, 2 ms).

    ``held`` ``(first, count)`` says WHICH of the router's experts the
    matrices are: experts ``first`` to ``first + count - 1``, expert ``e``'s
    at ``first_group + e - first``. The sum then runs over the copies routed
    to those; a copy routed to an absent expert is sorted behind the held
    groups, where no product reads or writes its row (the kernel leaves the
    rows past the last group as it found them), and counts as zero in the
    combine (:func:`held_expert_ffn` gives such a copy no row at all). By
    default every expert the router knows is held.

    Returns ``y`` [T, hidden] float32."""
    tokens, k = experts.shape
    groups = gate.shape[0]
    with jax.named_scope("sort"):
        flat = experts.reshape(-1)
        if held is None:
            n_experts = groups if n_experts is None else n_experts
            order = jnp.argsort(flat, stable=True)  # copies, sorted by expert
            load = expert_load(experts, n_experts)
        else:
            first, n_experts = held
            # a held expert's place among the held; the absent behind them all
            flat = jnp.where((flat >= first) & (flat < first + n_experts), flat - first, n_experts)
            order = jnp.argsort(flat, stable=True)
            load = expert_load(flat[:, None], n_experts)
        sizes = _group_sizes(load, groups, first_group)
        xs = x.astype(gate.dtype)[order // k]  # [T*k, hidden]
    with jax.named_scope("gmm"):
        out = _gated_products(xs, gate, up, down, sizes, n_experts)
    with jax.named_scope("combine"):
        # where each copy went: the inverse of the sort, by one scatter of
        # T*k integers, then a gather of rows (no scatter-add of rows)
        place = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
        out = out[place]
        if held is not None:
            # a row past the held groups was never written: it is not read as it is
            out = jnp.where((place < jnp.sum(load))[:, None], out, 0.0)
        out = out.reshape(tokens, k, -1)
        y = jnp.sum(out * weights[..., None], axis=1)
    return y


def held_expert_ffn(x, weights, experts, gate, up, down, held, first_group=0, counted=None, overflow="rounds"):
    """:func:`expert_ffn` over the share ``held`` ``(first, count, width)``
    of a router ``width`` experts wide, with only the held copies laid out:
    ``(y [T, hidden] float32, rounds)``.

    Group ``e`` lies from a multiple of the row tile, its copies in token
    order, then rows of padding up to the next multiple (they read token 0's
    row, meet the matrix and are never read back); a copy routed to an
    absent expert gets no row, nor does any copy of a token that ``counted``
    [T] bool leaves out (a stream's padding: its ``y`` is zero). The block
    that is sorted, gathered and multiplied has :func:`held_block` rows, not
    ``T * k``. It is a WINDOW on that layout: a routing whose groups take
    more rows than it has (one that sends every copy to held experts does)
    runs the same body again over the next window, and again, under one
    ``lax.while_loop``, until every held copy has met its matrix. ``rounds``
    (int32) says how many it took: ONE but for an overflow, none where no
    copy is held. A window's edge is a tile's edge, so it may cut a group and
    never a tile; a token whose copies lie in two windows has them summed
    window by window, not in the order of its ``k`` (float32: the last bit
    may differ from :func:`expert_ffn`'s). Where :func:`held_block` has no
    block (all or nearly all the experts held) this is ``expert_ffn(held=)``,
    in one round.

    ``overflow`` is the CALLER's choice of the way out, by the depth of its
    program (the readings stand beside ``HELD_ROOM``): ``"rounds"``, the loop
    above; ``"whole"``, the first window straight-line and, for a routing
    that does not fit it, all of ``expert_ffn(held=)`` as the other branch of
    one ``lax.cond`` (``rounds`` 2 then): twice the kernels where it is
    traced, nothing where it runs, and a token's ``k`` copies summed in
    their order whichever branch ran."""
    if overflow not in ("rounds", "whole"):
        raise ValueError(f"overflow={overflow!r}: 'rounds' or 'whole'")
    first, count, width = held
    block = held_block(*experts.shape, count, width)

    def whole():
        masked = weights if counted is None else jnp.where(counted[:, None], weights, 0.0)
        return expert_ffn(x, masked, experts, gate, up, down, first_group=first_group, held=(first, count))

    if block is None:
        return whole(), jnp.int32(1)
    (tokens, k), (rows, tile) = experts.shape, block
    # a float32 tile is 8 rows: ``[T * k, hidden]`` seen as ``[T, k, hidden]``
    # is the same bytes where 8 divides ``k`` or ``k`` divides 8 (the tile
    # shrinks to it) and a padded COPY of them all otherwise (10 rows lie in
    # 16: ``granite``'s combine took 3.7 ms a layer with it and the sum over
    # it, 2.0 without: PERF.md, PR 50), which the combine then goes around
    padded_k = bool(k % 8 and 8 % k)
    with jax.named_scope("sort"):
        place, at_home, start, padded = _held_layout(experts, first, count, tile, counted)
        token = jnp.broadcast_to(jnp.arange(tokens, dtype=jnp.int32)[:, None], (tokens, k)).reshape(-1)
        xs_of = x.astype(gate.dtype)

    def window(carry):
        low, y = carry
        with jax.named_scope("sort"):
            # the copies whose rows this window holds, and the token each of
            # its rows reads: a scatter of T*k integers (a copy of another
            # window's or of an absent expert falls off the end)
            local = place - low
            here = at_home & (local >= 0) & (local < rows)
            source = jnp.zeros(rows, jnp.int32).at[jnp.where(here, local, rows).reshape(-1)].set(token, mode="drop")
            # what of each group lies in the window: whole tiles, in order
            inside = jnp.clip(start + padded, low, low + rows) - jnp.clip(start, low, low + rows)
            sizes = _group_sizes(inside, gate.shape[0], first_group)
            xs = xs_of[source]  # [rows, hidden]
        with jax.named_scope("gmm"):
            out = _gated_products(xs, gate, up, down, sizes, count, tile)
        with jax.named_scope("combine"):
            # a copy reads its row; one that has none here counts as zero
            # (the select rides in the sum's own pass over the gathered rows)
            # (where ``k`` lies in no tile the rows are gathered copy by copy,
            # [k, T, hidden], and summed over the MAJOR axis: no re-layout)
            row, mine, weight = (a.T if padded_k else a for a in (jnp.where(here, local, 0), here, weights))
            out = out[row.reshape(-1)]
            out = jnp.where(mine.reshape(-1)[:, None], out, 0.0).reshape(*row.shape, -1)
            return low + rows, y + jnp.sum(out * weight[..., None], axis=0 if padded_k else 1)

    total = jnp.sum(padded)
    y = jnp.zeros((tokens, x.shape[1]), jnp.float32)
    if overflow == "whole":
        fits = total <= rows
        y = lax.cond(fits, lambda: window((jnp.int32(0), y))[1], whole)
        return y, jnp.where(fits, 1, 2).astype(jnp.int32)
    low, y = lax.while_loop(lambda carry: carry[0] < total, window, (jnp.int32(0), y))
    return y, low // rows
