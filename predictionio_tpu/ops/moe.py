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

A chip may hold a SHARE of the experts its router knows (``held``): it then
computes the held experts' part of the result for the tokens routed to them.
The copies routed to the absent experts are sorted behind the held groups,
meet no matrix and add nothing; no code stands in for the chips that hold
the others or for the exchange with them.

Scopes (``jax.named_scope``; the benchmark's per-layer metrics read them):
``router`` (the caller wraps :func:`route` in it), and inside ``experts``:
``sort`` (argsort by expert, gather of the copies, group sizes), ``gmm``
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
# SDAR's 768 compile to the tiles they had
COLUMN_TILES = {1792: 896}


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


def grouped_matmul(lhs, rhs, group_sizes, out_dtype, live=None):
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
    live group has (``SHORT_GROUP``)."""
    if jax.default_backend() != "tpu":
        out = lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32)
        return out.astype(out_dtype)
    return grouped_matmul_kernel(lhs, rhs, group_sizes, out_dtype, live=live)


def grouped_matmul_kernel(lhs, rhs, group_sizes, out_dtype, interpret: bool = False, live=None):
    """The kernel itself (``interpret`` is how a test runs it off the chip)."""
    rows, contraction, columns = TILING
    if lhs.shape[0] < SHORT_GROUP * (live or rhs.shape[0]):
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
    combine. By default every expert the router knows is held.

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
        sizes = lax.dynamic_update_slice(
            jnp.zeros(groups, jnp.int32),
            load,
            (jnp.asarray(first_group, jnp.int32),),
        )
        xs = x.astype(gate.dtype)[order // k]  # [T*k, hidden]
    with jax.named_scope("gmm"):
        # gate and up leave the kernel in the operands' type: `down` takes
        # them in it anyway, and float32 would double what the gate moves
        g = grouped_matmul(xs, gate, sizes, gate.dtype, live=n_experts).astype(jnp.float32)
        u = grouped_matmul(xs, up, sizes, gate.dtype, live=n_experts).astype(jnp.float32)
        out = grouped_matmul((jax.nn.silu(g) * u).astype(down.dtype), down, sizes, jnp.float32, live=n_experts)
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
