"""Attention kernels: fused single-chip attention (pallas) and ring
attention for sequence/context parallelism.

The reference has no sequence models (SURVEY.md section 5 — nearest analog
is the e2 MarkovChain), but long-context support is first-class in this
framework: a sequence encoder attached to any engine (see
``models/twotower``'s history encoder) must scale past single-chip memory.

Design:
  - ``ring_attention``: Q/K/V sharded over a named mesh axis (``sp``) along
    the sequence dimension. Each of the P ring steps computes one block of
    attention with a numerically-stable online softmax (flash-attention
    accumulation) and rotates the K/V shard to the next device with
    ``lax.ppermute`` — bandwidth rides ICI neighbor links, compute overlaps
    the permute under XLA's async scheduling. Supports causal masking with
    global position offsets.
  - ``fused_attention``: a pallas TPU kernel for the within-block attention
    (grid over batch x heads, K/V streamed through VMEM); falls back to the
    jnp reference path off-TPU. Used by ring_attention for its local block
    when running on TPU. With ``segment=`` several sequences share one row
    (a packed stream, ``models/sequential``): a key is seen only from inside
    its own segment.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _check_seq_divisible(L: int, axis: str, axis_size: int) -> None:
    if L % axis_size:
        raise ValueError(
            f"sequence length {L} not divisible by {axis}={axis_size}"
        )


# ---------------------------------------------------------------------------
# Reference (jnp) attention + online-softmax block update
# ---------------------------------------------------------------------------


def attention_reference(
    q: jnp.ndarray,  # [B, H, Lq, D]
    k: jnp.ndarray,  # [B, H, Lk, D]
    v: jnp.ndarray,  # [B, H, Lk, D]
    causal: bool = False,
    q_offset: int = 0,
    k_offset: int = 0,
    segment=None,  # [B, L] int32 (Lq == Lk == L), or ([B, Lq], [B, Lk])
    block: int | None = None,
) -> jnp.ndarray:
    """``segment``, where given, packs several sequences into a row: a key
    is seen where it has the query's id and the id is not negative (a
    negative id is padding: it sees nothing, is seen by none and comes out
    as 0); a pair gives the queries' ids and the keys' apart, for queries of
    one length against keys of another. ``k`` and ``v`` may carry FEWER
    heads than ``q`` (grouped queries): query head ``h`` reads key/value
    head ``h // (H / Hkv)``. ``block`` makes ``causal`` block-causal: a key
    is seen where ``key index // block <= query index // block`` (two-way
    inside a block of ``block``, causal across blocks)."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qi = jnp.arange(q.shape[2])[:, None] + q_offset
        ki = jnp.arange(k.shape[2])[None, :] + k_offset
        if block:
            qi, ki = qi // block, ki // block
        scores = jnp.where(qi >= ki, scores, -jnp.inf)
    if segment is not None:
        seg_q, seg_k = _segment_ids(segment)
        scores = jnp.where((seg_q == seg_k)[:, None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    # rows with no visible keys produce NaN from softmax(-inf row): zero
    # them. Not jnp.nan_to_num: fused into the einsum below it sends
    # libtpu 0.0.34's compiler into unbounded recursion (SIGSEGV, stack
    # overflow) at every shape tried; softmax yields no infinities, so the
    # NaN case is the only one there is
    weights = jnp.where(jnp.isnan(weights), 0.0, weights)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def _online_block(q, k, v, acc, row_max, row_sum, mask):
    """One flash-attention accumulation step.

    q [B,H,Lq,D]; k,v [B,H,Lk,D]; acc [B,H,Lq,D]; row_max/row_sum [B,H,Lq];
    mask [Lq, Lk] boolean (True = attend) or None.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    blk_max = jnp.max(scores, axis=-1)  # [B,H,Lq]
    new_max = jnp.maximum(row_max, blk_max)
    # guard fully-masked blocks: exp(-inf - -inf) -> use safe max
    safe_max = jnp.where(jnp.isneginf(new_max), 0.0, new_max)
    correction = jnp.exp(row_max - safe_max)
    correction = jnp.where(jnp.isneginf(row_max), 0.0, correction)
    p = jnp.exp(scores - safe_max[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    acc = acc * correction[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    row_sum = row_sum * correction + jnp.sum(p, axis=-1)
    return acc, new_max, row_sum


# ---------------------------------------------------------------------------
# Ring attention (sequence parallelism over a mesh axis)
# ---------------------------------------------------------------------------


def ring_attention(
    q: jnp.ndarray,  # [B, H, L, D] — L is the GLOBAL sequence length
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    batch_axis: str | None = None,
) -> jnp.ndarray:
    """Full attention over sequences sharded on ``axis``.

    Inputs/outputs are global arrays; under jit the sequence dimension is
    sharded over the axis and each device runs P ring steps, exchanging K/V
    shards with its neighbor. Requires L % axis_size == 0.

    ``batch_axis`` composes sequence parallelism with data parallelism:
    the batch dimension shards over that mesh axis (dp x sp over one 2-D
    mesh), so a dp-sharded caller (e.g. a sharded train step) does not
    force GSPMD to all-gather the batch around the shard_map boundary.
    """
    axis_size = mesh.shape[axis]
    L = q.shape[2]
    _check_seq_divisible(L, axis, axis_size)
    l_local = L // axis_size

    def local_fn(q_blk, k_blk, v_blk):
        # q_blk etc: [B, H, l_local, D] — this device's shard
        my_idx = lax.axis_index(axis)
        q_off = my_idx * l_local
        B, H, Lq, D = q_blk.shape
        # initial carries must share the input's varying-axes type under
        # shard_map's vma checking, so derive them from q_blk
        zero_rows = jnp.sum(q_blk.astype(jnp.float32) * 0.0, axis=-1)  # [B,H,Lq]
        acc0 = q_blk.astype(jnp.float32) * 0.0
        max0 = zero_rows - jnp.inf
        sum0 = zero_rows
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

        def step(i, carry):
            k_cur, v_cur, acc, row_max, row_sum = carry
            # the K/V block currently held came from device (my_idx - i)
            src = (my_idx - i) % axis_size
            k_off = src * l_local
            if causal:
                qi = jnp.arange(Lq)[:, None] + q_off
                ki = jnp.arange(Lq)[None, :] + k_off
                mask = qi >= ki
            else:
                mask = None
            acc, row_max, row_sum = _online_block(
                q_blk.astype(jnp.float32),
                k_cur.astype(jnp.float32),
                v_cur.astype(jnp.float32),
                acc,
                row_max,
                row_sum,
                mask,
            )
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            return k_nxt, v_nxt, acc, row_max, row_sum

        _, _, acc, row_max, row_sum = lax.fori_loop(
            0, axis_size, step, (k_blk, v_blk, acc0, max0, sum0)
        )
        safe_sum = jnp.where(row_sum == 0.0, 1.0, row_sum)
        return (acc / safe_sum[..., None]).astype(q_blk.dtype)

    spec = P(batch_axis, None, axis, None)
    sharded = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return sharded(q, k, v)


def ring_attention_sharded(
    q,
    k,
    v,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    batch_axis: str | None = None,
):
    """jit-wrapped ring attention with explicit input shardings."""
    sharding = NamedSharding(mesh, P(batch_axis, None, axis, None))
    fn = jax.jit(
        functools.partial(
            ring_attention, mesh=mesh, axis=axis, causal=causal,
            batch_axis=batch_axis,
        ),
        in_shardings=(sharding, sharding, sharding),
        out_shardings=sharding,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses attention (all-to-all sequence parallelism)
# ---------------------------------------------------------------------------


def ulysses_attention(
    q: jnp.ndarray,  # [B, H, L, D] — L is the GLOBAL sequence length
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    batch_axis: str | None = None,
) -> jnp.ndarray:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses scheme): inputs
    arrive sequence-sharded on ``axis``; one ``all_to_all`` re-shards them
    head-wise with the FULL sequence per device, attention runs locally with
    no inner communication, and a second ``all_to_all`` restores sequence
    sharding. Communication: 2 all-to-alls of activations total (vs. P-1
    K/V ``ppermute`` hops for ring attention) — the better schedule when
    heads are plentiful and the sequence shard still fits one device's
    memory as [H/P, L]. Requires H % axis_size == 0 and L % axis_size == 0.
    """
    axis_size = mesh.shape[axis]
    _, H, L, _ = q.shape
    _check_seq_divisible(L, axis, axis_size)
    if H % axis_size:
        raise ValueError(f"head count {H} not divisible by {axis}={axis_size}")

    def local_fn(q_blk, k_blk, v_blk):
        # [B, H, l_local, D] -> [B, H/P, L, D]: split heads, gather sequence
        def to_heads(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

        def to_seq(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

        q_h, k_h, v_h = to_heads(q_blk), to_heads(k_blk), to_heads(v_blk)
        # full sequence is present locally: plain causal offsets (0, 0).
        # fused_attention keeps the local block flash-style (no dense
        # [L, L] score tensor on TPU) — the point of sequence parallelism
        out = fused_attention(q_h, k_h, v_h, causal=causal)
        return to_seq(out)

    # batch_axis: dp x sp composition — see ring_attention
    spec = P(batch_axis, None, axis, None)
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call outputs carry no varying-axes metadata, so the
        # checker is off for a body that invokes pallas kernels
        check_vma=False,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Pallas fused attention (TPU single-chip hot path)
# ---------------------------------------------------------------------------


def _segment_ids(segment):
    """``(ids as the queries carry them [B, L, 1], as the keys do [B, 1, L])``:
    padding is -1 on one side and -2 on the other, so that equality alone
    is the mask. ``segment`` is one array for both, or the pair."""
    if not isinstance(segment, tuple):
        segment = segment.astype(jnp.int32)
        pad = segment < 0
        return jnp.where(pad, -1, segment)[:, :, None], jnp.where(pad, -2, segment)[:, None, :]
    of_q, of_k = (ids.astype(jnp.int32) for ids in segment)
    return jnp.where(of_q < 0, -1, of_q)[:, :, None], jnp.where(of_k < 0, -2, of_k)[:, None, :]


def _needed_blocks(segment, block_q: int, block_k: int):
    """For a PAIR of ids (queries [B, Lq], keys [B, Lk]; no order among
    them is known): ``(needed, fetch)``, both [B, Lq / block_q, Lk / block_k]
    int32. ``needed`` is 0 where no query of the block of queries can see a
    key of the block of keys (the ranges of their ids do not meet: exact
    where ids lie sorted, never too few elsewhere, and the mask itself is
    applied inside). ``fetch`` is the block of keys a step should hold: its
    own where needed, else the nearest needed one before it (the first
    needed one, for the steps in front of it), so that a step that computes
    nothing moves nothing either."""
    of_q, of_k = segment
    rows = of_q.shape[0]
    top = jnp.iinfo(jnp.int32).max
    of_q = of_q.astype(jnp.int32).reshape(rows, -1, block_q)
    of_k = of_k.astype(jnp.int32).reshape(rows, -1, block_k)
    q_low, q_high = jnp.min(jnp.where(of_q < 0, top, of_q), axis=2), jnp.max(of_q, axis=2)
    k_low, k_high = jnp.min(jnp.where(of_k < 0, top, of_k), axis=2), jnp.max(of_k, axis=2)
    needed = (q_low[:, :, None] <= k_high[:, None, :]) & (k_low[:, None, :] <= q_high[:, :, None])
    at = jnp.arange(of_k.shape[1], dtype=jnp.int32)
    before = lax.cummax(jnp.where(needed, at, -1), axis=2)
    first = jnp.argmax(needed, axis=2).astype(jnp.int32)[:, :, None]
    return needed.astype(jnp.int32), jnp.where(before < 0, first, before)


def _first_keys(segment, block_q: int):
    """[B * L / block_q] int32: the first key any query of a block of
    ``block_q`` queries sees, L where none sees any. A segment's positions
    are CONTIGUOUS and no two segments share an id: a query sees back to
    where its run of equal ids began."""
    rows, length = segment.shape
    at = jnp.arange(length, dtype=jnp.int32)[None, :]
    begins = jnp.concatenate(
        [jnp.ones((rows, 1), bool), segment[:, 1:] != segment[:, :-1]], axis=1
    )
    began = lax.cummax(jnp.where(begins, at, 0), axis=1)
    began = jnp.where(segment >= 0, began, length)
    return jnp.min(began.reshape(rows, length // block_q, block_q), axis=2).reshape(-1)


# queries and keys a step of the packed path off the chip
OFF_CHIP_BLOCK = 128


def _segmented_attention_blocked(q, k, v, causal: bool, segment, tile: int, block=None):
    """Attention over packed rows OFF the chip, by the flash kernel's own
    schedule and arithmetic in ``jax.numpy``: tiles of ``tile`` queries
    against tiles of keys under an online softmax, float32 accumulation of
    the operands as they come, and a tile of keys that ends before the
    first key any query of the tile sees (in any row), or lies above the
    diagonal (or, for a pair of ids, whose ids no query of the tile
    carries: ``_needed_blocks``), is not computed. The dense
    ``attention_reference`` does ``L * L`` work a head whatever the segments
    are: at a stream of 2,048 tokens that made the CPU rehearsal of a
    serving cell thirty times as slow as its sessions are long."""
    rows, heads, length, _ = q.shape
    group = heads // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    n, n_k, scale = length // tile, k.shape[2] // tile, 1.0 / math.sqrt(q.shape[-1])
    seg_q, seg_k = _segment_ids(segment)
    if isinstance(segment, tuple):
        wanted = jnp.any(_needed_blocks(segment, tile, tile)[0] > 0, axis=0)  # [n, n_k]
    else:
        first = jnp.min(_first_keys(segment, tile).reshape(rows, n), axis=0)
        wanted = (jnp.arange(n_k)[None, :] + 1) * tile > first[:, None]
    within = jnp.arange(tile)

    def block_of(x, i, axis):
        return lax.dynamic_slice_in_dim(x, i * tile, tile, axis)

    def queries(i):
        q_i, ids_i = block_of(q, i, 2).astype(jnp.float32), block_of(seg_q, i, 1)

        def keys(j, carry):
            def attend(carry):
                acc, top, total = carry
                scores = jnp.einsum("bhqd,bhkd->bhqk", q_i, block_of(k, j, 2).astype(jnp.float32)) * scale
                seen = ids_i == block_of(seg_k, j, 2)  # [B, tile, tile]
                if causal:
                    qi, ki = i * tile + within[:, None], j * tile + within[None, :]
                    seen = seen & ((qi // block >= ki // block) if block else (qi >= ki))
                scores = jnp.where(seen[:, None], scores, -jnp.inf)
                new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
                safe = jnp.where(jnp.isneginf(new_top), 0.0, new_top)
                p = jnp.exp(scores - safe[..., None])
                shrink = jnp.exp(top - safe)
                acc = acc * shrink[..., None] + jnp.einsum(
                    "bhqk,bhkd->bhqd", p, block_of(v, j, 2).astype(jnp.float32)
                )
                return acc, new_top, total * shrink + jnp.sum(p, axis=-1)

            needed = wanted[i, j]
            return lax.cond(needed & (j <= i) if causal else needed, attend, lambda c: c, carry)

        acc = jnp.zeros((rows, heads, tile, v.shape[-1]), jnp.float32)
        total = jnp.zeros((rows, heads, tile), jnp.float32)
        acc, _, total = lax.fori_loop(0, n_k, keys, (acc, total - jnp.inf, total))
        return acc / jnp.where(total == 0.0, 1.0, total)[..., None]

    out = lax.map(queries, jnp.arange(n))  # [n, B, H, tile, Dv]
    return jnp.moveaxis(out, 0, 2).reshape(rows, heads, length, v.shape[-1]).astype(q.dtype)


def _best_block(L: int) -> int:
    """Largest of 1024/512/256 dividing L. A round-4 sweep on a v5e at
    B4 H8 D64 causal measured (block_q, block_k) = (1024, 1024) fastest at
    every L it divides: L=2048 0.41ms vs 0.53ms for 512x512 (and 0.58ms
    for the XLA dense reference); L=4096 1.74ms vs 2.75ms (XLA reference
    9.04ms — the [L, L] score materialization falls off a cliff). Bigger
    tiles amortize the online-softmax rescale and keep the MXU on longer
    contractions; [1024, 1024] f32 scores + accumulators still fit VMEM.

    Over a PACKED stream (``segment=``; 26 streams of four batches of 32
    sessions drawn as the benchmark's cells draw them, five sessions a
    stream; my chip run, PR 33) the same tile wins although smaller ones
    skip more blocks: 16 heads of 128 at L 2,048 **0.241** ms for 1024,
    0.256 for 512, 0.501 for 256, 1.30 for 128 (0.287 with no segment); at
    4,096 **0.790** / 0.965 / 2.17 / 5.72 (0.849); keys of 192 and values
    of 128 over 32 heads at 2,048 0.612 / 0.566 / 0.720 / 1.60 and at
    4,096 **2.24** / 2.84 / 5.17 / 14.1; mixed (512, 256), (256, 512) and
    (1024, 512) lie between. OLMoE's whole program at 2,048 / 4,096 tokens:
    36.1 / 66.0 ms with 1024, 35.9 / 66.1 with 512, 36.6 / 71.6 with 256.
    So a packed row takes this function's answer like any other."""
    for b in (1024, 512, 256):
        if L % b == 0:
            return b
    return L


# The most a tile of queries and a tile of keys hold under a PAIR of ids
# (``fused_attention(segment=(ids of queries, ids of keys))``): there a tile
# of keys costs a tile of queries its whole product however few of its rows
# carry the keys' ids, so fewer rows a tile waste less and skip more. A
# denoise pass of the sequential engine's ``sdar`` (1,024 query rows of 32
# sessions against 32,768 slots, six layers, 4 heads; the whole pass on the
# chip, PR 34): 16.0 ms at (1024, 1024), **14.8** at (256, 1024), 14.9 at
# (128, 1024), 15.2 at (512, 1024), 15.1 at (256, 512), 16.4 at (1024, 512).
PAIR_TILES = (256, 1024)


def _flash_attention_pallas(
    q, k, v, causal: bool, interpret: bool, block_q: int = 1024, block_k: int = 1024,
    segment=None, block=None, value_width=None,
):
    """Tiled flash-attention pallas kernel: grid (B*H, Lq/bq, Lk/bk), online
    softmax carried across the (sequential, innermost) K-block grid axis in
    VMEM scratch. The single-block kernel below materializes the full
    [Lq, Lk] score matrix in VMEM, which blows the ~16MB scoped-VMEM limit
    at L=2048 (first observed on real hardware in the round-3 bench — the
    kernel had only ever run in interpret mode before); this one peaks at
    [bq, bk] scores + [bq, D] accumulators regardless of L.

    ``segment`` [B, L] (``attention_reference``'s): the ids ride in as two
    more blocked inputs and join the mask, and a K block that ends before
    the first key any query of the Q block sees (``_first_keys``, prefetched
    as scalars) is skipped as the blocks above the diagonal are. A PAIR of
    ids (queries of one length against keys of another, not causal) brings
    ``_needed_blocks`` instead: a K block no query of the Q block can see is
    neither computed nor fetched (its step holds the nearest needed block).

    ``k`` and ``v`` of fewer heads than ``q`` (grouped queries) are read
    where they lie: the index map sends query head ``h`` to key/value head
    ``h // group``. ``block`` turns the causal mask block-causal; the tiles
    are whole blocks, so the diagonal's skipping stands.

    ``value_width`` (``v`` None): the values are the first ``value_width``
    columns of the keys, cut out of the tile of keys a step already holds;
    no second operand is read."""
    import math as _math

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Lq, D = q.shape
    Lk, Dv = k.shape[2], value_width or v.shape[3]
    group = H // k.shape[1]
    bq, bk = min(block_q, Lq), min(block_k, Lk)
    assert Lq % bq == 0 and Lk % bk == 0, "flash path requires divisible blocks"
    assert not block or bq % block == 0, "a tile of queries holds whole blocks"
    nq, nk = Lq // bq, Lk // bk
    scale = 1.0 / _math.sqrt(D)
    paired = isinstance(segment, tuple)

    def kernel(*refs):
        if value_width:
            # the keys stand in for the operand the values would have been
            at = 1 + (0 if segment is None else 2 if paired else 1)
            refs = refs[: at + 1] + (None,) + refs[at + 1 :]
        if segment is None:
            q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        elif paired:
            needed_ref, _, q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, acc_ref, m_ref, l_ref = refs
        else:
            first_ref, q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, acc_ref, m_ref, l_ref = refs
        # program ids hoisted out of the pl.when bodies: the interpret-mode
        # lowering can't evaluate program_id inside a nested cond
        qi_blk = pl.program_id(1)
        kj = pl.program_id(2)
        if paired:
            is_needed = needed_ref[((pl.program_id(0) // H) * nq + qi_blk) * nk + kj] > 0
        elif segment is not None:
            first_key = first_ref[(pl.program_id(0) // H) * nq + qi_blk]

        @pl.when(kj == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        def compute():
            # bf16 multiplies, f32 accumulation: the MXU's native contract
            # and the flash-attention standard — HIGHEST (3-pass f32)
            # measured ~6x slower on a v5e for ~1e-2 output delta that the
            # softmax re-normalization mostly washes out anyway
            s = (
                jnp.dot(
                    q_ref[0].astype(jnp.bfloat16),
                    k_ref[0].astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            if causal:
                qi = qi_blk * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                ki = kj * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                if block:
                    # the last position of the query's block: every key up to it is seen
                    qi = qi // block * block + (block - 1)
                s = jnp.where(qi >= ki, s, -jnp.inf)
            if segment is not None:
                s = jnp.where(sq_ref[0] == sk_ref[0], s, -jnp.inf)
            m_prev = m_ref[...]  # [bq, 1]
            m_blk = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_blk)
            safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - safe))
            p = jnp.exp(s - safe)
            p = jnp.where(jnp.isneginf(s), 0.0, p)
            acc_ref[...] = acc_ref[...] * corr + jnp.dot(
                p.astype(jnp.bfloat16),
                (k_ref[0][:, :Dv] if value_width else v_ref[0]).astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[...] = m_new

        if causal:
            # skip K blocks lying entirely above the diagonal: they are
            # fully masked and would only burn MXU cycles (~2x at nq == nk)
            needed = kj * bk <= (qi_blk + 1) * bq - 1
            if segment is not None:
                needed = needed & ((kj + 1) * bk > first_key)

            @pl.when(needed)
            def _():
                compute()
        elif paired:
            pl.when(is_needed)(compute)
        elif segment is not None:
            pl.when((kj + 1) * bk > first_key)(compute)
        else:
            compute()

        @pl.when(kj == nk - 1)
        def _finish():
            denom = l_ref[...]
            denom = jnp.where(denom == 0.0, 1.0, denom)
            o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)

    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * H // group, Lk, D)

    def head_of(b):
        # (row b of `qr` is batch b // H, head b % H: its keys are row
        # b // group of `kr`)
        return b if group == 1 else b // group

    def keys_at(b, i, j, *scalars):
        # a pair's steps hold the block `_needed_blocks` says; the others'
        # index maps take the prefetched scalars of a packed row behind the
        # grid's indices and do not look at them
        return scalars[1][((b // H) * nq + i) * nk + j] if paired else j

    grid = dict(
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, *s: (head_of(b), keys_at(b, i, j, *s), 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j, *s: (head_of(b), keys_at(b, i, j, *s), 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )
    if value_width:
        operands, grid["in_specs"] = (qr, kr), grid["in_specs"][:2]
    else:
        operands = (qr, kr, v.reshape(B * H // group, Lk, Dv))
    if segment is not None:
        grid["in_specs"] += [
            pl.BlockSpec((1, bq, 1), lambda b, i, j, *_: (b // H, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j, *s: (b // H, 0, keys_at(b, i, j, *s))),
        ]
        scalars = (
            tuple(a.reshape(-1) for a in _needed_blocks(segment, bq, bk))
            if paired else (_first_keys(segment, bq),)
        )
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=len(scalars), **grid))
        operands = (*scalars, *operands, *_segment_ids(segment))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, Dv), q.dtype),
        interpret=interpret,
        **grid,
    )(*operands)
    return out.reshape(B, H, Lq, Dv)


def _fused_attention_pallas(
    q, k, v, causal: bool, interpret: bool, segment=None, block=None, value_width=None
):
    from jax.experimental import pallas as pl

    B, H, Lq, D = q.shape
    Lk, Dv = k.shape[2], value_width or v.shape[3]
    group = H // k.shape[1]

    def kernel(q_ref, k_ref, *rest):
        o_ref = rest[-1]
        qb = q_ref[0]  # [Lq, D]
        kb = k_ref[0]
        # (`value_width`: the values are the keys' first columns, read once)
        vb, rest = (kb[:, :Dv], rest) if value_width else (rest[0][0], rest[1:])
        scale = 1.0 / math.sqrt(D)
        # bf16 multiply / f32 accumulate — see _flash_attention_pallas
        scores = (
            jnp.dot(
                qb.astype(jnp.bfloat16),
                kb.astype(jnp.bfloat16).T,
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if causal:
            qi = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
            ki = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
            if block:
                qi = qi // block * block + (block - 1)
            scores = jnp.where(qi >= ki, scores, -jnp.inf)
        if segment is not None:
            sq_ref, sk_ref = rest[:2]
            scores = jnp.where(sq_ref[0] == sk_ref[0], scores, -jnp.inf)
        m = jnp.max(scores, axis=-1, keepdims=True)
        if segment is not None:
            # a padding row sees no key: its maximum is -inf and its sum 0
            m = jnp.where(jnp.isneginf(m), 0.0, m)
        p = jnp.exp(scores - m)
        out = jnp.dot(
            p.astype(jnp.bfloat16),
            vb.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        denom = jnp.sum(p, axis=-1, keepdims=True)
        if segment is not None:
            denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (out / denom).astype(o_ref.dtype)

    grid = (B * H,)
    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * H // group, Lk, D)

    def head_of(i):
        return i if group == 1 else i // group

    operands, in_specs = [qr, kr], [
        pl.BlockSpec((1, Lq, D), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, Lk, D), lambda i: (head_of(i), 0, 0)),
    ]
    if not value_width:
        operands.append(v.reshape(B * H // group, Lk, Dv))
        in_specs.append(pl.BlockSpec((1, Lk, Dv), lambda i: (head_of(i), 0, 0)))
    if segment is not None:
        operands += _segment_ids(segment)
        in_specs += [
            pl.BlockSpec((1, Lq, 1), lambda i: (i // H, 0, 0)),
            pl.BlockSpec((1, 1, Lk), lambda i: (i // H, 0, 0)),
        ]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, Dv), q.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Lq, Dv), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, H, Lq, Dv)


def fused_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    force_pallas: bool = False,
    segment=None,
    block: int | None = None,
    value_width: int | None = None,
) -> jnp.ndarray:
    """Single-device attention over ``q`` [B, H, Lq, D], ``k`` [B, Hkv, Lk, D]
    and ``v`` [B, Hkv, Lk, Dv]: queries and keys share a head width, the
    values may have another (latent attention expanded for a prefill: 192
    and 128; ``v`` is never padded to ``D``), the output is [B, H, Lq, Dv]
    and the scores are scaled by ``D ** -0.5``. ``Hkv`` is ``H`` or divides
    it (GROUPED queries: query head ``h`` reads key/value head
    ``h // (H / Hkv)``; the keys and values are read where they lie, never
    repeated in memory). On TPU: pallas kernel — the single-block
    variant when the whole [Lq, Lk] score tile fits VMEM comfortably, the
    tiled flash variant for long sequences. Elsewhere: the jnp reference
    path (``force_pallas`` runs the kernels in interpret mode, which is
    how the tests exercise them off-chip; on the chip the kernels are
    always compiled). Platform is read from ``jax.default_backend()`` so
    the choice also works on tracers (e.g. inside shard_map).

    ``segment`` [B, L] int32 (Lq == Lk == L) packs several sequences into a
    row: a key is seen where it carries the query's id (and, under
    ``causal``, does not follow it). A segment's positions are contiguous
    and its id its own; a negative id is padding, which sees nothing, is
    seen by none and comes out as 0. Without it every path is what it was.
    A PAIR ``(ids of the queries [B, Lq], ids of the keys [B, Lk])`` sets
    queries of one length against keys of another (a batch's block
    positions against its cached keys): a key is seen where it carries the
    query's id, nothing is known of their order (``causal`` is refused),
    and blocks of keys whose ids no query of a block carries cost nothing.

    ``block`` (with ``causal``) makes the mask BLOCK-causal: a key is seen
    where ``key index // block <= query index // block``, two-way inside a
    block of ``block`` positions and causal across blocks. Under ``segment``
    a segment starts on a multiple of ``block``, so that the index's blocks
    are the segment's own.

    ``value_width`` (with ``v`` None): the VALUES are the keys' own first
    ``value_width`` columns (latent attention ABSORBED for a decoding step:
    one key/value head of 512 + 64, the latent and the rotary key, whose
    first 512 are what the probabilities sum). The kernels cut them out of
    the tile of keys they hold, so the cache is read once a step.

    Limit of the kernel path: a sequence whose score tile is past the
    single-block budget (Lq * Lk >= 2**20, i.e. L >= 1024 square) must be
    a multiple of 256 in both lengths, or the call raises ValueError."""
    on_tpu = jax.default_backend() == "tpu"
    Lq, Lk = q.shape[2], k.shape[2]
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"fused_attention: {q.shape[1]} query heads over {k.shape[1]} key/value heads")
    if block and not causal:
        raise ValueError("fused_attention: `block` shapes the causal mask; give `causal=True`")
    if causal and (isinstance(segment, tuple) or (segment is not None and Lq != Lk)):
        raise ValueError("fused_attention: ids given apart carry no order: `causal` is refused")
    if (v is None) != bool(value_width):
        raise ValueError("fused_attention: `value_width` stands in for `v`; give one of them")
    if not (on_tpu or force_pallas):
        if value_width:
            v = k[..., :value_width]
        if segment is not None and Lq % OFF_CHIP_BLOCK == 0 and Lk % OFF_CHIP_BLOCK == 0:
            return _segmented_attention_blocked(q, k, v, causal, segment, OFF_CHIP_BLOCK, block)
        return attention_reference(q, k, v, causal=causal, segment=segment, block=block)
    # single-block kernel holds the [Lq, Lk] f32 score tile in VMEM
    # (strict <: a 4MiB tile — L=1024 square — already takes the flash
    # path, which the interpret-mode routing test pins)
    single_block = Lq * Lk * 4 < 4 * 1024 * 1024
    if not single_block and (Lq % 256 or Lk % 256):
        # past the single-block budget the flash kernel needs divisible
        # tiles; a quiet switch to the jnp path here would hide which code
        # ran on the chip
        raise ValueError(
            f"fused_attention: Lq={Lq}, Lk={Lk} is past the single-block "
            "kernel's budget and not a multiple of 256, which the flash "
            "kernel needs; pad the sequence to a multiple of 256 or call "
            "attention_reference"
        )
    interpret = not on_tpu
    if single_block:
        return _fused_attention_pallas(
            q, k, v, causal, interpret=interpret, segment=segment, block=block, value_width=value_width
        )
    # block sizes tuned per-shape (see _best_block): the largest
    # dividing tile wins on the MXU at every measured length
    block_q, block_k = _best_block(Lq), _best_block(Lk)
    if isinstance(segment, tuple):
        block_q, block_k = min(block_q, PAIR_TILES[0]), min(block_k, PAIR_TILES[1])
    return _flash_attention_pallas(
        q, k, v, causal, interpret=interpret,
        block_q=block_q, block_k=block_k, segment=segment, block=block, value_width=value_width,
    )
