"""Alternating least squares on TPU.

Replaces MLlib ALS (used by every reference recommendation template, e.g.
``tests/pio_tests/engines/recommendation-engine/src/main/scala/ALSAlgorithm.scala:79-85``)
with an ALX-style formulation (PAPERS.md: "ALX: Large Scale Matrix
Factorization on TPUs"): instead of Spark's shuffle-join of factor blocks,
each half-iteration builds per-entity normal equations with static-shape
chunked scatter-adds over the COO rating list, then solves all f-by-f systems
batched (MXU-friendly einsums + batched Cholesky).

Design notes (TPU):
  - COO triples are padded to a chunk multiple; padded rows scatter into a
    dummy entity row so shapes stay static under jit.
  - The nnz loop is a ``lax.scan`` over fixed-size chunks: each chunk gathers
    opposite-side factors, forms rank-1 Gram contributions via one einsum
    (``cf,cg->cfg``), and scatter-adds into the per-entity ``A``/``b``
    accumulators. No data-dependent shapes anywhere.
  - Explicit mode solves ``(A_u + reg*n_u*I) x = b_u`` per entity, where
    ``n_u`` is the entity's rating count — the ALS-WR degree-scaled
    regularization (Zhou et al., "Large-scale Parallel Collaborative
    Filtering for the Netflix Prize"; the same weighted-λ scheme MLlib's
    ALS popularized). This is a *numerical requirement* on TPU, not a
    style choice: under a power-law item popularity (bench triage round 3:
    the zipf head item carries ~25% of all ratings at ML-20M scale) the
    hub entity's Gram matrix ``Σ u u^T`` accumulates millions of fp32
    rank-1 terms, its condition number blows up, Cholesky hits a
    rounding-induced negative pivot, and the NaNs take the whole model
    down within two further iterations. Degree-scaled reg keeps the
    regularizer proportional to the Gram magnitude, so conditioning is
    degree-invariant. ``ALSConfig.reg_scaling`` selects: ``auto`` (degree
    for explicit, constant for implicit — implicit's shared ``V^T V``
    dense term already regularizes hubs), ``degree``, or ``constant``.
    Implicit mode (ref ``ALS.trainImplicit``) uses the classic trick:
    ``A_u = V^T V + Σ_i (c_i - 1) v_i v_i^T + reg*I`` with confidence
    ``c = 1 + alpha * r``, so the dense term is a single f×f matmul shared
    across entities.
  - Under a mesh, entity accumulators are sharded over the ``data`` axis and
    the COO chunks are sharded the same way; GSPMD inserts the all-gathers /
    reduce-scatters for cross-shard scatters. Callers annotate via
    ``in_shardings`` on the jitted step (see models/recommendation).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    rank: int = 16
    iterations: int = 10
    reg: float = 0.1  # lambda
    implicit: bool = False
    alpha: float = 1.0  # implicit confidence scale
    seed: int = 3
    chunk: int = 16384  # COO entries per scan step (blocked: block_d * blocks)
    block_d: int = 128  # entity-block width for the MXU Gram path
    # "cg" | "cholesky": batched f-by-f SPD solver. Jacobi-preconditioned
    # CG run for f+4 iterations is exact-termination on an f-dim Krylov
    # space (it IS a direct method for these sizes, modulo fp rounding). It
    # holds its systems batch-last ([f, f, n]: n on the 128 lanes), so a
    # matvec reads them at their own bytes, and on a TPU it solves a tile
    # of lanes to the end in VMEM (ops/spd_solve.py). Measured on a v5e in
    # rec-als-ml20m.train (PERF.md section 6, PR 27), solve seconds an
    # iteration: 0.180 over padded [n, f, f] as it was, 0.034 batch-last in
    # plain XLA, 0.007 as the tile kernel. "cg_fused" named an MXU kernel
    # over [64, f, f] blocks that took 0.256 s an iteration and is gone; the
    # spelling is still read and runs the one CG, until ROADMAP D3 removes
    # it. "cholesky" is the tests' reference.
    solver: str = "cg"
    # "auto" | "degree" | "constant" — see module docstring (ALS-WR)
    reg_scaling: str = "auto"
    # "f32" | "bf16": dtype of the FIXED factor table the nnz loop gathers
    # from. The solver iterations are gather-bound (PERF.md: ~21M row
    # gathers/iter dwarf the MXU Gram einsum), so halving the row bytes is
    # the remaining single-chip lever. "bf16" keeps a bf16 COPY of the
    # opposite side for the gather only — Gram/b accumulation, the shared
    # implicit gram term, regularization, and the batched solves all stay
    # f32, so only the gathered operand is rounded (8-bit mantissa).
    gather_dtype: str = "f32"
    # "auto" | "device" | "host": how the COO list becomes MXU block tables.
    # "device" (= "auto"): the three columns go up as they are before the
    # host has read them (235 MB in 26 ms at ML-20M's shape on a v5e, and
    # ``device_put`` returns in 1 ms), the host meanwhile checks every id and
    # counts the degrees (one native pass a column: the degrees size the
    # block tables, which are static shapes), and the device does the rest
    # (see _device_pack): a stable sort by user, a stable sort by item of
    # that, and both block tables by gather-expansion (no scatters). Measured
    # in rec-als-ml20m.train (PERF.md section 6, PR 29): the chip waited
    # 1.69 s a train for the host's group-by and wire codings before.
    # "host" keeps the original numpy block packing (exact reference for
    # tests; also what an empty input takes).
    pack: str = "auto"

    def __post_init__(self):
        # a typo'd reg_scaling silently reverting to constant reg would
        # reintroduce the hub-entity NaN blowup the docstring describes
        if self.reg_scaling not in ("auto", "degree", "constant"):
            raise ValueError(
                f"reg_scaling must be auto|degree|constant, got {self.reg_scaling!r}"
            )
        if self.solver not in ("cg", "cg_fused", "cholesky"):
            raise ValueError(
                f"solver must be cg|cg_fused|cholesky, got {self.solver!r}"
            )
        if self.pack not in ("auto", "device", "host"):
            raise ValueError(f"pack must be auto|device|host, got {self.pack!r}")
        if self.gather_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"gather_dtype must be f32|bf16, got {self.gather_dtype!r}"
            )

    @property
    def degree_scaled_reg(self) -> bool:
        if self.reg_scaling == "auto":
            return not self.implicit
        return self.reg_scaling == "degree"


def _pad_coo(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, chunk: int, dummy_row: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    n = rows.shape[0]
    pad = (-n) % chunk
    if pad:
        rows = np.concatenate([rows, np.full(pad, dummy_row, rows.dtype)])
        cols = np.concatenate([cols, np.zeros(pad, cols.dtype)])
        vals = np.concatenate([vals, np.zeros(pad, vals.dtype)])
    return rows, cols, vals


def _block_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    d: int,
    block_chunk: int,
    dummy_row: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pack a COO rating list into fixed-width entity blocks (ALX layout).

    Sorts by row, then gives each entity ``ceil(degree / d)`` consecutive
    blocks of ``d`` slots; unused slots carry weight 0. High-degree hub
    entities simply span many blocks — the degree skew that breaks padded
    dense layouts (one row per entity) costs only ``ceil`` waste here.
    Returns ``(block_rows [NB], cols [NB, d], vals [NB, d], w [NB, d])``
    with NB padded to a ``block_chunk`` multiple using dummy-row blocks;
    ``block_rows`` is sorted ascending (dummy = max index last), which the
    device-side scatter declares via ``indices_are_sorted``.
    """
    n = rows.shape[0]
    if n == 0:
        nb = block_chunk
        return (
            np.full((nb,), dummy_row, np.int32),
            np.zeros((nb, d), np.int32),
            np.zeros((nb, d), np.float32),
            np.zeros((nb, d), np.int8),  # same wire dtype as non-empty path
        )
    order = np.argsort(rows, kind="stable")
    r, c, v = rows[order], cols[order], vals[order]
    uniq, start, deg = np.unique(r, return_index=True, return_counts=True)
    nblk = -(-deg // d)
    block_base = np.concatenate([[0], np.cumsum(nblk)])
    nb_real = int(block_base[-1])
    nb = max(nb_real + (-nb_real) % block_chunk, block_chunk)
    # position of each entry within its entity -> (block, slot)
    p = np.arange(n) - np.repeat(start, deg)
    eidx = np.repeat(np.arange(len(uniq)), deg)
    dest_block = block_base[eidx] + p // d
    dest_slot = p % d
    cols_pad = np.zeros((nb, d), np.int32)
    vals_pad = np.zeros((nb, d), np.float32)
    # int8 mask: a quarter of the f32 host->device bytes (the block tables
    # are uploaded once per train); cast to f32 on device
    w_pad = np.zeros((nb, d), np.int8)
    cols_pad[dest_block, dest_slot] = c
    vals_pad[dest_block, dest_slot] = v
    w_pad[dest_block, dest_slot] = 1
    block_rows = np.full((nb,), dummy_row, np.int32)
    block_rows[:nb_real] = np.repeat(uniq, nblk)
    return block_rows, cols_pad, vals_pad, w_pad


def _normal_equations(
    rows: jnp.ndarray,  # [nnz] entity index being solved (incl. dummy)
    cols: jnp.ndarray,  # [nnz] opposite entity index
    vals: jnp.ndarray,  # [nnz] rating / confidence input
    opposite: jnp.ndarray,  # [n_opp, f] fixed factors
    n_entities: int,  # includes dummy row
    chunk: int,
    implicit: bool,
    alpha: float,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Accumulate A [E, f, f], b [E, f], and rating counts [E] over
    fixed-size COO chunks. Counts feed degree-scaled regularization; the
    dummy padding row accumulates garbage counts, which is harmless (its
    solution is discarded)."""
    f = opposite.shape[1]
    n_chunks = rows.shape[0] // chunk
    A0 = jnp.zeros((n_entities, f, f), opposite.dtype)
    b0 = jnp.zeros((n_entities, f), opposite.dtype)
    n0 = jnp.zeros((n_entities,), opposite.dtype)

    r_ch = rows.reshape(n_chunks, chunk)
    c_ch = cols.reshape(n_chunks, chunk)
    v_ch = vals.reshape(n_chunks, chunk)

    def step(carry, inputs):
        A, b, n = carry
        r, c, v = inputs
        vecs = opposite[c]  # [chunk, f] gather
        if implicit:
            # confidence c_i = 1 + alpha * r; contribution (c_i - 1) v v^T,
            # preference p = 1 -> b contribution c_i * v
            conf_minus_1 = alpha * v
            outer_w = conf_minus_1
            b_w = 1.0 + alpha * v
        else:
            outer_w = jnp.ones_like(v)
            b_w = v
        outers = jnp.einsum("c,cf,cg->cfg", outer_w, vecs, vecs)
        A = A.at[r].add(outers)
        b = b.at[r].add(b_w[:, None] * vecs)
        n = n.at[r].add(jnp.ones_like(v))
        return (A, b, n), None

    (A, b, n), _ = lax.scan(step, (A0, b0, n0), (r_ch, c_ch, v_ch))
    return A, b, n


def _normal_equations_blocked(
    block_rows: jnp.ndarray,  # [NB] owning entity per block (sorted, incl. dummy)
    cols: jnp.ndarray,  # [NB, D] opposite-entity indices
    vals: jnp.ndarray,  # [NB, D] ratings (0 in pad slots)
    w: jnp.ndarray,  # [NB, D] 1.0 real / 0.0 pad
    opposite: jnp.ndarray,  # [n_opp, f] fixed factors
    n_entities: int,
    block_chunk: int,
    implicit: bool,
    alpha: float,
    gather_dtype: str = "f32",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Block-Gram accumulation: the MXU path for the nnz loop.

    The chunked-scatter formulation (``_normal_equations``) spends one
    rank-1 [f,f] outer product + one scatter-add PER RATING — measured
    ~7.4s/iteration at ML-20M on a v5e chip, entirely scatter-bound (the
    ``indices_are_sorted`` hint bought nothing). Here each fixed-width
    entity block computes its Gram contribution as ONE batched matmul
    (``bdf,bdg->bfg`` — contraction depth D rides the MXU) and only the
    per-BLOCK [f,f] results are scattered: D times fewer scatter elements
    and the FLOPs move from the VPU to the MXU.

    ``gather_dtype="bf16"`` gathers from a bf16 copy of ``opposite``
    (half the row bytes on the gather-bound path); accumulation and the
    returned A/b/counts are always at least f32 (callers may pass an
    ``opposite`` that is ALREADY bf16 — e.g. the sharded path's bf16
    all_gather — without the accumulators degrading to bf16).
    """
    f = opposite.shape[1]
    acc_dtype = jnp.promote_types(opposite.dtype, jnp.float32)
    gathered = (
        opposite.astype(jnp.bfloat16) if gather_dtype == "bf16" else opposite
    )
    nb = block_rows.shape[0]
    n_chunks = nb // block_chunk
    A0 = jnp.zeros((n_entities, f, f), acc_dtype)
    b0 = jnp.zeros((n_entities, f), acc_dtype)
    n0 = jnp.zeros((n_entities,), acc_dtype)

    br_ch = block_rows.reshape(n_chunks, block_chunk)
    c_ch = cols.reshape(n_chunks, block_chunk, -1)
    v_ch = vals.reshape(n_chunks, block_chunk, -1)
    w_ch = w.reshape(n_chunks, block_chunk, -1)

    def step(carry, inputs):
        A, b, n = carry
        br, c, v, ww = inputs
        ww = ww.astype(acc_dtype)  # int8 wire format -> f32 math
        with jax.named_scope("gather"):
            vecs = gathered[c]  # [CB, D, f] gather (bf16 rows when opted in)
        with jax.named_scope("gram"):
            if implicit:
                ow = ww * (alpha * v)  # (conf - 1), 0 in pad slots
                bw = ww * (1.0 + alpha * v)
            else:
                ow = ww
                bw = ww * v
            # weights stay f32 on every mode (the f32*bf16 product promotes,
            # so ONLY the gathered rows are rounded — the documented
            # contract; the multiply precision was never the bottleneck, the
            # gather bytes are) and the einsums accumulate in acc_dtype
            A_blk = jnp.einsum(
                "bdf,bdg->bfg",
                ow[..., None] * vecs,
                vecs,
                preferred_element_type=acc_dtype,
            ).astype(acc_dtype)
            b_blk = jnp.einsum(
                "bd,bdf->bf", bw, vecs, preferred_element_type=acc_dtype
            ).astype(acc_dtype)
            n_blk = ww.sum(axis=-1)
            A = A.at[br].add(A_blk, indices_are_sorted=True)
            b = b.at[br].add(b_blk, indices_are_sorted=True)
            n = n.at[br].add(n_blk, indices_are_sorted=True)
        return (A, b, n), None

    (A, b, n), _ = lax.scan(step, (A0, b0, n0), (br_ch, c_ch, v_ch, w_ch))
    return A, b, n


def _batched_spd_solve(A: jnp.ndarray, b: jnp.ndarray, solver: str) -> jnp.ndarray:
    """Solve B independent f-by-f SPD systems. ``cg`` = Jacobi-preconditioned
    conjugate gradient for f+4 iterations (exact termination on the f-dim
    space), over the systems turned batch-last so that a matvec reads them
    at their own bytes (ops/spd_solve.py; see ALSConfig.solver, which also
    says what became of ``cg_fused``); ``cholesky`` = LAPACK-style
    factorization (the tests' reference, slower on TPU)."""
    if solver == "cholesky":
        return jax.scipy.linalg.cho_solve((jnp.linalg.cholesky(A), True), b)
    from predictionio_tpu.ops.spd_solve import _cg_body

    return _cg_body(A, b)


def _solve_blocked(
    block_rows,
    cols,
    vals,
    w,
    opposite,
    n_entities,
    block_chunk,
    reg,
    implicit,
    alpha,
    degree_scaled_reg: bool,
    solver: str = "cg",
    gather_dtype: str = "f32",
):
    f = opposite.shape[1]
    A, b, counts = _normal_equations_blocked(
        block_rows, cols, vals, w, opposite, n_entities, block_chunk, implicit, alpha,
        gather_dtype,
    )
    with jax.named_scope("gram"):
        eye = jnp.eye(f, dtype=A.dtype)
        if implicit:
            # shared dense term accumulates at the (>= f32) accumulator
            # dtype even if ``opposite`` arrived bf16 from a caller
            gram = jnp.einsum(
                "df,dg->fg", opposite, opposite, preferred_element_type=A.dtype
            )
            A = A + gram[None, :, :]
        if degree_scaled_reg:
            A = A + (reg * jnp.maximum(counts, 1.0))[:, None, None] * eye[None, :, :]
        else:
            A = A + reg * eye[None, :, :]
    with jax.named_scope("solve"):
        return _batched_spd_solve(A, b, solver)


def _solve_side(
    rows,
    cols,
    vals,
    opposite,
    n_entities,
    chunk,
    reg,
    implicit,
    alpha,
    degree_scaled_reg: bool = True,
    solver: str = "cg",
):
    f = opposite.shape[1]
    A, b, counts = _normal_equations(
        rows, cols, vals, opposite, n_entities, chunk, implicit, alpha
    )
    eye = jnp.eye(f, dtype=opposite.dtype)
    if implicit:
        gram = opposite.T @ opposite  # shared dense term, one f x f matmul
        A = A + gram[None, :, :]
    if degree_scaled_reg:
        # ALS-WR: λ·n_e·I — degree-invariant conditioning (module docstring)
        scale = jnp.maximum(counts, 1.0)
        A = A + (reg * scale)[:, None, None] * eye[None, :, :]
    else:
        A = A + reg * eye[None, :, :]
    return _batched_spd_solve(A, b, solver)


# One ALS iteration per executable launch — deliberately NOT a fused
# fori_loop over iterations: a fused loop with a static trip count gets
# unrolled by XLA (compile time scales with iterations) and with a traced
# trip count hides per-iteration progress. Host-looped dispatch costs one
# dispatch per iteration (0.3 ms against 298 ms of device work at the
# chip_smoke shape on a v5e), never recompiles when `iterations` changes,
# and gives the trainer natural mid-train checkpoint/convergence hooks.
# Factors and the COO tables stay resident on device across launches.
@functools.partial(
    jax.jit,
    static_argnames=(
        "n_users",
        "n_items",
        "reg",
        "implicit",
        "alpha",
        "block_chunk",
        "degree_scaled_reg",
        "solver",
        "gather_dtype",
    ),
    donate_argnums=(0, 1),
)
def _als_step(
    user_factors,
    item_factors,
    u_br,
    u_cols,
    u_vals,
    u_w,
    i_br,
    i_cols,
    i_vals,
    i_w,
    *,
    n_users: int,
    n_items: int,
    reg: float,
    implicit: bool,
    alpha: float,
    block_chunk: int,
    degree_scaled_reg: bool = True,
    solver: str = "cg",
    gather_dtype: str = "f32",
):
    user_factors = _solve_blocked(
        u_br, u_cols, u_vals, u_w, item_factors, n_users + 1, block_chunk,
        reg, implicit, alpha, degree_scaled_reg, solver, gather_dtype,
    )
    item_factors = _solve_blocked(
        i_br, i_cols, i_vals, i_w, user_factors, n_items + 1, block_chunk,
        reg, implicit, alpha, degree_scaled_reg, solver, gather_dtype,
    )
    return user_factors, item_factors


@functools.partial(jax.jit, static_argnames=("n_users", "n_items", "rank", "seed"))
def _als_init(*, n_users: int, n_items: int, rank: int, seed: int):
    key = jax.random.PRNGKey(seed)
    # +1 dummy row absorbs padding scatters
    item_factors = (
        jax.random.normal(key, (n_items + 1, rank), jnp.float32) / jnp.sqrt(rank)
    )
    user_factors = jnp.zeros((n_users + 1, rank), jnp.float32)
    return user_factors, item_factors


def _expand_blocks_traced(deg, cols_sorted, vals_sorted, d: int, nb: int, dummy_row: int):
    """Device-side equivalent of ``_block_coo`` for an already-grouped side.

    Inputs are grouped by owning entity (ascending, stable); ``deg`` is the
    per-entity count. Builds the [nb, d] block tables with searchsorted +
    gathers only — no scatters (TPU scatters of 20M elements are the thing
    the blocked layout exists to avoid). Produces the exact layout
    ``_block_coo`` computes: entity e owns ``ceil(deg[e]/d)`` consecutive
    blocks; pad slots carry weight 0; pad blocks point at ``dummy_row``.
    """
    # the slot indices below depend on ``deg`` alone, so XLA would compute
    # them ahead of the sort that makes the streams and then start on the
    # gathers the moment the sort ends, with one stream still where the sort
    # left it. Tied to the streams they are computed after the sort, and the
    # streams' move into the faster memory hides behind them: 0.19 s against
    # 0.48 for the item side's 21 M ratings on a v5e (PERF.md section 6, PR 29)
    deg, cols_sorted, vals_sorted = lax.optimization_barrier(
        (deg, cols_sorted, vals_sorted)
    )
    n_entities = deg.shape[0]
    nblk = (deg + (d - 1)) // d
    bb_incl = jnp.cumsum(nblk)  # inclusive block prefix
    block_base = bb_incl - nblk
    start = jnp.cumsum(deg) - deg
    b = jnp.arange(nb, dtype=jnp.int32)
    # owner[b] = first entity whose inclusive block prefix exceeds b;
    # == n_entities for pad blocks past the real range
    owner = jnp.searchsorted(bb_incl, b, side="right").astype(jnp.int32)
    is_real = owner < n_entities
    e = jnp.minimum(owner, n_entities - 1)
    local = b - block_base[e]
    offs = local[:, None] * d + jnp.arange(d, dtype=jnp.int32)[None, :]
    valid = is_real[:, None] & (offs < deg[e][:, None])
    src = jnp.where(valid, start[e][:, None] + offs, 0)
    cols_b = jnp.where(valid, cols_sorted[src], 0).astype(jnp.int32)
    vals_b = jnp.where(valid, vals_sorted[src], jnp.float32(0))
    w_b = valid.astype(jnp.int8)
    block_rows = jnp.where(is_real, e, jnp.int32(dummy_row))
    return block_rows, cols_b, vals_b, w_b


@functools.partial(
    jax.jit, static_argnames=("d", "nb_u", "nb_i", "n_users", "n_items")
)
def _device_pack(
    users,  # [nnz] int32, in the caller's order
    items,  # [nnz] int32
    ratings,  # [nnz] float32
    deg_u,  # [n_users] int32 per-user rating count
    deg_i,  # [n_items] int32 per-item rating count
    *,
    d: int,
    nb_u: int,
    nb_i: int,
    n_users: int,
    n_items: int,
):
    """Build BOTH sides' block tables on device from the raw columns.

    A stable sort by user groups the ratings as ``_host_group_by`` does (the
    order inside a user is the input's, as the counting sort's is), and a
    stable sort by item of THAT stream gives the item side's order; each
    side's tables are then one gather-expansion. The host hands over the
    columns untouched and the two degree histograms (``nb_u`` and ``nb_i``
    follow from them and are static shapes).
    """
    with jax.named_scope("pack"):
        users_u, items_u, ratings_u = lax.sort(
            (users, items, ratings), num_keys=1, is_stable=True
        )
        u_tables = _expand_blocks_traced(deg_u, items_u, ratings_u, d, nb_u, n_users)
        _, users_by_item, ratings_by_item = lax.sort(
            (items_u, users_u, ratings_u), num_keys=1, is_stable=True
        )
        i_tables = _expand_blocks_traced(
            deg_i, users_by_item, ratings_by_item, d, nb_i, n_items
        )
        return (*u_tables, *i_tables)


def _compress_ratings_wire(
    vals: "np.ndarray",
) -> tuple["np.ndarray", "np.ndarray | None"]:
    """Smallest LOSSLESS wire form of the ratings column; returns
    ``(wire_vals, table)``. The mesh-sharded trainer's wire
    (ops/als_sharded.py); ``als_train`` sends float32 as it is.

    - ≤256 distinct values (every real star-rating dataset: ML uses 0.5
      steps over [0.5, 5]) -> uint8 dictionary codes + a tiny f32 value
      table, decoded on device by one gather — 4x smaller than f32;
    - else f16 when every value round-trips exactly;
    - else untouched f32 — no quality-for-bandwidth trade is ever silent.

    Distinctness is probed on a 65536-sample first (one tiny unique)
    so the continuous case never pays a full-array sort; the candidate
    table is then verified exactly against the full column.
    """
    if vals.shape[0] == 0:
        return vals, None
    sample_uniq = np.unique(vals[:65536])
    if 0 < sample_uniq.size <= 256:
        idx = np.searchsorted(sample_uniq, vals)
        idx = np.minimum(idx, sample_uniq.size - 1)
        if np.array_equal(sample_uniq[idx], vals):
            return idx.astype(np.uint8), sample_uniq.astype(np.float32)
    v16 = vals.astype(np.float16)
    if np.array_equal(v16.astype(np.float32), vals):
        return v16, None
    return vals, None


def _host_group_by(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n_entities: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stable group-by-entity: native C++ counting sort (O(n), one pass each
    for histogram and scatter) with a numpy stable-argsort fallback. The
    mesh-sharded trainer's host pack (ops/als_sharded.py), and the order
    ``_device_pack``'s sort by user is held to.

    Ids must lie in [0, n_entities): an oversized id would give the degree
    histogram the wrong length and every downstream block table a silently
    corrupt layout (JAX clips the OOB gathers instead of failing), so it is
    rejected here on both paths."""
    if rows.shape[0] and int(rows.max()) >= n_entities:
        raise ValueError(
            f"entity index {int(rows.max())} out of range for {n_entities} entities"
        )
    from predictionio_tpu.utils import native

    out = native.coo_group(rows, cols, vals, n_entities)
    if out is not None:
        return out
    order = np.argsort(rows, kind="stable")
    deg = np.bincount(rows, minlength=n_entities).astype(np.int32)
    return cols[order], vals[order], deg


def _pad_blocks(nb_real: int, block_chunk: int) -> int:
    return max(nb_real + (-nb_real) % block_chunk, block_chunk)


@jax.jit
def _barrier_checksum(*arrays):
    """One scalar derived from every input array (barrier helper)."""
    total = jnp.float32(0)
    for a in arrays:
        total = total + jnp.sum(a, dtype=jnp.float32)
    return total


def fetch_barrier(*arrays) -> float:
    """Wait for every array and return their checksum: one scalar summed
    on the device over all of them, then fetched. The scalar does not exist
    until every input has been materialized, so the fetch is a completion
    barrier. On an attached chip ``jax.block_until_ready`` is one too (one
    ALS iteration at the chip_smoke shape on a v5e: both returned at 298 ms,
    0.4 ms apart, PERF.md); this form is kept where the caller also wants
    the value (``TrainProfile.device_barrier``'s convergence metric)."""
    # pio-lint: disable=train-unaccounted-sync -- this IS the timing instrument; callers time around it
    return float(np.asarray(_barrier_checksum(*arrays)))


def _checked_degrees(
    user_idx: np.ndarray, item_idx: np.ndarray, n_users: int, n_items: int
) -> tuple[np.ndarray, np.ndarray] | None:
    """``(deg_u, deg_i)``, every id checked on the way: one native pass a
    column (``utils/native.degrees``). None when a rating carries a negative
    id (the caller drops those and asks again); an id past its vocabulary
    raises. Without the native library, or to say WHICH id was out of range,
    numpy does the same in six passes."""
    from predictionio_tpu.utils import native

    columns = (("user", user_idx, n_users), ("item", item_idx, n_items))
    degrees = [native.degrees(idx, bound) for _, idx, bound in columns]
    if all(deg is not None for deg in degrees):
        return degrees[0], degrees[1]
    if min(int(user_idx.min()), int(item_idx.min())) < 0:
        return None
    for name, idx, bound in columns:
        mx = int(idx.max())
        if mx >= bound:
            raise ValueError(
                f"{name} index {mx} out of range for n_{name}s={bound}"
            )
    return tuple(
        np.bincount(idx, minlength=bound).astype(np.int32)
        for _, idx, bound in columns
    )


def als_train(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    config: ALSConfig,
    timings: dict | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Train explicit or implicit ALS; returns (user_factors [n_users, f],
    item_factors [n_items, f]).

    Pass a ``timings`` dict to get a wall-clock decomposition written into
    it: ``pack_s`` (the host's part: handing the raw columns to the
    transfer, then checking the ids and counting the degrees beside it; the
    whole numpy block packing on the host pack path), ``upload_s`` (what is
    left of the transfer once the host is done, barrier-confirmed),
    ``build_s`` (device-side block-table construction; 0 on the host pack
    path), ``device_s`` (solver iterations only, barrier-confirmed), and
    ``wire_bytes`` (what crossed to the device before the first sweep). The
    four clocks run back to back from the call's first line, so they sum to
    the call's wall clock. The un-instrumented path keeps the fully-async
    dispatch pipeline: no barrier between upload, build and the first sweep.
    The columns are handed to the transfer as they are (``jax.device_put``
    of the caller's arrays): do not write into them before the factors are
    ready.

    With an active train profile (obs/xray): the host pack/upload/build
    accounts as ``host_etl``, each iteration becomes one profiled
    ``sweep`` step closed by a true device barrier (the barrier per
    iteration serializes the at-most-one-deep dispatch overlap — that is
    the price of per-iteration device time, paid only when profiling),
    the per-iteration factor checksum rides as the step's convergence
    metric, and live-memory peaks are sampled per step.
    """
    import time

    from predictionio_tpu.obs import xray
    from predictionio_tpu.obs.jaxprof import annotate

    prof = xray.current_profile()
    # each stage is also a host span on the profiler's clock
    # (obs/jaxprof.annotate): on the plain path a span closes when the host's
    # call returns, which is what the host did
    with xray.phase(xray.PHASE_HOST_ETL):
        with annotate("pio:als.pack"):
            t0 = time.perf_counter()
            user_idx = np.asarray(user_idx, np.int32)
            item_idx = np.asarray(item_idx, np.int32)
            ratings = np.asarray(ratings, np.float32)
            d = max(8, min(config.block_d, config.chunk))
            block_chunk = max(8, config.chunk // d)
            columns = (user_idx, item_idx, ratings)
            wire, degrees = [], None
            while degrees is None and columns[0].shape[0]:
                if config.pack != "host":
                    # the columns leave as they are, before the host has read
                    # them: device_put returns at once and the transfer runs
                    # beside the host's one pass over the ids
                    wire = [jax.device_put(a) for a in columns]
                degrees = _checked_degrees(columns[0], columns[1], n_users, n_items)
                if degrees is None:
                    # a rating with a negative id is dropped, as ever; what is
                    # left takes the same road (what went up is let go)
                    valid = (columns[0] >= 0) & (columns[1] >= 0)
                    columns, wire = tuple(a[valid] for a in columns), []
            user_idx, item_idx, ratings = columns
            use_device_pack = bool(wire)
            if use_device_pack:
                nb_u, nb_i = (
                    _pad_blocks(int((-(-deg // d)).sum()), block_chunk)
                    for deg in degrees
                )
                host_made = degrees
            else:
                host_made = (
                    *_block_coo(user_idx, item_idx, ratings, d, block_chunk, n_users),
                    *_block_coo(item_idx, user_idx, ratings, d, block_chunk, n_items),
                )
            wire_bytes = sum(a.nbytes for a in (*wire, *host_made))
            t_pack = time.perf_counter()
        with annotate("pio:als.upload", bytes=wire_bytes):
            # what the host made crosses host->device ONCE (the degree
            # histograms behind the columns, or the host's block tables); the
            # per-iteration launches reuse the same device buffers
            dev = [*wire, *(jax.device_put(a) for a in host_made)]
            del wire  # the raw columns go when _device_pack has read them
            if timings is not None:
                fetch_barrier(*dev)
            t_upload = time.perf_counter()
        if use_device_pack:
            with annotate("pio:als.build"):
                dev = list(
                    _device_pack(
                        *dev, d=d, nb_u=nb_u, nb_i=nb_i, n_users=n_users, n_items=n_items
                    )
                )
                if timings is not None:
                    # device-side table build (two sorts + gather expansion)
                    # attributed to its own bucket: device_s means SOLVER
                    # iterations only, on both pack paths, or per-iteration
                    # figures aren't comparable
                    fetch_barrier(dev[0], dev[4])
        # tables arrive pre-built on the host path: its build_s is 0
        t_build = time.perf_counter() if use_device_pack else t_upload
        user_f, item_f = _als_init(
            n_users=n_users, n_items=n_items, rank=config.rank, seed=config.seed
        )
    import contextlib

    nnz = int(user_idx.shape[0])
    for iteration in range(config.iterations):
        with contextlib.ExitStack() as stack:
            rec = (
                stack.enter_context(prof.step(nnz=nnz))
                if prof is not None
                else None
            )
            with xray.phase(xray.PHASE_SWEEP), annotate(
                "pio:als.sweep", iteration=iteration
            ):
                user_f, item_f = _als_step(
                    user_f,
                    item_f,
                    *dev,
                    n_users=n_users,
                    n_items=n_items,
                    reg=config.reg,
                    implicit=config.implicit,
                    alpha=config.alpha,
                    block_chunk=block_chunk,
                    degree_scaled_reg=config.degree_scaled_reg,
                    solver=config.solver,
                    gather_dtype=config.gather_dtype,
                )
                if rec is not None:
                    rec["metric"] = prof.device_barrier(
                        user_f, item_f, where="als-sweep"
                    )
        if prof is not None:
            # profiler's own bookkeeping (live-array walk) accounts as
            # host_etl so it cannot open a hole in the tiling contract
            with prof.phase(xray.PHASE_HOST_ETL):
                prof.add_rows(nnz)
                prof.sample_memory()
    with annotate("pio:als.fetch"):
        if timings is not None:
            fetch_barrier(user_f, item_f)
            timings["pack_s"] = t_pack - t0
            timings["upload_s"] = t_upload - t_pack
            timings["build_s"] = t_build - t_upload
            timings["device_s"] = time.perf_counter() - t_build
            timings["wire_bytes"] = wire_bytes
            # block-table shapes, for a caller's model of the bytes an
            # iteration moves: nb = blocks per side, d = block width
            timings["nb_u"] = int(dev[0].shape[0])
            timings["nb_i"] = int(dev[4].shape[0])
            timings["d"] = d
        return user_f[:n_users], item_f[:n_items]
