"""Multi-device ALS: ALX-style sharded alternating least squares.

This is the TPU answer to SURVEY.md section 7 hard part (a) — the reference
scales ALS through MLlib's shuffle joins of factor blocks across Spark
executors (``ALSAlgorithm.scala:79-85`` calls into MLlib; MLlib partitions
user/item blocks and shuffles per iteration). Here the same computation is
laid out for an ICI mesh the way ALX (PAPERS.md) does:

  - Users and items are partitioned into one contiguous block per device
    along the mesh axis; each device owns its block's factors for the whole
    run (no resharding between iterations).
  - Ratings are partitioned twice on the host: by owning user block (for
    the user-side solve) and by owning item block (for the item-side
    solve) — the moral equivalent of MLlib's two pre-shuffled COO layouts,
    done once, not per iteration.
  - Each half-iteration ``all_gather``s the *opposite* side's factor blocks
    over ICI (the only cross-device traffic, f * n_opposite * 4 bytes),
    builds per-entity normal equations from the local COO shard with
    static-shape chunked scatter-adds, and solves its own block's f-by-f
    systems batched (Cholesky on the MXU).
  - Shapes are identical on every device (blocks and COO shards are padded;
    padding scatters land in a per-block dummy row). Each iteration is ONE
    ``shard_map`` launch (host-looped, like ``ops/als.py:_als_step``, for
    the reasons given there).

Communication per iteration: 2 all_gathers (U and V). MLlib pays 2 shuffles
of the *rating* table per iteration, which is strictly larger for any
realistic nnz >> entities * f.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops.als import (
    ALSConfig,
    _compress_ratings_wire,
    _host_group_by,
    _pad_blocks,
    _solve_blocked,
)

logger = logging.getLogger(__name__)


def _block_partition_blocked(
    owner_idx: np.ndarray,
    other_idx: np.ndarray,
    vals: np.ndarray,
    block: int,
    n_dev: int,
    d: int,
    block_chunk: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Split COO by owning device block, localize owner indices, and pack
    each device's shard into the ALX entity-block layout (the same MXU
    Gram formulation the single-chip path uses). All devices are padded to
    one common block count with dummy blocks (local dummy row = ``block``).

    One global O(n) group-by (native C++ counting sort — device blocks are
    contiguous entity ranges, so grouping by entity also groups by device)
    replaces the per-device stable argsorts this used to run: at ML-20M on
    8 devices that was 16 argsorts over the full rating list per train.
    The within-entity order (original event order) and the emitted layout
    are identical to the old packer's.

    Returns stacked [n_dev, NB], [n_dev, NB, d] x2, [n_dev, NB, d] arrays.
    """
    n_ent = n_dev * block
    cols_g, vals_g, deg = _host_group_by(
        owner_idx.astype(np.int32),
        other_idx.astype(np.int32),
        vals.astype(np.float32),
        n_ent,
    )
    start = np.concatenate([[0], np.cumsum(deg)])
    nblk = -(-deg // d)  # blocks per entity (0 for unrated entities)
    per_dev_blocks = nblk.reshape(n_dev, block).sum(axis=1)
    nb = _pad_blocks(int(per_dev_blocks.max()), block_chunk)
    br = np.full((n_dev, nb), block, np.int32)
    cols = np.zeros((n_dev, nb, d), np.int32)
    v = np.zeros((n_dev, nb, d), np.float32)
    w = np.zeros((n_dev, nb, d), np.int8)
    for dev in range(n_dev):
        e0, e1 = dev * block, (dev + 1) * block
        deg_l = deg[e0:e1]
        r0, r1 = int(start[e0]), int(start[e1])
        if r1 == r0:
            continue  # no ratings for this device's entities
        nblk_l = nblk[e0:e1]
        block_base = np.concatenate([[0], np.cumsum(nblk_l)])
        # position of each grouped row within its entity -> (block, slot)
        p = np.arange(r1 - r0) - np.repeat(start[e0:e1] - r0, deg_l)
        eidx = np.repeat(np.arange(block), deg_l)
        cols[dev, block_base[eidx] + p // d, p % d] = cols_g[r0:r1]
        v[dev, block_base[eidx] + p // d, p % d] = vals_g[r0:r1]
        w[dev, block_base[eidx] + p // d, p % d] = 1
        br[dev, : int(block_base[-1])] = np.repeat(np.arange(block), nblk_l)
    return br, cols, v, w


@functools.partial(jax.jit, static_argnames=("sharding",))
def _decode_ratings(codes, table, sharding):
    """One sharded gather decoding the uint8 dictionary ratings wire
    (module-level jit: compiles once per shape, not per train)."""
    return jax.lax.with_sharding_constraint(
        table[codes.astype(jnp.int32)], sharding
    )


def als_train_sharded(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    config: ALSConfig,
    mesh: Mesh | None = None,
    axis: str = "data",
) -> tuple[np.ndarray, np.ndarray]:
    """ALS over a device mesh; returns host numpy (user_factors,
    item_factors) exactly shaped [n_users, f] / [n_items, f].

    ``mesh`` defaults to a 1-D mesh over all visible devices. With one
    device this degrades gracefully to the single-chip schedule.
    """
    from predictionio_tpu.obs import xray

    prof = xray.current_profile()
    if mesh is None:
        # pio-lint: disable=train-unaccounted-sync -- host-side device list, not a device fetch
        mesh = Mesh(np.asarray(jax.devices()), (axis,))
    n_dev = mesh.shape[axis]

    with xray.phase(xray.PHASE_HOST_ETL):
        user_idx = np.asarray(user_idx, np.int32)
        item_idx = np.asarray(item_idx, np.int32)
        ratings = np.asarray(ratings, np.float32)
        valid = (user_idx >= 0) & (item_idx >= 0)
        user_idx, item_idx, ratings = (
            user_idx[valid], item_idx[valid], ratings[valid]
        )

        bu = max(1, -(-n_users // n_dev))  # users per device block
        bi = max(1, -(-n_items // n_dev))
        d = max(8, min(config.block_d, config.chunk))
        block_chunk = max(8, config.chunk // d)

        u_blocks = _block_partition_blocked(
            user_idx, item_idx, ratings, bu, n_dev, d, block_chunk
        )
        i_blocks = _block_partition_blocked(
            item_idx, user_idx, ratings, bi, n_dev, d, block_chunk
        )

        spec = P(axis)
        sharded = NamedSharding(mesh, spec)
        put = lambda x: jax.device_put(x, sharded)

        statics = dict(
            mesh=mesh,
            axis=axis,
            bu=bu,
            bi=bi,
            rank=config.rank,
            reg=config.reg,
            implicit=config.implicit,
            alpha=config.alpha,
            block_chunk=block_chunk,
            degree_scaled_reg=config.degree_scaled_reg,
            solver=config.solver,
            gather_dtype=config.gather_dtype,
        )
        def put_vals(v: np.ndarray):
            """Upload a [n_dev, nb, d] ratings table in its smallest LOSSLESS
            form: uint8 dictionary codes + a tiny replicated value table,
            decoded once on device by a sharded gather (same contract as the
            single-chip wire — every star-rating dataset fits; pad zeros join
            the dictionary). Falls back to the full f32 table otherwise."""
            codes, table = _compress_ratings_wire(v.reshape(-1))
            if table is None or codes.dtype != np.uint8:
                return put(v)
            return _decode_ratings(
                put(codes.reshape(v.shape)), jax.device_put(table), sharded
            )

        u_br, u_cols, u_v, u_w = u_blocks
        i_br, i_cols, i_v, i_w = i_blocks
        dev = (
            put(u_br), put(u_cols), put_vals(u_v), put(u_w),
            put(i_br), put(i_cols), put_vals(i_v), put(i_w),
        )
        # one iteration per launch — same watchdog/compile rationale as
        # ops/als.py:_als_step; collectives still ride ICI inside each launch
        uf, vf = _als_sharded_init(
            mesh=mesh, axis=axis, bu=bu, bi=bi, rank=config.rank,
            seed=config.seed, n_items=n_items,
        )
        # placement evidence, read from the arrays: one factor block per
        # device of the mesh, not everything on the first
        logger.info(
            "sharded ALS: user blocks %s as %s, item blocks %s; shards %s",
            uf.shape,
            uf.sharding.spec,
            vf.shape,
            [(str(sh.device), sh.data.shape) for sh in uf.addressable_shards],
        )
    import contextlib

    nnz = int(user_idx.shape[0])
    for _ in range(config.iterations):
        with contextlib.ExitStack() as stack:
            rec = (
                stack.enter_context(prof.step(nnz=nnz, mesh=str(dict(mesh.shape))))
                if prof is not None
                else None
            )
            with xray.phase(xray.PHASE_SWEEP):
                uf, vf = _als_sharded_step(uf, vf, *dev, **statics)
                if rec is not None:
                    rec["metric"] = prof.device_barrier(
                        uf, vf, where="als-sharded-sweep"
                    )
        if prof is not None:
            with prof.phase(xray.PHASE_HOST_ETL):
                prof.add_rows(nnz)
                prof.sample_memory()
    # [n_dev, b+1, f] -> drop per-block dummy row, concatenate, trim padding
    with xray.phase(xray.PHASE_HOST_ETL):
        uf = _fetch(uf).reshape(n_dev, bu + 1, config.rank)[:, :bu].reshape(
            -1, config.rank
        )
        vf = _fetch(vf).reshape(n_dev, bi + 1, config.rank)[:, :bi].reshape(
            -1, config.rank
        )
    return uf[:n_users], vf[:n_items]


def _fetch(a) -> np.ndarray:
    """Device -> host, gathering across processes when the mesh spans hosts
    (a multi-host sharded array is not addressable from any single host).
    The final fetch rides ``obs.xray.device_fetch`` so a profiled sharded
    train accounts its readback stall like every other device wait."""
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        from jax.experimental import multihost_utils

        a = multihost_utils.process_allgather(a, tiled=True)
    from predictionio_tpu.obs import xray

    return xray.device_fetch(a, where="als-sharded-fetch")


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "bu", "bi", "rank", "seed", "n_items"),
)
def _als_sharded_init(
    *, mesh: Mesh, axis: str, bu: int, bi: int, rank: int, seed: int, n_items: int
):
    spec = P(axis)

    def device_fn():
        d = lax.axis_index(axis)
        # per-device init of the owned item block (+ dummy row)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), d)
        vf_local = jax.random.normal(key, (bi + 1, rank), jnp.float32) / jnp.sqrt(
            rank
        )
        # zero padding rows whose global index >= n_items so they don't bias
        # the implicit-mode gram term in the first user-side solve (they only
        # self-zero after the first item solve otherwise)
        global_row = d * bi + jnp.arange(bi + 1)
        vf_local = jnp.where((global_row < n_items)[:, None], vf_local, 0.0)
        uf_local = jnp.zeros((bu + 1, rank), jnp.float32)
        # leading device axis for the P(axis) out_spec
        return uf_local[None], vf_local[None]

    return shard_map(
        device_fn, mesh=mesh, in_specs=(), out_specs=(spec, spec), check_vma=False
    )()


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh",
        "axis",
        "bu",
        "bi",
        "rank",
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
def _als_sharded_step(
    uf,
    vf,
    u_br,
    u_cols,
    u_vals,
    u_w,
    i_br,
    i_cols,
    i_vals,
    i_w,
    *,
    mesh: Mesh,
    axis: str,
    bu: int,
    bi: int,
    rank: int,
    reg: float,
    implicit: bool,
    alpha: float,
    block_chunk: int,
    degree_scaled_reg: bool = True,
    solver: str = "cg",
    gather_dtype: str = "f32",
):
    spec = P(axis)

    def device_fn(uf_l, vf_l, u_br, u_cols, u_vals, u_w, i_br, i_cols, i_vals, i_w):
        # shard_map hands each device its [1, ...] slice; flatten it
        uf_l, vf_l = uf_l[0], vf_l[0]
        n_dev = lax.psum(1, axis)

        # bf16 across the ICI only in EXPLICIT mode: it halves the
        # collective bytes and hands _solve_blocked the same bf16 rows the
        # single-chip path gathers (its accumulators stay f32 — see
        # _normal_equations_blocked). Implicit mode gathers f32 so the
        # shared V^T V gram term is computed from full-precision factors,
        # exactly like the single-chip bf16 path (which rounds ONLY the
        # per-row gathers, never the gram input).
        wire_bf16 = gather_dtype == "bf16" and not implicit

        def gather_side(local, block):
            # [n_dev, block+1, f] -> drop dummies -> [n_dev*block, f]
            send = local.astype(jnp.bfloat16) if wire_bf16 else local
            full = lax.all_gather(send, axis)  # ICI collective
            return full[:, :block].reshape(n_dev * block, rank)

        # per-device dummy-block padding means pads inflate only the local
        # dummy row's degree count, so ALS-WR scaling stays exact; the local
        # solve is the same MXU block-Gram path as the single-chip schedule
        v_full = gather_side(vf_l, bi)
        uf_l = _solve_blocked(
            u_br[0], u_cols[0], u_vals[0], u_w[0], v_full, bu + 1,
            block_chunk, reg, implicit, alpha, degree_scaled_reg, solver,
            gather_dtype,
        )
        u_full = gather_side(uf_l, bu)
        vf_l = _solve_blocked(
            i_br[0], i_cols[0], i_vals[0], i_w[0], u_full, bi + 1,
            block_chunk, reg, implicit, alpha, degree_scaled_reg, solver,
            gather_dtype,
        )
        return uf_l[None], vf_l[None]

    # checker off: the scan carries inside the block-Gram accumulation are
    # initialized unvarying (zeros) and become device-varying on the first
    # write, which the varying-manual-axes checker rejects; semantics are
    # unaffected
    return shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(spec,) * 10,
        out_specs=(spec, spec),
        check_vma=False,
    )(uf, vf, u_br, u_cols, u_vals, u_w, i_br, i_cols, i_vals, i_w)
