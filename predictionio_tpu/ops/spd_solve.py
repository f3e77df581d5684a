"""Batched small-SPD solve: Jacobi-CG over systems held batch-last, a tile of
lanes at a time in VMEM on the chip.

The ALS half-solve ends with ~n_entities independent [f, f] SPD systems
(f = rank, 10-64). ``_cg_body`` is the ONE conjugate gradient of the
repository: ``ops/als._batched_spd_solve`` (every ALS half-step, sharded or
not) and the stream layer's fold-in (``batched_spd_solve_auto``) run it.

The layout is the point. A TPU array keeps its last axis on 128 lanes and
its second-to-last on 8 sublanes, so ``[n, f, f]`` float32 at f = 32 takes
four times its bytes (about twenty times at f = 10), and CG re-reads the
systems f + 5 times: at ML-20M that was 37 passes over 2.3 GB on the user
side, the heaviest operation of a whole train (PERF.md section 6, PR 27).
So the solve turns its operands ONCE at the door: the batch goes last
(``[f, f, n]``, ``[f, n]``), n lies on the lanes and pads by at most 127
systems, f lies on sublanes and a major axis and pads not at all at 32 or
64. The matvec is then a float32 multiply-and-add over the MAJOR axis,
``sum_j A[j, i, :] * p[j, :]``: no lane reduction and no MXU pass, exact as
XLA's default for the old batched ``dot_general`` was.

On a TPU the same body runs as a Pallas kernel over ``[f, f, T]`` tiles of
lanes (``_cg_tiles``): systems are independent along the lanes, so a tile
is solved to the end in VMEM and the systems leave HBM once, not f + 5
times; the matvec stays on the VPU. Off the chip the body runs as plain
XLA. Measured on a v5e in ``rec-als-ml20m.train`` (rank 32, both sides;
PERF.md section 6, PR 27), seconds of ``solve`` an iteration: 0.180 as it
was (``[n, f, f]`` padded to 128 lanes), 0.034 batch-last in plain XLA,
0.007 as this kernel. The MXU kernel over ``[64, f, f]`` blocks that lived
here before (``cg_fused``) took 0.256 and is gone.

Reference analog: the per-entity normal-equation solves inside MLlib
ALS (``CholeskySolver`` in the reference's Spark stack); redesigned
TPU-first rather than translated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _cg_lanes(At, bt):
    """Jacobi-CG over systems held batch-last: ``At[j, i, :]`` is entry
    (i, j) of each system, ``bt`` is ``[f, n]``; returns x as ``[f, n]``.
    Same preconditioner, same f + 4 steps (exact termination on an
    f-dimensional Krylov space), same update order as ever; every vector
    is ``[f, n]`` and every reduction runs over f, never over the lanes."""
    f = At.shape[0]
    eye = jnp.eye(f, dtype=At.dtype)
    dinv = 1.0 / jnp.sum(At * eye[:, :, None], axis=0)  # [f, n]

    def mv(p):
        with jax.named_scope("matvec"):
            return jnp.sum(At * p[:, None, :], axis=0)

    def step(_, st):
        x, r, p, rz = st
        Ap = mv(p)
        alpha = rz / jnp.maximum(jnp.sum(p * Ap, 0), 1e-30)
        x = x + alpha[None, :] * p
        r = r - alpha[None, :] * Ap
        z = r * dinv
        rz2 = jnp.sum(r * z, 0)
        p = z + (rz2 / jnp.maximum(rz, 1e-30))[None, :] * p
        return x, r, p, rz2

    x = bt * dinv
    r = bt - mv(x)
    z = r * dinv
    return jax.lax.fori_loop(0, f + 4, step, (x, r, z, jnp.sum(r * z, 0)))[0]


def _tile_row_bytes(f: int) -> int:
    """Bytes of one lane of a float32 ``[f, f, T]`` tile as VMEM holds it
    (rows padded to the 8 sublanes)."""
    return f * -(-f // 8) * 8 * 4


def _lanes_per_tile(f: int) -> int:
    """Lanes (systems) a grid cell: the tile held to 2 MiB and to whole
    128-lane rows, one row at least: 512 at f = 32, 128 from f = 48 up. At
    4 MiB (f = 32, T = 1,024) the kernel's stack passes the 16 MiB of scoped
    VMEM; a taller single row (f over 64) raises that limit with it
    (``_cg_tiles``). 0 above f = 128, where one row passes 8 MiB: no kernel
    there (ahead-of-time compiles for a v5e, PR 27)."""
    if _tile_row_bytes(f) * 128 > 8 << 20:
        return 0
    return max(128, min(512, (2 << 20) // _tile_row_bytes(f) // 128 * 128))


def _kernel(a_ref, b_ref, x_ref):
    x_ref[...] = _cg_lanes(a_ref[...], b_ref[...])


def _cg_tiles(At, bt, interpret: bool = False):
    """``_cg_lanes`` a tile of lanes at a time, each tile held in VMEM from
    its first matvec to its last. The last tile may hang over n: its
    surplus lanes hold whatever the read brought, are solved like the rest
    (no lane ever meets another) and are dropped by the write."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f, n = bt.shape
    tile = _lanes_per_tile(f)
    return pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(n, tile),),
        in_specs=[
            pl.BlockSpec((f, f, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((f, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((f, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((f, n), bt.dtype),
        compiler_params=pltpu.CompilerParams(
            # XLA may fuse each operand's producer (the regularisation's
            # add, b's turn) into the kernel's reads. As a bare custom call
            # the kernel changed what XLA kept in VMEM across `_als_step`:
            # the item side's scatter-add of b went to HBM (55 ms an
            # iteration) and `gram` took 44 ms more, against the 27 ms the
            # kernel saves (v5e, PERF.md section 6, PR 27)
            allow_input_fusion=[True, True],
            # two buffers of the tile, the matvec's product and the vectors
            vmem_limit_bytes=max(16 << 20, 8 * _tile_row_bytes(f) * tile),
        ),
        interpret=interpret,
    )(At, bt)


def _cg_body(A, b):
    """Solve ``A [n, f, f] x = b [n, f]`` by Jacobi-CG; returns ``[n, f]``.
    One transposition in and one out buy every matvec over the systems at
    their own bytes (module docstring); on the chip the body runs tile by
    tile in VMEM, elsewhere (and above rank 128) as plain XLA."""
    At, bt = jnp.transpose(A, (2, 1, 0)), b.T
    if jax.default_backend() == "tpu" and _lanes_per_tile(A.shape[-1]):
        return _cg_tiles(At, bt).T
    return _cg_lanes(At, bt).T


@jax.jit
def batched_spd_solve_auto(A: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The jitted entry for callers outside a program of their own (the
    stream layer's fold-in of a handful of systems): the one CG, on every
    platform."""
    return _cg_body(A, b)
