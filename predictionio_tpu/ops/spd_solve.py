"""Batched small-SPD solve with a VMEM-resident fused-CG pallas kernel.

The ALS half-solve ends with ~n_entities independent [f, f] SPD systems
(f = rank, 16-64). The stock path (``ops/als.py:_batched_spd_solve``)
runs Jacobi-preconditioned CG for f+4 iterations as whole-array jnp ops:
every iteration re-reads the entire [n, f, f] A tensor from HBM — at
ML-20M that is 36 passes over ~680 MB per side, ~70% of the iteration's
mandatory memory traffic (the traffic model is
``ops/als.solver_hbm_bytes_per_iter``; PERF.md states it).

This kernel runs the IDENTICAL algorithm — same preconditioner, same
f+4 exact-termination iteration count, same update order, so results
match to float rounding — but tiles A into VMEM once and keeps every CG
vector on-chip: HBM traffic drops to one read of A + the vectors, and
the per-iteration matvecs become MXU ``dot_general``s over the resident
tile. One pallas grid cell handles ``bs`` systems (``_systems_per_cell``:
64 at f = 32).

Reference analog: the per-entity normal-equation solves inside MLlib
ALS (``CholeskySolver`` in the reference's Spark stack); redesigned
TPU-first rather than translated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _cg_body(A, b, iters: int, precision=None):
    """THE Jacobi-CG used everywhere: ops/als.py's stock ``cg`` branch
    and the pallas kernel both run this body, so the fused kernel's
    'identical algorithm' parity contract cannot silently drift. The
    iterations are a ``lax.fori_loop`` in both: unrolled inside the kernel,
    the f+4 = 36 iterations at f = 32 took Mosaic 169 s to compile against
    1.8 s for the loop (libtpu 0.0.34, ahead-of-time compile for a v5e).

    ``precision`` is the matvec's: XLA computes this batched f32 matvec
    exactly at its default (1e-6 from a float64 solve on a v5e), while
    Mosaic's default is one bf16 pass (2e-2), so the kernel asks for
    ``HIGHEST`` to keep the two paths within float rounding."""
    f = A.shape[-1]
    eye = jnp.eye(f, dtype=A.dtype)
    dinv = 1.0 / jnp.sum(A * eye, axis=-1)  # diagonal without jnp.diagonal

    def mv(x):
        with jax.named_scope("matvec"):
            return jax.lax.dot_general(
                A, x[..., None], (((2,), (1,)), ((0,), (0,))),
                precision=precision,
                preferred_element_type=jnp.float32,
            )[..., 0]

    def step(_, st):
        x, r, p, rz = st
        Ap = mv(p)
        alpha = rz / jnp.maximum(jnp.sum(p * Ap, -1), 1e-30)
        x = x + alpha[:, None] * p
        r = r - alpha[:, None] * Ap
        z = r * dinv
        rz2 = jnp.sum(r * z, -1)
        p = z + (rz2 / jnp.maximum(rz, 1e-30))[:, None] * p
        return x, r, p, rz2

    x = b * dinv
    r = b - mv(x)
    z = r * dinv
    return jax.lax.fori_loop(0, iters, step, (x, r, z, jnp.sum(r * z, -1)))[0]


def _kernel(a_ref, b_ref, x_ref, *, iters: int):
    x_ref[...] = _cg_body(
        a_ref[...], b_ref[...], iters, precision=jax.lax.Precision.HIGHEST
    )


def _systems_per_cell(f: int) -> int:
    """Systems per grid cell: the [bs, f, f] f32 tile, as VMEM pads it
    (rows to 8, lanes to 128), held to 1 MiB and to a multiple of the
    8-row sublane tile. At 2 MiB (128 systems at f = 32) the kernel's stack
    passes the 16 MiB scoped-VMEM limit with the matvec at HIGHEST."""
    padded = -(-f // 8) * 8 * -(-f // 128) * 128 * 4
    return max(8, min(128, (1 << 20) // padded // 8 * 8))


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def batched_spd_solve_fused(
    A: jnp.ndarray,  # [n, f, f] SPD (regularized normal equations)
    b: jnp.ndarray,  # [n, f]
    bs: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Solve n independent SPD systems; one HBM read of A total.

    ``bs`` systems per grid cell (default: what fits, ``_systems_per_cell``).
    Pads n up to a multiple of ``bs`` with identity systems (solution 0)
    — the pad rows are sliced off before returning.
    """
    from jax.experimental import pallas as pl

    n, f = A.shape[0], A.shape[-1]
    iters = f + 4
    bs = bs or _systems_per_cell(f)
    pad = (-n) % bs
    if pad:
        eye = jnp.broadcast_to(jnp.eye(f, dtype=A.dtype), (pad, f, f))
        A = jnp.concatenate([A, eye])
        b = jnp.concatenate([b, jnp.zeros((pad, f), b.dtype)])
    n_pad = A.shape[0]
    out = pl.pallas_call(
        functools.partial(_kernel, iters=iters),
        grid=(n_pad // bs,),
        in_specs=[
            pl.BlockSpec((bs, f, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((bs, f), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bs, f), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, f), jnp.float32),
        interpret=interpret,
    )(A.astype(jnp.float32), b.astype(jnp.float32))
    return out[:n]


def batched_spd_solve_auto(A: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Fused kernel on TPU; the identical-algorithm jnp path elsewhere
    (same platform contract as ops/attention.fused_attention)."""
    if jax.default_backend() == "tpu":
        return batched_spd_solve_fused(A, b)
    return _cg_body(A, b, A.shape[-1] + 4)
