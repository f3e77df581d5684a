"""The batched SPD solve: ONE Jacobi-CG over systems held batch-last
(ops/spd_solve.py), against a direct Cholesky solve, against float64, through
``als_train`` explicit and implicit, through the mesh-sharded trainer, and
held to its layout by the jaxpr."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.als import _batched_spd_solve
from predictionio_tpu.ops.spd_solve import (
    _cg_lanes,
    _cg_tiles,
    _lanes_per_tile,
    batched_spd_solve_auto,
)

RANKS = (10, 32, 64)
# one system, the fold-in's handful, either side of a 128-lane row, and a
# batch that is no multiple of 128
BATCHES = (1, 3, 127, 128, 1000)


def _spd_batch(n, f, seed=0, reg=0.05):
    """ALS-shaped systems: Gram matrices of random data + scaled ridge."""
    rng = np.random.default_rng(seed)
    G = rng.normal(size=(n, 3 * f, f)).astype(np.float32)
    A = np.einsum("bdf,bdg->bfg", G, G) + reg * (3 * f) * np.eye(f, dtype=np.float32)
    b = rng.normal(size=(n, f)).astype(np.float32)
    return jnp.asarray(A), jnp.asarray(b)


_solve_cg = jax.jit(lambda A, b: _batched_spd_solve(A, b, "cg"))


class TestLaneBatchedCG:
    @pytest.mark.parametrize("n", BATCHES)
    @pytest.mark.parametrize("f", RANKS)
    def test_matches_cholesky(self, f, n):
        A, b = _spd_batch(n, f, seed=f + n)
        x_chol = _batched_spd_solve(A, b, "cholesky")
        np.testing.assert_allclose(
            np.asarray(_solve_cg(A, b)), np.asarray(x_chol), rtol=0, atol=1e-5
        )

    @pytest.mark.parametrize("n", BATCHES)
    @pytest.mark.parametrize("f", RANKS)
    def test_matches_a_float64_solve(self, f, n):
        """float32 throughout and f + 4 steps: the solution is float64's to
        float32 rounding (systems conditioned like the trainer's)."""
        A, b = _spd_batch(n, f, seed=100 + f + n)
        x64 = np.linalg.solve(
            np.asarray(A, np.float64), np.asarray(b, np.float64)[..., None]
        )[..., 0]
        x = np.asarray(_solve_cg(A, b))
        assert x.shape == (n, f) and x.dtype == np.float32
        np.testing.assert_allclose(x, x64, rtol=0, atol=1e-6)

    def test_every_spelling_runs_the_one_cg(self):
        """``cg_fused`` was a Pallas kernel that lost on the chip (PERF.md
        section 6, PR 27); the spelling stays readable and runs the one CG."""
        A, b = _spd_batch(33, 16, seed=1)
        x_cg = _batched_spd_solve(A, b, "cg")
        x_fused = _batched_spd_solve(A, b, "cg_fused")
        np.testing.assert_array_equal(np.asarray(x_fused), np.asarray(x_cg))

    def test_lane_padding_does_not_reach_real_systems(self):
        """n not a multiple of the 128 lanes: a system's solution is the
        same alone, among 5 and among 133."""
        A, b = _spd_batch(133, 8, seed=2)
        x_all = np.asarray(_solve_cg(A, b))
        x_five = np.asarray(_solve_cg(A[:5], b[:5]))
        x_one = np.asarray(_solve_cg(A[:1], b[:1]))
        assert x_five.shape == (5, 8)
        np.testing.assert_allclose(x_five, x_all[:5], rtol=0, atol=1e-6)
        np.testing.assert_allclose(x_one, x_all[:1], rtol=0, atol=1e-6)

    def test_auto_is_the_one_cg_on_every_platform(self):
        """The stream layer's entry: jitted, host arrays in, the same body
        as ``solver="cg"``."""
        assert jax.default_backend() == "cpu"
        A, b = _spd_batch(9, 8, seed=3)
        x = batched_spd_solve_auto(np.asarray(A), np.asarray(b))
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(_solve_cg(A, b)), atol=1e-6
        )

    def test_the_matvec_in_the_loop_reads_the_systems_batch_last(self):
        """No later edit brings the padded layout back unseen: inside the
        CG loop, every operand of the ``matvec`` scope that holds the
        systems (rank 3) has the batch as its LAST axis."""
        n, f = 1000, 8
        A, b = _spd_batch(n, f, seed=4)
        jaxpr = jax.make_jaxpr(lambda A, b: _batched_spd_solve(A, b, "cg"))(A, b)

        def loops(jp):
            for eqn in jp.eqns:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    if eqn.primitive.name in ("scan", "while"):
                        yield sub
                    yield from loops(sub)

        def eqns_of(jp):
            for eqn in jp.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from eqns_of(sub)

        systems = [
            v.aval.shape
            for body in loops(jaxpr.jaxpr)
            for eqn in eqns_of(body)
            if "matvec" in str(eqn.source_info.name_stack)
            for v in eqn.invars
            if hasattr(v, "aval") and len(getattr(v.aval, "shape", ())) >= 3
        ]
        assert systems, "no matvec scope over the systems inside the CG loop"
        for shape in systems:
            assert shape[-1] == n and n not in shape[:-1], shape


class TestTileKernel:
    """What ``cg`` runs on the chip: the same body a tile of lanes at a
    time, here interpreted (the off-TPU execution of the kernel's code)."""

    @pytest.mark.parametrize(
        "f, n", [(10, 3), (10, 700), (32, 127), (32, 128), (32, 700), (64, 130)]
    )
    def test_matches_the_plain_body(self, f, n):
        """Whole tiles, one short tile, and a last tile that hangs over n:
        the surplus lanes never reach a real system."""
        A, b = _spd_batch(n, f, seed=200 + f + n)
        At, bt = jnp.transpose(A, (2, 1, 0)), b.T
        x = _cg_tiles(At, bt, interpret=True)
        assert x.shape == (f, n)
        assert bool(jnp.all(jnp.isfinite(x)))
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(_cg_lanes(At, bt)), rtol=0, atol=1e-6
        )

    def test_tiles_by_rank(self):
        """A 2 MiB tile of whole 128-lane rows, one row at least, and no
        kernel where a single row passes 8 MiB."""
        assert [_lanes_per_tile(f) for f in (10, 32, 40, 64, 128, 129)] == [
            512, 512, 256, 128, 128, 0,
        ]


def _ratings(n_u, n_i, nnz, rank, seed):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_u, nnz).astype(np.int32)
    i = rng.integers(0, n_i, nnz).astype(np.int32)
    U = rng.normal(size=(n_u, rank))
    V = rng.normal(size=(n_i, rank))
    return u, i, np.sum(U[u] * V[i], axis=1).astype(np.float32)


def _rmse(uf, vf, u, i, v):
    pred = (np.asarray(uf) @ np.asarray(vf).T)[u, i]
    return float(np.sqrt(np.mean((pred - v) ** 2)))


class TestALSThroughTheSolve:
    def test_train_quality_parity(self):
        """als_train reaches the same quality under every CG spelling."""
        from predictionio_tpu.ops.als import ALSConfig, als_train

        u, i, v = _ratings(120, 80, 4000, 4, seed=7)

        def rmse(solver):
            cfg = ALSConfig(rank=4, iterations=6, reg=0.05, solver=solver)
            return _rmse(*als_train(u, i, v, 120, 80, cfg), u, i, v)

        r_cg, r_fused = rmse("cg"), rmse("cg_fused")
        assert abs(r_cg - r_fused) < 1e-4, (r_cg, r_fused)

    @pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
    def test_three_iterations_equal_cholesky(self, implicit):
        """Three sweeps through the lane-batched CG give what the direct
        solve gives, within the tolerance the parity test above holds."""
        from predictionio_tpu.ops.als import ALSConfig, als_train

        u, i, v = _ratings(120, 80, 4000, 4, seed=11)
        if implicit:
            v = np.abs(v)

        def factors(solver):
            cfg = ALSConfig(
                rank=8, iterations=3, reg=0.05, implicit=implicit, alpha=2.0,
                solver=solver,
            )
            return als_train(u, i, v, 120, 80, cfg)

        uf, vf = factors("cg")
        uf_c, vf_c = factors("cholesky")
        assert abs(_rmse(uf, vf, u, i, v) - _rmse(uf_c, vf_c, u, i, v)) < 1e-4
        np.testing.assert_allclose(np.asarray(uf), np.asarray(uf_c), rtol=0, atol=1e-4)
        np.testing.assert_allclose(np.asarray(vf), np.asarray(vf_c), rtol=0, atol=1e-4)

    def test_three_sharded_iterations_equal_cholesky(self):
        """The turn is local to a shard: each device of the CPU mesh solves
        its own entity block inside shard_map."""
        from predictionio_tpu.ops.als import ALSConfig
        from predictionio_tpu.ops.als_sharded import als_train_sharded

        u, i, r = _ratings(50, 37, 2000, 4, seed=13)

        def factors(solver):
            cfg = ALSConfig(rank=8, iterations=3, reg=0.05, chunk=512, solver=solver)
            return als_train_sharded(u, i, r, 50, 37, cfg)

        uf, vf = factors("cg")
        uf_c, vf_c = factors("cholesky")
        assert abs(_rmse(uf, vf, u, i, r) - _rmse(uf_c, vf_c, u, i, r)) < 1e-4
        np.testing.assert_allclose(uf, uf_c, rtol=0, atol=1e-4)
        np.testing.assert_allclose(vf, vf_c, rtol=0, atol=1e-4)

    def test_bad_solver_rejected(self):
        from predictionio_tpu.ops.als import ALSConfig

        with pytest.raises(ValueError, match="cg_fused"):
            ALSConfig(solver="newton")

    def test_sharded_path_parity(self):
        """Every CG spelling flows through the mesh-sharded trainer (the
        solver runs inside shard_map on each device's entity block) with
        identical results."""
        from predictionio_tpu.ops.als import ALSConfig
        from predictionio_tpu.ops.als_sharded import als_train_sharded

        u, i, r = _ratings(50, 37, 2000, 4, seed=0)

        def factors(solver):
            cfg = ALSConfig(rank=8, iterations=6, reg=0.05, chunk=512, solver=solver)
            return als_train_sharded(u, i, r, 50, 37, cfg)

        uf_cg, vf_cg = factors("cg")
        uf_f, vf_f = factors("cg_fused")
        np.testing.assert_allclose(uf_f, uf_cg, rtol=0, atol=1e-4)
        np.testing.assert_allclose(vf_f, vf_cg, rtol=0, atol=1e-4)
