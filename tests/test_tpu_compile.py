"""The main path's kernels compiled for the chip, with no chip: the TPU
compiler is installed here and compiles for a v5e that is described, not
attached. It refuses what interpret mode lets through (a tile over the
VMEM limit, a slice off the tiling, a program over the device's memory),
so these guard every later edit at no chip time. Nothing runs: no result
and no time comes from here.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports every test file.
Keep every such compile in THIS file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# rank and systems: the template's default over ML-20M's items, the
# benchmark's rank over its users, rank 64 (one 128-lane row a tile), and
# ALX's rank over a quarter of the users (the raised VMEM limit)
@pytest.mark.parametrize(
    "f, n", [(10, 26_745), (32, 138_494), (64, 138_494), (128, 34_624)]
)
def test_the_cg_tile_kernel_compiles_for_v5e(one_chip, f, n):
    from predictionio_tpu.ops.spd_solve import _cg_tiles

    compiled = (
        jax.jit(_cg_tiles)
        .lower(_shape(one_chip, (f, f, n)), _shape(one_chip, (f, n)))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_als_step_at_the_benchmark_shape_holds_its_systems_unpadded(
    one_chip, monkeypatch
):
    """``_als_step`` at ML-20M, rank 32, as the chip traces it: the solve
    is the kernel, and the temporaries are 3.5 GB (7.1 GB while the CG read
    ``[n, f, f]`` padded to 128 lanes: PERF.md section 4)."""
    from predictionio_tpu.ops import als

    # code that asks for the backend sees the CPU here; the chip's branch
    # is steered from the test, not by an option of the program
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_users, n_items, d, nb_u, nb_i, f = 138_493, 26_744, 128, 258_304, 172_160, 32
    tables = lambda nb: (
        _shape(one_chip, (nb,), jnp.int32),
        _shape(one_chip, (nb, d), jnp.int32),
        _shape(one_chip, (nb, d)),
        _shape(one_chip, (nb, d), jnp.int8),
    )
    compiled = als._als_step.lower(
        _shape(one_chip, (n_users + 1, f)), _shape(one_chip, (n_items + 1, f)),
        *tables(nb_u), *tables(nb_i),
        n_users=n_users, n_items=n_items, reg=0.05, implicit=False, alpha=1.0,
        block_chunk=16384 // d, degree_scaled_reg=True, solver="cg",
        gather_dtype="f32",
    ).compile()
    text = compiled.as_text()
    assert "solve/pallas_call" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4.0e9
