"""Worker script for the multi-host launcher tests: rendezvous over the
PIO_COORDINATOR contract, build a mesh spanning both processes, run one
sharded jit step over a global array, and verify the cross-process result.

Run by tests/test_launcher.py via MultiHostLauncher — never by pytest
directly (no test_ prefix)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def main() -> int:
    if "--fail-rank" in sys.argv:
        rank = int(os.environ.get("PIO_PROCESS_ID", "0"))
        fail_rank = int(sys.argv[sys.argv.index("--fail-rank") + 1])
        if rank == fail_rank:
            print(f"rank {rank}: simulated failure", flush=True)
            return 3
        # the surviving rank blocks in rendezvous; the launcher must
        # terminate it once the failing rank exits

    from predictionio_tpu.parallel.distributed import (
        maybe_initialize_distributed,
    )

    assert maybe_initialize_distributed(), "coordinator env contract missing"

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_proc = jax.process_count()
    n_dev = jax.device_count()
    assert n_dev == 2 * n_proc, f"expected {2 * n_proc} global devices, got {n_dev}"

    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    local = np.arange(2, dtype=np.float32) + 10.0 * jax.process_index()
    garr = jax.make_array_from_process_local_data(sharding, local, (n_dev,))

    @jax.jit
    def step(x):
        return (x * 2).sum()  # cross-process reduction

    expected = float(
        sum((np.arange(2) + 10.0 * p).sum() * 2 for p in range(n_proc))
    )
    out = float(step(garr))
    assert out == expected, f"sharded step: {out} != {expected}"

    # shard_columns with UNEVEN per-process row counts: processes must
    # coordinate one global shape (an uncoordinated build inferred a
    # different global shape per process), and the mask column must select
    # exactly the real rows even though pads sit mid-global-array
    from predictionio_tpu.parallel.ingest import shard_columns

    rank = jax.process_index()
    local_rows = 3 if rank == 0 else 5
    vals = np.full((local_rows,), float(rank + 1), np.float32)
    cols, n_local = shard_columns(
        mesh, {"v": vals}, axis="data", mask_name="ok"
    )
    assert n_local == local_rows

    @jax.jit
    def masked_sum(v, ok):
        return (v * ok.astype(v.dtype)).sum()

    got = float(masked_sum(cols["v"], cols["ok"]))
    want = float(sum((3 if p == 0 else 5) * (p + 1) for p in range(n_proc)))
    assert got == want, f"masked shard_columns sum: {got} != {want}"
    print(
        f"rank {jax.process_index()}/{n_proc}: sharded step ok ({out}), "
        f"uneven shard_columns ok ({got})",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
