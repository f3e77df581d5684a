"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md section 4): Spark
``master=local[*]`` becomes ``JAX_PLATFORMS=cpu`` with
``--xla_force_host_platform_device_count=8`` so mesh/sharding logic is
exercised without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the environment may name the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

# Template DataSources read through the snapshot cache by default; tests
# must never write shards into the developer's ~/.pio_store
_snap_dir = tempfile.mkdtemp(prefix="pio_test_snapshots_")
os.environ["PIO_SNAPSHOT_DIR"] = _snap_dir
atexit.register(shutil.rmtree, _snap_dir, ignore_errors=True)

import pytest  # noqa: E402

from predictionio_tpu.data.storage.memory import MemoryStorageClient  # noqa: E402
from predictionio_tpu.data.storage.registry import Storage  # noqa: E402


@pytest.fixture
def memory_storage(monkeypatch):
    """An isolated Storage wired entirely to the in-memory backend."""
    storage = Storage(
        env={
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        }
    )
    monkeypatch.setattr(Storage, "_singleton", storage)
    return storage


@pytest.fixture
def sqlite_storage(tmp_path, monkeypatch):
    """An isolated Storage on a throwaway SQLite file."""
    storage = Storage(
        env={
            "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQL_PATH": str(tmp_path / "pio.db"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
        }
    )
    monkeypatch.setattr(Storage, "_singleton", storage)
    return storage
