"""What the bring-up on the chip put on the main path, held without one.

- one process for each chip: local ``--num-hosts N`` / ``--fleet N`` /
  ``--fleet-max N`` with N > 1 are refused unless ``JAX_PLATFORMS=cpu``
  or ``--hosts``. tests/conftest.py pins ``JAX_PLATFORMS=cpu`` for the
  suite, so these tests set ``tpu`` themselves; the refusals read the
  environment only and come before any backend is asked for.
- a serving warmup failure fails the start; ``/reload`` and candidate
  staging keep the lane that serves and report.
- the device report: ``xray.live_devices``, ``GET /``'s ``device``
  (``Trained on:`` is asserted in tests/test_cli.py's train test).
- ``xlaCompiles`` counts backend compiles.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# one process for each chip
# ---------------------------------------------------------------------------


def _pio(tmp_path, *argv: str, platform: str = "tpu"):
    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": platform,
        "PIO_FS_BASEDIR": str(tmp_path / "store"),
    }
    return subprocess.run(
        [sys.executable, "-m", "predictionio_tpu.tools.cli", *argv],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path), env=env,
    )


def test_train_refuses_local_num_hosts_on_the_chip(tmp_path):
    proc = _pio(tmp_path, "train", "--num-hosts", "2", "--engine-dir", str(tmp_path))
    assert proc.returncode != 0
    assert "each would claim every chip" in proc.stderr
    assert "JAX_PLATFORMS=cpu" in proc.stderr  # names the ways out


@pytest.mark.parametrize(
    "flags",
    [("--fleet", "2"), ("--fleet", "1", "--autoscale", "--fleet-max", "2")],
)
def test_deploy_refuses_a_local_device_fleet_on_the_chip(tmp_path, flags):
    proc = _pio(tmp_path, "deploy", "--engine-dir", str(tmp_path), *flags)
    assert proc.returncode != 0
    assert "a chip belongs to one process" in proc.stderr


def test_refusal_is_for_shared_chips_only(monkeypatch):
    from predictionio_tpu.fleet.launch import _refuse_shared_chips

    local, placed = argparse.Namespace(hosts=None), argparse.Namespace(hosts="h1,h2")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(ValueError, match="2 device-class workers"):
        _refuse_shared_chips(2, local)
    _refuse_shared_chips(1, local)  # one worker has the chip to itself
    _refuse_shared_chips(2, placed)  # --hosts: placement is per box
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    _refuse_shared_chips(2, local)  # the CPU is shared freely


# ---------------------------------------------------------------------------
# warmup failures
# ---------------------------------------------------------------------------


class LoweringRefused(Exception):
    """Stands for what a device compiler throws at a kernel it refuses:
    neither a ValueError nor a RuntimeError."""


def _refuse_warmup(monkeypatch):
    from tests.sample_engine import Algo0

    def warmup_serving(self, model, max_batch):
        raise LoweringRefused("the compiler refused the serving program")

    monkeypatch.setattr(Algo0, "warmup_serving", warmup_serving)


def test_warmup_failure_fails_the_start(tmp_path, monkeypatch):
    from tests.test_registry import _registry_server

    server, _, _ = _registry_server(tmp_path)
    _refuse_warmup(monkeypatch)
    with pytest.raises(LoweringRefused):
        asyncio.run(server.start())


def test_warmup_failure_on_reload_and_staging_keeps_the_lane(tmp_path, monkeypatch):
    from tests.test_registry import _registry_server, _run_server

    server, _, (id1, _) = _registry_server(tmp_path)

    async def body(client):
        _refuse_warmup(monkeypatch)
        # the newer instance cannot be warmed: the reload reports, commits
        # nothing, and the lane that served keeps serving
        resp = await client.post("/reload")
        assert resp.status == 500
        assert "refused the serving program" in (await resp.json())["message"]
        # the same for a candidate: reported, not staged
        resp = await client.post("/models/candidate", json={"version": "v000002"})
        assert resp.status == 500
        assert "refused the serving program" in (await resp.json())["message"]
        status = await (await client.get("/")).json()
        assert status["engineInstanceId"] == id1
        assert status["modelVersion"] == "v000001"
        assert status["rollout"]["candidate"] is None
        resp = await client.post("/queries.json", json={"qid": 7, "user": "u7"})
        assert resp.status == 200

    _run_server(body, server)
    assert server.instance_id == id1


# ---------------------------------------------------------------------------
# the device report
# ---------------------------------------------------------------------------


def test_live_devices_reads_the_arrays(tmp_path):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.obs import xray

    held = jax.device_put(jnp.ones(4), jax.devices()[3])
    report = xray.live_devices()
    assert report["platform"] == "cpu" == held.devices().pop().platform
    assert report["deviceKind"] == jax.devices()[0].device_kind
    assert 1 <= report["deviceCount"] <= report["visibleDevices"] == jax.device_count()


def test_server_status_carries_the_device(tmp_path):
    import jax.numpy as jnp

    from predictionio_tpu.obs import xray
    from tests.test_registry import _registry_server, _run_server

    server, _, _ = _registry_server(tmp_path)
    resident = jnp.ones(4)  # a pure-host engine holds no array of its own

    async def body(client):
        status = await (await client.get("/")).json()
        assert status["device"] is None  # not warmed yet: nothing claimed
        server._warmup()
        status = await (await client.get("/")).json()
        assert status["device"] == xray.live_devices()
        assert status["device"]["platform"] == "cpu"

    _run_server(body, server)
    del resident


def test_train_profile_counts_backend_compiles():
    """jax 0.9.0 reports compiles as durations only: a profile around one
    fresh jit carries a count and seconds, both from the same listener."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.obs import xray

    profile = xray.TrainProfile("t")
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(5)).block_until_ready()
    record = profile.finish().to_json_dict()
    assert record["xlaCompiles"] >= 1 and record["xlaCompileS"] > 0.0
