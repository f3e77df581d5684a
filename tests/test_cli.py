"""CLI console tests (ref pio_tests BasicAppUsecases + CLI contract)."""

import json

import pytest

from predictionio_tpu.tools.cli import main


def run(capsys, *argv):
    code = main(list(argv))
    out = capsys.readouterr()
    return code, out.out, out.err


class TestAppCommands:
    def test_app_lifecycle(self, memory_storage, capsys):
        code, out, _ = run(capsys, "app", "new", "myapp", "--description", "d")
        assert code == 0 and "Access Key:" in out

        code, out, _ = run(capsys, "app", "list")
        assert code == 0 and "myapp" in out

        code, out, _ = run(capsys, "app", "show", "myapp")
        assert code == 0 and "App ID" in out

        code, out, err = run(capsys, "app", "new", "myapp")
        assert code != 0 and "already exists" in err

        code, out, err = run(capsys, "app", "delete", "myapp")
        assert code != 0  # no --force

        code, out, _ = run(capsys, "app", "delete", "myapp", "--force")
        assert code == 0
        code, out, _ = run(capsys, "app", "list")
        assert "myapp" not in out

    def test_channels(self, memory_storage, capsys):
        run(capsys, "app", "new", "chanapp")
        code, out, _ = run(capsys, "app", "channel-new", "chanapp", "mobile")
        assert code == 0 and "mobile" in out
        code, _, err = run(capsys, "app", "channel-new", "chanapp", "bad name!")
        assert code != 0
        code, out, _ = run(capsys, "app", "show", "chanapp")
        assert "mobile" in out
        code, out, _ = run(
            capsys, "app", "channel-delete", "chanapp", "mobile", "--force"
        )
        assert code == 0

    def test_accesskeys(self, memory_storage, capsys):
        run(capsys, "app", "new", "keyapp")
        code, out, _ = run(
            capsys, "accesskey", "new", "keyapp", "--event", "buy", "--event", "view"
        )
        assert code == 0
        key = out.strip().split()[-1]
        code, out, _ = run(capsys, "accesskey", "list", "keyapp")
        assert key in out and "buy,view" in out
        code, _, _ = run(capsys, "accesskey", "delete", key)
        assert code == 0
        code, out, _ = run(capsys, "accesskey", "list", "keyapp")
        assert key not in out

    def test_data_delete(self, memory_storage, capsys):
        run(capsys, "app", "new", "dataapp")
        app = memory_storage.get_meta_data_apps().get_by_name("dataapp")
        from predictionio_tpu.data.event import Event

        memory_storage.get_l_events().insert(
            Event(event="x", entity_type="u", entity_id="1"), app.id
        )
        code, _, _ = run(capsys, "app", "data-delete", "dataapp", "--force")
        assert code == 0
        assert list(memory_storage.get_l_events().find(app.id)) == []


class TestStatusVersion:
    def test_version(self, capsys):
        code, out, _ = run(capsys, "version")
        assert code == 0 and out.strip()

    def test_status(self, memory_storage, capsys):
        code, out, _ = run(capsys, "status")
        assert code == 0
        assert "all data objects verified" in out

    def test_status_survives_wedged_device_probe(
        self, memory_storage, capsys, monkeypatch
    ):
        """A device probe that hangs (the chip is held by another process)
        must degrade the device line, never hang or crash `pio status`."""
        import subprocess

        def fake_run(*a, **kw):
            raise subprocess.TimeoutExpired(cmd="probe", timeout=45)

        monkeypatch.setattr("subprocess.run", fake_run)
        code, out, _ = run(capsys, "status")
        assert code == 0
        assert "timed out" in out
        assert "ready to train" in out

    def test_status_survives_noisy_probe_stdout(
        self, memory_storage, capsys, monkeypatch
    ):
        """Runtime banners on the probe's stdout must not break the parse
        (the marker line is searched, not assumed to be alone)."""
        import subprocess

        def fake_run(*a, **kw):
            return subprocess.CompletedProcess(
                a, 0,
                stdout="some plugin banner\nPIO-JAX 9.9.9 4\ntrailer\n",
                stderr="",
            )

        monkeypatch.setattr("subprocess.run", fake_run)
        code, out, _ = run(capsys, "status")
        assert code == 0
        assert "jax 9.9.9; devices: 4" in out

    def test_unregister(self, capsys, tmp_path):
        # ref Console.scala:172-177: the verb is part of the CLI surface
        # (vestigial there — parsed with no dispatch case); here it is an
        # explicit, explained no-op
        code, out, _ = run(capsys, "unregister", "--engine-dir", str(tmp_path))
        assert code == 0
        assert "Nothing to unregister" in out
        assert str(tmp_path) in out


class TestImportExport:
    def test_roundtrip(self, memory_storage, capsys, tmp_path):
        run(capsys, "app", "new", "ioapp")
        events = [
            {"event": "rate", "entityType": "user", "entityId": f"u{i}",
             "targetEntityType": "item", "targetEntityId": "i1",
             "properties": {"rating": float(i)},
             "eventTime": f"2024-01-0{i+1}T00:00:00.000Z"}
            for i in range(3)
        ]
        src = tmp_path / "events.json"
        src.write_text("\n".join(json.dumps(e) for e in events))
        code, out, _ = run(capsys, "import", "--appname", "ioapp", "--input", str(src))
        assert code == 0 and "Imported 3 events" in out

        dst = tmp_path / "out.json"
        code, out, _ = run(capsys, "export", "--appname", "ioapp", "--output", str(dst))
        assert code == 0 and "Exported 3 events" in out
        lines = [json.loads(l) for l in dst.read_text().splitlines()]
        assert {l["entityId"] for l in lines} == {"u0", "u1", "u2"}

        npz = tmp_path / "out.npz"
        code, out, _ = run(
            capsys, "export", "--appname", "ioapp", "--output", str(npz),
            "--format", "npz",
        )
        assert code == 0
        import numpy as np

        data = np.load(str(npz), allow_pickle=True)
        assert len(data["entity_ids"]) == 3

        # parquet round-trip through the CLI surface (EventsToFile.scala's
        # --format parquet switch)
        pytest.importorskip("pyarrow")
        pqf = tmp_path / "out.parquet"
        code, out, _ = run(
            capsys, "export", "--appname", "ioapp", "--output", str(pqf),
            "--format", "parquet",
        )
        assert code == 0 and "Exported 3 events" in out
        run(capsys, "app", "new", "ioapp2")
        code, out, _ = run(
            capsys, "import", "--appname", "ioapp2", "--input", str(pqf)
        )
        assert code == 0 and "Imported 3 events" in out

    def test_import_bad_line_reports_position(self, memory_storage, capsys, tmp_path):
        run(capsys, "app", "new", "badapp")
        src = tmp_path / "bad.json"
        src.write_text('{"event": "x", "entityType": "u", "entityId": "1"}\n{broken\n')
        code, _, err = run(capsys, "import", "--appname", "badapp", "--input", str(src))
        assert code != 0 and ":2:" in err


class TestTemplates:
    def test_list(self, capsys):
        code, out, _ = run(capsys, "template", "list")
        assert code == 0 and "recommendation" in out

    def test_get(self, capsys, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        code, out, _ = run(capsys, "template", "get", "recommendation", "mine")
        assert code == 0
        variant = json.loads((tmp_path / "mine" / "engine.json").read_text())
        assert variant["engineFactory"].endswith("engine_factory")
        assert (tmp_path / "mine" / "template.json").exists()


class TestEngineLifecycleCLI:
    def test_build_train_batchpredict(self, memory_storage, capsys, tmp_path):
        # seed app + events
        run(capsys, "app", "new", "MyApp1")
        app = memory_storage.get_meta_data_apps().get_by_name("MyApp1")
        import numpy as np

        from predictionio_tpu.data.datamap import DataMap
        from predictionio_tpu.data.event import Event

        rng = np.random.default_rng(0)
        events = [
            Event(
                event="rate",
                entity_type="user",
                entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{rng.integers(0, 10)}",
                properties=DataMap({"rating": float(rng.integers(1, 6))}),
            )
            for u in range(20)
            for _ in range(5)
        ]
        memory_storage.get_l_events().insert_batch(events, app.id)

        engine_dir = "predictionio_tpu/models/recommendation"
        code, out, _ = run(capsys, "build", "--engine-dir", engine_dir)
        assert code == 0 and "ready" in out

        code, out, _ = run(capsys, "train", "--engine-dir", engine_dir)
        assert code == 0 and "Engine instance ID" in out
        # the train says where it ran, read from its arrays
        (trained_on,) = [ln for ln in out.splitlines() if ln.startswith("Trained on: ")]
        device = json.loads(trained_on[len("Trained on: "):])
        assert device["platform"] == "cpu" and device["deviceKind"]
        assert 1 <= device["deviceCount"] <= device["visibleDevices"]

        queries = tmp_path / "queries.json"
        queries.write_text('{"user": "u1", "num": 3}\n{"user": "u2", "num": 2}\n')
        out_path = tmp_path / "predictions.json"
        code, out, _ = run(
            capsys,
            "batchpredict",
            "--engine-dir", engine_dir,
            "--input", str(queries),
            "--output", str(out_path),
        )
        assert code == 0
        preds = [json.loads(l) for l in out_path.read_text().splitlines()]
        assert len(preds) == 2
        assert len(preds[0]["itemScores"]) == 3
        assert len(preds[1]["itemScores"]) == 2

        # offline source straight off the event store, writeback included
        # (the ISSUE-14 CLI surface; pipeline mechanics in
        # tests/test_batch_predict.py)
        status_path = tmp_path / "bp.status.json"
        code, out, _ = run(
            capsys,
            "batchpredict",
            "--engine-dir", engine_dir,
            "--from-events",
            "--app-name", "MyApp1",
            "--to-events",
            "--query-num", "3",
            "--output", str(out_path),
            "--status-file", str(status_path),
        )
        assert code == 0 and "20 queries" in out  # 20 distinct users
        assert json.loads(status_path.read_text())["state"] == "done"

        # a mixed file keeps going (line-aligned error object), but a run
        # where EVERY line fails exits nonzero
        queries.write_text("BROKEN1\nBROKEN2\n")
        code, _, err = run(
            capsys,
            "batchpredict",
            "--engine-dir", engine_dir,
            "--input", str(queries),
            "--output", str(out_path),
        )
        assert code != 0 and "every query line failed" in err
        rows = [json.loads(l) for l in out_path.read_text().splitlines()]
        assert [r["line"] for r in rows] == [1, 2]

        # --from-events and --input are mutually exclusive
        code, _, err = run(
            capsys,
            "batchpredict",
            "--engine-dir", engine_dir,
            "--from-events",
            "--input", str(queries),
        )
        assert code != 0 and "mutually exclusive" in err
