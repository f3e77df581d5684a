"""Offline mega-batch prediction (ISSUE 14, docs/batch_predict.md):
streaming sources, the double-buffered pipeline and its tiling contract,
atomic/DAO writeback sinks, line-aligned error semantics, the online/offline
exactness contract, and the `pio top --batchpredict` progress line."""

import asyncio
import json
import os

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from predictionio_tpu.controller.base import BaseAlgorithm
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.models.recommendation import engine_factory
from predictionio_tpu.models.recommendation.engine import (
    ALSAlgorithm,
    ALSAlgorithmParams,
    ALSModel,
    Serving,
)
from predictionio_tpu.workflow.batch_predict import (
    BatchPredictInstruments,
    EventStoreSink,
    FileSink,
    MemorySink,
    OutRow,
    StatusFile,
    iter_event_users,
    iter_query_file,
    run_batch_predict,
    run_batch_predict_on,
    run_pipeline,
)

APP_NAME = "MyApp1"  # the recommendation template variant's appName


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------


def make_model(n_users=30, n_items=12, rank=6, seed=0):
    rng = np.random.default_rng(seed)
    return ALSModel(
        rng.normal(size=(n_users, rank)).astype(np.float32),
        rng.normal(size=(n_items, rank)).astype(np.float32),
        [f"u{i}" for i in range(n_users)],
        [f"i{i}" for i in range(n_items)],
    )


def make_components(rank=6):
    return (None, None, [ALSAlgorithm(ALSAlgorithmParams(rank=rank))], Serving())


def query_source(n, num=5):
    for i in range(n):
        yield i + 1, {"user": f"u{i % 30}", "num": num}


def seed_app(storage, n_users=12, n_items=8):
    """App + deterministic rating events (quickstart shape)."""
    app_id = storage.get_meta_data_apps().insert(App(0, APP_NAME))
    levents = storage.get_l_events()
    rng = np.random.default_rng(0)
    events = []
    for u in range(n_users):
        for i in range(n_items):
            if rng.random() < 0.25:
                continue
            events.append(
                Event(
                    event="rate",
                    entity_type="user",
                    entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{i}",
                    properties=DataMap(
                        {"rating": 5.0 if (u + i) % 3 == 0 else 1.0}
                    ),
                )
            )
    levents.insert_batch(events, app_id)
    return app_id


def train_template(storage):
    """Train the recommendation template exactly as the CLI would (same
    manifest `pio batchpredict` loads), returning the instance id."""
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.engine_loader import load_engine

    manifest, engine = load_engine("predictionio_tpu/models/recommendation")
    ep = engine.engine_params_from_variant(manifest.variant_json)
    return engine, ep, run_train(engine, manifest, ep, storage=storage)


# ---------------------------------------------------------------------------
# streaming sources
# ---------------------------------------------------------------------------


class TestSources:
    def test_file_source_streams_lazily_and_skips_blanks(self, tmp_path):
        p = tmp_path / "q.json"
        p.write_text('{"user": "u1"}\n\n{"user": "u2"}\n   \n{"user": "u3"}\n')
        src = iter_query_file(str(p))
        assert hasattr(src, "__next__")  # generator, not a list
        items = list(src)
        # 1-based FILE linenos survive blank-skipping — error objects stay
        # auditable against the input
        assert [ln for ln, _ in items] == [1, 3, 5]

    def test_event_source_dedupes_and_pages_bounded(self, memory_storage):
        app_id = seed_app(memory_storage, n_users=7)
        levents = memory_storage.get_l_events()

        limits: list[int] = []
        real = levents.find_after

        def spy(app_id, channel_id=None, cursor=None, limit=100):
            limits.append(limit)
            return real(app_id, channel_id=channel_id, cursor=cursor, limit=limit)

        levents.find_after = spy
        out = list(
            iter_event_users(levents, app_id, num=4, page=10)
        )
        assert len(out) == 7  # DISTINCT users, not events
        assert {q["user"] for _, q in out} == {f"u{i}" for i in range(7)}
        assert all(q["num"] == 4 for _, q in out)
        # every page rode the ordering contract with an explicit bound
        assert limits and all(lim == 10 for lim in limits)

    def test_event_source_bounded_at_run_start_head(self, memory_storage):
        # a --to-events run inserts results into the same store; the
        # source must mean "users known at run start", never chase the
        # head its own writeback is advancing
        app_id = seed_app(memory_storage, n_users=3)
        levents = memory_storage.get_l_events()
        src = iter_event_users(levents, app_id, num=2)
        first = next(src)
        levents.insert(
            Event(event="rate", entity_type="user", entity_id="u99",
                  target_entity_type="item", target_entity_id="i0"),
            app_id,
        )
        rest = list(src)
        assert {q["user"] for _, q in [first] + rest} == {"u0", "u1", "u2"}

    def test_event_source_limit_caps_distinct_users(self, memory_storage):
        app_id = seed_app(memory_storage, n_users=7)
        out = list(
            iter_event_users(
                memory_storage.get_l_events(), app_id, num=3, limit=4
            )
        )
        assert len(out) == 4


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------


def _rows(n, start=1):
    return [
        OutRow(start + i, {"user": f"u{i}"}, {"itemScores": []}, ok=True)
        for i in range(n)
    ]


class TestFileSink:
    def test_atomic_publish_on_success(self, tmp_path):
        target = tmp_path / "out.json"
        sink = FileSink(str(target))
        sink.write_batch(_rows(3))
        # mid-run: nothing at the destination, ever — a watcher can't see
        # a half-file that looks complete
        assert not target.exists()
        sink.close(True)
        assert len(target.read_text().splitlines()) == 3

    def test_killed_run_leaves_nothing(self, tmp_path):
        target = tmp_path / "out.json"
        sink = FileSink(str(target))
        sink.write_batch(_rows(2))
        sink.close(False)  # the pipeline's failure path
        assert not target.exists()
        assert not [p for p in os.listdir(tmp_path) if p.startswith(".tmp-")]

    def test_failed_flush_never_publishes(self, tmp_path):
        # disk-full at close: the destination must stay untouched (no
        # truncated file that looks complete) and the tmp must be gone
        target = tmp_path / "out.json"
        target.write_text("old\n")
        sink = FileSink(str(target))
        sink.write_batch(_rows(2))
        sink._fh.flush = lambda: (_ for _ in ()).throw(OSError("disk full"))
        with pytest.raises(OSError, match="disk full"):
            sink.close(True)
        assert target.read_text() == "old\n"
        assert not [p for p in os.listdir(tmp_path) if p.startswith(".tmp-")]

    def test_overwrite_is_atomic(self, tmp_path):
        target = tmp_path / "out.json"
        target.write_text("old\n")
        sink = FileSink(str(target))
        sink.write_batch(_rows(1))
        assert target.read_text() == "old\n"  # old stays until publish
        sink.close(True)
        assert "old" not in target.read_text()


class TestEventStoreSink:
    def test_writes_ok_rows_only_with_lineage(self, memory_storage):
        app_id = memory_storage.get_meta_data_apps().insert(App(0, "sinkapp"))
        levents = memory_storage.get_l_events()
        sink = EventStoreSink(
            levents, app_id, model_version="inst42", event_name="bp.result"
        )
        rows = _rows(2) + [
            OutRow(3, None, {"error": "nope", "line": 3}, ok=False)
        ]
        sink.write_batch(rows)
        written = list(levents.find(app_id=app_id, event_names=["bp.result"]))
        assert len(written) == 2  # error rows have no entity to attach to
        props = written[0].properties.fields
        assert props["modelVersion"] == "inst42"
        assert "prediction" in props and "line" in props

    def test_transient_failure_retried_behind_policy(self, memory_storage):
        app_id = memory_storage.get_meta_data_apps().insert(App(0, "sinkapp2"))
        levents = memory_storage.get_l_events()
        calls = {"n": 0}
        real = levents.insert_batch

        def flaky(events, app_id, channel_id=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ConnectionError("transient blip")
            return real(events, app_id, channel_id)

        levents.insert_batch = flaky
        retried = {"n": 0}
        sink = EventStoreSink(
            levents, app_id, on_retry=lambda: retried.__setitem__("n", retried["n"] + 1)
        )
        sink._retry.sleep = lambda s: None  # no real backoff in tests
        sink.write_batch(_rows(2))
        assert calls["n"] == 2 and retried["n"] == 1
        assert len(list(levents.find(app_id=app_id))) == 2


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------


class TestPipeline:
    def test_results_line_aligned_in_source_order(self):
        engine = engine_factory()
        model = make_model()
        sink = MemorySink()
        report = run_pipeline(
            engine,
            make_components(),
            [model],
            query_source(23),
            [sink],
            batch_size=8,
            warmup=False,
        )
        assert report.queries == 23 and report.ok == 23 and report.errors == 0
        assert report.batches == 3
        # double-buffering must not reorder: row i answers query i
        assert [r.lineno for r in sink.rows] == list(range(1, 24))
        assert all(len(r.result["itemScores"]) == 5 for r in sink.rows)

    def test_malformed_line_becomes_error_row_not_abort(self):
        engine = engine_factory()
        model = make_model()
        sink = MemorySink()
        instruments = BatchPredictInstruments()
        source = [
            (1, '{"user": "u1", "num": 3}'),
            (2, "NOT JSON {{{"),
            (3, '{"wrong_field": 1}'),  # decodes to Query -> KeyError
            (4, '{"user": "u2", "num": 2}'),
        ]
        report = run_pipeline(
            engine,
            make_components(),
            [model],
            source,
            [sink],
            batch_size=2,
            instruments=instruments,
            warmup=False,
        )
        assert report.queries == 4 and report.ok == 2 and report.errors == 2
        assert not report.all_failed
        errs = [r for r in sink.rows if not r.ok]
        assert [r.result["line"] for r in errs] == [2, 3]
        assert all("error" in r.result for r in errs)
        snap = instruments.registry.snapshot()

        def val(name):
            return snap[name]["samples"][0]["value"]

        assert val("pio_batchpredict_errors_total") == 2
        assert val("pio_batchpredict_queries_total") == 4

    def test_all_failed_flag(self):
        engine = engine_factory()
        sink = MemorySink()
        report = run_pipeline(
            engine,
            make_components(),
            [make_model()],
            [(1, "junk"), (2, "junk2")],
            [sink],
            batch_size=4,
            warmup=False,
        )
        assert report.all_failed

    def test_batch_failure_errors_batch_but_run_survives(self):
        class BoomAlgo(BaseAlgorithm):
            def predict(self, model, query):  # pragma: no cover - unused
                raise AssertionError

            def predict_batch_dispatch(self, model, queries):
                def finalize():
                    raise RuntimeError("device fell over")

                return finalize

        engine = engine_factory()
        sink = MemorySink()
        report = run_pipeline(
            engine,
            (None, None, [BoomAlgo()], Serving()),
            [object()],
            query_source(5),
            [sink],
            batch_size=2,
            warmup=False,
        )
        # every row errored (batch granularity), but the run completed and
        # stayed line-aligned
        assert report.queries == 5 and report.errors == 5
        assert [r.lineno for r in sink.rows] == [1, 2, 3, 4, 5]
        assert all("device fell over" in r.result["error"] for r in sink.rows)

    def test_sync_fallback_uses_indexed_batch_predict(self):
        # an algorithm that vectorizes only the indexed batch_predict
        # (e.g. the naive-Bayes classifier) must keep its one-call batch
        # path — not degrade to per-query predicts through the base
        # predict_batch
        calls = {"batch": 0, "single": 0}

        class IndexedOnlyAlgo(BaseAlgorithm):
            def predict(self, model, query):
                calls["single"] += 1
                return {"echo": query["user"]}

            def batch_predict(self, model, queries):
                calls["batch"] += 1
                return [(i, {"echo": q["user"]}) for i, q in queries]

        engine = engine_factory()
        engine.query_class = None  # raw dict queries
        sink = MemorySink()
        report = run_pipeline(
            engine,
            (None, None, [IndexedOnlyAlgo()], Serving()),
            [object()],
            ((i + 1, {"user": f"u{i}"}) for i in range(12)),
            [sink],
            batch_size=4,
            warmup=False,
        )
        assert report.ok == 12
        assert calls["batch"] == 3 and calls["single"] == 0
        assert sink.rows[0].result == {"echo": "u0"}

    def test_distinct_users_drive_users_per_s(self):
        engine = engine_factory()
        sink = MemorySink()
        # 20 queries cycling 5 users: qps counts queries, users_per_s
        # counts DISTINCT users
        report = run_pipeline(
            engine,
            make_components(),
            [make_model()],
            ((i + 1, {"user": f"u{i % 5}", "num": 3}) for i in range(20)),
            [sink],
            batch_size=8,
            warmup=False,
        )
        assert report.queries == 20 and report.distinct_users == 5
        assert report.users_per_s == pytest.approx(report.qps / 4.0, rel=0.01)

    def test_phase_timeline_tiles_wall_clock(self):
        """The ISSUE-14 contract: read->assemble->dispatch->fetch->write
        must cover the run wall clock within 10% (the PR-6/PR-7 evidence
        discipline, now on the offline path)."""
        engine = engine_factory()
        sink = MemorySink()
        report = run_pipeline(
            engine,
            make_components(),
            [make_model()],
            query_source(600),
            [sink],
            batch_size=64,
            warmup=True,
        )
        assert set(report.phase_p50_ms) == {
            "read",
            "assemble",
            "dispatch",
            "fetch",
            "write",
        }
        assert 0.9 <= report.tiling_ratio <= 1.001, report.tiling_ratio
        # the profile IS the manifest-grade evidence object
        assert report.profile["steps"] == 0 or "phases" in report.profile
        assert report.qps > 0

    def test_status_file_progress_and_final_state(self, tmp_path):
        status_path = tmp_path / "bp.status.json"
        status = StatusFile(str(status_path), interval_s=0.0)
        engine = engine_factory()
        run_pipeline(
            engine,
            make_components(),
            [make_model()],
            query_source(20),
            [MemorySink()],
            batch_size=8,
            status=status,
            warmup=False,
        )
        final = json.loads(status_path.read_text())
        assert final["state"] == "done"
        assert final["queries"] == 20 and final["ok"] == 20
        assert final["phaseP50Ms"]["dispatch"] >= 0


# ---------------------------------------------------------------------------
# file-level entry + the online/offline exactness contract
# ---------------------------------------------------------------------------


class TestRunBatchPredict:
    def test_from_events_matches_online_answers(self, memory_storage, tmp_path):
        """The e2e contract: ingest -> train -> `pio batchpredict
        --from-events` writeback rows must EXACTLY match what the online
        serving path answers for the same users — offline is a faster
        path to the same function, never a different function."""
        seed_app(memory_storage)
        engine, ep, instance_id = train_template(memory_storage)

        out = tmp_path / "preds.jsonl"
        report = run_batch_predict(
            "predictionio_tpu/models/recommendation",
            None,
            str(out),
            storage=memory_storage,
            from_events=True,
            to_events=True,
            query_num=4,
            batch_size=8,
        )
        assert report.queries == 12 and report.errors == 0  # 12 distinct users
        assert len(out.read_text().splitlines()) == 12
        # the writeback events carry the query identity (entity_id = user)
        events = list(
            memory_storage.get_l_events().find(
                app_id=memory_storage.get_meta_data_apps()
                .get_by_name(APP_NAME)
                .id,
                event_names=["batchpredict.result"],
            )
        )
        assert len(events) == 12
        by_user = {e.entity_id: e.properties.fields["prediction"] for e in events}
        assert all(
            e.properties.fields["modelVersion"] == instance_id for e in events
        )

        # online answers through the REAL QueryServer for sampled users
        from predictionio_tpu.workflow.core_workflow import (
            load_models_for_instance,
        )
        from predictionio_tpu.workflow.create_server import (
            QueryServer,
            ServerConfig,
        )
        from predictionio_tpu.workflow.engine_loader import load_engine

        manifest, engine2 = load_engine(
            "predictionio_tpu/models/recommendation"
        )
        models = load_models_for_instance(
            engine2, ep, instance_id, storage=memory_storage
        )
        server = QueryServer(
            engine=engine2,
            engine_params=ep,
            models=models,
            manifest=manifest,
            instance_id=instance_id,
            storage=memory_storage,
            config=ServerConfig(),
        )

        async def fetch_online(users):
            client = TestClient(TestServer(server.make_app()))
            await client.start_server()
            try:
                answers = {}
                for u in users:
                    resp = await client.post(
                        "/queries.json", json={"user": u, "num": 4}
                    )
                    assert resp.status == 200
                    answers[u] = await resp.json()
                return answers
            finally:
                await client.close()

        sampled = ["u0", "u3", "u7", "u11"]
        online = asyncio.run(fetch_online(sampled))
        for u in sampled:
            off_scores = by_user[u]["itemScores"]
            on_scores = online[u]["itemScores"]
            assert [s["item"] for s in off_scores] == [
                s["item"] for s in on_scores
            ], f"user {u}: offline/online item sets diverge"
            np.testing.assert_allclose(
                [s["score"] for s in off_scores],
                [s["score"] for s in on_scores],
                rtol=1e-5,
            )

    def test_file_input_compat_and_error_exit_semantics(
        self, memory_storage, tmp_path
    ):
        seed_app(memory_storage)
        train_template(memory_storage)
        qf = tmp_path / "q.json"
        qf.write_text('{"user": "u1", "num": 3}\nBROKEN\n')
        out = tmp_path / "out.json"
        report = run_batch_predict(
            "predictionio_tpu/models/recommendation",
            str(qf),
            str(out),
            storage=memory_storage,
            batch_size=4,
        )
        rows = [json.loads(l) for l in out.read_text().splitlines()]
        assert len(rows) == 2
        assert len(rows[0]["itemScores"]) == 3
        assert rows[1]["line"] == 2 and "error" in rows[1]
        assert not report.all_failed

        qf.write_text("BROKEN1\nBROKEN2\n")
        report = run_batch_predict(
            "predictionio_tpu/models/recommendation",
            str(qf),
            str(out),
            storage=memory_storage,
        )
        assert report.all_failed  # the CLI turns this into a nonzero exit

    def test_setup_errors_raise(self, memory_storage, tmp_path):
        seed_app(memory_storage)
        train_template(memory_storage)
        with pytest.raises(RuntimeError, match="--input.*--from-events"):
            run_batch_predict(
                "predictionio_tpu/models/recommendation",
                None,
                str(tmp_path / "o.json"),
                storage=memory_storage,
            )
        with pytest.raises(RuntimeError, match="app not found"):
            run_batch_predict(
                "predictionio_tpu/models/recommendation",
                None,
                str(tmp_path / "o.json"),
                storage=memory_storage,
                from_events=True,
                app_name="ghost-app",
            )

    def test_pure_core_compat(self, memory_storage):
        seed_app(memory_storage)
        engine, ep, _ = train_template(memory_storage)
        from predictionio_tpu.workflow.core_workflow import (
            load_models_for_instance,
        )
        from predictionio_tpu.workflow.engine_loader import load_engine

        manifest, engine = load_engine("predictionio_tpu/models/recommendation")
        instances = memory_storage.get_meta_data_engine_instances()
        inst = instances.get_latest_completed(
            manifest.engine_id, manifest.version, manifest.variant
        )
        models = load_models_for_instance(
            engine, ep, inst.id, storage=memory_storage
        )
        lines = run_batch_predict_on(
            engine,
            ep,
            models,
            ['{"user": "u1", "num": 3}', "", '{"user": "u2", "num": 2}'],
        )
        assert len(lines) == 2
        assert len(json.loads(lines[0])["itemScores"]) == 3
        assert len(json.loads(lines[1])["itemScores"]) == 2


# ---------------------------------------------------------------------------
# staging-upload decoupling (the double-buffer correctness contract)
# ---------------------------------------------------------------------------


class TestUploadDecoupling:
    """`jnp.asarray(host_numpy)` on the CPU backend is zero-copy: the jax
    array ALIASES the numpy buffer. The scratch-pool reuse every async
    dispatch path depends on ("the buffer is reusable as soon as dispatch
    returns") is only sound because ops.topk.upload copies — without it,
    the offline double-buffer pipeline intermittently served batch N's
    first rows with batch N+1's users (a torn read of the overwritten
    staging buffer)."""

    def test_upload_decouples_host_buffer(self):
        import numpy as np

        from predictionio_tpu.ops import topk

        buf = np.arange(8, dtype=np.int32)
        d = topk.upload(buf, np.int32)
        buf[:] = 99  # the next batch's assembly
        np.testing.assert_array_equal(
            np.asarray(d), np.arange(8, dtype=np.int32)
        )

    def test_upload_passes_device_arrays_through(self):
        import jax.numpy as jnp

        from predictionio_tpu.ops import topk

        d = jnp.arange(4)
        assert topk.upload(d) is d

    def test_dispatch_immune_to_post_dispatch_mutation(self):
        import numpy as np

        from predictionio_tpu.ops import topk

        rng = np.random.default_rng(0)
        index = topk.ServingIndex(
            rng.normal(size=(12, 6)).astype(np.float32),
            rng.normal(size=(8, 6)).astype(np.float32),
        )
        _, expect = topk.fetch_topk(
            index.serve_batch_async(np.arange(8, dtype=np.int32), 4)
        )
        buf = np.arange(8, dtype=np.int32)
        handle = index.serve_batch_async(buf, 4)
        buf[:] = 0  # overwrite the staging buffer mid-flight
        _, idx = topk.fetch_topk(handle)
        np.testing.assert_array_equal(idx, expect)


# ---------------------------------------------------------------------------
# pio top --batchpredict
# ---------------------------------------------------------------------------


class TestTopBatchpredict:
    STATUS = {
        "state": "running",
        "pid": 4242,
        "engineId": "recommendation",
        "source": "events",
        "batchSize": 512,
        "queries": 12000,
        "ok": 11990,
        "errors": 10,
        "batches": 24,
        "qps": 8123.4,
        "phaseP50Ms": {
            "read": 0.1,
            "assemble": 1.2,
            "dispatch": 3.4,
            "fetch": 10.2,
            "write": 9.1,
        },
    }

    def test_render_progress_line(self):
        from predictionio_tpu.tools.top import render_batchpredict

        text = render_batchpredict(self.STATUS)
        assert "batchpredict" in text and "running" in text
        assert "12000 q" in text and "10 err" in text
        assert "8123.4 q/s" in text
        assert "dispatch 3.4" in text and "write 9.1" in text

    def test_run_loop_json_and_unreadable(self, tmp_path):
        from predictionio_tpu.tools.top import run_batchpredict_top

        path = tmp_path / "bp.status.json"
        out: list[str] = []
        # missing file degrades, never raises
        rc = run_batchpredict_top(
            str(path), iterations=1, json_mode=True, out=out.append
        )
        assert rc == 0 and "error" in json.loads(out[0])
        path.write_text(json.dumps(self.STATUS))
        out.clear()
        run_batchpredict_top(
            str(path), iterations=1, json_mode=True, out=out.append
        )
        snap = json.loads(out[0])
        assert snap["qps"] == 8123.4 and snap["state"] == "running"
        out.clear()
        run_batchpredict_top(str(path), iterations=1, out=out.append)
        assert "batchpredict" in out[0]
