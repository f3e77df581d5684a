"""The profiling plane (ISSUE 18): on-demand device capture into
content-addressed bundles, the device-free cost-analysis roofline, the
always-on host sampler, and profile-on-alert.

Layout mirrors the subsystem: ProfileStore/ProfileSession units (bundle
grammar, single-flight, rails, rate limiting), HostSampler correctness
with a planted busy thread + the <1% overhead gate, cost-model finiteness
for every registered bucket family on the CPU backend, the HTTP surface
on a live in-process QueryServer, profile-on-alert bundle content, and
the CLI units.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, "tests") if "tests" not in sys.path else None

from predictionio_tpu.obs.profiler import (
    ProfileBusyError,
    ProfileSession,
    ProfileStore,
    maybe_profile_train,
)
from predictionio_tpu.obs.sampler import HostSampler


def _store(tmp_path, **kw):
    return ProfileStore(str(tmp_path / "profiles"), **kw)


# ---------------------------------------------------------------------------
# ProfileStore: the content-addressed bundle grammar
# ---------------------------------------------------------------------------


class TestProfileStore:
    def test_construction_writes_nothing(self, tmp_path):
        store = _store(tmp_path)
        assert not os.path.exists(store.dir)

    def test_publish_writes_manifest_parts_texts(self, tmp_path):
        store = _store(tmp_path)
        path = store.publish(
            "manual",
            context={"engine": "e1"},
            parts={"waterfall": {"p50": 1.5}},
            texts={"stacks_folded": "event-loop;main 3\n"},
        )
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["trigger"] == "manual"
        assert manifest["context"]["engine"] == "e1"
        assert manifest["parts"] == ["waterfall"]
        assert manifest["texts"] == ["stacks_folded"]
        assert len(manifest["sha256"]) == 64
        part = json.load(open(os.path.join(path, "waterfall.json")))
        assert part == {"p50": 1.5}
        text = open(os.path.join(path, "stacks_folded.txt")).read()
        assert "event-loop;main 3" in text

    def test_bundle_id_carries_digest_prefix(self, tmp_path):
        store = _store(tmp_path)
        path = store.publish("manual", context={"n": 1})
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert os.path.basename(path).endswith(manifest["sha256"][:12])

    def test_trace_dir_moved_and_inventoried(self, tmp_path):
        store = _store(tmp_path)
        trace = tmp_path / "rawtrace" / "plugins"
        trace.mkdir(parents=True)
        (trace / "a.xplane.pb").write_bytes(b"\x01\x02\x03")
        path = store.publish("manual", trace_dir=str(tmp_path / "rawtrace"))
        assert not (tmp_path / "rawtrace").exists()  # moved, not copied
        assert os.path.exists(
            os.path.join(path, "trace", "plugins", "a.xplane.pb")
        )
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["trace"][0]["name"] == os.path.join(
            "plugins", "a.xplane.pb"
        )
        assert manifest["trace"][0]["bytes"] == 3
        assert len(manifest["trace"][0]["sha256"]) == 64

    def test_no_tmp_leftovers(self, tmp_path):
        store = _store(tmp_path)
        store.publish("manual", context={"n": 1})
        leftovers = [e for e in os.listdir(store.dir) if e.startswith(".tmp-")]
        assert leftovers == []

    def test_gc_keeps_newest(self, tmp_path):
        store = _store(tmp_path, max_bundles=3)
        for i in range(5):
            store.publish("manual", context={"n": i})
        refs = store.list()
        assert len(refs) == 3
        # newest survive: the last three publishes (oldest-first listing)
        contexts = [
            json.load(open(os.path.join(r.path, "manifest.json")))["context"][
                "n"
            ]
            for r in refs
        ]
        assert contexts == [2, 3, 4]

    def test_list_load_export_roundtrip(self, tmp_path):
        store = _store(tmp_path)
        path = store.publish("manual", parts={"p": [1, 2]})
        bundle_id = os.path.basename(path)
        # unique-prefix load (the `pio profile show` contract)
        bundle = store.load(bundle_id[:10])
        assert bundle["parts"]["p"] == [1, 2]
        dest = store.export(bundle_id, str(tmp_path / "out"))
        assert os.path.exists(os.path.join(dest, "manifest.json"))


# ---------------------------------------------------------------------------
# ProfileSession: single-flight, rails, alert rate limiting
# ---------------------------------------------------------------------------


class TestProfileSession:
    def test_clamp_ms_rails(self, tmp_path):
        s = ProfileSession(_store(tmp_path), default_ms=500, max_ms=2000)
        assert s.clamp_ms(None) == 500
        assert s.clamp_ms(-5) == 0
        assert s.clamp_ms(99999) == 2000
        assert s.clamp_ms(30) == 30

    def test_capture_host_only_bundle(self, tmp_path):
        # ms=0 skips the device trace entirely: no jax import needed
        s = ProfileSession(_store(tmp_path))
        path = s.capture(ms=0, parts={"stacks": {"roles": {}}})
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["context"]["durationMs"] == 0
        assert manifest["trace"] == []
        assert not os.path.isdir(os.path.join(path, "trace"))

    def test_capture_bounded_duration_in_manifest(self, tmp_path):
        s = ProfileSession(_store(tmp_path), max_ms=0)
        # requested 10s, rail says 0 — the manifest records the truth
        path = s.capture(ms=10_000)
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["context"]["durationMs"] == 0

    def test_single_flight_raises_busy(self, tmp_path):
        s = ProfileSession(_store(tmp_path))
        hold = threading.Event()
        entered = threading.Event()

        def slow_parts():
            entered.set()
            hold.wait(5.0)
            return {}

        t = threading.Thread(
            target=lambda: s.capture(ms=0, parts=slow_parts() or {}),
            daemon=True,
        )
        # simpler: hold the flight lock directly — the lock IS the contract
        assert s._flight.acquire(blocking=False)
        try:
            with pytest.raises(ProfileBusyError):
                s.capture(ms=0)
        finally:
            s._flight.release()
        del t, entered

    def test_context_fn_merged_and_guarded(self, tmp_path):
        s = ProfileSession(
            _store(tmp_path), context_fn=lambda: {"engine": "e9"}
        )
        path = s.capture(ms=0, context={"extra": 1})
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["context"]["engine"] == "e9"
        assert manifest["context"]["extra"] == 1

        def boom():
            raise RuntimeError("no context for you")

        s_bad = ProfileSession(_store(tmp_path / "b"), context_fn=boom)
        path = s_bad.capture(ms=0)
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert "no context for you" in manifest["context"]["contextError"]

    def test_capture_metrics(self, tmp_path):
        from predictionio_tpu.obs.metrics import MetricsRegistry

        m = MetricsRegistry()
        s = ProfileSession(_store(tmp_path), metrics=m)
        s.capture(ms=0)
        text = m.render_prometheus()
        assert 'pio_profile_captures_total{trigger="manual"} 1' in text
        assert "pio_profile_bundles 1" in text
        with s._flight:
            with pytest.raises(ProfileBusyError):
                s.capture(ms=0)
        assert "pio_profile_capture_busy_total 1" in m.render_prometheus()

    def test_capture_alert_rate_limited_per_trigger(self, tmp_path):
        clock = [100.0]
        s = ProfileSession(
            _store(tmp_path),
            alert_min_interval_s=60.0,
            alert_trace_ms=0,
            clock=lambda: clock[0],
        )
        assert s.capture_alert("slo-alert", context={"n": 1}) is not None
        # inside the interval: suppressed
        clock[0] += 10.0
        assert s.capture_alert("slo-alert", context={"n": 2}) is None
        # a DIFFERENT trigger kind has its own limiter
        assert s.capture_alert("breaker-trip", context={"n": 3}) is not None
        # past the interval: fires again
        clock[0] += 60.0
        assert s.capture_alert("slo-alert", context={"n": 4}) is not None
        assert len(s.store.list()) == 3

    def test_capture_alert_never_raises(self, tmp_path, monkeypatch):
        s = ProfileSession(_store(tmp_path), alert_trace_ms=0)
        monkeypatch.setattr(
            s.store,
            "publish",
            lambda *a, **k: (_ for _ in ()).throw(OSError("disk gone")),
        )
        assert s.capture_alert("slo-alert") is None
        # busy is also swallowed, not raised, on the alert path
        s2 = ProfileSession(_store(tmp_path / "b"), alert_trace_ms=0)
        with s2._flight:
            assert s2.capture_alert("slo-alert") is None

    @pytest.mark.slow
    def test_capture_device_trace_on_cpu(self, tmp_path):
        # the real jax.profiler path: a short trace on the CPU backend
        # must land raw artifacts under trace/ with an inventory
        s = ProfileSession(_store(tmp_path))
        path = s.capture(ms=50, trigger="manual")
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["context"]["durationMs"] == 50
        assert manifest["trace"], "device trace produced no artifacts"
        assert os.path.isdir(os.path.join(path, "trace"))

    @pytest.mark.slow
    def test_maybe_profile_train_compat(self, tmp_path, monkeypatch):
        # PIO_PROFILE_DIR unset -> no-op
        monkeypatch.delenv("PIO_PROFILE_DIR", raising=False)
        with maybe_profile_train() as box:
            assert box is None
        # set -> the body runs under a trace that lands as a bundle
        monkeypatch.setenv("PIO_PROFILE_DIR", str(tmp_path / "prof"))
        with maybe_profile_train(
            context={"engine": "e1"}, parts_fn=lambda: {"xray": {"ok": 1}}
        ) as box:
            time.sleep(0.05)
        assert box["path"]
        manifest = json.load(
            open(os.path.join(box["path"], "manifest.json"))
        )
        assert manifest["trigger"] == "train"
        assert manifest["context"]["engine"] == "e1"
        assert "xray" in manifest["parts"]


# ---------------------------------------------------------------------------
# HostSampler: folded stacks, role attribution, overhead gate
# ---------------------------------------------------------------------------


def _busy_thread(name: str):
    stop = threading.Event()

    def body():
        while not stop.is_set():
            sum(i * i for i in range(500))

    t = threading.Thread(target=body, name=name, daemon=True)
    t.start()
    return stop, t


class TestHostSampler:
    def test_role_attribution(self):
        s = HostSampler()
        assert s.role_of("pio-dispatch-0") == "dispatch"
        assert s.role_of("pio-fetch-3") == "fetch"
        assert s.role_of("pio-shadow-1") == "shadow"
        assert s.role_of("pio-stream-x") == "stream"
        assert s.role_of("MainThread") == "event-loop"
        assert s.role_of("ThreadPoolExecutor-0_0") == "executor"
        assert s.role_of("random-thread") == "other"

    def test_planted_busy_thread_shows_in_folded_stacks(self):
        stop, t = _busy_thread("pio-fetch-0")
        try:
            s = HostSampler()
            for _ in range(10):
                s.sample_once()
        finally:
            stop.set()
            t.join(timeout=2.0)
        folded = s.folded()
        fetch_lines = [
            ln for ln in folded.splitlines() if ln.startswith("fetch;")
        ]
        assert fetch_lines, f"no fetch-role stacks in:\n{folded}"
        # folded grammar: "role;frame;...;leaf count" — leaf is this file's
        # busy loop, root-first order
        key, count = fetch_lines[0].rsplit(" ", 1)
        assert int(count) >= 1
        assert "test_profiler" in key

    def test_snapshot_roles_and_counts(self):
        stop, t = _busy_thread("pio-dispatch-7")
        try:
            s = HostSampler()
            for _ in range(5):
                s.sample_once()
        finally:
            stop.set()
            t.join(timeout=2.0)
        snap = s.snapshot()
        assert snap["samples"] == 5
        assert snap["roles"].get("dispatch", 0) >= 1
        assert isinstance(snap["stacks"], dict)
        assert snap["periodS"] == s.period_s

    def test_hotspots_table(self):
        stop, t = _busy_thread("pio-fetch-0")
        try:
            s = HostSampler()
            for _ in range(8):
                s.sample_once()
        finally:
            stop.set()
            t.join(timeout=2.0)
        hot = s.hotspots(top_n=2)
        assert "fetch" in hot
        entry = hot["fetch"][0]
        assert entry["count"] >= 1
        assert 0.0 < entry["frac"] <= 1.0

    def test_bounded_stacks_overflow_to_other(self):
        clock = [0.0]
        s = HostSampler(max_stacks=1, clock=lambda: clock[0])
        # two distinct synthetic keys through the real accounting path:
        # plant two differently-named busy threads
        stop1, t1 = _busy_thread("pio-fetch-a")
        stop2, t2 = _busy_thread("pio-dispatch-b")
        try:
            for _ in range(4):
                s.sample_once()
        finally:
            stop1.set(), stop2.set()
            t1.join(timeout=2.0), t2.join(timeout=2.0)
        snap = s.snapshot()
        assert snap["truncated"] >= 1
        assert any(key.endswith("<other>") for key in snap["stacks"])
        assert len({k for k in s._window}) <= 1 + len(
            {k for k in s._window if k.endswith("<other>")}
        ) + 1  # bounded: the one real stack + per-role <other> leaves

    def test_window_rotation_bounds_memory(self):
        clock = [0.0]
        s = HostSampler(window_s=10.0, ring_windows=2, clock=lambda: clock[0])
        stop, t = _busy_thread("pio-fetch-r")
        try:
            for _ in range(3):
                s.sample_once()
                clock[0] += 11.0  # every sample closes a window
        finally:
            stop.set()
            t.join(timeout=2.0)
        assert len(s._ring) <= 2
        # merged view still covers the ring + the live window
        assert s._merged()

    def test_start_stop_idempotent(self):
        s = HostSampler(period_s=0.01)
        s.start()
        s.start()
        assert s.running
        s.stop()
        s.stop()
        assert not s.running

    def test_sampler_thread_excluded_from_its_own_stacks(self):
        s = HostSampler(period_s=0.005)
        s.start()
        try:
            time.sleep(0.1)
        finally:
            s.stop()
        assert not any(
            key.startswith("sampler;") for key in s._merged()
        ), "the sampler sampled itself"

    def test_overhead_under_one_percent_at_default_period(self):
        """The always-on budget (ISSUE 18 acceptance): self-measured
        overhead < 1% CPU at the default 20 Hz period, with a real busy
        thread planted so stacks are non-trivial."""
        stop, t = _busy_thread("pio-dispatch-load")
        s = HostSampler()  # default period_s=0.05
        s.start()
        try:
            time.sleep(2.0)
        finally:
            s.stop()
            stop.set()
            t.join(timeout=2.0)
        frac = s.overhead_frac()
        assert s.snapshot()["samples"] >= 10
        assert frac < 0.01, f"sampler overhead {frac:.4f} >= 1%"

    def test_metrics_registered(self):
        from predictionio_tpu.obs.metrics import MetricsRegistry

        m = MetricsRegistry()
        s = HostSampler(metrics=m)
        s.sample_once()
        text = m.render_prometheus()
        assert "pio_profile_sampler_samples_total 1" in text
        assert "pio_profile_sampler_overhead_frac" in text
        assert "pio_profile_sampler_stacks" in text


# ---------------------------------------------------------------------------
# Cost model: finite numbers for every registered bucket family (CPU)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def roofline_report():
    from predictionio_tpu.obs import costmodel

    return costmodel.analyze()


class TestCostModel:
    @pytest.mark.parametrize("family", ["topk", "ann", "als", "twotower"])
    def test_family_finite_on_cpu(self, roofline_report, family):
        import math

        assert family not in roofline_report["errors"], roofline_report[
            "errors"
        ].get(family)
        entry = roofline_report["families"][family]
        assert entry["totalFlops"] > 0
        assert entry["totalBytes"] > 0
        assert math.isfinite(entry["arithmeticIntensity"])
        assert entry["arithmeticIntensity"] > 0
        assert entry["perQueryModelTimeS"] > 0
        assert entry["costPer1kQueriesUsd"] > 0
        for kernel in entry["kernels"]:
            assert math.isfinite(kernel["flops"])
            assert kernel["bytesAccessed"] > 0
            assert kernel["bound"] in ("compute", "memory")

    def test_roofline_bound_classification(self):
        from predictionio_tpu.obs.costmodel import (
            DEVICE_SPECS,
            roofline_time_s,
        )

        spec = DEVICE_SPECS["tpu-v4"]
        compute_heavy = {"flops": 1e12, "bytesAccessed": 1.0}
        memory_heavy = {"flops": 1.0, "bytesAccessed": 1e12}
        assert roofline_time_s(compute_heavy, spec)["bound"] == "compute"
        assert roofline_time_s(memory_heavy, spec)["bound"] == "memory"

    def test_unknown_family_is_reported_not_raised(self):
        from predictionio_tpu.obs import costmodel

        report = costmodel.analyze(families=["nope"])
        assert "nope" in report["errors"]
        assert report["families"] == {}


# ---------------------------------------------------------------------------
# HTTP surface + profile-on-alert on a live in-process QueryServer
# ---------------------------------------------------------------------------


def _run_server(body, **cfg_kw):
    from aiohttp.test_utils import TestClient, TestServer

    from tests.test_resilience import _make_query_server

    async def outer():
        server = _make_query_server(**cfg_kw)
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            await body(client, server)
        finally:
            await client.close()

    asyncio.run(outer())


class TestQueryServerProfileEndpoints:
    def test_capture_roundtrip_host_only(self, tmp_path):
        prof_dir = str(tmp_path / "profiles")

        async def body(client, server):
            resp = await client.post("/profile/capture?ms=0")
            assert resp.status == 200
            data = await resp.json()
            assert data["durationMs"] == 0
            assert data["modelVersion"] == server.model_version
            path = data["path"]
            manifest = json.load(open(os.path.join(path, "manifest.json")))
            # manifest model version matches the serving lane (acceptance)
            assert manifest["context"]["modelVersion"] == server.model_version
            assert manifest["context"]["engine"] == "resil"
            assert "waterfall" in manifest["parts"]
            assert "stacks" in manifest["parts"]
            assert len(server.profiler.store.list()) == 1

        _run_server(body, profile_dir=prof_dir)

    def test_capture_bad_ms_is_400(self, tmp_path):
        async def body(client, server):
            resp = await client.post("/profile/capture?ms=banana")
            assert resp.status == 400

        _run_server(body, profile_dir=str(tmp_path / "p"))

    def test_capture_busy_is_409(self, tmp_path):
        async def body(client, server):
            assert server.profiler._flight.acquire(blocking=False)
            try:
                resp = await client.post("/profile/capture?ms=0")
                assert resp.status == 409
            finally:
                server.profiler._flight.release()

        _run_server(body, profile_dir=str(tmp_path / "p"))

    def test_stacks_folded_and_json(self, tmp_path):
        async def body(client, server):
            # a planted busy thread so the sample has something to record
            # (sample_once skips the calling thread itself)
            stop, t = _busy_thread("pio-fetch-ep")
            try:
                for _ in range(3):
                    server.sampler.sample_once()
            finally:
                stop.set()
                t.join(timeout=2.0)
            resp = await client.get("/profile/stacks")
            assert resp.status == 200
            assert resp.content_type == "text/plain"
            text = await resp.text()
            assert ";" in text  # folded lines present
            resp = await client.get("/profile/stacks?format=json")
            data = await resp.json()
            assert data["samples"] >= 1
            assert "hotspots" in data
            assert "overheadFrac" in data

        _run_server(body, profile_dir=str(tmp_path / "p"))

    def test_profile_on_alert_bundle_contains_offending_stacks(
        self, tmp_path
    ):
        """Acceptance: an SLO-alert capture's bundle carries the folded
        host stacks of the offending (planted busy) thread."""

        async def body(client, server):
            stop, t = _busy_thread("pio-fetch-hot")
            try:
                for _ in range(5):
                    server.sampler.sample_once()
                server._profile_on_alert("slo-alert", {"slo": "latency-p95"})
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    if server.profiler.store.list():
                        break
                    await asyncio.sleep(0.02)
            finally:
                stop.set()
                t.join(timeout=2.0)
            refs = server.profiler.store.list()
            assert refs, "profile-on-alert produced no bundle"
            bundle = server.profiler.store.load(refs[-1].bundle_id)
            assert bundle["manifest"]["trigger"] == "slo-alert"
            assert bundle["manifest"]["context"]["slo"] == "latency-p95"
            folded = bundle["texts"]["stacks_folded"]
            assert any(
                ln.startswith("fetch;") for ln in folded.splitlines()
            ), f"offending thread's stacks missing:\n{folded}"
            assert "stacks" in bundle["parts"]

        _run_server(body, profile_dir=str(tmp_path / "p"))

    def test_slo_transition_fires_once_per_edge(self, tmp_path, monkeypatch):
        async def body(client, server):
            fired = []
            monkeypatch.setattr(
                server,
                "_profile_on_alert",
                lambda trig, ctx: fired.append((trig, ctx["slo"])),
            )
            reports = [{"name": "avail", "alerting": False}]
            monkeypatch.setattr(server.slo, "evaluate", lambda: reports)
            server._check_slo_alerts()
            assert fired == []
            reports[0] = {"name": "avail", "alerting": True}
            server._check_slo_alerts()
            server._check_slo_alerts()  # level, not transition: no re-fire
            assert fired == [("slo-alert", "avail")]
            reports[0] = {"name": "avail", "alerting": False}
            server._check_slo_alerts()
            reports[0] = {"name": "avail", "alerting": True}
            server._check_slo_alerts()
            assert len(fired) == 2

        _run_server(body, profile_dir=str(tmp_path / "p"))

    def test_profile_on_alert_disabled_by_config(self, tmp_path):
        async def body(client, server):
            server._profile_on_alert("slo-alert", {"slo": "x"})
            await asyncio.sleep(0.1)
            assert server.profiler.store.list() == []

        _run_server(
            body, profile_dir=str(tmp_path / "p"), profile_on_alert=False
        )


# ---------------------------------------------------------------------------
# CLI units
# ---------------------------------------------------------------------------


class TestProfileCLI:
    def test_profile_list_empty(self, tmp_path, capsys):
        from predictionio_tpu.tools.cli import main

        rc = main(
            ["profile", "list", "--profile-dir", str(tmp_path / "none")]
        )
        assert rc == 0
        assert "No profile bundles" in capsys.readouterr().out

    def test_profile_list_show_export(self, tmp_path, capsys):
        from predictionio_tpu.tools.cli import main

        store = _store(tmp_path)
        path = store.publish(
            "manual",
            context={"modelVersion": "v7"},
            parts={"stacks": {"roles": {"fetch": 3}}},
            texts={"stacks_folded": "fetch;f 3\n"},
        )
        bundle_id = os.path.basename(path)
        rc = main(["profile", "list", "--profile-dir", store.dir])
        out = capsys.readouterr().out
        assert rc == 0 and bundle_id in out and "manual" in out
        rc = main(
            ["profile", "show", bundle_id[:12], "--profile-dir", store.dir]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "trigger   manual" in out
        assert "v7" in out
        assert "stacks.json" in out
        assert "stacks_folded.txt" in out
        dest = str(tmp_path / "exported")
        rc = main(
            ["profile", "export", bundle_id, dest, "--profile-dir", store.dir]
        )
        assert rc == 0
        assert os.path.exists(
            os.path.join(dest, bundle_id, "manifest.json")
        )

    def test_profile_show_json_and_missing(self, tmp_path, capsys):
        from predictionio_tpu.tools.cli import main

        store = _store(tmp_path)
        path = store.publish("manual", parts={"p": 1})
        rc = main(
            [
                "profile",
                "show",
                os.path.basename(path),
                "--profile-dir",
                store.dir,
                "--json",
            ]
        )
        assert rc == 0
        data = json.loads(capsys.readouterr().out)
        assert data["manifest"]["trigger"] == "manual"
        rc = main(
            ["profile", "show", "zzz-nope", "--profile-dir", store.dir]
        )
        assert rc == 1

    def test_profile_serve_unreachable_is_one_line_error(self, capsys):
        from predictionio_tpu.tools.cli import main

        rc = main(
            [
                "profile",
                "serve",
                "--url",
                "http://127.0.0.1:1",
                "--timeout",
                "0.2",
            ]
        )
        assert rc == 1
        assert "unreachable" in capsys.readouterr().err

    def test_profile_dir_env_fallback(self, tmp_path, monkeypatch, capsys):
        from predictionio_tpu.tools.cli import main

        store = _store(tmp_path)
        store.publish("train", context={})
        monkeypatch.setenv("PIO_PROFILE_DIR", store.dir)
        rc = main(["profile", "list"])
        assert rc == 0
        assert "train" in capsys.readouterr().out

    @pytest.mark.slow
    def test_doctor_roofline_exits_zero_with_finite_numbers(self, capsys):
        import math

        from predictionio_tpu.tools.cli import main

        rc = main(["doctor", "--roofline", "--families", "topk"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        entry = report["families"]["topk"]
        assert math.isfinite(entry["arithmeticIntensity"])
        assert entry["costPer1kQueriesUsd"] > 0

    def test_top_hotspots_json_parity_and_degradation(self, capsys):
        from predictionio_tpu.tools.top import run_top

        metrics_text = "pio_requests_total 5\n"
        snap = {
            "samples": 4,
            "overheadFrac": 0.001,
            "roles": {"fetch": 4},
            "stacks": {"fetch;a;b": 4},
            "hotspots": {"fetch": [{"frame": "b", "count": 4, "frac": 1.0}]},
        }
        lines = []
        rc = run_top(
            "http://x",
            iterations=1,
            fetch=lambda u: metrics_text,
            stacks_fetch=lambda u: snap,
            out=lines.append,
            json_mode=True,
            hotspots=True,
        )
        assert rc == 0
        obj = json.loads(lines[0])
        assert obj["hotspots"]["roles"] == {"fetch": 4}
        # screen mode renders the hotspots block
        screens = []
        run_top(
            "http://x",
            iterations=1,
            fetch=lambda u: metrics_text,
            stacks_fetch=lambda u: snap,
            out=screens.append,
            clear_screen=False,
            hotspots=True,
        )
        assert "hotspots (sampler 0.10% ovh, 4 samples):" in screens[0]
        assert "fetch" in screens[0]
        # unreadable endpoint degrades to one line, never a crash
        screens2 = []
        run_top(
            "http://x",
            iterations=1,
            fetch=lambda u: metrics_text,
            stacks_fetch=lambda u: (_ for _ in ()).throw(OSError("nope")),
            out=screens2.append,
            clear_screen=False,
            hotspots=True,
        )
        assert "hotspots: unreachable (nope)" in screens2[0]
