"""Observability layer tests (tier-1, CPU-only, fast).

Covers the acceptance trail of the telemetry PR: the metrics registry
(including histogram bucket math under concurrent writers), Prometheus
exposition + the `pio top` parser round-trip, trace-id propagation
end-to-end (ingress header -> micro-batch -> storage span share one trace
id, in both the ring buffer and the structured JSON log), the re-based
/stats.json, the compile watcher, and counters moving under chaos
(shed/deadline/breaker) on live servers.
"""

import asyncio
import json
import logging
import threading

import pytest
from aiohttp.test_utils import TestClient, TestServer

from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.tracing import (
    TRACE_HEADER,
    Tracer,
    current_trace_id,
    get_tracer,
    mint_trace_id,
    reset_trace_id,
    set_trace_id,
)
from predictionio_tpu.resilience import CLOSED, OPEN
from predictionio_tpu.tools.top import (
    parse_prometheus,
    render,
    run_top,
    summarize,
)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestCounterGauge:
    def test_counter_labels_and_totals(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "help", labelnames=("status",))
        c.inc(status="200")
        c.inc(2, status="200")
        c.inc(status="503")
        assert c.value(status="200") == 3
        assert c.value(status="503") == 1
        assert c.total() == 4

    def test_counter_is_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total")
        with pytest.raises(ValueError):
            c.inc(-1)
        c.inc(5)
        c.set_total(3)  # mirror below current value: clamped, never down
        assert c.value() == 5

    def test_gauge_set_and_callback(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(7)
        assert g.value() == 7
        box = {"v": 1.0}
        g2 = reg.gauge("live_depth")
        g2.set_function(lambda: box["v"])
        box["v"] = 42.0
        assert g2.value() == 42.0
        assert "live_depth 42" in reg.render_prometheus()

    def test_get_or_create_and_conflicts(self):
        reg = MetricsRegistry()
        a = reg.counter("same", labelnames=("x",))
        b = reg.counter("same", labelnames=("x",))
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("same")
        with pytest.raises(ValueError):
            reg.counter("same", labelnames=("y",))
        with pytest.raises(ValueError):
            a.inc(wrong_label="1")

    def test_invalid_names_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("ok", labelnames=("bad-label",))


class TestHistogram:
    def test_percentiles_interpolate_in_bucket(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
        for _ in range(99):
            h.observe(0.05)  # (0.01, 0.1] bucket
        h.observe(5.0)  # +Inf bucket
        s = h.summary()
        assert s["count"] == 100
        assert 0.01 < s["p50"] <= 0.1
        assert 0.01 < s["p95"] <= 0.1
        # p99 still lands in the populated finite bucket (99 of 100)
        assert s["p99"] <= 1.0
        assert s["sum"] == pytest.approx(99 * 0.05 + 5.0)

    def test_empty_summary(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        assert h.summary() == {"count": 0}
        assert h.percentile(0.5) == 0.0

    def test_bucket_math_under_concurrent_writers(self):
        """The satellite guarantee: concurrent observes never lose or
        double-count — total count, per-bucket sums, and _sum agree."""
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.001, 0.01, 0.1, 1.0))
        values = (0.0005, 0.005, 0.05, 0.5, 2.0)
        n_threads, per_thread = 8, 2000

        def hammer(seed: int):
            for i in range(per_thread):
                h.observe(values[(i + seed) % len(values)])

        threads = [
            threading.Thread(target=hammer, args=(t,)) for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = n_threads * per_thread
        s = h.summary()
        assert s["count"] == total
        expected_sum = sum(values) / len(values) * total
        assert s["sum"] == pytest.approx(expected_sum)
        # the rendered cumulative buckets agree with the count
        metrics = parse_prometheus(reg.render_prometheus())
        inf_bucket = [
            v for labels, v in metrics["lat_bucket"] if labels["le"] == "+Inf"
        ]
        assert inf_bucket == [total]
        # each value class landed in exactly one bucket: cumulative counts
        # step by total/len(values) per populated bound
        per_class = total // len(values)
        cums = sorted(v for _, v in metrics["lat_bucket"])
        assert cums == [per_class * (i + 1) for i in range(len(values))]


class TestPrometheusExposition:
    def test_render_and_parse_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "requests", labelnames=("status",)).inc(
            3, status="200"
        )
        reg.gauge("depth", "queue depth").set(2)
        reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.05)
        text = reg.render_prometheus()
        assert "# HELP req_total requests" in text
        assert "# TYPE req_total counter" in text
        assert "# TYPE lat histogram" in text
        parsed = parse_prometheus(text)
        assert parsed["req_total"] == [({"status": "200"}, 3.0)]
        assert parsed["depth"] == [({}, 2.0)]
        assert ({"le": "+Inf"}, 1.0) in parsed["lat_bucket"]
        assert parsed["lat_count"] == [({}, 1.0)]

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("esc_total", labelnames=("msg",)).inc(
            msg='say "hi"\nback\\slash'
        )
        parsed = parse_prometheus(reg.render_prometheus())
        [(labels, value)] = parsed["esc_total"]
        assert value == 1.0
        assert labels["msg"] == 'say "hi"\nback\\slash'

    def test_collectors_run_at_scrape(self):
        reg = MetricsRegistry()
        g = reg.gauge("sampled")
        calls = []
        reg.register_collector(lambda: (calls.append(1), g.set(len(calls))))
        reg.render_prometheus()
        snap = reg.snapshot()
        assert len(calls) == 2
        assert snap["sampled"]["samples"][0]["value"] == 2


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


class TestTracing:
    def test_span_records_ring_and_log(self, caplog):
        tracer = Tracer(ring_size=4)
        token = set_trace_id("feedbeef00000000")
        try:
            with caplog.at_level(logging.INFO, logger="pio.trace"):
                with tracer.span("unit.work", kind="serving", step=1) as sp:
                    sp.tags["extra"] = "yes"
        finally:
            reset_trace_id(token)
        [recent] = tracer.recent()
        assert recent["traceId"] == "feedbeef00000000"
        assert recent["name"] == "unit.work"
        assert recent["kind"] == "serving"
        assert recent["tags"] == {"step": 1, "extra": "yes"}
        assert recent["durationMs"] >= 0
        # the structured log line is the span as one JSON object
        line = json.loads(caplog.records[-1].getMessage())
        assert line["traceId"] == "feedbeef00000000"
        assert line["status"] == "ok"

    def test_span_marks_error_status_and_reraises(self):
        tracer = Tracer()
        with pytest.raises(ValueError):
            with tracer.span("explodes"):
                raise ValueError("boom")
        assert tracer.recent()[0]["status"] == "ValueError"

    def test_ring_is_bounded_newest_first(self):
        tracer = Tracer(ring_size=3)
        for i in range(5):
            tracer.record_span(f"s{i}", "internal", 0.0)
        names = [s["name"] for s in tracer.recent()]
        assert names == ["s4", "s3", "s2"]
        assert tracer.spans_recorded == 5

    def test_span_parent_and_find_oldest_first(self):
        """A span started inside another has it as parent, and ``find``
        orders a request's spans by their monotonic start: the ring holds
        them in the order they ENDED (the child first)."""
        tracer = Tracer()
        with tracer.span("outer", trace_id="abcd") as outer:
            with tracer.span("inner", trace_id="abcd") as inner:
                pass
            late = tracer.record_span(
                "timed", "batch", 0.0, trace_id="abcd", parent_id=outer.span_id
            )
        assert outer.parent_id is None
        assert inner.parent_id == late.parent_id == outer.span_id
        assert [s["name"] for s in tracer.recent()] == ["outer", "timed", "inner"]
        found = tracer.find("abcd")
        assert [s["name"] for s in found] == ["outer", "inner", "timed"]
        starts = [s["startMonoNs"] for s in found]
        assert starts == sorted(starts) and all(isinstance(t, int) for t in starts)
        assert found[1]["parentId"] == found[0]["spanId"] and found[0]["parentId"] is None

    def test_contextvar_isolation(self):
        assert current_trace_id() is None
        token = set_trace_id("aaaa")
        assert current_trace_id() == "aaaa"
        reset_trace_id(token)
        assert current_trace_id() is None

    def test_mint_is_unique(self):
        assert mint_trace_id() != mint_trace_id()


# ---------------------------------------------------------------------------
# compile watcher
# ---------------------------------------------------------------------------


class TestCompileWatcher:
    def test_counts_recompiles_after_baseline(self, caplog):
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.obs.jaxprof import CompileWatcher

        reg = MetricsRegistry()
        watcher = CompileWatcher(reg, storm_threshold=2)

        @jax.jit
        def f(x):
            return x * 2

        f(jnp.ones(2))  # warmup compile, then baseline
        assert watcher.watch("test.f", f)
        assert watcher.sample() == 0  # baseline: warmup doesn't count
        f(jnp.ones(2))  # cache hit
        assert watcher.sample() == 0
        with caplog.at_level(logging.WARNING):
            f(jnp.ones(3))  # new shape -> recompile
            f(jnp.ones(4))  # another -> storm at threshold 2
            assert watcher.sample() == 2
        assert watcher.total_misses() == 2
        assert any("recompile storm" in r.getMessage() for r in caplog.records)
        parsed = parse_prometheus(reg.render_prometheus())
        assert (
            sum(v for _, v in parsed["pio_jit_cache_misses_total"]) == 2
        )
        sizes = {l["fn"]: v for l, v in parsed["pio_jit_cache_size"]}
        assert sizes["test.f"] == 3


# ---------------------------------------------------------------------------
# stats.json re-base
# ---------------------------------------------------------------------------


class TestStatsRebase:
    def _event(self, name="rate", target=None):
        from predictionio_tpu.data.event import Event

        return Event(
            event=name,
            entity_type="user",
            entity_id="u1",
            target_entity_type=target,
            target_entity_id="i1" if target else None,
        )

    def test_legacy_shape_and_registry_agree(self):
        from predictionio_tpu.data.api.stats import StatsCollector

        reg = MetricsRegistry()
        stats = StatsCollector(registry=reg)
        stats.bookkeeping(1, 201, self._event())
        stats.bookkeeping(1, 201, self._event(target="item"))
        stats.bookkeeping(1, 500, self._event())
        stats.bookkeeping(2, 201, self._event())  # other app: filtered out
        out = stats.get_stats(1)
        assert out["longLive"]["statusCode"] == [
            {"status": 201, "count": 2},
            {"status": 500, "count": 1},
        ]
        basic = out["longLive"]["basic"]
        assert {b["event"] for b in basic} == {"rate"}
        assert {b["targetEntityType"] for b in basic} == {None, "item"}
        assert out["currentHour"]["statusCode"] == out["longLive"]["statusCode"]
        assert "prevHour" not in out
        # the same totals back /metrics
        parsed = parse_prometheus(reg.render_prometheus())
        totals = {
            (l["app_id"], l["status"]): v
            for l, v in parsed["pio_events_ingested_total"]
        }
        assert totals[("1", "201")] == 2
        assert totals[("2", "201")] == 1


# ---------------------------------------------------------------------------
# query server end-to-end
# ---------------------------------------------------------------------------


def _run_query_server(body, **cfg_kw):
    import sys

    sys.path.insert(0, "tests") if "tests" not in sys.path else None
    from tests.test_resilience import _make_query_server

    async def outer():
        get_tracer().clear()
        server = _make_query_server(**cfg_kw)
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            await body(client, server)
        finally:
            await client.close()

    asyncio.run(outer())


class TestQueryServerObs:
    def test_metrics_endpoint_is_prometheus_parseable(self):
        """Acceptance: GET /metrics on a deployed QueryServer returns
        Prometheus-parseable text including the request latency histogram,
        admission-queue depth, breaker state, and jit recompile count."""

        async def body(client, server):
            for qid in range(3):
                resp = await client.post("/queries.json", json={"qid": qid})
                assert resp.status == 200
            m = await client.get("/metrics")
            assert m.status == 200
            assert m.headers["Content-Type"].startswith("text/plain")
            parsed = parse_prometheus(await m.text())
            assert (
                {"endpoint": "/queries.json", "status": "200"},
                3.0,
            ) in parsed["pio_requests_total"]
            assert any(
                l.get("le") == "+Inf" and v == 3.0
                for l, v in parsed["pio_request_seconds_bucket"]
            )
            assert parsed["pio_queue_depth"] == [({}, 0.0)]
            assert ({"breaker": "dispatch"}, 0.0) in parsed["pio_breaker_state"]
            # jit recompile count present (0 after warmup baseline is fine)
            assert "pio_jit_recompile_storm" in parsed
            assert "pio_load_shed_total" in parsed
            assert "pio_deadline_exceeded_total" in parsed

        _run_query_server(body)

    def test_trace_id_spans_ingress_batch_and_storage(self, memory_storage):
        """Acceptance: one trace id observed across ingress, batch, and
        storage spans — via the ring buffer, /traces/recent, and the
        structured JSON log."""
        from predictionio_tpu.data.storage.traced import trace_dao
        from tests.sample_engine import Serving0

        traced_apps = trace_dao(
            memory_storage.get_meta_data_apps(), "apps"
        )

        class StorageTouchingServing(Serving0):
            """Realistic query-time storage read (e.g. the ecommerce
            template fetching recent user events at predict time)."""

            def supplement(self, query):
                traced_apps.get_all()
                return query

        tid = mint_trace_id()

        async def body(client, server):
            trace_logger = logging.getLogger("pio.trace")
            records: list[str] = []

            class Capture(logging.Handler):
                def emit(self, record):
                    records.append(record.getMessage())

            handler = Capture(level=logging.INFO)
            old_level = trace_logger.level
            trace_logger.setLevel(logging.INFO)
            trace_logger.addHandler(handler)
            try:
                resp = await client.post(
                    "/queries.json",
                    json={"qid": 5},
                    headers={TRACE_HEADER: tid},
                )
                assert resp.status == 200
                assert resp.headers[TRACE_HEADER] == tid
            finally:
                trace_logger.removeHandler(handler)
                trace_logger.setLevel(old_level)
            spans = get_tracer().find(tid)
            kinds = {s["kind"] for s in spans}
            assert {"ingress", "batch", "storage"} <= kinds, spans
            storage_span = next(s for s in spans if s["kind"] == "storage")
            assert storage_span["name"] == "storage.apps.get_all"
            batch_span = next(s for s in spans if s["kind"] == "batch")
            for key in ("queue_ms", "dispatch_ms", "fetch_ms"):
                assert key in batch_span["tags"]
            # /traces/recent serves the same spans
            t = await client.get("/traces/recent?limit=50")
            served = [s for s in (await t.json())["spans"] if s["traceId"] == tid]
            assert {s["kind"] for s in served} >= {"ingress", "batch", "storage"}
            # the structured log saw all three hops under ONE trace id
            logged = [json.loads(r) for r in records]
            logged_kinds = {s["kind"] for s in logged if s["traceId"] == tid}
            assert {"ingress", "batch", "storage"} <= logged_kinds

        # swap the serving class into the engine the helper builds
        import sys

        sys.path.insert(0, "tests") if "tests" not in sys.path else None
        from tests.test_resilience import _make_query_server

        async def outer():
            get_tracer().clear()
            server = _make_query_server()
            engine = server.engine
            engine.serving_classes = {"s": StorageTouchingServing}
            server._active = server._active._replace(
                serving=StorageTouchingServing()
            )
            client = TestClient(TestServer(server.make_app()))
            await client.start_server()
            try:
                await body(client, server)
            finally:
                await client.close()

        asyncio.run(outer())

    def test_batch_span_has_the_ingress_span_as_parent(self):
        """The ingress span's id rides the queued item across the
        micro-batcher: a request's spans order and say which caused which."""
        tid = mint_trace_id()

        async def body(client, server):
            resp = await client.post(
                "/queries.json", json={"qid": 1}, headers={TRACE_HEADER: tid}
            )
            assert resp.status == 200
            ingress, batch = get_tracer().find(tid)
            assert (ingress["kind"], batch["kind"]) == ("ingress", "batch")
            assert ingress["parentId"] is None
            assert batch["parentId"] == ingress["spanId"]
            assert batch["startMonoNs"] >= ingress["startMonoNs"]
            # the batcher's running number of the batch, as on its pio: spans
            assert batch["tags"]["batch"] == server._batcher._batch_seq == 1

        _run_query_server(body)

    def test_shed_and_deadline_counters_move_under_chaos(self):
        """Acceptance: a chaos run shows shed/deadline counters moving."""
        from tests.sample_engine import Algo0

        async def body(client, server):
            # wedge the dispatch path so queries pile into the queue
            original = Algo0.predict_batch_dispatch

            def slow_dispatch(self, model, queries):
                import time as _t

                _t.sleep(0.4)  # > request_timeout_s
                return original(self, model, queries)

            Algo0.predict_batch_dispatch = slow_dispatch
            try:
                results = await asyncio.gather(
                    *(
                        client.post("/queries.json", json={"qid": i})
                        for i in range(8)
                    )
                )
                statuses = [r.status for r in results]
                assert all(s in (200, 503) for s in statuses)
                assert 503 in statuses
            finally:
                Algo0.predict_batch_dispatch = original
            parsed = parse_prometheus(await (await client.get("/metrics")).text())
            shed = sum(v for _, v in parsed.get("pio_load_shed_total", ()))
            deadlines = sum(
                v for _, v in parsed.get("pio_deadline_exceeded_total", ())
            )
            assert shed + deadlines > 0
            # 503s are counted per status by the envelope
            assert any(
                l.get("status") == "503" and v > 0
                for l, v in parsed["pio_requests_total"]
            )

        _run_query_server(
            body,
            request_timeout_s=0.15,
            queue_high_water=2,
            max_batch_size=1,
        )

    def test_breaker_transitions_counted(self):
        async def body(client, server):
            for _ in range(server.config.breaker_threshold):
                server.dispatch_breaker.record_failure()
            assert server.dispatch_breaker.state == OPEN
            parsed = parse_prometheus(await (await client.get("/metrics")).text())
            assert (
                {"breaker": "dispatch", "to": "open"},
                1.0,
            ) in parsed["pio_breaker_transitions_total"]
            assert ({"breaker": "dispatch"}, 2.0) in parsed["pio_breaker_state"]
            server.dispatch_breaker.reset()
            parsed = parse_prometheus(await (await client.get("/metrics")).text())
            assert (
                {"breaker": "dispatch", "to": "closed"},
                1.0,
            ) in parsed["pio_breaker_transitions_total"]

        _run_query_server(body)


# ---------------------------------------------------------------------------
# event server end-to-end
# ---------------------------------------------------------------------------


EVENT = {"event": "rate", "entityType": "user", "entityId": "u1"}


def _run_event_server(body):
    import sys

    sys.path.insert(0, "tests") if "tests" not in sys.path else None
    from tests.test_resilience import _make_event_server

    async def outer():
        get_tracer().clear()
        server, injector, key = _make_event_server()
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            await body(client, server, injector, key)
        finally:
            await client.close()

    asyncio.run(outer())


class TestEventServerObs:
    def test_metrics_and_trace_header(self):
        async def body(client, server, injector, key):
            tid = mint_trace_id()
            resp = await client.post(
                f"/events.json?accessKey={key}",
                json=EVENT,
                headers={TRACE_HEADER: tid},
            )
            assert resp.status == 201
            assert resp.headers[TRACE_HEADER] == tid
            # the storage span joined the ingress trace across the
            # executor hop
            spans = get_tracer().find(tid)
            kinds = {s["kind"] for s in spans}
            assert {"ingress", "storage"} <= kinds, spans
            names = {s["name"] for s in spans}
            assert "storage.l_events.insert" in names
            parsed = parse_prometheus(await (await client.get("/metrics")).text())
            assert (
                {"endpoint": "/events.json", "status": "201"},
                1.0,
            ) in parsed["pio_requests_total"]
            # ingestion counters are always-on (the --stats flag only
            # gates serving the legacy /stats.json view)
            assert any(
                l["status"] == "201" and v == 1.0
                for l, v in parsed["pio_events_ingested_total"]
            )
            assert ({"breaker": "eventdata"}, 0.0) in parsed["pio_breaker_state"]

        _run_event_server(body)

    def test_retry_and_breaker_counters_move_under_chaos(self):
        """Acceptance: chaos shows retry + breaker counters moving."""

        async def body(client, server, injector, key):
            injector.inject("insert", fail_count=1)
            resp = await client.post(f"/events.json?accessKey={key}", json=EVENT)
            assert resp.status == 201  # retried through the transient fault
            parsed = parse_prometheus(await (await client.get("/metrics")).text())
            assert sum(
                v for _, v in parsed["pio_storage_retries_total"]
            ) >= 1.0
            # now a persistent fault trips the breaker
            injector.inject("insert", fail_count=1000)
            await client.post(f"/events.json?accessKey={key}", json=EVENT)
            assert server.storage_policy.breaker.state == OPEN
            parsed = parse_prometheus(await (await client.get("/metrics")).text())
            assert (
                {"breaker": "eventdata", "to": "open"},
                1.0,
            ) in parsed["pio_breaker_transitions_total"]
            assert ({"breaker": "eventdata"}, 2.0) in parsed["pio_breaker_state"]
            server.storage_policy.breaker.reset()

        _run_event_server(body)

    def test_stats_json_still_backward_compatible(self):
        import sys

        sys.path.insert(0, "tests") if "tests" not in sys.path else None
        from predictionio_tpu.data.api.event_server import (
            EventServer,
            EventServerConfig,
        )
        from tests.test_event_server import make_storage

        async def outer():
            storage, key = make_storage()
            server = EventServer(
                storage=storage, config=EventServerConfig(stats=True)
            )
            client = TestClient(TestServer(server.make_app()))
            await client.start_server()
            try:
                await client.post(f"/events.json?accessKey={key}", json=EVENT)
                resp = await client.get(f"/stats.json?accessKey={key}")
                assert resp.status == 200
                data = await resp.json()
                assert data["longLive"]["statusCode"] == [
                    {"status": 201, "count": 1}
                ]
                assert data["longLive"]["basic"][0]["event"] == "rate"
                assert data["currentHour"]["startTime"]
            finally:
                await client.close()

        asyncio.run(outer())


# ---------------------------------------------------------------------------
# pio top + dashboard panels
# ---------------------------------------------------------------------------


def _fake_metrics_text(requests=100.0, shed=5.0) -> str:
    reg = MetricsRegistry()
    reg.counter(
        "pio_requests_total", labelnames=("endpoint", "status")
    ).inc(requests, endpoint="/queries.json", status="200")
    reg.counter("pio_load_shed_total").inc(shed)
    reg.counter("pio_deadline_exceeded_total").inc(2)
    reg.gauge("pio_queue_depth").set(3)
    reg.gauge("pio_queue_high_water").set(256)
    reg.gauge("pio_breaker_state", labelnames=("breaker",)).set(
        2, breaker="dispatch"
    )
    reg.counter("pio_jit_cache_misses_total", labelnames=("fn",)).inc(
        4, fn="ops.als._topk"
    )
    h = reg.histogram("pio_request_seconds", labelnames=("endpoint",))
    for v in (0.002, 0.004, 0.008, 0.2):
        h.observe(v, endpoint="/queries.json")
    return reg.render_prometheus()


class TestPioTop:
    def test_summarize_single_sample(self):
        s = summarize(parse_prometheus(_fake_metrics_text()))
        assert s["requests_total"] == 100
        assert s["shed_total"] == 5
        assert s["queue_depth"] == 3
        assert s["queue_high_water"] == 256
        assert s["recompiles"] == 4
        assert s["breakers"] == {"dispatch": "open"}
        assert s["qps"] is None  # needs two samples
        assert 0 < s["p50_ms"] < s["p99_ms"]

    def test_rates_from_two_samples(self):
        prev = parse_prometheus(_fake_metrics_text(requests=100, shed=5))
        cur = parse_prometheus(_fake_metrics_text(requests=150, shed=10))
        s = summarize(cur, prev=prev, interval_s=2.0)
        assert s["qps"] == pytest.approx(25.0)
        assert s["shed_rate"] == pytest.approx(2.5)

    def test_render_one_screen(self):
        s = summarize(parse_prometheus(_fake_metrics_text()))
        screen = render(s, "http://x:8000")
        assert "qps" in screen and "p95" in screen
        assert "dispatch=open" in screen
        assert "recompiles" in screen

    def test_stream_line_absent_without_stream_metrics(self):
        s = summarize(parse_prometheus(_fake_metrics_text()))
        assert s["stream"] is None
        assert "stream" not in render(s, "http://x")

    def test_stream_line_parsed_and_rendered(self):
        text = "\n".join(
            [
                "pio_stream_lag_events 42",
                "pio_stream_lag_seconds 3.5",
                "pio_stream_drains_total 120",
                "pio_stream_events_total 6000",
                "pio_stream_publishes_total 4",
                "pio_stream_drift_suppressed_total 1",
                "pio_stream_last_publish_timestamp 990",
            ]
        )
        s = summarize(parse_prometheus(text), now=1000.0)
        assert s["stream"]["lag_events"] == 42
        assert s["stream"]["lag_seconds"] == pytest.approx(3.5)
        assert s["stream"]["publishes_total"] == 4
        assert s["stream"]["drift_suppressed"] == 1
        assert s["stream"]["last_publish_age_s"] == pytest.approx(10.0)
        screen = render(s, "http://x")
        assert "stream" in screen
        assert "lag 42 ev / 3.5s" in screen
        assert "published 4 (age 10s)" in screen
        assert "drift-suppressed 1" in screen

    def test_stream_drain_rate_from_two_samples(self):
        prev = parse_prometheus("pio_stream_drains_total 100")
        cur = parse_prometheus("pio_stream_drains_total 110")
        s = summarize(cur, prev=prev, interval_s=5.0)
        assert s["stream_drain_rate"] == pytest.approx(2.0)
        assert "drains 2/s (110)" in render(s, "http://x")

    def test_run_top_loop_with_injected_fetch(self):
        screens: list[str] = []
        fetches = []

        def fetch(url):
            fetches.append(url)
            return _fake_metrics_text(requests=100 * (len(fetches)))

        rc = run_top(
            "http://fake:1",
            interval_s=0.0,
            iterations=3,
            fetch=fetch,
            out=screens.append,
            clear_screen=False,
            sleep=lambda s: None,
        )
        assert rc == 0
        assert len(screens) == 3
        assert "pio top — http://fake:1" in screens[0]

    def test_run_top_unreachable(self):
        screens: list[str] = []

        def fetch(url):
            raise ConnectionError("nope")

        rc = run_top(
            "http://down:1",
            iterations=1,
            fetch=fetch,
            out=screens.append,
            clear_screen=False,
        )
        assert rc == 0
        assert "unreachable" in screens[0]

    def test_cli_top_subcommand_registered(self):
        from predictionio_tpu.tools.cli import build_parser

        args = build_parser().parse_args(
            ["top", "--url", "http://h:8000", "--once"]
        )
        assert args.url == "http://h:8000" and args.once

    def test_top_against_live_server(self):
        """pio top's fetch/parse path against a real QueryServer."""

        async def body(client, server):
            await client.post("/queries.json", json={"qid": 1})
            text = await (await client.get("/metrics")).text()
            s = summarize(parse_prometheus(text))
            assert s["requests_total"] == 1
            assert s["breakers"].get("dispatch") == CLOSED
            assert render(s, "live")  # renders without raising

        _run_query_server(body)


class TestDashboardPanels:
    def test_panels_render_from_metrics(self, memory_storage):
        from predictionio_tpu.tools.dashboard import Dashboard

        dash = Dashboard(
            storage=memory_storage,
            metrics_urls=["http://qs:8000", "http://down:9"],
        )

        async def fake_fetch(url):
            return _fake_metrics_text() if "qs" in url else None

        dash._fetch_metrics = fake_fetch

        async def outer():
            client = TestClient(TestServer(dash.make_app()))
            await client.start_server()
            try:
                resp = await client.get("/")
                assert resp.status == 200
                page = await resp.text()
                assert "http://qs:8000" in page
                assert "state-open" in page  # breaker panel shows the state
                assert "jit recompiles" in page
                assert "unreachable" in page  # the down server degrades
            finally:
                await client.close()

        asyncio.run(outer())

    def test_no_sources_hint(self, memory_storage):
        from predictionio_tpu.tools.dashboard import Dashboard

        dash = Dashboard(storage=memory_storage)

        async def outer():
            client = TestClient(TestServer(dash.make_app()))
            await client.start_server()
            try:
                page = await (await client.get("/")).text()
                assert "--metrics-url" in page
            finally:
                await client.close()

        asyncio.run(outer())


# ---------------------------------------------------------------------------
# histogram exemplars
# ---------------------------------------------------------------------------


class TestExemplars:
    def test_observe_with_exemplar_and_accessor(self):
        reg = MetricsRegistry()
        h = reg.histogram("ex_seconds", labelnames=("phase",))
        h.observe(0.0003, exemplar="aaaa000011112222", phase="fetch")
        h.observe(0.2, exemplar="bbbb000011112222", phase="fetch")
        ex = h.exemplars(phase="fetch")
        assert ex["0.0005"]["exemplar"] == "aaaa000011112222"
        assert ex["0.25"]["exemplar"] == "bbbb000011112222"
        assert ex["0.25"]["value"] == pytest.approx(0.2)

    def test_last_writer_wins_per_bucket(self):
        reg = MetricsRegistry()
        h = reg.histogram("ex2_seconds")
        h.observe(0.0003, exemplar="first000")
        h.observe(0.0004, exemplar="second00")
        assert h.exemplars()["0.0005"]["exemplar"] == "second00"

    def test_render_plain_vs_openmetrics(self):
        reg = MetricsRegistry()
        h = reg.histogram("ex3_seconds")
        h.observe(0.0003, exemplar="cafe0000deadbeef")
        plain = reg.render_prometheus()
        assert "trace_id" not in plain  # strict v0.0.4 stays strict
        assert "# EOF" not in plain
        om = reg.render_prometheus(exemplars=True)
        assert '# {trace_id="cafe0000deadbeef"} 0.0003' in om
        assert om.rstrip().endswith("# EOF")

    def test_top_parser_tolerates_exemplar_clauses(self):
        reg = MetricsRegistry()
        h = reg.histogram("ex4_seconds")
        for v in (0.0003, 0.002, 0.3):
            h.observe(v, exemplar="feed0000feed0000")
        parsed = parse_prometheus(reg.render_prometheus(exemplars=True))
        # every bucket line still parses to its numeric value
        assert sum(
            v for l, v in parsed["ex4_seconds_bucket"] if l.get("le") == "+Inf"
        ) == 3.0
        assert parsed["ex4_seconds_count"] == [({}, 3.0)]


# ---------------------------------------------------------------------------
# phase waterfall end-to-end (the latency-attribution acceptance trail)
# ---------------------------------------------------------------------------


PHASE_NAMES = (
    "ingress_parse",
    "cache",  # version-keyed result-cache lookup (PR 8)
    "queue_wait",
    "batch_assembly",
    "dispatch",
    "device_compute",
    "fetch",
    "serve",
    "respond",
)


class TestWaterfallE2E:
    def test_phases_tile_e2e_latency_within_tolerance(self):
        """Acceptance: a serving round-trip produces a phase waterfall
        whose per-phase means sum to within 10% of the measured e2e
        latency (they tile the same wall clock by construction)."""

        async def body(client, server):
            for i in range(40):
                resp = await client.post("/queries.json", json={"qid": i})
                assert resp.status == 200
            hist = server.waterfall.hist
            counts = {p: hist.summary(phase=p).get("count") for p in PHASE_NAMES}
            assert all(c == 40 for c in counts.values()), counts
            phase_sum = sum(hist.summary(phase=p)["mean"] for p in PHASE_NAMES)
            e2e = server._m_latency.summary(endpoint="/queries.json")["mean"]
            assert phase_sum == pytest.approx(e2e, rel=0.10)

        _run_query_server(body)

    def test_phase_exemplar_resolves_to_trace(self):
        """Acceptance: every phase is visible on /metrics with an exemplar
        trace id resolvable in /traces/recent."""
        import re as _re

        async def body(client, server):
            for i in range(5):
                await client.post("/queries.json", json={"qid": i})
            m = await client.get("/metrics?exemplars=1")
            assert m.headers["Content-Type"].startswith(
                "application/openmetrics-text"
            )
            text = await m.text()
            by_phase: dict[str, set] = {}
            for match in _re.finditer(
                r'pio_phase_seconds_bucket\{phase="([a-z_]+)"[^}]*\}'
                r' \d+ # \{trace_id="([0-9a-f]+)"\}',
                text,
            ):
                by_phase.setdefault(match.group(1), set()).add(match.group(2))
            assert set(by_phase) == set(PHASE_NAMES), sorted(by_phase)
            served = (await (await client.get("/traces/recent?limit=500")).json())[
                "spans"
            ]
            ring_ids = {s["traceId"] for s in served}
            for phase, tids in by_phase.items():
                assert tids & ring_ids, f"{phase} exemplars not in trace ring"

        _run_query_server(body)

    def test_batch_and_ingress_spans_carry_phase_tags(self):
        tid = mint_trace_id()

        async def body(client, server):
            await client.post(
                "/queries.json", json={"qid": 1}, headers={TRACE_HEADER: tid}
            )
            spans = get_tracer().find(tid)
            batch = next(s for s in spans if s["kind"] == "batch")
            for key in (
                "queue_ms",
                "dispatch_ms",
                "fetch_ms",
                "device_compute_ms",
                "serve_ms",
                "fetch_residual_ms",
            ):
                assert key in batch["tags"], batch["tags"]
            ingress = next(s for s in spans if s["kind"] == "ingress")
            assert "ingress_parse_ms" in ingress["tags"]
            assert "respond_ms" in ingress["tags"]

        _run_query_server(body)

    def test_default_metrics_scrape_stays_plain_v004(self):
        async def body(client, server):
            await client.post("/queries.json", json={"qid": 1})
            m = await client.get("/metrics")
            assert m.headers["Content-Type"].startswith("text/plain")
            assert "trace_id" not in await m.text()

        _run_query_server(body)


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------


class TestSLOEngine:
    def _engine_with_counter(self, objective=0.999):
        from predictionio_tpu.obs.slo import SLOEngine, counter_ratio_source

        reg = MetricsRegistry()
        c = reg.counter("t_total", labelnames=("status",))
        engine = SLOEngine(reg)
        engine.add(
            "availability",
            "non-5xx",
            objective,
            counter_ratio_source(
                c, bad=lambda l: l.get("status", "").startswith("5")
            ),
        )
        return reg, c, engine

    def test_burn_rate_math_multi_window(self):
        reg, c, engine = self._engine_with_counter(objective=0.999)
        c.inc(100, status="200")
        engine.tick(now=0.0)
        c.inc(90, status="200")
        c.inc(10, status="503")
        engine.tick(now=100.0)
        [report] = engine.evaluate(now=100.0)
        fast, slow = report["windows"]
        # 10 bad / 100 total over the window = 10% bad; budget 0.1% -> 100x
        assert fast["bad_ratio"] == pytest.approx(0.1)
        assert fast["burn_rate"] == pytest.approx(100.0)
        assert slow["burn_rate"] == pytest.approx(100.0)
        assert report["alerting"] is True
        assert report["budget_remaining"] == 0.0
        # gauges refreshed for pio top / Prometheus
        parsed = parse_prometheus(reg.render_prometheus())
        burns = {
            l["window"]: v
            for l, v in parsed["pio_slo_burn_rate"]
            if l["slo"] == "availability"
        }
        assert burns["300"] == pytest.approx(100.0)
        assert ({"slo": "availability"}, 1.0) in parsed["pio_slo_alerting"]

    def test_healthy_traffic_not_alerting(self):
        reg, c, engine = self._engine_with_counter(objective=0.5)
        c.inc(100, status="200")
        engine.tick(now=0.0)
        c.inc(100, status="200")
        c.inc(10, status="503")
        engine.tick(now=60.0)
        [report] = engine.evaluate(now=60.0)
        # ~9% bad against a 50% budget: burn ~0.18, nowhere near threshold
        assert report["windows"][0]["burn_rate"] < 1.0
        assert report["alerting"] is False
        assert report["budget_remaining"] > 0.5

    def test_single_sample_is_no_data_not_alert(self):
        reg, c, engine = self._engine_with_counter()
        c.inc(5, status="500")
        engine.tick(now=0.0)
        [report] = engine.evaluate(now=0.0)
        assert report["alerting"] is False
        assert all(w["burn_rate"] == 0.0 for w in report["windows"])

    def test_histogram_threshold_source_counts_over_threshold(self):
        from predictionio_tpu.obs.slo import histogram_threshold_source

        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", labelnames=("endpoint",))
        for _ in range(9):
            h.observe(0.005, endpoint="/q")
        h.observe(0.05, endpoint="/q")
        src = histogram_threshold_source(h, 0.010, endpoint="/q")
        total, bad = src()
        assert (total, bad) == (10, 1)

    def test_loose_objective_can_still_alert(self):
        """Burn is bounded by 1/budget, so the SRE-default thresholds
        (14.4/6) are unreachable for a p50-style objective of 0.50 —
        thresholds must clamp to the achievable ceiling or the flagship
        latency SLO could structurally never alert."""
        reg, c, engine = self._engine_with_counter(objective=0.50)
        c.inc(10, status="200")
        engine.tick(now=0.0)
        c.inc(100, status="503")  # every event bad: burn = 1/0.5 = 2.0
        engine.tick(now=100.0)
        [report] = engine.evaluate(now=100.0)
        assert report["windows"][0]["burn_rate"] == pytest.approx(2.0)
        # clamped threshold: min(14.4, 0.9 * 2.0) = 1.8 < 2.0 -> alert
        assert report["windows"][0]["max_burn"] == pytest.approx(1.8)
        assert report["alerting"] is True

    def test_event_server_availability_rates_collection_routes_only(self):
        """A 100% ingestion outage must alert even while health checks
        and scrapes (counted by the same middleware) keep succeeding."""

        async def body(client, server, injector, key):
            # monitoring traffic: healthy non-collection requests
            for _ in range(20):
                server._m_requests.inc(endpoint="/healthz", status="200")
            server.slo.tick(now=0.0)
            # the entire collection API fails
            for _ in range(10):
                server._m_requests.inc(endpoint="/events.json", status="503")
            for _ in range(20):
                server._m_requests.inc(endpoint="/healthz", status="200")
            server.slo.tick(now=100.0)
            [report] = server.slo.evaluate(now=100.0)
            fast = report["windows"][0]
            assert fast["total"] == 10.0  # /healthz not in the denominator
            assert fast["bad_ratio"] == pytest.approx(1.0)
            assert report["alerting"] is True

        _run_event_server(body)

    def test_duplicate_and_invalid_objectives_rejected(self):
        reg, c, engine = self._engine_with_counter()
        with pytest.raises(ValueError):
            engine.add("availability", "dup", 0.9, lambda: (0, 0))
        with pytest.raises(ValueError):
            engine.add("impossible", "no budget", 1.0, lambda: (0, 0))

    def test_slo_endpoint_on_live_server(self):
        async def body(client, server):
            for i in range(3):
                await client.post("/queries.json", json={"qid": i})
            resp = await client.get("/slo")
            assert resp.status == 200
            data = await resp.json()
            names = {s["name"] for s in data["slos"]}
            assert names == {"latency", "availability", "shed"}
            for s in data["slos"]:
                assert {"objective", "windows", "alerting"} <= set(s)
            # the /slo report embeds the phase waterfall summary
            assert set(data["phases"]) == set(PHASE_NAMES)

        _run_query_server(body)

    def test_event_server_slo_endpoint(self):
        async def body(client, server, injector, key):
            await client.post(f"/events.json?accessKey={key}", json=EVENT)
            data = await (await client.get("/slo")).json()
            assert [s["name"] for s in data["slos"]] == ["availability"]

        _run_event_server(body)


# ---------------------------------------------------------------------------
# pio top: waterfall + SLO + --json
# ---------------------------------------------------------------------------


def _waterfall_metrics_text() -> str:
    reg = MetricsRegistry()
    h = reg.histogram("pio_phase_seconds", labelnames=("phase",))
    for phase, v in (
        ("ingress_parse", 0.0002),
        ("queue_wait", 0.0001),
        ("dispatch", 0.002),
        ("fetch", 0.004),
    ):
        h.observe(v, phase=phase)
    reg.gauge("pio_slo_objective", labelnames=("slo",)).set(0.5, slo="latency")
    g = reg.gauge("pio_slo_burn_rate", labelnames=("slo", "window"))
    g.set(0.4, slo="latency", window="300")
    g.set(0.2, slo="latency", window="3600")
    reg.gauge("pio_slo_alerting", labelnames=("slo",)).set(1.0, slo="latency")
    return _fake_metrics_text() + reg.render_prometheus()


class TestTopWaterfallSLO:
    def test_phases_and_slo_summarized(self):
        s = summarize(parse_prometheus(_waterfall_metrics_text()))
        assert list(s["phases"]) == [
            "ingress_parse",
            "queue_wait",
            "dispatch",
            "fetch",
        ]  # request order, not alphabetical
        assert s["phases"]["fetch"]["count"] == 1
        assert s["phases"]["fetch"]["p50_ms"] > s["phases"]["queue_wait"]["p50_ms"]
        assert s["slo"]["latency"]["objective"] == 0.5
        assert s["slo"]["latency"]["burn"] == {"300": 0.4, "3600": 0.2}
        assert s["slo"]["latency"]["alerting"] is True

    def test_render_waterfall_and_slo_lines(self):
        s = summarize(parse_prometheus(_waterfall_metrics_text()))
        screen = render(s, "http://x")
        assert "waterfall" in screen
        assert "ingress parse" in screen and "fetch" in screen
        assert "slo" in screen
        assert "latency burn 0.40/0.20 ALERT" in screen

    def test_absent_without_waterfall_metrics(self):
        s = summarize(parse_prometheus(_fake_metrics_text()))
        assert s["phases"] is None and s["slo"] is None
        screen = render(s, "http://x")
        assert "waterfall" not in screen and "slo" not in screen

    def test_json_mode_one_object_per_snapshot(self):
        outs: list[str] = []
        rc = run_top(
            "http://fake:1",
            interval_s=0.0,
            iterations=3,
            fetch=lambda url: _waterfall_metrics_text(),
            out=outs.append,
            sleep=lambda s: None,
            json_mode=True,
        )
        assert rc == 0
        assert len(outs) == 3
        for line in outs:
            snap = json.loads(line)  # every snapshot is one valid JSON line
            assert snap["url"] == "http://fake:1"
            assert snap["phases"]["dispatch"]["count"] == 1
            assert snap["slo"]["latency"]["alerting"] is True
            assert "\x1b" not in line  # no screen control codes

    def test_json_mode_unreachable_is_json_too(self):
        outs: list[str] = []

        def fetch(url):
            raise ConnectionError("nope")

        run_top(
            "http://down:1",
            iterations=1,
            fetch=fetch,
            out=outs.append,
            json_mode=True,
        )
        assert json.loads(outs[0])["error"] == "nope"

    def test_cli_top_json_flag(self):
        from predictionio_tpu.tools.cli import build_parser

        args = build_parser().parse_args(["top", "--json", "--once"])
        assert args.json and args.once


# ---------------------------------------------------------------------------
# metrics contract: every documented pio_* metric is actually registered
# ---------------------------------------------------------------------------


class TestMetricsContract:
    def test_documented_metrics_all_registered(self, tmp_path):
        """Every `pio_*` metric named in the docs/observability.md tables
        must be registered (and therefore exported with a # TYPE line) by
        the surface that owns it — docs that drift from the exporters are
        worse than no docs."""
        import os
        import re as _re
        import sys

        sys.path.insert(0, "tests") if "tests" not in sys.path else None
        from predictionio_tpu.fleet.gateway import Gateway, GatewayConfig
        from predictionio_tpu.fleet.supervisor import Supervisor, WorkerSpec
        from predictionio_tpu.obs.metrics import MetricsRegistry
        from predictionio_tpu.stream.pipeline import StreamInstruments
        from tests.test_resilience import _make_event_server, _make_query_server

        doc = open(
            os.path.join(os.path.dirname(__file__), "..", "docs", "observability.md")
        ).read()
        documented = set()
        for line in doc.splitlines():
            if line.lstrip().startswith("|"):
                documented.update(_re.findall(r"`(pio_[a-z0-9_]+)`", line))
        assert len(documented) > 30, "doc tables went missing?"

        registered: set[str] = set()
        qs = _make_query_server()
        registered.update(qs.metrics._metrics)
        es, _, _ = _make_event_server()
        registered.update(es.metrics._metrics)
        registered.update(StreamInstruments().registry._metrics)
        # the olmoe scorer's family is its algorithm's: a query server that
        # serves it hands over its registry (`register_metrics`)
        from predictionio_tpu.models.sequential.metrics import BackboneInstruments

        registered.update(BackboneInstruments().registry._metrics)
        # the offline batchpredict family rides the run's own registry
        # (no server to scrape — docs/batch_predict.md)
        from predictionio_tpu.workflow.batch_predict import (
            BatchPredictInstruments,
        )

        registered.update(BatchPredictInstruments().registry._metrics)
        # the evaluation-grid family rides the grid run's own registry
        # (docs/evaluation.md)
        from predictionio_tpu.tuning import EvalGridInstruments

        registered.update(EvalGridInstruments().registry._metrics)
        # the fleet family lives on the gateway/supervisor registry (the
        # `pio deploy --fleet` parent), not on any worker's — including
        # the flight-recorder instruments (telemetry ring + incidents)
        from predictionio_tpu.fleet.worklog import WorkerLogBook
        from predictionio_tpu.obs.incidents import IncidentRecorder

        fleet_metrics = MetricsRegistry()
        Gateway(
            GatewayConfig(replica_urls=("http://127.0.0.1:1",)),
            metrics=fleet_metrics,
        )
        sup = Supervisor(
            spawn=lambda spec: None,
            specs=[WorkerSpec(name="w0", port=1)],
            metrics=fleet_metrics,
            logbook=WorkerLogBook(str(tmp_path / "logs")),
        )
        IncidentRecorder(str(tmp_path / "incidents"), metrics=fleet_metrics)
        # the pio_autoscaler_* family rides the same fleet-parent registry
        from predictionio_tpu.fleet.autoscaler import (
            Autoscaler,
            AutoscalerConfig,
            ScalingPolicy,
        )
        from predictionio_tpu.fleet.gateway import Gateway as _Gw
        from predictionio_tpu.fleet.gateway import GatewayConfig as _GwCfg

        Autoscaler(
            ScalingPolicy(AutoscalerConfig()),
            sup,
            _Gw(
                _GwCfg(replica_urls=("http://127.0.0.1:1",)),
                metrics=MetricsRegistry(),
            ),
            lambda cls: WorkerSpec(name="w9", port=9),
            metrics=fleet_metrics,
        )
        # the pio_lifecycle_* family rides the fleet-parent registry too
        # (or a standalone `pio lifecycle run`'s own — same template)
        from predictionio_tpu.lifecycle import register_lifecycle_metrics

        register_lifecycle_metrics(fleet_metrics)
        registered.update(fleet_metrics._metrics)
        missing = documented - registered
        assert not missing, f"documented but not registered: {sorted(missing)}"
