"""The program's spans on the profiler's clock (docs/observability.md,
"Spans on the profiler's clock"): every ``pio:`` annotation is emitted where
the tables say, none wraps an ``await``, the counters that stand beside them
are on ``/metrics`` from the server's start and grow as said, and the device
scopes are in the compiled programs' ``op_name``s.

The annotations go through ONE helper (``obs/jaxprof.annotate``); its
``TraceAnnotation`` is replaced by a recorder here, so no profiler session is
needed.
"""

from __future__ import annotations

import ast
import asyncio
import gc
import socket
import threading
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu.obs import jaxprof

REPO = Path(__file__).resolve().parents[1]
MEMORY_STORAGE = {
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
}
SERVING_SPANS = [
    "pio:loop.collect",
    "pio:dispatch",
    "pio:dispatch.decode",
    "pio:dispatch.enqueue",
    "pio:fetch.block",
    "pio:fetch.unpack",
    "pio:serve",
    "pio:loop.finish",
]
REMOVED = ("pio_queue_wait_seconds", "pio_dispatch_seconds", "pio_fetch_seconds")


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    log: list = []  # (name, stats, thread name), in order of entry

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    def __enter__(self):
        Recorder.log.append((self.name, self.stats, threading.current_thread().name))
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(jaxprof, "_trace_annotation", lambda: Recorder)
    Recorder.log = []
    return Recorder.log


def make_server(**config):
    """An in-process ``QueryServer`` over the recommendation template with a
    tiny seeded model, as ``pio deploy`` would build it."""
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.models.recommendation import engine_factory
    from predictionio_tpu.models.recommendation.engine import ALSModel
    from predictionio_tpu.workflow.create_server import QueryServer, ServerConfig
    from predictionio_tpu.workflow.engine_loader import EngineManifest

    rng = np.random.default_rng(0)
    model = ALSModel(
        rng.normal(size=(40, 8)).astype(np.float32),
        rng.normal(size=(30, 8)).astype(np.float32),
        [f"u{i}" for i in range(40)],
        [f"i{i}" for i in range(30)],
    )
    engine = engine_factory()
    params = engine.engine_params_from_variant(
        {
            "datasource": {"params": {"appName": "spans"}},
            "algorithms": [{"name": "als", "params": {"rank": 8, "numIterations": 1}}],
        }
    )
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    config.setdefault("max_batch_size", 8)
    return QueryServer(
        engine=engine,
        engine_params=params,
        models=[model],
        manifest=EngineManifest(
            engine_id="spans",
            version="1",
            variant="engine.json",
            engine_factory="predictionio_tpu.models.recommendation.engine_factory",
        ),
        instance_id="spans",
        storage=Storage(env=MEMORY_STORAGE),
        config=ServerConfig(ip="127.0.0.1", port=port, **config),
    )


def payload(user: int) -> dict:
    return {"user": f"u{user}", "num": 3}


def metrics_of(server) -> dict[str, float]:
    """``/metrics`` as ``{name{labels}: value}``."""
    out = {}
    for line in server.metrics.render_prometheus().splitlines():
        if line and not line.startswith("#"):
            key, value = line.rsplit(" ", 1)
            out[key] = float(value)
    return out


async def until(condition, timeout=5.0):
    """Poll ``condition`` on the running loop until it holds."""
    end = asyncio.get_running_loop().time() + timeout
    while not condition():
        assert asyncio.get_running_loop().time() < end, "the condition never held"
        await asyncio.sleep(0.002)


async def submit_all(server, users):
    """Submit the users' queries in one go: they queue before the collect
    loop wakes, so they form ONE batch of ``len(users)``."""
    return await asyncio.gather(*(server._batcher.submit(payload(u)) for u in users))


@pytest.fixture(scope="module")
def served_batches():
    """The annotations of two batches through a live batcher: three queries
    at once (the pipelined path), then one alone (the idle fast path)."""
    real, jaxprof._trace_annotation = jaxprof._trace_annotation, lambda: Recorder
    Recorder.log = []
    server = make_server()

    async def body():
        first = await submit_all(server, [1, 2, 3])
        second = await submit_all(server, [4])
        await asyncio.sleep(0.05)  # the last _finish closes its span
        server._batcher.close()
        await server._batcher.wait_closed()
        return first + second

    try:
        answers = asyncio.run(body())
    finally:
        jaxprof._trace_annotation = real
    assert all(len(a["itemScores"]) == 3 for a in answers)
    return list(Recorder.log)


@pytest.mark.parametrize("name", SERVING_SPANS)
def test_every_serving_span_is_emitted_once_a_batch(served_batches, name):
    stats = [s for n, s, _ in served_batches if n == name]
    assert len(stats) == 2, [n for n, _, _ in served_batches]
    if name in ("pio:dispatch", "pio:loop.collect", "pio:loop.finish"):
        assert [s["batch"] for s in stats] == [1, 2]
    if name == "pio:dispatch":
        assert [s["n"] for s in stats] == [3, 1]


def test_spans_run_on_the_threads_the_table_names(served_batches):
    threads = {}
    for name, stats, thread in served_batches:
        threads.setdefault(name, []).append(thread)
    loop_thread = threading.current_thread().name
    assert set(threads["pio:loop.collect"] + threads["pio:loop.finish"]) == {loop_thread}
    for name in ("pio:dispatch", "pio:dispatch.decode", "pio:dispatch.enqueue"):
        assert all(t.startswith("pio-dispatch") for t in threads[name]), name
    # the batch of three is fetched and served on a fetch thread; the query
    # that came alone takes the idle fast path, all of it on the dispatch thread
    for name in ("pio:fetch.block", "pio:fetch.unpack", "pio:serve"):
        first, second = threads[name]
        assert first.startswith("pio-fetch") and second.startswith("pio-dispatch"), name
    others = {n for n, _, _ in served_batches} - set(SERVING_SPANS) - {"pio:gc"}
    assert not others


@pytest.mark.parametrize("instrumented", [False, True], ids=["plain", "timings"])
def test_every_training_span_is_emitted_once_a_train(recorded, instrumented):
    from predictionio_tpu.ops.als import ALSConfig, als_train

    rng = np.random.default_rng(1)
    users, items = rng.integers(0, 30, 400), rng.integers(0, 20, 400)
    timings = {} if instrumented else None
    als_train(
        users, items, rng.uniform(1, 5, 400).astype(np.float32), 30, 20,
        ALSConfig(rank=4, iterations=3), timings=timings,
    )
    names = [n for n, _, _ in recorded if n.startswith("pio:als.")]
    assert names == [
        "pio:als.pack", "pio:als.upload", "pio:als.build",
        "pio:als.sweep", "pio:als.sweep", "pio:als.sweep", "pio:als.fetch",
    ]
    assert [s["iteration"] for n, s, _ in recorded if n == "pio:als.sweep"] == [0, 1, 2]
    assert (timings is None) or timings["device_s"] > 0


def test_no_annotation_wraps_an_await():
    """An annotation wraps synchronous code on one thread: the event loop
    interleaves coroutines on one thread and would break the nesting."""
    tree = ast.parse((REPO / "predictionio_tpu/workflow/create_server.py").read_text())
    spans = 0
    for node in ast.walk(tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        calls = [i.context_expr for i in node.items if isinstance(i.context_expr, ast.Call)]
        if not any(getattr(c.func, "id", None) == "annotate" for c in calls):
            continue
        spans += 1
        waits = [
            n for stmt in node.body for n in ast.walk(stmt)
            if isinstance(n, (ast.Await, ast.AsyncFor, ast.AsyncWith))
        ]
        assert not waits, f"line {node.lineno}: an await inside `with annotate(...)`"
    assert spans >= 6


def test_trace_annotation_is_named_in_the_helper_alone():
    held = [
        str(path.relative_to(REPO))
        for path in (REPO / "predictionio_tpu").rglob("*.py")
        if "TraceAnnotation" in path.read_text()
    ]
    assert held == ["predictionio_tpu/obs/jaxprof.py"]


def test_counter_families_scrape_as_zero_from_the_start_and_histograms_are_gone():
    server = make_server()
    text = server.metrics.render_prometheus()
    scraped = metrics_of(server)
    assert scraped["pio_batch_slot_wait_seconds_total"] == 0.0
    assert scraped["pio_batch_joined_in_slot_wait_total"] == 0.0
    for generation in "012":
        assert f'pio_gc_pause_seconds_total{{generation="{generation}"}}' in scraped
        assert f'pio_gc_collections_total{{generation="{generation}"}}' in scraped
    assert 'pio_serve_rows_total{kind="real"}' in scraped
    assert 'pio_serve_rows_total{kind="bucket"}' in scraped
    assert "pio_compile_cache_hits_total" in scraped
    assert "pio_compile_cache_misses_total" in scraped
    for name in REMOVED:
        assert name not in text


def test_slot_wait_grows_while_both_slots_are_taken_and_arrivals_join_the_batch(monkeypatch):
    from predictionio_tpu.ops import topk

    server = make_server()
    batcher = server._batcher
    device = threading.Event()  # set: the device answers every batch
    real_fetch = topk.fetch_topk

    def gated_fetch(handle):
        device.wait(10)
        return real_fetch(handle)

    monkeypatch.setattr(topk, "fetch_topk", gated_fetch)

    async def body():
        first = asyncio.ensure_future(submit_all(server, [1, 2]))
        await until(lambda: batcher.batches_dispatched == 1)
        second = asyncio.ensure_future(submit_all(server, [3]))
        await until(lambda: batcher.batches_dispatched == 2)  # both slots taken
        third = asyncio.ensure_future(submit_all(server, [4]))
        await until(lambda: batcher.queue_depth == 1)  # pending, not collected
        await asyncio.sleep(0.05)
        fourth = asyncio.ensure_future(submit_all(server, [5]))  # joins the open batch
        await until(lambda: batcher.queue_depth == 2)
        device.set()
        answers = await asyncio.gather(first, second, third, fourth)
        batcher.close()
        await batcher.wait_closed()
        return answers

    answers = asyncio.run(body())
    assert all(len(a["itemScores"]) == 3 for group in answers for a in group)
    scraped = metrics_of(server)
    assert 0.04 < scraped["pio_batch_slot_wait_seconds_total"] < 5.0
    assert scraped["pio_batch_joined_in_slot_wait_total"] == 1.0
    assert (batcher.batches_dispatched, batcher.queries_dispatched) == (3, 5)


def test_bucket_rows_count_queries_against_what_the_device_scored():
    from predictionio_tpu.ops import topk

    server = make_server()
    before = metrics_of(server)
    assert [topk.batch_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]

    async def body():
        await submit_all(server, [1, 2, 3])
        server._batcher.close()
        await server._batcher.wait_closed()

    asyncio.run(body())
    after = metrics_of(server)

    def grown(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    assert grown('pio_serve_rows_total{kind="real"}') == 1 + 2 + 3 + 5 + 8 + 9 + 3
    assert grown('pio_serve_rows_total{kind="bucket"}') == 1 + 2 + 4 + 8 + 8 + 16 + 4
    assert grown('pio_serve_batches_total{bucket="4"}') == 2
    buckets = [
        int(key.split('"')[1]) for key in after if key.startswith("pio_serve_batches_total{")
    ]
    assert buckets and all(b & (b - 1) == 0 for b in buckets)


@pytest.mark.parametrize("item_bytes", [4, 2], ids=["float32", "bfloat16"])
def test_the_resident_tables_bytes_are_scraped_at_the_width_they_are_stored_in(
    monkeypatch, item_bytes
):
    import jax.numpy as jnp

    from predictionio_tpu.ops import topk

    if item_bytes == 2:  # the chip's answer, forced: off the chip it is float32
        monkeypatch.setattr(topk, "item_table_dtype", lambda: jnp.bfloat16)
    server = make_server()

    async def body():
        await submit_all(server, [1])  # the first query builds the index
        server._batcher.close()
        await server._batcher.wait_closed()

    asyncio.run(body())
    scraped = metrics_of(server)
    assert scraped['pio_serve_table_bytes{table="item"}'] == 30 * 8 * item_bytes
    assert scraped['pio_serve_table_bytes{table="user"}'] == 40 * 8 * 4


def test_gc_hook_counts_full_collections_and_is_gone_after_stop(recorded):
    server = make_server()

    async def body():
        await server.start()
        try:
            assert server.gc_watcher._on_gc in gc.callbacks
            before = metrics_of(server)
            gc.collect()
            after = metrics_of(server)
        finally:
            await server.stop()
        return before, after

    before, after = asyncio.run(body())
    assert server.gc_watcher._on_gc not in gc.callbacks
    key = 'pio_gc_collections_total{generation="2"}'
    assert after[key] >= before[key] + 1
    pause = 'pio_gc_pause_seconds_total{generation="2"}'
    assert after[pause] > before[pause]
    # a full collection is a span too; the young ones are only counted
    assert [s for n, s, _ in recorded if n == "pio:gc"][:1] == [{"generation": 2}]
    assert not [s for n, s, _ in recorded if n == "pio:gc" and s["generation"] != 2]


def test_compile_cache_listener_counts_the_caches_own_events():
    import jax.monitoring

    server = make_server()
    before = metrics_of(server)
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/something_else")
    after = metrics_of(server)
    assert after["pio_compile_cache_misses_total"] - before["pio_compile_cache_misses_total"] == 1
    assert after["pio_compile_cache_hits_total"] - before["pio_compile_cache_hits_total"] == 2


def _compiled_text(program: str) -> str:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als, topk

    S = jax.ShapeDtypeStruct
    table, mask = S((30, 8), jnp.float32), S((4, 30), jnp.bool_)
    if program == "_serve_by_index_batch":
        lowered = topk._serve_by_index_batch.lower(
            S((4,), jnp.int32), S((40, 8), jnp.float32), table, S((30,), jnp.bool_), k=4,
        )
    elif program == "_dot_top_k":
        lowered = topk._dot_top_k.lower(
            table, S((4, 8), jnp.float32), mask, S((30,), jnp.float32), k=4
        )
    elif program == "_gather_sum_top_k":
        lowered = topk._gather_sum_top_k.lower(
            table, S((4, 2), jnp.int32), S((4, 2), jnp.float32), mask, None, k=4
        )
    elif program == "_als_step":
        tables = [S((16,), jnp.int32), S((16, 8), jnp.int32), S((16, 8), jnp.float32), S((16, 8), jnp.int8)]
        lowered = als._als_step.lower(
            S((41, 4), jnp.float32), S((31, 4), jnp.float32), *tables, *tables,
            n_users=40, n_items=30, reg=0.05, implicit=False, alpha=1.0, block_chunk=8,
        )
    else:
        lowered = als._device_pack.lower(
            S((64,), jnp.int32), S((64,), jnp.int32), S((64,), jnp.float32),
            S((40,), jnp.int32), S((30,), jnp.int32),
            d=8, nb_u=48, nb_i=40, n_users=40, n_items=30,
        )
    return lowered.compile().as_text()


@pytest.mark.parametrize(
    "program, scopes",
    [
        ("_serve_by_index_batch", ["gather", "score", "topk"]),
        ("_dot_top_k", ["score", "topk"]),
        ("_gather_sum_top_k", ["gather", "score", "topk"]),
        ("_als_step", ["gather", "gram", "solve", "solve/while/body/closed_call/matvec"]),
        ("_device_pack", ["pack"]),
    ],
)
def test_compiled_programs_carry_every_scope_in_their_op_names(program, scopes):
    import re

    names = set(re.findall(r'op_name="([^"]*)"', _compiled_text(program)))
    for scope in scopes:
        under = [n for n in names if n.startswith(f"jit({program})/") and f"/{scope}/" in n]
        assert under, (program, scope, sorted(names)[:20])
