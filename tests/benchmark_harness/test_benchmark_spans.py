"""The readers that take the program's own spans and scopes from the traced
slice (``benchmark/readers/_slice.py``, ``scope_mean_ms.py``,
``idle_under_span.py``), and the four metrics that are data files over
readers that were there.

``benchmark_serve_slice_spans.xplane.pb.gz`` was cut on a TPU v5 lite in
PR 24 by ``benchmark/trace_reduce.Slice`` out of the saturated cell (64
callers, 5.7 M x 128), a quarter of a second of it, with the program's
``pio:`` spans and scopes in it. ``benchmark_serve_slice.xplane.pb.gz`` (PR
23) was recorded before the program wrote either: the trace a parent gives.
"""

import gzip
import json
import os
import types
from pathlib import Path

import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import _slice, idle_under_span, scope_mean_ms
from benchmark_testkit import REPO

HERE = Path(__file__).parent
WITH_SPANS = str(HERE / "benchmark_serve_slice_spans.xplane.pb.gz")
WITHOUT = str(HERE / "benchmark_serve_slice.xplane.pb.gz")
SERVE = "_serve_by_index_batch"
SERVE_SCOPES = ["gather", "score", "topk"]
GC, DISPATCH = ["pio:gc"], ["pio:dispatch"]
FINISH = ["pio:fetch.unpack", "pio:serve", "pio:loop."]
NEW_METRICS = 20


def traced_run(path: str, monkeypatch, **fields):
    """A ``Run`` whose traced slice is the recorded file."""
    monkeypatch.setattr(_slice, "load", lambda run: _slice.read(path))
    summary = trace_reduce.reduce(trace_reduce.load(path), chips=1)
    return harness.Run(0.0, 1.0, 1, 0, True, trace=summary, **fields)


def shares(run) -> list[float]:
    return [
        idle_under_span.read(run, GC, []),
        idle_under_span.read(run, DISPATCH, GC),
        idle_under_span.read(run, FINISH, GC + DISPATCH),
        idle_under_span.read(run, [], GC + DISPATCH + FINISH),
    ]


def test_the_recorded_slices_idle_shares_sum_to_its_idle_share(monkeypatch):
    run = traced_run(WITH_SPANS, monkeypatch)
    profile = _slice.read(WITH_SPANS)
    assert {name for name, _, _ in profile.spans} >= {
        "pio:dispatch", "pio:dispatch.decode", "pio:dispatch.enqueue", "pio:fetch.block",
        "pio:fetch.unpack", "pio:serve", "pio:loop.collect", "pio:loop.finish",
    }
    assert 100.0 * run.trace.idle_share == pytest.approx(21.168597, abs=1e-6)
    gc_share, dispatch, finish, unnamed = shares(run)
    assert sum(shares(run)) == pytest.approx(100.0 * run.trace.idle_share, abs=1e-6)
    # no full collection fell into this quarter second
    assert (gc_share, dispatch, finish, unnamed) == pytest.approx(
        (0.0, 2.364997, 3.490217, 15.313382), abs=1e-6
    )
    # with nothing named before it, a span's share is all the idle time under it
    assert idle_under_span.read(run, DISPATCH, []) >= dispatch


def test_the_recorded_slices_scopes_make_up_the_programs_device_time(monkeypatch):
    run = traced_run(WITH_SPANS, monkeypatch)
    row = run.trace.programs[f"jit_{SERVE}"]
    kernel_ms = 1e3 * row["seconds"] / row["count"]
    by_scope = {
        scope: scope_mean_ms.read(run, SERVE, scope, SERVE_SCOPES, 1e3) for scope in SERVE_SCOPES
    }
    assert row["count"] == 14 and kernel_ms == pytest.approx(14.092287, abs=1e-6)
    assert by_scope == pytest.approx(
        {"gather": 0.005070, "score": 5.024112, "topk": 9.063090}, abs=1e-6
    )
    assert 0.7 * kernel_ms <= by_scope["score"] + by_scope["topk"] <= kernel_ms
    assert sum(by_scope.values()) <= kernel_ms * (1 + 1e-9)
    assert by_scope["gather"] < by_scope["score"]
    # the stat that bears the op_name is tf_op, on the event's metadata
    names = {name for op in _slice.read(WITH_SPANS).ops for name in op[3]}
    assert f"jit({SERVE})/score/dot_general" in names
    assert f"jit({SERVE})/topk/top_k" in names


def test_a_slice_with_no_span_of_the_program_is_all_unnamed(monkeypatch, capsys):
    run = traced_run(WITHOUT, monkeypatch)
    assert not _slice.read(WITHOUT).spans
    assert shares(run) == [0.0, 0.0, 0.0, pytest.approx(100.0 * run.trace.idle_share, abs=1e-9)]
    # its executables carry no scope: the reader says so (once) and prints no 0
    scope_mean_ms._say_once.cache_clear()
    assert scope_mean_ms.read(run, SERVE, "score", SERVE_SCOPES, 1e3) is None
    assert "loaded from a cache written without scopes" in capsys.readouterr().err
    names = {name for op in _slice.read(WITHOUT).ops for name in op[3]}
    assert f"jit({SERVE})/dot_general" in names and f"jit({SERVE})/top_k" in names


def hand_made(monkeypatch, ops, spans=(), programs=None, end=1000.0):
    profile = _slice.SliceProfile(0.0, end, list(spans), list(ops))
    monkeypatch.setattr(_slice, "load", lambda run: profile)
    return types.SimpleNamespace(trace=types.SimpleNamespace(programs=programs or {}))


def test_precedence_is_gc_then_dispatch_then_finish(monkeypatch):
    # the device works in [0, 100) and [900, 1000): idle for 800 of 1000
    ops = [(0.0, 100.0, "%a = f32[] add()", frozenset()), (900.0, 1000.0, "%a = f32[] add()", frozenset())]
    spans = [
        ("pio:gc", 150.0, 250.0),  # under the dispatch span: the collection's
        ("pio:dispatch", 100.0, 400.0),
        ("pio:dispatch.enqueue", 300.0, 450.0),  # a prefix takes its sub-spans
        ("pio:serve", 350.0, 600.0),  # 450..600 is left to it
        ("pio:loop.finish", 550.0, 700.0),
        ("pio:fetch.block", 700.0, 800.0),  # the host waiting: no one's
        ("pio:loop.collect", 950.0, 2000.0),  # the device is busy: nothing, and clipped
    ]
    run = hand_made(monkeypatch, ops, spans)
    assert shares(run) == pytest.approx([10.0, 25.0, 25.0, 20.0])
    assert idle_under_span.read(run, [], []) == pytest.approx(80.0)


def test_a_scope_absent_from_a_trace_that_shows_the_others_reads_nothing(monkeypatch, capsys):
    ops = [
        (0.0, 400.0, "%dot = f32[8,64] fusion()", frozenset({"jit(_f)/score/dot_general"})),
        (400.0, 500.0, "%w = () while(())", frozenset({"jit(_f)/score/while"})),  # a loop's own
        (400.0, 500.0, "%m = f32[] multiply()", frozenset({"jit(_f)/score/while/body/mul"})),
        (500.0, 600.0, "%c = f32[] copy()", frozenset()),  # the compiler's: no scope
        (600.0, 700.0, "%g = f32[] gather()", frozenset({"jit(_other)/topk/gather"})),
        (900.0, 1200.0, "%d2 = f32[] fusion()", frozenset({"jit(_f)/score/dot_general"})),  # clipped
    ]
    run = hand_made(monkeypatch, ops, programs={"jit__f": {"count": 2, "seconds": 1e-6}})
    assert scope_mean_ms.read(run, "_f", "score", ["score", "topk"], 1e3) == pytest.approx(
        1e3 * (400 + 100 + 100) * 1e-9 / 2
    )
    assert scope_mean_ms.read(run, "_f", "topk", ["score", "topk"], 1e3) is None
    assert capsys.readouterr().err == ""
    # a program that did not run in the slice
    assert scope_mean_ms.read(run, "_other", "topk", ["score", "topk"]) is None


def test_an_untraced_run_and_a_lost_trace_read_nothing(monkeypatch, tmp_path):
    untraced = harness.Run(0.0, 1.0, 1, 0, True)
    assert _slice.load(untraced) is None
    assert idle_under_span.read(untraced, [], []) is None
    assert scope_mean_ms.read(untraced, SERVE, "score", SERVE_SCOPES) is None
    monkeypatch.setattr(_slice.tempfile, "tempdir", str(tmp_path))
    traced = harness.Run(0.0, 1.0, 1, 0, True, trace=object())
    assert _slice.load(traced) is None  # no benchmark-run-* directory stands
    # the newest run directory's profile is this run's
    for age, name in enumerate(("benchmark-run-old", "benchmark-run-new")):
        where = tmp_path / name / "trace" / "plugins" / "profile" / "2026_09_27"
        where.mkdir(parents=True)
        source = WITHOUT if name.endswith("old") else WITH_SPANS
        (where / "host.xplane.pb").write_bytes(gzip.open(source, "rb").read())
        os.utime(tmp_path / name / "trace", (1000 + age, 1000 + age))
    assert _slice.load(traced).spans


COUNTERS = {
    "pio_batch_slot_wait_seconds_total{}": (1.0, 1.5),
    "batcher.batches_dispatched": (100.0, 200.0),
    'pio_serve_rows_total{kind="real"}': (10.0, 310.0),
    'pio_serve_rows_total{kind="bucket"}': (16.0, 416.0),
    'pio_gc_pause_seconds_total{generation="0"}': (0.5, 0.75),
    'pio_gc_pause_seconds_total{generation="2"}': (1.0, 2.0),
    "pio_compile_cache_misses_total{}": (7.0, 7.0),
    "pio_compile_cache_hits_total{}": (7.0, 9.0),
}


@pytest.mark.parametrize(
    "name, value",
    [
        ("slot_wait_ms", 5.0),
        ("slot_wait_ms.sat", 5.0),
        ("bucket_fill_share", 75.0),
        ("bucket_fill_share.sat", 75.0),
        ("gc_pause_s_in_window", 1.25),
        ("gc_pause_s_in_window.sat", 1.25),
        ("compile_cache_misses_in_window", 0.0),
        ("compile_cache_misses_in_window.sat", 0.0),
    ],
)
def test_the_data_only_metrics_read_hand_made_counters(name, value):
    run = harness.Run(
        0.0, 51.0, 1, 0, True,
        counters_start={k: v[0] for k, v in COUNTERS.items()},
        counters_end={k: v[1] for k, v in COUNTERS.items()},
    )
    assert harness.read_metric(REPO, True, name, run) == pytest.approx(value)
    # a program without the counter (the parent): the metric is left out
    assert harness.read_metric(REPO, True, name, harness.Run(0.0, 51.0, 1, 0, True)) is None


def test_the_new_metrics_files_and_readers_are_found_and_each_reads(monkeypatch):
    """Driven by data: every metric this PR added is an entry of
    BENCHMARK.json, a file under layer_metrics/ naming a reader that exists,
    and reads a number from a hand-made run."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = bench["per_layer"][-NEW_METRICS:]
    new_readers = {"scope_mean_ms", "idle_under_span"}
    specs = {
        e["name"]: json.loads((REPO / "benchmark" / "layer_metrics" / f"{e['name']}.json").read_text())
        for e in entries
    }
    assert len(specs) == NEW_METRICS
    assert {s["reader"] for s in specs.values()} == new_readers | {"counter_ratio", "counter_delta"}
    for reader in new_readers:
        assert (REPO / "benchmark" / "readers" / f"{reader}.py").is_file()

    def op(start, end, program, scope):
        return (start, end, f"%f = f32[] fusion() {program}{scope}", frozenset({f"jit({program})/{scope}/add"}))

    ops = [op(0.0, 100.0, SERVE, s) for s in SERVE_SCOPES]
    ops += [op(200.0, 300.0, "_als_step", s) for s in ("gather", "gram", "solve")]
    spans = [
        ("pio:gc", 100.0, 120.0), ("pio:dispatch", 120.0, 150.0), ("pio:serve", 150.0, 180.0),
        ("pio:als.pack", 300.0, 700.0),
    ]
    profile = _slice.SliceProfile(0.0, 1000.0, spans, ops)
    monkeypatch.setattr(_slice, "load", lambda run: profile)
    programs = {f"jit_{SERVE}": {"count": 2, "seconds": 1.0}, "jit__als_step": {"count": 10, "seconds": 1.0}}
    run = harness.Run(
        0.0, 51.0, 1, 0, True,
        counters_start={k: v[0] for k, v in COUNTERS.items()},
        counters_end={k: v[1] for k, v in COUNTERS.items()},
        trace=types.SimpleNamespace(programs=programs),
    )
    values = {name: harness.read_metric(REPO, True, name, run) for name in specs}
    assert all(v is not None for v in values.values()), values
    assert values["serve_score_ms"] == values["serve_topk_ms.sat"] == pytest.approx(1e3 * 100e-9 / 2)
    assert values["solve_s_per_iter"] == pytest.approx(100e-9 / 10)
    assert values["idle_pack_share.train"] == pytest.approx(40.0)
    sat = [values[f"idle_{k}_share.sat"] for k in ("gc", "dispatch", "finish", "unnamed")]
    assert sat == pytest.approx([2.0, 3.0, 3.0, 72.0])
    # and each reports where the metric it moves is reported
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for e in entries:
        assert set(e["workloads"]) <= set(e2e[e["moves"]]["workloads"]), e["name"]
