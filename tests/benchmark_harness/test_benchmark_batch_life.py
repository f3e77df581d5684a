"""A batch's life in the benchmark (PR 39): the two readers over what the
trace already records (``benchmark/readers/launch_lag.py`` pairs a launch's
``DoEnqueueProgram`` with its execution on the device by ``run_id``;
``span_mean_ms.py`` takes the mean of the program's own spans by name) and
the fourteen metrics that are data files over ``counter_ratio`` and the
micro-batcher's new counters.

``benchmark_serve_slice_spans.xplane.pb.gz`` (TPU v5 lite, PR 24) holds 28
enqueues and 28 executions with a ``run_id``, all inside the slice and all
launched under a ``pio:dispatch`` span.
"""

import json
import types
from pathlib import Path

import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import _slice, launch_lag, span_mean_ms
from benchmark_testkit import REPO

HERE = Path(__file__).parent
WITH_SPANS = str(HERE / "benchmark_serve_slice_spans.xplane.pb.gz")
WITHOUT = str(HERE / "benchmark_serve_slice.xplane.pb.gz")
DISPATCH = ["pio:dispatch"]
PARENTS_LAST = "seq_programs_per_batch"
STEADY = ["rec-als-webgraph-de.serve-steady"]
SAT = [
    "rec-als-webgraph-de.serve-sat", "seq-olmoe.serve-sat", "seq-kimi-linear.serve-sat",
    "seq-sdar-moe.serve-sat",
]
TWINS = [
    "loop_idle_share", "loop_dispatch_share", "closed_after_idle_share",
    "closed_after_dispatch_share", "queries_after_idle_share", "inflight_at_close",
    "answer_gap_weighted_ms", "launch_lag_ms", "launch_queue_programs",
]
NEW = {
    **{name: STEADY for name in TWINS},
    **{f"{name}.sat": SAT for name in TWINS},
    "seq_launch_host_ms": SAT[1:],
    "seq_denoise_launch_host_ms": SAT[3:],
}


def traced_run(path: str, monkeypatch):
    """A ``Run`` whose traced slice is the recorded file."""
    monkeypatch.setattr(launch_lag, "slice_path", lambda run: path)
    monkeypatch.setattr(_slice, "load", lambda run: _slice.read(path))
    return harness.Run(0.0, 1.0, 1, 0, True, trace=object())


def test_every_execution_of_the_recorded_slice_pairs_with_its_enqueue(monkeypatch):
    run = traced_run(WITH_SPANS, monkeypatch)
    profile = _slice.read(WITH_SPANS)
    enqueues, executions = launch_lag.run_ids(WITH_SPANS)
    assert len(enqueues) == len(executions) == 28 and set(enqueues) == set(executions)
    assert all(profile.start_ns <= start < profile.end_ns for start, _ in executions.values())
    kept = launch_lag.pairs(profile, enqueues, executions, DISPATCH)
    assert len(kept) == 28
    length_ns = profile.end_ns - profile.start_ns
    lags_ns = sorted(device - enqueued for enqueued, device in kept)
    # a launch that meets an idle device reads -0.42 to -0.48 ms here: the
    # device's clock runs that far behind the host's in this trace, so a lag
    # is good to half a millisecond (and is not cut at 0, which would hide it)
    assert -0.5e6 < lags_ns[0] <= lags_ns[9] < -0.4e6 and 1e6 < lags_ns[10] <= lags_ns[-1] < length_ns
    lag_ms = launch_lag.read(run, "lag_ms", DISPATCH)
    assert lag_ms == pytest.approx(8.934723, abs=1e-6)
    assert lag_ms == pytest.approx(1e-6 * sum(d - e for e, d in kept) / 28)
    queued = launch_lag.read(run, "queued", DISPATCH)
    assert queued == pytest.approx(1.357143, abs=1e-6) and 0 <= queued < 28
    # the same launches by the sub-span they lie in; none under a span nobody wrote
    assert launch_lag.read(run, "lag_ms", ["pio:dispatch.enqueue"]) == pytest.approx(lag_ms)
    assert launch_lag.read(run, "lag_ms", ["pio:none"]) is None
    assert launch_lag.read(run, "queued", ["pio:none"]) is None
    with pytest.raises(ValueError, match="no measure"):
        launch_lag.read(run, "depth", DISPATCH)


def test_the_means_of_the_recorded_slices_spans(monkeypatch):
    run = traced_run(WITH_SPANS, monkeypatch)
    spans = [s for s in _slice.read(WITH_SPANS).spans if s[0] == "pio:dispatch"]
    inside = [b - a for _, a, b in spans if a >= _slice.read(WITH_SPANS).start_ns]
    assert len(spans) == 14 and len(inside) == 13  # the first began before the slice
    assert span_mean_ms.read(run, DISPATCH) == pytest.approx(1e-6 * sum(inside) / 13)
    # exact names: a prefix takes no sub-span, and nothing is no number
    assert span_mean_ms.read(run, ["pio:dispatch."]) is None
    assert span_mean_ms.read(run, ["pio:seq.launch"]) is None
    both = span_mean_ms.read(run, ["pio:fetch.block", "pio:fetch.unpack"])
    assert span_mean_ms.read(run, ["pio:fetch.unpack"]) < both < span_mean_ms.read(run, ["pio:fetch.block"])


def test_a_slice_with_no_span_of_the_program_reads_nothing(monkeypatch):
    run = traced_run(WITHOUT, monkeypatch)
    assert span_mean_ms.read(run, DISPATCH) is None
    # its launches pair, and none lies under a span of the program's
    enqueues, executions = launch_lag.run_ids(WITHOUT)
    assert enqueues and set(enqueues) & set(executions)
    assert launch_lag.read(run, "lag_ms", DISPATCH) is None
    assert launch_lag.read(run, "queued", DISPATCH) is None


def test_an_untraced_run_and_a_lost_trace_read_nothing(monkeypatch, tmp_path):
    untraced = harness.Run(0.0, 1.0, 1, 0, True)
    assert launch_lag.slice_path(untraced) is None
    assert launch_lag.read(untraced, "lag_ms", DISPATCH) is None
    assert span_mean_ms.read(untraced, DISPATCH) is None
    monkeypatch.setattr(launch_lag.tempfile, "tempdir", str(tmp_path))
    traced = harness.Run(0.0, 1.0, 1, 0, True, trace=object())
    assert launch_lag.slice_path(traced) is None  # no benchmark-run-* directory stands
    (tmp_path / "benchmark-run-x" / "trace").mkdir(parents=True)
    assert launch_lag.slice_path(traced) is None  # and none with a profile in it
    assert launch_lag.read(traced, "queued", DISPATCH) is None
    # where one stands, it is the file `_slice.load` reads
    where = tmp_path / "benchmark-run-x" / "trace" / "plugins" / "profile" / "2026_09_30"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(b"")
    assert launch_lag.slice_path(traced) == trace_reduce.find_xplane(str(tmp_path / "benchmark-run-x" / "trace"))


def hand_made(monkeypatch, enqueues, executions, spans=(("pio:dispatch", 0.0, 1000.0),)):
    profile = _slice.SliceProfile(100.0, 1000.0, list(spans), [])
    monkeypatch.setattr(launch_lag, "slice_path", lambda run: "hand-made")
    monkeypatch.setattr(_slice, "read", lambda path: profile)
    monkeypatch.setattr(launch_lag, "run_ids", lambda path: (enqueues, executions))
    return types.SimpleNamespace(trace=object())


def test_lag_and_depth_of_hand_made_launches(monkeypatch):
    enqueues = {1: 50.0, 2: 120.0, 3: 130.0, 4: 600.0, 5: 990.0, 6: 995.0}
    executions = {
        0: (60.0, 90.0),  # enqueued before the trace began: no pair, and out of the queue at 90
        1: (90.0, 200.0),  # began before the slice: no pair of the slice's, but it stands in the queue
        2: (200.0, 500.0),
        3: (500.0, 550.0),
        4: (700.0, 800.0),  # enqueued outside every span named
        5: (1000.0, 1100.0),  # begins at the slice's end: the next slice's
    }
    spans = [("pio:dispatch", 100.0, 300.0), ("pio:dispatch.enqueue", 110.0, 140.0), ("pio:serve", 550.0, 650.0)]
    run = hand_made(monkeypatch, enqueues, executions, spans)
    # launches 2 and 3 lie under pio:dispatch: lags 80 and 370 ns
    assert launch_lag.read(run, "lag_ms", DISPATCH) == pytest.approx(1e-6 * (80.0 + 370.0) / 2)
    # launch 2 meets execution 1 unfinished; launch 3 meets 1 and 2
    assert launch_lag.read(run, "queued", DISPATCH) == pytest.approx((1 + 2) / 2)
    # launch 4, under another span, meets an empty queue
    assert launch_lag.read(run, "lag_ms", ["pio:serve"]) == pytest.approx(1e-6 * 100.0)
    assert launch_lag.read(run, "queued", ["pio:serve"]) == 0.0
    assert launch_lag.read(run, "queued", ["pio:dispatch", "pio:serve"]) == pytest.approx(1.0)


def test_a_trace_whose_launches_do_not_pair_says_so_and_reads_nothing(monkeypatch, capsys):
    executions = {k: (200.0 + k, 210.0 + k) for k in range(10)}
    launch_lag._say_once.cache_clear()
    some = hand_made(monkeypatch, {k: 150.0 for k in range(4)}, executions)
    assert launch_lag.read(some, "lag_ms", DISPATCH) is None  # 4 of 10: under half
    assert "4 of the slice's 10 executions pair" in capsys.readouterr().err
    none = hand_made(monkeypatch, {}, executions)  # no run_id on the host's side
    assert launch_lag.read(none, "queued", DISPATCH) is None
    assert "0 of the slice's 10 executions pair" in capsys.readouterr().err
    # half pair: a number, and the line that says how few it stands on
    half = hand_made(monkeypatch, {k: 150.0 for k in range(5)}, executions)
    assert launch_lag.read(half, "lag_ms", DISPATCH) == pytest.approx(1e-6 * 52.0)
    err = capsys.readouterr().err
    assert "5 of the slice's 10 executions pair" in err and "no launch lag" not in err
    # all pair: silence
    every = hand_made(monkeypatch, {k: 150.0 for k in range(10)}, executions)
    assert launch_lag.read(every, "lag_ms", DISPATCH) == pytest.approx(1e-6 * 54.5)
    assert capsys.readouterr().err == ""


@pytest.mark.parametrize(
    "waiting, said",
    [(0, None), (1, None), (3, "17 of the slice's 20 executions pair"), (12, "8 of the slice's 20")],
    ids=["none-at-the-front", "one-in-twenty", "three-in-twenty", "more-than-half"],
)
def test_what_was_enqueued_before_the_trace_stands_at_the_front_and_is_left_out(
    monkeypatch, capsys, waiting, said
):
    """A saturated slice begins with the executions that stood in the queue
    when the trace began: they have no enqueue in it. The share that pairs is
    taken from the first execution that has one, so a deep queue at the start
    costs no reading; the line on stderr says how many were left out."""
    launch_lag._say_once.cache_clear()
    executions = {k: (200.0 + 10 * k, 210.0 + 10 * k) for k in range(20)}
    enqueues = {k: 150.0 + k for k in range(waiting, 20)}
    run = hand_made(monkeypatch, enqueues, executions)
    lags = [200.0 + 10 * k - (150.0 + k) for k in range(waiting, 20)]
    assert launch_lag.read(run, "lag_ms", DISPATCH) == pytest.approx(1e-6 * sum(lags) / len(lags))
    # launch k meets the k executions ahead of it, the front's among them,
    # less those that have ended: none has before the last enqueue (169 ns)
    depth = launch_lag.read(run, "queued", DISPATCH)
    assert depth == pytest.approx(sum(range(waiting, 20)) / (20 - waiting))
    err = capsys.readouterr().err
    if said is None:
        assert err == ""
    else:
        assert said in err and f"the first {waiting} were enqueued before the trace began" in err


@pytest.mark.parametrize("strangers", [0, 7, 700], ids=["alone", "a-few", "hundreds"])
def test_the_depth_is_the_first_chips_whatever_else_the_host_enqueues(monkeypatch, strangers):
    """Enqueues whose execution the first chip never shows (another chip's,
    a trace cut short) add nothing to the depth, but for those behind the
    newest execution seen, which wait when the trace ends."""
    executions = {k: (200.0 + 100 * k, 290.0 + 100 * k) for k in range(8)}
    enqueues = {k: 150.0 + 100 * k for k in range(8)}
    # another chip's launches, all through the slice, before the newest seen
    enqueues.update({1000 + k: 150.0 + 700.0 * k / max(strangers, 1) for k in range(strangers)})
    run = hand_made(monkeypatch, enqueues, executions)
    # launch k is enqueued at 150 + 100k; launch k - 1 runs from 100 + 100k to 190 + 100k
    assert launch_lag.read(run, "queued", DISPATCH) == pytest.approx(7 / 8)
    # two more behind the last execution seen: still waiting, and no launch kept meets them
    enqueues.update({2000: 980.0, 2001: 990.0})
    joined, left = launch_lag.queue(enqueues, executions)
    assert len(joined) == 10 and len(left) == 8 and joined[-2:] == [980.0, 990.0]
    assert launch_lag.read(run, "queued", DISPATCH) == pytest.approx(7 / 8)


LOOP = 'pio_batch_loop_seconds_total{state="%s"}'
CLOSED = 'pio_batch_closed_total{after="%s"}'
QUERIES = 'pio_batch_closed_queries_total{after="%s"}'
COUNTERS = {
    LOOP % "idle": (1.0, 11.0),
    LOOP % "collect": (0.5, 1.5),
    LOOP % "dispatch": (2.0, 22.0),
    "pio_batch_slot_wait_seconds_total{}": (3.0, 22.0),
    CLOSED % "slot": (10.0, 70.0),
    CLOSED % "idle": (5.0, 35.0),
    CLOSED % "dispatch": (0.0, 10.0),
    QUERIES % "slot": (100.0, 1900.0),
    QUERIES % "idle": (5.0, 155.0),
    QUERIES % "dispatch": (0.0, 50.0),
    "pio_batch_inflight_at_close_total{}": (400.0, 4600.0),
    "pio_batch_answer_gap_seconds_total{}": (1.0, 51.0),
    "pio_batch_answer_gap_squared_seconds_total{}": (0.5, 10.5),
    "batcher.batches_dispatched": (15.0, 115.0),
}
VALUES = {
    "loop_idle_share": 20.0,
    "loop_dispatch_share": 40.0,
    "closed_after_idle_share": 30.0,
    "closed_after_dispatch_share": 10.0,
    "queries_after_idle_share": 7.5,
    "inflight_at_close": 42.0,
    "answer_gap_weighted_ms": 200.0,
}


def counted(counters) -> harness.Run:
    return harness.Run(
        0.0, 51.0, 1, 0, True,
        counters_start={k: v[0] for k, v in counters.items()},
        counters_end={k: v[1] for k, v in counters.items()},
    )


@pytest.mark.parametrize("name", [f"{base}{twin}" for base in VALUES for twin in ("", ".sat")])
def test_the_data_only_metrics_read_hand_made_counters(name):
    value = VALUES[name.removesuffix(".sat")]
    assert harness.read_metric(REPO, True, name, counted(COUNTERS)) == pytest.approx(value)
    # a program without the counters (the parent): the metric is left out
    parent = {k: v for k, v in COUNTERS.items() if k.startswith(("batcher.", "pio_batch_slot"))}
    assert harness.read_metric(REPO, True, name, counted(parent)) is None
    assert harness.read_metric(REPO, True, name, harness.Run(0.0, 51.0, 1, 0, True)) is None


@pytest.mark.parametrize("name", ["closed_after_idle_share", "queries_after_idle_share.sat"])
def test_a_cell_in_which_no_batch_closes_on_waking_reads_zero(name):
    none = dict(COUNTERS, **{CLOSED % "idle": (5.0, 5.0), QUERIES % "idle": (5.0, 5.0)})
    assert harness.read_metric(REPO, True, name, counted(none)) == 0.0


def test_the_twenty_entries_are_found_by_name_behind_the_parents():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    cells = {c["name"] for c in bench["workloads"]}
    assert len(NEW) == 20 and len(names) == len(set(names))
    for name, workloads in NEW.items():
        entry = by_name[name]
        assert names.index(name) > names.index(PARENTS_LAST), name
        assert entry["workloads"] == workloads and set(workloads) <= cells
        assert entry["better"] == "lower"
        assert entry["moves"] == ("serve_p50_ms" if workloads == STEADY else "answered_qps")
        assert set(workloads) <= set(end_to_end[entry["moves"]]["workloads"])
        spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
        assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
        by_reader = {
            "counter_ratio": ("program_counter", "micro-batcher"),
            "launch_lag": ("device_trace", "device"),
            "span_mean_ms": ("program_span", "session scorer"),
        }
        assert (entry["source"], entry["layer"]) == by_reader[spec["reader"]]
    # a twin reads what its plain name reads
    for name in TWINS:
        plain, twin = (
            (REPO / "benchmark" / "layer_metrics" / f"{n}.json").read_text() for n in (name, f"{name}.sat")
        )
        assert plain == twin


def test_every_new_metric_of_a_cell_reads_from_one_hand_made_run(monkeypatch):
    enqueues = {1: 120.0, 2: 130.0}
    executions = {1: (200.0, 500.0), 2: (500.0, 550.0)}
    spans = [
        ("pio:dispatch", 100.0, 300.0), ("pio:seq.launch", 110.0, 125.0),
        ("pio:seq.launch", 126.0, 131.0), ("pio:seq.denoise", 140.0, 240.0),
    ]
    hand_made(monkeypatch, enqueues, executions, spans)
    monkeypatch.setattr(_slice, "load", lambda run: _slice.read("hand-made"))
    run = counted(COUNTERS)
    run.trace = object()
    values = {name: harness.read_metric(REPO, True, name, run) for name in NEW}
    assert all(v is not None for v in values.values()), values
    assert values["seq_launch_host_ms"] == pytest.approx(1e-6 * 10.0)
    assert values["seq_denoise_launch_host_ms"] == pytest.approx(1e-6 * 100.0)
    assert values["launch_lag_ms.sat"] == pytest.approx(1e-6 * 225.0)
    assert values["launch_queue_programs"] == pytest.approx(0.5)
