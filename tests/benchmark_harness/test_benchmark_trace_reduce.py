"""The reduction from a profiler trace to busy and idle time, per-program
time, top operations and idle gaps, held to a slice recorded on the chip.

``benchmark_serve_slice.xplane.pb.gz`` was taken on a TPU v5 lite in PR 23 by
``benchmark/trace_reduce.Slice`` around four calls of the program's batched
serve (buckets 32, 32, 8 and 128 over 5.7 M x 128) with 12 ms of sleep after
each: what the reduction must give for it is written here.
"""

from pathlib import Path

import pytest

from benchmark import trace_reduce

FIXTURE = str(Path(__file__).with_name("benchmark_serve_slice.xplane.pb.gz"))


@pytest.fixture(scope="module")
def profile():
    return trace_reduce.load(FIXTURE)


def test_busy_share_window_and_programs_of_the_recorded_slice(profile):
    s = trace_reduce.reduce(profile, chips=1)
    assert s.window_s == pytest.approx(0.143664572, abs=1e-9)
    assert s.busy_s == pytest.approx(0.086747071, abs=1e-9)
    assert 100 * s.idle_share == pytest.approx(39.618, abs=1e-3)
    serve = s.programs["jit__serve_by_index_batch"]
    assert serve["count"] == 4 and serve["seconds"] == pytest.approx(0.086744956, abs=1e-9)
    assert s.programs["jit_convert_element_type"]["count"] == 4
    assert set(s.programs) == {"jit__serve_by_index_batch", "jit_convert_element_type"}


def test_top_operation_is_the_top_k_over_the_largest_bucket(profile):
    s = trace_reduce.reduce(profile, chips=1)
    name, seconds = s.device_ops[0]
    assert name.startswith("%custom-call = (f32[128,16], s32[128,16]) custom-call(f32[128,5700000]")
    assert seconds == pytest.approx(0.041456221, abs=1e-9)
    assert "{" not in name and len(name) <= 96  # layouts stripped, cut to length
    # the score product of the two bucket-32 calls, summed under one name
    assert s.device_ops[2][0].startswith("%convolution_select_fusion = f32[32,5700000] fusion(bf16[32,128]")
    assert s.device_ops[2][1] == pytest.approx(0.010243489, abs=1e-9)
    assert not [n for n, _ in s.device_ops if " while(" in n]


def test_longest_gap_and_its_name(profile):
    s = trace_reduce.reduce(profile, 1, lambda a, b: "early" if a < 0.05 else "late")
    name, seconds, start = s.gaps[0]
    assert (name, seconds, start) == ("late", pytest.approx(0.01447674, abs=1e-9), pytest.approx(0.129187832, abs=1e-9))
    assert [g[0] for g in s.gaps[:4]] == ["late", "late", "early", "early"]
    assert s.gap_totals["early"] == pytest.approx(0.02796032, abs=1e-9)
    assert s.gap_totals["early"] + s.gap_totals["late"] == pytest.approx(s.window_s - s.busy_s, abs=1e-9)
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 7 <= 10
    assert b["idle_gaps"][0] == ["late", pytest.approx(0.01447674, abs=1e-9)]
    assert b["idle_gaps"][5][0] == "sum:late" and b["idle_gaps"][6][0] == "sum:early"
    unnamed = trace_reduce.reduce(profile, chips=1)
    assert {g[0] for g in unnamed.gaps} == {"unnamed"}


def test_labels():
    assert trace_reduce.program_label("jit__als_step(6288092397290172453)") == "jit__als_step"
    hlo = "%fusion = bf16[32,128]{1,0:T(8,128)(2,1)S(1)} fusion(f32[5700000,128]{1,0:T(8,128)} %p)"
    assert trace_reduce.op_label(hlo) == "%fusion = bf16[32,128] fusion(f32[5700000,128] %p)"


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _host(*events):
    return _Plane("/host:CPU", [_Line("python3", [_Event(*e) for e in events])])


def _device(n, ops, modules=()):
    return _Plane(
        f"/device:TPU:{n}",
        [_Line("XLA Ops", [_Event(*e) for e in ops]), _Line("XLA Modules", [_Event(*e) for e in modules])],
    )


def test_overlapping_operations_are_counted_once_and_clipped_to_the_slice():
    profile = _Profile([
        _host((trace_reduce.SLICE_START, 1000, 1), (trace_reduce.SLICE_END, 2000, 1)),
        _device(0, [("%a = f32[] add()", 900, 200), ("%w = () while(())", 1200, 400),
                    ("%b = f32[] mul()", 1300, 100), ("%c = f32[] mul()", 1900, 500)],
                [("jit_f(1)", 1200, 400), ("jit_f(2)", 2100, 50)]),
    ])
    s = trace_reduce.reduce(profile, 1)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((100 + 400 + 100) * 1e-9)  # %b lies inside the loop
    assert s.programs == {"jit_f": {"count": 1, "seconds": pytest.approx(400e-9)}}
    assert [n for n, _ in s.device_ops] == ["%a = f32[] add()", "%b = f32[] mul()", "%c = f32[] mul()"]
    assert sorted(round(g[1] * 1e9) for g in s.gaps) == [100, 300]


def test_busy_time_is_averaged_over_the_chips_used():
    marks = _host((trace_reduce.SLICE_START, 0, 1), (trace_reduce.SLICE_END, 1000, 1))
    profile = _Profile([marks, _device(0, [("%a = f32[] add()", 0, 1000)]), _device(1, [("%a = f32[] add()", 0, 500)])])
    assert trace_reduce.reduce(profile, chips=2).busy_s == pytest.approx(750e-9)
    assert trace_reduce.reduce(profile, chips=1).busy_s == pytest.approx(1000e-9)


@pytest.mark.parametrize(
    "planes, message",
    [
        ([_host((trace_reduce.SLICE_START, 0, 1))], "no /device:TPU"),
        ([_host((trace_reduce.SLICE_START, 0, 1)), _device(0, [])], "no operation ran"),
        ([_host(), _device(0, [("%a = f32[] add()", 0, 10)])], "annotations"),
    ],
)
def test_a_trace_with_nothing_to_reduce_is_an_error(planes, message):
    with pytest.raises(ValueError, match=message):
        trace_reduce.reduce(_Profile(planes), 1)
