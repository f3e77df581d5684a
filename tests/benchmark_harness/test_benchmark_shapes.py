"""The yardstick's arithmetic, pinned at the cells' shapes. Imports nothing
from ``ops/``: the program may change its own traffic model; these numbers
may not move with it."""

import json

import pytest

from benchmark_testkit import REPO

from benchmark import shapes

N, F = 5_700_000, 128  # rec-als-webgraph-de


@pytest.mark.parametrize(
    "batch, flops, nbytes",
    [
        (1, 1_459_200_000.0, 2_986_800_000.0),
        (32, 46_694_400_000.0, 5_107_200_000.0),
        (128, 186_777_600_000.0, 11_673_600_000.0),
        (37.5, 54_720_000_000.0, 5_483_400_000.0),  # a mean batch: both are linear in it
    ],
)
def test_serve_batch_flops_and_bytes(batch, flops, nbytes):
    assert shapes.serve_batch_flops(batch, N, F) == flops
    assert shapes.serve_batch_bytes(batch, N, F) == nbytes


@pytest.mark.parametrize(
    "kwargs, expected",
    [
        # rec-als-ml20m.train as it ran on the chip (PR 23): block counts from
        # the train's own timings, rank 32, stock cg, float32 gather
        ({}, 42_999_110_656),
        ({"gather_dtype": "bf16"}, 39_472_749_568),
        ({"solver": "cg_fused"}, 13_895_896_064),
        ({"implicit": True}, 43_020_261_248),
    ],
)
def test_solver_hbm_bytes_per_iter(kwargs, expected):
    got = shapes.solver_hbm_bytes_per_iter(258_304, 172_160, 128, 32, 138_493, 26_744, **kwargs)
    assert got == expected


def test_roofline_share_says_which_peak_bounds():
    peak = json.loads((REPO / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    share, bound = shapes.roofline_share(
        shapes.serve_batch_flops(32, N, F), shapes.serve_batch_bytes(32, N, F), 0.0149, peak
    )
    assert bound == "bandwidth" and share == pytest.approx(41.85, abs=0.01)
    share, bound = shapes.roofline_share(197e12, 1.0, 2.0, peak)
    assert bound == "compute" and share == pytest.approx(50.0)


def test_shapes_imports_nothing_of_the_program():
    text = (REPO / "benchmark" / "shapes.py").read_text()
    assert "import predictionio_tpu" not in text and "from predictionio_tpu" not in text
