"""``joined_in_slot_wait_share`` and ``.sat`` (PR 25): data files over the
``counter_ratio`` reader that was there, the micro-batcher's
``pio_batch_joined_in_slot_wait_total`` over the queries it dispatched."""

import json

import pytest

from benchmark import harness
from benchmark_testkit import REPO

NAMES = ["joined_in_slot_wait_share", "joined_in_slot_wait_share.sat"]
COUNTERS = {
    "pio_batch_joined_in_slot_wait_total{}": (40.0, 640.0),
    "batcher.queries_dispatched": (100.0, 1100.0),
    "batcher.batches_dispatched": (10.0, 110.0),
}


def run_with(counters):
    return harness.Run(
        0.0, 51.0, 1, 0, True,
        counters_start={k: v[0] for k, v in counters.items()},
        counters_end={k: v[1] for k, v in counters.items()},
    )


@pytest.mark.parametrize("name", NAMES)
def test_the_share_is_the_counters_growth_over_the_queries_dispatched(name):
    spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
    assert spec["reader"] == "counter_ratio"
    assert harness.read_metric(REPO, True, name, run_with(COUNTERS)) == pytest.approx(60.0)
    # nothing joined: the share is 0, and it is reported
    flat = dict(COUNTERS, **{"pio_batch_joined_in_slot_wait_total{}": (40.0, 40.0)})
    assert harness.read_metric(REPO, True, name, run_with(flat)) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counter_leaves_the_share_out(name):
    parent = {k: v for k, v in COUNTERS.items() if not k.startswith("pio_batch_joined")}
    assert harness.read_metric(REPO, True, name, run_with(parent)) is None
    assert harness.read_metric(REPO, True, name, harness.Run(0.0, 51.0, 1, 0, True)) is None
