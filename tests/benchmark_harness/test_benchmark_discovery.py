"""Driven by data: a configuration, an engine, a traffic mix, a driver, an
end-to-end metric and a per-layer metric with a reader of its own are each
added to a temporary copy as NEW files plus new entries of BENCHMARK.json,
and the harness finds them; no file of the copy is edited."""

import hashlib
import json

from benchmark_testkit import REPO, add_cell, last_line, rehearse

ENGINE = '''
def job(ctx):
    return {"base": ctx.config["base"], "seed": ctx.seed}
'''
DRIVER = '''
from benchmark import harness


def run(ctx, engine):
    job = engine.job(ctx)
    readings = [job["base"] + r for r in ctx.traffic["readings"]]
    return harness.Run(
        setup_seconds=0.25, window_s=ctx.seconds, attempted=len(readings), failed=0,
        correct=True, series={"readings": readings}, counts={"seen": len(readings)},
    )
'''
READER = '''
def read(run, factor):
    if "readings" not in run.series:
        return None
    return factor * max(run.series["readings"])
'''


def _digests(root):
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted((root / "benchmark").rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }


def test_new_files_and_entries_are_found_and_nothing_is_edited(tiny_root):
    before = _digests(tiny_root)
    b = tiny_root / "benchmark"
    (b / "configs" / "added-config.json").write_text(
        json.dumps({"name": "added-config", "engine": "added_engine", "base": 100.0, "reduced": []})
    )
    (b / "engines" / "added_engine.py").write_text(ENGINE)
    (b / "traffic" / "added-mix.json").write_text(
        json.dumps({"kind": "added_kind", "readings": [1.0, 2.0, 3.0, 10.0]})
    )
    (b / "cells" / "added-config.added-mix.json").write_text(json.dumps({"readings": [1.0, 2.0, 7.0]}))
    (b / "drivers" / "added_kind.py").write_text(DRIVER)
    (b / "readers" / "added_reader.py").write_text(READER)
    (b / "layer_metrics" / "added_layer_metric.json").write_text(
        json.dumps({"reader": "added_reader", "args": {"factor": 2.0}})
    )
    (b / "layer_metrics" / "added_absent_metric.json").write_text(
        json.dumps({"reader": "program_mean_ms", "args": {"program": "jit_nothing"}})
    )
    (b / "end_to_end" / "added_median.json").write_text(
        json.dumps({"reader": "series_percentile", "args": {"series": "readings", "q": 50}})
    )
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "added-config", "source": "a test's", "file": "benchmark/configs/added-config.json",
         "reduced": [], "why": "a test's"}
    )
    cell = "added-config.added-mix"
    add_cell(bench, cell, "added-config", "added-mix")
    bench["end_to_end"].append(
        {"name": "added_median", "unit": "ms", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": [cell]}
    )
    for name in ("added_layer_metric", "added_absent_metric"):
        bench["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower", "source": "program_span",
             "layer": "a test's", "moves": "added_median", "workloads": [cell]}
        )
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = last_line(rehearse(tiny_root, cell, 0, 1))
    # the cell's own file is laid over the mix's: readings 1, 2, 7 on base 100
    assert line["metrics"] == {
        "added_median": {"value": 102.0, "unit": "ms"},
        "setup_s": {"value": 0.25, "unit": "s"},
    }
    assert line["attempted"] == 3 and line["correct"] is True

    line = last_line(rehearse(tiny_root, cell, 1, 1))
    # a reader that finds nothing to read returns nothing: the metric is left out
    assert line["metrics"] == {"added_layer_metric": {"value": 214.0, "unit": "ms"}}

    after = _digests(tiny_root)
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 9


def test_the_copy_starts_as_the_repositorys_benchmark(tiny_root):
    ours = {
        str(p.relative_to(REPO)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted((REPO / "benchmark").rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }
    copy = _digests(tiny_root)
    assert {k: copy[k] for k in ours} == ours
