"""The benchmark's contract, as far as the CPU can hold it: BENCHMARK.json's
shape and limits, the result line's keys, and the refusals."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark_testkit import REPO, last_line, rehearse

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_benchmark_json_keys_and_limits(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16 and all(PATH.match(p) for p in bench["paths"])
    assert len(bench["command"]) <= 32 and all(one_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    # a full check with the full 24 cells fits the driver's 43,200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    # the command names no file outside paths
    for word in bench["command"]:
        if "/" in word:
            assert not word.startswith("/") and ".." not in word
            assert any(word.startswith(p + "/") for p in bench["paths"])
            assert (REPO / word).is_file()


def test_benchmark_json_configs_and_cells(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert len(configs) == len(bench["configs"]) <= 24
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        held = json.loads((REPO / c["file"]).read_text())
        assert held["name"] == c["name"] and held["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"]) and one_line(c["why"])
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert (REPO / "benchmark" / "traffic" / f"{c['traffic']}.json").is_file()
    assert {c["config"] for c in cells} == set(configs)  # each used by some cell
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


def test_benchmark_json_metrics(bench):
    cells = [c["name"] for c in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    assert 1 <= len(e2e) == len(bench["end_to_end"]) <= 16
    assert 1 <= len(layer) == len(bench["per_layer"]) <= 128
    assert not set(e2e) & set(layer)
    assert e2e["setup_s"]["bound"] <= 0.1 and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
        assert (REPO / "benchmark" / "end_to_end" / f"{m['name']}.json").is_file()
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and one_line(m["layer"]) and m["moves"] in e2e
        assert (REPO / "benchmark" / "layer_metrics" / f"{m['name']}.json").is_file()
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
        # reported only where the metric it moves is
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved), m["name"]
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(spellings) == 1 for spellings in layers.values())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:  # setup_s, another end-to-end metric, and a per-layer one
        assert any(cell in m.get("workloads", cells) for m in bench["end_to_end"] if m["name"] != "setup_s")
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_benchmark_files_are_named_from_a_names_characters(bench):
    for path in bench["paths"]:
        for dirpath, dirnames, filenames in os.walk(REPO / path):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for filename in filenames:
                rel = os.path.relpath(os.path.join(dirpath, filename), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_metric_file_names_a_reader_that_exists():
    for kind in ("end_to_end", "layer_metrics"):
        for path in (REPO / "benchmark" / kind).glob("*.json"):
            spec = json.loads(path.read_text())
            assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file(), path


def test_run_py_holds_no_cell_configuration_or_metric_name(bench):
    text = (REPO / "benchmark" / "run.py").read_text() + (REPO / "benchmark" / "harness.py").read_text()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in bench[k]]
    held = [n for n in names if re.search(r"(?<![\w.\-])" + re.escape(n) + r"(?![\w.\-])", text)]
    assert not held


def test_run_py_refuses_a_platform_that_is_not_tpu(bench):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "found platform 'cpu'" in proc.stderr


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rec-als-ml20m.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0 and proc.stdout == ""


def test_an_unknown_cell_is_refused(tiny_root):
    proc = rehearse(tiny_root, "no-such-cell", 0, 1)
    assert proc.returncode != 0 and "no cell" in proc.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_a_train_cell_prints_exactly_the_contracts_keys(tiny_root, trace):
    proc = rehearse(tiny_root, "tiny-train.train", trace, 0.5)
    line = last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    expected = {"compiles_in_window.train", "etl_s", "sweep_s_per_iter"} if trace else {"train_s", "setup_s"}
    assert set(line["metrics"]) == expected  # the trace's readers found no device plane on the CPU
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and isinstance(metric["value"], float)
    if trace:
        assert line["metrics"]["compiles_in_window.train"]["value"] == 0.0


def test_rehearsal_of_a_steady_cell_reports_the_served_path(tiny_root):
    line = last_line(rehearse(tiny_root, "tiny-serve.steady", 0, 1.5))
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"serve_p50_ms", "setup_s"}
    assert line["attempted"] > 100  # 150 q/s for 1.5 s, drawn from the seed
    assert 0 < line["metrics"]["serve_p50_ms"]["value"] < 10_000


def test_rehearsal_of_a_saturated_cell_counts_answers(tiny_root):
    line = last_line(rehearse(tiny_root, "tiny-serve.sat", 1, 1))
    assert set(line["metrics"]) >= {"batch_size.sat", "host_hops_ms.sat", "cache_hit_share.sat", "sat_latency_p50_ms"}
    assert line["attempted"] > 0 and line["metrics"]["batch_size.sat"]["value"] >= 1.0
