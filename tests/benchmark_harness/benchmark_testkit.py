"""Shared by the benchmark harness's tests (imported by name, so the name is
unique in the suite): a temporary copy of the benchmark
with tiny configurations added as NEW files and entries (no file of the copy
is edited, but for ``BENCHMARK.json``'s lists growing), and a rehearsal: one
cell run on the CPU through ``harness.run_cell(platform="cpu")``, a Python
call that only these tests make.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY = {
    "tiny-train": ("rec-als-ml20m", {"n_users": 500, "n_items": 200, "n_ratings": 20000}, {"rank": 8, "numIterations": 3}),
    "tiny-serve": ("rec-als-webgraph-de", {"n_users": 2000, "n_items": 1500, "server_config": {"max_batch_size": 4}}, {"rank": 16}),
}
# the mixes' own files with a short ramp and few connections, as new files
TINY_TRAFFIC = {
    "tiny-steady": ("steady", {"ramp_s": 0.3, "connections": 16, "trace_offset_s": 0.2, "trace_slice_s": 0.5}),
    "tiny-sat": ("sat", {"ramp_s": 0.3, "connections": 8, "users_drawn": 20000, "trace_offset_s": 0.2, "trace_slice_s": 0.5}),
}
TINY_CELLS = {
    "tiny-train.train": ("tiny-train", "train", "rec-als-ml20m.train"),
    "tiny-serve.steady": ("tiny-serve", "tiny-steady", "rec-als-webgraph-de.serve-steady"),
    "tiny-serve.sat": ("tiny-serve", "tiny-sat", "rec-als-webgraph-de.serve-sat"),
}

REHEARSE = """
import json, sys, time
start = time.monotonic()
root, cell, trace, seconds = sys.argv[1], sys.argv[2], int(sys.argv[3]), float(sys.argv[4])
sys.path.insert(0, root)
from benchmark import harness
print(json.dumps(harness.run_cell(root, cell, 1, seconds, bool(trace), start, platform="cpu")))
"""


def add_cell(bench: dict, name: str, config: str, traffic: str, like: str | None = None):
    """A new cell's entry, reporting the metrics that ``like`` reports."""
    bench["workloads"].append(
        {"name": name, "config": config, "traffic": traffic, "chips": 1, "why": "a test's"}
    )
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if like in metric.get("workloads", ()):
                metric["workloads"].append(name)


def make_tiny_root(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "predictionio_tpu", root / "predictionio_tpu")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, (base, sizes, params) in TINY.items():
        config = json.loads((REPO / "benchmark" / "configs" / f"{base}.json").read_text())
        config.update(sizes, name=name)
        config["variant"]["algorithms"][0]["params"].update(params)
        (root / "benchmark" / "configs" / f"{name}.json").write_text(json.dumps(config))
        bench["configs"].append(
            {"name": name, "source": "a test's", "file": f"benchmark/configs/{name}.json",
             "reduced": [], "why": "a test's"}
        )
    for name, (base, changes) in TINY_TRAFFIC.items():
        mix = json.loads((REPO / "benchmark" / "traffic" / f"{base}.json").read_text())
        (root / "benchmark" / "traffic" / f"{name}.json").write_text(json.dumps({**mix, **changes}))
    for name, (config, traffic, like) in TINY_CELLS.items():
        add_cell(bench, name, config, traffic, like)
    (root / "benchmark" / "cells" / "tiny-serve.steady.json").write_text('{"rate_qps": 150}')
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def rehearse(root: Path, cell: str, trace: int, seconds: float) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "-c", REHEARSE, str(root), cell, str(trace), str(seconds)],
        capture_output=True, text=True, env=env, timeout=120,
    )


def last_line(proc: subprocess.CompletedProcess) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
