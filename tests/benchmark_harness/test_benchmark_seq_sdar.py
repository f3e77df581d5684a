"""The ``seq-sdar-moe`` configuration's benchmark files: a tiny configuration
and cell are added to a temporary copy as NEW files and entries and rehearsed
on the CPU; the operation counts against hand-worked ones; the benchmark's
copy of the reference against the program's; the new readers on hand-made
runs; where the new entries stand in ``BENCHMARK.json`` (by NAME); the
check's parts on hand-made replies; every planted fault refused through the
deployed (tiny) cell's own check."""

import ast
import inspect
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, reference_sdar, shapes_sdar
from benchmark.readers import _slice, sdar_roofline
from benchmark_testkit import REPO, add_cell, last_line, rehearse

CELL = "seq-sdar-moe.serve-sat"
NEW_METRICS = [
    "seq_denoise_pass_ms", "denoise_time_share", "passes_per_batch", "generated_items_per_s",
    "cache_bytes_per_batch", "denoise_pass_roofline", "denoise_experts_roofline",
    "sdar_experts_roofline", "gqa_attn_roofline",
]
JOINED = [
    "seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "seq_program_ms", "seq_experts_ms",
    "seq_attn_ms", "seq_router_ms", "expert_load_max_over_mean",
]
COUNTER_FED = [
    "seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "expert_load_max_over_mean",
    "passes_per_batch", "generated_items_per_s", "cache_bytes_per_batch",
]
TINY_WIDTHS = {
    "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "vocab_size": 256, "generation": {"block_length": 4, "denoising_steps": 4, "mask_token_id": 255},
    "published": {"num_hidden_layers": 3},
}


def published_config() -> dict:
    return json.loads((REPO / "benchmark" / "configs" / "seq-sdar-moe.json").read_text())


def add_tiny_sdar(root):
    """``tiny-sdar`` and ``tiny-sdar.sat`` as new files and entries of the copy."""
    config = published_config()
    config.update(
        TINY_WIDTHS, name="tiny-sdar", n_users=300,
        session_length={"median": 24, "sigma": 0.9, "min": 3, "max": 128},
        server_config={"max_batch_size": 8},
    )
    (root / "benchmark" / "configs" / "tiny-sdar.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny-sdar", "source": "a test's", "file": "benchmark/configs/tiny-sdar.json",
         "reduced": [], "why": "a test's"}
    )
    mix = json.loads((REPO / "benchmark" / "traffic" / "sat.json").read_text())
    mix.update(ramp_s=0.5, connections=4, users_drawn=5000, trace_offset_s=0.2, trace_slice_s=0.5)
    (root / "benchmark" / "traffic" / "tiny-sdar-sat.json").write_text(json.dumps(mix))
    (root / "benchmark" / "cells" / "tiny-sdar.sat.json").write_text('{"num": 6}')
    add_cell(bench, "tiny-sdar.sat", "tiny-sdar", "tiny-sdar-sat", CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_sdar_cell_rehearses_on_the_cpu(tiny_root, trace):
    add_tiny_sdar(tiny_root)
    seconds = 12  # an answer is nine passes and more here: as the kimi rehearsal, twice the others' 6 s
    proc = rehearse(tiny_root, "tiny-sdar.sat", trace, seconds)
    line = last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 16
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"answered_qps", "setup_s"}
        assert metrics["answered_qps"]["value"] == pytest.approx(line["attempted"] / seconds)
        assert "worst |served - reference| log-probability by answer: median" in proc.stderr
        assert "0 can be no trajectory; " in proc.stderr and "; 0 wrong; " in proc.stderr
        return
    # what the program's counters feed is there; what only a device trace
    # feeds has nothing to read on the CPU and is left out
    assert set(COUNTER_FED) <= set(metrics)
    assert not (set(NEW_METRICS + JOINED) - set(COUNTER_FED)) & set(metrics)
    assert "seq_head_ms" not in metrics  # no `dot_top_k` in this cell: the passes hold the head
    assert metrics["compiles_in_window.sat"]["value"] == 0
    assert metrics["seq_tokens_per_s"]["value"] > 0 and 0 < metrics["pad_token_share"]["value"] < 100
    # num 6: a session makes 6 denoise passes and one or two commits, a batch the slowest's.
    # (A batch is counted where it is staged and its passes where they are launched: the
    # window's edge can fall between, and this window holds eight batches or so. The same
    # holds for the cache's bytes below.)
    edge = 0.85
    assert edge * 7 <= metrics["passes_per_batch"]["value"] <= 8
    # (a reply from the result cache generates nothing: 5,000 draws over 300 users repeat)
    assert 0 < metrics["generated_items_per_s"]["value"] <= 1.2 * 6 * line["attempted"] / seconds
    # a batch holds one stream of 2,048 slots or two and seven or eight chunks of 128
    a_slot = 3 * 2 * 2 * 16 * 2
    assert edge * (2048 + 7 * 128) * a_slot <= metrics["cache_bytes_per_batch"]["value"] <= (6144 + 8 * 128) * a_slot
    assert 1.0 <= metrics["expert_load_max_over_mean"]["value"] <= 4.0


def test_operation_counts_against_hand_worked_ones():
    c = TINY_WIDTHS
    # q 64 x 64, k and v 64 x 32, o 64 x 64; an expert 3 x 64 x 32
    assert shapes_sdar.attn_weights(c) == 64 * 64 + 2 * 64 * 32 + 64 * 64 == 12288
    assert shapes_sdar.expert_weights(c) == 3 * 64 * 32 == 6144
    assert shapes_sdar.kv_bytes_a_token(c) == 2 * 2 * 16 * 2 == 128
    # 2 of 8 experts a token: one token reaches 2, many reach all
    assert shapes_sdar.experts_reached(1, c) == pytest.approx(2.0)
    assert shapes_sdar.experts_reached(100, c) == pytest.approx(8.0, abs=1e-6)
    assert shapes_sdar.experts_flops(100, c) == 2 * 100 * 2 * 6144 == 2457600
    assert shapes_sdar.experts_bytes(100, c) == pytest.approx(8 * 6144 * 2 + 2 * 100 * 64 * 4)
    # where the program counted the experts its real rows reached, those are read and no more
    assert shapes_sdar.experts_bytes(100, c, reached=5.5) == pytest.approx(5.5 * 6144 * 2 + 2 * 100 * 64 * 4)
    # 3 streams of 64: projections, and the block-causal half of q.k and p.v over 4 heads of 16
    tokens = 3 * 64
    assert shapes_sdar.gqa_attn_flops(3, 64, c) == tokens * (2 * 12288 + 2 * 64 * 4 * 16) == 6291456
    assert shapes_sdar.gqa_attn_bytes(tokens, c) == 12288 * 2 + 2 * tokens * 64 * 4 + tokens * 128
    # a pass of 128 positions of 32 sessions over 3,200 cached keys (100 a session)
    a_layer = 128 * (2 * 12288 + 2 * 2 * 100 * 4 * 16) + 2 * 128 * 2 * 6144
    assert shapes_sdar.pass_flops(128, 3200, 32, c) == 3 * a_layer + 2 * 128 * 64 * 256
    a_layer = shapes_sdar.experts_bytes(128, c) + 12288 * 2 + 3200 * 128
    assert shapes_sdar.pass_bytes(128, 3200, c) == pytest.approx(3 * a_layer + 256 * 64 * 2)
    fewer = shapes_sdar.pass_bytes(128, 3200, c) - shapes_sdar.pass_bytes(128, 3200, c, reached=6.0)
    assert fewer == pytest.approx(3 * (shapes_sdar.experts_reached(128, c) - 6.0) * 6144 * 2)
    # at the published widths: the issue's arithmetic
    published = published_config()
    assert shapes_sdar.attn_weights(published) == pytest.approx(18.87e6, rel=0.001)
    assert 128 * shapes_sdar.expert_weights(published) == pytest.approx(604.0e6, rel=0.001)
    assert shapes_sdar.kv_bytes_a_token(published) * 6 == 12288
    # a denoise pass reads 7.5 GB of experts and the head's 0.62: 10.0 ms at 819 GB/s, bound by BYTES
    nbytes = shapes_sdar.pass_bytes(128, 14000, published)
    flops = shapes_sdar.pass_flops(128, 14000, 32, published)
    assert nbytes == pytest.approx(8.2e9, rel=0.02) and nbytes / 819e9 > 5 * flops / 197e12
    # a 2,048-token stream's experts: 1.21 GB and 155 GFLOP a layer, bound by bytes too
    assert shapes_sdar.experts_bytes(2048, published) == pytest.approx(1.24e9, rel=0.01)
    assert shapes_sdar.experts_flops(2048, published) == pytest.approx(155e9, rel=0.01)


def test_the_benchmarks_reference_is_the_programs_function_for_function():
    from predictionio_tpu.models.sequential import sdar_reference

    def functions(module):
        return {
            name: inspect.getsource(f) for name, f in inspect.getmembers(module, inspect.isfunction)
            if f.__module__ == module.__name__
        }

    ours, theirs = functions(reference_sdar), functions(sdar_reference)
    assert ours.keys() == theirs.keys() and len(ours) >= 24
    for name in ours:
        assert ours[name] == theirs[name], name
    # float32 at `highest`, and nothing of the program's ops/
    source = inspect.getsource(reference_sdar)
    assert '_HIGHEST = "highest"' in source and "predictionio_tpu" not in source.split('"""', 2)[2]
    assert "import" not in source.split('"""', 2)[2].replace(
        "from __future__ import annotations", ""
    ).replace("import jax\nimport jax.numpy as jnp\nimport numpy as np", "")


def test_the_engine_module_imports_the_programs_names_at_its_top():
    # so that a checkout without them (the PR's parent) fails at once
    tree = ast.parse((REPO / "benchmark" / "engines" / "sequential_sdar.py").read_text())
    top = {
        f"{node.module}.{alias.name}" for node in tree.body if isinstance(node, ast.ImportFrom)
        for alias in node.names
    }
    assert "predictionio_tpu.models.sequential.sdar" in top
    assert "predictionio_tpu.models.sequential.engine.SdarModel" in top


def test_the_new_entries_are_appended_and_the_old_ones_only_grew():
    # pinned by NAME, not by position: the next cell appends behind these
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    start = names.index(NEW_METRICS[0])
    assert names[start : start + len(NEW_METRICS)] == NEW_METRICS
    assert names[start - 1] == "absent_copy_share"  # behind PR 31's last
    cells = [c["name"] for c in bench["workloads"]]
    assert cells.index(CELL) == cells.index("seq-kimi-linear.serve-sat") + 1
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("seq-sdar-moe") == configs.index("seq-kimi-linear") + 1
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    qps = by_name["answered_qps"]["workloads"]
    assert qps.index(CELL) == qps.index("seq-kimi-linear.serve-sat") + 1
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "answered_qps"
        assert m["layer"] in ("sequence kernels", "session scorer")
        spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
        assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher" and m["source"] == "device_trace"
    for name in JOINED:
        joined = by_name[name]["workloads"]
        # (`seq_attn_ms` reads OLMoE's scope `attn`, which this prefill has and Kimi-Linear's has not)
        before = "seq-olmoe.serve-sat" if name == "seq_attn_ms" else "seq-kimi-linear.serve-sat"
        assert joined.index(CELL) == joined.index(before) + 1
    sat = [m["name"] for m in bench["per_layer"] if "seq-kimi-linear.serve-sat" in m["workloads"]]
    ours = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    # Kimi-Linear's own scopes and shapes, and `jit__dot_top_k`, which this cell does not run
    assert set(sat) - set(ours) == {
        "seq_kda_ms", "seq_mla_ms", "seq_shared_ms", "kda_roofline", "mla_roofline",
        "experts_held_roofline", "absent_copy_share", "seq_head_ms",
    }
    assert sum(name.endswith(".sat") or name == "sat_latency_p50_ms" for name in ours) == 14
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert cell == {**cell, "config": "seq-sdar-moe", "traffic": "sat", "chips": 1}
    assert len(cell["why"]) <= 200 and "passes" in cell["why"] and "bytes" in cell["why"]
    assert json.loads((REPO / "benchmark" / "cells" / f"{CELL}.json").read_text()) == {"num": 16}
    assert harness.load_cell(REPO, CELL)[3]["num"] == 16
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 0


def test_the_configuration_states_every_published_key_and_the_cut():
    from pathlib import Path

    config = published_config()
    entry = {c["name"]: c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]}["seq-sdar-moe"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 6 and config["published"] == {"num_hidden_layers": 48}
    assert "eight pipeline stages of six whole layers" in config["deployment"]
    widths = {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "num_experts": 128, "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "vocab_size": 151936, "rope_theta": 1000000,
    }
    assert {key: config[key] for key in widths} == widths
    assert config["generation"] == {"block_length": 4, "denoising_steps": 4, "mask_token_id": 151935}
    assert all(isinstance(line, str) and line for line in config["assumed"].values())
    for key in ("block_length", "denoising_steps", "mask_token_id", "candidates", "partial block", "weights"):
        assert key in config["assumed"]
    olmoe = json.loads((REPO / "benchmark" / "configs" / "seq-olmoe.json").read_text())
    for key in ("n_users", "session_length", "structure_seed", "seed_rule"):
        assert config[key] == olmoe[key], key
    # the issue's 64; the ALGORITHM cuts a batch to what one group of passes holds
    from predictionio_tpu.models.sequential import sdar
    from predictionio_tpu.models.sequential.engine import SdarAlgorithm

    assert config["server_config"] == {"max_batch_size": 64} and "max_batch_size" in config["assumed"]
    assert SdarAlgorithm.batch_limit(SdarAlgorithm.__new__(SdarAlgorithm)) == sdar.SESSIONS == 32
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(l) for l in catalog.read_text().splitlines() if "SDAR-30B-A3B-Chat" in l)
        assert entry["source"] == config["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if config[k] != v}
        assert differing == set(config["reduced"])  # no width among them


def test_the_variant_gives_the_algorithm_the_published_keys_and_the_generations():
    from benchmark.engines import sequential_sdar as engine
    from predictionio_tpu.models.sequential import engine_factory

    variant = engine.variant_of(published_config(), 3400000123)
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    assert (params.num_experts, params.vocab_size, params.num_hidden_layers) == (128, 151936, 6)
    config = params.config()
    assert (config.num_attention_heads, config.num_key_value_heads, config.head_dim) == (32, 4, 128)
    assert (config.block_length, config.denoising_steps, config.mask_token_id) == (4, 4, 151935)
    assert config.max_session == 4096 and config.stream_shapes() == (2048, 4096)
    assert config.cache_slots == 32768 and config.cache_bytes(config.cache_slots) == 32768 * 12288
    assert params.seed == 3400000123 % 2**31
    assert engine.PADDED == (512, 4160)


COUNTERS = {
    'pio_seq_tokens_total{kind="real"}': (1000.0, 1000.0 + 10 * 14000),
    'pio_seq_tokens_total{kind="padded"}': (4096.0, 4096.0 + 10 * 16384),
    'pio_seq_programs_total{bucket="2048"}': (2.0, 62.0),
    'pio_seq_programs_total{bucket="4096"}': (1.0, 11.0),
    'pio_seq_rows_total{bucket="2048"}': (2.0, 62.0),
    'pio_seq_rows_total{bucket="4096"}': (1.0, 11.0),
    'pio_seq_sessions_total{bucket="2048"}': (10.0, 290.0),
    'pio_seq_sessions_total{bucket="4096"}': (1.0, 21.0),
    "pio_seq_stage_seconds_total{}": (0.5, 0.6),
    "pio_seq_batches_total{}": (10.0, 20.0),
    'pio_seq_passes_total{kind="denoise"}': (19.0, 19.0 + 190),
    'pio_seq_passes_total{kind="commit"}': (0.0, 10.0),
    "pio_seq_blocks_total{}": (128.0, 128.0 + 1280),
    "pio_seq_generated_items_total{}": (512.0, 512.0 + 51 * 100),
    "pio_seq_cache_bytes_total{}": (1e9, 1e9 + 10 * 2.5e8),
    "pio_moe_expert_tokens_max_total{}": (100.0, 400.0),
    "pio_moe_expert_tokens_mean_total{}": (50.0, 250.0),
    # 200 passes of six layers: 120 of a layer's 128 experts reached in the mean
    "pio_moe_experts_reached_total{}": (7200.0, 7200.0 + 200 * 6 * 120),
    "pio_moe_experts_offered_total{}": (7680.0, 7680.0 + 200 * 6 * 128),
}


def hand_made_run(**fields):
    return harness.Run(
        0.0, 51.0, 1, 0, True,
        counters_start={k: v[0] for k, v in COUNTERS.items()},
        counters_end={k: v[1] for k, v in COUNTERS.items()},
        **fields,
    )


def test_the_counter_fed_metrics_read_a_hand_made_run():
    run = hand_made_run()
    assert harness.read_metric(REPO, True, "passes_per_batch", run) == pytest.approx(20.0)
    assert harness.read_metric(REPO, True, "generated_items_per_s", run) == pytest.approx(100.0)
    assert harness.read_metric(REPO, True, "cache_bytes_per_batch", run) == pytest.approx(2.5e8)
    # a program without the counters (the parent, another backbone): every new metric is left out
    bare = harness.Run(0.0, 51.0, 1, 0, True)
    assert all(harness.read_metric(REPO, True, name, bare) is None for name in NEW_METRICS)
    # OLMoE's run (its shapes, no counter of passes): the rooflines find nothing of theirs
    olmoe = harness.Run(0.0, 51.0, 1, 0, True, shapes={"hidden_size": 2048}, peak={}, trace=object())
    assert all(sdar_roofline.read(olmoe, kernel) is None for kernel in sdar_roofline.KERNELS)
    assert harness.read_metric(REPO, True, "denoise_time_share", hand_made_run()) is None  # no trace


def test_the_device_fed_metrics_read_a_hand_made_slice(monkeypatch):
    def op(start, end, program, scope, inner):
        return (start, end, f"%f = f32[] fusion() {scope}", frozenset({f"jit({program})/{scope}/{inner}/x"}))

    # in the slice: two prefills (attention 3 ms, experts 10 ms each) and five passes
    # (experts 6 ms, attention 1 ms, the head 1 ms each)
    ops = [
        op(0.0, 6e6, "session_vectors", "attn", "dot"), op(6e6, 26e6, "session_vectors", "experts", "gmm"),
        op(26e6, 56e6, "denoise_pass", "experts", "gmm"), op(56e6, 61e6, "denoise_pass", "attn", "dot"),
        op(61e6, 66e6, "denoise_pass", "head", "dot"),
    ]
    profile = _slice.SliceProfile(0.0, 1e9, [], ops)
    monkeypatch.setattr(_slice, "load", lambda run: profile)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    programs = {
        "jit_session_vectors": {"count": 2, "seconds": 0.026}, "jit_denoise_pass": {"count": 5, "seconds": 0.040},
        "jit_answer_of": {"count": 1, "seconds": 0.001},
    }
    shapes = {**published_config()}
    run = hand_made_run(trace=types.SimpleNamespace(programs=programs), shapes=shapes, peak=peak)
    assert harness.read_metric(REPO, True, "seq_denoise_pass_ms", run) == pytest.approx(8.0)
    assert harness.read_metric(REPO, True, "denoise_time_share", run) == pytest.approx(100 * 40 / 67)
    # the accepted readers the cell joined find the prefill's scopes and program too
    assert harness.read_metric(REPO, True, "seq_program_ms", run) == pytest.approx(13.0)
    assert harness.read_metric(REPO, True, "seq_experts_ms", run) == pytest.approx(10.0)
    assert harness.read_metric(REPO, True, "seq_attn_ms", run) == pytest.approx(3.0)

    def least(flops, nbytes):
        return max(flops / 197e12, nbytes / 819e9)

    tokens = (60 * 2048 + 10 * 4096) / 70  # the window's mean stream; five of the six layers run experts
    want = least(5 * shapes_sdar.experts_flops(tokens, shapes), 5 * shapes_sdar.experts_bytes(tokens, shapes))
    assert harness.read_metric(REPO, True, "sdar_experts_roofline", run) == pytest.approx(100 * want / 10e-3)
    flops = (60 * shapes_sdar.gqa_attn_flops(1, 2048, shapes) + 10 * shapes_sdar.gqa_attn_flops(1, 4096, shapes)) / 70
    want = least(5 * flops, 5 * shapes_sdar.gqa_attn_bytes(tokens, shapes))
    assert harness.read_metric(REPO, True, "gqa_attn_roofline", run) == pytest.approx(100 * want / 3e-3)
    # a pass: 30 sessions a batch (300 over 10), 120 positions, 14,000 cached keys, and of a
    # layer's 128 experts the 120 the program counted as reached (an even router's 127.9 are not read)
    want = least(6 * shapes_sdar.experts_flops(120, shapes), 6 * shapes_sdar.experts_bytes(120, shapes, 120.0))
    assert harness.read_metric(REPO, True, "denoise_experts_roofline", run) == pytest.approx(100 * want / 6e-3)
    assert want < least(0.0, 6 * shapes_sdar.experts_bytes(120, shapes))
    want = least(shapes_sdar.pass_flops(120, 14000, 30, shapes), shapes_sdar.pass_bytes(120, 14000, shapes, 120.0))
    got = harness.read_metric(REPO, True, "denoise_pass_roofline", run)
    assert got == pytest.approx(100 * want / 8e-3) and 100 < got < 130  # (8 ms is under the bytes' 9.4)
    # a program that counts no reached experts: nothing is assumed in their place
    blind = hand_made_run(trace=run.trace, shapes=shapes, peak=peak)
    del blind.counters_end["pio_moe_experts_reached_total{}"]
    assert sdar_roofline.read(blind, "denoise_pass") is None and sdar_roofline.read(blind, "sdar_experts") is not None
    # no trace (an untraced run, the CPU): nothing to read
    assert sdar_roofline.read(hand_made_run(shapes=shapes, peak=peak), "denoise_pass") is None


# ------------------------------------------------------------------ the check


def test_a_replys_steps_have_to_be_the_fixing_rules():
    from benchmark.engines import sequential_sdar as engine

    assert engine.steps_of_block(4, 4) == [1, 1, 1, 1] and engine.steps_of_block(3, 4) == [1, 1, 1]
    assert engine.steps_of_block(4, 2) == [2, 2] and engine.steps_of_block(3, 2) == [2, 1]
    assert engine.steps_of_block(4, 1) == [4] and engine.steps_of_block(4, 3) == [2, 1, 1]
    config = {"block_length": 4, "denoising_steps": 4}
    session = np.arange(17)  # one item in its partial block: 3 + 4 + 1 positions for 8 items
    items = list(range(100, 108))
    good = [2, 0, 1, 3, 0, 2, 1, 0]
    assert engine.trajectory_ok(config, session, items, good, 200)
    assert not engine.trajectory_ok(config, session, items, [2, 0, 1, 0, 0, 2, 1, 0], 200)  # two at step 0 of block 1
    assert not engine.trajectory_ok(config, session, items, [2, 0, 1, 3, 0, 2, 1, 1], 200)  # a last block from step 1
    assert not engine.trajectory_ok(config, session, items[:-1] + [100], good, 200)  # a repeated item
    assert not engine.trajectory_ok(config, session, [5] + items[1:], good, 200)  # an item of the session
    assert not engine.trajectory_ok(config, session, [250] + items[1:], good, 200)  # no item at all
    rng = np.random.default_rng(1)
    states = engine.states_of(config, session, items, good, rng)
    assert states[-1] == (2, 0) and states[0][0] == 0 and 0 <= states[0][1] < 3


def test_a_state_says_by_how_much_the_reference_prefers_another_choice():
    from benchmark.engines import sequential_sdar as engine

    rng = np.random.default_rng(2)
    logits = rng.normal(size=(4, 50)).astype(np.float32)
    allowed = np.ones(50, bool)
    allowed[:5] = False
    masked = np.array([True, False, True, True])
    logp = reference_sdar.log_probabilities(logits, allowed)
    best = {p: int(np.argmax(logp[p])) for p in (0, 2, 3)}
    place = max(best, key=lambda p: logp[p, best[p]])
    served = [(place, best[place], float(logp[place, best[place]]) + 0.004)]
    gap, error = engine.check_state(logits, masked, allowed, served)
    assert gap == 0.0 and error == pytest.approx(0.004, abs=1e-6)
    # another item than the reference's best: off by what the best leads it by
    worse = int(np.argsort(-logp[place])[5])
    gap, error = engine.check_state(logits, masked, allowed, [(place, worse, float(logp[place, worse]))])
    assert gap == pytest.approx(float(logp[place, best[place]] - logp[place, worse])) and gap > 0.5 and error == 0.0
    # another place than the reference's most confident: off by the confidences' distance
    other = min(best, key=lambda p: logp[p, best[p]])
    gap, _ = engine.check_state(logits, masked, allowed, [(other, best[other], float(logp[other, best[other]]))])
    assert gap == pytest.approx(float(logp[place, best[place]] - logp[other, best[other]])) and gap > 0.1
    # two fixed by one step: the second takes its best that the first did not take
    both = sorted(best, key=lambda p: -logp[p, best[p]])[:2]
    logits2 = logits.copy()
    logits2[both[1]] = logits2[both[0]]  # the same row: the same best candidate
    logp2 = reference_sdar.log_probabilities(logits2, allowed)
    second = int(np.argsort(-logp2[both[1]])[1])
    fixed = [(both[0], best[both[0]], float(logp2[both[0], best[both[0]]])), (both[1], second, float(logp2[both[1], second]) - 1e-4)]
    gap, error = engine.check_state(logits2, masked, allowed, fixed)
    assert gap == 0.0 and error == pytest.approx(1e-4, abs=1e-6)
    # a masked-out candidate served: off by no number
    gap, error = engine.check_state(logits, masked, allowed, [(place, 2, -3.0)])
    assert gap == np.inf and error == np.inf


def test_the_check_holds_the_median_answer_and_every_answer_to_be_a_trajectory():
    from benchmark.engines import sequential_sdar as engine

    tight = engine.SCORE_TOLERANCE
    fine = [tight / 2] * 15 + [2 * tight, 12 * tight]  # bf16 everywhere, two tipped answers
    assert engine.count_wrong(fine, [True] * 17) == 0
    assert engine.count_wrong(fine, [True] * 16 + [False]) == 1  # off its trajectory
    # the LARGEST is not judged: a sound run's and a lower precision's are not told apart by it
    assert not hasattr(engine, "FLIP_TOLERANCE") and engine.count_wrong(fine[:-1] + [50 * tight], [True] * 17) == 0
    assert engine.count_wrong([2 * tight] * 17, [True] * 17) == 17  # another arithmetic: the median is off
    assert engine.count_wrong([tight / 2] * 8 + [2 * tight] * 9, [True] * 17) == 9
    assert engine.count_wrong([float("nan")] + fine[1:], [True] * 17) == 1  # no number: off by any
    assert engine.count_wrong([float("nan")] * 9 + fine[9:], [True] * 17) == 11
    assert engine.count_wrong([float("inf")] * 17, [False] * 17) == 17


def test_the_check_holds_the_median_sessions_cached_keys_and_values():
    from benchmark.engines import sequential_sdar as engine

    limit = engine.CACHE_TOLERANCE
    # bfloat16 as configured reads 0.0024 a session and fp8 0.027 (the published widths):
    # the limit lies between with three times of room on both sides
    assert 3 * 0.0024 < limit < 0.027 / 3
    assert engine.cache_wrong([limit / 3] * 17) == 0 and engine.cache_wrong([]) == 0
    assert engine.cache_wrong([limit / 3] * 16 + [40 * limit]) == 0  # the median is held, not one session
    assert engine.cache_wrong([3 * limit] * 17) == 17
    assert engine.cache_wrong([limit / 3] * 8 + [3 * limit] * 9) == 9
    assert engine.cache_wrong([float("nan")] * 9 + [limit / 3] * 8) == 9  # no number: off by any


CONTROLS = ["experts_7", "experts_fp8", "not_renormalised", "token_causal", "stale_cache", "kv_fp8"]


def test_the_controls_script_deploys_the_cell_and_the_check_refuses_every_planted_fault(tiny_root):
    add_tiny_sdar(tiny_root)
    # the median's limit is set from the published widths' readings on the chip
    # (0.016 as configured, 0.037 under a stale cache); heads of 16 over three
    # layers read 0.004 and 0.025, and are held to a limit between THOSE
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); from benchmark import controls_sdar as c; "
        "from benchmark.engines import sequential_sdar as e; e.SCORE_TOLERANCE = 0.012; "
        f"sys.exit(0 if c.run(sys.argv[1], 5, [None] + {CONTROLS!r} + [None], 'cpu', 'tiny-sdar.sat') else 1)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tiny_root)], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [line.get("control") for line in lines] == ["as configured"] + CONTROLS + ["as configured", None]
    assert lines[-1] == {"ok": True}
    sound = lines[0]
    # bf16 operands against float32 at a tiny size
    assert sound["wrong"] == 0 and sound["checked"] >= 8 and sound["median_score_error"] < 0.01
    from benchmark.engines import sequential_sdar as engine

    assert sound["median_cache_error"] < engine.CACHE_TOLERANCE / 2
    for line in lines[1:-2]:
        assert line["wrong"] > 0 and line["as_expected"], line
        # the first layer's cached keys and values are moved by fp8 keys and values alone, and
        # by them past the limit whatever the scores say
        if line["control"] == "kv_fp8":
            assert line["median_cache_error"] > 2 * engine.CACHE_TOLERANCE
        else:
            assert line["median_cache_error"] == pytest.approx(sound["median_cache_error"], rel=1e-3)
    # and un-planted again the cell is as sound as it was: a control leaves nothing behind
    assert lines[-2]["wrong"] == 0 and lines[-2]["median_score_error"] == sound["median_score_error"]
