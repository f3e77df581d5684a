"""The ``seq-kanana-2`` configuration's benchmark files: a tiny configuration
and cell are added to a temporary copy as NEW files and entries and rehearsed
on the CPU; the operation counts against hand-worked ones; the benchmark's
copy of the reference against the program's; the new readers on hand-made
runs; the new entries found in ``BENCHMARK.json`` BY NAME and by "contains"
(never by tail or count: a later PR appends behind them); a swapped answer and
each planted control against the check."""

import ast
import inspect
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, reference_kanana, shapes_kanana
from benchmark.readers import _slice, kanana_roofline
from benchmark_testkit import REPO, add_cell, last_line, rehearse

CELL = "seq-kanana-2.serve-sat"
NEW_METRICS = [
    "seq_decode_step_ms", "decode_time_share", "decode_steps_per_batch", "seq_mla_absorbed_ms",
    "seq_decode_launch_host_ms", "decode_step_roofline", "mla_absorbed_roofline", "decode_experts_roofline",
    "mla_expanded_roofline",
]
SAT = [  # PR 23 to 25's fourteen
    "compiles_in_window.sat", "host_hops_ms.sat", "sat_latency_p50_ms", "queue_wait_ms.sat", "batch_size.sat",
    "cache_hit_share.sat", "device_idle_share.sat", "slot_wait_ms.sat", "gc_pause_s_in_window.sat",
    "compile_cache_misses_in_window.sat", "idle_gc_share.sat", "idle_dispatch_share.sat",
    "idle_finish_share.sat", "idle_unnamed_share.sat",
]
JOINED = [
    "seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "seq_program_ms", "seq_experts_ms", "seq_router_ms",
    "seq_mla_ms", "seq_shared_ms", "expert_load_max_over_mean", "generated_items_per_s", "cache_bytes_per_batch",
    "seq_rows_per_program", "seq_programs_per_batch",
]
# (``seq_rows_per_program`` and ``seq_programs_per_batch`` read the counters of the streams of 2,048 and 4,096)
COUNTER_FED = [
    "seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "expert_load_max_over_mean", "generated_items_per_s",
    "cache_bytes_per_batch", "decode_steps_per_batch",
]
TINY_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "qk_head_dim": 24, "head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "n_shared_experts": 2, "vocab_size": 256, "max_position_embeddings": 160,
}
TINY_NUM = 6


def published_config() -> dict:
    return json.loads((REPO / "benchmark" / "configs" / "seq-kanana-2.json").read_text())


def add_tiny_kanana(root):
    """``tiny-kanana`` and ``tiny-kanana.sat`` as new files and entries of the copy."""
    config = published_config()
    config.update(
        TINY_WIDTHS, name="tiny-kanana", n_users=300,
        session_length={"median": 24, "sigma": 0.9, "min": 3, "max": 128},
        server_config={"max_batch_size": 8},
    )
    (root / "benchmark" / "configs" / "tiny-kanana.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny-kanana", "source": "a test's", "file": "benchmark/configs/tiny-kanana.json",
         "reduced": [], "why": "a test's"}
    )
    mix = json.loads((REPO / "benchmark" / "traffic" / "sat.json").read_text())
    mix.update(ramp_s=0.5, connections=4, users_drawn=5000, trace_offset_s=0.2, trace_slice_s=0.5)
    (root / "benchmark" / "traffic" / "tiny-kanana-sat.json").write_text(json.dumps(mix))
    (root / "benchmark" / "cells" / "tiny-kanana.sat.json").write_text(json.dumps({"num": TINY_NUM}))
    add_cell(bench, "tiny-kanana.sat", "tiny-kanana", "tiny-kanana-sat", CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_kanana_cell_rehearses_on_the_cpu(tiny_root, trace):
    add_tiny_kanana(tiny_root)
    seconds = 12  # as the tiny Kimi-Linear cell: unrolled layers beside five busy test workers
    proc = rehearse(tiny_root, "tiny-kanana.sat", trace, seconds)
    line = last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 64
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"answered_qps", "setup_s"}
        assert metrics["answered_qps"]["value"] == pytest.approx(line["attempted"] / seconds)
        assert f"answers of {TINY_NUM} items" in proc.stderr and "at every generated position" in proc.stderr
        assert "latent and rotary key in the cache" in proc.stderr and "router's weights off the reference's" in proc.stderr
        return
    # what the program's counters feed is there; what only a device trace
    # feeds has nothing to read on the CPU and is left out
    assert set(COUNTER_FED) <= set(metrics)
    assert not (set(NEW_METRICS + JOINED) - set(COUNTER_FED)) & set(metrics)
    assert metrics["compiles_in_window.sat"]["value"] == 0
    assert metrics["seq_tokens_per_s"]["value"] > 0
    # every batch is one group of num - 1 steps
    # (a batch is counted when it is staged and its steps when they are launched: the window's edges cut between)
    assert metrics["decode_steps_per_batch"]["value"] == pytest.approx(TINY_NUM - 1, abs=0.25)
    assert metrics["generated_items_per_s"]["value"] > TINY_NUM
    # a batch's cache holds its real tokens and what its steps cached, 3 layers of 40 values in bfloat16 a slot
    assert metrics["cache_bytes_per_batch"]["value"] > 3 * 40 * 2 * 8 * (TINY_NUM - 1)
    assert 1.0 <= metrics["expert_load_max_over_mean"]["value"] <= 8.0


def test_operation_counts_against_hand_worked_ones():
    tiny = {**TINY_WIDTHS, "first_k_dense_replace": 1}
    assert shapes_kanana.layer_counts(tiny) == {"dense": 1, "sparse": 2}
    assert shapes_kanana.latent_width(tiny) == 40 and shapes_kanana.latent_bytes_a_token(tiny) == 80
    # wq 64 x 4 x 24, w_kva 64 x 40, w_kvb 32 x 4 x 32, wo 4 x 16 x 64
    assert shapes_kanana.mla_parts(tiny) == {"wq": 6144, "w_kva": 2560, "w_kvb": 4096, "wo": 4096}
    assert shapes_kanana.mla_weights(tiny) == 16896
    assert shapes_kanana.expert_weights(tiny) == 3 * 64 * 32 == 6144 and shapes_kanana.shared_weights(tiny) == 12288
    assert shapes_kanana.dense_weights(tiny) == 3 * 64 * 96 and shapes_kanana.router_weights(tiny) == 512
    # the prefill, 3 streams of 64: projections and the causal half of products at 24 and 16, 4 heads
    tokens = 3 * 64
    assert shapes_kanana.mla_expanded_flops(3, 64, tiny) == tokens * (2 * 16896 + 64 * 4 * 40) == 8454144
    assert shapes_kanana.mla_latent_flops(tokens, tiny) == tokens * 2 * (2560 + 4096)
    assert shapes_kanana.mla_expanded_bytes(tokens, tiny) == 16896 * 2 + 2 * tokens * 64 * 4 + tokens * 80 == 147456
    # a step, 8 rows over 500 slots: every head's scores over 40 and sums over 32
    assert shapes_kanana.mla_absorbed_flops(8, 500, tiny) == 8 * 2 * 16896 + 2 * 4 * 500 * (40 + 32) == 558336
    assert shapes_kanana.mla_absorbed_bytes(8, 500, tiny) == 16896 * 2 + 500 * 80 + 2 * 8 * 64 * 4 == 77888
    assert shapes_kanana.experts_flops(8, tiny) == 2 * 8 * 3 * 6144 == 294912
    assert shapes_kanana.experts_bytes(8, tiny, 5.0) == 5 * 6144 * 2 + 2 * 8 * 64 * 4 == 65536
    a_sparse = 294912 + 8 * 2 * (12288 + 512)
    assert shapes_kanana.step_flops(8, 500, tiny) == 3 * 558336 + 8 * 2 * 18432 + 2 * a_sparse + 2 * 8 * 64 * 256
    a_sparse = 65536 + (12288 + 512) * 2
    assert shapes_kanana.step_bytes(8, 500, tiny, 5.0) == 3 * 77888 + 18432 * 2 + 2 * a_sparse + 256 * 64 * 2
    # at the published widths: the issue's arithmetic
    published = published_config()
    assert shapes_kanana.layer_counts(published) == {"dense": 1, "sparse": 5}
    assert shapes_kanana.mla_weights(published) == pytest.approx(26.35e6, rel=0.001)
    assert shapes_kanana.expert_weights(published) == pytest.approx(4.72e6, rel=0.001)
    assert shapes_kanana.latent_bytes_a_token(published) == 1152
    sparse = 128 * shapes_kanana.expert_weights(published) + shapes_kanana.shared_weights(published) + shapes_kanana.router_weights(published)
    assert sparse + shapes_kanana.mla_weights(published) == pytest.approx(640.0e6, rel=0.001)
    assert shapes_kanana.dense_weights(published) + shapes_kanana.mla_weights(published) == pytest.approx(64.1e6, rel=0.001)
    # a step of 32 rows over 14,000 slots with 99 of 128 experts reached moves 5.8 GB: 7 ms at 819 GB/s,
    # thirty times what its operations take
    nbytes, flops = shapes_kanana.step_bytes(32, 14000, published, 99.0), shapes_kanana.step_flops(32, 14000, published)
    assert nbytes == pytest.approx(5.8e9, rel=0.02) and nbytes / 819e9 > 25 * flops / 197e12
    # the cache is a sixtieth of it
    assert 6 * 14000 * 1152 == pytest.approx(nbytes / 60, rel=0.05)
    # a prefill's attention block is bound by its operations: 2,048 tokens, 0.13 TFLOP a layer
    assert shapes_kanana.mla_expanded_flops(1, 2048, published) == pytest.approx(2048 * (52.7e6 + 21.0e6), rel=0.01)


def test_the_benchmarks_reference_is_the_programs_function_for_function():
    from predictionio_tpu.models.sequential import kanana_reference

    def functions(module):
        return {
            name: inspect.getsource(f) for name, f in inspect.getmembers(module, inspect.isfunction)
            if f.__module__ == module.__name__
        }

    ours, theirs = functions(reference_kanana), functions(kanana_reference)
    assert ours.keys() == theirs.keys() and len(ours) >= 20
    for name in ours:
        assert ours[name] == theirs[name], name
    assert reference_kanana.ROUTER_EPS == kanana_reference.ROUTER_EPS == 1e-20
    # float32 at `highest`, the expanded form only, and nothing of the program's ops/
    source = inspect.getsource(reference_kanana)
    assert '_HIGHEST = "highest"' in source and "predictionio_tpu" not in source.split('"""', 2)[2]
    assert "jnp.argsort" in source and "top_k" not in source  # the router a plain sort
    assert "w_kvb" in inspect.getsource(reference_kanana.mla_mixer) and "cache" not in source.split('"""', 2)[2].lower()


def test_the_engine_module_imports_the_programs_names_at_its_top():
    # so that a checkout without them (the PR's parent) fails at once
    tree = ast.parse((REPO / "benchmark" / "engines" / "sequential_kanana.py").read_text())
    top = {
        f"{node.module}.{alias.name}" for node in tree.body if isinstance(node, ast.ImportFrom)
        for alias in node.names
    }
    assert "predictionio_tpu.models.sequential.kanana" in top
    assert "predictionio_tpu.models.sequential.engine.KananaModel" in top


def test_the_new_entries_are_found_by_name_behind_the_parents_last():
    # by NAME and by ORDER among names, never by position from the end or by count
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names) <= 128
    start = names.index(NEW_METRICS[0])
    assert names[start : start + len(NEW_METRICS)] == NEW_METRICS
    assert start > names.index("held_whole_path_share")  # behind the parent's last
    cells = [c["name"] for c in bench["workloads"]]
    assert cells.index(CELL) > cells.index("seq-lfm2-moe.serve-sat")
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("seq-kanana-2") > configs.index("seq-lfm2-moe")
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    qps = by_name["answered_qps"]["workloads"]
    assert qps.index(CELL) > qps.index("seq-lfm2-moe.serve-sat")
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][0] == CELL and m["moves"] == "answered_qps"
        assert m["layer"] in ("sequence kernels", "session scorer")
        if name.endswith("_roofline") or name.endswith("_share"):
            assert (m["unit"], m["better"], m["source"]) == ("%", "higher", "device_trace")
        spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
        assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
    for name in SAT + JOINED:
        joined = by_name[name]["workloads"]
        # appended behind what the list held (Kimi-Linear's or SDAR's cell), wherever later cells stand
        before = [joined.index(c) for c in ("seq-kimi-linear.serve-sat", "seq-sdar-moe.serve-sat") if c in joined]
        assert before and joined.index(CELL) > max(before), name
    ours = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(NEW_METRICS + SAT + JOINED) <= ours
    # PR 39's twenty keep the lists a test of theirs pins whole; SDAR's and the others' own scopes are not this program's
    for name in ("loop_idle_share.sat", "launch_queue_programs.sat", "seq_launch_host_ms", "seq_attn_ms",
                 "seq_head_ms", "seq_denoise_pass_ms", "denoise_pass_roofline", "mla_roofline", "absent_copy_share"):
        assert CELL not in by_name[name]["workloads"]
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert cell == {**cell, "config": "seq-kanana-2", "traffic": "sat", "chips": 1}
    assert len(cell["why"]) <= 200 and "steps" in cell["why"] and "bytes" in cell["why"]
    assert json.loads((REPO / "benchmark" / "cells" / f"{CELL}.json").read_text()) == {"num": 32}
    assert harness.load_cell(REPO, CELL)[3]["num"] == 32
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 0
    # a scope metric the cell joined names the prefill's scope as this program has it
    for name, scope in (("seq_mla_ms", "mla"), ("seq_shared_ms", "shared"), ("seq_experts_ms", "experts"), ("seq_router_ms", "router")):
        spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{name}.json").read_text())["args"]
        assert spec["scope"] == scope and scope in kanana_roofline.PREFILL_SCOPES and spec["program"] == "session_vectors"


def test_the_configuration_states_every_published_key_and_the_cut():
    from pathlib import Path

    config = published_config()
    entry = {c["name"]: c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]}["seq-kanana-2"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 6 and config["published"] == {"num_hidden_layers": 48}
    widths = {
        "hidden_size": 2048, "num_attention_heads": 32, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "kv_lora_rank": 512, "n_routed_experts": 128, "moe_intermediate_size": 768,
        "num_experts_per_tok": 6, "scoring_func": "sigmoid", "norm_topk_prob": True, "routed_scaling_factor": 2.448,
        "n_shared_experts": 2, "intermediate_size": 6144, "vocab_size": 128256, "first_k_dense_replace": 1,
    }
    assert {key: config[key] for key in widths} == widths
    assert "eight pipeline stages" in config["deployment"] and "6,912 B" in config["deployment"]
    assert config["generation"] == {"decoding": "greedy", "cache": "latent, bfloat16, 576 a token and layer"}
    assert all(isinstance(line, str) and line for line in config["assumed"].values())
    for key in ("decoding", "cache", "rope", "router", "shared experts", "weights", "items", "num_hidden_layers"):
        assert key in config["assumed"], key
    assert "0.02" in config["assumed"]["weights"] and "1e-20" in config["assumed"]["router"]
    assert "3.79 B" in config["assumed"]["num_hidden_layers"] and "7.58 GB" in config["assumed"]["num_hidden_layers"]
    olmoe = json.loads((REPO / "benchmark" / "configs" / "seq-olmoe.json").read_text())
    for key in ("n_users", "session_length", "structure_seed", "seed_rule", "server_config"):
        assert config[key] == olmoe[key], key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(l) for l in catalog.read_text().splitlines() if '"kanana-2-30b-a3b-instruct-2601"' in l)
        assert entry["source"] == config["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if config[k] != v}
        assert differing == set(config["reduced"])  # no width among them


def test_the_variant_gives_the_algorithm_the_published_keys_and_the_cut():
    from benchmark.engines import sequential_kanana as engine
    from predictionio_tpu.models.sequential import engine_factory, kanana

    variant = engine.variant_of(published_config(), 2600000123)
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    assert (params.n_routed_experts, params.vocab_size, params.num_hidden_layers) == (128, 128256, 6)
    config = params.config()
    assert config.table_rows == 128256 and config.max_session == 4096 and config.sparse_layers == 5
    assert config.stream_shapes() == (2048, 4096) and config.buckets()[-4:] == (512, 1024, 2048, 4096)
    assert config.cache_slots == 32768 and config.cache_bytes(config.cache_slots) == 32768 * 6912
    assert params.seed == 2600000123 % 2**31 and engine.CHECKED_QUERIES == 64
    # the size, reckoned from the built tree's shapes
    total = sum(int(np.prod(shape)) for shape in kanana.weight_shapes(config).values())
    assert total == pytest.approx(3.79e9, rel=2e-3) and 2 * total == pytest.approx(7.58e9, rel=2e-3)
    assert engine.PADDED[1] >= config.max_session + 31 and set(engine.PUBLISHED) <= set(published_config())


COUNTERS = {
    'pio_seq_tokens_total{kind="real"}': (1000.0, 1000.0 + 51 * 700),
    'pio_seq_tokens_total{kind="padded"}': (4096.0, 4096.0 + 51 * 1000),
    'pio_seq_programs_total{bucket="64"}': (2.0, 12.0),
    'pio_seq_programs_total{bucket="128"}': (1.0, 11.0),
    'pio_seq_rows_total{bucket="64"}': (2.0, 12.0),
    'pio_seq_rows_total{bucket="128"}': (1.0, 11.0),
    'pio_seq_sessions_total{bucket="64"}': (5.0, 45.0),
    'pio_seq_sessions_total{bucket="128"}': (5.0, 45.0),
    "pio_seq_stage_seconds_total{}": (0.5, 0.6),
    "pio_seq_batches_total{}": (10.0, 20.0),
    'pio_seq_passes_total{kind="decode"}': (50.0, 100.0),
    "pio_seq_generated_items_total{}": (0.0, 51.0 * 80),
    "pio_seq_cache_bytes_total{}": (0.0, 10 * 24000.0),
    "pio_moe_expert_tokens_max_total{}": (100.0, 400.0),
    "pio_moe_expert_tokens_mean_total{}": (50.0, 250.0),
    "pio_moe_experts_reached_total{}": (0.0, 600.0),
    "pio_moe_experts_offered_total{}": (0.0, 800.0),
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
    assert harness.read_metric(REPO, True, "decode_steps_per_batch", run) == pytest.approx(5.0)
    assert harness.read_metric(REPO, True, "generated_items_per_s", run) == pytest.approx(80.0)
    assert harness.read_metric(REPO, True, "cache_bytes_per_batch", run) == pytest.approx(24000.0)
    assert harness.read_metric(REPO, True, "pad_token_share", run) == pytest.approx(30.0)
    # a program without the counters, the scopes or the trace (the parent, the CPU): every new metric is left out
    bare = harness.Run(0.0, 51.0, 1, 0, True)
    assert all(harness.read_metric(REPO, True, name, bare) is None for name in NEW_METRICS)
    # SDAR's and Kimi-Linear's runs (their shapes and counters): the reader finds nothing of its own
    for shapes in ({"moe_intermediate_size": 768, "generation": {}}, {"linear_attn_config": {}, "kv_lora_rank": 512}):
        other = harness.Run(0.0, 51.0, 1, 0, True, shapes=shapes, peak={}, trace=object())
        assert all(kanana_roofline.read(other, kernel) is None for kernel in kanana_roofline.KERNELS)
    # ... nor does SDAR's reader in this cell's run
    from benchmark.readers import sdar_roofline

    ours = hand_made_run(shapes={**TINY_WIDTHS, "rope_interleave": True}, peak={}, trace=object())
    assert all(sdar_roofline.read(ours, kernel) is None for kernel in sdar_roofline.KERNELS)


def test_the_roofline_shares_read_a_hand_made_slice(monkeypatch):
    def op(start, end, program, scope, inner):
        return (start, end, f"%f = f32[] fusion() {scope}", frozenset({f"jit({program})/{scope}/{inner}/x"}))

    # in the slice two prefills (their attention blocks 6 ms, the cache's writes 1) and ten steps (their
    # attention 5 ms, 1 of them the cache's scatter under its own scope, their experts 10)
    ops = [
        op(0.0, 6e6, "session_vectors", "mla", "expand"), op(6e6, 7e6, "session_vectors", "cache", "update"),
        op(7e6, 8e6, "session_vectors", "shared", "dot"), op(8e6, 9e6, "session_vectors", "experts", "gmm"),
        op(10e6, 14e6, "decode_step", "mla_absorbed", "attn"), op(14e6, 15e6, "decode_step", "cache", "scatter"),
        op(15e6, 25e6, "decode_step", "experts", "gmm"),
    ]
    profile = _slice.SliceProfile(0.0, 1e9, [("pio:seq.decode", 0.0, 4e6), ("pio:seq.decode", 5e6, 7e6)], ops)
    monkeypatch.setattr(_slice, "load", lambda run: profile)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    config = {**TINY_WIDTHS, "first_k_dense_replace": 1, "rope_interleave": True}
    programs = {"jit_session_vectors": {"count": 2, "seconds": 0.010}, "jit_decode_step": {"count": 10, "seconds": 0.030}}
    run = hand_made_run(trace=types.SimpleNamespace(programs=programs), shapes=config, peak=peak)

    def least(flops, nbytes):
        return max(flops / 197e12, nbytes / 819e9)

    # the window's mean stream: ten of 64 and ten of 128 tokens, a row each
    tokens = (10 * 64 + 10 * 128) / 20
    flops = 2 * (shapes_kanana.mla_expanded_flops(10, 64, config) + shapes_kanana.mla_expanded_flops(10, 128, config)) / 20
    flops += shapes_kanana.mla_latent_flops(tokens, config)
    want = least(flops, 3 * shapes_kanana.mla_expanded_bytes(tokens, config))
    assert kanana_roofline.read(run, "mla_expanded") == pytest.approx(100 * want / 3e-3)
    # the mean step: 8 sessions a batch over its 3,570 real tokens and the 3 positions a session of its mean step,
    # 6 of a layer's 8 experts reached
    rows, slots, reached = 8.0, 3570 + 8 * 3, 6.0
    want = least(3 * shapes_kanana.mla_absorbed_flops(rows, slots, config), 3 * shapes_kanana.mla_absorbed_bytes(rows, slots, config))
    assert harness.read_metric(REPO, True, "mla_absorbed_roofline", run) == pytest.approx(100 * want / 0.4e-3)
    want = least(2 * shapes_kanana.experts_flops(rows, config), 2 * shapes_kanana.experts_bytes(rows, config, reached))
    assert harness.read_metric(REPO, True, "decode_experts_roofline", run) == pytest.approx(100 * want / 1e-3)
    want = least(shapes_kanana.step_flops(rows, slots, config), shapes_kanana.step_bytes(rows, slots, config, reached))
    assert harness.read_metric(REPO, True, "decode_step_roofline", run) == pytest.approx(100 * want / 3e-3)
    assert harness.read_metric(REPO, True, "seq_decode_step_ms", run) == pytest.approx(3.0)
    assert harness.read_metric(REPO, True, "seq_mla_absorbed_ms", run) == pytest.approx(0.4)
    assert harness.read_metric(REPO, True, "decode_time_share", run) == pytest.approx(75.0)
    assert harness.read_metric(REPO, True, "seq_decode_launch_host_ms", run) == pytest.approx(3.0)
    # the accepted readers the cell joined find this program's scopes too
    assert harness.read_metric(REPO, True, "seq_mla_ms", run) == pytest.approx(3.0)
    assert harness.read_metric(REPO, True, "seq_shared_ms", run) == pytest.approx(0.5)
    assert harness.read_metric(REPO, True, "seq_experts_ms", run) == pytest.approx(0.5)
    assert harness.read_metric(REPO, True, "seq_program_ms", run) == pytest.approx(5.0)
    # no trace (an untraced run, the CPU): nothing to read
    assert kanana_roofline.read(hand_made_run(shapes=config, peak=peak), "decode_step") is None


_served: dict = {}  # a tiny served model and its checks: several tests ask


def served():
    """``(engine, tiny)`` at the tiny widths, float32 weights; ``tiny["verdict"]()``
    answers the six sessions through the served program (under whatever is
    planted at that time) and runs the check's parts on the replies."""
    import jax
    import jax.numpy as jnp

    from benchmark.engines import sequential_kanana as engine
    from predictionio_tpu.models.sequential import Query, engine_factory
    from predictionio_tpu.models.sequential.engine import session_tails

    if not _served:
        config = {**published_config(), **TINY_WIDTHS}
        variant = engine_factory().engine_params_from_variant(engine.variant_of(config, 4))
        params = variant.algorithms[0][1]
        algorithm = engine_factory().make_components(variant)[2][0]
        rng = np.random.default_rng(8)
        sessions = [rng.choice(200, n, replace=False).astype(np.int32) for n in (5, 40, 64, 70, 90, 120)]
        model = engine.KananaModel(
            params.config(), [f"i{i}" for i in range(200)], [f"u{i}" for i in range(6)],
            *session_tails(sessions, 128), engine.kanana.init_weights(params.config(), 4, jnp.float32),
        )
        shapes = {key: config[key] for key in engine.PUBLISHED}

        def verdict(swap=None):
            cache = jax.config.jax_enable_compilation_cache
            try:
                answers = algorithm.predict_batch(model, [Query(user=f"u{i}", num=TINY_NUM) for i in range(6)])
                if swap:
                    answers[swap[0]], answers[swap[1]] = answers[swap[1]], answers[swap[0]]
                replies = [([int(s.item[1:]) for s in a.item_scores], [s.score for s in a.item_scores]) for a in answers]
                ids_ok = [len(items) == TINY_NUM and engine.trajectory_ok(s, items, 200) for s, (items, _) in zip(sessions, replies)]
                jobs = [(np.concatenate([s, items[:-1]]).astype(np.int64), len(s) - 1) for s, (items, _) in zip(sessions, replies)]
                rows, ties, router_errors = engine.reference_rows(model.weights, shapes, jobs, TINY_NUM)
                errors = [
                    engine.answer_off(*engine.check_answer(r, s, items, scores, shapes, 200)) if ok else float("inf")
                    for r, s, (items, scores), ok in zip(rows, sessions, replies, ids_ok)
                ]
                kept_off = engine.cache_errors(algorithm, model, shapes, list(range(6)))
                return errors, ids_ok, router_errors, kept_off, replies
            finally:
                jax.config.update("jax_enable_compilation_cache", cache)

        _served.update(model=model, sessions=sessions, verdict=verdict)
        _served["as configured"] = verdict()
    return engine, _served


def test_a_swapped_answer_fails_the_check_that_the_servers_own_passes():
    engine, tiny = served()
    errors, ids_ok, router_errors, kept_off, _ = tiny["as configured"]
    # float32 against float32 here: the order of the sums
    assert all(ids_ok) and max(errors) < 1e-3 and max(router_errors) < 1e-5 and max(kept_off) < 1e-5
    assert engine.count_wrong(errors, ids_ok, router_errors, kept_off) == 0
    # two users get each other's answer: an item of the session, or choices the reference would not make
    errors, ids_ok, router_errors, kept_off, _ = tiny["verdict"](swap=(1, 2))
    assert min(errors[1:3]) > 10 * engine.SCORE_TOLERANCE
    assert engine.count_wrong(errors, ids_ok, router_errors, kept_off) >= 2


CONTROLS = ["cache_fp8", "stale_slots", "step_key_unturned", "experts_5", "no_bias", "latent_unnormalised"]


@pytest.mark.parametrize("control", CONTROLS)
def test_a_planted_control_shows_where_it_has_to(control, monkeypatch):
    """Each control of ``controls_kanana.py`` planted in the tiny program: the
    cache's probe meets what is written into it, the router's probe the
    router's faults, the replayed answers everything that moves a logit (the
    chip's readings and the limits they pass are PERF.md's)."""
    from benchmark import controls_kanana
    from predictionio_tpu.models.sequential import kanana
    from predictionio_tpu.ops import moe

    engine, tiny = served()
    sound_errors, _, sound_router, sound_cache, sound_replies = tiny["as configured"]
    for module, name in controls_kanana.PATCHED:
        target = {"kanana": kanana, "moe": moe}[module]
        monkeypatch.setattr(target, name, getattr(target, name))  # put back when the test ends
    programs = (kanana.session_vectors, kanana.first_pick, kanana.decode_step)
    controls_kanana.CONTROLS[control](kanana, moe, tiny["model"].config)
    for program in programs:
        program.clear_cache()
    try:
        errors, ids_ok, router_errors, kept_off, replies = tiny["verdict"]()
    finally:
        monkeypatch.undo()
        for program in programs:
            program.clear_cache()
    wrong = engine.count_wrong(errors, ids_ok, router_errors, kept_off)
    assert all(ids_ok)  # a fault moves logits: what is answered is still a trajectory the mask allows
    if control == "cache_fp8":
        # three mantissa bits: 2**-4 a value at the worst, 0.026 in the norm
        assert 0.015 < np.median(kept_off) < 0.04 and np.median(kept_off) > 3 * engine.CACHE_TOLERANCE
        assert max(router_errors) < 1e-5 and wrong == 6
    elif control == "latent_unnormalised":
        assert np.median(kept_off) > 10 * engine.CACHE_TOLERANCE and np.median(errors) > engine.SCORE_TOLERANCE
        assert wrong == 6
    elif control in ("experts_5", "no_bias"):
        assert np.allclose(kept_off, sound_cache, atol=1e-6) and max(router_errors) > 10 * engine.ROUTER_TOLERANCE and wrong >= 3
        assert np.median(errors) > 10 * max(sound_errors)
    else:
        # the prefill is sound (the cache's probe and the first item are what they were): the steps are not
        assert np.allclose(kept_off, sound_cache, atol=1e-6) and max(router_errors) < 1e-5
        assert [items[0] for items, _ in replies] == [items[0] for items, _ in sound_replies]
        assert np.median(errors) > 100 * max(sound_errors) and np.median(errors) > engine.SCORE_TOLERANCE / 5


def test_the_controls_script_deploys_the_cell_and_has_the_check_refuse_what_is_planted(tiny_root):
    add_tiny_kanana(tiny_root)
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); from benchmark import controls_kanana as c; "
        "sys.exit(0 if c.run(sys.argv[1], 5, [None, 'cache_fp8', 'no_bias'], 'cpu', 'tiny-kanana.sat') else 1)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tiny_root)], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [line.get("control") for line in lines] == ["as configured", "cache_fp8", "no_bias", None]
    assert lines[-1] == {"ok": True}
    sound, fp8, no_bias = lines[:3]
    assert sound["wrong"] == 0 and sound["checked"] >= 32
    assert fp8["wrong"] >= fp8["checked"] // 2 and fp8["median_cache_error"] > 5 * sound["median_cache_error"]
    assert no_bias["wrong"] >= 1 and no_bias["largest_router_error"] > 0.01 > sound["largest_router_error"]
    # the replies are the PLANTED program's: the served scores moved with it
    assert len({line["median_score_error"] for line in lines[:3]}) == 3


def test_the_check_holds_the_median_answer_and_the_probes():
    from benchmark.engines import sequential_kanana as engine

    tight = engine.SCORE_TOLERANCE
    fine = [tight / 2] * 62 + [3 * tight, 10 * tight]  # bf16 everywhere, two answers behind a tipped router
    assert engine.count_wrong(fine, [True] * 64) == 0
    assert engine.count_wrong(fine, [True] * 63 + [False]) == 1  # no trajectory the mask allows
    assert engine.count_wrong(fine[:-1] + [float("inf")], [True] * 64) == 1  # off by no number
    assert engine.count_wrong(fine[:-1] + [float("nan")], [True] * 64) == 1
    # another arithmetic than the configuration states: the median is off
    assert engine.count_wrong([2 * tight] * 64, [True] * 64) == 64
    # the probes: the router's every session's own, the cache's on the median session
    router, cache = engine.ROUTER_TOLERANCE, engine.CACHE_TOLERANCE
    assert engine.count_wrong(fine, [True] * 64, [router / 2] * 64, [cache / 2] * 64) == 0
    assert engine.count_wrong(fine, [True] * 64, [router / 2] * 63 + [2 * router], [cache / 2] * 64) == 1
    assert engine.count_wrong(fine, [True] * 64, [0.0] * 64, [cache / 2] * 60 + [2 * cache] * 4) == 0
    assert engine.count_wrong(fine, [True] * 64, [0.0] * 64, [3 * cache] * 64) == 64
    # an answer is off by the MEDIAN of its positions: one behind a tipped router does not make it
    assert engine.answer_off(np.asarray([0.0, 0.5, 0.01]), np.asarray([0.02, 0.3, 0.01])) == 0.02
    assert engine.trajectory_ok([1, 2], [3, 4], 10) and not engine.trajectory_ok([1, 2], [3, 3], 10)
    assert not engine.trajectory_ok([1, 2], [2, 4], 10) and not engine.trajectory_ok([1, 2], [3, 10], 10)
