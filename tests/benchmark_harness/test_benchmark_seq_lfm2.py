"""The ``seq-lfm2-moe`` configuration's benchmark files: a tiny configuration
and cell are added to a temporary copy as NEW files and entries and rehearsed
on the CPU; the operation counts against hand-worked ones; the benchmark's
copy of the reference against the program's; the new readers on hand-made
runs; the new entries found in ``BENCHMARK.json`` BY NAME (never by tail or
count: a later PR appends behind them); another session's answer and each
planted control against the check."""

import ast
import inspect
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, reference_lfm2, shapes_lfm2
from benchmark.readers import _slice, lfm2_roofline
from benchmark_testkit import REPO, add_cell, last_line, rehearse

CELL = "seq-lfm2-moe.serve-sat"
NEW_METRICS = [
    "seq_conv_ms", "seq_attn64_ms", "seq_dense_ms", "conv_roofline", "attn64_roofline",
    "experts_held8_roofline", "mixer_time_share",
]
SAT = [  # PR 23 to 25's fourteen
    "compiles_in_window.sat", "host_hops_ms.sat", "sat_latency_p50_ms", "queue_wait_ms.sat", "batch_size.sat",
    "cache_hit_share.sat", "device_idle_share.sat", "slot_wait_ms.sat", "gc_pause_s_in_window.sat",
    "compile_cache_misses_in_window.sat", "idle_gc_share.sat", "idle_dispatch_share.sat",
    "idle_finish_share.sat", "idle_unnamed_share.sat",
]
JOINED = [
    "seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "seq_program_ms", "seq_experts_ms",
    "seq_router_ms", "seq_head_ms", "expert_load_max_over_mean", "absent_copy_share",
    "seq_rows_per_program", "seq_programs_per_batch",
]
# (``seq_rows_per_program`` and ``seq_programs_per_batch`` read the counters of the streams of 2,048 and 4,096)
COUNTER_FED = ["seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "expert_load_max_over_mean", "absent_copy_share"]
TINY_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "full_attention", "conv"], "conv_L_cache": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_dense_layers": 1,
    "num_experts": 4, "num_experts_per_tok": 4, "vocab_size": 128, "max_position_embeddings": 128,
    "experts_held": [4, 4], "published": {"num_experts": 16},
}


def published_config() -> dict:
    return json.loads((REPO / "benchmark" / "configs" / "seq-lfm2-moe.json").read_text())


def add_tiny_lfm2(root):
    """``tiny-lfm2`` and ``tiny-lfm2.sat`` as new files and entries of the copy."""
    config = published_config()
    config.update(
        TINY_WIDTHS, name="tiny-lfm2", n_users=300,
        session_length={"median": 24, "sigma": 0.9, "min": 3, "max": 128},
        server_config={"max_batch_size": 8},
    )
    (root / "benchmark" / "configs" / "tiny-lfm2.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny-lfm2", "source": "a test's", "file": "benchmark/configs/tiny-lfm2.json",
         "reduced": [], "why": "a test's"}
    )
    mix = json.loads((REPO / "benchmark" / "traffic" / "sat.json").read_text())
    mix.update(ramp_s=0.5, connections=4, users_drawn=5000, trace_offset_s=0.2, trace_slice_s=0.5)
    (root / "benchmark" / "traffic" / "tiny-lfm2-sat.json").write_text(json.dumps(mix))
    add_cell(bench, "tiny-lfm2.sat", "tiny-lfm2", "tiny-lfm2-sat", CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_lfm2_cell_rehearses_on_the_cpu(tiny_root, trace):
    add_tiny_lfm2(tiny_root)
    seconds = 12  # as the tiny Kimi-Linear cell: unrolled layers beside five busy test workers
    proc = rehearse(tiny_root, "tiny-lfm2.sat", trace, seconds)
    line = last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 64
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"answered_qps", "setup_s"}
        assert metrics["answered_qps"]["value"] == pytest.approx(line["attempted"] / seconds)
        assert "checked" in proc.stderr and "worst |served - reference| by answer: median" in proc.stderr
        assert "gated convolution on the reference's projection" in proc.stderr
        return
    # what the program's counters feed is there; what only a device trace
    # feeds has nothing to read on the CPU and is left out
    assert set(COUNTER_FED) <= set(metrics)
    assert not (set(NEW_METRICS + JOINED) - set(COUNTER_FED)) & set(metrics)
    assert metrics["compiles_in_window.sat"]["value"] == 0
    assert metrics["seq_tokens_per_s"]["value"] > 0
    assert 0 < metrics["pad_token_share"]["value"] < 100
    # 4 of 16 experts held: three quarters of the copies are another chip's
    assert 65 < metrics["absent_copy_share"]["value"] < 85
    assert 1.0 <= metrics["expert_load_max_over_mean"]["value"] <= 8.0


def test_operation_counts_against_hand_worked_ones():
    c = TINY_WIDTHS
    assert shapes_lfm2.layer_counts(c) == {"conv": 4, "attn": 2, "dense": 1, "sparse": 5}
    # conv: in_proj 64 x 192, three taps of 64, out_proj 64 x 64
    assert shapes_lfm2.conv_weights(c) == 64 * 192 + 3 * 64 + 64 * 64 == 16576
    assert shapes_lfm2.conv_flops(100, c) == 100 * 2 * 16576 == 3315200
    assert shapes_lfm2.conv_bytes(100, c) == 16576 * 2 + 2 * 100 * 64 * 4 == 84352
    # attention: q and o at 4 heads of 16, k and v at 2
    assert shapes_lfm2.head_dim(c) == 16
    assert shapes_lfm2.attn_weights(c) == 2 * 64 * 64 + 2 * 64 * 32 == 12288
    # 3 streams of 64: the causal half of q.k and p.v at 16, 4 heads
    tokens = 3 * 64
    assert shapes_lfm2.attn_flops(3, 64, c) == tokens * (2 * 12288 + 4 * 64 * (16 + 16)) == 6291456
    assert shapes_lfm2.attn_bytes(tokens, c) == 12288 * 2 + 2 * tokens * 64 * 4 == 122880
    # experts: 4 of 16 held, 4 copies a token: one copy a token lands here
    assert shapes_lfm2.held_copies(100, c) == 100
    assert shapes_lfm2.experts_held_flops(100, c) == 2 * 3 * 100 * 64 * 32 == 1228800
    assert shapes_lfm2.experts_held_bytes(100, c) == 4 * 3 * 64 * 32 * 2 + 2 * 100 * 64 * 4 == 100352
    # at the published widths: the issue's arithmetic
    published = published_config()
    assert shapes_lfm2.layer_counts(published) == {"conv": 18, "attn": 6, "dense": 2, "sparse": 22}
    assert shapes_lfm2.conv_weights(published) == pytest.approx(16.78e6, rel=0.001)
    assert shapes_lfm2.attn_weights(published) == pytest.approx(10.49e6, rel=0.001)
    assert shapes_lfm2.conv_flops(1, published) == pytest.approx(33.6e6, rel=0.002)
    # a token of a 2,048-token stream: 21 M of projections and 8.4 M of products a layer
    assert shapes_lfm2.attn_flops(1, 2048, published) / 2048 == pytest.approx(21.0e6 + 8.4e6, rel=0.005)
    assert shapes_lfm2.held_copies(2048, published) == 2048 * 4 * 8 / 32 == 2048
    # one copy a token through an expert of 3 x 2,048 x 1,792: 22 MFLOP a token and layer
    assert shapes_lfm2.experts_held_flops(1, published) == pytest.approx(22.0e6, rel=0.002)
    # a 2,048-token program's held experts move 0.21 GB and are bound by their operations all the same
    flops, nbytes = shapes_lfm2.experts_held_flops(2048, published), shapes_lfm2.experts_held_bytes(2048, published)
    assert nbytes == pytest.approx(0.21e9, rel=0.01) and flops / 197e12 == pytest.approx(nbytes / 819e9, rel=0.15)


def test_the_benchmarks_reference_is_the_programs_function_for_function():
    from predictionio_tpu.models.sequential import lfm2_reference

    def functions(module):
        return {
            name: inspect.getsource(f) for name, f in inspect.getmembers(module, inspect.isfunction)
            if f.__module__ == module.__name__
        }

    ours, theirs = functions(reference_lfm2), functions(lfm2_reference)
    assert ours.keys() == theirs.keys() and len(ours) >= 20
    for name in ours:
        assert ours[name] == theirs[name], name
    assert reference_lfm2.ROUTER_EPS == lfm2_reference.ROUTER_EPS == 1e-6
    # float32 at `highest`, and nothing of the program's ops/
    source = inspect.getsource(reference_lfm2)
    assert '_HIGHEST = "highest"' in source and "predictionio_tpu" not in source.split('"""', 2)[2]
    assert "jnp.repeat(k, heads // kv, axis=1)" in source  # keys and values repeated per query head
    assert "for j in range(taps)" in source and "silu" not in inspect.getsource(reference_lfm2.short_conv)


def test_the_engine_module_imports_the_programs_names_at_its_top():
    # so that a checkout without them (the PR's parent) fails at once
    tree = ast.parse((REPO / "benchmark" / "engines" / "sequential_lfm2.py").read_text())
    top = {
        f"{node.module}.{alias.name}" for node in tree.body if isinstance(node, ast.ImportFrom)
        for alias in node.names
    }
    assert "predictionio_tpu.models.sequential.lfm2" in top
    assert "predictionio_tpu.models.sequential.engine.Lfm2Model" in top


def test_the_new_entries_are_found_by_name_behind_the_parents_last():
    # by NAME and by ORDER among names, not by position from the end or by count
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names) <= 128
    start = names.index(NEW_METRICS[0])
    assert names[start : start + len(NEW_METRICS)] == NEW_METRICS
    assert start > names.index("seq_denoise_launch_host_ms")  # behind the parent's last
    cells = [c["name"] for c in bench["workloads"]]
    assert cells.index(CELL) > cells.index("seq-sdar-moe.serve-sat")
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("seq-lfm2-moe") > configs.index("seq-sdar-moe")
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    qps = by_name["answered_qps"]["workloads"]
    assert qps.index(CELL) > qps.index("seq-sdar-moe.serve-sat")
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][0] == CELL and m["moves"] == "answered_qps" and m["layer"] == "sequence kernels"
        assert m["source"] == "device_trace"
        assert (m["unit"], m["better"]) == (("%", "higher") if "roofline" in name or "share" in name else ("ms", "lower"))
        spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
        assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
    for name in SAT + JOINED:
        joined = by_name[name]["workloads"]
        assert joined.index(CELL) > joined.index("seq-kimi-linear.serve-sat"), name
    ours = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert ours == set(NEW_METRICS + SAT + JOINED)
    # PR 39's twenty keep the lists a test of theirs pins whole
    for name in ("loop_idle_share.sat", "launch_queue_programs.sat", "seq_launch_host_ms"):
        assert CELL not in by_name[name]["workloads"]
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert cell == {**cell, "config": "seq-lfm2-moe", "traffic": "sat", "chips": 1}
    assert len(cell["why"]) <= 200 and not (REPO / "benchmark" / "cells" / f"{CELL}.json").exists()


def test_the_configuration_states_every_published_key_and_the_cut():
    from pathlib import Path

    config = published_config()
    entry = {c["name"]: c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]}["seq-lfm2-moe"]
    assert entry["reduced"] == config["reduced"] == ["num_experts"]
    assert config["num_experts"] == 8 and config["published"] == {"num_experts": 32}
    assert config["experts_held"] == [0, 8] and config["num_hidden_layers"] == 24 == len(config["layer_types"])
    assert [i for i, kind in enumerate(config["layer_types"]) if kind == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert "four-chip v5e host" in config["deployment"] and "8 a chip" in config["deployment"]
    assert all(isinstance(line, str) and line for line in config["assumed"].values())
    for key in ("tied head", "embedding_norm", "q_layernorm, k_layernorm", "router", "expert_bias", "weights"):
        assert key in config["assumed"], key
    assert "1e-6" in config["assumed"]["router"] and "0.02" in config["assumed"]["expert_bias"]
    olmoe = json.loads((REPO / "benchmark" / "configs" / "seq-olmoe.json").read_text())
    for key in ("n_users", "session_length", "structure_seed", "seed_rule", "server_config"):
        assert config[key] == olmoe[key], key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(l) for l in catalog.read_text().splitlines() if '"LFM2-8B-A1B"' in l)
        assert entry["source"] == config["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if config[k] != v}
        assert differing == set(config["reduced"])  # no width among them


def test_the_variant_gives_the_algorithm_the_published_count_and_the_share():
    from benchmark.engines import sequential_lfm2 as engine
    from predictionio_tpu.models.sequential import engine_factory

    variant = engine.variant_of(published_config(), 2600000123)
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    assert (params.num_experts, params.vocab_size, params.num_hidden_layers) == (32, 65536, 24)
    config = params.config()
    assert config.experts_held == (0, 8) and config.table_rows == 65536 and config.max_session == 4096
    assert config.sparse_layers == 22 and sum(config.is_conv(i) for i in range(24)) == 18
    assert config.stream_shapes() == (2048, 4096) and config.buckets()[-4:] == (512, 1024, 2048, 4096)
    assert params.seed == 2600000123 % 2**31 and engine.CHECKED_QUERIES == 64


COUNTERS = {
    'pio_seq_tokens_total{kind="real"}': (1000.0, 1000.0 + 51 * 700),
    'pio_seq_tokens_total{kind="padded"}': (4096.0, 4096.0 + 51 * 1000),
    'pio_seq_programs_total{bucket="64"}': (2.0, 12.0),
    'pio_seq_programs_total{bucket="128"}': (1.0, 11.0),
    'pio_seq_rows_total{bucket="64"}': (64.0, 64.0 + 320),
    'pio_seq_rows_total{bucket="128"}': (16.0, 16.0 + 240),
    "pio_seq_stage_seconds_total{}": (0.5, 0.6),
    "pio_seq_batches_total{}": (10.0, 20.0),
    "pio_moe_expert_tokens_max_total{}": (100.0, 400.0),
    "pio_moe_expert_tokens_mean_total{}": (50.0, 250.0),
    'pio_moe_copies_total{where="held"}': (10.0, 260.0),
    'pio_moe_copies_total{where="absent"}': (30.0, 780.0),
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
    assert harness.read_metric(REPO, True, "absent_copy_share", run) == pytest.approx(75.0)
    assert harness.read_metric(REPO, True, "pad_token_share", run) == pytest.approx(30.0)
    assert harness.read_metric(REPO, True, "expert_load_max_over_mean", run) == pytest.approx(1.5)
    # a program without the scopes or the trace (the parent, the CPU): every new metric is left out
    bare = harness.Run(0.0, 51.0, 1, 0, True)
    assert all(harness.read_metric(REPO, True, name, bare) is None for name in NEW_METRICS)
    # Kimi-Linear's run (its shapes): the readers find nothing of theirs
    kimi = harness.Run(0.0, 51.0, 1, 0, True, shapes={"linear_attn_config": {}}, peak={}, trace=object())
    assert all(lfm2_roofline.read(kimi, kernel) is None for kernel in lfm2_roofline.KERNELS)
    assert lfm2_roofline.read(kimi, share_of=["conv", "attn"]) is None


def test_the_roofline_shares_read_a_hand_made_slice(monkeypatch):
    def op(start, end, scope, inner):
        return (start, end, f"%f = f32[] fusion() {scope}", frozenset({f"jit(session_vectors)/{scope}/{inner}/x"}))

    # two executions of the program in the slice: the convolutions 6 ms (their
    # in_proj 4 of them), attention 1 ms, the dense layers 0.5, the held experts 2
    ops = [op(0.0, 8e6, "conv", "in_proj"), op(8e6, 12e6, "conv", "taps"), op(12e6, 14e6, "attn", "rope"),
           op(14e6, 15e6, "dense", "dot"), op(15e6, 19e6, "experts", "gmm")]
    profile = _slice.SliceProfile(0.0, 1e9, [], ops)
    monkeypatch.setattr(_slice, "load", lambda run: profile)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = hand_made_run(
        trace=types.SimpleNamespace(programs={"jit_session_vectors": {"count": 2, "seconds": 0.020}}),
        shapes=TINY_WIDTHS, peak=peak,
    )
    tokens = (320 * 64 + 240 * 128) / 20  # the window's mean program
    counts = shapes_lfm2.layer_counts(TINY_WIDTHS)

    def least(flops, nbytes, layers):
        return max(layers * flops / 197e12, layers * nbytes / 819e9)

    want = least(shapes_lfm2.conv_flops(tokens, TINY_WIDTHS), shapes_lfm2.conv_bytes(tokens, TINY_WIDTHS), counts["conv"])
    assert lfm2_roofline.read(run, "conv") == pytest.approx(100 * want / 6e-3)
    flops = (shapes_lfm2.attn_flops(320, 64, TINY_WIDTHS) + shapes_lfm2.attn_flops(240, 128, TINY_WIDTHS)) / 20
    want = least(flops, shapes_lfm2.attn_bytes(tokens, TINY_WIDTHS), counts["attn"])
    assert harness.read_metric(REPO, True, "attn64_roofline", run) == pytest.approx(100 * want / 1e-3)
    want = least(
        shapes_lfm2.experts_held_flops(tokens, TINY_WIDTHS), shapes_lfm2.experts_held_bytes(tokens, TINY_WIDTHS),
        counts["sparse"],
    )
    assert harness.read_metric(REPO, True, "experts_held8_roofline", run) == pytest.approx(100 * want / 2e-3)
    assert harness.read_metric(REPO, True, "seq_conv_ms", run) == pytest.approx(6.0)
    assert harness.read_metric(REPO, True, "seq_attn64_ms", run) == pytest.approx(1.0)
    assert harness.read_metric(REPO, True, "seq_dense_ms", run) == pytest.approx(0.5)
    # the mixers' 7 ms of the program's 10
    assert harness.read_metric(REPO, True, "mixer_time_share", run) == pytest.approx(70.0)
    # the accepted readers the cell joined find this program's scopes too
    assert harness.read_metric(REPO, True, "seq_experts_ms", run) == pytest.approx(2.0)
    assert harness.read_metric(REPO, True, "seq_program_ms", run) == pytest.approx(10.0)
    # no trace (an untraced run, the CPU): nothing to read
    assert lfm2_roofline.read(hand_made_run(shapes=TINY_WIDTHS, peak=peak), "conv") is None
    # a slice that shows no attention (a trace of another program's scopes): the share is left out, never half
    monkeypatch.setattr(_slice, "load", lambda run: _slice.SliceProfile(0.0, 1e9, [], ops[:2] + ops[3:]))
    assert harness.read_metric(REPO, True, "mixer_time_share", run) is None


_served: dict = {}  # a tiny served model, its answers and its reference: several tests ask


def served():
    """``(engine, tiny)`` at the tiny widths; ``tiny["reference_of"]()`` runs
    the check's reference (and its probes) on the model's own weights, under
    whatever is planted at that time, and ``tiny["answers_of"]()`` the served
    program."""
    import jax

    from benchmark.engines import sequential_lfm2 as engine
    from predictionio_tpu.models.sequential import Query, engine_factory
    from predictionio_tpu.models.sequential.engine import session_tails

    if not _served:
        config = {**published_config(), **TINY_WIDTHS}
        variant = engine_factory().engine_params_from_variant(engine.variant_of(config, 4))
        params = variant.algorithms[0][1]
        algorithm = engine_factory().make_components(variant)[2][0]
        rng = np.random.default_rng(8)
        sessions = [rng.integers(0, 128, n).astype(np.int32) for n in (5, 40, 64, 70, 90, 128)]
        model = engine.Lfm2Model(
            params.config(), [f"i{i}" for i in range(128)], [f"u{i}" for i in range(6)],
            *session_tails(sessions, 128), engine.lfm2.init_weights(params.config(), 4),
        )
        shapes = {key: config[key] for key in engine.PUBLISHED + ("experts_held", "published")}

        def answers_of():
            return algorithm.predict_batch(model, [Query(user=f"u{i}", num=10) for i in range(6)])

        def reference_of(lengths=None):
            cache = jax.config.jax_enable_compilation_cache
            try:
                return engine.reference_logits(model.weights, shapes, sessions, lengths)
            finally:
                jax.config.update("jax_enable_compilation_cache", cache)

        _served.update(
            model=model, sessions=sessions, answers=answers_of(), answers_of=answers_of,
            reference_of=reference_of, shapes=shapes,
        )
        _served["as configured"] = reference_of()
    return engine, _served


def verdicts(engine, logits, sessions, answers):
    checked = [
        engine.check_answer(
            ref, session, [int(s.item[1:]) for s in answer.item_scores], [s.score for s in answer.item_scores], 128,
        )
        for ref, session, answer in zip(logits, sessions, answers)
    ]
    return [ok for ok, _, _ in checked], [error for _, _, error in checked]


def test_another_sessions_answer_fails_the_check_that_the_servers_own_passes():
    engine, tiny = served()
    sessions, answers = tiny["sessions"], tiny["answers"]
    logits, tie_share, gate_errors, router_errors = tiny["as configured"]
    assert 0 <= tie_share < 0.2
    # float32 against float32 here: the probes read the order of the sums
    assert max(gate_errors) < engine.GATE_TOLERANCE / 10 and max(router_errors) < engine.ROUTER_TOLERANCE / 100
    ids_ok, errors = verdicts(engine, logits, sessions, answers)
    # a bf16 tree at a tiny size: a tipped router moves an answer by more than
    # at the published widths; the ids hold and nothing is off by the logits' order
    assert all(ids_ok) and max(errors) < 1.0
    assert engine.count_wrong(errors, ids_ok, gate_errors, router_errors) in (0, sum(e > engine.SCORE_TOLERANCE for e in errors))
    # the gross fault FLIP_TOLERANCE is there for: two users get each other's answer
    swapped = [answers[1], answers[0]] + answers[2:]
    ids_ok, errors = verdicts(engine, logits, sessions, swapped)
    assert ids_ok[:2] == [False, False] and min(errors[:2]) > engine.FLIP_TOLERANCE
    assert engine.count_wrong(errors, ids_ok) >= 2


@pytest.mark.parametrize("control", ["weights_fp8", "router_one_pass", "no_expert_bias", "no_position_mask", "gates_bf16"])
def test_a_planted_control_shows_where_it_has_to(control, monkeypatch):
    """Each control of ``controls_lfm2.py`` planted in the tiny program: the
    probes meet the router's and the gates' faults and the convolution's
    dropped mask; the float8 weights show in the served scores alone (the
    chip's readings and the limits they pass are PERF.md's)."""
    from benchmark import controls_lfm2
    from predictionio_tpu.models.sequential import lfm2
    from predictionio_tpu.ops import moe

    engine, tiny = served()
    logits, _, sound_gate, sound_router = tiny["as configured"]
    _, sound_errors = verdicts(engine, logits, tiny["sessions"], tiny["answers"])
    fine = [engine.SCORE_TOLERANCE / 2] * len(sound_gate)
    assert engine.count_wrong(fine, [True] * len(fine), sound_gate, sound_router) == 0
    for module, name in ((lfm2, "session_vectors"), (lfm2, "short_conv"), (lfm2, "gated_conv"), (moe, "route_sigmoid")):
        monkeypatch.setattr(module, name, getattr(module, name))  # put back when the test ends
    plain = lfm2.session_vectors
    controls_lfm2.CONTROLS[control](lfm2, moe)
    plain.clear_cache()
    try:
        _, _, gate_errors, router_errors = tiny["reference_of"]()
        _, errors = verdicts(engine, logits, tiny["sessions"], tiny["answers_of"]())
    finally:
        monkeypatch.undo()
        plain.clear_cache()
    wrong = engine.count_wrong(fine, [True] * len(fine), gate_errors, router_errors)
    moved = np.abs(np.asarray(errors) - np.asarray(sound_errors))
    if control in ("gates_bf16", "no_position_mask"):
        # the gate's probe lays a session twice in one row: it meets rounded
        # gates, and taps that reach into the session in front
        assert router_errors == sound_router and min(gate_errors) > 10 * engine.GATE_TOLERANCE
        assert wrong == len(fine)
        # (the six sessions ride two streams: all but each stream's first have a session in front)
        assert control == "gates_bf16" or (moved > 1e-3).sum() >= 3
    elif control in ("router_one_pass", "no_expert_bias"):
        assert gate_errors == sound_gate and max(router_errors) > 10 * engine.ROUTER_TOLERANCE
        assert wrong >= 1
    else:
        # the probes are given the served tree as it lies: only the scores move
        assert gate_errors == sound_gate and router_errors == sound_router and wrong == 0
        assert (moved > 1e-3).sum() >= 5


def test_the_controls_script_deploys_the_cell_and_has_the_check_refuse_what_is_planted(tiny_root):
    add_tiny_lfm2(tiny_root)
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); from benchmark import controls_lfm2 as c; "
        "sys.exit(0 if c.run(sys.argv[1], 5, [None, 'gates_bf16', 'no_expert_bias'], 'cpu', 'tiny-lfm2.sat') else 1)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tiny_root)], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [line.get("control") for line in lines] == ["as configured", "gates_bf16", "no_expert_bias", None]
    assert lines[-1] == {"ok": True}
    sound, gates, no_bias = lines[:3]
    assert sound["wrong"] == 0 and sound["checked"] >= 32
    assert gates["wrong"] == gates["checked"] and gates["largest_gate_error"] > 100 * sound["largest_gate_error"]
    # (a session of three items may meet no token whose choice the bias decides)
    assert no_bias["wrong"] >= no_bias["checked"] // 2 and no_bias["largest_router_error"] > 0.01
    # the replies are the PLANTED program's: the served scores moved with it
    assert len({line["median_score_error"] for line in lines[:3]}) == 3


def test_the_check_holds_the_median_answer_tight_and_every_answer_loosely():
    from benchmark.engines import sequential_lfm2 as engine

    tight, loose = engine.SCORE_TOLERANCE, engine.FLIP_TOLERANCE
    assert tight < loose
    fine = [tight / 2] * 62 + [2 * tight, 0.9 * loose]  # bf16 everywhere, two tipped answers
    assert engine.count_wrong(fine, [True] * 64) == 0
    assert engine.count_wrong(fine, [True] * 63 + [False]) == 1  # other ids than the reference's
    assert engine.count_wrong(fine[:-1] + [1.2 * loose], [True] * 64) == 1  # beyond a tipped router
    # another arithmetic than the configuration states: the median is off
    assert engine.count_wrong([2 * tight] * 64, [True] * 64) == 64
    # the probes: every session's own, whatever the scores say
    gate, router = engine.GATE_TOLERANCE, engine.ROUTER_TOLERANCE
    assert engine.count_wrong(fine, [True] * 64, [gate / 2] * 64, [router / 2] * 64) == 0
    assert engine.count_wrong(fine, [True] * 64, [gate / 2] * 63 + [2 * gate], [router / 2] * 64) == 1
    assert engine.count_wrong(fine, [True] * 64, [gate / 2] * 64, [2 * router] + [0.0] * 63) == 1
    assert engine.count_wrong(fine, [True] * 64, [float("nan")] + [0.0] * 63, [0.0] * 64) == 1


@pytest.mark.parametrize("lengths", [(64, 64, 64, 128, 128, 128), (128,) * 6])
def test_a_session_padded_to_a_longer_program_reads_as_it_does_at_its_own_length(lengths):
    """The check pads every session to one of two lengths: each layer is
    causal, so the logits at a session's last position, its probes and its
    ties are those of its true length."""
    engine, tiny = served()
    logits, tie_share, _, _ = tiny["as configured"]
    padded = tiny["reference_of"](list(lengths))
    for ours, theirs in zip(padded[0], logits):
        np.testing.assert_allclose(ours, theirs, atol=2e-5)
    assert padded[1] == pytest.approx(tie_share, abs=0.01)
    assert max(padded[2]) < engine.GATE_TOLERANCE / 10 and max(padded[3]) < engine.ROUTER_TOLERANCE / 100
