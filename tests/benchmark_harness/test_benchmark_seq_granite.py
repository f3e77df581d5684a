"""The ``seq-granite-4-h`` configuration's benchmark files: a tiny configuration
and cell are added to a temporary copy as NEW files and entries and rehearsed
on the CPU; the operation counts against hand-worked ones; the benchmark's
copy of the reference against the program's; the new readers on hand-made
runs; the new entries found in ``BENCHMARK.json`` BY NAME (never by tail, by
count or as a whole list: a later PR appends behind them and to them);
another session's answer and each planted control against the check."""

import ast
import inspect
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, reference_granite, shapes_granite
from benchmark.readers import _slice, granite_roofline
from benchmark_testkit import REPO, add_cell, last_line, rehearse

CELL = "seq-granite-4-h.serve-sat"
NEW_METRICS = ["seq_mamba_ms", "seq_ssd_ms", "mamba_time_share", "ssd_roofline", "experts_held36_roofline"]
JOINED = [
    "compiles_in_window.sat", "idle_unnamed_share.sat", "device_idle_share.sat", "seq_tokens_per_s",
    "pad_token_share", "seq_stage_ms", "seq_program_ms", "seq_experts_ms", "seq_router_ms", "seq_head_ms",
    "seq_shared_ms", "expert_load_max_over_mean", "absent_copy_share", "seq_rows_per_program",
    "seq_programs_per_batch",
]
COUNTER_FED = ["seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "expert_load_max_over_mean", "absent_copy_share"]
# (the multipliers at values of their own: at the published 12 and 0.22 a
# stream of hidden 64 is all embedding and a layer's fault moves no score)
TINY_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 32, "shared_intermediate_size": 48, "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention", "mamba", "mamba"], "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "attention_multiplier": 1.0, "embedding_multiplier": 3.0, "residual_multiplier": 0.5, "logits_scaling": 2.0,
    "num_local_experts": 6, "num_experts_per_tok": 3, "vocab_size": 128, "max_position_embeddings": 128,
    "experts_held": [6, 6], "vocab_slice": [0, 128],
    "published": {"num_hidden_layers": 4, "num_local_experts": 12, "vocab_size": 256},
}


def published_config() -> dict:
    return json.loads((REPO / "benchmark" / "configs" / "seq-granite-4-h.json").read_text())


def add_tiny_granite(root):
    """``tiny-granite`` and ``tiny-granite.sat`` as new files and entries of the copy."""
    config = published_config()
    # (the CELL's limits are the published widths': the stream's three multipliers as published, under which a
    # tipped router moves a tiny bf16 tree's scores as little as it does there)
    config.update(
        {**TINY_WIDTHS, **{key: config[key] for key in ("embedding_multiplier", "residual_multiplier", "logits_scaling")}},
        name="tiny-granite", n_users=300,
        session_length={"median": 24, "sigma": 0.9, "min": 3, "max": 128},
        server_config={"max_batch_size": 8},
    )
    (root / "benchmark" / "configs" / "tiny-granite.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny-granite", "source": "a test's", "file": "benchmark/configs/tiny-granite.json",
         "reduced": [], "why": "a test's"}
    )
    mix = json.loads((REPO / "benchmark" / "traffic" / "sat.json").read_text())
    mix.update(ramp_s=0.5, connections=4, users_drawn=5000, trace_offset_s=0.2, trace_slice_s=0.5)
    (root / "benchmark" / "traffic" / "tiny-granite-sat.json").write_text(json.dumps(mix))
    add_cell(bench, "tiny-granite.sat", "tiny-granite", "tiny-granite-sat", CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_granite_cell_rehearses_on_the_cpu(tiny_root, trace):
    add_tiny_granite(tiny_root)
    seconds = 12  # as the tiny Kimi-Linear cell: unrolled layers beside five busy test workers
    proc = rehearse(tiny_root, "tiny-granite.sat", trace, seconds)
    line = last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 64
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"answered_qps", "setup_s"}
        assert metrics["answered_qps"]["value"] == pytest.approx(line["attempted"] / seconds)
        assert "checked" in proc.stderr and "worst |served - reference| by answer: median" in proc.stderr
        assert "the program's functions on the reference's inputs" in proc.stderr
        return
    # what the program's counters feed is there; what only a device trace
    # feeds has nothing to read on the CPU and is left out
    assert set(COUNTER_FED) <= set(metrics)
    assert not (set(NEW_METRICS + JOINED) - set(COUNTER_FED) - {"compiles_in_window.sat"}) & set(metrics)
    assert metrics["compiles_in_window.sat"]["value"] == 0
    assert metrics["seq_tokens_per_s"]["value"] > 0
    assert 0 < metrics["pad_token_share"]["value"] < 100
    # 6 of 12 experts held: half the copies are the other chip's
    assert 35 < metrics["absent_copy_share"]["value"] < 65
    assert 1.0 <= metrics["expert_load_max_over_mean"]["value"] <= 8.0


def test_operation_counts_against_hand_worked_ones():
    c = TINY_WIDTHS
    assert shapes_granite.layer_counts(c) == {"mamba": 3, "attn": 1, "sparse": 4}
    # the projections: in_proj 64 x (128 + 128 + 2 x 16 + 8), out_proj 128 x 64
    assert shapes_granite.mamba_inner(c) == 128
    assert shapes_granite.mamba_proj_weights(c) == 64 * 296 + 128 * 64 == 27136
    assert shapes_granite.mamba_proj_flops(100, c) == 100 * 2 * 27136
    assert shapes_granite.mamba_proj_bytes(100, c) == 27136 * 2 + 2 * 100 * 64 * 4
    # the scan, by its recurrence: 8 heads, a state of 16 x 16 a head, 5 operations an entry and the skip's 2 x 16
    assert shapes_granite.ssd_flops(100, c) == 100 * 8 * (5 * 16 * 16 + 2 * 16) == 1049600
    # x and y of 128, B and C of 16, the step of 8, float32
    assert shapes_granite.ssd_bytes(100, c) == 100 * (2 * 128 + 32 + 8) * 4 == 118400
    # attention: q and o at 4 heads of 16, k and v at 2
    assert shapes_granite.head_dim(c) == 16
    assert shapes_granite.attn_weights(c) == 2 * 64 * 64 + 2 * 64 * 32 == 12288
    tokens = 3 * 64
    assert shapes_granite.attn_flops(3, 64, c) == tokens * (2 * 12288 + 4 * 64 * (16 + 16))
    assert shapes_granite.attn_bytes(tokens, c) == 12288 * 2 + 2 * tokens * 64 * 4
    # experts: 6 of 12 held, 3 copies a token: one and a half land here
    assert shapes_granite.held_copies(100, c) == 150
    assert shapes_granite.experts_held_flops(100, c) == 2 * 3 * 150 * 64 * 32
    assert shapes_granite.experts_held_bytes(100, c) == 6 * 3 * 64 * 32 * 2 + 2 * 100 * 64 * 4
    # at the published widths, a token: the issue's arithmetic
    published = published_config()
    assert shapes_granite.layer_counts(published) == {"mamba": 9, "attn": 1, "sparse": 10}
    assert shapes_granite.mamba_proj_flops(1, published) == pytest.approx(204.5e6, rel=0.001)
    assert shapes_granite.ssd_flops(1, published) == pytest.approx(5.26e6, rel=0.002)
    assert shapes_granite.attn_flops(1, 2048, published) / 2048 == pytest.approx(83.9e6 + 16.8e6, rel=0.002)
    assert shapes_granite.held_copies(2048, published) == 2048 * 10 * 36 / 72 == 10240
    assert shapes_granite.experts_held_flops(1, published) == pytest.approx(94.4e6, rel=0.001)
    # a 2,048-token program's scan is bound by the bytes of x and y, its held experts by their operations
    assert shapes_granite.ssd_bytes(2048, published) / 819e9 > 2 * shapes_granite.ssd_flops(2048, published) / 197e12
    flops, nbytes = (f(2048, published) for f in (shapes_granite.experts_held_flops, shapes_granite.experts_held_bytes))
    assert nbytes == pytest.approx(0.75e9, rel=0.01) and flops / 197e12 > nbytes / 819e9


def test_the_benchmarks_reference_is_the_programs_function_for_function():
    from predictionio_tpu.models.sequential import granite_reference

    def functions(module):
        return {
            name: inspect.getsource(f) for name, f in inspect.getmembers(module, inspect.isfunction)
            if f.__module__ == module.__name__
        }

    ours, theirs = functions(reference_granite), functions(granite_reference)
    assert ours.keys() == theirs.keys() and len(ours) >= 20
    for name in ours:
        assert ours[name] == theirs[name], name
    # float32 at `highest`, and nothing of the program's ops/
    source = inspect.getsource(reference_granite)
    assert '_HIGHEST = "highest"' in source and "predictionio_tpu" not in source.split('"""', 2)[2]
    assert "jnp.repeat(k, heads // kv, axis=1)" in source  # keys and values repeated per query head
    assert "jax.lax.scan(one, zero, (x, step, b, c))" in source  # the scan is the recurrence, a position a step
    assert "for j in range(taps)" in source and "cumsum" not in source


def test_the_engine_module_imports_the_programs_names_at_its_top():
    # so that a checkout without them (the PR's parent) fails at once
    tree = ast.parse((REPO / "benchmark" / "engines" / "sequential_granite.py").read_text())
    top = {
        f"{node.module}.{alias.name}" for node in tree.body if isinstance(node, ast.ImportFrom)
        for alias in node.names
    }
    assert "predictionio_tpu.models.sequential.granite" in top
    assert "predictionio_tpu.models.sequential.engine.GraniteModel" in top


def test_the_new_entries_are_found_by_name_behind_the_parents():
    # by NAME and by ORDER among names: no tail, no count, no list held whole
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names) <= 128
    for name in NEW_METRICS:
        assert names.index(name) > names.index("mla_expanded_roofline"), name  # behind the parent's last
    cells = [c["name"] for c in bench["workloads"]]
    assert cells.index(CELL) > cells.index("seq-kanana-2.serve-sat")
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("seq-granite-4-h") > configs.index("seq-kanana-2")
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    qps = by_name["answered_qps"]["workloads"]
    assert qps.index(CELL) > qps.index("seq-kanana-2.serve-sat")
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][0] == CELL and m["moves"] == "answered_qps" and m["layer"] == "sequence kernels"
        assert m["source"] == "device_trace"
        assert (m["unit"], m["better"]) == (("%", "higher") if "roofline" in name or "share" in name else ("ms", "lower"))
        spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
        assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
    for name in JOINED:
        joined = by_name[name]["workloads"]
        assert joined.index(CELL) > joined.index("seq-kimi-linear.serve-sat"), name
    # PR 39's twenty keep the lists a test of theirs pins whole
    for name in ("loop_idle_share.sat", "launch_queue_programs.sat", "seq_launch_host_ms"):
        assert CELL not in by_name[name]["workloads"]
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert cell == {**cell, "config": "seq-granite-4-h", "traffic": "sat", "chips": 1}
    assert len(cell["why"]) <= 200 and not (REPO / "benchmark" / "cells" / f"{CELL}.json").exists()


def test_the_configuration_states_every_published_key_and_the_cut():
    from pathlib import Path

    config = published_config()
    entry = {c["name"]: c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]}["seq-granite-4-h"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["num_local_experts"], config["vocab_size"]) == (10, 36, 50176)
    assert config["experts_held"] == [0, 36] and config["vocab_slice"] == [0, 50176]
    assert config["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4  # one whole period
    assert "8-chip v5e slice" in config["deployment"] and "36 a chip" in config["deployment"]
    assert all(isinstance(line, str) and line for line in config["assumed"].values())
    for key in ("router", "gated norm", "step", "A_log, dt_bias, D", "head_dim", "mamba_chunk_size", "weights"):
        assert key in config["assumed"], key
    olmoe = json.loads((REPO / "benchmark" / "configs" / "seq-olmoe.json").read_text())
    for key in ("n_users", "session_length", "structure_seed", "seed_rule", "server_config"):
        assert config[key] == olmoe[key], key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(l) for l in catalog.read_text().splitlines() if '"granite-4.0-h-small"' in l)
        assert entry["source"] == config["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if config[k] != v}
        assert differing == set(config["reduced"])  # no width among them


def test_the_variant_gives_the_algorithm_the_published_counts_and_the_share():
    from benchmark.engines import sequential_granite as engine
    from predictionio_tpu.models.sequential import engine_factory

    variant = engine.variant_of(published_config(), 2600000123)
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    assert (params.num_local_experts, params.vocab_size, params.num_hidden_layers) == (72, 100352, 10)
    config = params.config()
    assert config.experts_held == (0, 36) and config.table_rows == 50176 and config.max_session == 4096
    assert config.sparse_layers == 10 and sum(config.is_mamba(i) for i in range(10)) == 9
    assert config.stream_shapes() == (2048, 4096) and config.buckets()[-4:] == (512, 1024, 2048, 4096)
    assert params.seed == 2600000123 % 2**31 and engine.CHECKED_QUERIES == 64


COUNTERS = {
    'pio_seq_programs_total{bucket="64"}': (2.0, 12.0),
    'pio_seq_programs_total{bucket="128"}': (1.0, 11.0),
    'pio_seq_rows_total{bucket="64"}': (64.0, 64.0 + 320),
    'pio_seq_rows_total{bucket="128"}': (16.0, 16.0 + 240),
}


def hand_made_run(**fields):
    return harness.Run(
        0.0, 51.0, 1, 0, True,
        counters_start={k: v[0] for k, v in COUNTERS.items()},
        counters_end={k: v[1] for k, v in COUNTERS.items()},
        **fields,
    )


def test_the_new_readers_read_a_hand_made_slice_and_nothing_of_another_programs(monkeypatch):
    def op(start, end, *scopes):
        path = "/".join(scopes)
        return (start, end, f"%f = f32[] fusion() {path}", frozenset({f"jit(session_vectors)/{path}/x"}))

    # two executions of the program in the slice: the Mamba mixers 12 ms (their
    # scan 4 of them, the projections 6), attention 1 ms, the held experts 5, the shared expert 2
    ops = [
        op(0.0, 8e6, "mamba", "in_proj"), op(8e6, 12e6, "mamba", "conv"), op(12e6, 20e6, "mamba", "ssd", "while", "body"),
        op(20e6, 24e6, "mamba", "out_proj"), op(24e6, 26e6, "attn"), op(26e6, 36e6, "experts", "gmm"),
        op(36e6, 40e6, "shared"),
    ]
    # a bare run (no trace, no counters, no shapes): every new metric is left out
    bare = harness.Run(0.0, 51.0, 1, 0, True)
    assert all(harness.read_metric(REPO, True, name, bare) is None for name in NEW_METRICS)
    profile = _slice.SliceProfile(0.0, 1e9, [], ops)
    monkeypatch.setattr(_slice, "load", lambda run: profile)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = hand_made_run(
        trace=types.SimpleNamespace(programs={"jit_session_vectors": {"count": 2, "seconds": 0.040}}),
        shapes=TINY_WIDTHS, peak=peak,
    )
    tokens = (320 * 64 + 240 * 128) / 20  # the window's mean program
    counts = shapes_granite.layer_counts(TINY_WIDTHS)

    def least(flops, nbytes, layers):
        return max(layers * flops / 197e12, layers * nbytes / 819e9)

    want = least(shapes_granite.ssd_flops(tokens, TINY_WIDTHS), shapes_granite.ssd_bytes(tokens, TINY_WIDTHS), counts["mamba"])
    assert harness.read_metric(REPO, True, "ssd_roofline", run) == pytest.approx(100 * want / 4e-3)
    want = least(
        shapes_granite.experts_held_flops(tokens, TINY_WIDTHS), shapes_granite.experts_held_bytes(tokens, TINY_WIDTHS),
        counts["sparse"],
    )
    assert harness.read_metric(REPO, True, "experts_held36_roofline", run) == pytest.approx(100 * want / 5e-3)
    # the projections' two scopes are one kernel's time
    want = least(
        shapes_granite.mamba_proj_flops(tokens, TINY_WIDTHS), shapes_granite.mamba_proj_bytes(tokens, TINY_WIDTHS),
        counts["mamba"],
    )
    assert granite_roofline.read(run, "mamba_proj") == pytest.approx(100 * want / 6e-3)
    assert granite_roofline.read(run, "attn128") > 0
    assert harness.read_metric(REPO, True, "seq_mamba_ms", run) == pytest.approx(12.0)
    assert harness.read_metric(REPO, True, "seq_ssd_ms", run) == pytest.approx(4.0)
    # the Mamba mixers' 12 ms of the program's 20
    assert harness.read_metric(REPO, True, "mamba_time_share", run) == pytest.approx(60.0)
    # the accepted readers the cell joined find this program's scopes too
    assert harness.read_metric(REPO, True, "seq_experts_ms", run) == pytest.approx(5.0)
    assert harness.read_metric(REPO, True, "seq_shared_ms", run) == pytest.approx(2.0)
    assert harness.read_metric(REPO, True, "seq_program_ms", run) == pytest.approx(20.0)
    # no trace (an untraced run, the CPU): nothing to read
    assert granite_roofline.read(hand_made_run(shapes=TINY_WIDTHS, peak=peak), "ssd") is None
    # LFM2's run (its shapes): the readers find nothing of theirs
    other = harness.Run(0.0, 51.0, 1, 0, True, shapes={"layer_types": ["conv"]}, peak=peak, trace=object())
    assert all(granite_roofline.read(other, kernel) is None for kernel in granite_roofline.KERNELS)
    assert granite_roofline.read(other, share_of=["mamba"]) is None
    # a slice that shows no scan: the share of the roofline is left out, never 0
    monkeypatch.setattr(_slice, "load", lambda run: _slice.SliceProfile(0.0, 1e9, [], ops[:2] + ops[3:]))
    assert harness.read_metric(REPO, True, "ssd_roofline", run) is None


_served: dict = {}  # a tiny served model, its answers and its reference: several tests ask


def served():
    """``(engine, tiny)`` at the tiny widths; ``tiny["reference_of"]()`` runs
    the check's reference (and its probes) on the model's own weights, under
    whatever is planted at that time, and ``tiny["answers_of"]()`` the served
    program."""
    import jax

    from benchmark.engines import sequential_granite as engine
    from predictionio_tpu.models.sequential import Query, engine_factory
    from predictionio_tpu.models.sequential.engine import session_tails

    if not _served:
        config = {**published_config(), **TINY_WIDTHS}
        variant = engine_factory().engine_params_from_variant(engine.variant_of(config, 4))
        params = variant.algorithms[0][1]
        algorithm = engine_factory().make_components(variant)[2][0]
        rng = np.random.default_rng(8)
        sessions = [rng.integers(0, 128, n).astype(np.int32) for n in (5, 40, 64, 70, 90, 128)]
        model = engine.GraniteModel(
            params.config(), [f"i{i}" for i in range(128)], [f"u{i}" for i in range(6)],
            *session_tails(sessions, 128), engine.granite.init_weights(params.config(), 4),
        )
        shapes = {key: config[key] for key in engine.PUBLISHED + ("experts_held", "vocab_slice", "published")}

        def answers_of():
            return algorithm.predict_batch(model, [Query(user=f"u{i}", num=10) for i in range(6)])

        def reference_of(lengths=None):
            cache = jax.config.jax_enable_compilation_cache
            try:
                return engine.reference_logits(model.weights, shapes, model.config, sessions, lengths)
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


def within(engine, probes) -> bool:
    fine = [engine.SCORE_TOLERANCE / 2] * len(probes["scan"])
    return engine.count_wrong(fine, [True] * len(fine), probes) == 0


def test_another_sessions_answer_fails_the_check_that_the_servers_own_passes():
    engine, tiny = served()
    sessions, answers = tiny["sessions"], tiny["answers"]
    logits, tie_share, probes = tiny["as configured"]
    assert 0 <= tie_share < 0.2 and set(probes) == set(engine.PROBES)
    # float32 against float32 here but for the attention's bf16 operands: the probes read the order of the sums
    assert max(probes["conv"]) < engine.CONV_TOLERANCE / 10 and max(probes["router"]) < engine.ROUTER_TOLERANCE / 100
    assert max(probes["scan"]) < engine.SCAN_TOLERANCE / 10 and 0 < max(probes["attn"]) < engine.ATTN_TOLERANCE
    assert within(engine, probes)
    ids_ok, errors = verdicts(engine, logits, sessions, answers)
    # a bf16 tree at a tiny size: a tipped router moves an answer by more than
    # at the published widths; the ids hold and nothing is off by the logits' order
    assert all(ids_ok) and max(errors) < 1.0
    # the gross fault FLIP_TOLERANCE is there for: two users get each other's answer
    swapped = [answers[1], answers[0]] + answers[2:]
    ids_ok, errors = verdicts(engine, logits, sessions, swapped)
    assert ids_ok[:2] == [False, False] and min(errors[:2]) > engine.FLIP_TOLERANCE
    assert engine.count_wrong(errors, ids_ok) >= 2


# control -> the probe that has to meet it (None: the served scores alone)
MEETS = {
    "weights_fp8": None, "no_residual_multiplier": None, "state_bf16": "scan", "no_session_reset": "scan",
    "no_position_mask": "conv", "router_no_renorm": "router", "scale_rsqrt_d": "attn",
}


@pytest.mark.parametrize("control", list(MEETS))
def test_a_planted_control_shows_where_it_has_to(control, monkeypatch):
    """Each control of ``controls_granite.py`` planted in the tiny program:
    its probe meets it and no other probe moves; the float8 weights and the
    residual's multiplier show in the served scores alone (the chip's
    readings and the limits they pass are PERF.md's)."""
    from benchmark import controls_granite
    from predictionio_tpu.models.sequential import granite
    from predictionio_tpu.ops import moe

    assert set(controls_granite.CONTROLS) == set(MEETS)
    engine, tiny = served()
    logits, _, sound = tiny["as configured"]
    _, sound_errors = verdicts(engine, logits, tiny["sessions"], tiny["answers"])
    for module, name in (
        (granite, "session_vectors"), (granite, "ssd"), (granite, "short_conv"), (granite, "fused_attention"), (moe, "route"),
    ):
        monkeypatch.setattr(module, name, getattr(module, name))  # put back when the test ends
    if control == "state_bf16":
        # (a state is handed on where a session spans chunks: none of these six does at the chip's width)
        monkeypatch.setattr(granite, "SSD_CHUNK", 16)
    plain = granite.session_vectors
    controls_granite.CONTROLS[control](granite, moe)
    plain.clear_cache()
    try:
        _, _, probes = tiny["reference_of"]()
        _, errors = verdicts(engine, logits, tiny["sessions"], tiny["answers_of"]())
    finally:
        monkeypatch.undo()
        plain.clear_cache()
    moved = np.abs(np.asarray(errors) - np.asarray(sound_errors))
    met = MEETS[control]
    for name in engine.PROBES:
        if name != met:
            np.testing.assert_allclose(probes[name], sound[name], rtol=1e-3, atol=1e-9, err_msg=name)
    if met is None:
        # the probes are given the served tree and configuration as they lie: only the scores move
        assert within(engine, probes) and (moved > 1e-3).sum() >= 5
        if control == "no_residual_multiplier":
            assert np.median(errors) > 2 * engine.SCORE_TOLERANCE
        return
    limit, over = engine.LIMITS[met]
    if control == "state_bf16":
        # a state of 16 x 16 under decays this strong is little of the scan's output beside the skip: the
        # probe reads the rounding a thousand times over the float32 scan's own, and the limit is the chip's
        assert over(probes[met]) > 1000 * over(sound[met])
        return
    assert over(probes[met]) > 3 * limit and not within(engine, probes), (over(probes[met]), limit)
    assert (moved > 1e-4).sum() >= 3


def test_the_controls_script_deploys_the_cell_and_has_the_check_refuse_what_is_planted(tiny_root):
    add_tiny_granite(tiny_root)
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); from benchmark import controls_granite as c; "
        "sys.exit(0 if c.run(sys.argv[1], 5, [None, 'no_session_reset', 'router_no_renorm'], 'cpu', 'tiny-granite.sat') else 1)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tiny_root)], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [line.get("control") for line in lines] == ["as configured", "no_session_reset", "router_no_renorm", None]
    assert lines[-1] == {"ok": True}
    sound, no_reset, no_renorm = lines[:3]
    assert sound["wrong"] == 0 and sound["checked"] >= 32
    assert no_reset["wrong"] >= no_reset["checked"] // 2 and no_reset["scan_error"] > 100 * sound["scan_error"]
    assert no_renorm["wrong"] >= no_renorm["checked"] // 2 and no_renorm["router_error"] > 0.01
    # the replies are the PLANTED program's: the served scores moved with it
    assert len({line["median_score_error"] for line in lines[:3]}) == 3


def test_the_check_takes_the_head_a_block_of_rows_at_a_time_and_reads_the_same(monkeypatch):
    # the table upcast whole (and transposed) is what ran the chip out of memory beside the served model
    engine, tiny = served()
    weights, shapes = tiny["model"].weights, tiny["shapes"]
    x = np.random.default_rng(3).normal(size=weights["embed"].shape[1]).astype(np.float32)
    whole = np.asarray(engine.reference.head(weights, shapes, x))
    monkeypatch.setattr(engine, "HEAD_ROWS", 48)  # no divisor of the tiny table's 128 rows
    np.testing.assert_allclose(engine.head_in_blocks(weights, shapes, x), whole, rtol=1e-6, atol=1e-6)


def test_the_check_holds_the_median_answer_tight_and_every_answer_loosely():
    from benchmark.engines import sequential_granite as engine

    tight, loose = engine.SCORE_TOLERANCE, engine.FLIP_TOLERANCE
    assert tight < loose
    fine = [tight / 2] * 62 + [2 * tight, 0.9 * loose]  # bf16 everywhere, two tipped answers
    assert engine.count_wrong(fine, [True] * 64) == 0
    assert engine.count_wrong(fine, [True] * 63 + [False]) == 1  # other ids than the reference's
    assert engine.count_wrong(fine[:-1] + [1.2 * loose], [True] * 64) == 1  # beyond a tipped router
    # another arithmetic than the configuration states: the median is off
    assert engine.count_wrong([2 * tight] * 64, [True] * 64) == 64
    # the probes: every session's own or the median session's (``LIMITS``), whatever the scores say
    sound = {name: [engine.LIMITS[name][0] / 2] * 64 for name in engine.PROBES}
    assert engine.count_wrong(fine, [True] * 64, sound) == 0
    for name in engine.PROBES:
        limit, over = engine.LIMITS[name]
        one = {**sound, name: [limit / 2] * 63 + [2 * limit]}
        assert engine.count_wrong(fine, [True] * 64, one) == (0 if over is np.median else 1), name
        assert engine.count_wrong(fine, [True] * 64, {**sound, name: [2 * limit] * 64}) == 64, name
        assert engine.count_wrong(fine, [True] * 64, {**sound, name: [float("nan")] + [0.0] * 63}) >= (over is max), name


@pytest.mark.parametrize("lengths", [(64, 64, 64, 128, 128, 128), (128,) * 6])
def test_a_session_padded_to_a_longer_program_reads_as_it_does_at_its_own_length(lengths):
    """The check pads every session to one of two lengths: each layer is
    causal, so the logits at a session's last position, its probes and its
    ties are those of its true length."""
    engine, tiny = served()
    logits, tie_share, _ = tiny["as configured"]
    padded = tiny["reference_of"](list(lengths))
    for ours, theirs in zip(padded[0], logits):
        np.testing.assert_allclose(ours, theirs, atol=2e-5)
    assert padded[1] == pytest.approx(tie_share, abs=0.01)
    assert within(engine, padded[2])
