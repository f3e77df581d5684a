"""The ``seq-kimi-linear`` configuration's benchmark files: a tiny
configuration and cell are added to a temporary copy as NEW files and entries
and rehearsed on the CPU; the operation counts against hand-worked ones; the
benchmark's copy of the reference against the program's; the new readers on
hand-made runs; where the new entries stand in ``BENCHMARK.json``; a planted
wrong answer against the check."""

import ast
import inspect
import json
import types

import numpy as np
import pytest

from benchmark import harness, reference_kimi_linear, shapes_kimi_linear
from benchmark.readers import _slice, kimi_roofline
from benchmark_testkit import REPO, add_cell, last_line, rehearse

CELL = "seq-kimi-linear.serve-sat"
NEW_METRICS = [
    "seq_kda_ms", "seq_mla_ms", "seq_shared_ms", "kda_roofline", "mla_roofline",
    "experts_held_roofline", "absent_copy_share",
]
JOINED = [
    "seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "seq_program_ms", "seq_experts_ms",
    "seq_router_ms", "seq_head_ms", "expert_load_max_over_mean",
]
COUNTER_FED = ["seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "expert_load_max_over_mean", "absent_copy_share"]
TINY_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "linear_attn_config": {
        "kda_layers": [1, 2, 4], "full_attn_layers": [3], "num_heads": 4, "head_dim": 16,
        "short_conv_kernel_size": 4,
    },
    "first_k_dense_replace": 1, "num_experts": 4, "num_experts_per_token": 4, "vocab_size": 128,
    "experts_held": [4, 4],
    "vocab_slice": [128, 128], "model_max_length": 128,
    "published": {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 512},
}


def published_config() -> dict:
    return json.loads((REPO / "benchmark" / "configs" / "seq-kimi-linear.json").read_text())


def add_tiny_kimi(root):
    """``tiny-kimi`` and ``tiny-kimi.sat`` as new files and entries of the copy."""
    config = published_config()
    config.update(
        TINY_WIDTHS, name="tiny-kimi", n_users=300,
        session_length={"median": 24, "sigma": 0.9, "min": 3, "max": 128},
        server_config={"max_batch_size": 8},
    )
    (root / "benchmark" / "configs" / "tiny-kimi.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny-kimi", "source": "a test's", "file": "benchmark/configs/tiny-kimi.json",
         "reduced": [], "why": "a test's"}
    )
    mix = json.loads((REPO / "benchmark" / "traffic" / "sat.json").read_text())
    mix.update(ramp_s=0.5, connections=4, users_drawn=5000, trace_offset_s=0.2, trace_slice_s=0.5)
    (root / "benchmark" / "traffic" / "tiny-kimi-sat.json").write_text(json.dumps(mix))
    add_cell(bench, "tiny-kimi.sat", "tiny-kimi", "tiny-kimi-sat", CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_kimi_cell_rehearses_on_the_cpu(tiny_root, trace):
    add_tiny_kimi(tiny_root)
    # 12 s, not the 6 of the other rehearsals: four unrolled layers answer a
    # third as fast, and beside five busy test workers too few of the replies
    # the generators were to keep for the check would be in by 6 s
    seconds = 12
    proc = rehearse(tiny_root, "tiny-kimi.sat", trace, seconds)
    line = last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 16
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"answered_qps", "setup_s"}
        assert metrics["answered_qps"]["value"] == pytest.approx(line["attempted"] / seconds)
        assert "checked" in proc.stderr and "worst |served - reference| by answer: median" in proc.stderr
        return
    # what the program's counters feed is there; what only a device trace
    # feeds has nothing to read on the CPU and is left out
    assert set(COUNTER_FED) <= set(metrics)
    assert not (set(NEW_METRICS + JOINED) - set(COUNTER_FED)) & set(metrics)
    assert not {"seq_attn_ms", "attn_roofline", "experts_roofline"} & set(metrics)  # OLMoE's alone
    assert metrics["compiles_in_window.sat"]["value"] == 0
    assert metrics["seq_tokens_per_s"]["value"] > 0
    assert 0 < metrics["pad_token_share"]["value"] < 100
    # 4 of 16 experts held: three quarters of the copies are another chip's
    assert 65 < metrics["absent_copy_share"]["value"] < 85
    assert 1.0 <= metrics["expert_load_max_over_mean"]["value"] <= 8.0


def test_operation_counts_against_hand_worked_ones():
    c = TINY_WIDTHS
    assert shapes_kimi_linear.layer_counts(c) == {"kda": 3, "mla": 1, "dense": 1, "sparse": 3}
    # KDA: q, k, v, o of 64 x 64; two gates of 64 x 16 + 16 x 64; the step 64 x 4; three convolutions of 4 x 64
    weights = 4 * 64 * 64 + 2 * (64 * 16 + 16 * 64) + 64 * 4 + 3 * 4 * 64
    assert shapes_kimi_linear.kda_weights(c) == weights == 21504
    # 100 tokens: 2 flops a weight, and 7 * 16 * 16 a head of 4 for the recurrence
    assert shapes_kimi_linear.kda_flops(100, c) == 100 * (2 * 21504 + 7 * 4 * 16 * 16) == 5017600
    assert shapes_kimi_linear.kda_bytes(100, c) == 21504 * 2 + 2 * 100 * 64 * 4 == 94208
    # MLA: q 64 x 4*24, kv_a 64 x (24 + 8), kv_b 24 x 4*32, o 4*16 x 64
    weights = 64 * 96 + 64 * 32 + 24 * 128 + 64 * 64
    assert shapes_kimi_linear.mla_weights(c) == weights == 15360
    # 3 sessions of 64: the causal half of q.k at 24 and p.v at 16, 4 heads
    tokens = 3 * 64
    assert shapes_kimi_linear.mla_flops(3, 64, c) == tokens * (2 * 15360 + 4 * 64 * (24 + 16)) == 7864320
    assert shapes_kimi_linear.mla_bytes(tokens, c) == 15360 * 2 + 2 * tokens * 64 * 4 == 129024
    # experts: 4 of 16 held, 4 copies a token: one copy a token lands here
    assert shapes_kimi_linear.held_copies(100, c) == 100
    assert shapes_kimi_linear.experts_held_flops(100, c) == 2 * 3 * 100 * 64 * 32 == 1228800
    assert shapes_kimi_linear.experts_held_bytes(100, c) == 4 * 3 * 64 * 32 * 2 + 2 * 100 * 64 * 4 == 100352
    # at the published widths: the issue's arithmetic
    published = published_config()
    assert shapes_kimi_linear.kda_weights(published) == pytest.approx(39.5e6, rel=0.01)
    assert shapes_kimi_linear.mla_weights(published) == pytest.approx(29.1e6, rel=0.01)
    assert shapes_kimi_linear.kda_flops(1, published) == pytest.approx(83e6, rel=0.02)
    assert shapes_kimi_linear.held_copies(2048, published) == 2048 * 8 * 64 / 256 == 4096
    # a 2,048-token program's held experts are bound by their 0.9 GB of bytes
    flops = shapes_kimi_linear.experts_held_flops(2048, published)
    nbytes = shapes_kimi_linear.experts_held_bytes(2048, published)
    assert flops == pytest.approx(58e9, rel=0.01) and nbytes == pytest.approx(0.94e9, rel=0.01)
    assert nbytes / 819e9 > flops / 197e12


def test_the_benchmarks_reference_is_the_programs_function_for_function():
    from predictionio_tpu.models.sequential import kimi_linear_reference

    def functions(module):
        return {
            name: inspect.getsource(f) for name, f in inspect.getmembers(module, inspect.isfunction)
            if f.__module__ == module.__name__
        }

    ours, theirs = functions(reference_kimi_linear), functions(kimi_linear_reference)
    assert ours.keys() == theirs.keys() and len(ours) >= 20
    for name in ours:
        assert ours[name] == theirs[name], name
    # float32 at `highest`, and nothing of the program's ops/
    source = inspect.getsource(reference_kimi_linear)
    assert '_HIGHEST = "highest"' in source and "predictionio_tpu" not in source.split('"""', 2)[2]
    assert "lax.scan(one, zero, (q, k, v, g, b))" in source  # KDA as the recurrence


def test_the_engine_module_imports_the_programs_names_at_its_top():
    # so that a checkout without them (the PR's parent) fails at once
    tree = ast.parse((REPO / "benchmark" / "engines" / "sequential_kimi_linear.py").read_text())
    top = {
        f"{node.module}.{alias.name}" for node in tree.body if isinstance(node, ast.ImportFrom)
        for alias in node.names
    }
    assert "predictionio_tpu.models.sequential.kimi_linear" in top
    assert "predictionio_tpu.models.sequential.engine.KimiLinearModel" in top


def test_the_new_entries_are_appended_and_the_old_ones_only_grew():
    # pinned by NAME, not by position: the next cell appends behind these
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    start = names.index(NEW_METRICS[0])
    assert names[start : start + len(NEW_METRICS)] == NEW_METRICS
    assert names[start - 1] == "expert_load_max_over_mean"  # behind PR 26's last
    cells = [c["name"] for c in bench["workloads"]]
    assert cells.index(CELL) == cells.index("seq-olmoe.serve-sat") + 1
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("seq-kimi-linear") == configs.index("seq-olmoe") + 1
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    qps = by_name["answered_qps"]["workloads"]
    assert qps.index(CELL) == qps.index("seq-olmoe.serve-sat") + 1
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][0] == CELL and m["moves"] == "answered_qps" and m["layer"] == "sequence kernels"
        spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
        assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
    for name in JOINED:
        joined = by_name[name]["workloads"]
        assert joined.index(CELL) == joined.index("seq-olmoe.serve-sat") + 1
    for name in ("seq_attn_ms", "attn_roofline", "experts_roofline"):  # shapes_olmoe's
        assert CELL not in by_name[name]["workloads"]
    sat = [m["name"] for m in bench["per_layer"] if "seq-olmoe.serve-sat" in m["workloads"]]
    ours = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert set(sat) - set(ours) == {"seq_attn_ms", "attn_roofline", "experts_roofline"}
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert cell == {**cell, "config": "seq-kimi-linear", "traffic": "sat", "chips": 1}
    assert len(cell["why"]) <= 200
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 0


def test_the_configuration_states_every_published_key_and_the_cut():
    from pathlib import Path

    config = published_config()
    entry = {c["name"]: c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]}["seq-kimi-linear"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (8, 64, 40960)
    assert config["published"] == {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert config["experts_held"] == [0, 64] and config["vocab_slice"] == [0, 40960]
    assert all(isinstance(line, str) and line for line in config["assumed"].values())
    olmoe = json.loads((REPO / "benchmark" / "configs" / "seq-olmoe.json").read_text())
    for key in ("n_users", "session_length", "structure_seed", "seed_rule", "server_config"):
        assert config[key] == olmoe[key], key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(l) for l in catalog.read_text().splitlines() if "Kimi-Linear-48B-A3B" in l)
        assert entry["source"] == config["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if config[k] != v}
        assert differing == set(config["reduced"])  # no width among them


def test_the_variant_gives_the_algorithm_the_published_counts_and_the_share():
    from benchmark.engines import sequential_kimi_linear as engine
    from predictionio_tpu.models.sequential import engine_factory

    variant = engine.variant_of(published_config(), 2600000123)
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    assert (params.num_experts, params.vocab_size, params.num_hidden_layers) == (256, 163840, 8)
    config = params.config()
    assert config.experts_held == (0, 64) and config.table_rows == 40960 and config.max_session == 4096
    assert config.sparse_layers == 7 and [config.is_kda(i) for i in range(1, 9)] == [True] * 3 + [False] + [True] * 3 + [False]
    assert config.program_shapes() == ((32, 64), (16, 128), (8, 256), (4, 512), (2, 1024), (1, 2048), (1, 4096))
    assert params.seed == 2600000123 % 2**31


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
    # a program without the counter (the parent): every new metric is left out
    bare = harness.Run(0.0, 51.0, 1, 0, True)
    assert all(harness.read_metric(REPO, True, name, bare) is None for name in NEW_METRICS)
    # OLMoE's run (its shapes, no counter of copies): the rooflines find nothing of theirs
    olmoe = harness.Run(0.0, 51.0, 1, 0, True, shapes={"hidden_size": 2048}, peak={}, trace=object())
    assert all(kimi_roofline.read(olmoe, kernel) is None for kernel in kimi_roofline.KERNELS)


def test_the_roofline_shares_read_a_hand_made_slice(monkeypatch):
    def op(start, end, scope, inner):
        return (start, end, f"%f = f32[] fusion() {scope}", frozenset({f"jit(session_vectors)/{scope}/{inner}/x"}))

    # two executions of the program in the slice: KDA 6 ms, MLA 1 ms, the held
    # experts 2 ms and the shared expert 0.5 ms each
    ops = [op(0.0, 12e6, "kda", "scan"), op(12e6, 14e6, "mla", "dot"), op(14e6, 18e6, "experts", "gmm"),
           op(18e6, 19e6, "shared", "dot")]
    profile = _slice.SliceProfile(0.0, 1e9, [], ops)
    monkeypatch.setattr(_slice, "load", lambda run: profile)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = hand_made_run(
        trace=types.SimpleNamespace(programs={"jit_session_vectors": {"count": 2, "seconds": 0.019}}),
        shapes=TINY_WIDTHS, peak=peak,
    )
    tokens = (320 * 64 + 240 * 128) / 20  # the window's mean program
    counts = shapes_kimi_linear.layer_counts(TINY_WIDTHS)

    def least(flops, nbytes, layers):
        return max(layers * flops / 197e12, layers * nbytes / 819e9)

    want = least(shapes_kimi_linear.kda_flops(tokens, TINY_WIDTHS), shapes_kimi_linear.kda_bytes(tokens, TINY_WIDTHS), counts["kda"])
    assert kimi_roofline.read(run, "kda") == pytest.approx(100 * want / 6e-3)
    flops = (shapes_kimi_linear.mla_flops(320, 64, TINY_WIDTHS) + shapes_kimi_linear.mla_flops(240, 128, TINY_WIDTHS)) / 20
    want = least(flops, shapes_kimi_linear.mla_bytes(tokens, TINY_WIDTHS), counts["mla"])
    assert harness.read_metric(REPO, True, "mla_roofline", run) == pytest.approx(100 * want / 1e-3)
    want = least(
        shapes_kimi_linear.experts_held_flops(tokens, TINY_WIDTHS),
        shapes_kimi_linear.experts_held_bytes(tokens, TINY_WIDTHS), counts["sparse"],
    )
    assert harness.read_metric(REPO, True, "experts_held_roofline", run) == pytest.approx(100 * want / 2e-3)
    assert harness.read_metric(REPO, True, "seq_kda_ms", run) == pytest.approx(6.0)
    assert harness.read_metric(REPO, True, "seq_mla_ms", run) == pytest.approx(1.0)
    assert harness.read_metric(REPO, True, "seq_shared_ms", run) == pytest.approx(0.5)
    # the accepted readers the cell joined find this program's scopes too
    assert harness.read_metric(REPO, True, "seq_experts_ms", run) == pytest.approx(2.0)
    assert harness.read_metric(REPO, True, "seq_program_ms", run) == pytest.approx(9.5)
    # OLMoE's attention scope is not in this program: left out, never 0
    assert harness.read_metric(REPO, True, "seq_attn_ms", run) is None
    # no trace (an untraced run, the CPU): nothing to read
    assert kimi_roofline.read(hand_made_run(shapes=TINY_WIDTHS, peak=peak), "kda") is None


_served: dict = {}  # a tiny served model, its answers and its reference: three tests ask


def served():
    """``(engine, model, sessions, answers, reference_of)`` at the tiny
    widths; ``reference_of()`` runs the check's reference (and its probes)
    on the model's own weights, under whatever is planted at that time."""
    import jax
    import numpy as np

    from benchmark.engines import sequential_kimi_linear as engine
    from predictionio_tpu.models.sequential import Query, engine_factory
    from predictionio_tpu.models.sequential.engine import session_tails

    if not _served:
        config = {**published_config(), **TINY_WIDTHS}
        variant = engine_factory().engine_params_from_variant(engine.variant_of(config, 4))
        params = variant.algorithms[0][1]
        algorithm = engine_factory().make_components(variant)[2][0]
        rng = np.random.default_rng(8)
        sessions = [rng.integers(0, 128, n).astype(np.int32) for n in (5, 40, 64, 70, 90, 128)]
        model = engine.KimiLinearModel(
            params.config(), [f"i{i}" for i in range(128)], [f"u{i}" for i in range(6)],
            *session_tails(sessions, 128), engine.kimi_linear.init_weights(params.config(), 4),
        )
        answers = algorithm.predict_batch(model, [Query(user=f"u{i}", num=10) for i in range(6)])
        shapes = {key: config[key] for key in engine.PUBLISHED + ("experts_held", "vocab_slice", "published")}

        def reference_of():
            cache = jax.config.jax_enable_compilation_cache
            try:
                return engine.reference_logits(model.weights, shapes, sessions)
            finally:
                jax.config.update("jax_enable_compilation_cache", cache)

        _served.update(model=model, sessions=sessions, answers=answers, reference_of=reference_of, shapes=shapes)
        _served["as configured"] = reference_of()
    return engine, _served


def test_another_sessions_answer_fails_the_check_that_the_servers_own_passes():
    engine, tiny = served()
    sessions, answers = tiny["sessions"], tiny["answers"]
    logits, tie_share, scan_errors, router_errors = tiny["as configured"]
    assert 0 <= tie_share < 0.2
    # float32 against float32 here: the probes read the order of the sums
    assert max(scan_errors) < engine.SCAN_TOLERANCE / 10 and max(router_errors) < engine.ROUTER_TOLERANCE / 100

    def verdicts(answers):
        checked = [
            engine.check_answer(
                ref, session, [int(s.item[1:]) for s in answer.item_scores],
                [s.score for s in answer.item_scores], 128,
            )
            for ref, session, answer in zip(logits, sessions, answers)
        ]
        return [ok for ok, _, _ in checked], [error for _, _, error in checked]

    ids_ok, errors = verdicts(answers)
    # a bf16 tree at a tiny size: a tipped router moves an answer by more than
    # at the published widths; the ids hold and nothing is off by the logits' order
    assert all(ids_ok) and max(errors) < 1.0
    # the gross fault FLIP_TOLERANCE is there for: two users get each other's answer
    swapped = [answers[1], answers[0]] + answers[2:]
    ids_ok, errors = verdicts(swapped)
    assert ids_ok[:2] == [False, False] and min(errors[:2]) > engine.FLIP_TOLERANCE
    assert engine.count_wrong(errors, ids_ok) >= 2


@pytest.mark.parametrize("control", ["state_bf16", "decay_bf16", "one_pass", "experts_7", "no_bias"])
def test_a_planted_precision_or_router_fails_the_check_through_its_probes(control, monkeypatch):
    from benchmark import controls_kimi_linear
    from predictionio_tpu.ops import linear_attention, moe

    engine, tiny = served()
    _, _, sound_scan, sound_router = tiny["as configured"]
    fine = [engine.SCORE_TOLERANCE / 2] * len(sound_scan)
    assert engine.count_wrong(fine, [True] * len(fine), sound_scan, sound_router) == 0
    for module, name in ((linear_attention, "kda"), (linear_attention, "_dot"), (moe, "route_sigmoid")):
        monkeypatch.setattr(module, name, getattr(module, name))  # put back when the test ends
    controls_kimi_linear.CONTROLS[control](linear_attention, moe)
    _, _, scan_errors, router_errors = tiny["reference_of"]()
    wrong = engine.count_wrong(fine, [True] * len(fine), scan_errors, router_errors)
    print(control, scan_errors, router_errors)
    if control in ("experts_7", "no_bias"):
        assert scan_errors == sound_scan and min(router_errors) > 100 * engine.ROUTER_TOLERANCE
        assert wrong == len(fine)
    else:
        assert router_errors == sound_router
        if control == "decay_bf16":
            # bfloat16's eight bits in the log decay: 5e-5 to 7e-5 of the output, a hundred times
            # float32's own and still under what three-pass products do on the chip: not told
            assert min(scan_errors) > 100 * max(sound_scan) and max(scan_errors) < engine.SCAN_TOLERANCE
            assert control in controls_kimi_linear.NOT_TOLD and wrong == 0
            return
        # a state is handed on from the 64th position: shorter sessions read as configured
        carried = [len(session) > 64 or control != "state_bf16" for session in tiny["sessions"]]
        assert [error > engine.SCAN_TOLERANCE for error in scan_errors] == carried
        assert wrong == (sum(carried) if np.median(scan_errors) > engine.SCAN_TOLERANCE else 0) > 0


def test_the_controls_script_deploys_the_cell_and_has_the_check_refuse_what_is_planted(tiny_root):
    import os
    import subprocess
    import sys

    add_tiny_kimi(tiny_root)
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); from benchmark import controls_kimi_linear as c; "
        "sys.exit(0 if c.run(sys.argv[1], 5, [None, 'one_pass', 'decay_bf16', 'no_bias'], 'cpu', 'tiny-kimi.sat') else 1)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tiny_root)], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [line.get("control") for line in lines] == ["as configured", "one_pass", "decay_bf16", "no_bias", None]
    assert lines[-1] == {"ok": True}
    sound, one_pass, decay, no_bias = lines[:4]
    assert sound["wrong"] == 0 and sound["checked"] >= 8
    assert one_pass["wrong"] > 0 and one_pass["median_scan_error"] > 10 * decay["median_scan_error"]
    assert decay["wrong"] == 0 and decay["median_scan_error"] > 10 * sound["median_scan_error"]
    # (a session of three items may meet no token whose choice the bias decides)
    assert no_bias["wrong"] >= no_bias["checked"] - 2 and no_bias["largest_router_error"] > 0.01
    # the replies are the configured program's under every control: only the probes see it
    assert len({line["median_score_error"] for line in lines[:4]}) == 1


def test_the_check_holds_the_median_answer_tight_and_every_answer_loosely():
    from benchmark.engines import sequential_kimi_linear as engine

    tight, loose = engine.SCORE_TOLERANCE, engine.FLIP_TOLERANCE
    fine = [tight / 2] * 15 + [2 * tight, 0.9 * loose]  # bf16 everywhere, two tipped answers
    assert engine.count_wrong(fine, [True] * 17) == 0
    assert engine.count_wrong(fine, [True] * 16 + [False]) == 1  # other ids than the reference's
    assert engine.count_wrong(fine[:-1] + [1.2 * loose], [True] * 17) == 1  # beyond a tipped router
    # another arithmetic than the configuration states: the median is off
    assert engine.count_wrong([2 * tight] * 17, [True] * 17) == 17
    # the probes: every session's own, whatever the scores say
    scan, router = engine.SCAN_TOLERANCE, engine.ROUTER_TOLERANCE
    assert engine.count_wrong(fine, [True] * 17, [scan / 2] * 17, [router / 2] * 17) == 0
    # a long session of a sound run may read over the limit: the MEDIAN session decides
    assert engine.count_wrong(fine, [True] * 17, [scan / 2] * 15 + [2 * scan] * 2, [router / 2] * 17) == 0
    assert engine.count_wrong(fine, [True] * 17, [scan / 2] * 8 + [2 * scan] * 9, [router / 2] * 17) == 9
    assert engine.count_wrong(fine, [True] * 17, [scan / 2] * 17, [2 * router] + [0.0] * 16) == 1
    assert engine.count_wrong(fine, [True] * 17, [float("nan")] + [0.0] * 16, [0.0] * 17) == 1


@pytest.mark.parametrize("lengths", [(64, 64, 64, 128, 128, 128), (128,) * 6])
def test_a_session_padded_to_a_longer_program_reads_as_it_does_at_its_own_length(lengths):
    """The check pads every session to one of two lengths: each layer is
    causal, so the logits at a session's last position, its scan probe and its
    ties are those of its true length."""
    import jax

    engine, tiny = served()
    logits, tie_share, scan_errors, router_errors = tiny["as configured"]
    cache = jax.config.jax_enable_compilation_cache
    try:
        padded = engine.reference_logits(tiny["model"].weights, tiny["shapes"], tiny["sessions"], list(lengths))
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    for ours, theirs in zip(padded[0], logits):
        np.testing.assert_allclose(ours, theirs, atol=2e-5)
    assert padded[1] == pytest.approx(tie_share, abs=0.01)
    assert max(padded[2]) < engine.SCAN_TOLERANCE / 10 and max(padded[3]) < engine.ROUTER_TOLERANCE / 100


def kept_users(from_window: int):
    return (5, 0, 2, 1, 3, 4)[:from_window]


@pytest.mark.parametrize("from_window", [0, 2, 6])
def test_the_check_asks_for_the_replies_the_generators_did_not_bring(from_window, capsys):
    """A generator keeps 8 of its requests 256 to 1,279 and sends about 980
    in a window: the check asks, after the window, for what is missing of
    ``CHECKED_QUERIES`` (here every user there is), the users the generators
    asked first, and says how many replies are the window's."""
    import jax

    engine, tiny = served()
    replies = {
        user: json.dumps({"itemScores": [{"item": s.item, "score": s.score} for s in answer.item_scores]})
        for user, answer in enumerate(tiny["answers"])
    }
    asked = []
    serving = object.__new__(engine.Serving)
    # (the longest bucket's user, 3, is none of those the generators keep replies of)
    serving.asked_early, serving.stream = {0, 1, 2, 4, 5} | set(kept_users(from_window)), np.asarray([3, 1, 3, 0, 2, 5, 4, 1])
    serving.model, serving.model_config = tiny["model"], tiny["model"].config
    serving.config, serving.num = {**tiny["shapes"]}, 10
    serving.ask = lambda user: asked.append(user) or replies[user]
    kept = {user: replies[user] for user in kept_users(from_window)}
    cache = jax.config.jax_enable_compilation_cache
    try:
        checked, wrong, worst = serving.check(kept)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert (checked, wrong) == (6, 0) and worst < 1.0
    # the longest bucket's user first (here the one session over 64 items that comes first), then the stream's order
    assert sorted(asked) == sorted(set(range(6)) - set(kept)) and len(asked) == len(set(asked))
    assert asked == [u for u in dict.fromkeys([3] * (3 not in kept) + [3, 1, 0, 2, 5, 4]) if u not in kept]
    assert f"{from_window} of the replies are the window's" in capsys.readouterr().err
    # a second check of the deployment (the controls' script makes one a control) is given what the
    # first one checked: it asks for nothing again, and what the first asked for itself is no stranger
    del asked[:]
    try:
        assert serving.check(serving.checked_replies)[:2] == (6, 0) and not asked
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
