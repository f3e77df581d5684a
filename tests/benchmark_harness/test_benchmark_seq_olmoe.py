"""The ``seq-olmoe`` configuration's benchmark files: a tiny ``seq``
configuration and cell are added to a temporary copy as NEW files and entries
and rehearsed on the CPU; the operation counts against hand-worked ones; the
benchmark's copy of the reference against the program's; the new readers on
hand-made runs; where the new entries stand in ``BENCHMARK.json``."""

import ast
import inspect
import json
import types

import pytest

from benchmark import harness, reference_olmoe, shapes_olmoe
from benchmark.readers import _slice, seq_roofline
from benchmark_testkit import REPO, add_cell, last_line, rehearse

CELL = "seq-olmoe.serve-sat"
NEW_METRICS = [
    "seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "seq_program_ms", "seq_experts_ms",
    "seq_attn_ms", "seq_router_ms", "seq_head_ms", "experts_roofline", "attn_roofline",
    "expert_load_max_over_mean",
]
COUNTER_FED = ["seq_tokens_per_s", "pad_token_share", "seq_stage_ms", "expert_load_max_over_mean"]
TINY_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 128,
    "max_position_embeddings": 128,
}


def add_tiny_seq(root):
    """``tiny-seq`` and ``tiny-seq.sat`` as new files and entries of the copy."""
    config = json.loads((REPO / "benchmark" / "configs" / "seq-olmoe.json").read_text())
    config.update(
        TINY_WIDTHS, name="tiny-seq", n_users=300,
        session_length={"median": 24, "sigma": 0.9, "min": 3, "max": 128},
        server_config={"max_batch_size": 8},
    )
    (root / "benchmark" / "configs" / "tiny-seq.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny-seq", "source": "a test's", "file": "benchmark/configs/tiny-seq.json",
         "reduced": [], "why": "a test's"}
    )
    mix = json.loads((REPO / "benchmark" / "traffic" / "sat.json").read_text())
    mix.update(ramp_s=0.5, connections=4, users_drawn=5000, trace_offset_s=0.2, trace_slice_s=0.5)
    (root / "benchmark" / "traffic" / "tiny-seq-sat.json").write_text(json.dumps(mix))
    add_cell(bench, "tiny-seq.sat", "tiny-seq", "tiny-seq-sat", CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_seq_cell_rehearses_on_the_cpu(tiny_root, trace):
    add_tiny_seq(tiny_root)
    proc = rehearse(tiny_root, "tiny-seq.sat", trace, 6)
    line = last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 32
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"answered_qps", "setup_s"}
        assert metrics["answered_qps"]["value"] == pytest.approx(line["attempted"] / 6)
        assert "checked" in proc.stderr and "worst |served - reference| by answer: median" in proc.stderr
        return
    # what the program's counters feed is there; what only a device trace
    # feeds has nothing to read on the CPU and is left out
    assert set(COUNTER_FED) <= set(metrics)
    assert not set(NEW_METRICS) - set(COUNTER_FED) & set(metrics)
    assert {"batch_size.sat", "queue_wait_ms.sat", "slot_wait_ms.sat", "cache_hit_share.sat",
            "host_hops_ms.sat", "sat_latency_p50_ms", "gc_pause_s_in_window.sat",
            "compiles_in_window.sat"} <= set(metrics)
    assert metrics["compiles_in_window.sat"]["value"] == 0
    assert metrics["seq_tokens_per_s"]["value"] > 0
    assert 0 < metrics["pad_token_share"]["value"] < 100
    assert 1.0 <= metrics["expert_load_max_over_mean"]["value"] <= 8.0
    assert metrics["seq_stage_ms"]["value"] > 0


def test_operation_counts_against_hand_worked_ones():
    c = TINY_WIDTHS
    # experts, 100 tokens: 2 experts a token, three 64 x 32 products, 2 flops
    assert shapes_olmoe.experts_flops(100, c) == 2 * 3 * 100 * 2 * 64 * 32 == 2457600
    # 8 experts' three matrices in bf16, 100 rows of 64 float32 in and out
    assert shapes_olmoe.experts_bytes(100, c) == 8 * 3 * 64 * 32 * 2 + 2 * 100 * 64 * 4 == 149504
    # attention, 3 sessions of 64: four 64 x 64 projections, and the causal
    # half of q.k and p.v: 2 * L * hidden a token
    tokens = 3 * 64
    assert shapes_olmoe.attn_flops(3, 64, c) == tokens * (2 * 4 * 64 * 64 + 2 * 64 * 64) == 7864320
    assert shapes_olmoe.attn_bytes(tokens, c) == 4 * 64 * 64 * 2 + 2 * tokens * 64 * 4 == 131072
    assert shapes_olmoe.program_flops(3, 64, c) == 2 * (7864320 + 2 * 3 * tokens * 2 * 64 * 32)
    # at the published widths a token's experts are three quarters of its layer
    published = json.loads((REPO / "benchmark" / "configs" / "seq-olmoe.json").read_text())
    experts = shapes_olmoe.experts_flops(1, published)
    assert experts == 2 * 50_331_648
    assert 0.70 < experts / (experts + shapes_olmoe.attn_flops(1, 1024, published) / 1024) < 0.76


def test_the_benchmarks_reference_is_the_programs_function_for_function():
    from predictionio_tpu.models.sequential import olmoe_reference

    def functions(module):
        return {
            name: inspect.getsource(f) for name, f in inspect.getmembers(module, inspect.isfunction)
            if f.__module__ == module.__name__
        }

    ours, theirs = functions(reference_olmoe), functions(olmoe_reference)
    assert ours.keys() == theirs.keys() and len(ours) >= 15
    for name in ours:
        assert ours[name] == theirs[name], name
    # float32 at `highest`, and nothing of the program's ops/
    source = inspect.getsource(reference_olmoe)
    assert '_HIGHEST = "highest"' in source and "predictionio_tpu" not in source.split('"""', 2)[2]


def test_the_engine_module_imports_the_programs_names_at_its_top():
    # so that a checkout without them (the PR's parent) fails at once
    tree = ast.parse((REPO / "benchmark" / "engines" / "sequential_olmoe.py").read_text())
    top = {
        f"{node.module}.{alias.name}" for node in tree.body if isinstance(node, ast.ImportFrom)
        for alias in node.names
    }
    assert "predictionio_tpu.models.sequential.olmoe" in top
    assert "predictionio_tpu.models.sequential.engine.OlmoeModel" in top


def test_the_new_entries_stand_at_the_end_and_the_old_ones_only_grew():
    # at the END of every list, as the driver's check of the benchmark asks: an
    # entry put in the middle reads there as a change to the one it displaced
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW_METRICS) :] == NEW_METRICS
    assert names[-len(NEW_METRICS) - 1] == "idle_pack_share.train"
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == "seq-olmoe"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["answered_qps"]["workloads"] == ["rec-als-webgraph-de.serve-sat", CELL]
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "answered_qps"
            spec = json.loads((REPO / "benchmark" / "layer_metrics" / f"{m['name']}.json").read_text())
            assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
        elif CELL in m["workloads"]:
            # an accepted metric the new cell joined: appended, and it moves answered_qps
            assert m["workloads"] == ["rec-als-webgraph-de.serve-sat", CELL]
            assert m["moves"] == "answered_qps"
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert cell == {**cell, "config": "seq-olmoe", "traffic": "sat", "chips": 1}
    config = {c["name"]: c for c in bench["configs"]}["seq-olmoe"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert json.loads((REPO / config["file"]).read_text())["num_hidden_layers"] == 8


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
    values = {name: harness.read_metric(REPO, True, name, run) for name in COUNTER_FED}
    assert values == pytest.approx(
        {"seq_tokens_per_s": 700.0, "pad_token_share": 30.0, "seq_stage_ms": 10.0,
         "expert_load_max_over_mean": 1.5}
    )
    # a program without the counters (the parent): every one is left out
    bare = harness.Run(0.0, 51.0, 1, 0, True)
    assert all(harness.read_metric(REPO, True, name, bare) is None for name in NEW_METRICS)


def test_the_roofline_shares_read_a_hand_made_slice(monkeypatch):
    def op(start, end, scope):
        return (start, end, f"%f = f32[] fusion() {scope}", frozenset({f"jit(session_vectors)/while/body/{scope}/gmm/x"}))

    # two executions of the program in the slice: experts 4 ms, attention 1 ms each
    ops = [op(0.0, 8e6, "experts"), op(8e6, 10e6, "attn")]
    profile = _slice.SliceProfile(0.0, 1e9, [], ops)
    monkeypatch.setattr(_slice, "load", lambda run: profile)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = hand_made_run(
        trace=types.SimpleNamespace(programs={"jit_session_vectors": {"count": 2, "seconds": 0.011}}),
        shapes=TINY_WIDTHS, peak=peak,
    )
    # the window's mean program: 20 programs, 320 rows of 64 and 240 of 128
    tokens = (320 * 64 + 240 * 128) / 20
    layers = TINY_WIDTHS["num_hidden_layers"]
    least = max(
        layers * shapes_olmoe.experts_flops(tokens, TINY_WIDTHS) / 197e12,
        layers * shapes_olmoe.experts_bytes(tokens, TINY_WIDTHS) / 819e9,
    )
    assert seq_roofline.read(run, "experts") == pytest.approx(100 * least / 4e-3)
    flops = (shapes_olmoe.attn_flops(320, 64, TINY_WIDTHS) + shapes_olmoe.attn_flops(240, 128, TINY_WIDTHS)) / 20
    least = max(layers * flops / 197e12, layers * shapes_olmoe.attn_bytes(tokens, TINY_WIDTHS) / 819e9)
    assert harness.read_metric(REPO, True, "attn_roofline", run) == pytest.approx(100 * least / 1e-3)
    assert harness.read_metric(REPO, True, "seq_experts_ms", run) == pytest.approx(4.0)
    assert harness.read_metric(REPO, True, "seq_program_ms", run) == pytest.approx(5.5)
    # no trace (an untraced run, the CPU): nothing to read
    assert seq_roofline.read(hand_made_run(shapes=TINY_WIDTHS, peak=peak), "experts") is None


def test_lengths_are_dealt_in_blocks_along_the_stream_the_driver_will_send(monkeypatch):
    import numpy as np

    from benchmark import http_load
    from benchmark.drivers import closed_loop_http, open_loop_http
    from benchmark.engines import sequential_olmoe as engine

    config = json.loads((REPO / "benchmark" / "configs" / "seq-olmoe.json").read_text())
    config.update(n_users=20_000)
    sat = json.loads((REPO / "benchmark" / "traffic" / "sat.json").read_text())
    sat.update(users_drawn=20_000)
    steady = {"kind": "open_loop_http", "rate_qps": 50.0, "ramp_s": 1.0, "user_zipf_exponent": 0.6}
    sent = []
    monkeypatch.setattr(http_load, "measure", lambda ctx, eng, dep, mode, users, due=None: sent.append(users))
    deployment = types.SimpleNamespace(n_users=20_000)
    for driver, traffic in ((closed_loop_http, sat), (open_loop_http, steady)):
        ctx = types.SimpleNamespace(seed=2600000123, traffic=traffic, seconds=6.0)
        driver.measure(ctx, engine, deployment)
        # the engine expects the users the driver sends, in its order
        assert np.array_equal(engine.stream_of(ctx, 20_000), sent[-1])
    ctx = types.SimpleNamespace(seed=1, traffic={"kind": "replay"}, seconds=6.0)
    assert len(engine.stream_of(ctx, 20_000)) == 0  # a kind it does not know: dealt at random

    asked = sent[0]
    _, offsets = engine.sessions_of(config, 2600000123, asked)
    lengths = np.diff(offsets)
    multiset = engine.session_lengths(config)
    assert np.array_equal(np.sort(lengths), multiset)  # whoever has which, the same lengths
    _, first = np.unique(asked, return_index=True)
    in_order = lengths[asked[np.sort(first)]]
    blocks = in_order[: len(in_order) // engine.DEALT_BLOCK * engine.DEALT_BLOCK].reshape(-1, engine.DEALT_BLOCK)
    # every block of first-asked users holds the population's mix (a random
    # 256 of this distribution have a mean that spreads by 7%) ...
    assert np.abs(blocks.mean(axis=1) / multiset.mean() - 1).max() < 0.05
    # ... in random order inside: a batch's 32 spread as a sample does
    batches = blocks.reshape(-1, 32).mean(axis=1) / multiset.mean()
    assert 0.12 < batches.std() < 0.30
    # dealt without the stream, a block is a sample like any other
    _, offsets = engine.sessions_of(config, 2600000123)
    at_random = np.diff(offsets)[asked[np.sort(first)]][: blocks.size].reshape(blocks.shape)
    assert np.abs(at_random.mean(axis=1) / multiset.mean() - 1).max() > 0.10


def test_the_check_refuses_a_stream_other_than_the_one_dealt_along():
    from benchmark.engines import sequential_olmoe as engine

    deployment = types.SimpleNamespace(asked_early={3, 4, 5})
    with pytest.raises(RuntimeError, match="not dealt along the stream"):
        engine.Serving.check(deployment, {4: "{}", 77: "{}"})


def test_another_sessions_answer_fails_the_check_that_the_servers_own_passes():
    import jax
    import numpy as np

    from benchmark.engines import sequential_olmoe as engine
    from predictionio_tpu.models.sequential import OlmoeAlgorithm, OlmoeAlgorithmParams, Query
    from predictionio_tpu.models.sequential.engine import session_tails

    widths = {**TINY_WIDTHS, "rms_norm_eps": 1e-5, "rope_theta": 10000.0}
    algorithm = OlmoeAlgorithm(OlmoeAlgorithmParams(**widths, seed=4))
    rng = np.random.default_rng(8)
    sessions = [rng.integers(0, 128, n).astype(np.int32) for n in (5, 40, 64, 70, 90, 128)]
    model = engine.OlmoeModel(
        algorithm.params.config(), [f"i{i}" for i in range(128)], [f"u{i}" for i in range(6)],
        *session_tails(sessions, 128), engine.olmoe.init_weights(algorithm.params.config(), 4),
    )
    answers = algorithm.predict_batch(model, [Query(user=f"u{i}", num=10) for i in range(6)])
    cache = jax.config.jax_enable_compilation_cache
    try:
        logits, _ = engine.reference_logits(model.weights, widths, sessions)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)

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
    assert all(ids_ok) and engine.count_wrong(errors, ids_ok) == 0
    # the gross fault FLIP_TOLERANCE is there for: two users get each other's answer
    swapped = [answers[1], answers[0]] + answers[2:]
    ids_ok, errors = verdicts(swapped)
    assert ids_ok[:2] == [False, False] and min(errors[:2]) > engine.FLIP_TOLERANCE
    assert engine.count_wrong(errors, ids_ok) == 2


def test_the_ids_are_held_to_the_tight_limit_and_a_tipped_answer_is_flagged():
    import numpy as np

    from benchmark.engines import sequential_olmoe as engine

    logits = np.linspace(4.0, -4.0, 128)  # item i scores 4 - i * 0.063
    session = np.array([120, 121])
    top = list(range(10))
    exact = logits[top]
    assert engine.check_answer(logits, session, top, exact, 126) == (True, False, 0.0)
    # places 3 and 4 changed: the reference holds them 0.063 apart, over twice the tight limit
    turned = [0, 1, 2, 4, 3, 5, 6, 7, 8, 9]
    assert engine.check_answer(logits, session, turned, logits[turned] + 0.005, 126)[:2] == (False, False)
    # the same from an answer that is itself off by 0.05 (a tipped router): by the set, flagged
    ok, by_set, error = engine.check_answer(logits, session, turned, logits[turned] + 0.05, 126)
    assert (ok, by_set) == (True, True) and error == pytest.approx(0.05)
    # an item of the session, an unused row of the vocabulary, an item twice: never
    for ids in ([120] + top[1:], [127] + top[1:], [0, 0] + top[2:]):
        assert engine.check_answer(logits, session, ids, logits[ids], 126)[0] is False
    # far down the reference's order: no error of the answer's own excuses it
    far = top[:9] + [40]
    assert engine.check_answer(logits, session, far, logits[far] + 0.05, 126)[0] is False


def test_the_check_holds_the_median_answer_tight_and_every_answer_loosely():
    from benchmark.engines import sequential_olmoe as engine

    fine = [0.006] * 15 + [0.03, 0.10]  # bf16 everywhere, two answers with a tipped router
    assert engine.count_wrong(fine, [True] * 17) == 0
    assert engine.count_wrong(fine, [True] * 16 + [False]) == 1  # other ids than the reference's
    assert engine.count_wrong(fine[:-1] + [0.3], [True] * 17) == 1  # beyond what a tipped router does
    # what fp8 expert weights read on the chip (PERF.md, PR 26): the largest is
    # inside the flips' range, the median is not
    fp8 = [0.0273, 0.0605, 0.0236, 0.0301, 0.0169, 0.0167, 0.057, 0.0348, 0.0568, 0.0202, 0.0369,
           0.0394, 0.025, 0.0357, 0.0338, 0.0212, 0.0167]
    assert engine.count_wrong(fp8, [True] * 17) == 17
    assert engine.SCORE_TOLERANCE <= 3 * 0.0086 and engine.FLIP_TOLERANCE <= 3 * 0.1024
