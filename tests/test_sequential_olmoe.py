"""The sequential engine's ``olmoe`` scorer against its plain reference, at a
tiny size on the CPU: 2 layers, hidden 64, 4 heads of 16, 8 experts of width
32 with 2 a token, vocabulary 128, sessions of 3 to 70 items packed into
token streams of 256, so that streams of one to four sessions occur.

The weights are the algorithm's own (drawn in bfloat16 from its seed). Where
a test compares values it upcasts the SAME weights to float32 for both sides:
the program's operands follow its weights' type, so on the CPU both sides
compute in float32 and differ only by the order of float32 sums.
"""

import asyncio
import dataclasses
import json
import socket
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import PersistentModelManifest
from predictionio_tpu.models.sequential import (
    OlmoeAlgorithm,
    OlmoeAlgorithmParams,
    OlmoeModel,
    Query,
    TrainingData,
    engine_factory,
    olmoe,
    olmoe_reference as reference,
)
from predictionio_tpu.ops import moe

TINY = dict(
    hidden_size=64, intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, num_experts=8, num_experts_per_tok=2, vocab_size=128,
    max_position_embeddings=128,
)
N_ITEMS = 120  # 8 rows of the vocabulary are no item
# float32 against float32 on the CPU: both sides multiply in float32 and
# differ by the order of their sums (blocked attention, grouped products, a
# scan over experts), a few units of 2^-23 a sum, through two layers. Logits
# are of unit order; the worst seen over the seeds below is 2e-6. 1e-4 is
# fifty times that and a hundred times under what ONE bf16 product does
# (2^-9 = 2e-3), so a lower precision anywhere fails.
ATOL = 1e-4
MEMORY_STORAGE = {
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
}


@pytest.fixture(autouse=True)
def small_programs(monkeypatch):
    """The token budget is a constant the chip set (2,048): here a stream
    holds 256 tokens and four sessions at most, so a dozen sessions take
    several."""
    monkeypatch.setattr(olmoe, "TOKEN_BUDGET", 256)


def staged(algorithm, model, sessions, starts, length):
    """The sessions as ONE stream of ``length`` tokens, each from its start:
    ``_stage``'s arrays but the mask."""
    stream = (length, list(enumerate(starts)))
    return [jnp.asarray(a) for a in algorithm._stage(model, sessions, stream)[:4]]


def training_data(seed=0, n_users=14) -> TrainingData:
    rng = np.random.default_rng(seed)
    # a few distinct lengths (the reference compiles once for each), the
    # ends of both buckets among them
    lengths = rng.choice([3, 17, 40, 64, 65, 70], n_users)
    lengths[:3] = (3, 64, 70)
    return TrainingData(
        [f"u{i}" for i in range(n_users)],
        [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths],
        [f"i{i}" for i in range(N_ITEMS)],
    )


@pytest.fixture(scope="module")
def trained():
    algorithm = OlmoeAlgorithm(OlmoeAlgorithmParams(**TINY, seed=5))
    model = algorithm.train(None, training_data())
    # the same bf16 draws, upcast: both sides then compute in float32
    model.weights = jax.tree.map(lambda a: a.astype(jnp.float32), model.weights)
    return algorithm, model


def reference_weights(model: OlmoeModel) -> dict:
    w = model.weights
    layers = [olmoe.layer_of(w, i) for i in range(model.config.num_hidden_layers)]
    return {**{k: w[k] for k in olmoe.TOP_ARRAYS}, "layers": layers}


_reference_logits: dict = {}  # the `trained` model's, by session: two tests ask for the same
_reference_jit: dict = {}  # one jitted reference a model: one compile a session length


def reference_answer(model: OlmoeModel, session: np.ndarray, num: int):
    """top-``num`` of the reference's logits at the session's last position,
    its items and the vocabulary's unused rows left out."""
    config = dataclasses.asdict(model.config)
    key = session.tobytes()
    if id(model) not in _reference_jit:
        weights = reference_weights(model)
        _reference_jit[id(model)] = jax.jit(
            lambda tokens: reference.next_item_logits(weights, config, tokens)
        )
    if key not in _reference_logits:
        _reference_logits[key] = np.asarray(_reference_jit[id(model)](jnp.asarray(session)))
    logits = _reference_logits[key]
    allowed = np.ones(len(logits), bool)
    allowed[N_ITEMS:] = False
    allowed[session] = False
    order = np.argsort(-np.where(allowed, logits, -np.inf), kind="stable")[:num]
    return logits, order


# ---------------------------------------------------------------- ops/moe


@pytest.mark.parametrize("tokens,k,seed", [(64, 2, 0), (192, 2, 1), (128, 3, 2), (64, 8, 3)])
def test_grouped_experts_equal_the_dense_form(tokens, k, seed):
    rng = np.random.default_rng(seed)
    hidden, width, n_experts = 64, 32, 8
    x = jnp.asarray(rng.normal(size=(tokens, hidden)), jnp.float32)
    layer = {
        "router": jnp.asarray(rng.normal(size=(hidden, n_experts)) / 8, jnp.float32),
        "gate": jnp.asarray(rng.normal(size=(n_experts, hidden, width)) / 8, jnp.float32),
        "up": jnp.asarray(rng.normal(size=(n_experts, hidden, width)) / 8, jnp.float32),
        "down": jnp.asarray(rng.normal(size=(n_experts, width, hidden)) / 6, jnp.float32),
    }
    weights, experts = moe.route(x, layer["router"], k)
    got = moe.expert_ffn(x, weights, experts, layer["gate"], layer["up"], layer["down"])
    want = reference.moe(x, layer, {"num_experts_per_tok": k})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # every token got its k experts, whatever the imbalance: nothing dropped
    group_sizes = moe.expert_load(experts, n_experts)
    assert int(group_sizes.sum()) == tokens * k
    assert np.array_equal(
        np.asarray(group_sizes), np.bincount(np.asarray(experts).ravel(), minlength=n_experts)
    )
    # counted over some of the tokens only (a program's real positions)
    counted = np.arange(tokens) % 3 == 0
    assert np.array_equal(
        np.asarray(moe.expert_load(experts, n_experts, jnp.asarray(counted))),
        np.bincount(np.asarray(experts)[counted].ravel(), minlength=n_experts),
    )
    # the weights are the softmax's own, not renormalised
    probs = np.asarray(reference.router_probs(x, layer))
    np.testing.assert_allclose(
        np.asarray(weights), -np.sort(-probs, axis=1)[:, :k], atol=1e-6
    )
    assert k == n_experts or float(np.asarray(weights).sum(axis=1).max()) < 1.0


@pytest.mark.parametrize("first_group", [0, 8])
def test_the_chips_kernel_interpreted_equals_xlas_ragged_dot(first_group):
    # the megablox kernel as the chip runs it (tiles of 256 rows cut to the
    # block's height), over two layers' stacked groups of which one is live
    rng = np.random.default_rng(6)
    lhs = jnp.asarray(rng.normal(size=(384, 64)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(16, 64, 32)), jnp.float32)
    sizes = np.zeros(16, np.int32)
    sizes[first_group : first_group + 8] = rng.multinomial(384, [1 / 8] * 8)
    sizes = jnp.asarray(sizes)
    got = moe.grouped_matmul_kernel(lhs, rhs, sizes, jnp.float32, interpret=True)
    want = moe.grouped_matmul(lhs, rhs, sizes, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_one_expert_taking_every_token_drops_none():
    # the router sends all tokens to experts 0 and 1: groups of 64, 64, 0, ...
    hidden, width, n_experts, tokens = 64, 32, 8, 64
    rng = np.random.default_rng(4)
    x = jnp.asarray(np.abs(rng.normal(size=(tokens, hidden))), jnp.float32)
    router = np.zeros((hidden, n_experts), np.float32)
    router[:, 0], router[:, 1] = 1.0, 0.5
    layer = {
        "router": jnp.asarray(router),
        "gate": jnp.asarray(rng.normal(size=(n_experts, hidden, width)) / 8, jnp.float32),
        "up": jnp.asarray(rng.normal(size=(n_experts, hidden, width)) / 8, jnp.float32),
        "down": jnp.asarray(rng.normal(size=(n_experts, width, hidden)) / 6, jnp.float32),
    }
    weights, experts = moe.route(x, layer["router"], 2)
    got = moe.expert_ffn(x, weights, experts, layer["gate"], layer["up"], layer["down"])
    assert np.asarray(moe.expert_load(experts, n_experts)).tolist() == [tokens, tokens, 0, 0, 0, 0, 0, 0]
    want = reference.moe(x, layer, {"num_experts_per_tok": 2})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ------------------------------------------------------- the whole model


@pytest.mark.parametrize("length,kernel", [(64, False), (128, False), (64, True)])
def test_full_logits_equal_the_references(trained, length, kernel, monkeypatch):
    _, model = trained
    if kernel:
        # the grouped product the chip serves with, interpreted, through a
        # whole program: every layer's experts stacked, the layer's first group
        traced = []

        def interpreted(*args, **kw):
            traced.append(args[0].shape)
            return moe.grouped_matmul_kernel(*args, interpret=True, **kw)

        monkeypatch.setattr(moe, "grouped_matmul", interpreted)
        olmoe.all_logits.clear_cache()
    rng = np.random.default_rng(length)
    tokens = rng.integers(0, N_ITEMS, (3, length)).astype(np.int32)
    got = np.asarray(olmoe.all_logits(model.weights, jnp.asarray(tokens), config=model.config))
    config = dataclasses.asdict(model.config)
    for row in range(3):
        want = np.asarray(reference.forward(reference_weights(model), config, tokens[row]))
        assert 0.5 < want.std() < 2.0  # logits of unit order, as the scaling promises
        np.testing.assert_allclose(got[row], want, atol=ATOL)
    if kernel:
        assert len(traced) == 3  # the scan's one layer: gate, up, down
        olmoe.all_logits.clear_cache()


def test_the_busiest_experts_count_leaves_the_padding_out(trained):
    algorithm, model = trained
    session = np.random.default_rng(13).integers(0, N_ITEMS, 37).astype(np.int32)
    counts = []
    for start, length, pad in ((0, 64, 0), (64, 128, 5), (128, 256, 0)):
        tokens, segment, position, last = staged(algorithm, model, [session], [start], length)
        tokens = jnp.where(segment < 0, pad, tokens)
        _, busiest = olmoe.session_vectors(
            model.weights, tokens, segment, position, last, config=model.config
        )
        counts.append(int(busiest))
    # 37 real tokens with 2 experts each over 2 layers, whatever is padded around them
    assert counts[0] == counts[1] == counts[2]
    assert 2 * 37 * 2 / 8 <= counts[0] <= 2 * 37


@pytest.mark.parametrize("users", [(0,), (0, 1, 2, 3, 4, 5, 6, 7), (3, 3, 9, 1)])
def test_the_answer_hook_launches_one_prefill_and_one_top_k_a_program_and_counts_as_before(
    trained, users, monkeypatch
):
    """``predict_batch_dispatch`` stages, then ``_answer`` (the hook a
    generating backbone answers otherwise) launches: for this backbone one
    ``session_vectors`` and one ``dot_top_k_async`` a PROGRAM (``_programs``:
    a stream, or several as its rows), the counters of before, and none of a
    generation's."""
    from predictionio_tpu.models.sequential.engine import BackboneAlgorithm
    from predictionio_tpu.ops import topk

    _, model = trained
    algorithm = OlmoeAlgorithm(OlmoeAlgorithmParams(**TINY, seed=5))
    assert type(algorithm)._answer is BackboneAlgorithm._answer
    assert type(algorithm).predict_batch_dispatch is BackboneAlgorithm.predict_batch_dispatch
    calls = {"session_vectors": 0, "dot_top_k_async": 0}
    program, prefill, ending = model.program(), model.program().session_vectors, topk.dot_top_k_async

    def counted(name, function):
        def call(*args, **kwargs):
            calls[name] += 1
            return function(*args, **kwargs)

        return call

    monkeypatch.setattr(program, "session_vectors", counted("session_vectors", prefill))
    monkeypatch.setattr(topk, "dot_top_k_async", counted("dot_top_k_async", ending))
    queries = [Query(user=f"u{u}", num=4) for u in users]
    sessions, streams = algorithm._plan(model, queries)
    programs = algorithm._programs(model, streams)
    assert sorted(i for rows in programs for i in rows) == list(range(len(streams)))
    answers = algorithm.predict_batch_dispatch(model, queries)()
    assert [len(a.item_scores) for a in answers] == [4] * len(users)
    assert all(s.step is None and set(s.to_json_dict()) == {"item", "score"} for a in answers for s in a.item_scores)
    assert calls == {"session_vectors": len(programs), "dot_top_k_async": len(programs)}
    counters = algorithm.instruments
    real = sum(len(s) for s in sessions)
    assert counters.tokens.value(kind="real") == real
    assert counters.tokens.value(kind="padded") == sum(length for length, _ in streams)
    assert sum(counters.programs.value(bucket=str(b)) for b in model.config.stream_shapes()) == len(programs)
    assert sum(counters.rows.value(bucket=str(b)) for b in model.config.stream_shapes()) == len(streams)
    assert sum(counters.sessions.value(bucket=str(b)) for b in model.config.stream_shapes()) == len(users)
    assert counters.batches.value() == 1 and counters.stage_seconds.value() > 0
    assert counters.expert_tokens_mean.value() == model.config.even_expert_load(real)
    assert counters.passes.value(kind="denoise") == counters.passes.value(kind="commit") == 0
    assert counters.blocks.value() == counters.generated_items.value() == counters.cache_bytes.value() == 0


def test_two_deployments_in_one_process_count_apart(trained):
    from predictionio_tpu.obs.metrics import MetricsRegistry

    _, model = trained
    one, other = (OlmoeAlgorithm(OlmoeAlgorithmParams(**TINY, seed=1)) for _ in range(2))
    served = MetricsRegistry()
    one.register_metrics(served)  # as a query server does before traffic
    one.predict(model, Query(user="u0", num=3))
    assert served.get("pio_seq_tokens_total").value(kind="real") == len(
        model.session_tokens(Query(user="u0"))
    )
    assert served.get("pio_seq_batches_total").value() == 1
    assert other.instruments.tokens.value(kind="real") == 0
    assert other.instruments.registry is not served


def test_right_padding_changes_no_real_positions_output(trained):
    _, model = trained
    rng = np.random.default_rng(9)
    session = rng.integers(0, N_ITEMS, 37).astype(np.int32)
    outs = []
    for bucket, pad in ((64, 0), (64, 77), (128, 5)):
        tokens = np.full((2, bucket), pad, np.int32)
        tokens[0, :37] = session
        logits = olmoe.all_logits(model.weights, jnp.asarray(tokens), config=model.config)
        outs.append(np.asarray(logits)[0, :37])
    # the same bucket with other padding: the same program, bit for bit
    assert np.array_equal(outs[0], outs[1])
    # a longer bucket is another program: float32 sums in another order
    np.testing.assert_allclose(outs[0], outs[2], atol=ATOL)


def test_a_batch_of_mixed_lengths_is_answered_in_order_as_the_reference_does(trained):
    algorithm, model = trained
    td = training_data()
    rng = np.random.default_rng(11)
    queries = [Query(user=u, num=5) for u in td.users]
    # explicit recentItems win over the stored tail; unknown items are dropped
    recent = tuple(f"i{i}" for i in rng.integers(0, N_ITEMS, 9)) + ("no-such-item",)
    queries.insert(4, Query(user="u0", recent_items=recent, num=7))
    queries.insert(9, Query(user="nobody", num=5))  # no session: no items
    sessions, streams = algorithm._plan(model, queries)
    assert {length for length, _ in streams} == {256}  # no session is longer: one shape
    # more sessions than one stream holds, and some streams hold several
    assert len(streams) >= 2 and max(len(members) for _, members in streams) >= 2
    assert sorted(i for _, members in streams for i, _ in members) == [
        i for i in range(len(queries)) if i != 9
    ]
    answers = algorithm.predict_batch_dispatch(model, queries)()
    assert len(answers) == len(queries) and answers[9].item_scores == ()
    for query, session, answer in zip(queries, sessions, answers):
        if not len(session):
            continue
        logits, order = reference_answer(model, session, query.num)
        ids = [int(s.item[1:]) for s in answer.item_scores]
        assert len(ids) == query.num and not set(ids) & set(session.tolist())
        np.testing.assert_allclose([s.score for s in answer.item_scores], logits[ids], atol=ATOL)
        for place, (got, want) in enumerate(zip(ids, order)):
            # the reference's item, but where it scores the two within the tolerance
            assert got == want or abs(logits[got] - logits[want]) <= 2 * ATOL, place
    assert np.array_equal(sessions[4], [int(i[1:]) for i in recent[:-1]])


def test_one_query_equals_its_row_of_a_batch(trained):
    algorithm, model = trained
    queries = [Query(user=f"u{i}", num=4) for i in (2, 1, 0, 5)]
    batch = algorithm.predict_batch(model, queries)
    alone = algorithm.predict(model, queries[1])
    assert [s.item for s in alone.item_scores] == [s.item for s in batch[1].item_scores]
    np.testing.assert_allclose(
        [s.score for s in alone.item_scores], [s.score for s in batch[1].item_scores], atol=ATOL
    )


def test_stream_shapes_are_a_small_closed_set(monkeypatch):
    monkeypatch.undo()  # the constants the chip set
    config = OlmoeAlgorithmParams().config()
    # the ladder the benchmark's check pads its references by
    assert config.buckets() == (64, 128, 256, 512, 1024, 2048, 4096)
    # what compiles: the budget, and the longest session's where it is longer
    assert config.stream_shapes() == (2048, 4096)
    assert olmoe.SESSION_ALIGN == 64 and olmoe.TOKEN_BUDGET // olmoe.SESSION_ALIGN == 32
    assert olmoe.stream_shapes(256, 128) == (256,) and olmoe.stream_shapes(256, 300) == (256, 320)


# sessions (their lengths) of ONE stream, where each starts, the stream's
# length, the budget and the longest session the model takes
PACKED = {
    "one ends inside a chunk, one is exactly 64": ((37, 64, 100), (0, 64, 128), 256, 256, 512),
    "one longer than the budget shares its stream": ((300, 64, 17, 40), (0, 320, 384, 448), 512, 256, 512),
    "32 sessions at the chip's budget": (tuple(range(33, 65)), tuple(range(0, 2048, 64)), 2048, 2048, 4096),
    "2,049 to 4,096 items beside others": ((2100, 1000, 64, 500), (0, 2112, 3136, 3200), 4096, 2048, 4096),
}


@pytest.mark.parametrize("case", list(PACKED))
def test_a_packed_streams_session_vectors_equal_the_sessions_alone(case, monkeypatch):
    lengths, starts, length, budget, longest = PACKED[case]
    monkeypatch.setattr(olmoe, "TOKEN_BUDGET", budget)
    params = OlmoeAlgorithmParams(**{**TINY, "max_position_embeddings": longest}, seed=4)
    algorithm = OlmoeAlgorithm(params)
    rng = np.random.default_rng(len(lengths))
    sessions = [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths]
    model = algorithm.train(None, TrainingData(["u"], [sessions[0]], [f"i{i}" for i in range(N_ITEMS)]))
    model.weights = jax.tree.map(lambda a: a.astype(jnp.float32), model.weights)
    assert length in model.config.stream_shapes()
    packed, _ = olmoe.session_vectors(
        model.weights, *staged(algorithm, model, sessions, starts, length), config=model.config
    )
    assert packed.shape == (budget // 64, 64)
    for row, session in enumerate(sessions):
        # alone, from the stream's first position: the same compiled program
        alone, _ = olmoe.session_vectors(
            model.weights, *staged(algorithm, model, [session], [0], length), config=model.config
        )
        np.testing.assert_allclose(packed[row], alone[0], atol=ATOL, rtol=0, err_msg=f"session {row}")
    # ... and the model's own answer at the session's true length
    logits = olmoe.all_logits(model.weights, jnp.asarray(sessions[1])[None], config=model.config)
    head = np.asarray(model.weights["lm_head"], np.float32)
    np.testing.assert_allclose(np.asarray(packed[1]) @ head.T, np.asarray(logits)[0, -1], atol=ATOL)


# ------------------------------------------- streams as the rows of a program

# the sessions (their lengths) of four streams of 256 tokens, each from its
# start: a full one, one with a padded end, a lone short session, four sessions
ROWS = (
    ((37, 64, 100), (0, 64, 128)), ((70, 17), (0, 128)), ((3,), (0,)), ((64, 64, 64, 40), (0, 64, 128, 192)),
)


def stacked_streams(algorithm, model, seed=0):
    """``ROWS`` staged one by one, as ``_answer`` is handed them."""
    rng = np.random.default_rng(seed)
    sessions, staged_rows = [], []
    for lengths, starts in ROWS:
        members = [(len(sessions) + j, start) for j, start in enumerate(starts)]
        sessions += [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths]
        staged_rows.append(algorithm._stage(model, sessions, (256, members)))
    return staged_rows


def vectors_of(algorithm, model, staged_rows, rows, program=olmoe.session_vectors):
    *arrays, _ = algorithm._stack([staged_rows[r] for r in rows])
    out, _ = program(model.weights, *map(jnp.asarray, arrays), config=model.config)
    return np.asarray(out).reshape(len(rows), -1, out.shape[-1])


@pytest.mark.parametrize("rows", [(0, 1), (0, 1, 2, 3), (3, 3, 0, 2)])
def test_streams_stacked_as_rows_equal_the_streams_alone(trained, rows):
    algorithm, model = trained
    staged_rows = stacked_streams(algorithm, model)
    stacked = vectors_of(algorithm, model, staged_rows, rows)
    assert stacked.shape == (len(rows), 4, 64)
    for r, row in enumerate(rows):
        alone = vectors_of(algorithm, model, staged_rows, (row,))
        held = len(ROWS[row][0])
        np.testing.assert_allclose(stacked[r, :held], alone[0, :held], atol=ATOL, rtol=0, err_msg=f"row {r}")


def test_a_key_leaked_from_the_row_in_front_moves_the_vectors(trained, monkeypatch):
    """What the equality above can tell: a program whose row sees ONE key of
    the row in front of it (the first position's) is off by the vectors' own
    order, not by a rounding."""
    algorithm, model = trained
    staged_rows = stacked_streams(algorithm, model)
    sound = vectors_of(algorithm, model, staged_rows, (0, 1, 2, 3))
    attend = olmoe.fused_attention

    def leaky(q, k, v, **kwargs):
        k = k.at[:, :, 0].set(jnp.roll(k, 1, axis=0)[:, :, 0])
        return attend(q, k, v, **kwargs)

    monkeypatch.setattr(olmoe, "fused_attention", leaky)
    # (a function of its own: a jit's traces are kept by the function traced)
    planted = jax.jit(lambda *a, config: olmoe.session_vectors.__wrapped__(*a, config=config), static_argnames=("config",))
    leaked = vectors_of(algorithm, model, staged_rows, (0, 1, 2, 3), planted)
    for r, (lengths, _) in enumerate(ROWS):
        assert np.abs(leaked[r, : len(lengths)] - sound[r, : len(lengths)]).max() > 100 * ATOL, r


@pytest.fixture(scope="module")
def stacking():
    """An algorithm and a model whose sessions reach 512 items, so that the
    streams are of 256 tokens and of 512, and users by their session's length."""
    lengths = [17, 40, 60, 500] + [150] * 8
    rng = np.random.default_rng(38)
    algorithm = OlmoeAlgorithm(OlmoeAlgorithmParams(**{**TINY, "max_position_embeddings": 512}, seed=6))
    users = [f"u{i}" for i in range(len(lengths))]
    model = algorithm.train(None, TrainingData(
        users, [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths], [f"i{i}" for i in range(N_ITEMS)],
    ))
    model.weights = jax.tree.map(lambda a: a.astype(jnp.float32), model.weights)
    return algorithm, model, users


# batches by the streams they make at a budget of 256 tokens and sessions of
# up to 512 items: (items a session; the programs as (rows, a row's tokens))
BATCHES = {
    "one query": ((40,), [(1, 256)]),
    "three streams and a bit": ((150, 150, 150, 40, 17, 60), [(1, 256)] * 3),
    "a four, two left over and a long one": (
        (150, 150, 500, 150, 40, 150, 150, 17, 150), [(4, 256), (1, 256), (1, 256), (1, 512)],
    ),
    "two fours": ((150,) * 8, [(4, 256), (4, 256)]),
}


@pytest.mark.parametrize("case", list(BATCHES))
def test_the_answer_hook_stacks_whole_fours_and_answers_in_the_queries_order(stacking, case):
    algorithm, model, users = stacking
    lengths, shapes = BATCHES[case]
    assert model.program().STACKED_ROWS == 4
    pool = {n: [u for u in users if len(model.session_tokens(Query(user=u))) == n] for n in set(lengths)}
    queries = [Query(user=pool[n].pop(), num=5) for n in lengths]
    _, streams = algorithm._plan(model, queries)
    programs = algorithm._programs(model, streams)
    assert sorted((len(rows), streams[rows[0]][0]) for rows in programs) == sorted(shapes)
    assert all(len({streams[i][0] for i in rows}) == 1 for rows in programs)  # a program's rows are of one length
    counters = algorithm.instruments
    before = {b: (counters.programs.value(bucket=b), counters.rows.value(bucket=b)) for b in ("256", "512")}
    answers = algorithm.predict_batch(model, queries)
    for b in ("256", "512"):
        launched = [rows for rows, length in shapes if str(length) == b]
        assert counters.programs.value(bucket=b) - before[b][0] == len(launched)
        assert counters.rows.value(bucket=b) - before[b][1] == sum(launched)
    for query, answer in zip(queries, answers):
        alone = algorithm.predict(model, query)  # one stream, one row
        assert [s.item for s in answer.item_scores] == [s.item for s in alone.item_scores], query.user
        assert 1 <= len(answer.item_scores) <= 5  # (a session of 500 leaves few of 120 items)
        np.testing.assert_allclose(
            [s.score for s in answer.item_scores], [s.score for s in alone.item_scores], atol=ATOL, rtol=0
        )


def test_warmup_serving_compiles_every_shape_of_the_closed_set(stacking):
    """After the warm-up no batch compiles: not one query, not streams short
    of a four, not a four with leftovers and a long stream beside it."""
    from jax import monitoring

    algorithm, model, users = stacking
    compiled = []

    def listener(event, duration_secs, **kw):
        if event.endswith("/backend_compile_duration"):
            compiled.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    algorithm.warmup_serving(model, 64)
    warmed, traced = len(compiled), model.program().session_vectors._cache_size()
    for lengths, _ in BATCHES.values():
        pool = {n: [u for u in users if len(model.session_tokens(Query(user=u))) == n] for n in set(lengths)}
        answers = algorithm.predict_batch(model, [Query(user=pool[n].pop(), num=10) for n in lengths])
        assert len(answers) == len(lengths) and all(a.item_scores for a in answers)
    assert len(compiled) == warmed and model.program().session_vectors._cache_size() == traced


def drawn_as_the_cells_draw(seed, n):
    rng = np.random.default_rng(seed)
    return np.clip(np.rint(np.exp(rng.normal(np.log(256), 1.0, n))), 16, 4096).astype(int)


@pytest.mark.parametrize("seed,n", [(0, 32), (1, 32), (2, 64), (3, 7), (4, 1)])
def test_the_plan_never_splits_a_session_nor_overfills_a_stream(seed, n, monkeypatch):
    monkeypatch.undo()  # the constants the chip set
    algorithm = OlmoeAlgorithm(OlmoeAlgorithmParams(**{**TINY, "max_position_embeddings": 4096}, seed=1))
    lengths = drawn_as_the_cells_draw(seed, n)
    lengths[0] = 4096 if seed == 0 else lengths[0]
    users = [f"u{i}" for i in range(n)]
    rng = np.random.default_rng(seed)
    model = algorithm.train(None, TrainingData(
        users, [rng.integers(0, N_ITEMS, k).astype(np.int32) for k in lengths],
        [f"i{i}" for i in range(N_ITEMS)],
    ))
    queries = [Query(user=u, num=3) for u in users] + [Query(user="nobody", num=3)]
    sessions, streams = algorithm._plan(model, queries)
    assert [len(s) for s in sessions] == [*lengths, 0]
    placed = sorted(i for _, members in streams for i, _ in members)
    assert placed == list(range(n))  # each session once, whole; the empty one nowhere
    for length, members in streams:
        assert length in (2048, 4096) and 1 <= len(members) <= 32
        # a stream of 4,096 is opened only by a session that needs it
        assert length == 2048 or len(sessions[members[0][0]]) > 2048
        ends = [0]
        for i, start in members:
            assert start % 64 == 0 and start >= ends[-1]  # aligned, behind the one before
            ends.append(start + len(sessions[i]))
        assert ends[-1] <= length
    padded = sum(length for length, _ in streams)
    assert padded < 2 * max(2048, int(lengths.sum()))  # packed: under half of it padding
    tokens, segment, position, last, mask = algorithm._stage(model, sessions, streams[0])
    assert tokens.shape == segment.shape == position.shape == (1, streams[0][0])
    assert last.shape == (32,) and mask.shape == (32, 128)
    for row, (i, start) in enumerate(streams[0][1]):
        own = slice(start, start + len(sessions[i]))
        assert (segment[0, own] == row).all() and last[row] == own.stop - 1
        assert np.array_equal(tokens[0, own], sessions[i])
        assert np.array_equal(position[0, own], np.arange(len(sessions[i])))
        assert not mask[row, sessions[i]].any() and not mask[row, N_ITEMS:].any()
    assert int((segment >= 0).sum()) == sum(len(sessions[i]) for i, _ in streams[0][1])
    assert (last[len(streams[0][1]):] == -1).all() and not mask[len(streams[0][1]):].any()


def test_warmup_serving_leaves_nothing_to_compile():
    from jax import monitoring

    # a width no other test of this process compiles
    algorithm = OlmoeAlgorithm(OlmoeAlgorithmParams(**{**TINY, "intermediate_size": 16}, seed=2))
    td = training_data(seed=3, n_users=16)
    model = algorithm.train(None, td)
    compiled = []

    def listener(event, duration_secs, **kw):
        if event.endswith("/backend_compile_duration"):
            compiled.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    algorithm.warmup_serving(model, 64)
    warmed = len(compiled)
    assert warmed >= len(model.config.stream_shapes())
    answers = algorithm.predict_batch(model, [Query(user=u, num=10) for u in td.users])
    assert all(len(a.item_scores) == 10 for a in answers)
    assert len(compiled) == warmed


def test_unimplemented_config_values_are_refused_not_ignored():
    for key, value in (
        ("norm_topk_prob", True), ("attention_bias", True), ("clip_qkv", 8.0),
        ("num_key_value_heads", 2), ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ):
        with pytest.raises(ValueError, match=key):
            OlmoeAlgorithmParams(**{**TINY, key: value}).config()


def test_the_variant_file_carries_the_published_config_verbatim():
    from pathlib import Path

    import predictionio_tpu.models.sequential as package

    variant = json.loads((Path(package.__file__).parent / "variants" / "olmoe-1b-7b.json").read_text())
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304,
    }
    raw = variant["algorithms"][0]["params"]
    assert {k: raw[k] for k in published} == published
    assert {k: getattr(params, k) for k in published} == published
    assert dataclasses.asdict(OlmoeAlgorithmParams(seed=params.seed)) == dataclasses.asdict(params)


# ---------------------------------------------------------- persistence


def test_save_then_load_is_equal_bit_for_bit(tmp_path):
    algorithm = OlmoeAlgorithm(OlmoeAlgorithmParams(**TINY, seed=7))
    model = algorithm.train(None, training_data(seed=1))
    assert model.weights["gate"].dtype == jnp.bfloat16  # drawn in bf16, stacked by layer
    assert model.weights["gate"].shape == (2, 8, 64, 32)
    assert model.save("m1", algorithm.params, str(tmp_path))
    files = sorted(p.name for p in (tmp_path / "m1").iterdir())
    assert files == sorted([f"{name}.bin" for name in [*model.weights, "tails", "offsets"]] + ["olmoe.json"])
    # raw arrays: a file is exactly its array's bytes
    assert (tmp_path / "m1" / "gate.bin").stat().st_size == 2 * 8 * 64 * 32 * 2
    loaded = OlmoeModel.load("m1", algorithm.params, str(tmp_path))
    assert loaded.config == model.config
    assert loaded.item_vocab == model.item_vocab and loaded.users == model.users
    assert np.array_equal(loaded.tails, model.tails) and np.array_equal(loaded.offsets, model.offsets)
    assert loaded.weights.keys() == model.weights.keys()
    for name, array in model.weights.items():
        assert loaded.weights[name].dtype == array.dtype, name
        assert np.array_equal(
            np.asarray(loaded.weights[name]).view(np.uint8), np.asarray(array).view(np.uint8)
        ), name
    queries = [Query(user=f"u{i}", num=5) for i in range(6)]
    assert algorithm.predict_batch(loaded, queries) == algorithm.predict_batch(model, queries)
    # a truncated file is an error that names it, not a wrong model
    with open(tmp_path / "m1" / "wo.bin", "r+b") as f:
        f.truncate(100)
    with pytest.raises(ValueError, match="wo.bin"):
        OlmoeModel.load("m1", algorithm.params, str(tmp_path))


def test_the_model_repository_holds_a_manifest_not_the_weights(tmp_path, monkeypatch):
    from predictionio_tpu.workflow import model_io

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    engine = engine_factory()
    params = engine.engine_params_from_variant(
        {
            "datasource": {"params": {"appName": "seq"}},
            "algorithms": [{"name": "olmoe", "params": {**TINY, "seed": 7}}],
        }
    )
    _, _, (algorithm,), _ = engine.make_components(params)
    model = algorithm.train(None, training_data(seed=1))
    (persisted,) = engine.make_serializable_models(None, params, [model])
    assert isinstance(persisted, PersistentModelManifest)
    assert persisted.class_path == "predictionio_tpu.models.sequential.engine.OlmoeModel"
    blob = model_io.serialize_models([persisted])
    assert len(blob) < 1024  # what train → deploy passes through the pickled blob
    assert (tmp_path / "models" / persisted.model_id / "olmoe.json").is_file()
    (deployed,) = engine.prepare_deploy(None, params, model_io.deserialize_models(blob))
    assert isinstance(deployed, OlmoeModel)
    queries = [Query(user=f"u{i}", num=5) for i in range(4)]
    assert algorithm.predict_batch(deployed, queries) == algorithm.predict_batch(model, queries)


def test_session_tails_keep_the_last_items_in_one_array():
    from predictionio_tpu.models.sequential.engine import session_tails

    sequences = [np.arange(5, dtype=np.int32), np.arange(200, dtype=np.int32), np.empty(0, np.int32)]
    tails, offsets = session_tails(sequences, keep=128)
    assert tails.dtype == np.int32 and offsets.tolist() == [0, 5, 133, 133]
    assert tails[:5].tolist() == [0, 1, 2, 3, 4] and tails[5:].tolist() == list(range(72, 200))


# --------------------------------------------------------------- server


def test_query_server_answers_mixed_lengths_over_http_and_counts_them(trained):
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.workflow.create_server import QueryServer, ServerConfig
    from predictionio_tpu.workflow.engine_loader import EngineManifest

    _, model = trained
    engine = engine_factory()
    params = engine.engine_params_from_variant(
        {
            "datasource": {"params": {"appName": "seq"}},
            "algorithms": [{"name": "olmoe", "params": {**TINY, "seed": 5}}],
        }
    )
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    server = QueryServer(
        engine=engine, engine_params=params, models=[model],
        manifest=EngineManifest(
            engine_id="seq", version="1", variant="engine.json",
            engine_factory="predictionio_tpu.models.sequential.engine_factory",
        ),
        instance_id="seq", storage=Storage(env=MEMORY_STORAGE),
        config=ServerConfig(ip="127.0.0.1", port=port, max_batch_size=32),
    )
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(server.start())
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    assert started.wait(120)

    def post(body: dict) -> dict:
        request = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json", json.dumps(body).encode(),
            {"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request, timeout=30) as resp:
            return json.loads(resp.read())

    def scrape() -> dict:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            lines = resp.read().decode().splitlines()
        return {k: float(v) for k, _, v in (l.rpartition(" ") for l in lines if l and l[0] != "#")}

    try:
        # a scorer has no batch limit of its own: the operator's stands
        assert server.algorithms[0].batch_limit() is None and server._batcher.max_batch == 32
        before = scrape()
        td = training_data()
        replies = {}

        def ask(user):
            replies[user] = post({"user": user, "num": 6})

        threads = [threading.Thread(target=ask, args=(u,)) for u in td.users]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for user, session in zip(td.users, td.sequences):
            logits, order = reference_answer(model, session, 6)
            rows = replies[user]["itemScores"]
            ids = [int(r["item"][1:]) for r in rows]
            assert len(ids) == 6 and not set(ids) & set(session.tolist())
            np.testing.assert_allclose([r["score"] for r in rows], logits[ids], atol=ATOL)
            assert all(
                g == w or abs(logits[g] - logits[w]) <= 2 * ATOL for g, w in zip(ids, order)
            )
        with_items = post({"recentItems": ["i3", "i4", "i5"], "num": 3})["itemScores"]
        assert len(with_items) == 3 and not {"i3", "i4", "i5"} & {r["item"] for r in with_items}
        after = scrape()

        def grown(key):
            return after.get(key, 0.0) - before.get(key, 0.0)

        real = sum(len(s) for s in td.sequences) + 3
        assert grown('pio_seq_tokens_total{kind="real"}') == real
        padded = grown('pio_seq_tokens_total{kind="padded"}')
        # a stream of 256 tokens here is one row of a program of its "bucket"
        programs, rows = grown('pio_seq_programs_total{bucket="256"}'), grown('pio_seq_rows_total{bucket="256"}')
        assert padded == 256 * rows > real and rows >= programs
        assert grown('pio_seq_sessions_total{bucket="256"}') == len(td.users) + 1
        assert programs >= 2 and grown("pio_seq_batches_total") >= 1
        assert grown("pio_seq_stage_seconds_total") > 0
        # per layer and program the busiest expert has at least the mean
        mean = grown("pio_moe_expert_tokens_mean_total")
        assert mean == 2 * real * 2 / 8  # real tokens' copies: the padding is not counted
        assert mean <= grown("pio_moe_expert_tokens_max_total") <= 8 * mean
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=30)
