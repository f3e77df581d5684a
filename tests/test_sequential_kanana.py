"""The ``kanana`` algorithm (kanana-2-30b-a3b's block behind the sequential
engine, answering token by token over a latent cache) at a tiny size on
seeded weights: the prefill and the steps through the cache against the plain
reference's whole-sequence forward, the two forms of latent attention against
each other, the parts (interleaved RoPE, the router, the shared experts)
against hand-worked values, and what the engine does with a group (packing,
masks, groups, ``num``, counters, storage, the server)."""

import asyncio
import dataclasses
import json
import socket
import threading
import types
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from predictionio_tpu.models.sequential import (
    KananaAlgorithm, KananaAlgorithmParams, KananaModel, Query, engine_factory, kanana,
    kanana_reference as reference,
)
from predictionio_tpu.models.sequential.engine import GroupedAlgorithm, SdarAlgorithm, session_tails
from predictionio_tpu.ops import attention, moe

TINY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, qk_head_dim=24, head_dim=8, v_head_dim=16, n_routed_experts=8,
    num_experts_per_tok=3, n_shared_experts=2, vocab_size=256, max_position_embeddings=600,
)
N_ITEMS = 200
# float32 against float32: the order of the sums (tests/test_sequential_olmoe.py); logits are of
# unit order. A cache rounded to float8 (three mantissa bits: 2**-4 a value) and a product whose
# operands are rounded to bfloat16 (2**-9 an operand, through three layers) each move a logit by
# thirty times this and more
ATOL = 2e-4
LENGTHS = (5, 40, 64, 70, 17, 100, 3)
MEMORY_STORAGE = {
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
}


@pytest.fixture(autouse=True)
def small_programs(monkeypatch):
    """Streams of 256 tokens (512 where a session is longer), four sessions a
    stream at most."""
    monkeypatch.setattr(kanana, "TOKEN_BUDGET", 256)
    monkeypatch.setattr(kanana, "MAX_SESSION", 512)


def small(config, **changes):
    return dataclasses.replace(config, **{"cache_tokens": 1024, "generated_slots": 8, **changes})


def deployment(lengths=LENGTHS, seed=5, dtype=jnp.float32, **changes):
    """``(algorithm, model, sessions, the reference's config)``: the
    sessions' items distinct (so that what an answer may not repeat is plain)."""
    params = KananaAlgorithmParams(**TINY, seed=seed)
    config = small(params.config(), **changes)
    rng = np.random.default_rng(seed)
    sessions = [rng.choice(N_ITEMS, n, replace=n > N_ITEMS).astype(np.int32) for n in lengths]
    model = KananaModel(
        config, [f"i{i}" for i in range(N_ITEMS)], [f"u{i}" for i in range(len(lengths))],
        *session_tails(sessions, 512), kanana.init_weights(config, seed, dtype),
    )
    model.sanity_check()
    return KananaAlgorithm(params), model, sessions, dataclasses.asdict(params)


PADDED = 128  # the reference's one compiled length: every layer is causal, so what lies behind is not seen
_forward: dict = {}


@pytest.fixture
def padded_forward(monkeypatch):
    """``reference.forward`` on the sequence right-padded to ``PADDED``, one
    compile for the file: the sequence's own rows are what they are alone."""
    if not _forward:
        plain = reference.forward
        _forward["jit"] = jax.jit(lambda weights, tokens, config: plain(weights, dict(config), tokens), static_argnums=2)

    def forward(weights, config, tokens):
        tokens = np.asarray(tokens)
        padded = np.concatenate([tokens, np.zeros(PADDED - len(tokens), tokens.dtype)])
        frozen = tuple(sorted((k, v) for k, v in config.items() if not isinstance(v, dict)))
        return _forward["jit"](weights, padded, frozen)[: len(tokens)]

    monkeypatch.setattr(reference, "forward", forward)


def answers_of(algorithm, model, num, users=None):
    users = range(len(model.users)) if users is None else users
    nums = num if isinstance(num, (list, tuple)) else [num] * len(users)
    return algorithm.predict_batch(model, [Query(user=f"u{u}", num=n) for u, n in zip(users, nums)])


def as_rows(answer):
    return [(int(s.item[1:]), s.score) for s in answer.item_scores]


def spied(algorithm, model, num, monkeypatch, users=None, before_first=None):
    """``(answers, [the logits [SESSIONS, vocabulary] of the first pick and of
    every step], the state's rows by user)``: the engine's own launch, its two
    generating programs replaced by their unjitted bodies' logits."""
    logits, rows = [], {}
    first = jax.jit(kanana._first, static_argnames="config")
    step = jax.jit(kanana._step, static_argnames="config")

    def first_pick(weights, state, *, config):
        if before_first is not None:
            state = before_first(state)
        out, state = first(weights, state, config=config)
        logits.append(np.asarray(out))
        return state

    def decode_step(weights, state, *, config):
        out, state = step(weights, state, config=config)
        logits.append(np.asarray(out))
        return state

    launch = algorithm._launch_group

    def launch_group(*args):
        launched = launch(*args)
        rows.update({i: s for i, s, _ in launched[0]})
        return launched

    with monkeypatch.context() as patch:
        patch.setattr(kanana, "first_pick", first_pick)
        patch.setattr(kanana, "decode_step", decode_step)
        patch.setattr(algorithm, "_launch_group", launch_group)
        answers = answers_of(algorithm, model, num, users)
    return answers, logits, rows


def fp8_cache(monkeypatch):
    plain = kanana._latent
    monkeypatch.setattr(kanana, "_latent", lambda *a: lax.reduce_precision(plain(*a), 4, 3))


def one_pass_products(monkeypatch):
    def project(x, w):
        rounded = x.astype(jnp.bfloat16).astype(jnp.float32), w.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.dot(*rounded, preferred_element_type=jnp.float32)

    monkeypatch.setattr(kanana, "_project", project)


@pytest.mark.parametrize("fault", [None, fp8_cache, one_pass_products])
def test_prefill_and_steps_give_the_references_logits_at_every_generated_position(fault, padded_forward, monkeypatch):
    algorithm, model, sessions, plain = deployment()
    if fault is not None:
        fault(monkeypatch)
        kanana.session_vectors.clear_cache()
    num = 6
    try:
        answers, logits, rows = spied(algorithm, model, num, monkeypatch)
    finally:
        kanana.session_vectors.clear_cache()
    assert len(logits) == num and all(len(a.item_scores) == num for a in answers)
    worst = 0.0
    for user, (session, answer) in enumerate(zip(sessions, answers)):
        items = [item for item, _ in as_rows(answer)]
        # ONE forward of the session and what was chosen: its rows from the
        # session's last position on score each generated position
        whole = np.asarray(reference.forward(model.weights, plain, np.concatenate([session, items[:-1]])))
        want = whole[len(session) - 1 :]
        got = np.stack([step[rows[user]] for step in logits])
        worst = max(worst, float(np.abs(got - want).max()))
        if fault is None:
            allowed = reference.candidates(plain, session, N_ITEMS)
            for g, (item, score) in enumerate(as_rows(answer)):
                logp = reference.log_probabilities(want[g], allowed)
                assert item == int(np.argmax(logp)) and score == pytest.approx(float(logp[item]), abs=ATOL)
                allowed[item] = False
    assert worst < ATOL if fault is None else worst > 30 * ATOL


def test_the_padded_forward_is_the_plain_one_on_the_sequences_own_rows(padded_forward, monkeypatch):
    _, model, sessions, plain = deployment()
    padded = np.asarray(reference.forward(model.weights, plain, sessions[4]))
    monkeypatch.undo()
    np.testing.assert_allclose(padded, reference.forward(model.weights, plain, sessions[4]), atol=2e-5)


def test_answers_equal_the_references_own_plain_loop(padded_forward):
    algorithm, model, sessions, plain = deployment()
    for user in (0, 3):
        answer = as_rows(answers_of(algorithm, model, 4, [user])[0])
        want = reference.generate(model.weights, plain, sessions[user], 4, N_ITEMS)
        assert [item for item, _ in answer] == [item for item, _ in want]
        np.testing.assert_allclose([s for _, s in answer], [s for _, s in want], atol=ATOL)


@pytest.mark.parametrize("length", [1, 7, 33])
def test_the_absorbed_form_is_the_expanded_form(length):
    """One layer, one session: the last position's attention by the step's
    absorbed form over a cache that holds the positions before it, against
    the prefill's expanded form over the whole session."""
    _, model, _, plain = deployment()
    config, layer = model.config, kanana.layer_of(model.weights, 1)
    x = jax.random.normal(jax.random.key(length), (1, length, config.hidden_size), jnp.float32)
    position = jnp.arange(length, dtype=jnp.int32)[None]
    slots = 128

    @jax.jit
    def both(x, layer):
        n1 = kanana._rms(x, layer["w_in"], config.rms_norm_eps)
        kept = kanana._latent(n1, position, layer, config)
        q = jnp.concatenate(kanana._queries(n1, position, layer, config), axis=-1).transpose(0, 2, 1, 3)
        k, v = kanana._expanded(kept, layer, config)
        out = attention.attention_reference(q, k, v, causal=True).transpose(0, 2, 1, 3).reshape(1, length, -1)
        expanded = (x + kanana._project(out, layer["wo"]))[0, -1]
        # the cache holds every position but the last; the step writes that one
        cache = jnp.zeros((slots, config.latent_width), jnp.float32).at[3 : 3 + length - 1].set(kept[0, :-1])
        ids_k = jnp.full(slots, -1, jnp.int32).at[3 : 3 + length].set(0)
        absorbed, cache = kanana._mla_absorbed(
            x[0, -1:], position[0, -1:], jnp.zeros(1, jnp.int32), ids_k, jnp.asarray([3 + length - 1]), cache, layer, config
        )
        return expanded, absorbed[0], cache[3 + length - 1], kept[0, -1], reference.mixer_block(x[0], layer, plain)[-1]

    expanded, absorbed, written, kept, want = both(x, layer)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5)
    np.testing.assert_allclose(written, kept, atol=1e-6)
    # ... and both are the reference's
    np.testing.assert_allclose(expanded, want, atol=2e-5)


def test_interleaved_rope_turns_a_hand_worked_pair_and_the_keys_part_once():
    # dimensions 2 and 3 (pair j = 1 of d = 8) at position 3, theta 100: the angle is 3 * 100 ** (-2 / 8)
    x = np.zeros((1, 1, 1, 8), np.float32)
    x[..., 2], x[..., 3] = 1.0, 2.0
    angle = 3 * 100 ** (-2 / 8)
    out = np.asarray(kanana._rope_interleaved(jnp.asarray(x), jnp.asarray([[3]]), 100.0))[0, 0, 0]
    want = np.zeros(8)
    want[1], want[4 + 1] = np.cos(angle) - 2 * np.sin(angle), 2 * np.cos(angle) + np.sin(angle)
    np.testing.assert_allclose(out, want, atol=1e-6)
    np.testing.assert_allclose(reference.rope(x[0, :, 0], 100.0, jnp.asarray([3]))[0], want, atol=1e-6)
    # a pair's score is the interleaved pair's own: the turn keeps the product of two vectors turned alike
    _, model, _, plain = deployment()
    config, layer = model.config, kanana.layer_of(model.weights, 0)
    n1 = jax.random.normal(jax.random.key(1), (1, 9, config.hidden_size), jnp.float32)
    position = jnp.arange(9, dtype=jnp.int32)[None]
    kept = kanana._latent(n1, position, layer, config)
    # ONE rotary key a token, no head axis: all heads read these 8 values
    assert kept.shape == (1, 9, config.kv_lora_rank + config.qk_rope_head_dim)
    c, k_r = reference.latent(n1[0], layer, plain)
    np.testing.assert_allclose(kept[0], np.concatenate([c, k_r], axis=-1), atol=1e-5)
    # the latent is cached NORMALISED
    np.testing.assert_allclose(
        np.sqrt(np.mean(np.square(kept[0, :, : config.kv_lora_rank] / np.asarray(layer["kv_norm"])), axis=-1)), 1.0, atol=1e-3
    )


def test_the_routers_bias_moves_the_choice_and_not_the_weight():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.5, jnp.float32)
    weights, experts = moe.route_sigmoid(x, router, bias, 3, 2.448, eps=kanana.ROUTER_EPS)
    scores = reference.router_scores(x, {"router": router})
    want = np.asarray(reference.router_choice(scores, bias, 3, 2.448))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # renormalised, times the scaling factor
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 2.448, rtol=1e-6)
    # the choice is by score + bias (another than by score for some token), the weight by score alone
    _, unbiased = moe.route_sigmoid(x, router, jnp.zeros(8), 3, 2.448)
    assert (np.sort(np.asarray(experts), axis=1) != np.sort(np.asarray(unbiased), axis=1)).any()
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(experts), axis=1)
    np.testing.assert_allclose(np.asarray(weights), 2.448 * chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-5)
    # by hand: scores (0.5, 0.5, 0.4), bias (0, -1, 0), two of three: the first and the third
    hand = reference.router_choice(jnp.asarray([[0.5, 0.5, 0.4]]), jnp.asarray([0.0, -1.0, 0.0]), 2, 2.0)
    np.testing.assert_allclose(hand, [[2.0 * 0.5 / 0.9, 0.0, 2.0 * 0.4 / 0.9]], rtol=1e-6)


def test_two_shared_experts_are_one_product_of_twice_the_width():
    _, model, _, _ = deployment()
    layer, w = kanana.layer_of(model.weights, 1), TINY["moe_intermediate_size"]
    assert layer["shared_gate"].shape == (64, 2 * w) and layer["shared_down"].shape == (2 * w, 64)
    x = jax.random.normal(jax.random.key(0), (11, 64), jnp.float32)
    one = moe.gated_mlp(x, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    two = sum(
        moe.gated_mlp(x, layer["shared_gate"][:, cut], layer["shared_up"][:, cut], layer["shared_down"][cut])
        for cut in (slice(0, w), slice(w, 2 * w))
    )
    np.testing.assert_allclose(one, two, atol=1e-5)


def test_a_session_packed_behind_another_is_answered_as_it_is_alone():
    algorithm, model, _, _ = deployment()
    together = answers_of(algorithm, model, 5)
    for user in (0, 2, 6):
        alone = as_rows(answers_of(algorithm, model, 5, [user])[0])
        assert [item for item, _ in alone] == [item for item, _ in as_rows(together[user])]
        np.testing.assert_allclose([s for _, s in alone], [s for _, s in as_rows(together[user])], atol=ATOL)


def test_a_session_never_reads_another_sessions_slots(monkeypatch):
    """What the cache holds for ANOTHER session is spoiled before the first
    step (large and finite: a probability of 0 times a NaN is a NaN, which is
    why the cache starts as zeros): nobody else's answer moves by a bit."""
    algorithm, model, _, _ = deployment()
    sound = [as_rows(a) for a in answers_of(algorithm, model, 5)]

    def spoil(state):
        latents, vectors = state["cache"]
        theirs = jnp.concatenate([state["seg"] == 0, jnp.zeros(model.config.cache_slots - state["seg"].shape[0], bool)])
        return {**state, "cache": (tuple(jnp.where(theirs[:, None], 1e4, a) for a in latents), vectors)}

    answers, _, rows = spied(algorithm, model, 5, monkeypatch, before_first=spoil)
    victim = next(user for user, row in rows.items() if row == 0)
    assert as_rows(answers[victim]) != sound[victim]
    for user, answer in enumerate(answers):
        if user != victim:
            assert as_rows(answer) == sound[user]


def test_a_session_that_reaches_its_num_early_changes_nobodys_answer():
    algorithm, model, _, _ = deployment()
    users = [1, 2, 3]
    even = [as_rows(a) for a in answers_of(algorithm, model, [6, 6, 6], users)]
    mixed = [as_rows(a) for a in answers_of(algorithm, model, [2, 6, 1], users)]
    assert mixed[0] == even[0][:2] and mixed[1] == even[1] and mixed[2] == even[2][:1]


@pytest.mark.parametrize(
    "streams, want",
    [
        ([(256, [0] * 4)] * 4, [[0, 1, 2, 3]]),  # 1,024 tokens: the cache's room, to the token
        ([(256, [0] * 2)] * 5, [[0, 1, 2, 3], [4]]),  # a fifth stream is past it
        ([(256, [0] * 4)] * 3 + [(512, [0])], [[0, 1, 2], [3]]),
        ([(64, [0] * 20), (64, [0] * 12), (64, [0])], [[0, 1], [2]]),  # 32 sessions: a group's rows
        ([], []),
    ],
)
def test_groups_are_cut_at_the_caches_room_and_at_the_sessions_a_group_holds(streams, want):
    model = types.SimpleNamespace(program=lambda: kanana, config=types.SimpleNamespace(cache_tokens=1024))
    assert KananaAlgorithm._groups(model, streams) == want
    # one base for both generating algorithms
    assert KananaAlgorithm._groups is SdarAlgorithm._groups is GroupedAlgorithm._groups
    assert KananaAlgorithm(KananaAlgorithmParams(**TINY)).batch_limit() == kanana.SESSIONS == 32


def test_a_batch_past_the_caches_room_is_answered_in_more_than_one_group(monkeypatch):
    algorithm, model, _, _ = deployment(cache_tokens=256)
    launches = []
    launch = algorithm._launch_group
    monkeypatch.setattr(algorithm, "_launch_group", lambda *a: launches.append(len(a[3])) or launch(*a))
    several = [as_rows(a) for a in answers_of(algorithm, model, 3)]
    assert len(launches) >= 2 and all(streams == 1 for streams in launches)
    one_group, whole, _, _ = deployment()
    assert [[item for item, _ in a] for a in several] == [
        [item for item, _ in as_rows(a)] for a in answers_of(one_group, whole, 3)
    ]


def test_an_answer_holds_no_item_of_its_session_and_none_twice():
    # 200 items, a session of 190: ten candidates for eight places
    algorithm, model, sessions, _ = deployment(lengths=(190, 12))
    long, short = answers_of(algorithm, model, 8)
    for session, answer in ((sessions[0], long), (sessions[1], short)):
        items = [item for item, _ in as_rows(answer)]
        assert len(items) == len(set(items)) == 8 and not set(items) & set(session.tolist())
        assert all(0 <= item < N_ITEMS for item in items)
        scores = [score for _, score in as_rows(answer)]
        assert all(score <= 0 for score in scores)
    # ten candidates: the first is chosen among ten, the eighth among three
    assert as_rows(long)[7][1] >= np.log(1 / 3) - 2.0 and as_rows(long)[0][1] >= np.log(1 / 10) - 2.0
    # num is cut to the places the state has; a session that holds every item ends early
    algorithm, model, sessions, _ = deployment(lengths=(198, 6))
    ends, cut = answers_of(algorithm, model, 20)
    assert len(ends.item_scores) == 2 and len(cut.item_scores) == model.config.generated_slots == 8
    # no session, no answer
    assert algorithm.predict_batch(model, [Query(user="nobody", num=3)])[0].item_scores == ()


def test_num_1_is_the_prefill_alone_and_the_counters_count_what_ran(monkeypatch):
    algorithm, model, sessions, _ = deployment()
    counters = algorithm.instruments
    with monkeypatch.context() as patch:
        patch.setattr(kanana, "decode_step", lambda *a, **k: pytest.fail("a step ran"))
        assert all(len(a.item_scores) == 1 for a in answers_of(algorithm, model, 1))
    assert counters.passes.value(kind="decode") == 0 and counters.generated_items.value() == len(LENGTHS)
    config = model.config
    assert counters.cache_bytes.value() == sum(LENGTHS) * config.num_hidden_layers * 40 * 4 // 2
    kanana_step = kanana.decode_step
    steps = []
    monkeypatch.setattr(kanana, "decode_step", lambda *a, **k: steps.append(1) or kanana_step(*a, **k))
    answers = answers_of(algorithm, model, [4, 2, 1, 3, 4, 4, 4])
    assert [len(a.item_scores) for a in answers] == [4, 2, 1, 3, 4, 4, 4]
    assert len(steps) == 3 == counters.passes.value(kind="decode")
    assert counters.generated_items.value() == len(LENGTHS) + 22
    assert counters.passes.value(kind="denoise") == counters.passes.value(kind="commit") == 0
    # the steps' real rows are routed too: 22 - 7 of them through two sparse layers of three experts a token
    stepped = 22 - 7
    prefilled = 2 * sum(LENGTHS) - sum(LENGTHS) + len(LENGTHS)  # the last layer routes the last positions alone
    assert counters.copies.value(where="held") == 3 * (2 * prefilled + 2 * stepped) and stepped == 15
    assert counters.experts_offered.value() == 3 * 2 * 8
    assert 0 < counters.experts_reached.value() <= counters.experts_offered.value()
    assert counters.batches.value() == 2 and counters.programs.value(bucket="256") == 6  # 576 aligned tokens a batch
    assert (
        counters.cache_bytes.value()
        == (2 * sum(LENGTHS) + stepped) * config.cache_bytes(1) == (2 * sum(LENGTHS) + stepped) * 3 * 40 * 2
    )


def test_the_step_attends_through_the_kernel_as_through_the_plain_path(monkeypatch):
    """``fused_attention(value_width=)``: the values cut out of the keys' tile,
    both kernels interpreted, against the plain path with the values sliced."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 1, 256, 40)), jnp.float32)
    for slots in (512, 4096):  # the single-block kernel, the tiled one
        k = jnp.asarray(rng.normal(size=(1, 1, slots, 40)), jnp.float32)
        ids_q = jnp.asarray(np.repeat(np.arange(8), 32)[None], jnp.int32).at[0, -32:].set(-1)
        ids_k = jnp.asarray(np.sort(rng.integers(-1, 8, slots))[None], jnp.int32)
        want = attention.attention_reference(q, k, k[..., :32], segment=(ids_q, ids_k))
        plain = attention.fused_attention(q, k, None, segment=(ids_q, ids_k), value_width=32)
        kernel = attention.fused_attention(q, k, None, segment=(ids_q, ids_k), value_width=32, force_pallas=True)
        assert kernel.shape == (1, 1, 256, 32)
        np.testing.assert_allclose(plain, want, atol=1e-5)
        np.testing.assert_allclose(kernel, want, atol=3e-2)  # the kernel multiplies in bfloat16
    with pytest.raises(ValueError, match="value_width"):
        attention.fused_attention(q, k, k, value_width=32)
    with pytest.raises(ValueError, match="value_width"):
        attention.fused_attention(q, k, None)


def test_warmup_serving_leaves_nothing_to_compile():
    from jax import monitoring

    # a width no other test of this process compiles
    params = KananaAlgorithmParams(**{**TINY, "moe_intermediate_size": 16}, seed=2)
    rng = np.random.default_rng(3)
    sessions = [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in (5, 17, 64, 70, 100, 33, 260)]
    model = KananaModel(
        small(params.config()), [f"i{i}" for i in range(N_ITEMS)], [f"u{i}" for i in range(7)],
        *session_tails(sessions, 512), kanana.init_weights(small(params.config()), 2),
    )
    algorithm = KananaAlgorithm(params)
    compiled = []

    def listener(event, duration_secs, **kw):
        if event.endswith("/backend_compile_duration"):
            compiled.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    algorithm.warmup_serving(model, 64)
    warmed = len(compiled)
    assert warmed >= 4  # two stream lengths, the first pick and the step
    answers = answers_of(algorithm, model, 5)
    assert all(len(a.item_scores) == 5 for a in answers)
    assert len(compiled) == warmed


def test_unimplemented_config_values_are_refused_not_ignored():
    for key, value in (
        ("q_lora_rank", 768), ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
        ("norm_topk_prob", False), ("rope_interleave", False), ("rope_scaling", {"type": "yarn"}),
        ("tie_word_embeddings", True), ("moe_layer_freq", 2), ("model_type", "deepseek_v2"),
        ("num_key_value_heads", 2), ("qk_head_dim", 32), ("head_dim", 16), ("attention_bias", True),
    ):
        with pytest.raises(ValueError, match=key):
            KananaAlgorithmParams(**{**TINY, key: value}).config()


def test_the_variant_file_carries_the_published_config_verbatim():
    from pathlib import Path

    import predictionio_tpu.models.sequential as package

    variant = json.loads((Path(package.__file__).parent / "variants" / "kanana-2-30b-a3b.json").read_text())
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    raw = variant["algorithms"][0]["params"]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(l) for l in catalog.read_text().splitlines() if '"kanana-2-30b-a3b-instruct-2601"' in l)
        assert {k: raw[k] for k in row["config"]} == row["config"] and set(raw) == set(row["config"]) | {"seed"}
    assert variant["algorithms"][0]["name"] == "kanana" and raw["num_hidden_layers"] == 48
    config = params.config()
    assert (config.num_attention_heads, config.kv_lora_rank, config.latent_width) == (32, 512, 576)
    assert (config.n_routed_experts, config.num_experts_per_tok, config.n_shared_experts) == (128, 6, 2)
    assert config.routed_scaling_factor == 2.448 and config.sparse_layers == 47 and config.is_dense(0)
    assert config.cache_slots == 32768 and config.cache_tokens == 31744 and config.generated_slots == 32
    assert config.fit(16) == 16 and config.fit(50) == 32 and config.cache_bytes(1) == 48 * 1152
    assert config.stream_shapes() == (256, 512)  # (this file's small programs; 2,048 and 4,096 as shipped)
    # the size, reckoned from the tree's own shapes: six layers are 3.79 B parameters
    six = dataclasses.replace(config, num_hidden_layers=6)
    assert sum(int(np.prod(shape)) for shape in kanana.weight_shapes(six).values()) == pytest.approx(3.79e9, rel=2e-3)


def test_save_then_load_is_equal_bit_for_bit(tmp_path):
    algorithm, model, _, _ = deployment()
    model.save("m1", None, str(tmp_path))
    loaded = KananaModel.load("m1", None, str(tmp_path))
    assert loaded.config == model.config and loaded.item_vocab == model.item_vocab
    for name, array in model.weights.items():
        np.testing.assert_array_equal(np.asarray(loaded.weights[name]), np.asarray(array))
    assert [as_rows(a) for a in answers_of(algorithm, loaded, 4)] == [
        as_rows(a) for a in answers_of(algorithm, model, 4)
    ]


def test_query_server_answers_num_items_in_order_with_their_scores_over_http():
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.workflow.create_server import QueryServer, ServerConfig
    from predictionio_tpu.workflow.engine_loader import EngineManifest

    algorithm, model, sessions, _ = deployment()
    engine = engine_factory()
    params = engine.engine_params_from_variant(
        {
            "datasource": {"params": {"appName": "seq"}},
            "algorithms": [{"name": "kanana", "params": {**TINY, "seed": 5}}],
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
        config=ServerConfig(ip="127.0.0.1", port=port, max_batch_size=64),
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
    assert started.wait(300)

    def post(body: dict) -> dict:
        request = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json", json.dumps(body).encode(),
            {"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request, timeout=60) as resp:
            return json.loads(resp.read())

    try:
        # the operator's 64 is cut to the sessions ONE group of steps holds: the algorithm says so
        assert server._batcher.max_batch == server.algorithms[0].batch_limit() == 32
        rows = post({"user": "u1", "num": 5})["itemScores"]
        assert [(int(r["item"][1:]), r["score"]) for r in rows] == as_rows(answers_of(algorithm, model, 5, [1])[0])
        assert all(set(r) == {"item", "score"} and r["score"] < 0 for r in rows)
        assert not {int(r["item"][1:]) for r in rows} & set(sessions[1].tolist())
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        assert 'pio_seq_passes_total{kind="decode"}' in text and "pio_seq_cache_bytes_total" in text
        assert "pio_moe_experts_reached_total" in text and 'kind="denoise"' not in text
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=30)
