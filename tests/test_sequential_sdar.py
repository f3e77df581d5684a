"""The sequential engine's ``sdar`` algorithm against its plain reference, at
a tiny size on the CPU: 3 layers, hidden 64, 4 query heads reading 2
key/value heads of 16, 8 experts of width 32 with 2 a token (renormalised),
vocabulary 128 whose last id is the mask, blocks of 4; sessions of 3 to 70
items packed into token streams of 256, a cache of 1,024 stream slots and 16
passes' chunks, 12 generated places a session.

Both sides compute in float32 here (the program's operands follow its
weights' type): the prefill, the batch's cache and the denoise and commit
passes against the reference's forward of the WHOLE sequence at every step.
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

from predictionio_tpu.models.sequential import (
    Query,
    SdarAlgorithm,
    SdarAlgorithmParams,
    SdarModel,
    engine_factory,
    sdar,
    sdar_reference as reference,
)
from predictionio_tpu.models.sequential.engine import session_tails
from predictionio_tpu.ops import attention, moe, topk

TINY = dict(
    hidden_size=64, moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2, vocab_size=128,
)
N_ITEMS = 120  # the last 8 rows of the vocabulary are no item; 127 is the mask
MASK = 127
ATOL = 1e-4  # float32 against float32: the order of the sums (tests/test_sequential_olmoe.py)
PADDED = 96  # the reference's one compiled length: later blocks never reach back
LENGTHS = (16, 17, 18, 19, 5, 40, 3)  # every L mod 4, and one under a block
MEMORY_STORAGE = {
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
}


@pytest.fixture(autouse=True)
def small_programs(monkeypatch):
    """Streams of 256 tokens (512 where a session is longer), four sessions
    a stream at most."""
    monkeypatch.setattr(sdar, "TOKEN_BUDGET", 256)
    monkeypatch.setattr(sdar, "MAX_SESSION", 512)


def small(config, **changes):
    return dataclasses.replace(config, **{"cache_tokens": 1024, "most_passes": 16, "generated_slots": 12, **changes})


def deployment(steps=4, lengths=LENGTHS, seed=5, **changes):
    """``(algorithm, model, sessions, the reference's config)``: float32
    weights, the sessions' items distinct (so that what an answer may not
    repeat is plain)."""
    params = SdarAlgorithmParams(**TINY, denoising_steps=steps, seed=seed)
    config = small(params.config(), **changes)
    rng = np.random.default_rng(seed)
    sessions = [rng.choice(N_ITEMS, n, replace=n > N_ITEMS).astype(np.int32) for n in lengths]
    model = SdarModel(
        config, [f"i{i}" for i in range(N_ITEMS)], [f"u{i}" for i in range(len(lengths))],
        *session_tails(sessions, 512), sdar.init_weights(config, seed, jnp.float32),
    )
    model.sanity_check()
    plain = {**dataclasses.asdict(params), "mask_token_id": MASK}
    return SdarAlgorithm(params), model, sessions, plain


def reference_weights(model) -> dict:
    w = model.weights
    layers = [sdar.layer_of(w, i) for i in range(model.config.num_hidden_layers)]
    return {**{k: w[k] for k in ("embed", "final_norm", "lm_head")}, "layers": layers}


_forward: dict = {}


@pytest.fixture
def padded_forward(monkeypatch):
    """``reference.forward`` compiled ONCE: a sequence of whole blocks
    right-padded with the mask to ``PADDED`` positions (a block sees no later
    one, so the sequence's own rows are what they are alone). A sequence
    that ends inside a block (an answer's short last block) would see the
    padding beside it: it goes through the reference as it is."""

    def forward(weights, config, tokens):
        if len(tokens) % int(config["block_length"]):
            return plain_forward(weights, config, tokens)
        key = (id(weights["embed"]), json.dumps(config, sort_keys=True, default=str))
        if key not in _forward:
            _forward[key] = jax.jit(lambda t: plain_forward(weights, config, t))
        tokens = np.asarray(tokens, np.int32)
        padded = np.concatenate([tokens, np.full(PADDED - len(tokens), MASK, np.int32)])
        return _forward[key](jnp.asarray(padded))[: len(tokens)]

    plain_forward = forward.plain = reference.forward
    monkeypatch.setattr(reference, "forward", forward)
    return forward


def test_the_padded_forward_is_the_plain_one_on_the_sequences_own_rows(padded_forward):
    _, model, sessions, plain = deployment()
    weights = reference_weights(model)
    tokens = np.concatenate([sessions[1], [MASK] * 3])  # a partial block and its masks: 20 positions
    # against the reference as it is, at the sequence's own length
    np.testing.assert_allclose(
        np.asarray(padded_forward(weights, plain, tokens)),
        np.asarray(padded_forward.plain(weights, plain, tokens)), atol=1e-5,
    )


# ------------------------------------------------- the generation, end to end


def answers_of(algorithm, model, num, users=None):
    users = range(len(model.users)) if users is None else users
    return algorithm.predict_batch(model, [Query(user=f"u{u}", num=num) for u in users])


def as_rows(answer):
    return [(int(s.item[1:]), s.score, s.step) for s in answer.item_scores]


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_answers_equal_the_references_own_plain_loop(steps, padded_forward):
    """Items, steps and log-probabilities of every L mod 4 (and a session
    shorter than a block) against ``reference.generate``, which keeps no
    cache and recomputes the whole sequence at every step."""
    algorithm, model, sessions, plain = deployment(steps)
    weights = reference_weights(model)
    for session, answer in zip(sessions, answers_of(algorithm, model, 7)):
        want = reference.generate(weights, plain, session, 7, N_ITEMS)
        got = as_rows(answer)
        assert [g[0] for g in got] == [w[0] for w in want]
        assert [g[2] for g in got] == [w[2] for w in want]
        np.testing.assert_allclose([g[1] for g in got], [w[1] for w in want], atol=ATOL)
        ids = [g[0] for g in got]
        assert len(set(ids)) == 7 and not set(ids) & set(session.tolist()) and max(ids) < N_ITEMS
        assert max(g[2] for g in got) < steps and all(g[1] < 0 for g in got)


# a pass with its logits handed out (serving's ``denoise_pass`` drops them)
pass_with_logits = jax.jit(sdar._pass, static_argnums=2)


def spied_passes(algorithm, model, num, monkeypatch):
    """The served batch (ONE group) with every pass's logits kept:
    ``(answers, [(block [S], tick [S], blocks [S], logits [S, B, V]), ...],
    {query: its row of the state})``."""
    seen, rows = [], {}
    launch = algorithm._launch_group

    def launched(*args):
        out = launch(*args)
        rows.update(dict(out[0]))
        return out

    monkeypatch.setattr(algorithm, "_launch_group", launched)

    def spy(weights, state, *, config):
        logits, after = pass_with_logits(weights, state, config)
        logits = logits.reshape(state["tokens"].shape[0], config.block_length, -1)
        seen.append(tuple(np.asarray(a) for a in (state["block"], state["tick"], state["blocks"], logits)))
        return after

    monkeypatch.setattr(sdar, "denoise_pass", spy)
    return answers_of(algorithm, model, num), seen, rows


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_every_passes_logits_equal_the_references_forward_of_the_whole_sequence(
    steps, padded_forward, monkeypatch
):
    """Prefill, cache, denoise and commit passes: the logits of a session's
    current block at EVERY pass (denoise: the block as it stood; commit: the
    clean block) against the reference's forward of the sequence replayed
    from the reply to that state, for L mod 4 = 0, 1, 2, 3."""
    algorithm, model, sessions, plain = deployment(steps)
    weights = reference_weights(model)
    num = 7
    answers, passes, row_of = spied_passes(algorithm, model, num, monkeypatch)
    schedules = [model.config.schedule(len(s), num) for s in sessions]
    assert len(passes) == max(map(len, schedules))
    checked = 0
    for user, (session, answer) in enumerate(zip(sessions, answers)):
        rows, s = as_rows(answer), row_of[user]
        items, fixed_at = [r[0] for r in rows], [r[2] for r in rows]
        for block, tick, blocks, logits in passes:
            if block[s] >= blocks[s]:
                continue  # done: rides along
            # (a commit pass reads the block with every step behind it)
            tokens, low, masked, _ = reference.state_at(
                plain, session, items, fixed_at, int(block[s]), int(tick[s]) if tick[s] < steps else steps
            )
            want = np.asarray(padded_forward(weights, plain, tokens))[low:]
            np.testing.assert_allclose(logits[s, : len(want)], want, atol=ATOL)
            checked += 1
    assert checked == sum(map(len, schedules))


def test_a_sessions_schedule_is_its_denoise_passes_and_a_commit_between_blocks():
    config = small(SdarAlgorithmParams(**TINY).config())
    assert config.schedule(16, 16) == "ddddcddddcddddcdddd"  # 16 denoise and 3 commits: 19
    assert config.schedule(17, 16) == "dddcddddcddddcddddcd"  # 3 of its first block, 1 of its last: 20
    assert config.schedule(18, 16) == "ddcddddcddddcddddcdd" and len(config.schedule(19, 16)) == 20
    assert config.schedule(16, 0) == "" and config.schedule(3, 1) == "d"
    two = dataclasses.replace(config, denoising_steps=2)
    assert two.schedule(16, 8) == "ddcdd" and two.schedule(17, 8) == "ddcddcd" and two.choices == 2
    assert config.choices == 1 and config.cache_slots == 1024 + 16 * 128 and config.chunk == 128
    # an answer is cut to the places of the state (12, the partial block's items among them) ...
    assert [config.fit(length, 50) for length in (16, 17, 18, 19)] == [12, 11, 10, 9]
    # ... and to the passes the cache has chunks for
    few = dataclasses.replace(config, most_passes=6)
    assert [few.fit(length, 50) for length in (16, 17, 18, 19)] == [5, 5, 5, 5] and few.fit(16, 3) == 3
    assert all(len(few.schedule(length, few.fit(length, 50))) <= 6 for length in range(3, 40))


def test_a_packed_streams_sessions_are_answered_as_the_same_sessions_alone():
    algorithm, model, sessions, _ = deployment()
    together = answers_of(algorithm, model, 5)
    for u in range(len(sessions)):
        (alone,) = answers_of(algorithm, model, 5, [u])
        assert [r[0] for r in as_rows(alone)] == [r[0] for r in as_rows(together[u])]
        np.testing.assert_allclose(
            [r[1] for r in as_rows(alone)], [r[1] for r in as_rows(together[u])], atol=ATOL
        )


def test_a_batch_past_the_caches_capacity_is_answered_in_more_than_one_group(monkeypatch):
    lengths = (100, 100, 90, 90, 80, 80, 70, 70, 64, 60, 50, 33, 20)
    algorithm, model, sessions, _ = deployment(lengths=lengths, cache_tokens=512)
    queries = [Query(user=f"u{u}", num=4) for u in range(len(lengths))]
    planned, streams = algorithm._plan(model, queries)
    groups = algorithm._groups(model, streams)
    assert len(groups) >= 2 and sorted(i for g in groups for i in g) == list(range(len(streams)))
    assert all(sum(streams[i][0] for i in g) <= 512 for g in groups)
    launched = []
    launch = algorithm._launch_group
    monkeypatch.setattr(
        algorithm, "_launch_group", lambda *a: launched.append(1) or launch(*a)
    )
    together = algorithm.predict_batch(model, queries)
    assert len(launched) == len(groups)
    wide = dataclasses.replace(model.config, cache_tokens=1024)
    model.config = wide
    assert len(algorithm._groups(model, streams)) < len(groups)
    for one, other in zip(together, algorithm.predict_batch(model, queries)):
        assert [r[0] for r in as_rows(one)] == [r[0] for r in as_rows(other)] and len(as_rows(one)) == 4
        np.testing.assert_allclose([r[1] for r in as_rows(one)], [r[1] for r in as_rows(other)], atol=ATOL)
    # more than the passes' sessions in a batch: groups of 32 at most
    many = [(f"u{u % len(lengths)}") for u in range(40)]
    _, streams = algorithm._plan(model, [Query(user=u, num=1) for u in many])
    assert all(sum(len(streams[i][1]) for i in g) <= sdar.SESSIONS for g in algorithm._groups(model, streams))


def test_num_is_cut_to_what_the_state_and_the_cache_hold_and_none_is_none():
    algorithm, model, sessions, _ = deployment()
    few, none, empty = algorithm.predict_batch(
        model, [Query(user="u1", num=50), Query(user="u2", num=0), Query(user="nobody", num=3)]
    )
    assert len(few.item_scores) == model.config.fit(17, 50) == 11
    assert none.item_scores == () and empty.item_scores == ()
    with pytest.raises(ValueError, match="mask"):
        SdarModel(
            model.config, [f"i{i}" for i in range(128)], model.users, model.tails, model.offsets, model.weights
        ).sanity_check()


# ------------------------------------------------------------ planted faults


def differs_from_the_reference(algorithm, model, sessions, plain, forward, num=7) -> bool:
    """Whether some served answer is NOT the reference's (another item, or a
    log-probability off by ten times the tests' tolerance)."""
    weights = reference_weights(model)
    for session, answer in zip(sessions, answers_of(algorithm, model, num)):
        want = reference.generate(weights, plain, session, num, N_ITEMS)
        got = as_rows(answer)
        if [g[0] for g in got] != [w[0] for w in want] or [g[2] for g in got] != [w[2] for w in want]:
            return True
        if np.abs(np.asarray([g[1] for g in got]) - np.asarray([w[1] for w in want])).max() > 10 * ATOL:
            return True
    return False


def plant(fault: str, monkeypatch):
    """Each fault the reference has to tell, planted in the program."""
    fused = sdar.fused_attention
    if fault == "a denoise pass's keys and values kept in place of the commit's":
        new_state = sdar.new_state

        def stale(weights, config, seg, commits, *rest):
            # later blocks read the chunk of the pass BEFORE a block's commit:
            # what its last denoise pass wrote, one position still masked
            return new_state(weights, config, seg, np.roll(commits, -1, axis=0), *rest)

        monkeypatch.setattr(sdar, "new_state", stale)
    elif fault == "a key of the next block seen":
        monkeypatch.setattr(
            sdar, "fused_attention",
            lambda q, k, v, **kw: fused(q, k, v, **{**kw, "block": 2 * kw["block"]} if kw.get("block") else kw),
        )
    elif fault == "a token-causal mask inside the block":
        monkeypatch.setattr(
            sdar, "fused_attention", lambda q, k, v, **kw: fused(q, k, v, **{**kw, "block": None})
        )
    elif fault == "a key leaked from the session in front":

        def leaky(q, k, v, **kw):
            ids = kw["segment"]
            if isinstance(ids, tuple):  # a pass: the slots of session s - 1 pass for s's
                of_q, of_k = ids
                kw["segment"] = (jnp.where(of_q > 0, of_q - 1, of_q), jnp.where(of_k > 0, of_k - 1, of_k))
            return fused(q, k, v, **kw)

        monkeypatch.setattr(sdar, "fused_attention", leaky)
    elif fault == "weights not renormalised":
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda x, w, k, renormalise=False: route(x, w, k))
    elif fault == "query head h reading key/value head h % 2":

        def crossed(q, k, v, **kw):
            heads = jnp.arange(q.shape[1]) % k.shape[1]
            return fused(q, k[:, heads], v[:, heads], **kw)

        monkeypatch.setattr(sdar, "fused_attention", crossed)
    else:
        raise AssertionError(fault)
    for program in (sdar.session_vectors, sdar.denoise_pass, pass_with_logits):
        program.clear_cache()


FAULTS = [
    "a denoise pass's keys and values kept in place of the commit's",
    "a key of the next block seen",
    "a token-causal mask inside the block",
    "a key leaked from the session in front",
    "weights not renormalised",
    "query head h reading key/value head h % 2",
]


@pytest.mark.parametrize("fault", [None] + FAULTS)
def test_a_planted_fault_is_another_answer_than_the_references(fault, padded_forward, monkeypatch):
    # sessions long enough that every answer crosses committed blocks and reads cached keys
    algorithm, model, sessions, plain = deployment(lengths=(16, 17, 18, 19, 40, 22))
    try:
        if fault is not None:
            plant(fault, monkeypatch)
        assert differs_from_the_reference(algorithm, model, sessions, plain, padded_forward) == (fault is not None)
    finally:
        monkeypatch.undo()
        for program in (sdar.session_vectors, sdar.denoise_pass, pass_with_logits):
            program.clear_cache()


def test_an_item_of_the_session_or_a_repeated_item_in_an_answer_is_told(monkeypatch):
    def clean(answers, sessions):
        return all(
            len({s.item for s in a.item_scores}) == len(a.item_scores)
            and not {int(s.item[1:]) for s in a.item_scores} & set(session.tolist())
            for a, session in zip(answers, sessions)
        )

    lengths = (100, 90, 80, 70, 60, 50)
    algorithm, model, sessions, _ = deployment(lengths=lengths)
    assert clean(answers_of(algorithm, model, 5), sessions)
    select = topk.select_top_k
    monkeypatch.setattr(
        topk, "select_top_k", lambda scores, k, mask=None, **kw: select(scores, k, mask=None, **kw)
    )
    sdar.denoise_pass.clear_cache()
    try:
        # with no candidate masked, sessions of most of the items meet their own
        # (or a row of the vocabulary that is no item: the answer cannot even be named)
        try:
            assert not clean(answers_of(algorithm, model, 5), sessions)
        except IndexError:
            pass
    finally:
        monkeypatch.undo()
        sdar.denoise_pass.clear_cache()


# ------------------------------------------------------------- the program


def test_the_prefill_writes_whole_blocks_where_the_stream_lies_and_no_more():
    algorithm, model, sessions, _ = deployment()
    queries = [Query(user=f"u{u}", num=3) for u in range(len(sessions))]
    planned, streams = algorithm._plan(model, queries)
    staged = [algorithm._stage(model, planned, stream) for stream in streams]
    kept = {}
    new_state = sdar.new_state

    def keep(*args):
        kept["seg"] = np.asarray(args[2]).copy()
        return new_state(*args)

    import unittest.mock

    with unittest.mock.patch.object(sdar, "new_state", keep):
        algorithm._launch_group(model, queries, planned, streams, staged)
    seg, offset, row = kept["seg"], 0, 0
    assert seg.shape == (model.config.cache_tokens,)
    for length, members in streams:
        for i, at in members:
            whole = len(planned[i]) - len(planned[i]) % 4
            assert (seg[offset + at : offset + at + whole] == row).all()
            assert (seg[offset + at + whole : offset + at + whole + 4] == -1).all()
            row += 1
        offset += length
    assert (seg >= 0).sum() == sum(len(s) - len(s) % 4 for s in sessions)


def test_counters_count_passes_blocks_items_and_the_caches_bytes():
    algorithm, model, sessions, _ = deployment(lengths=(16, 17, 40))
    answers_of(algorithm, model, 7)
    got = {
        (name, tuple(sorted(sample["labels"].items()))): sample["value"]
        for name, family in algorithm.instruments.registry.snapshot().items()
        for sample in family["samples"]
    }
    config = model.config
    schedules = [config.schedule(len(s), 7) for s in sessions]
    assert schedules == ["ddddcddd", "dddcdddd", "ddddcddd"]
    # pass 4 is a commit for two sessions and a denoise for the third: it counts as a denoise
    assert got[("pio_seq_passes_total", (("kind", "denoise"),))] == 8
    assert got[("pio_seq_passes_total", (("kind", "commit"),))] == 0
    assert got[("pio_seq_blocks_total", ())] == 6 and got[("pio_seq_generated_items_total", ())] == 21
    # one stream of 256 slots as it lies and eight passes' chunks of 128, 3 layers x (k, v) x 2 heads x 16 x 2 bytes
    assert got[("pio_seq_cache_bytes_total", ())] == (256 + 8 * 128) * 3 * 2 * 2 * 16 * 2
    assert got[("pio_seq_batches_total", ())] == 1
    assert got[("pio_seq_tokens_total", (("kind", "real"),))] == 73
    # copies: 2 of 3 layers in the prefill, every layer a pass; a pass takes the session's whole block
    # (16 and 40 items: 5 passes over a block of 4, 3 over the short last one of 3; 17: 4 and 4 over 4)
    rows = (5 * 4 + 3 * 3) + (4 * 4 + 4 * 4) + (5 * 4 + 3 * 3)
    routed = 2 * (2 * 73 + 3 * rows)
    assert got[("pio_moe_copies_total", (("where", "held"),))] == routed
    assert got[("pio_moe_expert_tokens_mean_total", ())] == routed / 8
    assert routed / 8 <= got[("pio_moe_expert_tokens_max_total", ())] <= routed


def counters_of(algorithm) -> dict:
    return {
        (name, tuple(sorted(sample["labels"].items()))): sample["value"]
        for name, family in algorithm.instruments.registry.snapshot().items()
        for sample in family["samples"]
    }


def test_the_experts_a_passes_real_rows_reach_are_counted(monkeypatch):
    """What a pass has to read of a layer's experts: those a REAL row was
    sent to (a session that is done rides along and reaches none)."""
    algorithm, model, _, _ = deployment(lengths=(16, 17, 40))
    answers_of(algorithm, model, 7)
    got = counters_of(algorithm)
    offered = 8 * 3 * 8  # eight passes, three layers, eight experts
    assert got[("pio_moe_experts_offered_total", ())] == offered
    # a real row goes to two experts, and a pass holds four to twelve real rows
    assert 8 * 3 * 2 <= got[("pio_moe_experts_reached_total", ())] <= offered
    # a router that knows two experts only: two are reached, whatever rides along
    plain = moe.route

    def narrow(x, router_w, k, renormalise=False):
        weights, experts = plain(x, router_w, k, renormalise)
        return weights, jnp.broadcast_to(jnp.arange(k, dtype=experts.dtype) + 3, experts.shape)

    monkeypatch.setattr(moe, "route", narrow)
    sdar.session_vectors.clear_cache(), sdar.denoise_pass.clear_cache()
    try:
        algorithm, model, _, _ = deployment(lengths=(16, 17, 40))
        answers_of(algorithm, model, 7)
        got = counters_of(algorithm)
        assert got[("pio_moe_experts_reached_total", ())] == 8 * 3 * 2
        assert got[("pio_moe_experts_offered_total", ())] == offered
    finally:
        monkeypatch.undo()
        sdar.session_vectors.clear_cache(), sdar.denoise_pass.clear_cache()


def test_the_stage_clock_stops_before_a_groups_first_launch(monkeypatch):
    """``pio_seq_stage_seconds_total`` is the HOST's part: ``new_state``
    launches (uploads, the cache's zeroing), and a launch waits in the
    device's queue behind the other batch's programs."""
    import time

    algorithm, model, _, _ = deployment(lengths=(16, 17))
    plain = sdar.new_state

    def slow(*args):
        time.sleep(0.5)
        return plain(*args)

    monkeypatch.setattr(sdar, "new_state", slow)
    answers_of(algorithm, model, 5)
    assert counters_of(algorithm)[("pio_seq_stage_seconds_total", ())] < 0.5


@pytest.mark.parametrize(
    "rows, groups, live, tile",
    [
        (1024, 6 * 128, 128, 128),  # a denoise pass: 8 rows an expert
        (16384, 6 * 128, 128, 256),  # its prefill: 128
        (16384, 8 * 64, 64, 256),  # OLMoE's stream: 256
        (32768, 8 * 64, 64, 256),
        (16384, 64, None, 256),  # Kimi-Linear's held experts: every group live
        (16384, 64, 64, 256),
    ],
)
def test_the_grouped_products_row_tile_follows_the_rows_a_live_group_has(rows, groups, live, tile, monkeypatch):
    seen = []
    monkeypatch.setattr(moe, "gmm", lambda lhs, rhs, sizes, **kw: seen.append(kw["tiling"]))
    lhs = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16)
    rhs = jax.ShapeDtypeStruct((groups, 2048, 768), jnp.bfloat16)
    moe.grouped_matmul_kernel(lhs, rhs, None, jnp.bfloat16, live=live)
    assert seen == [(tile, 2048, 768)]


def test_only_commits_in_a_pass_count_it_as_a_commit():
    algorithm, model, _, _ = deployment(lengths=(16, 20, 8))
    answers_of(algorithm, model, 8)
    snapshot = algorithm.instruments.registry.snapshot()["pio_seq_passes_total"]["samples"]
    assert {s["labels"]["kind"]: s["value"] for s in snapshot} == {"denoise": 8, "commit": 1}


def test_warmup_serving_leaves_nothing_to_compile():
    from jax import monitoring

    # a width no other test of this process compiles
    params = SdarAlgorithmParams(**{**TINY, "moe_intermediate_size": 16}, seed=2)
    rng = np.random.default_rng(3)
    sessions = [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in (5, 17, 64, 70, 100, 33, 260)]
    model = SdarModel(
        small(params.config()), [f"i{i}" for i in range(N_ITEMS)], [f"u{i}" for i in range(7)],
        *session_tails(sessions, 512), sdar.init_weights(small(params.config()), 2),
    )
    algorithm = SdarAlgorithm(params)
    compiled = []

    def listener(event, duration_secs, **kw):
        if event.endswith("/backend_compile_duration"):
            compiled.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    algorithm.warmup_serving(model, 64)
    warmed = len(compiled)
    assert warmed >= 3  # two stream lengths and the pass
    answers = answers_of(algorithm, model, 5)
    assert all(len(a.item_scores) == 5 for a in answers)
    assert len(compiled) == warmed


def test_unimplemented_config_values_are_refused_not_ignored():
    for key, value in (
        ("norm_topk_prob", False), ("attention_bias", True), ("decoder_sparse_step", 2),
        ("mlp_only_layers", [0]), ("tie_word_embeddings", True), ("hidden_act", "gelu"),
        ("use_sliding_window", True), ("model_type", "qwen3_moe"),
    ):
        with pytest.raises(ValueError, match=key):
            SdarAlgorithmParams(**{**TINY, key: value}).config()
    with pytest.raises(ValueError, match="key/value heads"):
        SdarAlgorithmParams(**{**TINY, "num_key_value_heads": 3}).config()


def test_the_variant_file_carries_the_published_config_verbatim():
    from pathlib import Path

    import predictionio_tpu.models.sequential as package

    variant = json.loads((Path(package.__file__).parent / "variants" / "sdar-30b-a3b.json").read_text())
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }
    raw = variant["algorithms"][0]["params"]
    assert {k: raw[k] for k in published} == published
    assert variant["algorithms"][0]["name"] == "sdar"
    config = params.config()
    assert (config.num_attention_heads, config.num_key_value_heads, config.head_dim) == (32, 4, 128)
    assert (config.block_length, config.denoising_steps, config.mask_token_id) == (4, 4, 151935)
    assert config.cache_slots == 32768 and config.cache_tokens == 29696 and config.most_passes == 24
    assert config.fit(4096, 16) == 16 and config.fit(4095, 16) == 16 and config.fit(4095, 50) == 19
    assert config.cache_bytes(1) == 48 * 2 * 4 * 128 * 2


def test_save_then_load_is_equal_bit_for_bit(tmp_path):
    algorithm, model, sessions, _ = deployment()
    model.save("m1", None, str(tmp_path))
    loaded = SdarModel.load("m1", None, str(tmp_path))
    assert loaded.config == model.config and loaded.item_vocab == model.item_vocab
    for name, array in model.weights.items():
        np.testing.assert_array_equal(np.asarray(loaded.weights[name]), np.asarray(array))
    assert [as_rows(a) for a in answers_of(algorithm, loaded, 4)] == [
        as_rows(a) for a in answers_of(algorithm, model, 4)
    ]


def test_query_server_answers_num_distinct_items_with_score_and_step_over_http():
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.workflow.create_server import QueryServer, ServerConfig
    from predictionio_tpu.workflow.engine_loader import EngineManifest

    _, model, sessions, _ = deployment()
    engine = engine_factory()
    params = engine.engine_params_from_variant(
        {
            "datasource": {"params": {"appName": "seq"}},
            "algorithms": [{"name": "sdar", "params": {**TINY, "seed": 5}}],
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
        # the operator's 64 is cut to the sessions ONE group of passes holds: the algorithm says so
        assert server._batcher.max_batch == server.algorithms[0].batch_limit() == sdar.SESSIONS == 32
        rows = post({"user": "u1", "num": 5})["itemScores"]
        assert len(rows) == 5 and len({r["item"] for r in rows}) == 5
        assert all(set(r) == {"item", "score", "step"} and r["score"] < 0 and 0 <= r["step"] < 4 for r in rows)
        assert not {int(r["item"][1:]) for r in rows} & set(sessions[1].tolist())
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        assert 'pio_seq_passes_total{kind="denoise"}' in text and "pio_seq_cache_bytes_total" in text
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=30)
