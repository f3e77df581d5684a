"""The sequential engine's ``kimi_linear`` scorer against its plain reference,
at a tiny size on the CPU with every kind of layer: 4 layers (KDA with the
dense feed-forward, KDA sparse, latent attention sparse, KDA sparse), hidden
64, 4 heads of 16, latent rank 24, keys of 16 + 8 and values of 16, 16 routed
experts of width 32 with 4 a token of which the chip holds experts 4 to 7,
one shared expert, a vocabulary slice of 128.

Where a test compares values it upcasts the algorithm's own bf16 draws to
float32 for both sides, as ``test_sequential_olmoe.py`` does.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import PersistentModelManifest
from predictionio_tpu.models.sequential import (
    KimiLinearAlgorithm,
    KimiLinearAlgorithmParams,
    KimiLinearModel,
    OlmoeAlgorithm,
    Query,
    TrainingData,
    engine_factory,
    kimi_linear,
    kimi_linear_reference as reference,
)
from predictionio_tpu.ops import attention, moe

TINY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    linear_attn_config={
        "kda_layers": [1, 2, 4], "full_attn_layers": [3], "num_heads": 4, "head_dim": 16,
        "short_conv_kernel_size": 4,
    },
    num_experts=16, num_experts_per_token=4, vocab_size=512, experts_held=(4, 4),
    vocab_slice=(128, 128), model_max_length=128,
)
N_ITEMS = 120  # 8 rows of the slice are no item
# float32 against float32 on the CPU: the sides differ by the order of their
# sums (the chunked scan against the recurrence, blocked attention, grouped
# products against a loop over experts), through four layers; logits are of
# unit order and the worst seen over the seeds below is 5e-6. 1e-4 is twenty
# times that and twenty times under what ONE bf16 product does (2^-9).
ATOL = 1e-4
# The algorithm's own bf16 tree against the SAME values in float32 through
# the reference: every projection rounds its operands to bf16 (2^-9 each)
# while the scan, the norms and the router stay float32. That error is small
# and everywhere: over seeds 8 to 10 the MEDIAN position's worst logit is off
# by 0.016 to 0.042 of unit-order logits; 0.1 is over twice that. And it is
# large and rare: 2 to 7% of positions are off by 0.15 to 0.9, where a stream
# off by 1e-2 tips one of the sigmoid router's close choices and a token
# takes another expert (at 4 of 16 experts of width 32 one expert is a large
# part of a token's layer), as OLMoE's softmax router's ties do. So the
# median is held tight and the share of tipped positions loosely; a wrong
# layer, decay or share moves EVERY position by the logits' own order.
BF16_MEDIAN, BF16_TIPPED = 0.1, (0.15, 0.2)


@pytest.fixture(autouse=True)
def small_programs(monkeypatch):
    """A stream holds 256 tokens here, and four sessions at most."""
    monkeypatch.setattr(kimi_linear, "TOKEN_BUDGET", 256)


def staged(algorithm, model, sessions, starts, length):
    """The sessions as ONE stream of ``length`` tokens, each from its start:
    ``_stage``'s arrays but the mask."""
    stream = (length, list(enumerate(starts)))
    return [jnp.asarray(a) for a in algorithm._stage(model, sessions, stream)[:4]]


def training_data(seed=0, n_users=12) -> TrainingData:
    rng = np.random.default_rng(seed)
    lengths = rng.choice([3, 17, 40, 64, 65, 70], n_users)
    lengths[:3] = (3, 64, 70)
    return TrainingData(
        [f"u{i}" for i in range(n_users)],
        [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths],
        [f"i{i}" for i in range(N_ITEMS)],
    )


def upcast(weights):
    return jax.tree.map(lambda a: a.astype(jnp.float32), weights)


@pytest.fixture(scope="module")
def trained():
    algorithm = KimiLinearAlgorithm(KimiLinearAlgorithmParams(**TINY, seed=5))
    model = algorithm.train(None, training_data())
    model.weights = upcast(model.weights)
    return algorithm, model


def reference_config(params: KimiLinearAlgorithmParams, **changes) -> dict:
    """What the reference reads: the published keys and the chip's share."""
    return {**dataclasses.asdict(params), **changes}


_logits: dict = {}
_jitted: dict = {}


def reference_answer(algorithm, model, session: np.ndarray, num: int):
    config = reference_config(algorithm.params)
    if id(model) not in _jitted:
        weights = model.weights
        _jitted[id(model)] = jax.jit(lambda t: reference.next_item_logits(weights, config, t))
    key = (id(model), session.tobytes())
    if key not in _logits:
        _logits[key] = np.asarray(_jitted[id(model)](jnp.asarray(session)))
    logits = _logits[key]
    allowed = np.ones(len(logits), bool)
    allowed[N_ITEMS:] = False
    allowed[session] = False
    return logits, np.argsort(-np.where(allowed, logits, -np.inf), kind="stable")[:num]


# ---------------------------------------------------------------- ops/moe


def test_the_sigmoid_router_selects_by_score_plus_bias_and_weighs_by_score():
    # three experts, one a token: the bias lifts expert 2 over expert 0 in
    # the CHOICE; the weight is the score without it, renormalised and scaled
    x = jnp.eye(2, dtype=jnp.float32)
    router = jnp.asarray([[2.0, 0.0, 1.5], [0.0, 2.0, -3.0]])
    bias = jnp.asarray([0.0, 0.0, 0.2])
    weights, experts = moe.route_sigmoid(x, router, bias, 1, 2.5)
    assert experts.tolist() == [[2], [1]]  # by s alone token 0 would take expert 0
    np.testing.assert_allclose(weights, [[2.5], [2.5]], rtol=1e-6)  # one chosen: s / s * scale
    weights, experts = moe.route_sigmoid(x, router, bias, 2, 2.5)
    s = jax.nn.sigmoid(router)
    assert experts.tolist() == [[2, 0], [1, 0]]
    np.testing.assert_allclose(
        weights[0], 2.5 * np.array([s[0, 2], s[0, 0]]) / (s[0, 2] + s[0, 0]), rtol=1e-6
    )
    _, plain = moe.route_sigmoid(x, router, jnp.zeros(3), 1, 2.5)
    assert plain.tolist() == [[0], [1]]


def expert_case(seed, tokens=96, hidden=32, width=16, n_experts=16, k=4):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(tokens, hidden)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(n_experts, hidden, width)) / 6, jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(n_experts, width, hidden)) / 4, jnp.float32)
    router = jnp.asarray(rng.normal(size=(hidden, n_experts)) / 6, jnp.float32)
    bias = jnp.asarray(rng.normal(size=n_experts) * 0.1, jnp.float32)
    return x, gate, up, down, router, bias


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_every_expert_held_by_default_or_by_name_is_the_same_bit_for_bit(seed):
    # what OLMoE calls (no `held`) and the same call with every expert named
    x, gate, up, down, router, _ = expert_case(seed)
    weights, experts = moe.route(x, router, 4)
    default = moe.expert_ffn(x, weights, experts, gate, up, down)
    named = moe.expert_ffn(x, weights, experts, gate, up, down, held=(0, 16))
    np.testing.assert_array_equal(default, named)
    # ... and stacked behind another layer's, as OLMoE's scan reads them
    stacked = [jnp.concatenate([jnp.zeros_like(a), a]) for a in (gate, up, down)]
    behind = moe.expert_ffn(x, weights, experts, *stacked, n_experts=16, first_group=16)
    np.testing.assert_array_equal(default, behind)


@pytest.mark.parametrize("seed,shares", [(0, 4), (1, 2), (2, 8)])
def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once(seed, shares):
    x, gate, up, down, router, bias = expert_case(seed)
    shared = [a[0] for a in (gate, up, down)]  # any gated MLP will do
    layer = {
        "router": router, "router_bias": bias, "gate": gate, "up": up, "down": down,
        "shared_gate": shared[0], "shared_up": shared[1], "shared_down": shared[2],
    }
    config = {"num_experts_per_token": 4, "routed_scaling_factor": 2.446, "experts_held": [0, 16]}
    uncut = reference.sparse_ffn(x, layer, config)  # the reference's whole layer
    weights, experts = moe.route_sigmoid(x, router, bias, 4, 2.446)
    each = 16 // shares
    total = moe.gated_mlp(x, *shared)  # what every chip computes alike: once
    for chip in range(shares):
        block = slice(chip * each, (chip + 1) * each)
        part = moe.expert_ffn(
            x, weights, experts, gate[block], up[block], down[block], held=(chip * each, each)
        )
        # the reference, given the same share, gives the same part
        own = {**layer, "gate": gate[block], "up": up[block], "down": down[block]}
        theirs = reference.experts(
            x, reference.router_choice(reference.router_scores(x, layer), bias, 4, 2.446), own,
            [chip * each, each],
        )
        np.testing.assert_allclose(part, theirs, atol=ATOL, rtol=0)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=ATOL, rtol=0)


def test_copies_routed_to_absent_experts_never_reach_the_result(monkeypatch):
    # the chip's kernel leaves the rows past the last held group as it found
    # them: fill them with NaN, as uninitialised memory may be
    x, gate, up, down, router, bias = expert_case(3)
    weights, experts = moe.route_sigmoid(x, router, bias, 4, 2.446)
    want = moe.expert_ffn(x, weights, experts, gate[4:8], up[4:8], down[4:8], held=(4, 4))
    plain = moe.grouped_matmul

    def unwritten(lhs, rhs, sizes, out_dtype, **kw):
        out = plain(lhs, rhs, sizes, out_dtype, **kw)
        return jnp.where((jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None], out, jnp.nan)

    monkeypatch.setattr(moe, "grouped_matmul", unwritten)
    got = moe.expert_ffn(x, weights, experts, gate[4:8], up[4:8], down[4:8], held=(4, 4))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, want)


def test_the_chips_kernel_interpreted_serves_a_share():
    x, gate, up, down, router, bias = expert_case(4, tokens=64)
    weights, experts = moe.route_sigmoid(x, router, bias, 4, 2.446)
    want = moe.expert_ffn(x, weights, experts, gate[8:12], up[8:12], down[8:12], held=(8, 4))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            moe, "grouped_matmul",
            lambda lhs, rhs, sizes, dtype, **kw: moe.grouped_matmul_kernel(
                lhs, rhs, sizes, dtype, interpret=True, **kw
            ),
        )
        got = moe.expert_ffn(x, weights, experts, gate[8:12], up[8:12], down[8:12], held=(8, 4))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


# the compact block (``held_expert_ffn``): 96 tokens at 4 copies over 16
# experts of which 4 to 7 are held is a block of 192 rows in tiles of 16
HELD_CASES = (
    "an even router", "every copy held", "no copy held", "one held expert takes every held copy",
    "exactly the block", "one copy over the block", "groups off the tile and NaN in the padding",
    "stacked behind another layer", "every copy held and half the tokens padding",
)


def held_routing(case, tokens, k, n_experts, held, rng):
    """``experts`` [tokens, k] of ``case``, a token's all different."""
    first, count = held
    rows, tile = moe.held_block(tokens, k, count, n_experts)
    absent = [e for e in range(n_experts) if not first <= e < first + count]
    takers = {e: [] for e in range(first, first + count)}  # the tokens routed to each held expert
    if case in ("an even router", "stacked behind another layer"):
        return np.stack([rng.permutation(n_experts)[:k] for _ in range(tokens)]).astype(np.int32)
    if case.startswith("every copy held"):
        return np.stack([first + rng.permutation(count)[:k] for _ in range(tokens)]).astype(np.int32)
    if case == "one held expert takes every held copy":
        takers[first + 2] = [t for t in range(tokens) if t % 2]
    elif case in ("exactly the block", "one copy over the block"):
        each = rows // count
        assert each % tile == 0 and each <= tokens and count * each <= tokens * k
        for i, e in enumerate(takers):
            # (each expert's from another end of the stream, so that no token takes more than k)
            takers[e] = list(range(each) if i % 2 == 0 else range(tokens - each, tokens))
        if case == "one copy over the block":
            takers[first + 2].append(each)
    elif case.startswith("groups off the tile"):
        for i, e in enumerate(takers):
            at = 1 + i * tile  # never token 0, and no token in more than three groups
            takers[e] = list(range(at, at + (5, tile + 1, 2 * tile - 1, 1)[i % 4]))
    experts = np.zeros((tokens, k), np.int32)
    for t in range(tokens):
        mine = [e for e, ts in takers.items() if t in ts]
        assert len(mine) <= k
        experts[t] = mine + list(rng.permutation(absent)[: k - len(mine)])
    return experts


def check_held_block(case, kernel, tokens, k, n_experts, held, monkeypatch):
    """The compact layout against the layout of all the copies and against
    the plain float32 reference, with XLA's ``ragged_dot`` or the chip's
    kernel interpreted."""
    rng = np.random.default_rng(HELD_CASES.index(case))
    first, count = held
    x, gate, up, down, _, _ = expert_case(HELD_CASES.index(case), tokens=tokens, n_experts=n_experts)
    experts = held_routing(case, tokens, k, n_experts, held, rng)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, k)), jnp.float32)
    # two tokens of three are padding: their copies get no row
    real = jnp.arange(tokens) % 3 == 0 if case.endswith("padding") else None
    # the rounds the held groups, each from a tile's edge, take over the block
    rows, tile = moe.held_block(tokens, k, count, n_experts)
    sizes = [int((experts[slice(None) if real is None else np.asarray(real)] == e).sum()) for e in range(first, first + count)]
    rounds = -(-sum(-(-size // tile) * tile for size in sizes) // rows)
    # (twice the block's copies, and a third round where the groups end off a tile's edge)
    assert rounds in {"no copy held": (0,), "every copy held": (2, 3), "one copy over the block": (2,)}.get(case, (1,))
    experts = jnp.asarray(experts)
    counted = weights if real is None else jnp.where(real[:, None], weights, 0.0)
    own = [a[first : first + count] for a in (gate, up, down)]
    if kernel == "interpreted":
        monkeypatch.setattr(
            moe, "grouped_matmul",
            lambda lhs, rhs, sizes, dtype, **kw: moe.grouped_matmul_kernel(lhs, rhs, sizes, dtype, interpret=True, **kw),
        )
    at = {}
    if case == "stacked behind another layer":
        own, at = [jnp.concatenate([jnp.zeros_like(a), a]) for a in own], {"first_group": count}
    whole = moe.expert_ffn(x, counted, experts, *own, held=held, **at)
    if case.startswith("groups off the tile"):
        # a row of padding reads token 0, which no held expert takes: what
        # the first two products leave there is NaN, and the rows past the
        # last group are NaN as uninitialised memory may be
        x = x.at[0].set(jnp.nan)
        plain = moe.grouped_matmul

        def unwritten(lhs, rhs, sizes, out_dtype, **kw):
            out = plain(lhs, rhs, sizes, out_dtype, **kw)
            return jnp.where((jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None], out, jnp.nan)

        monkeypatch.setattr(moe, "grouped_matmul", unwritten)
    got, took = moe.held_expert_ffn(x, weights, experts, *own, held=(*held, n_experts), counted=real, **at)
    assert int(took) == rounds
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, whole, atol=ATOL, rtol=0)
    dense = jnp.zeros((tokens, n_experts)).at[jnp.arange(tokens)[:, None], experts].add(counted)
    layer = dict(zip(("gate", "up", "down"), (a[-count:] for a in own)))
    want = reference.experts(jnp.nan_to_num(x), dense, layer, list(held))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    if real is not None:
        assert not np.asarray(got)[~np.asarray(real)].any()
    if case == "no copy held":
        assert not np.asarray(got).any()
    else:
        assert float(jnp.abs(got).max()) > 100 * ATOL


# Kimi-Linear's share in small (4 of 16 held, tiles of 16) and LFM2's (8 of
# 32 at the same 4 copies a token: 12 rows a group expected, tiles of 8)
@pytest.mark.parametrize("kernel", ["ragged_dot", "interpreted"])
@pytest.mark.parametrize("n_experts,held,tile", [(16, (4, 4), 16), (32, (8, 8), 8)], ids=["4 of 16", "8 of 32"])
@pytest.mark.parametrize("case", HELD_CASES)
def test_the_compact_block_of_the_held_copies_is_all_the_copies_laid_out_and_the_reference(
    case, n_experts, held, tile, kernel, monkeypatch
):
    assert moe.held_block(96, 4, held[1], n_experts) == (192, tile)
    check_held_block(case, kernel, 96, 4, n_experts, held, monkeypatch)


def test_the_block_and_its_tile_follow_the_shapes_and_a_large_share_has_none():
    # Kimi-Linear's streams of 2,048 and 4,096 tokens: 64 and 128 rows a held expert
    assert moe.held_block(2048, 8, 64, 256) == (8192, 64) and moe.held_block(4096, 8, 64, 256) == (16384, 128)
    # LFM2's shapes: 256 and 512 rows a held expert take the kernel's own tile and no longer one
    assert moe.held_block(2048, 4, 8, 32) == (4096, 256) and moe.held_block(4096, 4, 8, 32) == (8192, 256)
    # half of the experts: twice the expected copies are all of them, and the
    # block has all the copies' rows (PR 50: the held groups from a tile's
    # edge, the absent with no row); every expert: the expected copies and a
    # tile a group do not fit, and ``expert_ffn`` lays all the copies out, in
    # one round
    assert moe.held_block(96, 4, 8, 16) == (384, 16) and moe.held_block(96, 4, 16, 16) is None
    x, gate, up, down, router, _ = expert_case(5)
    weights, experts = moe.route(x, router, 4)
    named, rounds = moe.held_expert_ffn(x, weights, experts, gate, up, down, held=(0, 16, 16))
    np.testing.assert_array_equal(named, moe.expert_ffn(x, weights, experts, gate, up, down))
    assert int(rounds) == 1


# ---------------------------------------------------------- ops/attention


@pytest.mark.parametrize("length", [64, 1024])  # the single-block kernel, the tiled one
def test_fused_attention_takes_values_narrower_than_keys(length):
    rng = np.random.default_rng(length)
    q, k = (jnp.asarray(rng.normal(size=(1, 2, length, 192)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 2, length, 128)), jnp.float32)
    want = attention.attention_reference(q, k, v, causal=True)
    got = attention.fused_attention(q, k, v, causal=True, force_pallas=True)
    assert got.shape == (1, 2, length, 128)
    # the kernels multiply in bf16, as they do at one width
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=0)


# ------------------------------------------------------------ the program


@pytest.mark.parametrize("length,seed", [(64, 5), (100, 6), (128, 7)])
def test_full_logits_equal_the_references(length, seed):
    params = KimiLinearAlgorithmParams(**TINY, seed=seed)
    config = params.config()
    weights = upcast(kimi_linear.init_weights(config, seed))
    tokens = np.random.default_rng(seed).integers(0, N_ITEMS, (2, length)).astype(np.int32)
    got = np.asarray(kimi_linear.all_logits(weights, tokens, config=config))
    assert got.shape == (2, length, 128) and 0.5 < got.std() < 2.0  # of unit order
    for row in range(2):
        want = reference.forward(weights, reference_config(params), tokens[row])
        np.testing.assert_allclose(got[row], want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("seed", [8, 9, 10])
def test_the_bf16_tree_stays_within_its_own_tolerance_of_the_reference(seed):
    params = KimiLinearAlgorithmParams(**TINY, seed=seed)
    config = params.config()
    weights = kimi_linear.init_weights(config, seed)
    tokens = np.random.default_rng(seed).integers(0, N_ITEMS, (2, 96)).astype(np.int32)
    got = np.asarray(kimi_linear.all_logits(weights, tokens, config=config))
    for row in range(2):
        want = np.asarray(reference.forward(weights, reference_config(params), tokens[row]))
        worst = np.abs(got[row] - want).max(axis=-1)  # by position
        assert 1e-3 < np.median(worst) < BF16_MEDIAN
        assert (worst > BF16_TIPPED[0]).mean() < BF16_TIPPED[1]


@pytest.mark.parametrize(
    "fault",
    ["experts_per_token", "no_bias", "other_share", "kda_as_mla"],
)
def test_the_reference_tells_a_wrong_layer_from_the_right_one(fault):
    # ATOL is no formality: each of these is another model by far more
    params = KimiLinearAlgorithmParams(**TINY, seed=9)
    config = params.config()
    weights = upcast(kimi_linear.init_weights(config, 9))
    tokens = np.random.default_rng(9).integers(0, N_ITEMS, 80).astype(np.int32)
    got = np.asarray(kimi_linear.all_logits(weights, tokens[None], config=config))[0]
    wrong = reference_config(params)
    if fault == "experts_per_token":
        wrong["num_experts_per_token"] = 3
    elif fault == "no_bias":
        weights = {k: (jnp.zeros_like(a) if k.endswith("router_bias") else a) for k, a in weights.items()}
    elif fault == "other_share":
        wrong["experts_held"] = (8, 4)
    else:
        wrong["first_k_dense_replace"] = 0
    with pytest.raises((AssertionError, KeyError)):
        np.testing.assert_allclose(got, reference.forward(weights, wrong, tokens), atol=ATOL, rtol=0)


def test_the_decays_drawn_spread_over_where_a_dropped_one_shows(trained):
    _, model = trained
    config = model.config
    layer = kimi_linear.layer_of(model.weights, 1)
    rate = jax.nn.softplus(layer["dt_bias"]).reshape(config.kda_num_heads, -1)
    decay = np.exp(-np.exp(np.asarray(layer["A_log"]))[:, None] * np.asarray(rate))
    assert 0.88 < decay.min() < 0.97 and 0.999 < decay.max() < 1.0
    assert float(jnp.abs(kimi_linear.layer_of(model.weights, 2)["router_bias"]).max()) > 0.01


def test_the_counts_leave_the_padding_out_and_split_held_from_absent(trained):
    algorithm, model = trained
    config = model.config
    stream = staged(algorithm, model, [np.arange(10, dtype=np.int32)], [64], 256)
    _, counted = kimi_linear.session_vectors(model.weights, *stream, config=config)
    busiest, held, more = (int(c) for c in counted)
    # ten tokens' held copies take a tile an expert, half the block: the
    # padding's 246 tokens, which route alike, get no row in it
    assert more == 0
    routed = config.routed_copies(10)
    assert routed == 3 * 10 * 4  # three sparse layers, four copies a token
    assert 0 < held < routed and held / 12 <= busiest <= min(held, 3 * 10)
    assert config.even_expert_load(10) == pytest.approx(3 * 10 * 4 / 16)


def test_the_padding_around_a_session_changes_nothing_of_it(trained):
    algorithm, model = trained
    config = model.config
    session = np.random.default_rng(2).integers(0, N_ITEMS, 40).astype(np.int32)
    vectors = []
    for start, length, fill in ((0, 64, 0), (0, 64, 77), (64, 128, 5), (192, 256, 9)):
        tokens, segment, position, last = staged(algorithm, model, [session], [start], length)
        tokens = jnp.where(segment < 0, fill, tokens)
        out, _ = kimi_linear.session_vectors(model.weights, tokens, segment, position, last, config=config)
        vectors.append(np.asarray(out[0]))
    for other in vectors[1:]:
        np.testing.assert_allclose(vectors[0], other, atol=1e-5, rtol=0)


# sessions (their lengths) of ONE stream, where each starts, the stream's
# length, the budget and the longest session the engine keeps
PACKED = {
    "one ends inside a chunk, one is exactly 64": ((37, 64, 100), (0, 64, 128), 256, 256, 512),
    "one longer than the budget shares its stream": ((300, 64, 17, 40), (0, 320, 384, 448), 512, 256, 512),
    "32 sessions at the chip's budget": (tuple(range(33, 65)), tuple(range(0, 2048, 64)), 2048, 2048, 4096),
    "2,049 to 4,096 items beside others": ((2100, 1000, 64, 500), (0, 2112, 3136, 3200), 4096, 2048, 4096),
}


@pytest.mark.parametrize("case", list(PACKED))
def test_a_packed_streams_session_vectors_equal_the_sessions_alone(case, monkeypatch):
    lengths, starts, length, budget, longest = PACKED[case]
    monkeypatch.setattr(kimi_linear, "TOKEN_BUDGET", budget)
    monkeypatch.setattr(kimi_linear, "MAX_SESSION", longest)
    params = KimiLinearAlgorithmParams(**{**TINY, "model_max_length": longest}, seed=4)
    algorithm = KimiLinearAlgorithm(params)
    rng = np.random.default_rng(len(lengths))
    sessions = [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths]
    model = algorithm.train(None, TrainingData(["u"], [sessions[0]], [f"i{i}" for i in range(N_ITEMS)]))
    model.weights = upcast(model.weights)
    assert length in model.config.stream_shapes()
    packed, _ = kimi_linear.session_vectors(
        model.weights, *staged(algorithm, model, sessions, starts, length), config=model.config
    )
    assert packed.shape == (budget // 64, 64)
    for row, session in enumerate(sessions):
        # alone, from the stream's first position: the same compiled program.
        # A state, a convolution's taps or a key leaked from the session in
        # front would move it by the vectors' own order
        alone, _ = kimi_linear.session_vectors(
            model.weights, *staged(algorithm, model, [session], [0], length), config=model.config
        )
        np.testing.assert_allclose(packed[row], alone[0], atol=ATOL, rtol=0, err_msg=f"session {row}")
    # ... and the model's own answer at the session's true length
    logits = kimi_linear.all_logits(model.weights, jnp.asarray(sessions[1])[None], config=model.config)
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


def vectors_of(algorithm, model, staged_rows, rows, program=kimi_linear.session_vectors):
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


def _a_key(monkeypatch):
    attend = kimi_linear.fused_attention

    def leaky(q, k, v, **kwargs):  # the first position's key is the row in front's
        k = k.at[:, :, 0].set(jnp.roll(k, 1, axis=0)[:, :, 0])
        return attend(q, k, v, **kwargs)

    monkeypatch.setattr(kimi_linear, "fused_attention", leaky)


def _a_scan_state(monkeypatch):
    scan = kimi_linear.kda

    def leaky(q, k, v, g, b, starts=None):  # a row begins from the state the row in front ended in
        _, state = scan(q, k, v, g, b, starts=starts)
        return scan(q, k, v, g, b, state=jnp.roll(state, 1, axis=0), starts=starts.at[:, 0].set(False))

    monkeypatch.setattr(kimi_linear, "kda", leaky)


def _a_convolution_tap(monkeypatch):
    convolve = kimi_linear.short_conv

    def leaky(x, w, position=None):  # the row in front's last input reaches a row's first position
        return convolve(x.at[:, 0].add(jnp.roll(x, 1, axis=0)[:, -1]), w, position=position)

    monkeypatch.setattr(kimi_linear, "short_conv", leaky)


@pytest.mark.parametrize("plant", [_a_key, _a_scan_state, _a_convolution_tap])
def test_a_leak_from_the_row_in_front_moves_the_vectors(trained, plant, monkeypatch):
    """What the equality above can tell: a key, a scan state or a convolution
    tap of the row in front, each planted alone, moves a row's vectors by far
    more than the tolerance."""
    algorithm, model = trained
    staged_rows = stacked_streams(algorithm, model)
    sound = vectors_of(algorithm, model, staged_rows, (0, 1, 2, 3))
    plant(monkeypatch)
    # (a function of its own: a jit's traces are kept by the function traced)
    planted = jax.jit(lambda *a, config: kimi_linear.session_vectors.__wrapped__(*a, config=config), static_argnames=("config",))
    leaked = vectors_of(algorithm, model, staged_rows, (0, 1, 2, 3), planted)
    for r, (lengths, _) in enumerate(ROWS):
        assert np.abs(leaked[r, : len(lengths)] - sound[r, : len(lengths)]).max() > 100 * ATOL, r


@pytest.fixture(scope="module")
def stacking():
    """An algorithm and a model whose sessions reach 512 items, so that the
    streams are of 256 tokens and of 512, and users by their session's length."""
    lengths = [17, 40, 60, 500] + [150] * 8
    rng = np.random.default_rng(38)
    algorithm = KimiLinearAlgorithm(KimiLinearAlgorithmParams(**{**TINY, "model_max_length": 512}, seed=6))
    users = [f"u{i}" for i in range(len(lengths))]
    model = algorithm.train(None, TrainingData(
        users, [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths], [f"i{i}" for i in range(N_ITEMS)],
    ))
    model.weights = upcast(model.weights)
    return algorithm, model, users


@pytest.fixture(autouse=True)
def sessions_of_up_to_512_items_in_stacks_of_four(request, monkeypatch):
    """The engine's stacking, which this backbone shares with ``olmoe``, at
    ``olmoe``'s height: as shipped its own is ONE (the chip's readings stand
    beside ``kimi_linear.STACKED_ROWS``), and one row is the path of every
    other test of this file."""
    if "stacking" in request.fixturenames:
        assert kimi_linear.STACKED_ROWS == 1
        monkeypatch.setattr(kimi_linear, "MAX_SESSION", 512)
        monkeypatch.setattr(kimi_linear, "STACKED_ROWS", 4)


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


# -------------------------------------------------------------- the engine


def test_kimi_linear_is_an_algorithm_of_the_engine_that_shares_olmoes_serving():
    engine = engine_factory()
    variant = {
        "datasource": {"params": {"appName": "seq"}},
        "algorithms": [{"name": "kimi_linear", "params": {**TINY, "seed": 7}}],
    }
    _, _, (algorithm,), _ = engine.make_components(engine.engine_params_from_variant(variant))
    assert type(algorithm) is KimiLinearAlgorithm and algorithm.params.experts_held == (4, 4)
    for name in ("_plan", "_stage", "predict_batch_dispatch", "warmup_serving", "train", "register_metrics"):
        assert getattr(KimiLinearAlgorithm, name) is getattr(OlmoeAlgorithm, name), name
    for name in ("save", "session_tokens", "head"):
        assert getattr(KimiLinearModel, name) is getattr(OlmoeAlgorithm.model_class, name), name
    assert KimiLinearModel.load.__func__ is OlmoeAlgorithm.model_class.load.__func__


def test_a_batch_of_mixed_lengths_is_answered_in_order_as_the_reference_does(trained):
    algorithm, model = trained
    data = training_data()
    queries = [Query(user=u, num=5) for u in data.users] + [Query(user="nobody", num=5)]
    before = {k: m.value(where=k) for k, m in (("held", algorithm.instruments.copies), ("absent", algorithm.instruments.copies))}
    answers = algorithm.predict_batch(model, queries)
    assert answers[-1].item_scores == ()
    real = 0
    for user, session, answer in zip(data.users, data.sequences, answers):
        logits, order = reference_answer(algorithm, model, session, 5)
        assert [s.item for s in answer.item_scores] == [f"i{i}" for i in order], user
        np.testing.assert_allclose([s.score for s in answer.item_scores], logits[order], atol=ATOL, rtol=0)
        real += len(session)
    held = algorithm.instruments.copies.value(where="held") - before["held"]
    absent = algorithm.instruments.copies.value(where="absent") - before["absent"]
    assert held + absent == model.config.routed_copies(real)
    # 4 of 16 experts held: about a quarter of the copies
    assert 0.15 < held / (held + absent) < 0.35


def test_the_share_of_held_blocks_that_overflowed_reads_from_two_scrapes_of_the_counter(trained):
    """``held_whole_path_share``: the benchmark's entry found BY NAME, in the
    held cell whose shapes take the compact block, read by the harness from
    the algorithm's own counter scraped before and after a batch."""
    import json
    from pathlib import Path

    from benchmark import harness
    from benchmark.engines.recommendation_als import parse_metrics

    root = Path(__file__).resolve().parents[1]
    entries = {m["name"]: m for m in json.loads((root / "BENCHMARK.json").read_text())["per_layer"]}
    entry = entries["held_whole_path_share"]
    assert "seq-kimi-linear.serve-sat" in entry["workloads"]
    assert (entry["moves"], entry["better"], entry["source"]) == ("answered_qps", "lower", "program_counter")
    assert entry["layer"] == entries["absent_copy_share"]["layer"]
    algorithm, model = trained
    registry = algorithm.instruments.registry
    start = parse_metrics(registry.render_prometheus())
    data = training_data()
    algorithm.predict_batch(model, [Query(user=u, num=5) for u in data.users])
    end = parse_metrics(registry.render_prometheus())
    run = harness.Run(0.0, 51.0, 1, 0, True, counters_start=start, counters_end=end)
    share = harness.read_metric(root, True, "held_whole_path_share", run)
    blocks = {n: run.grown(f'pio_moe_held_blocks_total{{rounds="{n}"}}') for n in ("one", "more")}
    # three sparse layers a program, each counted once under one of the two
    assert sum(blocks.values()) > 0 and sum(blocks.values()) % model.config.sparse_layers == 0
    assert share == pytest.approx(100.0 * blocks["more"] / sum(blocks.values())) and 0.0 <= share <= 100.0
    # a program that has no such counter (this PR's parent): nothing is read, nothing raised
    assert harness.read_metric(root, True, "held_whole_path_share", harness.Run(0.0, 51.0, 1, 0, True)) is None
    # every block on one path, by hand
    by_hand = harness.Run(
        0.0, 51.0, 1, 0, True,
        counters_start={'pio_moe_held_blocks_total{rounds="one"}': 7.0, 'pio_moe_held_blocks_total{rounds="more"}': 0.0},
        counters_end={'pio_moe_held_blocks_total{rounds="one"}': 700.0, 'pio_moe_held_blocks_total{rounds="more"}': 7.0},
    )
    assert harness.read_metric(root, True, "held_whole_path_share", by_hand) == pytest.approx(1.0)


def test_olmoe_counts_every_copy_as_held():
    from predictionio_tpu.models.sequential import OlmoeAlgorithmParams

    algorithm = OlmoeAlgorithm(OlmoeAlgorithmParams(
        hidden_size=64, intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, num_experts=8, num_experts_per_tok=2, vocab_size=128,
        max_position_embeddings=128, seed=1,
    ))
    model = algorithm.train(None, training_data(n_users=3))
    algorithm.predict_batch(model, [Query(user="u0", num=3)])
    assert algorithm.instruments.copies.value(where="held") == 2 * 3 * 2  # layers, tokens, k
    assert algorithm.instruments.copies.value(where="absent") == 0


def test_program_shapes_are_a_small_closed_set_and_warmup_compiles_them_all():
    algorithm = KimiLinearAlgorithm(KimiLinearAlgorithmParams(**{**TINY, "num_hidden_layers": 2}, seed=2))
    model = algorithm.train(None, training_data(n_users=5))
    # the streams compile; `program_shapes` is the ladder of before them, kept for the benchmark's pin
    assert model.config.stream_shapes() == (256,) and model.config.program_shapes() == ((4, 64), (2, 128))
    algorithm.warmup_serving(model, 8)
    compiled = kimi_linear.session_vectors._cache_size()
    algorithm.predict_batch(model, [Query(user=f"u{i}", num=4) for i in range(5)])
    assert kimi_linear.session_vectors._cache_size() == compiled


@pytest.mark.parametrize(
    "change",
    [{"moe_router_activation_func": "softmax"}, {"mla_use_nope": False}, {"q_lora_rank": 1536},
     {"moe_renormalize": False}, {"num_expert_group": 8}, {"tie_word_embeddings": True}],
)
def test_unimplemented_config_values_are_refused_not_ignored(change):
    with pytest.raises(ValueError, match="not implemented"):
        KimiLinearAlgorithmParams(**{**TINY, **change}).config()


@pytest.mark.parametrize(
    "change,match",
    [({"experts_held": (14, 4)}, "no block"),
     ({"linear_attn_config": {**TINY["linear_attn_config"], "full_attn_layers": []}}, "neither or both")],
)
def test_a_share_or_a_layer_pattern_that_cannot_be_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        KimiLinearAlgorithmParams(**{**TINY, **change}).config()


def test_the_published_defaults_are_the_published_config():
    import json
    from pathlib import Path

    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog beside the guides here")
    row = next(
        json.loads(line) for line in catalog.read_text().splitlines() if "Kimi-Linear-48B-A3B" in line
    )
    params = dataclasses.asdict(KimiLinearAlgorithmParams())
    assert {key: params[key] for key in row["config"]} == row["config"]
    config = KimiLinearAlgorithmParams().config()
    assert config.experts_held == (0, 256) and config.vocab_slice == (0, 163840)
    assert config.sparse_layers == 26 and sum(config.is_kda(i) for i in range(1, 28)) == 20


def test_the_variant_file_carries_the_published_config_and_states_the_share():
    import json
    from pathlib import Path

    import predictionio_tpu.models.sequential as package

    variant = json.loads((Path(package.__file__).parent / "variants" / "kimi-linear-48b-a3b.json").read_text())
    raw = variant["algorithms"][0]["params"]
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    published = dataclasses.asdict(KimiLinearAlgorithmParams())
    stated = {"num_hidden_layers": 8, "experts_held": [0, 64], "vocab_slice": [0, 40960], "seed": 3}
    assert {k: v for k, v in raw.items() if k not in stated} == {
        k: v for k, v in published.items() if k not in stated
    }
    assert {k: raw[k] for k in stated} == stated
    config = params.config()
    assert config.experts_held == (0, 64) and config.table_rows == 40960 and config.num_hidden_layers == 8
    shapes = kimi_linear.weight_shapes(config)
    parameters = sum(int(np.prod(shape)) for shape in shapes.values())
    assert 3.7e9 < parameters < 3.8e9  # 7.5 GB in bfloat16
    assert shapes["2.gate"] == (64, 2304, 1024) and shapes["2.router"] == (2304, 256)
    assert shapes["4.wq"] == (2304, 32 * 192) and shapes["4.w_kvb"] == (512, 32 * 256)
    assert shapes["1.dense_gate"] == (2304, 9216) and shapes["lm_head"] == (40960, 2304)


def test_save_then_load_is_equal_bit_for_bit_and_the_manifest_names_the_backbone(tmp_path, monkeypatch):
    from predictionio_tpu.workflow import model_io

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "base"))
    algorithm = KimiLinearAlgorithm(KimiLinearAlgorithmParams(**TINY, seed=7))
    model = algorithm.train(None, training_data(n_users=4))
    engine = engine_factory()
    params = engine.engine_params_from_variant({
        "datasource": {"params": {"appName": "seq"}},
        "algorithms": [{"name": "kimi_linear", "params": {**TINY, "seed": 7}}],
    })
    (persisted,) = engine.make_serializable_models(None, params, [model])
    assert isinstance(persisted, PersistentModelManifest)
    assert persisted.class_path == "predictionio_tpu.models.sequential.engine.KimiLinearModel"
    (deployed,) = engine.prepare_deploy(None, params, model_io.deserialize_models(model_io.serialize_models([persisted])))
    assert isinstance(deployed, KimiLinearModel) and deployed.config == model.config
    assert model.save("m1", algorithm.params, str(tmp_path))
    loaded = KimiLinearModel.load("m1", algorithm.params, str(tmp_path))
    assert loaded.config == model.config and loaded.item_vocab == model.item_vocab
    assert loaded.weights.keys() == model.weights.keys()
    for name in model.weights:
        assert loaded.weights[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(loaded.weights[name]), np.asarray(model.weights[name]))
    queries = [Query(user=f"u{i}", num=4) for i in range(4)]
    assert algorithm.predict_batch(loaded, queries) == algorithm.predict_batch(model, queries)


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
    algorithm = KimiLinearAlgorithm(KimiLinearAlgorithmParams(**TINY, seed=5))
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
