"""The sequential engine's ``lfm2`` scorer against its plain reference, at a
tiny size on the CPU with every kind of layer, in an order that only the LIST
gives: 6 layers (conv dense, conv sparse, attention sparse, conv sparse,
attention sparse, conv sparse), hidden 64, 4 query heads over 2 key/value
heads of 16, 3 taps, 16 routed experts of width 32 with 4 a token of which
the chip holds experts 4 to 7, a vocabulary of 128 tied to the head.

Where a test compares values it upcasts the algorithm's own bf16 draws to
float32 for both sides, as ``test_sequential_olmoe.py`` does.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import PersistentModelManifest
from predictionio_tpu.models.sequential import (
    Lfm2Algorithm,
    Lfm2AlgorithmParams,
    Lfm2Model,
    OlmoeAlgorithm,
    Query,
    TrainingData,
    engine_factory,
    lfm2,
    lfm2_reference as reference,
)
from predictionio_tpu.ops import attention, linear_attention, moe

TINY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=6,
    layer_types=("conv", "conv", "full_attention", "conv", "full_attention", "conv"),
    num_attention_heads=4, num_key_value_heads=2, num_dense_layers=1, num_experts=16,
    num_experts_per_tok=4, vocab_size=128, max_position_embeddings=128, experts_held=(4, 4),
)
N_ITEMS = 120  # 8 rows of the vocabulary are no item
# float32 against float32 on the CPU: the sides differ by the order of their
# sums (blocked attention, grouped products against a loop over experts),
# through six layers; logits are of unit order and the worst seen over the
# seeds below is 6e-6. 1e-4 is over ten times that and twenty times under what
# ONE bf16 product does (2^-9).
ATOL = 1e-4
# The algorithm's own bf16 tree against the SAME values in float32 through the
# reference (``test_sequential_kimi_linear.py``: why two numbers): the MEDIAN
# position's worst logit tight, the share of positions a tipped router moved
# loosely.
BF16_MEDIAN, BF16_TIPPED = 0.1, (0.15, 0.25)


@pytest.fixture(autouse=True)
def small_programs(monkeypatch):
    """A stream holds 256 tokens here, and four sessions at most."""
    monkeypatch.setattr(lfm2, "TOKEN_BUDGET", 256)


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


def published(weights, layers=len(TINY["layer_types"])):
    """The tree as the reference reads it: every layer's arrays under
    ``"<i>.<name>"``, whatever ``lfm2.scan_plan`` stacked."""
    flat = {name: weights[name] for name in ("embed", "embedding_norm")}
    for i in range(layers):
        flat.update({f"{i}.{name}": a for name, a in lfm2.layer_of(weights, i).items()})
    return flat


@pytest.fixture(scope="module")
def trained():
    algorithm = Lfm2Algorithm(Lfm2AlgorithmParams(**TINY, seed=5))
    model = algorithm.train(None, training_data())
    model.weights = upcast(model.weights)
    return algorithm, model


def reference_config(params: Lfm2AlgorithmParams, **changes) -> dict:
    """What the reference reads: the published keys and the chip's share."""
    return {**dataclasses.asdict(params), **changes}


_logits: dict = {}
_jitted: dict = {}


def reference_answer(algorithm, model, session: np.ndarray, num: int):
    config = reference_config(algorithm.params)
    if id(model) not in _jitted:
        weights = published(model.weights)
        _jitted[id(model)] = jax.jit(lambda t: reference.next_item_logits(weights, config, t))
    key = (id(model), session.tobytes())
    if key not in _logits:
        _logits[key] = np.asarray(_jitted[id(model)](jnp.asarray(session)))
    logits = _logits[key]
    allowed = np.ones(len(logits), bool)
    allowed[N_ITEMS:] = False
    allowed[session] = False
    return logits, np.argsort(-np.where(allowed, logits, -np.inf), kind="stable")[:num]


# ------------------------------------------------ ops/linear_attention, ops/moe


def conv_case(seed, rows=2, length=50, width=24, taps=3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, length, 3 * width)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, width)), jnp.float32)
    return x, w


@pytest.mark.parametrize("seed,cut", [(0, 1), (1, 2), (2, 17), (3, 49)])
def test_a_prefix_then_the_rest_from_its_tail_equals_the_whole_gated_convolution(seed, cut):
    # the gates are a position's own; the taps go on from the returned tail
    x, w = conv_case(seed)
    b, c, u = jnp.split(x, 3, axis=-1)
    whole, tail = linear_attention.short_conv(b * u, w, activation=None)
    first, carried = linear_attention.short_conv((b * u)[:, :cut], w, activation=None)
    rest, after = linear_attention.short_conv((b * u)[:, cut:], w, tail=carried, activation=None)
    np.testing.assert_allclose(jnp.concatenate([first, rest], axis=1), whole, atol=1e-6)
    np.testing.assert_array_equal(after, tail)
    np.testing.assert_allclose(c * whole, lfm2.gated_conv(x, w), atol=1e-6)
    # ... and is the reference's three shifted products, a row at a time
    for row in range(x.shape[0]):
        want = c[row] * reference.short_conv((b * u)[row], w)
        np.testing.assert_allclose(lfm2.gated_conv(x, w)[row], want, atol=1e-6)


def test_the_activation_is_a_parameter_and_silu_by_default():
    x, w = conv_case(4)
    x = x[..., :24]
    plain, _ = linear_attention.short_conv(x, w, activation=None)
    default, _ = linear_attention.short_conv(x, w)
    np.testing.assert_allclose(default, jax.nn.silu(plain), atol=1e-6)
    assert float(jnp.abs(default - plain).max()) > 0.1
    tanh, _ = linear_attention.short_conv(x, w, activation=jnp.tanh)
    np.testing.assert_allclose(tanh, jnp.tanh(plain), atol=1e-6)


def test_a_gated_convolutions_taps_never_reach_into_the_session_in_front():
    # three sessions in one row: every one comes out as it does alone
    x, w = conv_case(5, rows=1, length=40)
    position = jnp.asarray(np.concatenate([np.arange(13), np.arange(20), np.arange(7)])[None], jnp.int32)
    packed = lfm2.gated_conv(x, w, position)
    for start, n in ((0, 13), (13, 20), (33, 7)):
        alone = lfm2.gated_conv(x[:, start : start + n], w)
        np.testing.assert_allclose(packed[:, start : start + n], alone, atol=1e-6)
    leaked = lfm2.gated_conv(x, w)  # no positions: one session, the taps cross
    assert float(jnp.abs(leaked[:, 13:15] - packed[:, 13:15]).max()) > 0.1


def test_route_sigmoid_takes_the_published_eps_into_the_renormalisation():
    x = jnp.eye(2, dtype=jnp.float32)
    router = jnp.asarray([[2.0, 0.0, 1.5], [0.0, 2.0, -3.0]])
    bias = jnp.asarray([0.0, 0.0, 0.2])
    s = np.asarray(jax.nn.sigmoid(router))
    plain, experts = moe.route_sigmoid(x, router, bias, 2, 1.0)
    with_eps, same = moe.route_sigmoid(x, router, bias, 2, 1.0, eps=0.5)
    assert experts.tolist() == same.tolist() == [[2, 0], [1, 0]]  # the choice is by s + bias
    np.testing.assert_allclose(plain[0], np.array([s[0, 2], s[0, 0]]) / (s[0, 2] + s[0, 0]), rtol=1e-6)
    np.testing.assert_allclose(with_eps[0], np.array([s[0, 2], s[0, 0]]) / (s[0, 2] + s[0, 0] + 0.5), rtol=1e-6)
    tiny, _ = moe.route_sigmoid(x, router, bias, 2, 1.0, eps=lfm2.ROUTER_EPS)
    assert 0 < float(jnp.abs(tiny - plain).max()) < 2e-6
    weights = reference.router_choice(jnp.asarray(s), bias, 2, 1.0)
    np.testing.assert_allclose(weights[0, [2, 0]], tiny[0], rtol=1e-6)


def expert_case(seed, tokens=96, hidden=32, width=16, n_experts=16):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(tokens, hidden)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(n_experts, hidden, width)) / 6, jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(n_experts, width, hidden)) / 4, jnp.float32)
    router = jnp.asarray(rng.normal(size=(hidden, n_experts)) / 6, jnp.float32)
    bias = jnp.asarray(rng.normal(size=n_experts) * 0.1, jnp.float32)
    return x, gate, up, down, router, bias


@pytest.mark.parametrize("seed,shares", [(0, 4), (1, 2), (2, 8)])
def test_the_shares_add_up_to_the_uncut_layer_with_the_router_counted_once(seed, shares):
    """The guide's share test: one sparse layer's partial results of all the
    chips that share it, the router (which every chip computes alike) run
    once, add up to the uncut reference's layer."""
    x, gate, up, down, router, bias = expert_case(seed)
    layer = {"router": router, "expert_bias": bias, "gate": gate, "up": up, "down": down}
    config = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.0, "experts_held": [0, 16]}
    uncut = reference.sparse_ffn(x, layer, config)  # the reference's whole layer
    weights, experts = moe.route_sigmoid(x, router, bias, 4, 1.0, eps=lfm2.ROUTER_EPS)
    each = 16 // shares
    total = jnp.zeros_like(x)
    for chip in range(shares):
        block = slice(chip * each, (chip + 1) * each)
        part = moe.expert_ffn(x, weights, experts, gate[block], up[block], down[block], held=(chip * each, each))
        # the reference, given the same share, gives the same part
        own = {**layer, "gate": gate[block], "up": up[block], "down": down[block]}
        theirs = reference.sparse_ffn(x, own, {**config, "experts_held": [chip * each, each]})
        np.testing.assert_allclose(part, theirs, atol=ATOL, rtol=0)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=ATOL, rtol=0)
    assert float(jnp.abs(uncut).max()) > 100 * ATOL


@pytest.mark.parametrize("width,tile", [(1792, 896), (1280, 1024), (768, 768)])
def test_the_grouped_product_at_a_width_1024_does_not_divide(width, tile, monkeypatch):
    """LFM2's experts are 1,792 wide: the kernel's column tile there is the
    one written beside ``TILING`` (two tiles of 896, whole); a width with no
    entry keeps the old rule (1,280: 1,024 and a masked rest; 768: whole), and
    every one of them is the product, interpreted."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm as real_gmm

    seen = []

    def spy(lhs, rhs, sizes, **kw):
        seen.append(kw["tiling"])
        return real_gmm(lhs, rhs, sizes, **kw)

    monkeypatch.setattr(moe, "gmm", spy)
    rng = np.random.default_rng(width)
    lhs = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 128, width)) / 11, jnp.float32)
    sizes = jnp.asarray([10, 0, 30, 16], jnp.int32)  # 8 rows past the last group
    got = moe.grouped_matmul_kernel(lhs, rhs, sizes, jnp.float32, interpret=True)
    assert seen[-1][2] == tile and moe.COLUMN_TILES == {1792: 896}
    want = jax.lax.ragged_dot(lhs, rhs, sizes, precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got[:56], want[:56], atol=2e-2, rtol=0)  # the kernel multiplies in one pass
    down = moe.grouped_matmul_kernel(got[:, :128], rhs.transpose(0, 2, 1)[:, :128], sizes, jnp.float32, interpret=True)
    assert down.shape == (64, 128)  # as the contraction's side


@pytest.mark.parametrize("hidden,width", [(2048, 1024), (2304, 1024), (2048, 768)])
def test_the_other_backbones_tiles_are_what_they_were(hidden, width, monkeypatch):
    # olmoe, kimi_linear, sdar: (rows, contraction, columns) of gate/up and of down
    seen = []
    monkeypatch.setattr(moe, "gmm", lambda lhs, rhs, sizes, **kw: seen.append(kw["tiling"]) or lhs)
    lhs = jax.ShapeDtypeStruct((16384, hidden), jnp.bfloat16)
    moe.grouped_matmul_kernel(lhs, jax.ShapeDtypeStruct((64, hidden, width), jnp.bfloat16), None, jnp.bfloat16)
    moe.grouped_matmul_kernel(
        jax.ShapeDtypeStruct((16384, width), jnp.bfloat16), jax.ShapeDtypeStruct((64, width, hidden), jnp.bfloat16),
        None, jnp.float32,
    )
    assert seen == [(256, min(2048, hidden), width), (256, width, 1024)]


# ---------------------------------------------------------- ops/attention


def packed_ids(length, rng):
    ids, at, s = np.full(length, -1, np.int32), 0, 0
    while at < length:
        n = int(rng.choice([5, 40, 64, 100, 300]))
        room = -(-n // 64) * 64
        if at + room > length:
            break
        ids[at : at + n] = s
        at, s = at + room, s + 1
    return ids


@pytest.mark.parametrize("length", [256, 1024])  # the single-block kernel, the tiled one
def test_the_kernels_interpreted_at_32_query_heads_over_8_of_width_64_inside_segments(length):
    rng = np.random.default_rng(length)
    q = jnp.asarray(rng.normal(size=(1, 32, length, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 8, length, 64)), jnp.float32) for _ in range(2))
    segment = jnp.asarray(packed_ids(length, rng)[None])
    want = attention.attention_reference(q, k, v, causal=True, segment=segment)
    got = attention.fused_attention(q, k, v, causal=True, segment=segment, force_pallas=True)
    assert got.shape == (1, 32, length, 64)
    real = np.asarray(segment[0] >= 0)
    # the kernels multiply in bf16
    np.testing.assert_allclose(np.asarray(got)[:, :, real], np.asarray(want)[:, :, real], atol=3e-2, rtol=0)
    assert float(jnp.abs(got[:, :, ~real]).max()) == 0.0  # padding comes out as 0
    # a head reads its GROUP's keys: head 4 with key/value head 1, not 0 or 4
    alone = attention.attention_reference(q[:, 4:5], k[:, 1:2], v[:, 1:2], causal=True, segment=segment)
    np.testing.assert_allclose(np.asarray(got)[:, 4:5][:, :, real], np.asarray(alone)[:, :, real], atol=3e-2, rtol=0)


# ------------------------------------------------------------ the program


@pytest.mark.parametrize("length,seed", [(64, 5), (100, 6), (128, 7)])
def test_full_logits_equal_the_references(length, seed):
    params = Lfm2AlgorithmParams(**TINY, seed=seed)
    config = params.config()
    weights = upcast(lfm2.init_weights(config, seed))
    tokens = np.random.default_rng(seed).integers(0, N_ITEMS, (2, length)).astype(np.int32)
    got = np.asarray(lfm2.all_logits(weights, tokens, config=config))
    assert got.shape == (2, length, 128) and 0.5 < got.std() < 2.0  # of unit order
    for row in range(2):
        want = reference.forward(published(weights), reference_config(params), tokens[row])
        np.testing.assert_allclose(got[row], want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("seed", [8, 9, 10])
def test_the_bf16_tree_stays_within_its_own_tolerance_of_the_reference(seed):
    params = Lfm2AlgorithmParams(**TINY, seed=seed)
    config = params.config()
    weights = lfm2.init_weights(config, seed)
    assert all(a.dtype == jnp.bfloat16 for a in weights.values())
    tokens = np.random.default_rng(seed).integers(0, N_ITEMS, (2, 96)).astype(np.int32)
    got = np.asarray(lfm2.all_logits(weights, tokens, config=config))
    for row in range(2):
        want = np.asarray(reference.forward(published(weights), reference_config(params), tokens[row]))
        worst = np.abs(got[row] - want).max(axis=-1)  # by position
        assert 1e-3 < np.median(worst) < BF16_MEDIAN
        assert (worst > BF16_TIPPED[0]).mean() < BF16_TIPPED[1]


@pytest.mark.parametrize(
    "fault", ["experts_per_tok", "no_bias", "other_share", "conv_as_attention", "order_from_a_formula", "no_eps"],
)
def test_the_reference_tells_a_wrong_layer_from_the_right_one(fault):
    # ATOL is no formality: each of these is another model by far more
    params = Lfm2AlgorithmParams(**TINY, seed=9)
    config = params.config()
    weights = upcast(lfm2.init_weights(config, 9))
    tokens = np.random.default_rng(9).integers(0, N_ITEMS, 80).astype(np.int32)
    got = np.asarray(lfm2.all_logits(weights, tokens[None], config=config))[0]
    wrong, weights = reference_config(params), published(weights)
    if fault == "experts_per_tok":
        wrong["num_experts_per_tok"] = 3
    elif fault == "no_bias":
        weights = {k: (jnp.zeros_like(a) if k.endswith("expert_bias") else a) for k, a in weights.items()}
    elif fault == "other_share":
        wrong["experts_held"] = (8, 4)
    elif fault == "conv_as_attention":
        wrong["layer_types"] = ("conv", "conv", "full_attention", "full_attention", "full_attention", "conv")
    elif fault == "order_from_a_formula":
        # every third layer attention: the published list's last period is irregular
        wrong["layer_types"] = ("conv", "conv", "full_attention") * 2
    else:
        wrong["num_dense_layers"] = 0
    with pytest.raises((AssertionError, KeyError)):
        np.testing.assert_allclose(got, reference.forward(weights, wrong, tokens), atol=ATOL, rtol=0)


def test_the_counts_leave_the_padding_out_and_split_held_from_absent(trained):
    algorithm, model = trained
    config = model.config
    stream = staged(algorithm, model, [np.arange(10, dtype=np.int32)], [64], 256)
    _, counted = lfm2.session_vectors(model.weights, *stream, config=config)
    busiest, held, overflowed = (int(c) for c in counted)
    assert overflowed == 0  # (the third count: the sparse layers whose held copies overflowed their block)
    routed = config.routed_copies(10)
    assert routed == 5 * 10 * 4  # five sparse layers, four copies a token
    assert 0 < held < routed and held / 20 <= busiest <= min(held, 5 * 10)
    assert config.even_expert_load(10) == pytest.approx(5 * 10 * 4 / 16)
    assert float(jnp.abs(lfm2.layer_of(model.weights, 1)["expert_bias"]).max()) > 0.01


def test_the_padding_around_a_session_changes_nothing_of_it(trained):
    # a session padded into a longer program reads as at its own length
    algorithm, model = trained
    config = model.config
    session = np.random.default_rng(2).integers(0, N_ITEMS, 40).astype(np.int32)
    vectors = []
    for start, length, fill in ((0, 64, 0), (0, 64, 77), (64, 128, 5), (192, 256, 9)):
        tokens, segment, position, last = staged(algorithm, model, [session], [start], length)
        tokens = jnp.where(segment < 0, fill, tokens)
        out, _ = lfm2.session_vectors(model.weights, tokens, segment, position, last, config=config)
        vectors.append(np.asarray(out[0]))
    for other in vectors[1:]:
        np.testing.assert_allclose(vectors[0], other, atol=1e-5, rtol=0)
    logits = lfm2.all_logits(model.weights, jnp.asarray(session)[None], config=config)
    head = np.asarray(model.weights["embed"], np.float32)
    np.testing.assert_allclose(vectors[0] @ head.T, np.asarray(logits)[0, -1], atol=ATOL)


# ------------------------------------------------- scanned bodies, one tree

PERIOD = ("full_attention", "conv", "conv", "conv")
# layer lists (and their dense layers): the published one, the benchmark
# harness's tiny one, and one in which no pattern of mixers repeats
LISTS = {
    "published": (("conv", "conv") + PERIOD * 4 + PERIOD[:3] * 2, 2),
    "harness": (TINY["layer_types"], 1),
    "no repeat": (("conv", "conv", "full_attention", "conv"), 1),
}
PLANS = {
    "published": ((0, 1, 1), (1, 1, 1), (2, 4, 4), (18, 3, 2)),
    "harness": ((0, 1, 1), (1, 2, 2), (5, 1, 1)),
    "no repeat": ((0, 1, 1), (1, 1, 1), (2, 1, 1), (3, 1, 1)),
}


def params_of(case, seed):
    kinds, dense = LISTS[case]
    return Lfm2AlgorithmParams(
        **{**TINY, "layer_types": kinds, "num_hidden_layers": len(kinds), "num_dense_layers": dense}, seed=seed
    )


def parents_logits(weights, tokens, config):
    """The program as the parent ran it: layer by layer, unrolled, every
    sparse layer through ``expert_ffn(held=)`` over ALL the copies."""
    x = weights["embed"][tokens].astype(jnp.float32)
    rows, length, hidden = x.shape
    position = jnp.broadcast_to(jnp.arange(length), tokens.shape)
    first, count = config.experts_held
    for i in range(config.num_hidden_layers):
        layer = lfm2.layer_of(weights, i)
        n = lfm2._rms(x, layer["operator_norm"], config.norm_eps)
        if config.is_conv(i):
            h = x + lfm2._conv_mixer(n, position, layer)
        else:
            h = x + lfm2._attention_mixer(n, None, position, layer, config)
        n2 = lfm2._rms(h, layer["ffn_norm"], config.norm_eps).reshape(rows * length, hidden)
        if config.is_dense(i):
            y = moe.gated_mlp(n2, layer["w1"], layer["w3"], layer["w2"])
        else:
            chosen = moe.route_sigmoid(
                n2, layer["router"], layer["expert_bias"], config.num_experts_per_tok,
                config.routed_scaling_factor, eps=lfm2.ROUTER_EPS,
            )
            y = moe.expert_ffn(n2, *chosen, layer["gate"], layer["up"], layer["down"], held=(first, count))
        x = h + y.reshape(rows, length, hidden)
    out = lfm2._rms(x, weights["embedding_norm"], config.norm_eps)
    return jnp.dot(out, weights["embed"].astype(jnp.float32).T, precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("case", list(LISTS))
def test_the_scanned_program_equals_the_reference_and_the_parents_unrolled_layers(case):
    params = params_of(case, 12)
    config = params.config()
    assert lfm2.scan_plan(config) == PLANS[case]
    weights = upcast(lfm2.init_weights(config, 12))
    tokens = np.random.default_rng(12).integers(0, N_ITEMS, (2, 64)).astype(np.int32)
    got = np.asarray(lfm2.all_logits(weights, tokens, config=config))
    layers = config.num_hidden_layers
    np.testing.assert_allclose(got, parents_logits(weights, jnp.asarray(tokens), config), atol=ATOL, rtol=0)
    for row in range(2):
        want = reference.forward(published(weights, layers), reference_config(params), tokens[row])
        np.testing.assert_allclose(got[row], want, atol=ATOL, rtol=0)
    # one scan a repeat, and none where nothing repeats: that list compiles unrolled
    program = str(jax.make_jaxpr(lambda w, t: lfm2.all_logits.__wrapped__(w, t, config=config))(weights, tokens))
    assert program.count(" scan[") == sum(repeats > 1 for _, _, repeats in PLANS[case])
    # the sparse layers' bodies: a scan's pattern once, an unrolled layer's own
    bodies = sum(period for start, period, _ in PLANS[case] if not config.is_dense(start))
    assert program.count(" cond[") == bodies == {"published": 7, "harness": 3, "no repeat": 3}[case]


def parents_draw(config, seed, dtype=jnp.bfloat16):
    """``init_weights`` as the parent had it: one key a published array, the
    names sorted, every array alone under ``"<i>.<name>"``."""
    from predictionio_tpu.models.sequential.olmoe import _normal

    h = config.hidden_size
    specs = {"embed": ((config.vocab_size, h), h), "embedding_norm": ((h,), None)}
    for i in range(config.num_hidden_layers):
        specs.update({f"{i}.{name}": spec for name, spec in lfm2._layer_shapes(config, i).items()})
    keys = jax.random.split(jax.random.key(seed, impl="rbg"), len(specs))
    weights = {}
    for key, (name, (shape, fan_in)) in zip(keys, sorted(specs.items())):
        if fan_in is not None:
            weights[name] = _normal(key, shape, 1.0 / float(np.sqrt(fan_in)), 0.0, dtype)
        elif name.endswith(".expert_bias"):
            weights[name] = _normal(key, shape, 0.02, 0.0, dtype)
        else:
            weights[name] = _normal(key, shape, 0.1, 1.0, dtype)
    return weights


@pytest.mark.parametrize("case,seed", [("published", 3), ("harness", 4), ("no repeat", 2**31 - 5)])
def test_every_layers_arrays_are_the_parents_draw_for_the_seed_however_they_are_stacked(case, seed):
    config = params_of(case, seed).config()
    weights = lfm2.init_weights(config, seed)
    assert weights.keys() == lfm2.weight_shapes(config).keys()
    assert {name: a.shape for name, a in weights.items()} == lfm2.weight_shapes(config)
    flat = published(weights, config.num_hidden_layers)
    want = parents_draw(config, seed)
    assert flat.keys() == want.keys()
    for name in want:
        assert flat[name].dtype == want[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(flat[name], np.float32), np.asarray(want[name], np.float32), err_msg=name)
    # a scan's arrays lie stacked in the tree, its steps in front
    stacked = [name for name in weights if "+" in name]
    assert bool(stacked) == any(repeats > 1 for _, _, repeats in PLANS[case])
    for start, period, repeats in PLANS[case]:
        if repeats > 1:
            gate = weights[f"{start}+{period}x{repeats}.0.gate"]
            assert gate.shape == (repeats, 4, 64, 32)
            np.testing.assert_array_equal(
                np.asarray(gate[1], np.float32), np.asarray(want[f"{start + period}.gate"], np.float32)
            )


def routed(seed, tokens=256, held=(4, 4), all_held=False, padding=0):
    """A layer's inputs: ``tokens`` tokens of which the last ``padding`` are
    a stream's padding, 4 copies each over 16 experts (``all_held``: every
    copy to one of the four held; the padding's always)."""
    x, gate, up, down, router, bias = expert_case(seed, tokens=tokens)
    first, count = held
    if all_held:
        bias = bias.at[first : first + count].add(10.0)
    weights, experts = moe.route_sigmoid(x, router, bias, 4, 1.0, eps=lfm2.ROUTER_EPS)
    real = jnp.arange(tokens) < tokens - padding
    experts = jnp.where(real[:, None], experts, first + jnp.arange(4))
    block = slice(first, first + count)
    return x, weights, experts, gate[block], up[block], down[block], real


@pytest.mark.parametrize("overflow", ["whole", "rounds"])
@pytest.mark.parametrize("all_held", [True, False])
def test_a_routing_that_overflows_the_block_takes_the_way_out_and_says_so(all_held, overflow):
    x, weights, experts, gate, up, down, _ = routed(21, all_held=all_held)
    assert moe.held_block(256, 4, 4, 16) == (512, 64)  # half of the 1,024 copies
    want = moe.expert_ffn(x, weights, experts, gate, up, down, held=(4, 4))
    got, rounds = moe.held_expert_ffn(x, weights, experts, gate, up, down, held=(4, 4, 16), overflow=overflow)
    assert int(rounds) == (2 if all_held else 1)  # (1,024 held copies are two windows of 512 too)
    # (the other branch IS that path; compiled here and run eagerly there, the last bit may differ)
    np.testing.assert_allclose(got, want, atol=1e-6 if all_held and overflow == "whole" else 1e-5, rtol=0)
    assert float(jnp.abs(want).max()) > 0.1
    with pytest.raises(ValueError, match="overflow"):
        moe.held_expert_ffn(x, weights, experts, gate, up, down, held=(4, 4, 16), overflow="cond")


@pytest.mark.parametrize("case", ["every copy to a held expert", "the router's own"])
def test_the_program_counts_the_sparse_layers_that_overflowed(trained, case):
    algorithm, model = trained
    config = model.config
    weights = dict(model.weights)
    if case == "every copy to a held expert":
        for name in [n for n in weights if n.endswith(".expert_bias")]:
            weights[name] = weights[name].at[..., 4:8].add(10.0)
    session = np.random.default_rng(3).integers(0, N_ITEMS, 250).astype(np.int32)
    monkeypatched = dataclasses.replace(config, max_position_embeddings=512)
    stream = staged(algorithm, model, [session], [0], 256)
    out, counted = lfm2.session_vectors(weights, *stream, config=monkeypatched)
    overflowed = int(counted[2])
    assert overflowed == (config.sparse_layers if case == "every copy to a held expert" else 0)
    assert int(counted[1]) == (5 * 250 * 4 if overflowed else int(counted[1])) and int(counted[0]) <= int(counted[1])
    # ... and the way out is exact: the parent's layers on the same tree
    want = parents_logits(weights, jnp.asarray(session)[None], config)[0, -1]
    head = np.asarray(weights["embed"], np.float32)
    np.testing.assert_allclose(np.asarray(out[0]) @ head.T, want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("overflow", ["whole", "rounds"])
@pytest.mark.parametrize("padding", [64, 200])
def test_a_streams_padding_gets_no_row_and_changes_nothing_of_a_real_token(padding, overflow):
    # the padding's copies ALL name held experts: with rows of their own they would overflow the block
    x, weights, experts, gate, up, down, real = routed(22, padding=padding)
    got, rounds = moe.held_expert_ffn(
        x, weights, experts, gate, up, down, held=(4, 4, 16), counted=real, overflow=overflow
    )
    assert int(rounds) == 1
    uncounted, more = moe.held_expert_ffn(x, weights, experts, gate, up, down, held=(4, 4, 16), overflow=overflow)
    assert int(more) == 2
    keep = np.asarray(real)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(uncounted)[keep], atol=1e-5, rtol=0)
    assert float(jnp.abs(got[~keep]).max()) == 0.0 and float(jnp.abs(uncounted[~keep]).max()) > 0.1
    # ... and none of the copies counted are the padding's
    load = moe.expert_load(experts - 4, 4, real)
    everyones = int(moe.expert_load(experts - 4, 4).sum())
    assert int(load.sum()) == int(moe.expert_load(experts[:-padding] - 4, 4).sum()) < everyones



# sessions (their lengths) of ONE stream, where each starts, the stream's
# length, the budget and the longest session the engine keeps
PACKED = {
    "one ends inside a block of 64, one is exactly 64": ((37, 64, 100), (0, 64, 128), 256, 256, 512),
    "one longer than the budget shares its stream": ((300, 64, 17, 40), (0, 320, 384, 448), 512, 256, 512),
    "32 sessions at the chip's budget": (tuple(range(33, 65)), tuple(range(0, 2048, 64)), 2048, 2048, 4096),
    "2,049 to 4,096 items beside others": ((2100, 1000, 64, 500), (0, 2112, 3136, 3200), 4096, 2048, 4096),
}


@pytest.mark.parametrize("case", list(PACKED))
def test_a_packed_streams_session_vectors_equal_the_sessions_alone(case, monkeypatch):
    lengths, starts, length, budget, longest = PACKED[case]
    monkeypatch.setattr(lfm2, "TOKEN_BUDGET", budget)
    monkeypatch.setattr(lfm2, "MAX_SESSION", longest)
    params = Lfm2AlgorithmParams(**{**TINY, "max_position_embeddings": longest}, seed=4)
    algorithm = Lfm2Algorithm(params)
    rng = np.random.default_rng(len(lengths))
    sessions = [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths]
    model = algorithm.train(None, TrainingData(["u"], [sessions[0]], [f"i{i}" for i in range(N_ITEMS)]))
    model.weights = upcast(model.weights)
    assert length in model.config.stream_shapes()
    packed, _ = lfm2.session_vectors(
        model.weights, *staged(algorithm, model, sessions, starts, length), config=model.config
    )
    assert packed.shape == (budget // 64, 64)
    for row, session in enumerate(sessions):
        # alone, from the stream's first position: the same compiled program.
        # A convolution's taps or a key leaked from the session in front
        # would move it by the vectors' own order
        alone, _ = lfm2.session_vectors(
            model.weights, *staged(algorithm, model, [session], [0], length), config=model.config
        )
        np.testing.assert_allclose(packed[row], alone[0], atol=ATOL, rtol=0, err_msg=f"session {row}")
    # ... and the reference's answer at the session's true length
    want = reference.next_item_logits(published(model.weights), reference_config(params), sessions[1])
    head = np.asarray(model.weights["embed"], np.float32)
    np.testing.assert_allclose(np.asarray(packed[1]) @ head.T, want, atol=ATOL)


def test_a_sessions_scores_do_not_move_when_its_neighbour_in_the_stream_changes(trained):
    algorithm, model = trained
    rng = np.random.default_rng(11)
    mine = rng.integers(0, N_ITEMS, 50).astype(np.int32)
    vectors = []
    for seed in (0, 1):
        other = np.random.default_rng(seed).integers(0, N_ITEMS, 64).astype(np.int32)  # ends where mine begins
        stream = staged(algorithm, model, [other, mine, other[:9]], [0, 64, 128], 256)
        out, _ = lfm2.session_vectors(model.weights, *stream, config=model.config)
        vectors.append(np.asarray(out))
    np.testing.assert_allclose(vectors[0][1], vectors[1][1], atol=1e-6, rtol=0)
    assert np.abs(vectors[0][0] - vectors[1][0]).max() > 100 * ATOL  # the neighbour itself did change


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


def vectors_of(algorithm, model, staged_rows, rows, program=None):
    *arrays, _ = algorithm._stack([staged_rows[r] for r in rows])
    out, _ = (program or lfm2.session_vectors)(model.weights, *map(jnp.asarray, arrays), config=model.config)
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
    attend = lfm2.fused_attention

    def leaky(q, k, v, **kwargs):  # the first position's key is the row in front's
        k = k.at[:, :, 0].set(jnp.roll(k, 1, axis=0)[:, :, 0])
        return attend(q, k, v, **kwargs)

    monkeypatch.setattr(lfm2, "fused_attention", leaky)


def _a_convolution_tap(monkeypatch):
    convolve = lfm2.short_conv

    def leaky(x, w, position=None, **kw):  # the row in front's last input reaches a row's first position
        return convolve(x.at[:, 0].add(jnp.roll(x, 1, axis=0)[:, -1]), w, position=position, **kw)

    monkeypatch.setattr(lfm2, "short_conv", leaky)


def _no_position_mask(monkeypatch):
    convolve = lfm2.short_conv
    monkeypatch.setattr(lfm2, "short_conv", lambda x, w, position=None, **kw: convolve(x, w, **kw))


@pytest.mark.parametrize("plant", [_a_key, _a_convolution_tap, _no_position_mask])
def test_a_leak_from_the_row_or_the_session_in_front_moves_the_vectors(trained, plant, monkeypatch):
    """What the equalities above can tell: a key or a convolution tap of the
    row in front, or the taps' mask dropped, each planted alone, moves the
    vectors by far more than the tolerance."""
    algorithm, model = trained
    staged_rows = stacked_streams(algorithm, model)
    sound = vectors_of(algorithm, model, staged_rows, (0, 1, 2, 3))
    plant(monkeypatch)
    # (a function of its own: a jit's traces are kept by the function traced)
    planted = jax.jit(lambda *a, config: lfm2.session_vectors.__wrapped__(*a, config=config), static_argnames=("config",))
    leaked = vectors_of(algorithm, model, staged_rows, (0, 1, 2, 3), planted)
    for r, (lengths, _) in enumerate(ROWS):
        if plant is _no_position_mask and len(lengths) == 1:
            continue  # a lone session at its stream's start has nothing in front
        assert np.abs(leaked[r, : len(lengths)] - sound[r, : len(lengths)]).max() > 100 * ATOL, r


@pytest.fixture(scope="module")
def stacking():
    """An algorithm and a model whose sessions reach 512 items, so that the
    streams are of 256 tokens and of 512, and users by their session's length."""
    lengths = [17, 40, 60, 500] + [150] * 8
    rng = np.random.default_rng(38)
    algorithm = Lfm2Algorithm(Lfm2AlgorithmParams(**{**TINY, "max_position_embeddings": 512}, seed=6))
    users = [f"u{i}" for i in range(len(lengths))]
    model = algorithm.train(None, TrainingData(
        users, [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths], [f"i{i}" for i in range(N_ITEMS)],
    ))
    model.weights = upcast(model.weights)
    return algorithm, model, users


@pytest.fixture(autouse=True)
def sessions_of_up_to_512_items_in_stacks_of_four(request, monkeypatch):
    """The engine's stacking, which this backbone shares with ``olmoe``, at
    ``olmoe``'s height whatever its own is (the chip's readings stand beside
    ``lfm2.STACKED_ROWS``)."""
    if "stacking" in request.fixturenames:
        monkeypatch.setattr(lfm2, "MAX_SESSION", 512)
        monkeypatch.setattr(lfm2, "STACKED_ROWS", 4)


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
    pool = {n: [u for u in users if len(model.session_tokens(Query(user=u))) == n] for n in set(lengths)}
    queries = [Query(user=pool[n].pop(), num=5) for n in lengths]
    _, streams = algorithm._plan(model, queries)
    programs = algorithm._programs(model, streams)
    assert sorted((len(rows), streams[rows[0]][0]) for rows in programs) == sorted(shapes)
    answers = algorithm.predict_batch(model, queries)
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


def test_lfm2_is_an_algorithm_of_the_engine_that_shares_olmoes_serving():
    from predictionio_tpu.models.sequential.engine import BackboneAlgorithm

    engine = engine_factory()
    variant = {
        "datasource": {"params": {"appName": "seq"}},
        "algorithms": [{"name": "lfm2", "params": {**TINY, "seed": 7}}],
    }
    _, _, (algorithm,), _ = engine.make_components(engine.engine_params_from_variant(variant))
    assert type(algorithm) is Lfm2Algorithm and algorithm.params.experts_held == (4, 4)
    # no staging, batching or serving code of its own
    own = {name for name in vars(Lfm2Algorithm) if not name.startswith("__")}
    assert own == {"params_class", "model_class"}
    assert {name for name in vars(Lfm2Model) if not name.startswith("__")} == {"module"}
    for name in ("_plan", "_stage", "_stack", "_answer", "predict_batch_dispatch", "warmup_serving", "train"):
        assert getattr(Lfm2Algorithm, name) is getattr(BackboneAlgorithm, name) is getattr(OlmoeAlgorithm, name)
    assert Lfm2Model.load.__func__ is OlmoeAlgorithm.model_class.load.__func__
    assert lfm2.session_vectors.__name__ == "session_vectors"  # the program's name in a trace


def test_a_batch_of_mixed_lengths_is_answered_in_order_as_the_reference_does(trained):
    algorithm, model = trained
    data = training_data()
    queries = [Query(user=u, num=5) for u in data.users] + [Query(user="nobody", num=5)]
    before = {k: algorithm.instruments.copies.value(where=k) for k in ("held", "absent")}
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


def test_the_head_is_the_embedding_and_the_tree_holds_no_second_table(trained):
    _, model = trained
    assert "lm_head" not in model.weights and "embed" in model.weights
    np.testing.assert_array_equal(np.asarray(model.head()), np.asarray(model.weights["embed"], np.float32))
    assert model.head().dtype == jnp.float32 and model.head() is model.head()


@pytest.mark.parametrize(
    "change", [{"conv_bias": True}, {"norm_topk_prob": False}, {"use_expert_bias": False}, {"model_type": "lfm2"}],
)
def test_unimplemented_config_values_are_refused_not_ignored(change):
    with pytest.raises(ValueError, match="not implemented"):
        Lfm2AlgorithmParams(**{**TINY, **change}).config()


@pytest.mark.parametrize(
    "change,match",
    [({"experts_held": (14, 4)}, "no block"),
     ({"layer_types": ("conv",) * 5}, "names 5 layers"),
     ({"layer_types": ("conv",) * 5 + ("sliding_attention",)}, "only 'conv' and 'full_attention'"),
     ({"num_key_value_heads": 3}, "do not divide")],
)
def test_a_share_or_a_layer_list_that_cannot_be_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        Lfm2AlgorithmParams(**{**TINY, **change}).config()


def test_the_published_defaults_are_the_published_config():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines() if '"LFM2-8B-A1B"' in line)
    params = dataclasses.asdict(Lfm2AlgorithmParams())
    assert {key: list(params[key]) if key == "layer_types" else params[key] for key in row["config"]} == row["config"]
    config = Lfm2AlgorithmParams().config()
    assert config.experts_held == (0, 32) and config.head_dim == 64 and config.sparse_layers == 22
    assert [i for i in range(24) if not config.is_conv(i)] == [2, 6, 10, 14, 18, 21]
    assert [i for i in range(24) if config.is_dense(i)] == [0, 1]


def test_the_variant_file_carries_the_published_config_and_states_the_share():
    import predictionio_tpu.models.sequential as package

    variant = json.loads((Path(package.__file__).parent / "variants" / "lfm2-8b-a1b.json").read_text())
    raw = variant["algorithms"][0]["params"]
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    published = dataclasses.asdict(Lfm2AlgorithmParams())
    published["layer_types"] = list(published["layer_types"])
    stated = {"experts_held": [0, 8], "seed": 3}
    assert {k: v for k, v in raw.items() if k not in stated} == {k: v for k, v in published.items() if k not in stated}
    assert {k: raw[k] for k in stated} == stated
    config = params.config()
    assert config.experts_held == (0, 8) and config.table_rows == 65536 and config.num_hidden_layers == 24
    shapes = lfm2.weight_shapes(config)
    parameters = sum(int(np.prod(shape)) for shape in shapes.values())
    assert 2.52e9 < parameters < 2.54e9  # 5.05 GB in bfloat16
    whole = sum(int(np.prod(s)) for s in lfm2.weight_shapes(Lfm2AlgorithmParams().config()).values())
    assert 8.3e9 < whole < 8.4e9  # the published model, every expert held
    # layers 2 to 17 are one scan of four steps over [attention, conv, conv, conv], each array its steps' stacked
    assert lfm2.scan_plan(config) == ((0, 1, 1), (1, 1, 1), (2, 4, 4), (18, 3, 2))
    assert shapes["2+4x4.0.gate"] == (4, 8, 2048, 1792) and shapes["2+4x4.0.router"] == (4, 2048, 32)
    assert shapes["2+4x4.0.q_proj"] == (4, 2048, 2048) and shapes["2+4x4.0.k_proj"] == (4, 2048, 512)
    assert shapes["2+4x4.0.q_layernorm"] == (4, 64) and shapes["18+3x2.2.in_proj"] == (2, 2048, 6144)
    assert shapes["0.in_proj"] == (2048, 6144) and shapes["0.conv"] == (3, 2048) and shapes["0.w1"] == (2048, 7168)
    assert shapes["embed"] == (65536, 2048) and "lm_head" not in shapes
    assert not any(name.endswith(".w1") for name in shapes if name[:2] not in ("0.", "1."))


def test_save_then_load_is_equal_bit_for_bit_and_the_manifest_names_the_backbone(tmp_path, monkeypatch):
    from predictionio_tpu.workflow import model_io

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "base"))
    algorithm = Lfm2Algorithm(Lfm2AlgorithmParams(**TINY, seed=7))
    model = algorithm.train(None, training_data(n_users=4))
    engine = engine_factory()
    params = engine.engine_params_from_variant({
        "datasource": {"params": {"appName": "seq"}},
        "algorithms": [{"name": "lfm2", "params": {**TINY, "seed": 7}}],
    })
    (persisted,) = engine.make_serializable_models(None, params, [model])
    assert isinstance(persisted, PersistentModelManifest)
    assert persisted.class_path == "predictionio_tpu.models.sequential.engine.Lfm2Model"
    (deployed,) = engine.prepare_deploy(None, params, model_io.deserialize_models(model_io.serialize_models([persisted])))
    assert isinstance(deployed, Lfm2Model) and deployed.config == model.config
    assert model.save("m1", algorithm.params, str(tmp_path))
    loaded = Lfm2Model.load("m1", algorithm.params, str(tmp_path))
    assert loaded.config == model.config and loaded.item_vocab == model.item_vocab
    assert loaded.weights.keys() == model.weights.keys()
    for name in model.weights:
        assert loaded.weights[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(loaded.weights[name]), np.asarray(model.weights[name]))
    queries = [Query(user=f"u{i}", num=4) for i in range(4)]
    assert algorithm.predict_batch(loaded, queries) == algorithm.predict_batch(model, queries)


# --------------------------------------------------------------- server

MEMORY_STORAGE = {
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
}


def test_train_then_deploy_then_one_query_through_the_variant(tmp_path, monkeypatch):
    """What ``pio train`` → ``pio deploy`` → ``POST /queries.json`` do with the
    variant file (at the tiny widths): the variant's ``"name": "lfm2"`` builds
    the algorithm, the trained model goes through the model repository as a
    manifest, and the deployed one answers over HTTP behind ``QueryServer``
    and its ``_MicroBatcher`` as the reference does."""
    import asyncio
    import socket
    import threading
    import urllib.request

    import predictionio_tpu.models.sequential as package
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.workflow import model_io
    from predictionio_tpu.workflow.create_server import QueryServer, ServerConfig
    from predictionio_tpu.workflow.engine_loader import EngineManifest

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    variant = json.loads((Path(package.__file__).parent / "variants" / "lfm2-8b-a1b.json").read_text())
    assert variant["algorithms"][0]["name"] == "lfm2"
    variant["algorithms"][0]["params"].update({**TINY, "layer_types": list(TINY["layer_types"]), "experts_held": [4, 4], "seed": 5})
    engine = engine_factory()
    params = engine.engine_params_from_variant(variant)
    _, _, (algorithm,), _ = engine.make_components(params)
    data = training_data()
    trained = algorithm.train(None, data)  # `pio train`
    (persisted,) = engine.make_serializable_models(None, params, [trained])
    blob = model_io.serialize_models([persisted])
    assert len(blob) < 1024  # a manifest, not the weights
    (model,) = engine.prepare_deploy(None, params, model_io.deserialize_models(blob))  # `pio deploy`
    assert isinstance(model, Lfm2Model) and all(a.dtype == jnp.bfloat16 for a in model.weights.values())
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
        config=ServerConfig(ip="127.0.0.1", port=port, max_batch_size=8),
    )
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(server.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=serve, daemon=True).start()
    assert started.wait(180)
    try:
        assert type(server.algorithms[0]) is Lfm2Algorithm and server.algorithms[0].batch_limit() is None
        request = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json", json.dumps({"user": "u2", "num": 5}).encode(),
            {"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request, timeout=60) as resp:
            rows = json.loads(resp.read())["itemScores"]
        session = data.sequences[2]
        config = reference_config(algorithm.params)
        logits = np.asarray(reference.next_item_logits(published(model.weights), config, session))
        ids = [int(r["item"][1:]) for r in rows]
        assert len(ids) == 5 and not set(ids) & set(session.tolist()) and max(ids) < N_ITEMS
        # the served tree is bfloat16: within the bf16 tree's own tolerance of the reference
        assert np.abs(np.asarray([r["score"] for r in rows]) - logits[ids]).max() < BF16_TIPPED[0] * 4
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        assert 'pio_seq_tokens_total{kind="real"}' in text and 'pio_moe_copies_total{where="absent"}' in text
    finally:
        loop.call_soon_threadsafe(loop.stop)
