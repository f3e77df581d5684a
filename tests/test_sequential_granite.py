"""The sequential engine's ``granite`` scorer against its plain reference, at a
tiny size on the CPU with both kinds of layer: 4 layers (mamba, attention,
mamba, mamba: the LIST's first four of six), hidden 64, a scan of 8 heads of
16 over a state of 16 with 4 taps, 4 query heads over 2 key/value heads of 16
with no positions, 12 routed experts (no power of two) of width 32 with 3 a
token of which the chip holds experts 6 to 11 (HALF: every copy is laid out),
a shared expert of width 48, a vocabulary of 128 rows of 256 tied to the head,
and the four multipliers at values of their own.

Where a test compares values it upcasts the algorithm's own bf16 draws to
float32 for both sides, as ``test_sequential_olmoe.py`` does.
"""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import PersistentModelManifest
from predictionio_tpu.models.sequential import (
    GraniteAlgorithm,
    GraniteAlgorithmParams,
    GraniteModel,
    OlmoeAlgorithm,
    Query,
    TrainingData,
    engine_factory,
    granite,
    granite_reference as reference,
)
from predictionio_tpu.ops import linear_attention, moe

TINY = dict(
    hidden_size=64, intermediate_size=32, shared_intermediate_size=48, num_hidden_layers=4,
    layer_types=("mamba", "attention", "mamba", "mamba", "mamba", "attention"),
    num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    num_local_experts=12, num_experts_per_tok=3, attention_multiplier=0.1, embedding_multiplier=3.0,
    residual_multiplier=0.5, logits_scaling=2.0, vocab_size=256, max_position_embeddings=256,
    experts_held=(6, 6), vocab_slice=(0, 128),
)
N_ITEMS = 120  # 8 rows of the slice are no item
# float32 against float32 on the CPU: the sides differ by the order of their
# sums (a chunked scan against the recurrence, blocked attention, grouped
# products against a loop over experts), through four layers; logits are of
# unit order and the worst seen over the seeds below is 2e-5. 2e-4 is ten
# times that and ten times under what ONE bf16 product does (2^-9).
ATOL = 2e-4
# The algorithm's own bf16 tree against the SAME values in float32 through the
# reference (``test_sequential_kimi_linear.py``: why two numbers): the MEDIAN
# position's worst logit tight, the share of positions a tipped router moved
# loosely.
BF16_MEDIAN, BF16_TIPPED = 0.1, (0.15, 0.25)


@pytest.fixture(autouse=True)
def small_programs(monkeypatch):
    """A stream holds 256 tokens here, and four sessions at most."""
    monkeypatch.setattr(granite, "TOKEN_BUDGET", 256)


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
    algorithm = GraniteAlgorithm(GraniteAlgorithmParams(**TINY, seed=5))
    model = algorithm.train(None, training_data())
    model.weights = upcast(model.weights)
    return algorithm, model


def reference_config(params: GraniteAlgorithmParams, **changes) -> dict:
    """What the reference reads: the published keys and the chip's share."""
    return {**dataclasses.asdict(params), **changes}


_logits: dict = {}
_jitted: dict = {}


def reference_answer(algorithm, model, session: np.ndarray, num: int):
    config = reference_config(algorithm.params)
    if id(model) not in _jitted:
        _jitted[id(model)] = jax.jit(lambda t: reference.next_item_logits(model.weights, config, t))
    key = (id(model), session.tobytes())
    if key not in _logits:
        _logits[key] = np.asarray(_jitted[id(model)](jnp.asarray(session)))
    logits = _logits[key]
    allowed = np.ones(len(logits), bool)
    allowed[N_ITEMS:] = False
    allowed[session] = False
    return logits, np.argsort(-np.where(allowed, logits, -np.inf), kind="stable")[:num]


# ------------------------------------------------------- ops/linear_attention


def scan_case(seed, length, heads=4, p=8, state=16):
    """Inputs of the strengths a layer gives them: steps of 0.01 to 1, ``a``
    of -1 to -16, so that a head's decay a step runs from 0.99 to e^-16."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(length, heads, p)), jnp.float32)
    step = jnp.asarray(np.exp(rng.uniform(np.log(0.01), 0.0, (length, heads))), jnp.float32)
    a = -jnp.asarray(np.exp(rng.uniform(0.0, np.log(16.0), heads)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(length, state)), jnp.float32) for _ in range(2))
    d = jnp.asarray(rng.normal(size=heads), jnp.float32)
    return x, step, a, b, c, d


# The scan has two forms (``ops/linear_attention.ssd``): the XLA form, which is
# what ``ssd`` is off the chip, and the kernel that serves on it, run here
# interpreted. The kernel's cases take the published head (64 channels over a
# state of 128: what the chip's kernel tiles) and its products' three
# bfloat16 passes, which the CPU rounds as the chip does: 1e-5
# of the outputs' size where the XLA form, float32 here, is within 3e-6.
FORMS = {"xla": linear_attention.ssd, "kernel": functools.partial(linear_attention.ssd_kernel, interpret=True)}
HEADS = {"xla": dict(heads=4, p=8, state=16), "kernel": dict(heads=4, p=64, state=128)}
# how far a prefix and the rest from its state may lie from the whole scan: the
# XLA form, float32 here, absolutely; the kernel's three-pass products by the
# size of what they sum (its outputs and its state reach 10 and more)
HANDED_ON = {"xla": lambda whole: 2e-4, "kernel": lambda whole: 2e-4 * max(1.0, float(jnp.abs(whole).max()))}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("chunk", [64, 128, 256])
@pytest.mark.parametrize("length", [50, 256, 640])
def test_the_chunked_scan_equals_the_recurrence_at_every_chunk(chunk, length, form):
    x, step, a, b, c, d = scan_case(length, length, **HEADS[form])
    want = reference.ssd_recurrence(x, step, a, b, c, d)
    got, _ = FORMS[form](x[None], step[None], a, b[None], c[None], d, chunk=chunk)
    assert got.shape == (1, length) + x.shape[1:]
    np.testing.assert_allclose(got[0], want, atol=1e-4 * float(jnp.abs(want).max()), rtol=0)


# sessions (start, end) in a row of 640: the second and the third begin INSIDE
# a chunk of 128 and of 256 (on multiples of 64), the padding between them too
SESSIONS = ((0, 100), (128, 328), (384, 640))


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("chunk", [64, 128, 256])
def test_a_session_that_begins_inside_a_chunk_begins_from_a_zero_state(chunk, form):
    ssd = FORMS[form]
    x, step, a, b, c, d = scan_case(3, 640, **HEADS[form])
    segment = np.full(640, -1, np.int32)
    for i, (start, end) in enumerate(SESSIONS):
        segment[start:end] = i
    got, _ = ssd(x[None], step[None], a, b[None], c[None], d, segment=jnp.asarray(segment)[None], chunk=chunk)
    assert bool(jnp.isfinite(got).all())  # the padding's output means nothing and is a number
    for start, end in SESSIONS:
        alone = (v[start:end] for v in (x, step, b, c))
        x_, step_, b_, c_ = alone
        want = reference.ssd_recurrence(x_, step_, a, b_, c_, d)
        np.testing.assert_allclose(got[0, start:end], want, atol=1e-4 * float(jnp.abs(want).max()), rtol=0)
    # ... and WITHOUT the ids the second session reads the first one's state
    leaked, _ = ssd(x[None], step[None], a, b[None], c[None], d, chunk=chunk)
    assert float(jnp.abs(leaked[0, 128:160] - got[0, 128:160]).max()) > 1e-2


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("cut", [64, 200, 256])
def test_a_prefix_then_the_rest_from_its_state_equals_the_whole_scan(cut, form):
    ssd = FORMS[form]
    x, step, a, b, c, d = (v[None] if v.ndim > 1 else v for v in scan_case(9, 400, **HEADS[form]))
    whole, last = ssd(x, step, a, b, c, d, chunk=128)
    head, state = ssd(x[:, :cut], step[:, :cut], a, b[:, :cut], c[:, :cut], d, chunk=128)
    tail, end = ssd(x[:, cut:], step[:, cut:], a, b[:, cut:], c[:, cut:], d, state=state, chunk=128)
    np.testing.assert_allclose(jnp.concatenate([head, tail], axis=1), whole, atol=HANDED_ON[form](whole), rtol=0)
    np.testing.assert_allclose(end, last, atol=HANDED_ON[form](last), rtol=0)


def test_the_kernel_without_ids_is_the_xla_form_and_hands_on_the_same_state():
    """``segment=None``: one session a row, the padding behind a ragged
    length (its step is 0) part of it, so the state that comes back is the
    state after the last real position in both forms."""
    x, step, a, b, c, d = (v[None] if v.ndim > 1 else v for v in scan_case(11, 200, **HEADS["kernel"]))
    want, last = FORMS["xla"](x, step, a, b, c, d, chunk=128)
    got, state = FORMS["kernel"](x, step, a, b, c, d, chunk=128)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()), rtol=0)
    np.testing.assert_allclose(state, last, atol=1e-4 * float(jnp.abs(last).max()), rtol=0)
    # ... and no skip where none is given
    bare, _ = FORMS["kernel"](x, step, a, b, c, chunk=128)
    np.testing.assert_allclose(bare, got - d[:, None] * x, atol=1e-5 * float(jnp.abs(want).max()), rtol=0)


@pytest.mark.parametrize("form", list(FORMS))
def test_a_row_of_padding_alone_hands_the_state_on_as_it_came(form):
    """What ``ssd`` pads a row with (the id -1 and a step of 0) through two
    whole chunks: nothing is added to the state and nothing of it decays."""
    x, _, a, b, c, d = (v[None] if v.ndim > 1 else v for v in scan_case(5, 256, **HEADS[form]))
    rng = np.random.default_rng(5)
    state = jnp.asarray(rng.normal(size=(1,) + x.shape[2:] + b.shape[-1:]), jnp.float32)
    padding = dict(segment=jnp.full((1, 256), -1, jnp.int32), state=state, chunk=128)
    y, after = FORMS[form](x, jnp.zeros(x.shape[:3], jnp.float32), a, b, c, d, **padding)
    assert bool(jnp.isfinite(y).all())
    np.testing.assert_array_equal(after, state)


@pytest.mark.parametrize("form", list(FORMS))
def test_two_rows_are_scanned_each_alone(form):
    """``B`` = 2, each row with its own sessions and its own incoming state:
    a row comes out as it does when it is the only one."""
    ssd = FORMS[form]
    x, step, a, b, c, d = scan_case(13, 2 * 320, **HEADS[form])
    x, step, b, c = (v.reshape((2, 320) + v.shape[1:]) for v in (x, step, b, c))
    segment = np.full((2, 320), -1, np.int32)
    segment[0, :90], segment[0, 128:320], segment[1, :200], segment[1, 256:300] = 0, 1, 0, 1
    segment = jnp.asarray(segment)
    rng = np.random.default_rng(13)
    state = jnp.asarray(rng.normal(size=(2,) + x.shape[2:] + b.shape[-1:]), jnp.float32)
    both, after = ssd(x, step, a, b, c, d, state=state, segment=segment, chunk=128)
    for row in range(2):
        at = slice(row, row + 1)
        alone, its = ssd(x[at], step[at], a, b[at], c[at], d, state=state[at], segment=segment[at], chunk=128)
        np.testing.assert_allclose(both[at], alone, atol=1e-6 * float(jnp.abs(alone).max()), rtol=0)
        np.testing.assert_allclose(after[at], its, atol=1e-6 * float(jnp.abs(its).max()), rtol=0)
    # the incoming state reaches the row's FIRST session and no other
    fresh, _ = ssd(x, step, a, b, c, d, segment=segment, chunk=128)
    assert float(jnp.abs(fresh[0, :90] - both[0, :90]).max()) > 1e-3
    np.testing.assert_array_equal(fresh[0, 128:320], both[0, 128:320])


def test_the_convolutions_bias_stands_inside_the_activation():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 40, 24)), jnp.float32)
    w, bias = jnp.asarray(rng.normal(size=(4, 24)), jnp.float32), jnp.asarray(rng.normal(size=24), jnp.float32)
    plain, _ = linear_attention.short_conv(x, w, activation=None)
    biased, tail = linear_attention.short_conv(x, w, bias=bias)
    np.testing.assert_allclose(biased, jax.nn.silu(plain + bias), atol=1e-6)
    np.testing.assert_allclose(tail, x[:, -3:])
    for row in range(2):
        np.testing.assert_allclose(biased[row], reference.short_conv(x[row], w, bias), atol=1e-6)
    # no bias: what it was
    np.testing.assert_array_equal(linear_attention.short_conv(x, w)[0], jax.nn.silu(plain))


# ------------------------------------------------------------ ops/moe: the share


def expert_case(seed, tokens=96, hidden=32, width=16, n_experts=12):
    rng = np.random.default_rng(seed)
    m = jnp.asarray(rng.normal(size=(tokens, hidden)), jnp.float32)
    layer = {
        "router": rng.normal(size=(hidden, n_experts)) / np.sqrt(hidden),
        "gate": rng.normal(size=(n_experts, hidden, width)) / np.sqrt(hidden),
        "up": rng.normal(size=(n_experts, hidden, width)) / np.sqrt(hidden),
        "down": rng.normal(size=(n_experts, width, hidden)) / np.sqrt(width),
        "shared_gate": rng.normal(size=(hidden, 24)) / np.sqrt(hidden),
        "shared_up": rng.normal(size=(hidden, 24)) / np.sqrt(hidden),
        "shared_down": rng.normal(size=(24, hidden)) / np.sqrt(24),
    }
    return m, {name: jnp.asarray(a, jnp.float32) for name, a in layer.items()}


@pytest.mark.parametrize("seed,shares", [(0, 2), (1, 4), (2, 3)])
def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once(seed, shares):
    """The two chips' partial expert sums (and four's, and three's), the
    shared expert counted once, add up to the uncut layer: by the program's
    ``held_expert_ffn`` and by the reference's loop alike."""
    m, layer = expert_case(seed)
    n_experts, k = 12, 3
    whole = reference.sparse_ffn(m, layer, {"num_experts_per_tok": k, "experts_held": (0, n_experts)})
    shared = reference.gated_mlp(m, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    weights, experts = moe.route(m, layer["router"], k, renormalise=True)
    # the program's router IS the reference's: a softmax over the chosen logits
    dense = jnp.zeros((m.shape[0], n_experts)).at[jnp.arange(m.shape[0])[:, None], experts].set(weights)
    np.testing.assert_allclose(dense, reference.router_choice(reference.router_logits(m, layer), k), atol=1e-6)
    count = n_experts // shares
    ours, theirs = shared, shared
    for first in range(0, n_experts, count):
        part = {name: layer[name][first : first + count] for name in ("gate", "up", "down")}
        y, rounds = moe.held_expert_ffn(
            m, weights, experts, part["gate"], part["up"], part["down"], held=(first, count, n_experts)
        )
        assert int(rounds) == 1
        ours = ours + y
        held = {"num_experts_per_tok": k, "experts_held": (first, count)}
        theirs = theirs + reference.sparse_ffn(m, {**layer, **part}, held) - shared
    np.testing.assert_allclose(ours, whole, atol=2e-5, rtol=0)
    np.testing.assert_allclose(theirs, whole, atol=2e-5, rtol=0)


def test_half_the_router_held_lays_out_every_copy_and_a_quarter_a_compact_block():
    # (the name is PR 49's; since PR 50 a half share has a block too.) The
    # cell's share at its two programs: twice the expected copies are all of
    # them, so the block has all the copies' rows, in the tile at or under
    # the 284 / 568 rows a group; what it saves is not rows but what the rows
    # cost: no argsort, every group from a tile's edge, no row for padding
    assert moe.held_block(2048, 10, 36, 72) == (20480, 256)
    assert moe.held_block(4096, 10, 36, 72) == (40960, 256)
    # the fallback's quarter: twice its expected copies, as Kimi-Linear's and LFM2's
    assert moe.held_block(2048, 10, 18, 72) == (10240, 256)
    # these tests' half, third and quarter
    assert moe.held_block(96, 3, 6, 12) == (288, 16)
    assert moe.held_block(96, 3, 4, 12) == (192, 16) and moe.held_block(96, 3, 3, 12) == (144, 16)
    # the expected copies and a tile a held group have to fit: three quarters
    # of the experts held, or all, is all the copies laid out by ``expert_ffn``
    assert moe.held_block(2048, 10, 54, 72) is None and moe.held_block(2048, 10, 72, 72) is None


# the block at a HALF share: 96 tokens at 3 copies over 12 experts, each half
# of the router a block of 288 rows in tiles of 16 for 288 copies (and 3
# copies a token is no part or multiple of a tile's 8 rows: the combine sums
# over the major axis, as at the cell's 10)
HALF_CASES = (
    "the router's own choice", "the router's own choice and two tokens of three padding",
    "every copy to one half", "every copy to one half and two tokens of three padding",
)


@pytest.mark.parametrize("kernel", ["ragged_dot", "interpreted"])
@pytest.mark.parametrize("overflow", ["rounds", "whole"])
@pytest.mark.parametrize("case", HALF_CASES)
def test_the_two_halves_add_up_to_the_uncut_layer_through_the_block(case, overflow, kernel, monkeypatch):
    """Each half of the router through its compact block, the way out on an
    overflow in both forms, with XLA's ``ragged_dot`` or the chip's kernel
    interpreted at the block's tile: the halves add up to the uncut layer
    (every copy laid out, and the reference's loop), a routing planted to
    send EVERY copy to one half overflows that half's block and is still
    exact, and a padding token gets no row: its ``y`` is zero and every real
    token's is what it was."""
    n_experts, k, count = 12, 3, 6
    m, layer = expert_case(10 + HALF_CASES.index(case))
    tokens = m.shape[0]
    rows, tile = moe.held_block(tokens, k, count, n_experts)
    weights, experts = moe.route(m, layer["router"], k, renormalise=True)
    if case.startswith("every copy to one half"):
        rng = np.random.default_rng(HALF_CASES.index(case))
        experts = jnp.asarray(np.stack([6 + rng.permutation(count)[:k] for _ in range(tokens)]).astype(np.int32))
    real = jnp.arange(tokens) % 3 == 0 if case.endswith("padding") else None
    counted = weights if real is None else jnp.where(real[:, None], weights, 0.0)
    if kernel == "interpreted":
        monkeypatch.setattr(
            moe, "grouped_matmul",
            lambda lhs, rhs, sizes, dtype, **kw: moe.grouped_matmul_kernel(lhs, rhs, sizes, dtype, interpret=True, **kw),
        )
    whole = moe.expert_ffn(m, counted, experts, layer["gate"], layer["up"], layer["down"])
    dense = jnp.zeros((tokens, n_experts)).at[jnp.arange(tokens)[:, None], experts].add(counted)
    want = reference.experts(m, dense, layer, (0, n_experts))
    ours, took = jnp.zeros_like(whole), []
    for first in (0, 6):
        part = [layer[name][first : first + count] for name in ("gate", "up", "down")]
        y, rounds = moe.held_expert_ffn(
            m, weights, experts, *part, held=(first, count, n_experts), counted=real, overflow=overflow
        )
        # the rounds that the half's groups, each from a tile's edge, take over the block
        mine = np.asarray(experts)[slice(None) if real is None else np.asarray(real)]
        padded = sum(-(-int((mine == e).sum()) // tile) * tile for e in range(first, first + count))
        assert int(rounds) == (-(-padded // rows) if overflow == "rounds" else 1 + (padded > rows))
        np.testing.assert_allclose(
            y, moe.expert_ffn(m, counted, experts, *part, held=(first, count)), atol=2e-5, rtol=0
        )
        if real is not None:
            assert not np.asarray(y)[~np.asarray(real)].any()
            bare, _ = moe.held_expert_ffn(m, weights, experts, *part, held=(first, count, n_experts), overflow=overflow)
            np.testing.assert_allclose(y[real], bare[real], atol=2e-5, rtol=0)
        ours, took = ours + y, took + [int(rounds)]
    # every copy to one half and every token real: 288 copies and their
    # groups' rounding in a block of 288 rows
    assert (max(took) > 1) == (case == "every copy to one half")
    np.testing.assert_allclose(ours, whole, atol=2e-5, rtol=0)
    np.testing.assert_allclose(ours, want, atol=2e-5, rtol=0)
    assert float(jnp.abs(ours).max()) > 100 * 2e-5


# ------------------------------------------------------------ the program


@pytest.mark.parametrize("length,seed", [(64, 5), (100, 6), (200, 7)])
def test_full_logits_equal_the_references(length, seed):
    params = GraniteAlgorithmParams(**TINY, seed=seed)
    config = params.config()
    weights = upcast(granite.init_weights(config, seed))
    tokens = np.random.default_rng(seed).integers(0, N_ITEMS, (2, length)).astype(np.int32)
    got = np.asarray(granite.all_logits(weights, tokens, config=config))
    assert got.shape == (2, length, 128) and 0.5 < got.std() < 2.0  # of unit order
    for row in range(2):
        want = reference.forward(weights, reference_config(params), tokens[row])
        np.testing.assert_allclose(got[row], want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("seed", [8, 9, 10])
def test_the_bf16_tree_stays_within_its_own_tolerance_of_the_reference(seed):
    params = GraniteAlgorithmParams(**TINY, seed=seed)
    config = params.config()
    weights = granite.init_weights(config, seed)
    assert all(a.dtype == jnp.bfloat16 for a in weights.values())
    tokens = np.random.default_rng(seed).integers(0, N_ITEMS, (2, 96)).astype(np.int32)
    got = np.asarray(granite.all_logits(weights, tokens, config=config))
    for row in range(2):
        want = np.asarray(reference.forward(weights, reference_config(params), tokens[row]))
        worst = np.abs(got[row] - want).max(axis=-1)  # by position
        assert 1e-3 < np.median(worst) < BF16_MEDIAN
        assert (worst > BF16_TIPPED[0]).mean() < BF16_TIPPED[1]


FAULTS = {
    "residual_multiplier": {"residual_multiplier": 1.0},
    "embedding_multiplier": {"embedding_multiplier": 1.0},
    "logits_scaling": {"logits_scaling": 1.0},
    "attention_multiplier": {"attention_multiplier": 16**-0.5},
    "experts_per_tok": {"num_experts_per_tok": 2},
    "other_share": {"experts_held": (0, 6)},
    "mamba_as_attention_order": {"layer_types": ("attention", "mamba", "mamba", "mamba")},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_reference_tells_a_wrong_layer_from_the_right_one(fault):
    params = GraniteAlgorithmParams(**TINY, seed=3)
    config = params.config()
    weights = upcast(granite.init_weights(config, 3))
    tokens = np.random.default_rng(3).integers(0, N_ITEMS, (1, 80)).astype(np.int32)
    got = np.asarray(granite.all_logits(weights, tokens, config=config))[0]
    if fault == "mamba_as_attention_order":
        # the same arrays cannot serve another order: the kinds' arrays differ
        with pytest.raises(KeyError):
            reference.forward(weights, reference_config(params, **FAULTS[fault]), tokens[0])
        return
    wrong = np.asarray(reference.forward(weights, reference_config(params, **FAULTS[fault]), tokens[0]))
    assert np.abs(got - wrong).max() > 100 * ATOL


@pytest.mark.parametrize("left_out", ["conv_bias", "D", "dt_bias"])
def test_each_of_the_scans_small_arrays_is_read(left_out):
    params = GraniteAlgorithmParams(**TINY, seed=4)
    config = params.config()
    weights = upcast(granite.init_weights(config, 4))
    tokens = np.random.default_rng(4).integers(0, N_ITEMS, (1, 80)).astype(np.int32)
    got = np.asarray(granite.all_logits(weights, tokens, config=config))[0]
    without = {name: jnp.zeros_like(a) if name.endswith("." + left_out) else a for name, a in weights.items()}
    moved = np.asarray(granite.all_logits(without, tokens, config=config))[0]
    assert np.abs(got - moved).max() > 10 * ATOL
    np.testing.assert_allclose(moved, reference.forward(without, reference_config(params), tokens[0]), atol=ATOL)


def test_the_counts_leave_the_padding_out_and_split_held_from_absent(trained):
    algorithm, model = trained
    session = np.arange(40, dtype=np.int32)
    _, counts = granite.session_vectors(
        model.weights, *staged(algorithm, model, [session], [0], 256), config=model.config
    )
    busiest, held, overflowed = (int(v) for v in counts)
    routed = model.config.routed_copies(40)
    assert routed == 4 * 40 * 3 and 0 < held < routed and busiest <= held and overflowed == 0


# (the sessions' lengths, where each starts, the stream's length, the budget, the longest session)
PACKED = {
    "three sessions and padding between them": ((37, 64, 100), (0, 64, 128), 256, 256, 256),
    "a session that begins inside the scan's chunk": ((100, 30, 17), (0, 128, 192), 256, 256, 256),
    "a long session beside others in its own shape": ((300, 64, 70), (0, 320, 384), 512, 256, 512),
}


@pytest.mark.parametrize("case", list(PACKED))
def test_a_packed_streams_session_vectors_equal_the_sessions_alone(case, monkeypatch):
    lengths, starts, length, budget, longest = PACKED[case]
    monkeypatch.setattr(granite, "TOKEN_BUDGET", budget)
    monkeypatch.setattr(granite, "MAX_SESSION", longest)
    params = GraniteAlgorithmParams(**{**TINY, "max_position_embeddings": longest}, seed=4)
    algorithm = GraniteAlgorithm(params)
    rng = np.random.default_rng(len(lengths))
    sessions = [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in lengths]
    model = algorithm.train(None, TrainingData(["u"], [sessions[0]], [f"i{i}" for i in range(N_ITEMS)]))
    model.weights = upcast(model.weights)
    assert length in model.config.stream_shapes()
    packed, _ = granite.session_vectors(
        model.weights, *staged(algorithm, model, sessions, starts, length), config=model.config
    )
    assert packed.shape == (budget // 64, 64)
    head = np.asarray(model.weights["embed"], np.float32)
    for row, session in enumerate(sessions):
        # alone, from the stream's first position: the same compiled program.
        # The scan's state, a convolution's taps or a key leaked from the
        # session in front would move it by the vectors' own order
        alone, _ = granite.session_vectors(
            model.weights, *staged(algorithm, model, [session], [0], length), config=model.config
        )
        np.testing.assert_allclose(packed[row], alone[0], atol=ATOL, rtol=0, err_msg=f"session {row}")
        # ... and the reference's answer at the session's true length
        want = reference.next_item_logits(model.weights, reference_config(params), session)
        np.testing.assert_allclose(np.asarray(packed[row]) @ head.T, want, atol=ATOL)


def test_a_sessions_scores_do_not_move_when_its_neighbour_in_the_stream_changes(trained):
    algorithm, model = trained
    rng = np.random.default_rng(11)
    mine = rng.integers(0, N_ITEMS, 50).astype(np.int32)
    vectors = []
    for seed in (0, 1):
        other = np.random.default_rng(seed).integers(0, N_ITEMS, 64).astype(np.int32)  # ends where mine begins
        stream = staged(algorithm, model, [other, mine, other[:9]], [0, 64, 128], 256)
        out, _ = granite.session_vectors(model.weights, *stream, config=model.config)
        vectors.append(np.asarray(out))
    np.testing.assert_allclose(vectors[0][1], vectors[1][1], atol=1e-6, rtol=0)
    assert np.abs(vectors[0][0] - vectors[1][0]).max() > 100 * ATOL  # the neighbour itself did change


def _no_session_reset(monkeypatch):
    plain = granite.ssd
    monkeypatch.setattr(granite, "ssd", lambda *a, segment=None, **kw: plain(*a, **kw))


def _no_position_mask(monkeypatch):
    plain = granite.short_conv
    monkeypatch.setattr(granite, "short_conv", lambda x, w, position=None, **kw: plain(x, w, **kw))


def _a_key(monkeypatch):
    plain = granite.fused_attention
    monkeypatch.setattr(granite, "fused_attention", lambda q, k, v, causal, segment=None: plain(q, k, v, causal=causal))


@pytest.mark.parametrize("plant", [_no_session_reset, _no_position_mask, _a_key])
def test_a_leak_from_the_session_in_front_moves_the_vectors(trained, plant, monkeypatch):
    algorithm, model = trained
    rng = np.random.default_rng(2)
    sessions = [rng.integers(0, N_ITEMS, n).astype(np.int32) for n in (64, 6)]
    stream = staged(algorithm, model, sessions, [0, 64], 256)
    sound, _ = granite.session_vectors(model.weights, *stream, config=model.config)
    plant(monkeypatch)
    granite.session_vectors.clear_cache()
    try:
        leaky, _ = granite.session_vectors(model.weights, *stream, config=model.config)
    finally:
        monkeypatch.undo()
        granite.session_vectors.clear_cache()
    np.testing.assert_allclose(leaky[0], sound[0], atol=1e-5)  # nothing lies in front of the first
    assert np.abs(np.asarray(leaky[1]) - np.asarray(sound[1])).max() > 100 * ATOL


# -------------------------------------------------------------- the engine


def test_granite_is_an_algorithm_of_the_engine_that_shares_olmoes_serving():
    from predictionio_tpu.models.sequential.engine import BackboneAlgorithm

    engine = engine_factory()
    variant = {
        "datasource": {"params": {"appName": "seq"}},
        "algorithms": [{"name": "granite", "params": {**TINY, "seed": 7}}],
    }
    _, _, (algorithm,), _ = engine.make_components(engine.engine_params_from_variant(variant))
    assert type(algorithm) is GraniteAlgorithm and algorithm.params.experts_held == (6, 6)
    # no staging, batching or serving code of its own
    own = {name for name in vars(GraniteAlgorithm) if not name.startswith("__")}
    assert own == {"params_class", "model_class"}
    assert {name for name in vars(GraniteModel) if not name.startswith("__")} == {"module"}
    for name in ("_plan", "_stage", "_stack", "_answer", "predict_batch_dispatch", "warmup_serving", "train"):
        assert getattr(GraniteAlgorithm, name) is getattr(BackboneAlgorithm, name) is getattr(OlmoeAlgorithm, name)
    assert GraniteModel.load.__func__ is OlmoeAlgorithm.model_class.load.__func__
    assert granite.session_vectors.__name__ == "session_vectors"  # the program's name in a trace


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
    # 6 of 12 experts held: about half the copies
    assert 0.35 < held / (held + absent) < 0.65


def test_the_head_is_the_embedding_and_the_tree_holds_no_second_table(trained):
    _, model = trained
    assert "lm_head" not in model.weights and model.weights["embed"].shape == (128, 64)
    np.testing.assert_array_equal(np.asarray(model.head()), np.asarray(model.weights["embed"], np.float32))
    assert model.head().dtype == jnp.float32 and model.head() is model.head()


@pytest.mark.parametrize(
    "change",
    [{"mamba_conv_bias": False}, {"mamba_n_groups": 2}, {"position_embedding_type": "rope"},
     {"tie_word_embeddings": False}, {"mamba_expand": 4}, {"model_type": "granitemoe"}],
)
def test_unimplemented_config_values_are_refused_not_ignored(change):
    with pytest.raises(ValueError, match="not implemented"):
        GraniteAlgorithmParams(**{**TINY, **change}).config()


@pytest.mark.parametrize(
    "change,match",
    [({"experts_held": (8, 6)}, "no block"),
     ({"layer_types": ("mamba",) * 3}, "names 3 layers"),
     ({"layer_types": ("mamba",) * 3 + ("sliding_attention",)}, "only 'mamba' and 'attention'"),
     ({"num_key_value_heads": 3}, "do not divide")],
)
def test_a_share_or_a_layer_list_that_cannot_be_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        GraniteAlgorithmParams(**{**TINY, **change}).config()


def test_the_published_defaults_are_the_published_config():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines() if '"granite-4.0-h-small"' in line)
    params = dataclasses.asdict(GraniteAlgorithmParams())
    assert {key: list(params[key]) if key == "layer_types" else params[key] for key in row["config"]} == row["config"]
    config = GraniteAlgorithmParams().config()
    assert config.experts_held == (0, 72) and config.vocab_slice == (0, 100352)
    assert config.head_dim == 128 and config.mamba_inner == 8192 and config.sparse_layers == 40
    assert [i for i in range(40) if not config.is_mamba(i)] == [5, 15, 25, 35]


def test_the_variant_file_carries_the_published_config_and_states_the_share():
    import predictionio_tpu.models.sequential as package

    variant = json.loads((Path(package.__file__).parent / "variants" / "granite-4.0-h-small.json").read_text())
    raw = variant["algorithms"][0]["params"]
    params = engine_factory().engine_params_from_variant(variant).algorithms[0][1]
    published = dataclasses.asdict(GraniteAlgorithmParams())
    published["layer_types"] = list(published["layer_types"])
    stated = {"num_hidden_layers": 10, "experts_held": [0, 36], "vocab_slice": [0, 50176], "seed": 3}
    assert {k: v for k, v in raw.items() if k not in stated} == {k: v for k, v in published.items() if k not in stated}
    assert {k: raw[k] for k in stated} == stated
    config = params.config()
    assert config.experts_held == (0, 36) and config.table_rows == 50176 and config.num_hidden_layers == 10
    assert [config.is_mamba(i) for i in range(10)] == [True] * 5 + [False] + [True] * 4  # one whole period
    shapes = granite.weight_shapes(config)
    # the issue's arithmetic, to the parameter: 9.51 GB in bfloat16 at the cut, 64.4 GB whole
    assert sum(int(np.prod(shape)) for shape in shapes.values()) == 4_757_211_776
    whole = dataclasses.replace(params, num_hidden_layers=40, experts_held=None, vocab_slice=None).config()
    assert sum(int(np.prod(s)) for s in granite.weight_shapes(whole).values()) == 32_207_337_984
    assert shapes["0.in_proj"] == (4096, 16768) and shapes["0.conv"] == (4, 8448) and shapes["0.conv_bias"] == (8448,)
    assert shapes["0.out_proj"] == (8192, 4096) and shapes["0.gate_norm"] == (8192,) and shapes["0.A_log"] == (128,)
    assert shapes["5.wq"] == (4096, 4096) and shapes["5.wk"] == (4096, 1024) and "5.in_proj" not in shapes
    assert shapes["9.gate"] == (36, 4096, 768) and shapes["9.router"] == (4096, 72)
    assert shapes["9.shared_gate"] == (4096, 1536) and shapes["embed"] == (50176, 4096) and "lm_head" not in shapes


def test_the_seeded_scan_has_a_trained_models_strengths():
    config = GraniteAlgorithmParams(**TINY, seed=1).config()
    weights = granite.init_weights(config, 1)
    layer = granite.layer_of(weights, 0)
    a = np.exp(np.asarray(layer["A_log"], np.float32))
    step = np.asarray(jax.nn.softplus(layer["dt_bias"].astype(jnp.float32)))
    assert (a >= 0.99).all() and (a <= 16.1).all() and (step > 9e-4).all() and (step < 0.11).all()
    np.testing.assert_array_equal(np.asarray(layer["D"], np.float32), 1.0)
    assert float(jnp.abs(layer["conv_bias"].astype(jnp.float32)).max()) > 0.01


def test_save_then_load_is_equal_bit_for_bit_and_the_manifest_names_the_backbone(tmp_path, monkeypatch):
    from predictionio_tpu.workflow import model_io

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "base"))
    algorithm = GraniteAlgorithm(GraniteAlgorithmParams(**TINY, seed=7))
    model = algorithm.train(None, training_data(n_users=4))
    engine = engine_factory()
    params = engine.engine_params_from_variant({
        "datasource": {"params": {"appName": "seq"}},
        "algorithms": [{"name": "granite", "params": {**TINY, "seed": 7}}],
    })
    (persisted,) = engine.make_serializable_models(None, params, [model])
    assert isinstance(persisted, PersistentModelManifest)
    assert persisted.class_path == "predictionio_tpu.models.sequential.engine.GraniteModel"
    (deployed,) = engine.prepare_deploy(None, params, model_io.deserialize_models(model_io.serialize_models([persisted])))
    assert isinstance(deployed, GraniteModel) and deployed.config == model.config
    assert model.save("m1", algorithm.params, str(tmp_path))
    loaded = GraniteModel.load("m1", algorithm.params, str(tmp_path))
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
    variant file (at the tiny widths): the variant's ``"name": "granite"``
    builds the algorithm, the trained model goes through the model repository
    as a manifest, and the deployed one answers over HTTP behind
    ``QueryServer`` and its ``_MicroBatcher`` as the reference does."""
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
    variant = json.loads((Path(package.__file__).parent / "variants" / "granite-4.0-h-small.json").read_text())
    assert variant["algorithms"][0]["name"] == "granite"
    tiny = {key: list(value) if isinstance(value, tuple) else value for key, value in TINY.items()}
    variant["algorithms"][0]["params"].update({**tiny, "seed": 5})
    engine = engine_factory()
    params = engine.engine_params_from_variant(variant)
    _, _, (algorithm,), _ = engine.make_components(params)
    data = training_data()
    trained = algorithm.train(None, data)  # `pio train`
    (persisted,) = engine.make_serializable_models(None, params, [trained])
    blob = model_io.serialize_models([persisted])
    assert len(blob) < 1024  # a manifest, not the weights
    (model,) = engine.prepare_deploy(None, params, model_io.deserialize_models(blob))  # `pio deploy`
    assert isinstance(model, GraniteModel) and all(a.dtype == jnp.bfloat16 for a in model.weights.values())
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
        assert type(server.algorithms[0]) is GraniteAlgorithm and server.algorithms[0].batch_limit() is None
        request = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json", json.dumps({"user": "u2", "num": 5}).encode(),
            {"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request, timeout=60) as resp:
            rows = json.loads(resp.read())["itemScores"]
        session = data.sequences[2]
        config = reference_config(algorithm.params)
        logits = np.asarray(reference.next_item_logits(model.weights, config, session))
        ids = [int(r["item"][1:]) for r in rows]
        assert len(ids) == 5 and not set(ids) & set(session.tolist()) and max(ids) < N_ITEMS
        # the served tree is bfloat16: within the bf16 tree's own tolerance of the reference
        assert np.abs(np.asarray([r["score"] for r in rows]) - logits[ids]).max() < BF16_TIPPED[0] * 4
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        assert 'pio_seq_tokens_total{kind="real"}' in text and 'pio_moe_copies_total{where="absent"}' in text
    finally:
        loop.call_soon_threadsafe(loop.stop)
