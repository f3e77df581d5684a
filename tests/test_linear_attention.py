"""``ops/linear_attention``: the chunked gated delta rule against the
recurrence it evaluates (``kimi_linear_reference.kda_recurrence``, one
position at a time), and the short convolution, on the CPU in float32.

Both sides multiply in float32 and differ by the order of their sums; the
outputs are of order 0.3. The worst seen over the cases below is 4e-7; 1e-5
is twenty-five times that and two hundred times under one bf16 product.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models.sequential import kimi_linear_reference as reference
from predictionio_tpu.ops import linear_attention

ATOL = 1e-5
HEADS, WIDTH = 3, 32


def inputs(seed, batch, length, decay):
    rng = np.random.default_rng(seed)
    shape = (batch, length, HEADS, WIDTH)
    q, k, v = (rng.normal(size=shape).astype(np.float32) for _ in range(3))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * WIDTH**-0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = np.log(rng.uniform(*decay, size=shape)).astype(np.float32)
    b = rng.uniform(0.05, 0.95, size=shape[:3]).astype(np.float32)
    return q, k, v, g, b


def recurrence(q, k, v, g, b):
    return np.stack([reference.kda_recurrence(*(x[i] for x in (q, k, v, g, b))) for i in range(len(q))])


def kda_at(chunk, monkeypatch):
    """``kda`` jitted at another chunk than the module's: ``CHUNK`` is read
    when a program is traced, so every case traces a function of its own."""
    monkeypatch.setattr(linear_attention, "CHUNK", chunk)
    return jax.jit(lambda *args: linear_attention.kda(*args))


DECAYS = {
    "near one": (0.999, 1.0),
    "0.9 to 0.9999": (0.9, 0.9999),
    # exp(-cumsum g) over a chunk of 64 would be 1e1900: nothing overflows
    # and no pair is lost, because no such factor is formed
    "killing": (1e-30, 0.5),
}


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("decay", list(DECAYS))
def test_chunked_equals_the_recurrence(chunk, decay, monkeypatch):
    # 150 positions: no multiple of any chunk, so the last one is padded
    args = inputs(chunk, 2, 150, DECAYS[decay])
    out, state = kda_at(chunk, monkeypatch)(*args)
    assert out.shape == (2, 150, HEADS, WIDTH) and state.shape == (2, HEADS, WIDTH, WIDTH)
    np.testing.assert_allclose(out, recurrence(*args), atol=ATOL, rtol=0)


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_right_padded_batch_gives_every_session_its_own_output(chunk, monkeypatch):
    lengths = [5, 64, 97, 128]
    args = inputs(7, len(lengths), 128, DECAYS["0.9 to 0.9999"])
    kda = kda_at(chunk, monkeypatch)
    out, _ = kda(*args)
    for row, n in enumerate(lengths):
        # whatever stands behind a session's end, its positions read the same
        alone, _ = kda(*(x[row : row + 1, :n] for x in args))
        np.testing.assert_allclose(out[row, :n], alone[0], atol=ATOL, rtol=0)


def packed(lengths, chunk):
    """Where each session of a packed row starts (whole chunks each), the
    row's length, and the chunks that begin one."""
    starts = np.concatenate([[0], np.cumsum([-(-n // chunk) * chunk for n in lengths])])
    fresh = np.zeros((1, starts[-1] // chunk), bool)
    fresh[0, starts[:-1] // chunk] = True
    return starts[:-1], int(starts[-1]), fresh


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("lengths", [(5, 64, 97, 128), (64, 64, 1), (200,)])
def test_sessions_packed_into_a_row_equal_the_sessions_one_at_a_time(chunk, lengths, monkeypatch):
    # float32 against float32: a state leaked from the session in front shows
    # at the outputs' own order (0.1), a hundred thousand times the tolerance
    starts, total, fresh = packed(lengths, chunk)
    args = inputs(11, 1, total, DECAYS["0.9 to 0.9999"])
    kda = kda_at(chunk, monkeypatch)
    out, _ = jax.jit(lambda *a: linear_attention.kda(*a, starts=jnp.asarray(fresh)))(*args)
    leaked, _ = kda(*args)
    for start, n in zip(starts, lengths):
        alone, _ = kda(*(x[:, start : start + n] for x in args))
        np.testing.assert_allclose(out[:, start : start + n], alone, atol=1e-6, rtol=0)
        if start:
            assert float(np.abs(leaked[:, start : start + n] - alone).max()) > 1e-3
    # a state handed in reaches the first chunk only where no session begins there
    state = jnp.ones((1, HEADS, WIDTH, WIDTH))
    again, _ = linear_attention.kda(*args, state, starts=jnp.asarray(fresh))
    np.testing.assert_allclose(again, out, atol=1e-6, rtol=0)


def test_short_conv_reaches_no_further_back_than_a_sessions_first_position():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 24, 8)).astype(np.float32)
    taps = rng.normal(size=(4, 8)).astype(np.float32)
    # sessions of 5, 3 and 9 positions from 0, 8 and 12; the rest is padding, at position 0
    position = np.zeros((1, 24), np.int32)
    for start, n in ((0, 5), (8, 3), (12, 9)):
        position[0, start : start + n] = np.arange(n)
    y, _ = linear_attention.short_conv(x, taps, position=jnp.asarray(position))
    for start, n in ((0, 5), (8, 3), (12, 9)):
        alone, _ = linear_attention.short_conv(x[:, start : start + n], taps)
        np.testing.assert_array_equal(y[:, start : start + n], alone)
    # without it, a session's first three positions read the one in front
    plain, _ = linear_attention.short_conv(x, taps)
    differs = np.abs(np.asarray(plain) - np.asarray(y)).max(axis=(0, 2)) > 0
    assert differs[8:11].all() and differs[12:15].all() and not differs[15:21].any()


@pytest.mark.parametrize("cut", [1, 37, 64, 100])
def test_a_prefix_then_the_rest_from_the_returned_state_equals_the_whole_pass(cut):
    # the scan AND the convolution in front of it: the state and the tail
    rng = np.random.default_rng(cut)
    length, wide = 130, HEADS * WIDTH
    x = rng.normal(size=(2, length, wide)).astype(np.float32)
    taps = rng.normal(size=(4, wide)).astype(np.float32) * 0.5
    _, _, v, g, b = inputs(cut, 2, length, DECAYS["0.9 to 0.9999"])

    def mixer(x, v, g, b, tail=None, state=None):
        y, tail = linear_attention.short_conv(x, taps, tail)
        k = y.reshape(y.shape[:2] + (HEADS, WIDTH))
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        out, state = linear_attention.kda(k * WIDTH**-0.5, k, v, g, b, state)
        return out, tail, state

    whole, tail, state = mixer(x, v, g, b)
    head, tail_1, state_1 = mixer(x[:, :cut], v[:, :cut], g[:, :cut], b[:, :cut])
    rest, tail_2, state_2 = mixer(x[:, cut:], v[:, cut:], g[:, cut:], b[:, cut:], tail_1, state_1)
    np.testing.assert_allclose(jnp.concatenate([head, rest], axis=1), whole, atol=ATOL, rtol=0)
    np.testing.assert_allclose(state_2, state, atol=ATOL, rtol=0)
    np.testing.assert_array_equal(tail_2, tail)
    np.testing.assert_array_equal(tail, x[:, -3:])


def test_short_conv_equals_the_references_and_is_causal():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 20, 8)).astype(np.float32)
    taps = rng.normal(size=(4, 8)).astype(np.float32)
    y, _ = linear_attention.short_conv(x, taps)
    for row in range(2):
        np.testing.assert_allclose(y[row], reference.short_conv(x[row], taps), atol=1e-6, rtol=0)
    # position t reads x[t - 3 .. t] alone
    changed = x.copy()
    changed[:, 10] += 1.0
    y2, _ = linear_attention.short_conv(changed, taps)
    differs = np.abs(np.asarray(y2) - np.asarray(y)).max(axis=(0, 2)) > 0
    assert differs.tolist() == [10 <= t <= 13 for t in range(20)]


@pytest.mark.parametrize("size", [16, 64, 128])
def test_the_triangular_inverse_is_exact_where_a_series_would_cancel(size):
    # every key alike, every step 1 and no decay: I + A is the lower triangle
    # of ones, its inverse has 1 on the diagonal and -1 under it, and the
    # powers of A a Neumann series would sum reach 1e18 at 64 positions
    k = jnp.zeros((1, size, 8)).at[..., 0].set(1.0)
    ones = jnp.ones((1, size, 1))
    m, t = linear_attention._triangles(2.0 * k, k, ones, jnp.zeros_like(k))
    want = np.eye(size) - np.eye(size, k=-1)
    np.testing.assert_allclose(t[0], want, atol=1e-6, rtol=0)
    np.testing.assert_allclose(m[0], 2.0 * np.tril(np.ones((size, size))), atol=1e-6, rtol=0)


def test_a_chunk_that_is_no_power_of_two_is_refused(monkeypatch):
    args = inputs(0, 1, 48, DECAYS["near one"])
    monkeypatch.setattr(linear_attention, "CHUNK", 48)
    with pytest.raises(ValueError, match="no power of two"):
        linear_attention.kda(*args)


@pytest.mark.parametrize("what", ["decay", "state"])
def test_a_decay_or_a_state_kept_in_bfloat16_is_told_from_float32(what):
    # ATOL is no formality: bf16's eight bits in the decay show at 7 times it
    # and in the state at 30 times (of outputs of order 0.1: a thousandth).
    # On the chip the benchmark's check holds the same function to the same
    # recurrence on a served model's own inputs (its scan probe: PERF.md, PR 31)
    from jax import lax

    args = inputs(5, 1, 256, DECAYS["0.9 to 0.9999"])
    want = recurrence(*args)
    if what == "decay":
        q, k, v, g, b = args
        out, _ = linear_attention.kda(q, k, v, lax.reduce_precision(g, 8, 7), b)
    else:
        state, outs = None, []
        for start in range(0, 256, 64):
            o, state = linear_attention.kda(*(x[:, start : start + 64] for x in args), state)
            state = lax.reduce_precision(state, 8, 7)
            outs.append(o)
        out = jnp.concatenate(outs, axis=1)
    assert (5 if what == "decay" else 20) * ATOL < float(np.abs(out - want).max()) < 0.01
