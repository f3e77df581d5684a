"""Two recurrent token mixers evaluated chunk by chunk, and the short causal
convolution that feeds both: linear attention by the gated delta rule with a
decay a CHANNEL (Kimi Delta Attention, ``kda``: this docstring down to "The
state-space scan") and Mamba-2's state-space scan with a decay a HEAD and no
delta rule (``ssd``: the last section).

A head keeps a state ``S`` [keys, values] and reads it with its query::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,   o_t = S_t^T q_t

with ``a_t = exp(g_t)`` in (0, 1] a channel of the keys and ``b_t`` in
(0, 1) a head. Written with the pseudo-value ``u_t = b_t (v_t - (a_t k_t)^T
S_{t-1})`` the state is ``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``, and over a
chunk of ``C`` positions, ``G_t`` the running sum of ``g`` from the chunk's
start::

    (I + A) U = b (V - (K e^G) S_0),   A[t, s] = b_t sum_c k_t[c] k_s[c] e^(G_t[c] - G_s[c])  (s < t)
    O = (Q e^G) S_0 + M U,             M[t, s] = sum_c q_t[c] k_s[c] e^(G_t[c] - G_s[c])      (s <= t)
    S_C = Diag(e^(G_C)) S_0 + (K e^(G_C - G))^T U

``A``, ``M`` and the solve with the unit lower-triangular ``I + A`` (the WY /
UT form) are every chunk's own and computed for all chunks at once; only the
three lines above with ``S_0`` in them run under a ``lax.scan`` over the
chunks, which carries the state.

Decays stay in log space and every exponent that is taken is at most 0. The
triangles are built HALF BY HALF: a block of ``2h`` positions is its two
halves' own triangles and the square under them, rows of the second half
against keys of the first, and in that square ``e^(G_t - G_s)`` is split at
the LAST position ``r`` of the first half, ``e^(G_t - G_r) e^(G_r - G_s)``
with ``s <= r < t``: one product of two factors that are both at most 1.
From single positions (the diagonal, where the exponent is 0) up to the
chunk that is ``log2 C`` levels of batched products. No ``exp(-cumsum g)`` is
formed, so a decay of any strength neither overflows nor loses the pairs it
does not kill. ``(I + A)^-1`` grows by the same halves, exactly (no series
whose terms cancel): the inverse of a unit lower-triangular block is its
halves' inverses and ``-T_22 A_21 T_11`` under them.

Everything here is float32 and the products run at ``PRECISION`` (float32
operands in three bf16 passes on the chip): the state is what a later
position reads every earlier one through.

A right-padded session needs no mask: a real position never sees what
follows it. Several sessions PACKED into one row (``models/sequential``'s
streams) need one thing each: where a session begins on a chunk's first
position (``kda(starts=)``) the state that reaches the chunk is zeroed, and
every quantity of a chunk is the session's own as if it stood alone (the
padding behind its last real position follows them inside the chunk and is
seen by none); ``short_conv(position=)`` leaves out the taps that would reach
before a session's first position. The final state of a padded row is the
padding's too, and of use only to a caller that passed no padding.

The state-space scan (``ssd``). A head keeps a state ``S`` [values, state]
that decays by ONE scalar a step and is read with ``C``; ``B`` and ``C`` are
one group's, the same for every head::

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + d x_t

with ``dt_t > 0`` a head (the caller's ``softplus``) and ``a < 0`` a head.
Over a chunk, ``G_t`` the running sum of ``dt a`` from the chunk's start
(position ``t`` included)::

    Y[t] = exp(G_t) S_0 C_t + sum_{s <= t} (C_t . B_s) exp(G_t - G_s) dt_s x_s
    S_C  = exp(G_C) S_0 + sum_s exp(G_C - G_s) dt_s x_s B_s^T

There is no solve, so nothing but ``S_0`` ties a chunk to the one before:
every chunk's triangle, its own sum into ``S_C`` and, once the states are
known, what ``S_0`` adds to ``Y`` are computed for ALL chunks at once, and the
``lax.scan`` over the chunks is the second line alone (a multiply and an add
a state). ``C_t . B_s`` is computed once for all heads. Every exponent taken
is at most 0 (``G`` only falls, and only ``G_t - G_s`` with ``s <= t`` is
taken); ``G`` starts anew in every chunk, as the published kernels' does, so
its float32 sum is over a chunk's positions and no more.

Here the chunk is the CALLER's to choose (the same function at any width),
and it may be wider than the multiple sessions start on: ``ssd(segment=)``
takes a session id a position (the published kernels' ``seq_idx``). A pair
``(t, s)`` of two sessions gives nothing, and ``S_0`` reaches only the
positions of the session that was live at the end of the chunk before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["short_conv", "kda", "ssd", "ssd_kernel", "ssd_tiles", "CHUNK"]

# positions a chunk holds (a power of two): the published kernels' choice
CHUNK = 64
# three bf16 passes a float32 product: on the chip 2.7e-5 of the outputs' size
# from six passes (`highest`) and 0.8 ms of 6.6 a layer faster at a 2,048-token
# program; one pass is off by 6e-3 (my chip runs, PR 31). Off the chip: float32
PRECISION = lax.Precision.HIGH

_dot = functools.partial(jnp.einsum, precision=PRECISION, preferred_element_type=jnp.float32)


def short_conv(x, w, tail=None, position=None, activation=jax.nn.silu, bias=None):
    """``activation`` (``silu``, Kimi-Linear's and Granite's Mamba-2 layers';
    None for none, LFM2's, whose caller gates the input and the output itself)
    of a causal depthwise convolution over positions, plus ``bias`` [D]
    INSIDE the activation where there is one (Granite's ``mamba_conv_bias``):
    ``x``
    [B, L, D] float32, ``w`` [taps, D] (``w[-1]`` meets the position itself),
    ``tail`` [B, taps - 1, D] the inputs that came before position 0 (zeros
    when there were none). ``position`` [B, L] int32, where several sessions
    share a row, is each input's index inside its own session: a tap that
    would reach before index 0 meets a zero, not the session in front.
    Returns ``(y [B, L, D], tail')``, ``tail'`` the last ``taps - 1`` inputs,
    for a caller that goes on from here."""
    taps, length = w.shape[0], x.shape[1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], taps - 1, x.shape[2]), x.dtype)
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = w.astype(x.dtype)

    def tap(j):
        reached = padded[:, j : j + length]
        if position is None or j == taps - 1:
            return reached
        return jnp.where((position >= taps - 1 - j)[:, :, None], reached, 0.0)

    y = sum(w[j] * tap(j) for j in range(taps))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return (y if activation is None else activation(y)), padded[:, length:]


def _triangles(q, k, b, cum):
    """``(M, T)`` of every chunk, each [..., C, C]: ``M[t, s] = sum_c q_t[c]
    k_s[c] e^(cum_t[c] - cum_s[c])`` for ``s <= t`` (0 above the diagonal)
    and ``T = (I + A)^-1`` with ``A[t, s] = b_t sum_c k_t[c] k_s[c] e^(cum_t[c]
    - cum_s[c])`` for ``s < t``. ``q``, ``k``, ``cum`` [..., C, d], ``b``
    [..., C, 1]; ``cum`` the running sum of the log decays from the chunk's
    start; ``C`` a power of two. Built half by half (the module's docstring)."""
    lead, size = k.shape[:-2], k.shape[-2]
    m = jnp.sum(q * k, axis=-1)[..., None, None]  # single positions: [..., C, 1, 1]
    t = jnp.ones_like(m)
    half = 1
    while half < size:
        pairs = size // (2 * half)

        def halves(x):
            x = x.reshape(lead + (pairs, 2, half, x.shape[-1]))
            return x[..., 0, :, :], x[..., 1, :, :]

        def grown(blocks, under):
            # [[first half's, 0], [under, second half's]]
            blocks = blocks.reshape(lead + (pairs, 2, half, half))
            top = jnp.concatenate([blocks[..., 0, :, :], jnp.zeros_like(under)], axis=-1)
            return jnp.concatenate([top, jnp.concatenate([under, blocks[..., 1, :, :]], axis=-1)], axis=-2)

        (k_1, k_2), (_, q_2), (cum_1, cum_2), (_, b_2) = halves(k), halves(q), halves(cum), halves(b)
        split = cum_1[..., -1:, :]  # at the first half's last position
        right = k_1 * jnp.exp(split - cum_1)
        since = jnp.exp(cum_2 - split)
        a_21 = _dot("...tc,...sc->...ts", b_2 * k_2 * since, right)
        m_21 = _dot("...tc,...sc->...ts", q_2 * since, right)
        t_1, t_2 = halves(t.reshape(lead + (2 * pairs * half, half)))
        t_21 = -_dot("...ts,...sr->...tr", _dot("...ts,...sr->...tr", t_2, a_21), t_1)
        m, t = grown(m, m_21), grown(t, t_21)
        half *= 2
    return m[..., 0, :, :], t[..., 0, :, :]


def kda(q, k, v, g, b, state=None, starts=None):
    """The gated delta rule over ``q``, ``k``, ``g`` [B, L, heads, d_k],
    ``v`` [B, L, heads, d_v] and ``b`` [B, L, heads], float32: ``q`` and
    ``k`` as the state is to meet them (normalised, ``q`` scaled), ``g`` the
    log decay a channel (at most 0), ``b`` the step size. ``state``
    [B, heads, d_k, d_v] is what came before position 0 (zeros by default).
    ``starts`` [B, chunks] bool marks the chunks on whose first position a
    session begins: no state reaches them (the one thing sessions packed
    into a row, each from a multiple of ``CHUNK``, need).
    Returns ``(o [B, L, heads, d_v], the state after position L - 1)``.

    ``L`` is padded to whole chunks of ``CHUNK`` with positions that leave
    the state as it is (``k`` 0, ``b`` 0, ``g`` 0)."""
    batch, length, heads, d_k = k.shape
    d_v = v.shape[-1]
    chunk = CHUNK
    if chunk & (chunk - 1):
        raise ValueError(f"kda: a chunk of {chunk} positions is no power of two")
    n = -(-length // chunk)
    pad = n * chunk - length

    def chunked(x):
        # [B, L, heads, ...] -> [n, B, heads, chunk, ...]
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((batch, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    q, k, v, g = (chunked(x.astype(jnp.float32)) for x in (q, k, v, g))
    b = chunked(b.astype(jnp.float32)[..., None])  # [n, B, heads, chunk, 1]
    cum = jnp.cumsum(g, axis=-2)
    m, t = _triangles(q, k, b, cum)
    grown = jnp.exp(cum)
    # (I + A)^-1 of the values and of the keys as S_0 meets them, in one product
    solved = _dot("...ts,...sn->...tn", t, jnp.concatenate([b * v, b * k * grown], axis=-1))
    u_own, w = solved[..., :d_v], solved[..., d_v:]
    q_in = q * grown
    k_out = k * jnp.exp(cum[..., -1:, :] - cum)
    kept = jnp.exp(cum[..., -1, :])  # [n, B, heads, d_k]

    def step(s, xs):
        u_own, w, q_in, m, k_out, kept, *fresh = xs
        if fresh:
            s = jnp.where(fresh[0][:, None, None, None], 0.0, s)
        u = u_own - _dot("bhtc,bhcv->bhtv", w, s)
        o = _dot("bhtc,bhcv->bhtv", q_in, s) + _dot("bhts,bhsv->bhtv", m, u)
        s = kept[..., None] * s + _dot("bhtc,bhtv->bhcv", k_out, u)
        return s, o

    if state is None:
        state = jnp.zeros((batch, heads, d_k, d_v), jnp.float32)
    xs = (u_own, w, q_in, m, k_out, kept) + (() if starts is None else (starts.T,))
    state, o = lax.scan(step, state.astype(jnp.float32), xs)
    # [n, B, heads, chunk, d_v] -> [B, L, heads, d_v]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(batch, n * chunk, heads, d_v)
    return o[:, :length], state


def ssd(x, dt, a, b, c, d=None, state=None, segment=None, chunk: int = 256):
    """Mamba-2's state-space scan (the module's last section) over ``x``
    [B, L, heads, p], ``dt`` [B, L, heads] (the step, positive), ``a``
    [heads] (negative), ``b`` and ``c`` [B, L, n] (ONE group: every head
    reads the same) and the skip ``d`` [heads] (None: none), all float32.
    ``state`` [B, heads, p, n] is what came before position 0 (zeros by
    default). ``segment`` [B, L] int32, where several sessions share a row,
    is each position's session id (a session's positions are contiguous and
    its id its own, as ``ops/attention.fused_attention(segment=)`` takes
    them; a negative id is padding, whose output means nothing and which no
    session sees): wherever a session begins, inside a chunk or on its first
    position, it begins from a zero state. ``chunk`` positions are evaluated
    at once: any width gives the same function.
    Returns ``(y [B, L, heads, p], the state after position L - 1)``.

    ``L`` is padded to whole chunks with positions that leave the state as it
    is (``dt`` 0).

    One function in two forms, chosen by what can be seen here (as
    ``ops/moe.grouped_matmul`` chooses its): on the chip, at the shapes the
    kernel tiles (``ssd_tiles``), ``ssd_kernel``, which keeps a chunk in
    VMEM; off the chip and at any other shape the XLA form below, which is
    the kernel's yardstick in the tests too."""
    if jax.default_backend() == "tpu" and ssd_tiles(x.shape[2], x.shape[3], b.shape[-1], chunk):
        return ssd_kernel(x, dt, a, b, c, d, state=state, segment=segment, chunk=chunk)
    batch, length, heads, p = x.shape
    n = -(-length // chunk)
    pad = n * chunk - length

    def chunked(v, fill=0):
        # [B, L, ...] -> [B, chunks, chunk, ...]
        if pad:
            v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2), constant_values=fill)
        return v.reshape((batch, n, chunk) + v.shape[2:])

    x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
    a = a.astype(jnp.float32)
    xs = chunked(dt[..., None] * x)  # what a position adds, [B, n, C, heads, p]
    bs, cs = chunked(b), chunked(c)
    cum = jnp.cumsum(chunked(dt * a), axis=2)  # G, [B, n, C, heads]
    at = jnp.arange(chunk)
    if segment is None:
        same = (at[:, None] >= at[None, :])[None, None]
        carried = ends = None
    else:
        seg = chunked(segment, -1)  # [B, n, C]
        same = (seg[..., :, None] == seg[..., None, :]) & (at[:, None] >= at[None, :])
        # the session live at the end of the chunk before (the first chunk's: the row's first)
        before = jnp.concatenate([seg[:, :1, 0], seg[:, :-1, -1]], axis=1)
        carried = seg == before[..., None]  # S_0 reaches these positions
        ends = seg == seg[..., -1:]  # ... and these reach S_C
    # the decays inside a chunk, [B, n, heads, C(t), C(s)]: exponents at most 0
    g = jnp.moveaxis(cum, 3, 2)
    decay = jnp.exp(jnp.where(same[:, :, None], g[..., :, None] - g[..., None, :], -jnp.inf))
    m = _dot("bntk,bnsk->bnts", cs, bs)[:, :, None] * decay
    y = _dot("bnhts,bnshp->bnthp", m, xs)
    # each chunk's own sum into the state it hands on, and what it keeps of S_0
    out = jnp.exp(cum[:, :, -1:] - cum)  # [B, n, C, heads]
    kept = jnp.exp(cum[:, :, -1])  # [B, n, heads]
    if segment is not None:
        out = jnp.where(ends[..., None], out, 0.0)
        kept = jnp.where(carried[:, :, -1, None], kept, 0.0)
    own = _dot("bnshp,bnsk->bnhpk", xs * out[..., None], bs)

    def step(s, xs):
        kept, own = xs
        return kept[..., None, None] * s + own, s

    if state is None:
        state = jnp.zeros((batch, heads, p, b.shape[-1]), jnp.float32)
    state, s_0 = lax.scan(
        step, state.astype(jnp.float32), (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(own, 1, 0))
    )
    reach = jnp.exp(cum)
    if segment is not None:
        reach = jnp.where(carried[..., None], reach, 0.0)
    y = y + reach[..., None] * _dot("bntk,nbhpk->bnthp", cs, s_0)
    y = y.reshape(batch, n * chunk, heads, p)[:, :length]
    if d is not None:
        y = y + d.astype(jnp.float32)[:, None] * x
    return y, state


# ---------------------------------------------------------------------------
# the state-space scan as one kernel
# ---------------------------------------------------------------------------

# channels of ``x`` and ``y`` one step of the kernel's grid holds: its block
# of heads (16 heads of 64). From the chip (PERF.md, PR 51; the bare scan at
# [1, 2048] / [1, 4096] in chunks of 128, ms a call by the wall clock of nine
# chained calls, a pass over ``y`` between them in every reading): 512: 0.53 /
# 1.23, **1024: 0.45 / 1.07**, 2048: 0.42 / 1.01, which does not fit VMEM at a
# chunk of 256 (a step of the grid costs 0.6 us beside its work; the XLA form
# 1.83 / 5.50; the kernel's own events in the cell's trace: 0.34 / 0.65)
SSD_WIDTH = 1024
_TILE = 128  # lanes of a vector tile, rows and columns of the matrix unit


def _head_block(heads: int, p: int) -> int:
    """Heads a step of the kernel's grid holds."""
    return min(heads, max(1, SSD_WIDTH // p))


def ssd_tiles(heads: int, p: int, n: int, chunk: int) -> bool:
    """Whether the chip is served these shapes by ``ssd_kernel``: the ones it
    was compiled for (``tests/test_tpu_compile.py``) and timed at on the chip,
    a head of 64 channels over a state of 128 (granite-4.0-h-small's), the
    heads whole blocks of ``SSD_WIDTH`` channels, a chunk of 128 or 256. The
    body asks for less (the chunk, the state's and a block's width whole tiles
    of lanes, a head's channels whole tiles of rows), but what a step holds in
    VMEM grows with all four and a shape that overflows it fails in Mosaic
    where the XLA form runs: another shape joins when it has been compiled
    and timed."""
    return (p, n) == (64, 128) and chunk in (128, 256) and heads % (SSD_WIDTH // p) == 0


def _split(v):
    """float32 ``v`` as its bfloat16 rounding and what that leaves."""
    high = v.astype(jnp.bfloat16)
    return high, (v - high.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot3(a, b):
    """``a @ b`` of two float32 matrices, each given as its ``_split``, in
    ``PRECISION``'s three bfloat16 passes with float32 sums (Mosaic takes no
    ``Precision.HIGH``: the passes XLA makes of it, written out)."""
    (a_high, a_low), (b_high, b_low) = a, b
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    return dot(a_high, b_high) + (dot(a_high, b_low) + dot(a_low, b_high))


def _ssd_chunk(
    skip_ref, x_ref, c_ref, b_ref, g_s_ref, rows_ref, kept_ref, seg_s_ref, seg_t_ref, s0_ref,
    y_ref, s_ref, yt_ref, xw_ref, *, block: int, p: int,
):
    """One chunk of one block of heads (``ssd_kernel``: the operands).
    Inside, positions are LANES: ``x`` and ``C`` are transposed as they come
    into VMEM and ``y`` as it leaves, so a head's channels are whole tiles of
    rows, what a position and head have of their own (the step, ``G``) is a
    row laid over them, and a head's triangle is built as ``[s, t]``. The
    block's state ``[block * p, n]`` stays in ``s_ref`` from a row's first
    chunk to its last."""
    from jax.experimental import pallas as pl

    chunk = x_ref.shape[0]
    first = pl.program_id(1) * block

    @pl.when(pl.program_id(2) == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    s_at = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    t_at = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    same = (seg_s_ref[...] == seg_t_ref[...]) & (s_at <= t_at)
    ct, b = _split(c_ref[...].T), _split(b_ref[...])
    cb = jnp.where(same, _dot3(b, ct), 0.0)  # C_t . B_s at [s, t], once for the block's heads
    from_state = _dot3(_split(s_ref[...]), ct)  # S_0 C_t, [block * p, C]
    xt = x_ref[...].T  # [block * p, C]
    for h in range(block):
        at = slice(h * p, (h + 1) * p)
        x = xt[at, :]
        g_t, dt, reach, w = (rows_ref[j * block + h : j * block + h + 1, :] for j in range(4))
        # the head's triangle: every exponent at most 0, and nothing where ``cb`` is masked
        m = cb * jnp.exp(jnp.minimum(g_t - g_s_ref[:, h : h + 1], 0.0))
        y = _dot3(_split(dt * x), _split(m))
        yt_ref[at, :] = y + reach * from_state[at, :] + skip_ref[first + h] * x
        xw_ref[at, :] = w * x
        s_ref[at, :] = kept_ref[h : h + 1, :] * s_ref[at, :]  # what the block keeps of S_0
    y_ref[...] = yt_ref[...].T
    s_ref[...] += _dot3(_split(xw_ref[...]), b)  # the chunk's own sum into the state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_kernel(x, dt, a, b, c, d=None, state=None, segment=None, chunk: int = 256, interpret: bool = False):
    """``ssd`` as ONE Pallas kernel (its arguments and results; ``interpret``
    is how a test runs it off the chip). The grid is ``(row, block of heads,
    chunk)``, the chunks in order, and a step holds one chunk of one block of
    heads in VMEM: ``C_t . B_s`` once, a head's triangle ``exp(G_t - G_s)``
    where ``s <= t`` are one session's, the three products (a head's ``dt x``
    with its triangle; the block's state with ``C``; the block's ``x`` with
    ``B``) each in ``PRECISION``'s three passes, and the block's state
    ``[block * p, n]`` float32, which enters at a row's first chunk and
    leaves after its last. HBM sees ``x``, ``B``, ``C`` in and ``y`` out once
    and, a POSITION AND HEAD, what XLA prepares in front: the running sum
    ``G`` of ``dt a`` inside each chunk, ``exp(G_t)`` where ``S_0`` reaches
    (``reach``), ``dt_s exp(G_C - G_s)`` where ``s`` reaches ``S_C``
    (``w``), and a chunk and head ``exp(G_C)`` where the state is carried
    over (``kept``). None of the XLA form's triangles, sums or states."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, length, heads, p = x.shape
    n = b.shape[-1]
    block = _head_block(heads, p)
    if heads % block:
        raise ValueError(f"ssd_kernel: {heads} heads are no whole blocks of {block}")
    chunks = -(-length // chunk)
    total, blocks, width = chunks * chunk, heads // block, block * p

    def padded(v, fill=0):
        if total == length:
            return v
        return jnp.pad(v, [(0, 0), (0, total - length)] + [(0, 0)] * (v.ndim - 2), constant_values=fill)

    x, dt, b, c = (padded(v.astype(jnp.float32)) for v in (x, dt, b, c))
    # no ids: one session, the padding behind it too (its ``dt`` is 0)
    seg = jnp.zeros((batch, total), jnp.int32) if segment is None else padded(segment, -1)
    by_chunk = seg.reshape(batch, chunks, chunk)
    # the session live at the end of the chunk before (the first chunk's: the row's first)
    before = jnp.concatenate([by_chunk[:, :1, 0], by_chunk[:, :-1, -1]], axis=1)
    carried = (by_chunk == before[..., None])[..., None]  # S_0 reaches these positions
    ends = (by_chunk == by_chunk[..., -1:])[..., None]  # ... and these reach S_C
    step = dt.reshape(batch, chunks, chunk, heads)
    cum = jnp.cumsum(step * a.astype(jnp.float32), axis=2)  # G, anew in every chunk
    reach = jnp.where(carried, jnp.exp(cum), 0.0)
    w = jnp.where(ends, jnp.exp(cum[:, :, -1:] - cum), 0.0) * step
    kept = jnp.where(carried[:, :, -1], jnp.exp(cum[:, :, -1]), 0.0)  # [B, chunks, heads]
    # ... a row a head over the state's width, [B, blocks, chunks, block, n]
    kept = kept.reshape(batch, chunks, blocks, block, 1).transpose(0, 2, 1, 3, 4)
    kept = jnp.broadcast_to(kept, (batch, blocks, chunks, block, n))

    def by_block(v):  # [B, chunks, C, heads] -> [B, blocks, L, block]: a column a head
        return v.reshape(batch, total, blocks, block).transpose(0, 2, 1, 3)

    # ... and [B, blocks, 4 * block, L]: a row a head of each
    rows = jnp.concatenate([by_block(v).transpose(0, 1, 3, 2) for v in (cum, step, reach, w)], axis=2)
    skip = jnp.zeros(heads, jnp.float32) if d is None else d.astype(jnp.float32)
    if state is None:
        state = jnp.zeros((batch, heads, p, n), jnp.float32)
    y, state = pl.pallas_call(
        functools.partial(_ssd_chunk, block=block, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the skip: a number a head, read as a scalar
            grid=(batch, blocks, chunks),
            in_specs=[
                pl.BlockSpec((None, chunk, width), lambda r, h, k, *_: (r, k, h)),
                pl.BlockSpec((None, chunk, n), lambda r, h, k, *_: (r, k, 0)),
                pl.BlockSpec((None, chunk, n), lambda r, h, k, *_: (r, k, 0)),
                pl.BlockSpec((None, None, chunk, block), lambda r, h, k, *_: (r, h, k, 0)),
                pl.BlockSpec((None, None, 4 * block, chunk), lambda r, h, k, *_: (r, h, 0, k)),
                pl.BlockSpec((None, None, None, block, n), lambda r, h, k, *_: (r, h, k, 0, 0)),
                pl.BlockSpec((None, chunk, 1), lambda r, h, k, *_: (r, k, 0)),
                pl.BlockSpec((None, 1, chunk), lambda r, h, k, *_: (r, 0, k)),
                pl.BlockSpec((None, width, n), lambda r, h, k, *_: (r, h, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, chunk, width), lambda r, h, k, *_: (r, k, h)),
                pl.BlockSpec((None, width, n), lambda r, h, k, *_: (r, h, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((width, chunk), jnp.float32)] * 2,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((batch, total, heads * p), jnp.float32),
            jax.ShapeDtypeStruct((batch, heads * p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(
        skip, x.reshape(batch, total, heads * p), c, b, by_block(cum), rows, kept,
        seg[:, :, None], seg[:, None, :], state.astype(jnp.float32).reshape(batch, heads * p, n),
    )
    return y.reshape(batch, total, heads, p)[:, :length], state.reshape(batch, heads, p, n)
