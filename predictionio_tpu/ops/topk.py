"""Shared fused score->mask->top-k serving kernels.

Every serving engine used to run its own ending: the recommendation
template already kept score+select fused on device (``ops/als.ServingIndex``,
the ALX recipe — batched matmul feeding ``lax.top_k``, one packed [B,2,k]
int32 fetch), while twotower / similarproduct / ecommerce / recommendeduser
fetched the FULL score vector to host and argsorted there. That is
O(batch * corpus) floats from device to host per batch; through this
module it becomes O(batch * k) for everyone.

Design (mirrors ops/als):
  - score + mask + select compile into ONE jitted program per
    (batch-bucket, k-bucket) shape; the resident factor table never moves.
  - results come back as a single packed int32 fetch: row 0 carries the
    float32 score bits via ``bitcast_convert_type`` (packing indices as
    floats would flush small indices to denormal zero), row 1 the indices.
  - per-batch host buffers (query vectors, gathered indices, masks) are
    DONATED to the kernel (``donate_argnums``): XLA may reuse their device
    allocation for the output instead of holding both live. The resident
    table argument is never donated. Donation is a no-op on the CPU
    backend; the warning it would log is filtered below.
  - ``ScratchBuffers`` gives the dispatch path preallocated, reusable host
    staging buffers (thread-local: the micro-batcher's dispatch thread and
    the shadow/stable-retry threads each get their own pool), so batch
    assembly writes queries straight into a recycled numpy buffer instead
    of allocating per window. Reuse is only sound because every staging
    upload goes through ``ops.als.upload`` (re-exported here), which
    COPIES: ``jnp.asarray`` on the CPU backend aliases host numpy memory,
    and an aliased buffer overwritten for batch N+1 while batch N's
    kernel is still in flight serves batch N the wrong queries.
  - ``batch_bucket`` is the ONE place a serving batch is rounded up to its
    power-of-two bucket, and it counts what the rounding costs: the rows
    launched against the rows that are queries
    (``pio_serve_rows_total{kind}``, ``pio_serve_batches_total{bucket}``).
  - ``host_top_k`` is the sanctioned HOST ending for score vectors that
    are host-born in the first place (popularity counts, cooccurrence
    maps). It lives here so the ``serving-host-roundtrip`` lint rule can
    hold engines to "no argsort outside the fused helper".
"""

from __future__ import annotations

import functools
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu.obs.jaxprof import annotate
from predictionio_tpu.ops.als import next_pow2, upload

__all__ = [
    "batch_bucket",
    "bucket_counts",
    "dot_top_k_async",
    "gather_sum_top_k_async",
    "fused_top_k_async",
    "fetch_topk",
    "host_top_k",
    "warmup_pow2_buckets",
    "pack_batch",
    "scratch",
    "upload",
    "ScratchBuffers",
    "next_pow2",
]

# donation is unsupported on the CPU backend; jax warns once per compiled
# donating program. The fallback (plain copy) is exactly the pre-donation
# behavior, so the warning is noise on CPU dev boxes — filtered narrowly
# by message for server/CLI runs. Under pytest this import-time filter is
# overridden by the test config; pyproject.toml carries the matching
# filterwarnings entry for CI.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


# what bucketing launched, process-wide ({bucket: [batches, real rows]}): the
# engines that bucket know no server, so the server's registry mirrors these
# at scrape (QueryServer._collect_buckets)
_bucket_lock = threading.Lock()
_bucket_tally: dict[int, list[int]] = {}


def batch_bucket(n: int) -> int:
    """The power-of-two bucket a batch of ``n`` real rows is launched as
    (``next_pow2``: what ``warmup_pow2_buckets`` and
    ``ServingIndex.warmup_buckets`` compiled), counted: ``n`` rows are
    queries, ``bucket - n`` are padding the device scores all the same."""
    bucket = next_pow2(n)
    with _bucket_lock:
        tally = _bucket_tally.setdefault(bucket, [0, 0])
        tally[0] += 1
        tally[1] += n
    return bucket


def bucket_counts() -> tuple[int, int, dict[int, int]]:
    """``(real rows, bucket rows, {bucket: batches})`` launched so far."""
    with _bucket_lock:
        tallies = {bucket: tuple(t) for bucket, t in _bucket_tally.items()}
    return (
        sum(real for _, real in tallies.values()),
        sum(bucket * batches for bucket, (batches, _) in tallies.items()),
        {bucket: batches for bucket, (batches, _) in tallies.items()},
    )


def pack_batch(scores: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """[B,k] scores + [B,k] indices -> packed [B,2,k] int32 (score bits in
    row 0 — same wire idiom as ops/als). Public so engines composing their
    own device program (e.g. the two-tower forward) can end it on the
    same one-fetch wire format ``fetch_topk`` decodes."""
    return jnp.stack([lax.bitcast_convert_type(scores, jnp.int32), idx], axis=1)


_pack_batch = pack_batch  # internal alias


@functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(1, 2)
)
def _dot_top_k(table, vecs, mask, k: int):
    """scores = vecs @ table.T, masked, top-k. table [n,f] resident;
    vecs [B,f] and mask [B,n] are per-batch uploads (donated)."""
    scores = vecs @ table.T  # [B, n] on the MXU
    scores = jnp.where(mask, scores, -jnp.inf)
    s, i = lax.top_k(scores, k)
    return _pack_batch(s, i)


@functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(1,)
)
def _dot_top_k_unmasked(table, vecs, k: int):
    scores = vecs @ table.T
    s, i = lax.top_k(scores, k)
    return _pack_batch(s, i)


@functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(1, 2, 3)
)
def _dot_top_k_weighted(table, vecs, mask, weights, k: int):
    """The adjust-score variant: a per-item weight vector multiplies the
    scores before selection (weights ride up per call, donated)."""
    scores = (vecs @ table.T) * weights[None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    s, i = lax.top_k(scores, k)
    return pack_batch(s, i)


@functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(1, 2, 3)
)
def _gather_sum_top_k(table, qidx, qweight, mask, k: int):
    """The summed-similarity pattern (similarproduct / recommendeduser):
    gather the query rows, matmul against the whole table, sum over the
    query axis, mask, select. table [n,f]; qidx [B,Q] int32 (pad rows point
    at row 0 and are zero-weighted); qweight [B,Q] float32; mask [B,n]."""
    q = table[qidx] * qweight[..., None]  # [B, Q, f]
    scores = jnp.einsum("nf,bqf->bn", table, q)
    scores = jnp.where(mask, scores, -jnp.inf)
    s, i = lax.top_k(scores, k)
    return pack_batch(s, i)


@functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(1, 2, 3, 4)
)
def _gather_sum_top_k_weighted(table, qidx, qweight, mask, weights, k: int):
    q = table[qidx] * qweight[..., None]
    scores = jnp.einsum("nf,bqf->bn", table, q) * weights[None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    s, i = lax.top_k(scores, k)
    return pack_batch(s, i)


@functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0, 1)
)
def _mask_top_k(scores, mask, k: int):
    scores = jnp.where(mask, scores, -jnp.inf)
    s, i = lax.top_k(scores, k)
    return _pack_batch(s, i)


def dot_top_k_async(table, vecs, mask, k: int, weights=None):
    """Dispatch (no fetch) the fused matmul+mask+top-k: ``table`` [n,f]
    device-resident, ``vecs`` [B,f], ``mask`` [B,n] bool or None,
    ``weights`` an optional [n] per-item score multiplier. Returns the
    packed [B,2,k] device handle; decode with :func:`fetch_topk`."""
    vecs_d = upload(vecs, np.float32)
    if weights is not None:
        m = (
            upload(mask)
            if mask is not None
            else jnp.ones((vecs_d.shape[0], table.shape[0]), bool)
        )
        return _dot_top_k_weighted(
            table, vecs_d, m, upload(weights, np.float32), k
        )
    if mask is None:
        return _dot_top_k_unmasked(table, vecs_d, k)
    return _dot_top_k(table, vecs_d, upload(mask), k)


def gather_sum_top_k_async(table, qidx, qweight, mask, k: int, weights=None):
    """Dispatch the gather->sum->mask->top-k kernel; see
    :func:`_gather_sum_top_k` for shapes. Returns the packed handle."""
    qidx_d = upload(qidx, np.int32)
    qw_d = upload(qweight, np.float32)
    mask_d = upload(mask)
    if weights is not None:
        return _gather_sum_top_k_weighted(
            table, qidx_d, qw_d, mask_d, upload(weights, np.float32), k
        )
    return _gather_sum_top_k(table, qidx_d, qw_d, mask_d, k)


def fused_top_k_async(scores, mask, k: int):
    """Mask + top-k over an already-computed device score matrix [B,n]
    (both donated — the scores buffer is consumed by the selection)."""
    return _mask_top_k(scores, upload(mask), k)


def fetch_topk(handle) -> tuple[np.ndarray, np.ndarray]:
    """The ONE sanctioned device->host fetch on the serving path: a packed
    [B,2,k] (or [2,k]) int32 result — O(batch*k), never O(batch*corpus).
    Returns ([B,k] float32 scores, [B,k] int32 indices)."""
    from predictionio_tpu.ops.als import ServingIndex

    with annotate("pio:fetch.block"):  # the host blocked on the device
        # pio-lint: disable=serving-host-roundtrip -- the ONE sanctioned fetch: O(batch*k) packed result, accounted by the request waterfall
        packed = np.asarray(handle)
    if packed.ndim == 2:  # single-query [2,k]
        packed = packed[None]
    # ops/als owns the wire format; this is the one decode of it
    return ServingIndex.unpack_batch(packed)


def warmup_pow2_buckets(max_batch: int, dispatch) -> None:
    """Shared engine warmup: pre-compile one fused program per pow2 batch
    bucket by calling ``dispatch(b)`` for b = 1, 2, ..., next_pow2(max_batch)
    and blocking on every returned handle, so the first burst after
    deploy/reload pays no XLA compiles on the common shapes. ``dispatch``
    is the engine's per-bucket kernel call (dot / gather-sum / tower)."""
    import jax

    handles = []
    b = 1
    top = next_pow2(max_batch)
    while b <= top:
        handles.append(dispatch(b))
        b *= 2
    jax.block_until_ready(handles)


def host_top_k(
    scores: np.ndarray, mask: np.ndarray | None, k: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host ending for host-born score vectors (popularity counts,
    cooccurrence maps — nothing device-resident to fuse with). Masked
    entries and -inf scores never surface. Returns (scores_k, idx_k)
    sorted descending; may return fewer than k when the finite pool is
    smaller."""
    scores = np.asarray(scores, np.float64)
    if mask is not None:
        scores = np.where(mask, scores, -np.inf)
    k = min(int(k), scores.shape[0])
    if k <= 0:
        return np.empty(0), np.empty(0, np.int64)
    # pio-lint: disable=serving-host-roundtrip -- host-born scores (popularity/cooccurrence): this IS the sanctioned host ending, no device round-trip
    idx = np.argpartition(-scores, k - 1)[:k]
    # pio-lint: disable=serving-host-roundtrip -- host-born scores: same sanctioned host ending
    idx = idx[np.argsort(-scores[idx])]
    finite = np.isfinite(scores[idx])
    idx = idx[finite]
    return scores[idx], idx


class ScratchBuffers:
    """Reusable host staging buffers for batch assembly.

    ``get(name, shape, dtype)`` returns a preallocated array, growing a
    named slot geometrically (pow2 per axis) so steady-state serving does
    zero per-batch allocation; the caller owns the buffer until its next
    ``get`` of the same name. ``zeros``/``full`` variants re-fill in place.
    NOT thread-safe by design — use :func:`scratch` for the thread-local
    pool.
    """

    def __init__(self):
        self._bufs: dict[str, np.ndarray] = {}

    def get(self, name: str, shape: tuple[int, ...], dtype) -> np.ndarray:
        dtype = np.dtype(dtype)
        buf = self._bufs.get(name)
        if buf is None or buf.dtype != dtype or any(
            have < want for have, want in zip(buf.shape, shape)
        ) or buf.ndim != len(shape):
            alloc = tuple(max(1, next_pow2(s)) for s in shape)
            if buf is not None and buf.dtype == dtype and buf.ndim == len(shape):
                alloc = tuple(
                    max(a, have) for a, have in zip(alloc, buf.shape)
                )
            buf = np.empty(alloc, dtype)
            self._bufs[name] = buf
        view = buf[tuple(slice(0, s) for s in shape)]
        return view

    def zeros(self, name: str, shape: tuple[int, ...], dtype) -> np.ndarray:
        view = self.get(name, shape, dtype)
        view[...] = 0
        return view

    def full(self, name: str, shape: tuple[int, ...], dtype, value) -> np.ndarray:
        view = self.get(name, shape, dtype)
        view[...] = value
        return view


_SCRATCH = threading.local()


def scratch() -> ScratchBuffers:
    """The calling thread's scratch pool (dispatch thread, shadow thread
    and stable-retry fetch threads must not share staging buffers)."""
    pool = getattr(_SCRATCH, "pool", None)
    if pool is None:
        pool = _SCRATCH.pool = ScratchBuffers()
    return pool
