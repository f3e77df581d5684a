"""The serving ending: a score matrix becomes k ids and scores on the wire.

Every engine's device answer ends here, so a change to the ending (a
narrower table, a selection by blocks) is made once and measured on all of
them.

  - ONE body, :func:`select_top_k`: mask -> (per-item weights) ->
    ``lax.top_k`` -> pack, under the ``named_scope``s ``score`` and ``topk``.
    It is a plain function, so an engine that composes a program of its own
    (the two-tower's tower -> scores) ends in it too.
  - THREE jitted fronts that make the scores and end in the body
    (``PROGRAMS``): ``_serve_by_index_batch`` (gather user rows by index
    from a resident table, then the product: ``ServingIndex``, which keeps
    the item table every batch reads whole in the type the platform's
    product multiplies in, :func:`item_table_dtype`),
    ``_dot_top_k`` (query vectors given: ``dot_top_k_async``) and
    ``_gather_sum_top_k`` (gather, weight and sum the query rows:
    ``gather_sum_top_k_async``). An operand a caller leaves out (mask,
    weights) is ``None`` in the SAME jitted function: an absent operand is
    an empty pytree and jit specialises on it. Each compiles once per
    (batch bucket, k bucket); the resident tables never move and are never
    donated, the per-batch uploads are (``donate_argnums``; a no-op on the
    CPU backend, whose warning is filtered below).
  - The wire format: one packed [B, 2, k] int32 result, row 0 the float32
    score bits (``bitcast_convert_type``: exact in an int32 lane), row 1
    the indices (:func:`pack_batch`). Packing the INDICES as floats would
    be wrong: small indices are denormal floats and XLA flushes them to
    zero. :func:`unpack_batch` is the one decode.
  - :func:`fetch_topk` is the one device->host fetch of the serving path:
    O(batch * k), never O(batch * corpus). The ``serving-host-roundtrip``
    lint rule holds engines to it.
  - :func:`upload` is the one host->device staging call and it COPIES.
    ``ScratchBuffers`` gives the dispatch path reusable host staging buffers
    (thread-local: the micro-batcher's dispatch thread and the
    shadow/stable-retry threads each get their own pool); reuse is only
    sound because of that copy: ``jnp.asarray`` on the CPU backend aliases
    host numpy memory, and an aliased buffer overwritten for batch N+1
    while batch N's kernel is still in flight serves batch N the wrong
    queries.
  - :func:`batch_bucket` is the ONE place a serving batch is rounded up to
    its power-of-two bucket (:func:`next_pow2`, which the warmups share),
    and it counts what the rounding costs: the rows launched against the
    rows that are queries (``pio_serve_rows_total{kind}``,
    ``pio_serve_batches_total{bucket}``).
  - :func:`host_top_k` is the HOST ending for score vectors that are
    host-born in the first place (popularity counts, cooccurrence maps):
    nothing device-resident to fuse with.
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

__all__ = [
    "PROGRAMS",
    "ScratchBuffers",
    "ServingIndex",
    "batch_bucket",
    "bucket_counts",
    "dot_top_k_async",
    "fetch_topk",
    "gather_sum_top_k_async",
    "host_top_k",
    "item_table_dtype",
    "next_pow2",
    "pack_batch",
    "scratch",
    "select_top_k",
    "table_bytes",
    "unpack_batch",
    "upload",
    "warmup_pow2_buckets",
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


def next_pow2(n: int) -> int:
    """The bucket-rounding rule the dispatch paths and the warmups share:
    they must agree or warmed shapes won't match served shapes and
    serve-time compiles come back."""
    return 1 << max(0, n - 1).bit_length()


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


# the resident tables' bytes, process-wide too ({table: bytes} of the
# ``ServingIndex`` built last) and mirrored the same way
# (``pio_serve_table_bytes{table}``)
_table_bytes = {"item": 0, "user": 0}


def table_bytes() -> dict[str, int]:
    """``{"item": bytes, "user": bytes}`` of the newest ``ServingIndex``."""
    with _bucket_lock:
        return dict(_table_bytes)


def upload(x, dtype=None):
    """Host->device upload that GUARANTEES the device buffer is decoupled
    from the host array.

    On the CPU backend ``jnp.asarray(host_numpy)`` is ZERO-COPY: the jax
    array aliases the numpy memory. Every async serving dispatch that
    stages its batch in a reused ``ScratchBuffers`` slot then races the
    in-flight kernel against the next batch's assembly — the observed
    failure (offline double-buffer pipeline, CPU backend) was batch N's
    first rows answering with batch N+1's users, a torn read of the
    overwritten staging buffer. ``copy=True`` restores the contract the
    scratch pools are built on: the host buffer is reusable the moment the
    dispatch call returns. Device arrays pass through untouched (immutable,
    nothing to decouple), and so does ``None`` (an operand the caller left
    out); on non-CPU backends the H2D transfer is a copy regardless."""
    if x is None or isinstance(x, jax.Array):
        return x
    # pio-lint: disable=train-unaccounted-sync,serving-host-roundtrip -- host staging array (device handles returned above), never a device round-trip
    arr = np.asarray(x) if dtype is None else np.asarray(x, dtype)
    return jnp.asarray(arr, copy=True)


# ---------------------------------------------------------------------------
# the wire format
# ---------------------------------------------------------------------------


def pack_batch(scores: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """[B,k] scores + [B,k] indices -> packed [B,2,k] int32 (score bits in
    row 0). Public for the programs that select over something other than
    a [B, n] score matrix (``ann/search``'s gathered candidates) and still
    end on the wire format ``fetch_topk`` decodes."""
    return jnp.stack([lax.bitcast_convert_type(scores, jnp.int32), idx], axis=1)


def unpack_batch(packed: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The one decode of a fetched [B,2,k] packed result: ([B,k] float32
    scores, [B,k] int32 indices)."""
    return (
        np.ascontiguousarray(packed[:, 0, :]).view(np.float32),
        packed[:, 1, :],
    )


def fetch_topk(handle) -> tuple[np.ndarray, np.ndarray]:
    """The ONE sanctioned device->host fetch on the serving path: a packed
    [B,2,k] int32 result — O(batch*k), never O(batch*corpus).
    Returns ([B,k] float32 scores, [B,k] int32 indices)."""
    with annotate("pio:fetch.block"):  # the host blocked on the device
        # pio-lint: disable=serving-host-roundtrip -- the ONE sanctioned fetch: O(batch*k) packed result, accounted by the request waterfall
        packed = np.asarray(handle)
    return unpack_batch(packed)


# ---------------------------------------------------------------------------
# the body and its three fronts
# ---------------------------------------------------------------------------


def select_top_k(scores, k: int, mask=None, weights=None, log_sum_exp: bool = False):
    """The one ending, traced inside a jitted program: ``scores`` [B, n]
    times ``weights`` ([n] per-item multiplier, or None), entries whose
    ``mask`` ([n] or [B, n] bool, or None) is False sent to -inf, the k
    best of each row packed as [B, 2, k]. The scopes name each HLO
    operation's op_name (``jit(_serve_by_index_batch)/topk/...``), so a
    trace can follow the product and the selection from build to build; a
    front puts its own product under ``score`` as well.

    ``log_sum_exp`` hands back ``(packed, [B] float32)``: beside the k best
    the log of the sum of ``exp(score)`` over the row's candidates that the
    mask leaves, which turns a best score into its log-probability among
    them (a generated item's confidence)."""
    with jax.named_scope("score"):
        if weights is not None:
            scores = scores * weights[None, :]
        if mask is not None:
            scores = jnp.where(
                mask if mask.ndim == 2 else mask[None, :], scores, -jnp.inf
            )
    with jax.named_scope("topk"):
        packed = pack_batch(*lax.top_k(scores, k))
        if log_sum_exp:
            return packed, jax.nn.logsumexp(scores.astype(jnp.float32), axis=-1)
        return packed


@functools.partial(jax.jit, static_argnames=("k",))
def _serve_by_index_batch(uidxs, user_factors, item_factors, mask, k: int):
    """Both tables resident; uidxs [B] int32 is the whole upload. The
    product multiplies in the type ``item_factors`` is STORED in
    (:func:`item_table_dtype`): the B gathered float32 user rows are cast to
    it, the item rows are read as they lie, the scores are float32."""
    with jax.named_scope("gather"):
        user_vecs = user_factors[uidxs]
    with jax.named_scope("score"):
        scores = jnp.matmul(  # [B, n_items] on the MXU
            user_vecs.astype(item_factors.dtype),
            item_factors.T,
            preferred_element_type=jnp.float32,
        )
    return select_top_k(scores, k, mask)


@functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(1, 2, 3)
)
def _dot_top_k(table, vecs, mask, weights, k: int):
    """table [n,f] resident; vecs [B,f], mask and weights are per-batch
    uploads (donated)."""
    with jax.named_scope("score"):
        scores = vecs @ table.T  # [B, n] on the MXU
    return select_top_k(scores, k, mask, weights)


@functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(1, 2, 3, 4)
)
def _gather_sum_top_k(table, qidx, qweight, mask, weights, k: int):
    """The summed-similarity pattern (similarproduct / recommendeduser):
    gather the query rows, matmul against the whole table, sum over the
    query axis. table [n,f]; qidx [B,Q] int32 (pad rows point at row 0 and
    are zero-weighted); qweight [B,Q] float32."""
    with jax.named_scope("gather"):
        q = table[qidx] * qweight[..., None]  # [B, Q, f]
    with jax.named_scope("score"):
        scores = jnp.einsum("nf,bqf->bn", table, q)
    return select_top_k(scores, k, mask, weights)


# every jitted program of this module (obs/costmodel prices exactly these)
PROGRAMS = (_serve_by_index_batch, _dot_top_k, _gather_sum_top_k)


def dot_top_k_async(table, vecs, mask, k: int, weights=None):
    """Dispatch (no fetch) the fused matmul+mask+top-k: ``table`` [n,f]
    device-resident, ``vecs`` [B,f], ``mask`` [n] or [B,n] bool or None,
    ``weights`` an optional [n] per-item score multiplier. Returns the
    packed [B,2,k] device handle; decode with :func:`fetch_topk`."""
    return _dot_top_k(
        table,
        upload(vecs, np.float32),
        upload(mask),
        upload(weights, np.float32),
        k,
    )


def gather_sum_top_k_async(table, qidx, qweight, mask, k: int, weights=None):
    """Dispatch the gather->sum->mask->top-k program; see
    :func:`_gather_sum_top_k` for shapes, :func:`dot_top_k_async` for
    ``mask`` and ``weights``. Returns the packed handle."""
    return _gather_sum_top_k(
        table,
        upload(qidx, np.int32),
        upload(qweight, np.float32),
        upload(mask),
        upload(weights, np.float32),
        k,
    )


def item_table_dtype():
    """The type a resident ITEM table is stored in: the one the platform's
    default float32 product multiplies in. On a TPU that product is ONE
    bfloat16 pass on the MXU unless a higher ``jax_default_matmul_precision``
    is configured, so a float32 table is read at 32 bits an entry every batch
    and rounded to 16 inside the fusion; rounded once when it is made
    resident it is read at the width it is multiplied in, and nothing is lost
    that the program did not already drop. Everywhere else a float32 product
    multiplies float32, and the table stays float32."""
    one_pass = jax.config.jax_default_matmul_precision in (None, "default", "bfloat16")
    if jax.default_backend() == "tpu" and one_pass:
        return jnp.bfloat16
    return jnp.float32


class ServingIndex:
    """Device-resident factor tables with index-addressed top-k serve.

    The TPU replacement for the reference's in-JVM model broadcast
    (``CreateServer.scala:196-200`` deserializes the kryo model into the
    server heap; here the model lives in HBM and every batch is one compiled
    program). Per-batch cost: one [B] int32 upload + one [B,2,k] int32 fetch.

    The item table, which every batch reads whole, is kept in
    :func:`item_table_dtype` and in no other copy (round-to-nearest-even,
    once, here); the user table, read B rows a batch, stays as it is given.
    """

    def __init__(self, user_factors, item_factors):
        self.user_factors = jnp.asarray(user_factors)
        self.item_factors = jnp.asarray(item_factors).astype(item_table_dtype())
        self._full_mask = jnp.ones((self.item_factors.shape[0],), bool)
        with _bucket_lock:
            _table_bytes.update(
                item=self.item_factors.nbytes, user=self.user_factors.nbytes
            )

    @property
    def n_users(self) -> int:
        return self.user_factors.shape[0]

    @property
    def n_items(self) -> int:
        return self.item_factors.shape[0]

    def warmup_buckets(self, k: int, max_batch: int) -> None:
        """Pre-compile every power-of-two batch bucket up to
        ``next_pow2(max_batch)`` (the range the dispatch path buckets
        len(batch) <= max_batch into) for top-``k`` (k rounded up to its own
        bucket), so neither a single query nor the first ragged burst pays
        a compile."""
        kk = min(next_pow2(k), self.n_items)
        # through serve_batch_async, as the serving path stages its
        # indices: upload()'s copy is a program of its own for every bucket,
        # and a bucket's first batch would load it
        warmup_pow2_buckets(
            max_batch,
            lambda b: self.serve_batch_async(np.zeros((b,), np.int32), kk),
        )

    def serve(
        self, user_index: int, k: int, mask: jax.Array | np.ndarray | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-k (scores, item indices) for one user index: row 0 of batch
        bucket 1 at k's bucket (the shapes ``warmup_buckets`` compiled),
        the first ``k`` kept. ``mask`` [n_items] False = excluded item."""
        kk = min(next_pow2(k), self.n_items)
        # the fetch below ends before the caller has its mask back: no
        # staging copy (a program of its own, and not a warmed one)
        m = None if mask is None else jnp.asarray(mask)
        scores, idx = fetch_topk(
            self.serve_batch_async(np.array([user_index], np.int32), kk, m)
        )
        return scores[0, :k], idx[0, :k]

    def serve_batch_async(
        self,
        user_indices: np.ndarray | jax.Array,
        k: int,
        mask: jax.Array | np.ndarray | None = None,
    ) -> jax.Array:
        """Non-blocking batched serve: dispatches the program and returns
        the packed [B,2,k] int32 device array WITHOUT fetching it. An async
        query server dispatches batch n+1 while fetching batch n's result, so
        device work and transport overlap; decode with ``fetch_topk``."""
        m = self._full_mask if mask is None else upload(mask)
        if isinstance(user_indices, jax.Array):
            # already on device: a np.asarray round-trip would block on a
            # D2H fetch and defeat the non-blocking contract
            idxs = user_indices.astype(jnp.int32)
        else:
            # upload() COPIES: callers stage indices in reusable scratch
            # buffers and overwrite them for the next batch while this
            # batch's kernel is still in flight
            idxs = upload(user_indices, np.int32)
        return _serve_by_index_batch(
            idxs, self.user_factors, self.item_factors, m, k
        )


def warmup_pow2_buckets(max_batch: int, dispatch) -> None:
    """Shared engine warmup: pre-compile one fused program per pow2 batch
    bucket by calling ``dispatch(b)`` for b = 1, 2, ..., next_pow2(max_batch)
    and blocking on every returned handle, so the first burst after
    deploy/reload pays no XLA compiles on the common shapes. ``dispatch``
    is the engine's per-bucket kernel call (dot / gather-sum / tower)."""
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
