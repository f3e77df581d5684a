"""On-device two-stage IVF-MIPS search.

Stage 1: ``query @ centroids.T`` -> top-``nprobe`` clusters (one [B, C]
matmul — C is hundreds, not the corpus). Stage 2: gather each probed
bucket as one contiguous padded slab, score the [B, P*cap, f] candidates
with one batched matmul, mask pads/filters to ``-inf``, and end on the
shared fused top-k wire format (``ops/topk.pack_batch``: [B, 2, k] int32,
score bits in row 0). The fetch stays O(batch * k) — candidate generation
no longer touches the other ~(1 - nprobe*cap/n) of the corpus.

Kernel discipline mirrors ops/topk: one compiled program per (pow2 batch,
k, nprobe) bucket; the index tables ride resident and are never donated;
the per-batch query/mask uploads are donated. The int8 variant scores the
quantized buckets, keeps a ``rescore * k`` survivor pool, gathers those
rows from the resident exact f32 table and re-scores them exactly before
the final top-k.

Each search returns TWO device arrays — the packed top-k and a [B] int32
count of real (non-pad) candidates scored — fetched together in
:meth:`AnnSearcher.fetch`; the count feeds the ``pio_ann_candidates_*``
metrics and the <=10%-of-corpus acceptance measurement.
"""

from __future__ import annotations

import functools

import numpy as np

from predictionio_tpu.ann.index import AnnIndex

__all__ = ["AnnSearcher"]


def _kernels():
    """jit-compiled kernel set, built lazily so importing the ann package
    never drags jax in (pio top / pio models are stdlib-light).

    Stage 2 gathers each probed bucket as ONE contiguous ``cap*f`` slab
    (the tables ride flattened [C, cap*f]) and scores the reshaped
    [B, P*cap, f] candidates with one batched matmul against the query —
    big-row gathers are memcpy-shaped on every backend, where the naive
    [B, P, cap, f] element gather + einsum measured ~7x slower on CPU.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from predictionio_tpu.ops.topk import pack_batch

    def _stage1(centroids, q, nprobe: int):
        cs = q @ centroids.T  # [B, C]
        _, probe = lax.top_k(cs, nprobe)
        return probe  # [B, nprobe]

    def _flat_candidates(bucket_flat, bucket_ids, q, probe):
        b, f = q.shape
        vecs = bucket_flat[probe].reshape(b, -1, f)  # [B, P*cap, f]
        ids = bucket_ids[probe].reshape(b, -1)  # [B, P*cap]
        scores = jnp.matmul(vecs, q[:, :, None])[:, :, 0]
        return scores, ids

    def _counts(ids):
        return (ids >= 0).sum(axis=1).astype(jnp.int32)

    @functools.partial(
        jax.jit, static_argnames=("nprobe", "k"), donate_argnums=(3,)
    )
    def search(centroids, bucket_flat, bucket_ids, q, nprobe: int, k: int):
        probe = _stage1(centroids, q, nprobe)
        flat_s, flat_i = _flat_candidates(bucket_flat, bucket_ids, q, probe)
        flat_s = jnp.where(flat_i >= 0, flat_s, -jnp.inf)
        s, pos = lax.top_k(flat_s, k)
        items = jnp.take_along_axis(flat_i, pos, axis=1)
        return pack_batch(s, items), _counts(flat_i)

    @functools.partial(
        jax.jit, static_argnames=("nprobe", "k"), donate_argnums=(3, 4)
    )
    def search_excl(
        centroids, bucket_flat, bucket_ids, q, excl, nprobe: int, k: int
    ):
        """``excl`` [B, E] int32 item ids never returned (a query's own
        items) — pad with -1, which matches no candidate."""
        probe = _stage1(centroids, q, nprobe)
        flat_s, flat_i = _flat_candidates(bucket_flat, bucket_ids, q, probe)
        hit = (flat_i[:, :, None] == excl[:, None, :]).any(axis=2)
        flat_s = jnp.where((flat_i >= 0) & ~hit, flat_s, -jnp.inf)
        s, pos = lax.top_k(flat_s, k)
        items = jnp.take_along_axis(flat_i, pos, axis=1)
        return pack_batch(s, items), _counts(flat_i)

    @functools.partial(
        jax.jit, static_argnames=("nprobe", "k"), donate_argnums=(3, 4)
    )
    def search_masked(
        centroids, bucket_flat, bucket_ids, q, mask, nprobe: int, k: int
    ):
        """``mask`` [B, n] bool over the FULL corpus (the engines' existing
        candidate masks); candidate rows gather their own mask bit."""
        probe = _stage1(centroids, q, nprobe)
        flat_s, flat_i = _flat_candidates(bucket_flat, bucket_ids, q, probe)
        ok = jnp.take_along_axis(mask, jnp.maximum(flat_i, 0), axis=1)
        flat_s = jnp.where((flat_i >= 0) & ok, flat_s, -jnp.inf)
        s, pos = lax.top_k(flat_s, k)
        items = jnp.take_along_axis(flat_i, pos, axis=1)
        return pack_batch(s, items), _counts(flat_i)

    @functools.partial(
        jax.jit,
        static_argnames=("nprobe", "k", "pool"),
        donate_argnums=(5, 6),
    )
    def search_q8(
        centroids,
        bucket_q8_flat,
        bucket_scale,
        bucket_ids,
        exact_table,
        q,
        excl,
        nprobe: int,
        k: int,
        pool: int,
    ):
        """int8 score pass + exact f32 rescore of the ``pool`` survivors.
        ``exact_table`` [n, f] is the engine's resident full-precision
        table — gathered only at the survivor rows. The int8 dot rides
        the same slab-gather shape; the per-item scale multiplies the
        scalar score, not the vectors. ``excl`` [B, E] int32 (-1 padded)
        works exactly as in ``search_excl`` — exclusion compares ids, it
        never needs the full-precision vectors, so the similarproduct
        filter-less dispatch stays on the int8 path."""
        probe = _stage1(centroids, q, nprobe)
        b, f = q.shape
        vq = bucket_q8_flat[probe].reshape(b, -1, f).astype(jnp.float32)
        flat_i = bucket_ids[probe].reshape(b, -1)
        scale = bucket_scale[probe].reshape(b, -1)
        flat_s = jnp.matmul(vq, q[:, :, None])[:, :, 0] * scale
        hit = (flat_i[:, :, None] == excl[:, None, :]).any(axis=2)
        flat_s = jnp.where((flat_i >= 0) & ~hit, flat_s, -jnp.inf)
        ps, pos = lax.top_k(flat_s, pool)
        cand = jnp.take_along_axis(flat_i, pos, axis=1)  # [B, pool]
        cvec = exact_table[jnp.maximum(cand, 0)]  # [B, pool, f]
        es = jnp.matmul(cvec, q[:, :, None])[:, :, 0]
        es = jnp.where((cand >= 0) & jnp.isfinite(ps), es, -jnp.inf)
        s, p2 = lax.top_k(es, k)
        items = jnp.take_along_axis(cand, p2, axis=1)
        return pack_batch(s, items), _counts(flat_i)

    return search, search_excl, search_masked, search_q8


_KERNELS = None


class AnnSearcher:
    """Device-resident index tables + the jitted two-stage search.

    ``exact_table`` (the engine's resident [n, f] device table) is
    required for the int8 rescore path and ignored otherwise.
    """

    def __init__(self, index: AnnIndex, exact_table=None):
        import jax.numpy as jnp

        global _KERNELS
        if _KERNELS is None:
            _KERNELS = _kernels()
        self.index = index
        self._centroids = jnp.asarray(index.centroids)
        self._bucket_ids = jnp.asarray(index.bucket_ids)
        # resident flattened [C, cap*f]: stage 2 gathers one contiguous
        # slab per probed cluster (see _kernels)
        c = index.clusters
        self._bucket_flat = jnp.asarray(index.bucket_vecs.reshape(c, -1))
        self._bucket_scale = (
            jnp.asarray(index.bucket_scale)
            if index.bucket_scale is not None
            else None
        )
        self._exact_table = exact_table
        if index.bucket_scale is not None and exact_table is None:
            raise ValueError(
                "an int8-quantized index needs the engine's exact f32 table "
                "for survivor rescoring"
            )

    @property
    def n_items(self) -> int:
        return self.index.n_items

    @property
    def nprobe(self) -> int:
        return self.index.nprobe

    def candidate_pool(self, nprobe: int | None = None) -> int:
        """Upper bound of candidates one query can score (pads included)."""
        return (nprobe or self.nprobe) * self.index.bucket_cap

    def supports(self, k: int, nprobe: int | None = None) -> bool:
        """Can this index answer top-``k``? ``lax.top_k`` needs the pool at
        least k wide; callers fall back to exact scoring when it can't."""
        return 0 < k <= self.candidate_pool(nprobe)

    def search_async(self, qvecs, k: int, *, mask=None, exclude=None,
                     nprobe: int | None = None):
        """Dispatch (no fetch). ``qvecs`` [B, f] — host numpy or a device
        array (e.g. the two-tower user embedding handle, composed without
        a host round-trip). At most one of ``mask`` ([B, n] bool) /
        ``exclude`` ([B, E] int32, -1 padded) may be given. Returns the
        (packed [B,2,k], counts [B]) device-handle pair."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.topk import upload

        search, search_excl, search_masked, search_q8 = _KERNELS
        nprobe = min(nprobe or self.nprobe, self.index.clusters)
        # upload() COPIES host staging buffers (scratch-pool reuse must
        # not race the in-flight kernel); device handles pass through
        q = qvecs if hasattr(qvecs, "dtype") and not isinstance(
            qvecs, np.ndarray
        ) else upload(qvecs, np.float32)
        if self._bucket_scale is not None:
            if mask is not None:
                # a [B, n] mask gather is fine on ids, but masked queries
                # carry engine filters whose exact fallback is cheap and
                # already wired — keep the int8 surface to the hot path
                raise ValueError(
                    "mask filtering is unsupported on the int8 path; "
                    "route filtered queries to the exact fallback "
                    "(AnnServing.supports(filtered=True) says so)"
                )
            pool = min(
                max(k, self.index.config.rescore * k), self.candidate_pool(nprobe)
            )
            excl = (
                upload(exclude, np.int32)
                if exclude is not None
                else jnp.full((q.shape[0], 1), -1, jnp.int32)
            )
            return search_q8(
                self._centroids,
                self._bucket_flat,
                self._bucket_scale,
                self._bucket_ids,
                self._exact_table,
                q,
                excl,
                nprobe,
                k,
                pool,
            )
        if mask is not None:
            return search_masked(
                self._centroids,
                self._bucket_flat,
                self._bucket_ids,
                q,
                upload(mask),
                nprobe,
                k,
            )
        if exclude is not None:
            return search_excl(
                self._centroids,
                self._bucket_flat,
                self._bucket_ids,
                q,
                upload(exclude, np.int32),
                nprobe,
                k,
            )
        return search(
            self._centroids, self._bucket_flat, self._bucket_ids, q, nprobe, k
        )

    @staticmethod
    def fetch(handle) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The one sanctioned fetch of an ANN search: the packed [B,2,k]
        top-k plus the [B] candidate counts — O(batch*k), never
        O(batch*corpus). Returns (scores, item indices, counts)."""
        from predictionio_tpu.ops.topk import unpack_batch

        packed, counts = handle
        # pio-lint: disable=serving-host-roundtrip -- k-only packed fetch + [B] counts, the ANN wire contract
        packed_np, counts_np = np.asarray(packed), np.asarray(counts)
        scores, idx = unpack_batch(packed_np)
        return scores, idx, counts_np

    def warmup(self, max_batch: int, k: int) -> None:
        """Pre-compile one search program per pow2 batch bucket (same
        discipline as ops/topk.warmup_pow2_buckets) so the first burst
        after deploy/reload pays no XLA compiles on the ANN path."""
        from predictionio_tpu.ops import topk

        dim = self.index.dim
        kk = min(topk.next_pow2(k), self.candidate_pool())

        def dispatch(b: int):
            packed, _counts = self.search_async(
                np.zeros((b, dim), np.float32), kk
            )
            return packed

        topk.warmup_pow2_buckets(max_batch, dispatch)
