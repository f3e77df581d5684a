"""ANN index lifecycle: registry artifact <-> serving attachment.

The index is a **content-addressed registry artifact with lineage**: its
blob lives in the engine's blob store next to the model blobs, and the
model version's manifest records it under ``ann_index`` (sha256 + layout
metadata). Three producers, one consumer:

  - ``pio train`` (workflow/core_workflow.py) calls
    :func:`build_for_version` after the registry publish when the trained
    model exposes an item-vector table and the corpus clears the
    ``min_items`` threshold.
  - the stream layer (stream/pipeline.py) calls
    :func:`refresh_for_publish` on every candidate publish: new/updated
    item vectors are assigned to the parent index's centroids
    (incremental rebucket); when assignment drift crosses the guard a
    full k-means rebuild runs instead. The refreshed index rides the
    CANDIDATE version — the same publish-as-candidate discipline as the
    model itself, so a bad index can never hot-swap into stable.
  - serving (workflow/create_server.py) calls :func:`attach_from_registry`
    when loading any lane from the registry; when the manifest pins an
    index, an :class:`AnnServing` lands on the model object under the
    ``ann_serving`` attribute and the engines' dispatch paths consult it.
    No index pinned -> attribute stays None -> exact scoring, unchanged.

Model support is duck-typed on the item-vector table: two-tower
(``item_embeddings``), similarproduct's :class:`SimilarModel`
(``item_factors``), and the recommendation template's ALSModel
(``item_factors`` + ``user_factors``) — the last so the fold-in ALS
stream trainer refreshes an index end to end.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any

import numpy as np

from predictionio_tpu.ann.index import (
    AnnConfig,
    AnnIndex,
    build_index,
    deserialize_index,
    refresh_index,
    serialize_index,
)
from predictionio_tpu.ann.metrics import AnnInstruments
from predictionio_tpu.ann.search import AnnSearcher

logger = logging.getLogger(__name__)

#: attribute engines consult on their model object
ATTR = "ann_serving"

_RECALL_EWMA = 0.2


def config_from_env() -> AnnConfig:
    """Build-time knobs from the environment (the train/stream paths have
    no per-engine params surface for a cross-cutting subsystem):
    ``PIO_ANN_MIN_ITEMS`` (corpus threshold, default 50000),
    ``PIO_ANN_CLUSTERS`` / ``PIO_ANN_NPROBE`` (0 = auto),
    ``PIO_ANN_INT8`` (quantized score pass). ``PIO_ANN=0`` disables the
    build entirely (checked by the callers, not here)."""
    return AnnConfig(
        clusters=int(os.environ.get("PIO_ANN_CLUSTERS", "0") or 0),
        nprobe=int(os.environ.get("PIO_ANN_NPROBE", "0") or 0),
        min_items=int(os.environ.get("PIO_ANN_MIN_ITEMS", "50000")),
        quantize_int8=os.environ.get("PIO_ANN_INT8", "0").lower()
        in ("1", "true", "yes"),
    )


def ann_enabled() -> bool:
    return os.environ.get("PIO_ANN", "1").lower() not in ("0", "false", "off")


# ---------------------------------------------------------------------------
# model-type plumbing (duck-typed)
# ---------------------------------------------------------------------------


def item_vectors_of(model: Any) -> np.ndarray | None:
    """The model's item-vector table, or None for model types ANN does not
    apply to (popularity/cooccurrence/NB...)."""
    if hasattr(model, "item_embeddings"):  # two-tower
        # pio-lint: disable=hostsync-serving-path -- one-time lane-load/refresh materialization feeding the host-side ANN build, not per-request
        return np.asarray(model.item_embeddings, np.float32)
    if hasattr(model, "item_factors"):  # SimilarModel / ALSModel
        # pio-lint: disable=hostsync-serving-path -- one-time lane-load/refresh materialization feeding the host-side ANN build, not per-request
        return np.asarray(model.item_factors, np.float32)
    return None


def _exact_device_table(model: Any):
    """The engine's resident full-precision device table (the int8 rescore
    gathers survivor rows from it)."""
    if hasattr(model, "device_items"):
        return model.device_items()
    if hasattr(model, "device_factors"):
        return model.device_factors()
    if hasattr(model, "serving_index"):
        # ALSModel: its ServingIndex keeps the item table at the width the
        # product multiplies in (ops/topk.item_table_dtype), so the float32
        # rows are an upload of their own, as the other templates' are
        import jax.numpy as jnp

        return jnp.asarray(model.item_factors, jnp.float32)
    return None


def find_indexable_model(models: list[Any]) -> Any | None:
    for m in models:
        if item_vectors_of(m) is not None:
            return m
    return None


# ---------------------------------------------------------------------------
# serving wrapper
# ---------------------------------------------------------------------------


class AnnServing:
    """One pinned index wired for the dispatch path: the device searcher,
    the ``pio_ann_*`` instruments, and the shadow-exact recall sampler.

    Thread contract: dispatch threads (micro-batcher, shadow, stable
    retry) share one instance; the metrics registry's own locks make the
    counter math safe, and the sampler keeps its own lock.
    """

    def __init__(
        self,
        index: AnnIndex,
        model: Any,
        instruments: AnnInstruments | None = None,
        recall_sample_every: int | None = None,
    ):
        self.index = index
        self.searcher = AnnSearcher(
            index, exact_table=_exact_device_table(model) if index.quantized else None
        )
        self.instruments = instruments
        # 0 disables the recall shadow; None = the env default
        self._sample_every = (
            recall_sample_every
            if recall_sample_every is not None
            else int(os.environ.get("PIO_ANN_RECALL_EVERY", "64"))
        )
        self._sample_lock = threading.Lock()
        self._batches = 0
        self._recall_ewma: float | None = None
        if instruments is not None:
            self.bind(instruments)

    def bind(self, instruments: AnnInstruments) -> None:
        self.instruments = instruments
        instruments.set_index(
            self.index.model_version or "?",
            self.index.n_items,
            self.index.clusters,
        )

    # ------------------------------------------------------------- dispatch
    def supports(self, k: int, *, filtered: bool = False) -> bool:
        """False routes the batch to the exact path: a k wider than the
        probe pool, or filters on an int8 index (filter gathers need
        full-precision candidate ids). Pure — dispatch paths that fall
        back call :meth:`count_fallback` so warmup probes stay silent."""
        return self.searcher.supports(k) and not (
            filtered and self.index.quantized
        )

    def count_fallback(self, rows: int = 1) -> None:
        if self.instruments is not None and rows > 0:
            self.instruments.fallbacks.inc(rows)

    def search_async(self, qvecs, k: int, *, mask=None, exclude=None):
        return self.searcher.search_async(qvecs, k, mask=mask, exclude=exclude)

    def take_recall_sample(self) -> bool:
        """True on every Nth dispatched batch: the caller then ALSO
        dispatches its exact kernel and hands both results to
        :meth:`record_recall` — a measured recall proxy on live traffic,
        not a build-time promise."""
        with self._sample_lock:
            self._batches += 1
            return self._sample_every > 0 and (
                (self._batches - 1) % self._sample_every == 0
            )

    # --------------------------------------------------------------- fetch
    def fetch(self, handle, rows: int):
        """Fetch + account one batch: returns (scores, idx) shaped like
        ``ops.topk.fetch_topk``. ``rows`` = real (non-pad) batch rows."""
        scores, idx, counts = AnnSearcher.fetch(handle)
        ins = self.instruments
        if ins is not None and rows > 0:
            ins.queries.inc(rows)
            ins.probes.inc(rows * self.searcher.nprobe)
            real = counts[:rows]
            ins.candidates.inc(float(real.sum()))
            if self.index.n_items:
                ins.candidates_frac.set(
                    float(real.mean()) / float(self.index.n_items)
                )
        return scores, idx

    def record_recall(
        self, ann_idx: np.ndarray, exact_idx: np.ndarray, rows: int
    ) -> float | None:
        """Overlap@k of the ANN vs shadow-exact indices over the batch's
        real rows -> EWMA gauge. Returns the batch's recall."""
        rows = min(rows, len(ann_idx), len(exact_idx))
        if rows <= 0:
            return None
        k = min(ann_idx.shape[1], exact_idx.shape[1])
        if k <= 0:
            return None
        hits = 0
        for r in range(rows):
            hits += len(
                set(map(int, ann_idx[r, :k])) & set(map(int, exact_idx[r, :k]))
            )
        recall = hits / float(rows * k)
        with self._sample_lock:
            if self._recall_ewma is None:
                self._recall_ewma = recall
            else:
                self._recall_ewma += _RECALL_EWMA * (recall - self._recall_ewma)
            value = self._recall_ewma
        if self.instruments is not None:
            self.instruments.recall_samples.inc()
            self.instruments.recall_sampled.set(value)
        return recall

    def warmup(self, max_batch: int, k: int = 10) -> None:
        self.searcher.warmup(max_batch, k)


# ---------------------------------------------------------------------------
# registry lifecycle
# ---------------------------------------------------------------------------


def build_for_version(
    store: Any,
    engine_id: str,
    version: str,
    models: list[Any],
    config: AnnConfig | None = None,
    *,
    force: bool = False,
) -> dict[str, Any] | None:
    """End-of-train build: when a model in ``models`` exposes an item
    table with at least ``config.min_items`` rows (or ``force``), build
    the index, write it content-addressed, and pin it on ``version``'s
    manifest. Returns the manifest's ``ann_index`` entry, or None when no
    index applies. Never raises past the registry contract — callers keep
    publish best-effort."""
    if not ann_enabled():
        return None
    config = config or config_from_env()
    model = find_indexable_model(models)
    if model is None:
        return None
    vecs = item_vectors_of(model)
    if vecs is None or len(vecs) == 0:
        return None
    if len(vecs) < config.min_items and not force:
        logger.debug(
            "ann: corpus %d below min_items %d; exact serving stays default",
            len(vecs),
            config.min_items,
        )
        return None
    index = build_index(vecs, config, model_version=version, built_from="train")
    manifest = store.attach_ann_index(
        engine_id, version, serialize_index(index), index.manifest_meta()
    )
    logger.info(
        "ann: built index for %s (%d items, %d clusters, nprobe %d)",
        version,
        index.n_items,
        index.clusters,
        index.nprobe,
    )
    return manifest.ann_index


def refresh_for_publish(
    store: Any,
    engine_id: str,
    parent_version: str,
    version: str,
    models: list[Any],
    instruments: AnnInstruments | None = None,
) -> dict[str, Any] | None:
    """Stream-layer refresh: when the PARENT (stable) version pins an
    index and the freshly published candidate's models carry item
    vectors, re-derive the candidate's index from the parent's centroids
    (incremental) or rebuild on drift, and pin it on the candidate's
    manifest. Returns the refresh report (path + drift) or None when no
    parent index exists."""
    if not ann_enabled() or not parent_version:
        return None
    loaded = load_index(store, engine_id, parent_version)
    if loaded is None:
        return None
    model = find_indexable_model(models)
    vecs = item_vectors_of(model) if model is not None else None
    if vecs is None or len(vecs) == 0:
        return None
    refreshed, report = refresh_index(loaded, vecs, model_version=version)
    store.attach_ann_index(
        engine_id, version, serialize_index(refreshed), refreshed.manifest_meta()
    )
    if instruments is not None:
        if report["path"] == "rebuild":
            instruments.rebuilds.inc()
        else:
            instruments.refreshes.inc()
    logger.info(
        "ann: %s index for candidate %s (drift %.3f)",
        report["path"],
        version,
        report.get("drift", 0.0),
    )
    return report


def load_index(store: Any, engine_id: str, version: str) -> AnnIndex | None:
    """The verified index artifact pinned on ``version``, or None."""
    loaded = store.load_ann_blob(engine_id, version)
    if loaded is None:
        return None
    blob, _meta = loaded
    return deserialize_index(blob)


def attach_from_registry(
    store: Any,
    engine_id: str,
    version: str,
    models: list[Any],
    instruments: AnnInstruments | None = None,
) -> AnnServing | None:
    """Serving-side attach: when ``version``'s manifest pins an index,
    wire an :class:`AnnServing` onto the matching model object (attribute
    ``ann_serving``). Best-effort: a broken index artifact logs and
    leaves the lane on exact scoring — the index is an accelerator, never
    a single point of failure."""
    try:
        index = load_index(store, engine_id, version)
    except Exception:
        logger.exception(
            "ann: index artifact for %s unusable; serving exact", version
        )
        return None
    if index is None:
        return None
    model = find_indexable_model(models)
    if model is None:
        return None
    vecs = item_vectors_of(model)
    if vecs is None or len(vecs) != index.n_items:
        logger.warning(
            "ann: index for %s covers %d items but the model has %d; "
            "serving exact",
            version,
            index.n_items,
            0 if vecs is None else len(vecs),
        )
        return None
    serving = AnnServing(index, model, instruments=instruments)
    setattr(model, ATTR, serving)
    return serving


def bind_instruments(models: list[Any], instruments: AnnInstruments) -> None:
    """Late-bind the server's instruments onto any attached AnnServing
    (the attach happens in the lane loader, before the server's registry
    is in scope)."""
    for m in models:
        serving = getattr(m, ATTR, None)
        if isinstance(serving, AnnServing):
            serving.bind(instruments)


def pinned_indexes(
    model_lists: list[list[Any]],
) -> dict[str, tuple[float, float]]:
    """The (version -> (items, clusters)) map of every index attached to
    the given lanes' models — what the query server feeds
    :meth:`AnnInstruments.sync_indexes` at scrape time so retired
    versions' gauge series zero out after a reload."""
    out: dict[str, tuple[float, float]] = {}
    for models in model_lists:
        for m in models or ():
            serving = getattr(m, ATTR, None)
            if isinstance(serving, AnnServing):
                out[serving.index.model_version or "?"] = (
                    float(serving.index.n_items),
                    float(serving.index.clusters),
                )
    return out
