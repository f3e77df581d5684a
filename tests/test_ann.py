"""ANN subsystem tests (predictionio_tpu/ann, docs/ann.md).

Four layers, matching the lifecycle: index build/serialization mechanics
(determinism, padded-bucket edge cases, int8), the measured recall
harness (recall@10 vs exact ACROSS nprobe settings — measured, never
asserted blind), registry lifecycle (attach/verify/GC, refresh vs
drift-rebuild, the stream refresh -> candidate -> promote e2e), and the
serving integration (twotower + similarproduct dispatch through a pinned
index, filters, fallback, recall shadow sampling, metrics/doctor/top).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from predictionio_tpu.ann import (
    AnnConfig,
    build_index,
    default_clusters,
    default_nprobe,
    deserialize_index,
    refresh_index,
    serialize_index,
)
from predictionio_tpu.ann import lifecycle
from predictionio_tpu.ann.index import AnnFormatError, bucket_capacity
from predictionio_tpu.ann.metrics import AnnInstruments
from predictionio_tpu.ann.search import AnnSearcher
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.registry import ArtifactStore, ModelManifest
from predictionio_tpu.registry.store import ArtifactIntegrityError
from predictionio_tpu.workflow import model_io


def clustered_corpus(n, f, modes=32, noise=0.1, seed=0):
    """Synthetic item table with real cluster structure (normalized rows
    — the shape trained retrieval embeddings have)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(modes, f))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    vecs = centers[rng.integers(0, modes, n)] + noise * rng.normal(size=(n, f))
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    return vecs.astype(np.float32)


def exact_topk(vecs, q, k):
    return np.argsort(-(q @ vecs.T), axis=1, kind="stable")[:, :k]


def measured_recall(items, exact_idx, k):
    rows = len(exact_idx)
    hits = sum(
        len(set(map(int, items[r, :k])) & set(map(int, exact_idx[r, :k])))
        for r in range(rows)
    )
    return hits / float(rows * k)


# ---------------------------------------------------------------------------
# build mechanics
# ---------------------------------------------------------------------------


class TestBuild:
    def test_deterministic_bytes(self):
        vecs = clustered_corpus(2000, 8)
        cfg = AnnConfig(min_items=0)
        a = serialize_index(build_index(vecs, cfg, model_version="v1"))
        b = serialize_index(build_index(vecs, cfg, model_version="v1"))
        # content addressing in the registry dedupes identical rebuilds
        assert a == b

    def test_serialization_roundtrip(self):
        vecs = clustered_corpus(1500, 8)
        idx = build_index(vecs, AnnConfig(min_items=0), model_version="v7")
        rt = deserialize_index(serialize_index(idx))
        assert rt.model_version == "v7"
        assert rt.n_items == idx.n_items and rt.nprobe == idx.nprobe
        np.testing.assert_array_equal(rt.centroids, idx.centroids)
        np.testing.assert_array_equal(rt.bucket_ids, idx.bucket_ids)
        np.testing.assert_array_equal(rt.bucket_vecs, idx.bucket_vecs)
        np.testing.assert_array_equal(rt.nearest_assign, idx.nearest_assign)
        assert rt.config == idx.config

    def test_corrupt_blob_raises_format_error(self):
        idx = build_index(clustered_corpus(300, 4), AnnConfig(min_items=0))
        blob = serialize_index(idx)
        with pytest.raises(AnnFormatError):
            deserialize_index(b"NOTANINDEX" + blob)
        with pytest.raises(AnnFormatError):
            deserialize_index(blob[: len(blob) // 2])  # truncated arrays

    def test_every_item_in_exactly_one_bucket(self):
        vecs = clustered_corpus(3000, 8)
        idx = build_index(vecs, AnnConfig(min_items=0))
        ids = idx.bucket_ids[idx.bucket_ids >= 0]
        assert sorted(ids.tolist()) == list(range(3000))

    def test_skewed_corpus_spills_instead_of_inflating_cap(self):
        # everything in ONE natural cluster: the fattest-cluster rule
        # would pad every bucket to ~n; the balanced rule must hold the
        # 2x-mean capacity and spill
        rng = np.random.default_rng(3)
        vecs = (
            np.ones((2048, 8), np.float32)
            + 0.001 * rng.normal(size=(2048, 8)).astype(np.float32)
        )
        idx = build_index(vecs, AnnConfig(min_items=0, clusters=64))
        assert idx.bucket_cap == bucket_capacity(2048, 64)
        ids = idx.bucket_ids[idx.bucket_ids >= 0]
        assert sorted(ids.tolist()) == list(range(2048))  # nothing lost
        per_bucket = (idx.bucket_ids >= 0).sum(axis=1)
        assert per_bucket.max() <= idx.bucket_cap

    def test_fewer_items_than_clusters(self):
        vecs = clustered_corpus(10, 4)
        idx = build_index(vecs, AnnConfig(min_items=0, clusters=64))
        assert idx.clusters == 10  # clamped to the corpus
        ids = idx.bucket_ids[idx.bucket_ids >= 0]
        assert sorted(ids.tolist()) == list(range(10))

    def test_single_cluster(self):
        vecs = clustered_corpus(40, 4)
        idx = build_index(vecs, AnnConfig(min_items=0, clusters=1, nprobe=1))
        s = AnnSearcher(idx)
        _, items, counts = AnnSearcher.fetch(s.search_async(vecs[:4].copy(), 5))
        assert measured_recall(items, exact_topk(vecs, vecs[:4], 5), 5) == 1.0
        assert (counts == 40).all()  # one bucket = the whole corpus

    def test_int8_quantization_layout(self):
        vecs = clustered_corpus(500, 8)
        idx = build_index(vecs, AnnConfig(min_items=0, quantize_int8=True))
        assert idx.quantized and idx.bucket_vecs.dtype == np.int8
        pads = idx.bucket_ids < 0
        assert (idx.bucket_scale[pads] == 0).all()
        assert (idx.bucket_vecs[pads] == 0).all()
        # dequantized real rows approximate the originals
        real = ~pads
        deq = idx.bucket_vecs[real].astype(np.float32) * idx.bucket_scale[
            real
        ][:, None]
        orig = vecs[idx.bucket_ids[real]]
        assert float(np.abs(deq - orig).max()) < 0.02

    def test_hbm_bytes_counts_every_resident_array(self):
        idx = build_index(
            clustered_corpus(500, 8), AnnConfig(min_items=0, quantize_int8=True)
        )
        expected = (
            idx.centroids.nbytes
            + idx.bucket_ids.nbytes
            + idx.bucket_vecs.nbytes
            + idx.bucket_scale.nbytes
        )
        assert idx.hbm_bytes() == expected

    def test_default_sizing_rules(self):
        assert default_clusters(100_000) == 2048
        assert default_nprobe(2048) == 16
        assert default_nprobe(8192) == 64
        assert default_nprobe(8) == 8  # floor clamped to cluster count
        cfg = AnnConfig().resolved(100_000)
        assert cfg.clusters == 2048 and cfg.nprobe == 16


# ---------------------------------------------------------------------------
# recall harness — measured across nprobe settings
# ---------------------------------------------------------------------------


class TestRecallHarness:
    N, F, K = 6000, 16, 10

    @pytest.fixture(scope="class")
    def corpus(self):
        vecs = clustered_corpus(self.N, self.F, modes=32, seed=1)
        rng = np.random.default_rng(2)
        q = vecs[rng.integers(0, self.N, 64)].copy()
        return vecs, q, exact_topk(vecs, q, self.K)

    def test_recall_curve_across_nprobe(self, corpus):
        """The tradeoff is MEASURED: recall grows with nprobe, clears
        0.95 at the default, and the real candidate count stays <=10% of
        the corpus — the acceptance rails, held by measurement."""
        vecs, q, exact = corpus
        curve = {}
        fracs = {}
        for nprobe in (2, 8, 16):
            idx = build_index(
                vecs, AnnConfig(min_items=0, clusters=512, nprobe=nprobe)
            )
            _, items, counts = AnnSearcher.fetch(
                AnnSearcher(idx).search_async(q.copy(), self.K)
            )
            curve[nprobe] = measured_recall(items, exact, self.K)
            fracs[nprobe] = float(counts.mean()) / self.N
        assert curve[2] <= curve[8] + 0.02 <= curve[16] + 0.04
        assert curve[16] >= 0.95, f"measured recall curve: {curve}"
        assert fracs[16] <= 0.10, f"candidate fraction: {fracs}"

    def test_default_config_meets_acceptance(self, corpus):
        vecs, q, exact = corpus
        idx = build_index(vecs, AnnConfig(min_items=0))
        _, items, counts = AnnSearcher.fetch(
            AnnSearcher(idx).search_async(q.copy(), self.K)
        )
        assert measured_recall(items, exact, self.K) >= 0.95
        assert float(counts.mean()) / self.N <= 0.10

    def test_int8_rescore_recall(self, corpus):
        import jax.numpy as jnp

        vecs, q, exact = corpus
        idx = build_index(vecs, AnnConfig(min_items=0, quantize_int8=True))
        s = AnnSearcher(idx, exact_table=jnp.asarray(vecs))
        _, items, _ = AnnSearcher.fetch(s.search_async(q.copy(), self.K))
        assert measured_recall(items, exact, self.K) >= 0.95

    def test_masked_search_never_returns_masked_items(self, corpus):
        vecs, q, _ = corpus
        mask = np.ones((len(q), self.N), bool)
        mask[:, : self.N // 2] = False
        scores, items, _ = AnnSearcher.fetch(
            AnnSearcher(build_index(vecs, AnnConfig(min_items=0))).search_async(
                q.copy(), self.K, mask=mask
            )
        )
        finite = np.isfinite(scores)
        assert finite.any()
        assert (items[finite] >= self.N // 2).all()

    def test_int8_exclusion_works_and_filters(self, corpus):
        """Exclusion compares ids, never vectors — the int8 path must
        honor it (the similarproduct filter-less dispatch always sends
        its query items as exclusions)."""
        import jax.numpy as jnp

        vecs, _, _ = corpus
        idx = build_index(vecs, AnnConfig(min_items=0, quantize_int8=True))
        s = AnnSearcher(idx, exact_table=jnp.asarray(vecs))
        rng = np.random.default_rng(11)
        qi = rng.integers(0, self.N, 8)
        excl = np.full((8, 2), -1, np.int32)
        excl[:, 0] = qi
        scores, items, _ = AnnSearcher.fetch(
            s.search_async(vecs[qi].copy(), self.K, exclude=excl)
        )
        assert not any(int(qi[r]) in set(items[r].tolist()) for r in range(8))
        # mask stays the exact fallback's job on int8
        with pytest.raises(ValueError):
            s.search_async(
                vecs[qi].copy(), self.K, mask=np.ones((8, self.N), bool)
            )

    def test_exclusion_never_returns_excluded_ids(self, corpus):
        vecs, _, _ = corpus
        rng = np.random.default_rng(5)
        qi = rng.integers(0, self.N, 16)
        excl = np.full((16, 2), -1, np.int32)
        excl[:, 0] = qi
        _, items, _ = AnnSearcher.fetch(
            AnnSearcher(build_index(vecs, AnnConfig(min_items=0))).search_async(
                vecs[qi].copy(), self.K, exclude=excl
            )
        )
        assert not any(int(qi[r]) in set(items[r].tolist()) for r in range(16))

    def test_counts_measure_real_candidates_not_padding(self, corpus):
        vecs, q, _ = corpus
        idx = build_index(vecs, AnnConfig(min_items=0, clusters=256, nprobe=4))
        _, _, counts = AnnSearcher.fetch(
            AnnSearcher(idx).search_async(q.copy(), self.K)
        )
        assert (counts <= 4 * idx.bucket_cap).all()
        assert (counts > 0).all()

    def test_supports_bounds_k_by_probe_pool(self, corpus):
        vecs, _, _ = corpus
        idx = build_index(vecs, AnnConfig(min_items=0, clusters=256, nprobe=2))
        s = AnnSearcher(idx)
        assert s.supports(10)
        assert not s.supports(2 * idx.bucket_cap + 1)

    def test_device_array_query_composes_without_host_roundtrip(self, corpus):
        import jax.numpy as jnp

        vecs, q, exact = corpus
        idx = build_index(vecs, AnnConfig(min_items=0))
        _, items, _ = AnnSearcher.fetch(
            AnnSearcher(idx).search_async(jnp.asarray(q), self.K)
        )
        assert measured_recall(items, exact, self.K) >= 0.95


# ---------------------------------------------------------------------------
# refresh / rebuild
# ---------------------------------------------------------------------------


class TestRefresh:
    def test_incremental_refresh_covers_new_items(self):
        vecs = clustered_corpus(2000, 8, seed=4)
        idx = build_index(vecs, AnnConfig(min_items=0), model_version="v1")
        grown = np.vstack([vecs, clustered_corpus(200, 8, seed=9)]).astype(
            np.float32
        )
        new, report = refresh_index(idx, grown, model_version="v2")
        assert report["path"] == "refresh"
        assert new.built_from == "refresh" and new.model_version == "v2"
        assert new.n_items == 2200
        ids = new.bucket_ids[new.bucket_ids >= 0]
        assert sorted(ids.tolist()) == list(range(2200))
        np.testing.assert_array_equal(new.centroids, idx.centroids)  # no k-means

    def test_drift_guard_triggers_full_rebuild(self):
        vecs = clustered_corpus(2000, 8, seed=4)
        idx = build_index(vecs, AnnConfig(min_items=0), model_version="v1")
        shifted = clustered_corpus(2000, 8, seed=77)  # unrelated geometry
        new, report = refresh_index(idx, shifted, model_version="v2")
        assert report["path"] == "rebuild" and report["reason"] == "drift-guard"
        assert report["drift"] > idx.config.refresh_drift
        assert new.built_from == "rebuild"

    def test_dim_change_forces_rebuild(self):
        idx = build_index(clustered_corpus(1000, 8), AnnConfig(min_items=0))
        new, report = refresh_index(idx, clustered_corpus(1000, 16))
        assert report["reason"] == "dim-changed" and new.dim == 16


# ---------------------------------------------------------------------------
# registry lifecycle
# ---------------------------------------------------------------------------


def _publish_similar_model(store, engine_id, vecs):
    from predictionio_tpu.models.similarproduct.engine import SimilarModel

    model = SimilarModel(
        vecs.copy(), [f"i{j}" for j in range(len(vecs))], [None] * len(vecs)
    )
    manifest = store.publish(
        ModelManifest(
            version="",
            engine_id=engine_id,
            engine_version="1",
            engine_variant="engine.json",
        ),
        model_io.serialize_models([model]),
    )
    return manifest, model


class TestRegistryLifecycle:
    def test_build_for_version_respects_min_items(self, tmp_path):
        store = ArtifactStore(str(tmp_path))
        vecs = clustered_corpus(300, 8)
        m, model = _publish_similar_model(store, "eng", vecs)
        assert (
            lifecycle.build_for_version(
                store, "eng", m.version, [model], AnnConfig(min_items=1000)
            )
            is None
        )
        assert not store.get_manifest("eng", m.version).ann_index
        meta = lifecycle.build_for_version(
            store, "eng", m.version, [model], AnnConfig(min_items=1000), force=True
        )
        assert meta and meta["items"] == 300 and meta["sha256"]

    def test_attach_verifies_and_serves(self, tmp_path):
        store = ArtifactStore(str(tmp_path))
        vecs = clustered_corpus(400, 8)
        m, model = _publish_similar_model(store, "eng", vecs)
        assert lifecycle.attach_from_registry(store, "eng", m.version, [model]) is None
        lifecycle.build_for_version(
            store, "eng", m.version, [model], AnnConfig(min_items=0), force=True
        )
        fresh = model_io.deserialize_models(store.load_blob("eng", m.version))
        serving = lifecycle.attach_from_registry(store, "eng", m.version, fresh)
        assert serving is not None
        assert getattr(fresh[0], lifecycle.ATTR) is serving
        assert serving.index.model_version == m.version

    def test_attach_rejects_item_count_mismatch(self, tmp_path):
        store = ArtifactStore(str(tmp_path))
        vecs = clustered_corpus(400, 8)
        m, model = _publish_similar_model(store, "eng", vecs)
        lifecycle.build_for_version(
            store, "eng", m.version, [model], AnnConfig(min_items=0), force=True
        )
        from predictionio_tpu.models.similarproduct.engine import SimilarModel

        shrunk = SimilarModel(vecs[:100].copy(), [f"i{j}" for j in range(100)], [None] * 100)
        assert (
            lifecycle.attach_from_registry(store, "eng", m.version, [shrunk]) is None
        )

    def test_corrupted_index_blob_fails_verification_not_serving(self, tmp_path):
        import os

        store = ArtifactStore(str(tmp_path))
        vecs = clustered_corpus(400, 8)
        m, model = _publish_similar_model(store, "eng", vecs)
        lifecycle.build_for_version(
            store, "eng", m.version, [model], AnnConfig(min_items=0), force=True
        )
        sha = store.get_manifest("eng", m.version).ann_index["sha256"]
        path = store._blob_path("eng", sha)
        blob = open(path, "rb").read()
        with open(path, "wb") as fh:  # bit-flip
            fh.write(blob[:100] + bytes([blob[100] ^ 0xFF]) + blob[101:])
        with pytest.raises(ArtifactIntegrityError):
            store.load_ann_blob("eng", m.version)
        # the serving attach degrades to exact instead of crashing the lane
        assert lifecycle.attach_from_registry(store, "eng", m.version, [model]) is None

    def test_gc_keeps_referenced_ann_blobs_and_drops_orphaned(self, tmp_path):
        import os

        store = ArtifactStore(str(tmp_path))
        vecs = clustered_corpus(300, 8)
        manifests = []
        for seed in range(3):
            m, model = _publish_similar_model(
                store, "eng", clustered_corpus(300, 8, seed=seed)
            )
            lifecycle.build_for_version(
                store, "eng", m.version, [model], AnnConfig(min_items=0), force=True
            )
            manifests.append(store.get_manifest("eng", m.version))
        store.promote("eng", manifests[-1].version)
        removed = store.gc("eng", keep_last=1)
        # v000002 is neither pinned nor newest-1 -> its ann blob must go
        assert "v000002" in removed
        gone = manifests[1].ann_index["sha256"]
        assert not os.path.exists(store._blob_path("eng", gone))
        # the promoted stable keeps its index artifact
        assert store.load_ann_blob("eng", manifests[-1].version) is not None


# ---------------------------------------------------------------------------
# stream refresh -> candidate -> promote e2e
# ---------------------------------------------------------------------------


class TestStreamRefreshE2E:
    def _rate_event(self, user, item, rating, n):
        import datetime as dt

        from predictionio_tpu.data.datamap import DataMap
        from predictionio_tpu.data.event import Event

        when = dt.datetime(2024, 3, 1, 0, 0, 0, n, tzinfo=dt.timezone.utc)
        return Event(
            event="rate",
            entity_type="user",
            entity_id=user,
            target_entity_type="item",
            target_entity_id=item,
            properties=DataMap({"rating": rating}),
            event_time=when,
            creation_time=when,
        )

    def test_stream_publish_carries_refreshed_index_to_promote(self, tmp_path):
        from predictionio_tpu.data.storage.memory import MemoryStorageClient
        from predictionio_tpu.models.recommendation.engine import ALSModel
        from predictionio_tpu.stream.cursor import CursorStore
        from predictionio_tpu.stream.pipeline import (
            StreamConfig,
            StreamInstruments,
            StreamPipeline,
        )
        from predictionio_tpu.stream.tailer import EventTailer
        from predictionio_tpu.stream.trainers import FoldInALSTrainer

        rng = np.random.default_rng(0)
        n_users, n_items, rank = 20, 60, 4
        seed_model = ALSModel(
            rng.normal(size=(n_users, rank)).astype(np.float32),
            rng.normal(size=(n_items, rank)).astype(np.float32),
            [f"u{i}" for i in range(n_users)],
            [f"i{i}" for i in range(n_items)],
        )
        store = ArtifactStore(str(tmp_path / "registry"))
        stable = store.publish(
            ModelManifest(
                version="",
                engine_id="streameng",
                engine_version="1",
                engine_variant="engine.json",
            ),
            model_io.serialize_models([seed_model]),
        )
        # the batch train built the stable's index
        meta = lifecycle.build_for_version(
            store, "streameng", stable.version, [seed_model],
            AnnConfig(min_items=0), force=True,
        )
        assert meta["builtFrom"] == "train"

        levents = MemoryStorageClient().l_events()
        levents.init(1)
        for i in range(12):
            levents.insert(
                self._rate_event(f"u{i % 5}", f"i{i % 7}", 4.0, i), 1
            )
        trainer = FoldInALSTrainer([seed_model])
        instruments = StreamInstruments(MetricsRegistry())
        pipeline = StreamPipeline(
            EventTailer(levents, 1, batch_limit=50),
            trainer,
            CursorStore(str(tmp_path / "cursors")),
            store,
            StreamConfig(engine_id="streameng", publish_min_events=1),
            instruments=instruments,
        )
        summary = pipeline.run_once()
        candidate = summary["published"]
        assert candidate == "v000002"
        state = store.get_state("streameng")
        assert state.stable == stable.version
        assert state.candidate == candidate
        # the candidate's manifest pins a REFRESHED index with lineage
        cm = store.get_manifest("streameng", candidate)
        assert cm.ann_index and cm.ann_index["builtFrom"] in ("refresh", "rebuild")
        assert cm.ann_index["modelVersion"] == candidate
        assert (
            instruments.ann.refreshes.value() + instruments.ann.rebuilds.value()
            == 1
        )
        # candidate models serve through the candidate's own index
        models = model_io.deserialize_models(store.load_blob("streameng", candidate))
        serving = lifecycle.attach_from_registry(store, "streameng", candidate, models)
        assert serving is not None
        assert serving.index.n_items == len(models[0].item_vocab)
        # ... and the normal rollout path promotes it, index included
        store.promote("streameng")
        assert store.get_state("streameng").stable == candidate
        assert store.load_ann_blob("streameng", candidate) is not None

    def test_no_parent_index_means_no_refresh(self, tmp_path):
        store = ArtifactStore(str(tmp_path))
        vecs = clustered_corpus(200, 8)
        m, model = _publish_similar_model(store, "eng", vecs)
        report = lifecycle.refresh_for_publish(
            store, "eng", m.version, m.version, [model]
        )
        assert report is None


def test_the_rescore_table_of_an_als_model_is_float32_whatever_its_index_stores(
    monkeypatch,
):
    # ServingIndex keeps its item table at the width its product multiplies in
    # (bfloat16 on the chip: forced here); the int8 rescore gathers survivor
    # rows at full precision, from an upload of the model's own rows
    import jax.numpy as jnp

    from predictionio_tpu.models.recommendation.engine import ALSModel
    from predictionio_tpu.ops import topk

    monkeypatch.setattr(topk, "item_table_dtype", lambda: jnp.bfloat16)
    rng = np.random.default_rng(3)
    model = ALSModel(
        rng.normal(size=(5, 8)).astype(np.float32),
        rng.normal(size=(40, 8)).astype(np.float32),
        [f"u{i}" for i in range(5)],
        [f"i{i}" for i in range(40)],
    )
    assert model.serving_index().item_factors.dtype == jnp.bfloat16
    table = lifecycle._exact_device_table(model)
    assert table.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(table), model.item_factors)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


class TestSimilarproductServing:
    N, F = 3000, 8

    @pytest.fixture(scope="class")
    def served(self):
        from predictionio_tpu.models.similarproduct.engine import (
            ALSAlgorithm,
            SimilarModel,
        )

        vecs = clustered_corpus(self.N, self.F, seed=6)
        vocab = [f"i{j}" for j in range(self.N)]
        cats = [
            frozenset({"even"} if j % 2 == 0 else {"odd"}) for j in range(self.N)
        ]
        plain = SimilarModel(vecs.copy(), list(vocab), list(cats))
        indexed = SimilarModel(vecs.copy(), list(vocab), list(cats))
        idx = build_index(vecs, AnnConfig(min_items=0), model_version="v1")
        serving = lifecycle.AnnServing(idx, indexed, recall_sample_every=0)
        setattr(indexed, lifecycle.ATTR, serving)
        return ALSAlgorithm(None), plain, indexed, vocab

    def test_ann_path_matches_exact(self, served):
        from predictionio_tpu.models.similarproduct.engine import Query

        algo, plain, indexed, vocab = served
        rng = np.random.default_rng(8)
        queries = [
            Query(items=(vocab[int(j)],), num=10)
            for j in rng.integers(0, self.N, 24)
        ]
        exact = algo.predict_batch(plain, queries)
        ann = algo.predict_batch(indexed, queries)
        hits = total = 0
        for a, e in zip(ann, exact):
            ai = {s.item for s in a.item_scores}
            hits += sum(1 for s in e.item_scores if s.item in ai)
            total += len(e.item_scores)
        assert total and hits / total >= 0.9
        for a, q in zip(ann, queries):
            assert all(s.item not in q.items for s in a.item_scores)

    def test_filtered_queries_route_through_masked_search(self, served):
        from predictionio_tpu.models.similarproduct.engine import Query

        algo, _plain, indexed, vocab = served
        q = Query(items=(vocab[5],), num=10, categories=frozenset({"odd"}))
        (res,) = algo.predict_batch(indexed, [q])
        assert res.item_scores
        for s in res.item_scores:
            assert int(s.item[1:]) % 2 == 1  # category filter honored

    def test_blacklist_honored_on_ann_path(self, served):
        from predictionio_tpu.models.similarproduct.engine import Query

        algo, plain, indexed, vocab = served
        (probe,) = algo.predict_batch(plain, [Query(items=(vocab[5],), num=3)])
        banned = frozenset(s.item for s in probe.item_scores)
        (res,) = algo.predict_batch(
            indexed, [Query(items=(vocab[5],), num=10, black_list=banned)]
        )
        assert res.item_scores
        assert all(s.item not in banned for s in res.item_scores)

    def test_metrics_and_recall_sampling(self, served):
        from predictionio_tpu.models.similarproduct.engine import Query

        algo, _plain, indexed, vocab = served
        serving = getattr(indexed, lifecycle.ATTR)
        ins = AnnInstruments(MetricsRegistry())
        serving.bind(ins)
        serving._sample_every = 1  # every batch shadow-scores exact
        algo.predict_batch(indexed, [Query(items=(vocab[1],), num=10)])
        assert ins.queries.value() == 1
        assert ins.probes.value() == serving.searcher.nprobe
        assert ins.candidates.value() > 0
        assert 0 < ins.candidates_frac.value() <= 0.10
        assert ins.recall_samples.value() == 1
        assert ins.recall_sampled.value() >= 0.9

    def test_int8_index_serves_the_filterless_dispatch(self, served):
        """An int8-quantized pinned index must keep answering the hot
        (filter-less, exclusion-based) path — and filtered queries fall
        back to exact instead of erroring."""
        from predictionio_tpu.models.similarproduct.engine import (
            Query,
            SimilarModel,
        )

        algo, plain, _indexed, vocab = served
        vecs = plain.item_factors
        q8model = SimilarModel(
            vecs.copy(), list(vocab), list(plain.item_categories)
        )
        idx = build_index(
            vecs, AnnConfig(min_items=0, quantize_int8=True), model_version="v8"
        )
        serving = lifecycle.AnnServing(idx, q8model, recall_sample_every=0)
        setattr(q8model, lifecycle.ATTR, serving)
        ins = AnnInstruments(MetricsRegistry())
        serving.bind(ins)
        queries = [Query(items=(vocab[7],), num=10)]
        exact = algo.predict_batch(plain, queries)
        res = algo.predict_batch(q8model, queries)
        assert res[0].item_scores
        assert vocab[7] not in {s.item for s in res[0].item_scores}
        overlap = {s.item for s in res[0].item_scores} & {
            s.item for s in exact[0].item_scores
        }
        assert len(overlap) >= 8
        assert ins.queries.value() == 1
        # filtered query on the int8 index: exact fallback, counted
        (fres,) = algo.predict_batch(
            q8model,
            [Query(items=(vocab[7],), num=10, categories=frozenset({"odd"}))],
        )
        assert fres.item_scores
        assert all(int(s.item[1:]) % 2 == 1 for s in fres.item_scores)
        assert ins.fallbacks.value() == 1

    def test_oversized_k_falls_back_to_exact_and_counts(self, served):
        from predictionio_tpu.models.similarproduct.engine import Query

        algo, plain, indexed, vocab = served
        serving = getattr(indexed, lifecycle.ATTR)
        ins = AnnInstruments(MetricsRegistry())
        serving.bind(ins)
        big = serving.searcher.candidate_pool() + 1
        res = algo.predict_batch(indexed, [Query(items=(vocab[2],), num=big)])
        exact = algo.predict_batch(plain, [Query(items=(vocab[2],), num=big)])
        assert [s.item for s in res[0].item_scores] == [
            s.item for s in exact[0].item_scores
        ]
        assert ins.fallbacks.value() == 1
        assert ins.queries.value() == 0


class TestTwoTowerServing:
    @pytest.fixture(scope="class")
    def served(self):
        from predictionio_tpu.models.twotower.engine import (
            TwoTowerAlgorithm,
            TwoTowerModelState,
        )
        from predictionio_tpu.models.twotower.model import TwoTower, TwoTowerConfig

        import jax

        n_users, n_items = 50, 2500
        config = TwoTowerConfig(
            n_users=n_users, n_items=n_items, embed_dim=8, hidden=(8,), out_dim=8
        )
        model = TwoTower(config)
        rng = jax.random.PRNGKey(0)
        import jax.numpy as jnp

        params = model.init(
            rng, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), None
        )["params"]
        params = jax.tree_util.tree_map(np.asarray, params)
        ids = jnp.arange(n_items, dtype=jnp.int32)
        item_emb = np.asarray(
            model.apply({"params": params}, ids, method=TwoTower.embed_items)
        )

        def state():
            return TwoTowerModelState(
                config=config,
                params=params,
                item_embeddings=item_emb,
                user_vocab=[f"u{i}" for i in range(n_users)],
                item_vocab=[f"i{i}" for i in range(n_items)],
                losses=[],
            )

        plain, indexed = state(), state()
        idx = build_index(
            item_emb, AnnConfig(min_items=0), model_version="v1"
        )
        serving = lifecycle.AnnServing(idx, indexed, recall_sample_every=0)
        setattr(indexed, lifecycle.ATTR, serving)
        return TwoTowerAlgorithm(None), plain, indexed

    def test_ann_path_matches_exact(self, served):
        from predictionio_tpu.models.twotower.engine import Query

        algo, plain, indexed = served
        queries = [Query(user=f"u{i}", num=10) for i in range(16)]
        exact = algo.predict_batch(plain, queries)
        ann = algo.predict_batch(indexed, queries)
        hits = total = 0
        for a, e in zip(ann, exact):
            ai = {s.item for s in a.item_scores}
            hits += sum(1 for s in e.item_scores if s.item in ai)
            total += len(e.item_scores)
        assert total and hits / total >= 0.9

    def test_unknown_user_answers_empty_without_device(self, served):
        from predictionio_tpu.models.twotower.engine import Query

        algo, _plain, indexed = served
        res = algo.predict_batch(indexed, [Query(user="nobody", num=5)])
        assert res[0].item_scores == ()

    def test_recall_shadow_sampling_records_gauge(self, served):
        from predictionio_tpu.models.twotower.engine import Query

        algo, _plain, indexed = served
        serving = getattr(indexed, lifecycle.ATTR)
        ins = AnnInstruments(MetricsRegistry())
        serving.bind(ins)
        serving._sample_every = 1
        serving._batches = 0
        algo.predict_batch(indexed, [Query(user="u3", num=10)])
        assert ins.recall_samples.value() == 1
        assert ins.recall_sampled.value() >= 0.9

    def test_warmup_covers_ann_and_exact(self, served):
        algo, _plain, indexed = served
        algo.warmup_serving(indexed, max_batch=4)  # must not raise


# ---------------------------------------------------------------------------
# capacity planner + doctor + top
# ---------------------------------------------------------------------------


class TestCapacityPlanner:
    def test_estimate_matches_build_rule(self):
        from predictionio_tpu.obs import xray

        est = xray.estimate_ann(100_000, 32)
        assert est["clusters"] == default_clusters(100_000)
        assert est["bucketCap"] == bucket_capacity(100_000, est["clusters"])
        # the estimate prices the same arrays the build lays out
        idx = build_index(
            clustered_corpus(4000, 8), AnnConfig(min_items=0)
        )
        est2 = xray.estimate_ann(4000, 8, idx.clusters, idx.nprobe)
        assert est2["bucketCap"] == idx.bucket_cap
        assert est2["perDeviceBytes"] == (
            idx.centroids.nbytes + idx.bucket_ids.nbytes + idx.bucket_vecs.nbytes
        )

    def test_estimate_validates_input(self):
        from predictionio_tpu.obs import xray

        with pytest.raises(ValueError):
            xray.estimate_ann(0, 8)

    def test_doctor_ann_prices_and_gates(self, capsys):
        from predictionio_tpu.tools.cli import build_parser, cmd_doctor

        args = build_parser().parse_args(
            ["doctor", "--capacity", "100000", "100000", "32",
             "--ann", "0,0", "--hbm-bytes", "16GB"]
        )
        assert cmd_doctor(args) == 0
        out = json.loads(capsys.readouterr().out.rsplit("\n", 2)[0])
        assert out["ann"]["clusters"] == 2048
        assert out["perDeviceBytesTotal"] > out["capacity"]["per_device_bytes"]
        assert out["fits"] is True

        args = build_parser().parse_args(
            ["doctor", "--capacity", "1000", "1000", "8",
             "--ann", "64,16", "--hbm-bytes", "1KB"]
        )
        assert cmd_doctor(args) == 1  # over budget exits nonzero
        capsys.readouterr()

    def test_doctor_ann_requires_capacity(self, capsys):
        from predictionio_tpu.tools.cli import build_parser, cmd_doctor

        args = build_parser().parse_args(["doctor", "--ann", "0,0"])
        assert cmd_doctor(args) == 1
        capsys.readouterr()

    def test_doctor_inventory_lists_pinned_index(self, tmp_path, capsys):
        from predictionio_tpu.tools.cli import _doctor_ann_inventory

        store = ArtifactStore(str(tmp_path))
        vecs = clustered_corpus(300, 8)
        m, model = _publish_similar_model(store, "inveng", vecs)
        lifecycle.build_for_version(
            store, "inveng", m.version, [model], AnnConfig(min_items=0), force=True
        )
        _doctor_ann_inventory(str(tmp_path))
        out = capsys.readouterr().out
        assert "ann indexes" in out and "300 items" in out and m.version in out


class TestTopAnnLine:
    def _scrape(self, registry):
        from predictionio_tpu.tools import top

        return top.parse_prometheus(registry.render_prometheus())

    def test_silent_until_an_index_is_pinned(self):
        from predictionio_tpu.tools import top

        registry = MetricsRegistry()
        AnnInstruments(registry)  # eager zero registration
        summary = top.summarize(self._scrape(registry))
        assert summary["ann"] is None
        assert "ann " not in top.render(summary, "http://x")

    def test_renders_index_and_live_counters(self):
        from predictionio_tpu.tools import top

        registry = MetricsRegistry()
        ins = AnnInstruments(registry)
        ins.index_items.set(100_000, version="v000003")
        ins.index_clusters.set(2048, version="v000003")
        ins.queries.inc(200)
        ins.probes.inc(3200)
        ins.candidates_frac.set(0.0077)
        ins.recall_samples.inc(3)
        ins.recall_sampled.set(0.996)
        ins.fallbacks.inc(2)
        summary = top.summarize(self._scrape(registry))
        ann = summary["ann"]
        assert ann["queries_total"] == 200
        assert ann["probes_per_query"] == 16.0
        assert ann["indexes"]["v000003"]["items"] == 100_000
        screen = top.render(summary, "http://x")
        assert "ann" in screen and "v000003" in screen
        assert "probes/q 16.0" in screen and "recall~0.996" in screen

    def test_reload_retires_stale_version_gauges(self):
        """sync_indexes must zero a version's gauge series once no live
        lane pins it — `pio top` would otherwise list every version a
        long-running server ever served as simultaneously pinned."""
        from predictionio_tpu.tools import top

        registry = MetricsRegistry()
        ins = AnnInstruments(registry)
        ins.sync_indexes({"v1": (1000.0, 64.0)})
        ins.sync_indexes({"v2": (1200.0, 64.0)})  # reload: v1 retired
        summary = top.summarize(self._scrape(registry))
        assert set(summary["ann"]["indexes"]) == {"v2"}
        # both lanes pinned during a rollout: both render
        ins.sync_indexes({"v2": (1200.0, 64.0), "v3": (1300.0, 64.0)})
        summary = top.summarize(self._scrape(registry))
        assert set(summary["ann"]["indexes"]) == {"v2", "v3"}

    def test_json_mode_carries_ann_fields(self):
        from predictionio_tpu.tools import top

        registry = MetricsRegistry()
        ins = AnnInstruments(registry)
        ins.index_items.set(500, version="v1")
        text = registry.render_prometheus()
        outs = []
        top.run_top(
            "http://a",
            iterations=1,
            fetch=lambda u: text,
            out=outs.append,
            json_mode=True,
        )
        payload = json.loads(outs[0])
        assert payload["ann"]["indexes"]["v1"]["items"] == 500
