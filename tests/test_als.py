"""ALS solver correctness tests (CPU, small synthetic problems)."""

import numpy as np
import pytest

from predictionio_tpu.ops.als import ALSConfig, als_train


def synthetic_ratings(n_users=30, n_items=20, rank=4, density=0.5, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    V = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    full = U @ V.T + 3.0
    mask = rng.random((n_users, n_items)) < density
    users, items = np.nonzero(mask)
    return users, items, full[users, items].astype(np.float32)


class TestExplicitALS:
    def test_reconstructs_observed_ratings(self):
        users, items, vals = synthetic_ratings()
        uf, vf = als_train(
            users, items, vals, 30, 20, ALSConfig(rank=8, iterations=15, reg=0.01)
        )
        uf, vf = np.asarray(uf), np.asarray(vf)
        assert uf.shape == (30, 8) and vf.shape == (20, 8)
        pred = np.sum(uf[users] * vf[items], axis=1)
        rmse = float(np.sqrt(np.mean((pred - vals) ** 2)))
        assert rmse < 0.15, f"rmse too high: {rmse}"

    def test_loss_better_than_mean_baseline(self):
        users, items, vals = synthetic_ratings(density=0.7, seed=1)
        uf, vf = als_train(
            users, items, vals, 30, 20, ALSConfig(rank=6, iterations=10, reg=0.05)
        )
        pred = np.sum(np.asarray(uf)[users] * np.asarray(vf)[items], axis=1)
        rmse = np.sqrt(np.mean((pred - vals) ** 2))
        baseline = np.sqrt(np.mean((vals - vals.mean()) ** 2))
        assert rmse < baseline / 3

    def test_deterministic_given_seed(self):
        users, items, vals = synthetic_ratings()
        cfg = ALSConfig(rank=4, iterations=3, seed=7)
        uf1, _ = als_train(users, items, vals, 30, 20, cfg)
        uf2, _ = als_train(users, items, vals, 30, 20, cfg)
        np.testing.assert_allclose(np.asarray(uf1), np.asarray(uf2))

    def test_negative_indices_dropped(self):
        users = np.array([0, 1, -1, 2], np.int32)
        items = np.array([0, 1, 2, -1], np.int32)
        vals = np.array([5, 4, 3, 2], np.float32)
        uf, vf = als_train(users, items, vals, 3, 3, ALSConfig(rank=2, iterations=2))
        assert np.all(np.isfinite(np.asarray(uf)))

    def test_bf16_gather_quality_parity(self):
        # gather_dtype="bf16" rounds only the gathered operand of the Gram
        # accumulation (accumulators/solves stay f32): quality must stay
        # within bf16 rounding of the f32 path, not just "finite"
        users, items, vals = synthetic_ratings(density=0.7, seed=2)

        def rmse(dt):
            uf, vf = als_train(
                users, items, vals, 30, 20,
                ALSConfig(rank=6, iterations=8, reg=0.05, gather_dtype=dt),
            )
            pred = np.sum(np.asarray(uf)[users] * np.asarray(vf)[items], axis=1)
            return float(np.sqrt(np.mean((pred - vals) ** 2)))

        r32, r16 = rmse("f32"), rmse("bf16")
        assert abs(r16 - r32) < 0.02, (r32, r16)

    def test_gather_dtype_validated(self):
        with pytest.raises(ValueError):
            ALSConfig(gather_dtype="f64")

    def test_cold_entities_zero_safe(self):
        # user 2 and item 2 have no ratings; solve must stay finite
        users = np.array([0, 1], np.int32)
        items = np.array([0, 1], np.int32)
        vals = np.array([4.0, 3.0], np.float32)
        uf, vf = als_train(users, items, vals, 3, 3, ALSConfig(rank=4, iterations=3))
        assert np.all(np.isfinite(np.asarray(uf)))
        assert np.all(np.isfinite(np.asarray(vf)))


class TestImplicitALS:
    def test_ranks_positive_interactions_higher(self):
        rng = np.random.default_rng(2)
        # two user groups preferring two item groups
        users, items, vals = [], [], []
        for u in range(20):
            group = u % 2
            for _ in range(8):
                i = rng.integers(0, 10) + group * 10
                users.append(u)
                items.append(int(i))
                vals.append(1.0)
        uf, vf = als_train(
            np.array(users, np.int32),
            np.array(items, np.int32),
            np.array(vals, np.float32),
            20,
            20,
            ALSConfig(rank=8, iterations=10, implicit=True, alpha=40.0, reg=0.1),
        )
        uf, vf = np.asarray(uf), np.asarray(vf)
        scores = uf @ vf.T
        # group-0 users should score group-0 items higher on average
        g0 = scores[0, :10].mean() - scores[0, 10:].mean()
        g1 = scores[1, 10:].mean() - scores[1, :10].mean()
        assert g0 > 0 and g1 > 0


class TestShardedALS:
    """ALX-style mesh-parallel ALS (ops/als_sharded.py) on the virtual
    8-device CPU mesh — the multi-chip schedule the driver dry-runs."""

    def _problem(self, n_u=50, n_i=37, nnz=2000, k=4, seed=0):
        rng = np.random.default_rng(seed)
        u = rng.integers(0, n_u, nnz).astype(np.int32)
        i = rng.integers(0, n_i, nnz).astype(np.int32)
        U = rng.normal(size=(n_u, k))
        V = rng.normal(size=(n_i, k))
        r = np.sum(U[u] * V[i], axis=1).astype(np.float32)
        return u, i, r, n_u, n_i

    def test_matches_single_device_quality(self):
        import jax

        from predictionio_tpu.ops.als import ALSConfig, als_train
        from predictionio_tpu.ops.als_sharded import als_train_sharded

        assert len(jax.devices()) == 8  # conftest forces the virtual mesh
        u, i, r, n_u, n_i = self._problem()
        cfg = ALSConfig(rank=8, iterations=10, reg=0.05, chunk=512)
        uf_s, vf_s = als_train(u, i, r, n_u, n_i, cfg)
        uf_m, vf_m = als_train_sharded(u, i, r, n_u, n_i, cfg)
        assert uf_m.shape == (n_u, 8) and vf_m.shape == (n_i, 8)
        rmse_single = float(
            np.sqrt(np.mean(((np.asarray(uf_s) @ np.asarray(vf_s).T)[u, i] - r) ** 2))
        )
        rmse_multi = float(np.sqrt(np.mean(((uf_m @ vf_m.T)[u, i] - r) ** 2)))
        assert rmse_multi < 0.15
        assert rmse_multi < max(5 * abs(rmse_single), 0.15)

    def test_dictionary_wire_sharded_parity(self, monkeypatch):
        """Star-rating data rides the uint8 dictionary wire on the sharded
        path too; factors must match the f32-wire run exactly (the decode
        gather reproduces identical f32 values)."""
        from predictionio_tpu.ops.als import ALSConfig
        from predictionio_tpu.ops.als_sharded import als_train_sharded

        u, i, _, n_u, n_i = self._problem()
        r = np.random.default_rng(7).choice(
            np.arange(1.0, 5.5, 0.5), len(u)
        ).astype(np.float32)
        cfg = ALSConfig(rank=8, iterations=4, reg=0.05, chunk=512)
        uf_dict, vf_dict = als_train_sharded(u, i, r, n_u, n_i, cfg)
        # force the f32 wire by disabling the compressor
        import predictionio_tpu.ops.als_sharded as sh

        monkeypatch.setattr(sh, "_compress_ratings_wire", lambda v: (v, None))
        uf_f32, vf_f32 = als_train_sharded(u, i, r, n_u, n_i, cfg)
        np.testing.assert_allclose(uf_dict, uf_f32, rtol=0, atol=1e-5)
        np.testing.assert_allclose(vf_dict, vf_f32, rtol=0, atol=1e-5)

    def test_bf16_gather_quality_parity_sharded(self):
        # the sharded path must honor gather_dtype too (bf16 factors across
        # the ICI all_gather + bf16 HBM row gathers), with quality within
        # bf16 rounding of the sharded f32 run
        from predictionio_tpu.ops.als import ALSConfig
        from predictionio_tpu.ops.als_sharded import als_train_sharded

        u, i, r, n_u, n_i = self._problem()

        def rmse(dt):
            cfg = ALSConfig(
                rank=8, iterations=10, reg=0.05, chunk=512, gather_dtype=dt
            )
            uf, vf = als_train_sharded(u, i, r, n_u, n_i, cfg)
            return float(np.sqrt(np.mean(((uf @ vf.T)[u, i] - r) ** 2)))

        r32, r16 = rmse("f32"), rmse("bf16")
        assert r16 < 0.2 and abs(r16 - r32) < 0.05, (r32, r16)

    @pytest.mark.parametrize("gather_dtype", ["f32", "bf16"])
    def test_implicit_mode(self, gather_dtype):
        # bf16 variant: the implicit path must keep its shared V^T V gram
        # term at full precision (f32 all_gather) while still ranking
        # correctly — the contract the explicit path's wire-bf16 skips
        from predictionio_tpu.ops.als import ALSConfig
        from predictionio_tpu.ops.als_sharded import als_train_sharded

        u, i, r, n_u, n_i = self._problem()
        cfg = ALSConfig(
            rank=8, iterations=6, reg=0.05, implicit=True, alpha=2.0, chunk=512,
            gather_dtype=gather_dtype,
        )
        uf, vf = als_train_sharded(u, i, np.abs(r), n_u, n_i, cfg)
        assert np.all(np.isfinite(uf)) and np.all(np.isfinite(vf))
        # observed pairs should score above unobserved on average
        scores = uf @ vf.T
        seen = scores[u, i].mean()
        assert seen > scores.mean()

    def test_entity_counts_not_divisible_by_mesh(self):
        from predictionio_tpu.ops.als import ALSConfig
        from predictionio_tpu.ops.als_sharded import als_train_sharded

        # 13 users / 5 items on 8 devices: blocks are mostly padding
        u, i, r, n_u, n_i = self._problem(n_u=13, n_i=5, nnz=400)
        cfg = ALSConfig(rank=4, iterations=6, reg=0.05, chunk=256)
        uf, vf = als_train_sharded(u, i, r, n_u, n_i, cfg)
        assert uf.shape == (13, 4) and vf.shape == (5, 4)
        rmse = float(np.sqrt(np.mean(((uf @ vf.T)[u, i] - r) ** 2)))
        assert rmse < 0.2

    def test_block_partition_localizes_and_pads(self):
        from predictionio_tpu.ops.als_sharded import _block_partition_blocked

        owner = np.array([0, 3, 4, 7, 7], np.int32)
        other = np.array([10, 11, 12, 13, 14], np.int32)
        vals = np.arange(5, dtype=np.float32) + 1
        br, cols, v, w = _block_partition_blocked(
            owner, other, vals, block=4, n_dev=2, d=8, block_chunk=8
        )
        nb = br.shape[1]
        assert br.shape == (2, nb) and cols.shape == v.shape == w.shape == (2, nb, 8)
        # device 0 owns users 0-3 (local rows 0 and 3); device 1 owns 4-7
        # (local rows 0 and 3); one block per distinct local entity here
        assert list(br[0, :2]) == [0, 3]
        assert list(br[1, :2]) == [0, 3]
        # pad blocks target the local dummy row (== block)
        assert (br[:, 2:] == 4).all()
        # entries land with their values; pad slots carry weight 0
        assert v[0, 0, 0] == 1.0 and cols[0, 0, 0] == 10
        assert v[1, 1, 0] == 4.0 and v[1, 1, 1] == 5.0  # user 7's two ratings
        assert w[1, 1, 0] == 1 and w[1, 1, 2] == 0

    def test_block_partition_matches_per_device_block_coo(self):
        """The one-pass global group-by packer must emit bit-identical
        tables to its predecessor (per-device stable-argsort _block_coo),
        including within-entity event order, dummy padding, and the
        common-nb padding rule."""
        from predictionio_tpu.ops.als import _block_coo
        from predictionio_tpu.ops.als_sharded import _block_partition_blocked

        rng = np.random.default_rng(11)
        for trial, (n_ent, n_dev, d, bc, nnz) in enumerate(
            [(16, 4, 8, 8, 500), (7, 3, 8, 16, 0), (40, 8, 16, 8, 3000), (5, 2, 8, 8, 37)]
        ):
            block = -(-n_ent // n_dev)
            owner = rng.integers(0, n_ent, nnz).astype(np.int32)
            other = rng.integers(0, 50, nnz).astype(np.int32)
            vals = rng.random(nnz).astype(np.float32)
            got = _block_partition_blocked(owner, other, vals, block, n_dev, d, bc)
            # predecessor: per-device localized _block_coo, padded to max nb
            owners = owner // block
            layouts = [
                _block_coo(
                    (owner[owners == dev] - dev * block).astype(np.int32),
                    other[owners == dev],
                    vals[owners == dev],
                    d,
                    bc,
                    dummy_row=block,
                )
                for dev in range(n_dev)
            ]
            nb = max(l[0].shape[0] for l in layouts)
            nb += (-nb) % bc
            want = (
                np.full((n_dev, nb), block, np.int32),
                np.zeros((n_dev, nb, d), np.int32),
                np.zeros((n_dev, nb, d), np.float32),
                np.zeros((n_dev, nb, d), np.int8),
            )
            for dev, tables in enumerate(layouts):
                n = tables[0].shape[0]
                for w_arr, t in zip(want, tables):
                    w_arr[dev, :n] = t
            for g, w_arr, name in zip(got, want, ("br", "cols", "vals", "w")):
                assert np.array_equal(g, w_arr), (trial, name)


def _raw_columns(case: str):
    """``(users, items, ratings, n_users, n_items)`` for one shape of input
    ``_device_pack`` has to group as the host reference does."""
    rng = np.random.default_rng(11)
    n_users, n_items, nnz = 120, 80, 6000
    if case == "n_items_over_int16":
        n_items = 40_000
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    if case == "already_grouped":
        u = np.sort(u)
    elif case == "one_user_only":
        u[:] = 7
    elif case == "degree_0_and_over_d":
        # user 3 and item 5 rate nothing; user 9 and item 2 span several blocks
        u[u == 3], i[i == 5] = 4, 6
        u[:300], i[300:700] = 9, 2
    elif case == "n_items_over_int16":
        i[:50] = rng.integers(32_768, n_items, 50)
    elif case == "duplicate_pairs":
        u[:40], i[:40] = 17, 23
    else:
        assert case == "shuffled"
    # half-star ratings, distinct enough that a slot swapped inside a block shows
    v = (rng.integers(2, 11, nnz) / 2.0).astype(np.float32)
    return u, i, v, n_users, n_items


class TestDevicePack:
    """The device-side block-building pipeline: the host hands over the raw
    columns and the two degree histograms; the device groups by user with a
    stable sort, sorts that stream by item, and gather-expands both block
    tables. Must agree with the all-host ``_block_coo`` reference layout."""

    def _coo(self, n_users=120, n_items=80, nnz=6000, seed=3):
        rng = np.random.default_rng(seed)
        u = rng.integers(0, n_users, nnz).astype(np.int32)
        i = rng.integers(0, n_items, nnz).astype(np.int32)
        v = (rng.integers(2, 11, nnz) / 2.0).astype(np.float32)
        return u, i, v

    @pytest.mark.parametrize(
        "case",
        [
            "shuffled",
            "already_grouped",
            "one_user_only",
            "degree_0_and_over_d",
            "n_items_over_int16",
            "duplicate_pairs",
        ],
    )
    def test_tables_from_raw_columns_equal_the_host_reference(self, case):
        """All eight tables, ``array_equal``. The user side is ``_block_coo``
        of the columns as they are; the item side is ``_block_coo`` of the
        user-grouped stream, which is the order the item sort starts from."""
        from predictionio_tpu.ops.als import _block_coo, _device_pack, _pad_blocks

        u, i, v, n_users, n_items = _raw_columns(case)
        d, bc = 16, 64
        deg_u = np.bincount(u, minlength=n_users).astype(np.int32)
        deg_i = np.bincount(i, minlength=n_items).astype(np.int32)
        tables = _device_pack(
            u, i, v, deg_u, deg_i,
            d=d,
            nb_u=_pad_blocks(int((-(-deg_u // d)).sum()), bc),
            nb_i=_pad_blocks(int((-(-deg_i // d)).sum()), bc),
            n_users=n_users,
            n_items=n_items,
        )
        grouped = np.argsort(u, kind="stable")
        host = (
            *_block_coo(u, i, v, d, bc, n_users),
            *_block_coo(i[grouped], u[grouped], v[grouped], d, bc, n_items),
        )
        names = [f"{side}-side {t}" for side in "ui" for t in ("br", "cols", "vals", "w")]
        for dev_t, host_t, name in zip(tables, host, names, strict=True):
            np.testing.assert_array_equal(np.asarray(dev_t), host_t, err_msg=name)
        if case == "degree_0_and_over_d":
            assert deg_u[3] == 0 and deg_i[5] == 0 and deg_u[9] > d and deg_i[2] > d

    @pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
    @pytest.mark.parametrize("case", ["negative_ids", "no_negative_ids", "empty", "all_negative"])
    def test_train_returns_what_the_host_pack_returns(self, case, implicit):
        """Ratings with a negative id are dropped on both pack paths, an
        input with none is not copied, and an empty one (or one that is
        empty once they are dropped) trains on the host tables."""
        u, i, v = self._coo(nnz=4000)
        if case == "negative_ids":
            u, i = u.copy(), i.copy()
            u[::7], i[3::11] = -1, -5
        elif case == "all_negative":
            u = np.full_like(u, -1)
        elif case == "empty":
            u, i, v = u[:0], i[:0], v[:0]

        def train(pack, *columns):
            cfg = ALSConfig(rank=4, iterations=3, reg=0.05, implicit=implicit, pack=pack)
            return [np.asarray(f) for f in als_train(*columns, 120, 80, cfg)]

        dev, host = train("device", u, i, v), train("host", u, i, v)
        if case in ("empty", "all_negative"):
            # no rating is left: both take the host's (empty) tables
            for got, want in zip(dev, host):
                np.testing.assert_array_equal(got, want)
            return
        # the item side sums in another order (see the parity test below)
        for got, want in zip(dev, host):
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
        if case == "negative_ids":
            keep = (u >= 0) & (i >= 0)
            assert 0 < keep.sum() < len(u)
            for got, want in zip(dev, train("device", u[keep], i[keep], v[keep])):
                np.testing.assert_array_equal(got, want)

    def test_host_group_by_native_matches_numpy(self):
        from predictionio_tpu.ops.als import _host_group_by
        from predictionio_tpu.utils import native

        u, i, v = self._coo(seed=7)
        got = native.coo_group(u, i, v, 120)
        if got is None:
            pytest.skip("native library unavailable")
        order = np.argsort(u, kind="stable")
        np.testing.assert_array_equal(got[0], i[order])
        np.testing.assert_array_equal(got[1], v[order])
        np.testing.assert_array_equal(
            got[2], np.bincount(u, minlength=120).astype(np.int32)
        )
        # out-of-range entity ids -> clean refusal (caller falls back)
        bad = u.copy()
        bad[0] = 10_000
        assert native.coo_group(bad, i, v, 120) is None

    @pytest.mark.parametrize("native_library", [True, False], ids=["native", "numpy"])
    def test_checked_degrees_counts_and_refuses(self, native_library, monkeypatch):
        """One pass a column checks and counts; numpy does the same without
        the library, and says which id it was when one is out of range."""
        from predictionio_tpu.ops.als import _checked_degrees
        from predictionio_tpu.utils import native

        if not native_library:
            monkeypatch.setattr(native, "degrees", lambda ids, n: None)
        elif native.get_library() is None:
            pytest.skip("native library unavailable")
        u, i, _ = self._coo(seed=9)
        deg_u, deg_i = _checked_degrees(u, i, 120, 80)
        assert deg_u.dtype == np.int32 and deg_i.dtype == np.int32
        np.testing.assert_array_equal(deg_u, np.bincount(u, minlength=120))
        np.testing.assert_array_equal(deg_i, np.bincount(i, minlength=80))
        negative = i.copy()
        negative[5] = -1
        assert _checked_degrees(u, negative, 120, 80) is None
        # a negative id wins over one past the vocabulary: that rating may be
        # the one the caller is about to drop
        too_large = u.copy()
        too_large[5] = 120
        assert _checked_degrees(too_large, negative, 120, 80) is None
        with pytest.raises(ValueError, match="user index 120 out of range for n_users=120"):
            _checked_degrees(too_large, i, 120, 80)
        with pytest.raises(ValueError, match="item index 99 out of range for n_items=80"):
            _checked_degrees(u, np.where(i == 0, 99, i).astype(np.int32), 120, 80)

    @pytest.mark.parametrize("implicit", [False, True])
    def test_end_to_end_quality_parity_with_host_pack(self, implicit):
        u, i, v = self._coo(nnz=4000)
        preds = {}
        for pack in ("host", "device"):
            cfg = ALSConfig(rank=8, iterations=6, reg=0.05, implicit=implicit, pack=pack)
            uf, vf = als_train(u, i, v, 120, 80, cfg)
            preds[pack] = np.sum(np.asarray(uf)[u] * np.asarray(vf)[i], axis=1)
        # fp summation order differs on the item side (device sorts by item
        # over the user-grouped order), so factors drift chaotically while
        # prediction quality must not
        rmse = {
            k: float(np.sqrt(np.mean((p - v) ** 2))) for k, p in preds.items()
        }
        assert abs(rmse["host"] - rmse["device"]) < 5e-3, rmse

    def test_empty_input_falls_back_cleanly(self):
        cfg = ALSConfig(rank=4, iterations=2, pack="device")
        uf, vf = als_train(
            np.zeros(0, np.int32), np.zeros(0, np.int32), np.zeros(0, np.float32),
            10, 8, cfg,
        )
        assert np.asarray(uf).shape == (10, 4)
        assert np.all(np.isfinite(np.asarray(uf)))

    @pytest.mark.parametrize("pack", ["device", "host"])
    def test_timings_decomposition_present(self, pack):
        u, i, v = self._coo(nnz=2000)
        t: dict = {}
        als_train(u, i, v, 120, 80, ALSConfig(rank=4, iterations=2, pack=pack), timings=t)
        assert set(t) == {
            "pack_s", "upload_s", "build_s", "device_s", "wire_bytes",
            "nb_u", "nb_i", "d",
        }
        assert all(val >= 0 for val in t.values())
        assert t["nb_u"] > 0 and t["nb_i"] > 0 and t["d"] >= 8
        if pack == "device":
            # the three columns as they are, and the two degree histograms
            assert t["wire_bytes"] == 3 * 4 * 2000 + 4 * (120 + 80)
        else:
            assert t["build_s"] == 0 and t["wire_bytes"] > 3 * 4 * 2000

    def test_timings_tile_the_call_and_the_spans_are_written_in_order(self, tmp_path):
        """What ``benchmark/readers`` stand on (``timings_sum`` and
        ``idle_under_span``): the four clocks run back to back from the
        call's first line, and under an open profiler session each stage is
        one host span, in the clocks' order, ``pio:als.upload`` with the
        bytes that crossed."""
        import glob
        import time

        import jax
        from jax.profiler import ProfileData

        u, i, v = self._coo(nnz=2000)
        cfg = ALSConfig(rank=4, iterations=3)
        als_train(u, i, v, 120, 80, cfg, timings={})  # every program compiled
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            t: dict = {}
            t0 = time.perf_counter()
            als_train(u, i, v, 120, 80, cfg, timings=t)
            wall = time.perf_counter() - t0
        finally:
            jax.profiler.stop_trace()
        stages = t["pack_s"] + t["upload_s"] + t["build_s"] + t["device_s"]
        # nothing but imports and one look-up precedes the pack's clock
        assert stages <= wall and wall - stages < 0.05, (t, wall)

        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        spans = sorted(
            (event.start_ns, event.name, dict(event.stats), event.duration_ns)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines
            for event in line.events
            if event.name.startswith("pio:als.")
        )
        assert [name for _, name, _, _ in spans] == [
            "pio:als.pack", "pio:als.upload", "pio:als.build",
            "pio:als.sweep", "pio:als.sweep", "pio:als.sweep", "pio:als.fetch",
        ]
        by_name = {name: (stats, duration) for _, name, stats, duration in spans}
        assert by_name["pio:als.upload"][0]["bytes"] == t["wire_bytes"]
        # a span is its clock: the pack's closes where upload_s starts
        for name, key in (("pio:als.pack", "pack_s"), ("pio:als.upload", "upload_s")):
            assert abs(by_name[name][1] / 1e9 - t[key]) < 0.005, (name, by_name[name], t)

    def test_ratings_wire_compression_forms(self):
        """Smallest lossless wire form: uint8 dictionary for <=256 distinct
        values (every star-rating dataset), f16 when exact, f32 otherwise."""
        from predictionio_tpu.ops.als import _compress_ratings_wire

        stars = np.random.default_rng(0).choice(
            np.arange(0.5, 5.5, 0.5), size=100_000
        ).astype(np.float32)
        wire, table = _compress_ratings_wire(stars)
        assert wire.dtype == np.uint8 and table is not None
        np.testing.assert_array_equal(table[wire], stars)  # exact decode

        # >256 distinct but f16-exact (integers): dictionary declines, f16
        ints = np.arange(1000, dtype=np.float32)
        wire, table = _compress_ratings_wire(ints)
        assert wire.dtype == np.float16 and table is None
        np.testing.assert_array_equal(wire.astype(np.float32), ints)

        # continuous: untouched f32 (no silent quality trade)
        cont = np.random.default_rng(1).normal(size=100_000).astype(np.float32)
        wire, table = _compress_ratings_wire(cont)
        assert wire.dtype == np.float32 and table is None

        # sample-probe edge: first 65536 values all identical, tail adds
        # values — table verification must still be exact over the FULL
        # column (a wrong early exit would silently corrupt ratings)
        tricky = np.concatenate(
            [np.full(70_000, 3.0, np.float32), stars]
        )
        wire, table = _compress_ratings_wire(tricky)
        if table is not None:
            np.testing.assert_array_equal(table[wire], tricky)

    def test_star_ratings_train_as_on_the_host_pack_path(self):
        """Star-rating data goes up as float32 and trains to the host-pack
        path's factors."""
        rng = np.random.default_rng(5)
        u = rng.integers(0, 120, 4000).astype(np.int32)
        i = rng.integers(0, 80, 4000).astype(np.int32)
        v = rng.choice(np.arange(1.0, 5.5, 0.5), 4000).astype(np.float32)
        cfg_dev = ALSConfig(rank=4, iterations=3, pack="device")
        cfg_host = ALSConfig(rank=4, iterations=3, pack="host")
        uf_d, vf_d = als_train(u, i, v, 120, 80, cfg_dev)
        uf_h, vf_h = als_train(u, i, v, 120, 80, cfg_host)
        np.testing.assert_allclose(
            np.asarray(uf_d), np.asarray(uf_h), rtol=0, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(vf_d), np.asarray(vf_h), rtol=0, atol=1e-5
        )

    def test_block_shapes_match_across_pack_paths(self):
        u, i, v = self._coo(nnz=2000)
        t_dev: dict = {}
        t_host: dict = {}
        als_train(
            u, i, v, 120, 80,
            ALSConfig(rank=4, iterations=1, pack="device"), timings=t_dev,
        )
        als_train(
            u, i, v, 120, 80,
            ALSConfig(rank=4, iterations=1, pack="host"), timings=t_host,
        )
        assert (t_dev["nb_u"], t_dev["nb_i"], t_dev["d"]) == (
            t_host["nb_u"], t_host["nb_i"], t_host["d"]
        )

    def test_out_of_range_indices_rejected(self):
        u, i, v = self._coo(nnz=100)
        u = u.copy()
        u[0] = 500  # >= n_users
        with pytest.raises(ValueError, match="out of range"):
            als_train(u, i, v, 120, 80, ALSConfig(rank=4, iterations=1))
