"""ops/topk: the one serving ending (score -> mask -> weights -> top-k ->
pack) behind its three jitted fronts, against a NumPy reference
(``host_top_k`` over float64 scores). Small on purpose: n a few hundred,
f 8."""

import ast
import pathlib

import numpy as np
import pytest

from predictionio_tpu.ops import topk

N, F, USERS, Q = 300, 8, 40, 4
FRONTS = ("by_index", "by_vector", "gather_sum")
_OPS = pathlib.Path(topk.__file__).parent


def _tables(n=N, f=F, users=USERS, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.normal(size=(users, f)).astype(np.float32),
        rng.normal(size=(n, f)).astype(np.float32),
    )


class Front:
    """One front on seeded queries: ``dispatch(rows, bucket, k, mask,
    weights)`` stages ``rows`` queries into ``bucket`` rows (pad rows as the
    engines pad them) and returns the packed handle; ``scores64(rows)`` is
    what the reference selects from."""

    def __init__(self, name: str, n=N, f=F, seed=0):
        self.name = name
        self.users, self.items = _tables(n, f, seed=seed)
        self.n = n
        rng = np.random.default_rng(seed + 1)
        self.uidx = rng.integers(0, USERS, 8).astype(np.int32)
        self.qidx = rng.integers(0, n, (8, Q)).astype(np.int32)
        self.qweight = rng.uniform(0.5, 2.0, (8, Q)).astype(np.float32)
        self.qidx[:, -1] = 0  # a padded query slot: row 0, zero weight
        self.qweight[:, -1] = 0.0
        self.index = topk.ServingIndex(self.users, self.items)

    def scores64(self, rows: int) -> np.ndarray:
        items = self.items.astype(np.float64)
        if self.name == "gather_sum":
            q = items[self.qidx[:rows]] * self.qweight[:rows, :, None].astype(np.float64)
            return np.einsum("nf,bqf->bn", items, q)
        return self.users.astype(np.float64)[self.uidx[:rows]] @ items.T

    def dispatch(self, rows, bucket, k, mask=None, weights=None):
        if mask is not None and mask.ndim == 2:
            padded = np.ones((bucket, self.n), bool)
            padded[:rows] = mask[:rows]
            mask = padded
        if self.name == "by_index":
            assert weights is None  # the recommendation engine has none
            staged = np.zeros((bucket,), np.int32)
            staged[:rows] = self.uidx[:rows]
            return self.index.serve_batch_async(staged, k, mask)
        if self.name == "by_vector":
            staged = np.zeros((bucket, self.items.shape[1]), np.float32)
            staged[:rows] = self.users[self.uidx[:rows]]
            return topk.dot_top_k_async(
                self.index.item_factors, staged, mask, k, weights=weights
            )
        qidx = np.zeros((bucket, Q), np.int32)
        qweight = np.zeros((bucket, Q), np.float32)
        qidx[:rows], qweight[:rows] = self.qidx[:rows], self.qweight[:rows]
        return topk.gather_sum_top_k_async(
            self.index.item_factors, qidx, qweight, mask, k, weights=weights
        )


@pytest.fixture(scope="module")
def fronts():
    return {name: Front(name) for name in FRONTS}


def _mask(kind: str, rows: int, n=N):
    rng = np.random.default_rng(7)
    if kind == "none":
        return None
    return rng.random((n,) if kind == "n" else (rows, n)) < 0.7


def _assert_rows_match(front, handle, rows, k, mask, weights):
    scores, idx = topk.fetch_topk(handle)
    assert scores.dtype == np.float32 and idx.dtype == np.int32
    dense = front.scores64(rows)
    if weights is not None:
        dense = dense * weights.astype(np.float64)
    for row in range(rows):
        row_mask = mask if mask is None or mask.ndim == 1 else mask[row]
        want_s, want_i = topk.host_top_k(dense[row], row_mask, k)
        finite = np.isfinite(scores[row, :k])
        assert list(idx[row, :k][finite]) == list(want_i)
        np.testing.assert_allclose(scores[row, :k][finite], want_s, rtol=1e-4, atol=1e-5)
        # whatever is not finite is at the end: selection sorts descending
        assert not finite[int(finite.sum()):].any()


_PARITY = [
    (front, mask, weights, rows, k)
    for front in FRONTS
    for mask in ("none", "n", "Bn")
    for weights in ((False,) if front == "by_index" else (False, True))
    for rows in (1, 3, 8)
    for k in (1, 10, N)
]


@pytest.mark.parametrize(
    "name,mask_kind,weighted,rows,k",
    _PARITY,
    ids=[
        f"{f}-mask_{m}-{'weighted' if w else 'plain'}-rows{r}-k{k}"
        for f, m, w, r, k in _PARITY
    ],
)
def test_every_front_matches_the_numpy_reference(fronts, name, mask_kind, weighted, rows, k):
    front = fronts[name]
    mask = _mask(mask_kind, rows)
    weights = (
        np.random.default_rng(11).uniform(0.25, 4.0, N).astype(np.float32)
        if weighted
        else None
    )
    # as the engines ask: rows and k in their power-of-two buckets, k at
    # most the catalogue; the first rows and the first k are the answer
    bucket, kk = topk.next_pow2(rows), min(topk.next_pow2(k), N)
    handle = front.dispatch(rows, bucket, kk, mask, weights)
    assert handle.shape == (bucket, 2, kk)
    _assert_rows_match(front, handle, rows, k, mask, weights)


@pytest.mark.parametrize("name", FRONTS)
def test_pad_rows_change_no_real_row(fronts, name):
    front = fronts[name]
    _, padded = topk.fetch_topk(front.dispatch(3, 4, 16))
    _, full = topk.fetch_topk(front.dispatch(4, 4, 16))
    np.testing.assert_array_equal(padded[:3], full[:3])
    _assert_rows_match(front, front.dispatch(3, 4, 16), 3, 16, None, None)


@pytest.mark.parametrize("name", FRONTS)
def test_a_masked_item_never_surfaces_and_fewer_than_k_come_back_as_fewer(fronts, name):
    front = fronts[name]
    allowed = [5, 17, 211]
    mask = np.zeros((2, N), bool)
    mask[:, allowed] = True
    scores, idx = topk.fetch_topk(front.dispatch(2, 2, 8, mask))
    finite = np.isfinite(scores)
    assert finite.sum(axis=1).tolist() == [3, 3]
    assert (scores[~finite] == -np.inf).all()
    for row in range(2):
        assert sorted(idx[row][finite[row]]) == allowed
        want_s, want_i = topk.host_top_k(front.scores64(2)[row], mask[row], 8)
        assert len(want_i) == 3 and list(idx[row][finite[row]]) == list(want_i)


class TestWireFormat:
    def test_small_indices_survive_packing(self):
        # regression: packing indices as bitcast *float32* made small indices
        # denormal floats, which XLA flush-to-zero turned into index 0. The
        # packed row must be int32 (scores ride as the bitcast instead).
        uf, vf = _tables(50, 8, users=5)
        idx = topk.ServingIndex(uf, vf)
        scores, items = idx.serve(1, 4)
        dense = vf @ uf[1]
        expect = np.argsort(-dense)[:4]
        assert list(items) == list(expect)
        np.testing.assert_allclose(scores, dense[expect], rtol=1e-5)
        _, bi = topk.fetch_topk(idx.serve_batch_async(np.array([1, 3]), 4))
        assert list(bi[0]) == list(expect)

    def test_index_bitcast_exact_for_large_indices(self):
        # indices > 2^24 would lose precision as float casts; the packed
        # path bitcasts, so spot-check determinism on a bigger table
        uf, vf = _tables(50_000, 8, users=4)
        _, items = topk.ServingIndex(uf, vf).serve(1, 5)
        assert list(items) == list(np.argsort(-(vf @ uf[1]))[:5])

    def test_pack_then_unpack_is_the_identity(self):
        import jax.numpy as jnp

        scores = np.array([[3.5, -0.0, -np.inf], [1e-40, 2.0, np.float32(np.pi)]], np.float32)
        idx = np.array([[0, 1, 2], [2**24 + 1, 2**31 - 1, 7]], np.int32)
        packed = np.asarray(topk.pack_batch(jnp.asarray(scores), jnp.asarray(idx)))
        assert packed.shape == (2, 2, 3) and packed.dtype == np.int32
        got_s, got_i = topk.unpack_batch(packed)
        np.testing.assert_array_equal(got_s.view(np.int32), scores.view(np.int32))
        np.testing.assert_array_equal(got_i, idx)


class TestServingIndex:
    def _index(self):
        uf = np.eye(4, 5, dtype=np.float32)  # user u scores item via vf
        vf = np.diag(np.arange(1.0, 6.0)).astype(np.float32)[:, :5]
        return topk.ServingIndex(uf, vf)

    def test_serve_matches_dense_scores(self):
        idx = self._index()
        scores, items = idx.serve(2, 3)  # k 3 in bucket 4: the first 3 kept
        dense = np.asarray(idx.item_factors) @ np.asarray(idx.user_factors)[2]
        order = np.argsort(-dense)[:3]
        assert list(items) == list(order)
        np.testing.assert_allclose(scores, dense[order], rtol=1e-6)

    def test_serve_mask_blacklist(self):
        idx = self._index()
        mask = np.ones(5, bool)
        _, items = idx.serve(2, 1)
        mask[int(items[0])] = False
        _, items2 = idx.serve(2, 1, mask)
        assert int(items2[0]) != int(items[0])

    def test_serve_asks_for_no_more_than_the_catalogue(self):
        scores, items = self._index().serve(1, 9)  # 5 items
        assert len(items) == 5 and sorted(items) == [0, 1, 2, 3, 4]

    @pytest.mark.parametrize("black_list", [(), (3, 250)], ids=["plain", "black_list"])
    def test_a_single_query_is_row_0_of_bucket_1(self, fronts, black_list):
        index = fronts["by_index"].index
        mask = None
        if black_list:
            mask = np.ones(N, bool)
            mask[list(black_list)] = False
        for user in range(4):
            scores, items = index.serve(user, 10, mask)
            bs, bi = topk.fetch_topk(
                index.serve_batch_async(np.array([user], np.int32), 16, mask)
            )
            np.testing.assert_array_equal(items, bi[0, :10])
            np.testing.assert_array_equal(scores, bs[0, :10])
            assert not set(black_list) & set(items.tolist())
        # and of any other bucket
        bs, bi = topk.fetch_topk(index.serve_batch_async(np.arange(4, dtype=np.int32), 16, mask))
        np.testing.assert_array_equal(bi[3, :10], items)
        np.testing.assert_allclose(bs[3, :10], scores, rtol=1e-6)


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
@pytest.mark.parametrize("bucket", [1, 8, 32])
def test_the_item_table_is_stored_in_the_type_the_product_multiplies_in(
    monkeypatch, bucket, masked
):
    # the storage rule is read off the platform (item_table_dtype); forced to
    # the chip's answer here, the served answer is that of a float32 index
    # whose operands were rounded to bfloat16 first: one rounding, made once
    import jax.numpy as jnp

    # at the cells' width: over 128 terms the roundings average out as they do
    # on the chip (2^-9.3 of Σ|u·v| at worst there: benchmark/reference.py)
    f = 128
    users, items = _tables(f=f, seed=5)
    uidx = np.random.default_rng(6).integers(0, USERS, bucket).astype(np.int32)
    mask = _mask("n", bucket) if masked else None
    k = 16

    def rounded(x):
        return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))

    plain = topk.ServingIndex(users, items)  # off the chip, unforced
    assert plain.item_factors.dtype == jnp.float32
    twin = topk.ServingIndex(rounded(users), rounded(items))
    monkeypatch.setattr(topk, "item_table_dtype", lambda: jnp.bfloat16)
    forced = topk.ServingIndex(users, items)
    assert forced.item_factors.dtype == jnp.bfloat16
    assert forced.user_factors.dtype == jnp.float32
    assert not [
        name
        for name, held in vars(forced).items()
        if getattr(held, "shape", None) == items.shape and held.dtype == jnp.float32
    ]
    assert topk.table_bytes() == {"item": N * f * 2, "user": USERS * f * 4}

    got_s, got_i = topk.fetch_topk(forced.serve_batch_async(uidx, k, mask))
    want_s, want_i = topk.fetch_topk(twin.serve_batch_async(uidx, k, mask))
    assert got_s.dtype == np.float32 and got_s.shape == (bucket, k)
    np.testing.assert_array_equal(got_i, want_i)
    u64, v64 = users.astype(np.float64)[uidx], items.astype(np.float64)
    at = lambda dense, idx: np.take_along_axis(dense, idx, axis=1)
    magnitude = at(np.abs(u64) @ np.abs(v64).T, got_i)  # Σ|u·v| of each answer
    assert np.all(np.abs(got_s - want_s) <= 1e-6 * magnitude)
    exact = at(u64 @ v64.T, got_i)
    for served in (got_s, want_s):
        assert np.all(np.abs(served - exact) <= 2.0**-8 * magnitude)
    if mask is not None:
        assert mask[got_i].all()


def test_top_k_by_vector_and_mask():
    vf = np.diag(np.arange(1.0, 6.0)).astype(np.float32)  # 5 items, rank 5
    user = np.ones((1, 5), np.float32)
    _, idx = topk.fetch_topk(topk.dot_top_k_async(topk.upload(vf), user, None, 3))
    assert list(idx[0]) == [4, 3, 2]
    mask = np.ones(5, bool)
    mask[4] = False  # blacklist best item
    _, idx = topk.fetch_topk(topk.dot_top_k_async(topk.upload(vf), user, mask, 3))
    assert list(idx[0]) == [3, 2, 1]


@pytest.fixture(scope="module")
def compiles():
    """Every backend compile of this process from here on, as a list."""
    from jax import monitoring

    seen = []

    def listener(event, duration_secs, **kw):
        if event.endswith("/backend_compile_duration"):
            seen.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    return seen


@pytest.mark.parametrize("name,n", [("by_index", 41), ("by_vector", 43), ("gather_sum", 47)])
def test_a_warmed_buckets_first_batch_compiles_nothing(compiles, name, n):
    # the staging copy in upload() is a program of its own for every bucket
    # shape: a warmup has to take the serving path's upload, or a bucket's
    # first batch compiles (or loads) it at serve time. Shapes no other test
    # of this file serves: nothing is compiled yet
    front = Front(name, n=n, f=6, seed=n)
    mask_for = {
        "by_index": lambda b: None,  # the index's own all-true [n]
        "by_vector": lambda b: np.ones((b, n), bool),
        "gather_sum": lambda b: np.ones((b, n), bool),
    }[name]
    before = len(compiles)
    if name == "by_index":
        front.index.warmup_buckets(3, 13)  # buckets 1, 2, 4, 8, 16; k bucket 4
    else:
        topk.warmup_pow2_buckets(13, lambda b: front.dispatch(min(b, 8), b, 4, mask_for(b)))
    warmed = len(compiles)
    assert warmed > before
    for rows in (1, 2, 3, 5, 8):
        bucket = topk.next_pow2(rows)
        np.asarray(front.dispatch(rows, bucket, 4, mask_for(bucket)))
    np.asarray(front.dispatch(8, 16, 4, mask_for(16)))
    if name == "by_index":
        front.index.serve(2, 3)  # a single query is bucket 1's
        front.index.serve(2, 3, np.arange(n) != 5)  # and so is a black-listed one
    assert len(compiles) == warmed


@pytest.mark.parametrize("name", FRONTS)
def test_a_staging_buffer_overwritten_after_dispatch_changes_nothing(name):
    # upload() copies: jnp.asarray would alias the host buffer on the CPU
    # backend, and the next batch's assembly would reach into this one
    users, items = _tables(64, 6, users=12, seed=3)
    table = topk.upload(items)
    mask = np.ones((8, 64), bool)
    mask[:, ::3] = False
    staged = {
        "by_index": [np.arange(8, dtype=np.int32)],
        "by_vector": [users[:8].copy()],
        "gather_sum": [
            np.arange(16, dtype=np.int32).reshape(8, 2),
            np.ones((8, 2), np.float32),
        ],
    }[name]

    def dispatch():
        if name == "by_index":
            return topk.ServingIndex(users, items).serve_batch_async(staged[0], 4, mask[0])
        if name == "by_vector":
            return topk.dot_top_k_async(table, staged[0], mask, 4)
        return topk.gather_sum_top_k_async(table, staged[0], staged[1], mask, 4)

    want_s, want_i = topk.fetch_topk(dispatch())
    handle = dispatch()
    for buf in staged:
        buf[...] = 0  # the next batch's assembly, mid-flight
    mask[...] = True
    got_s, got_i = topk.fetch_topk(handle)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_s, want_s)


def _imports(path: pathlib.Path) -> set[str]:
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{alias.name}" for alias in node.names)
    return names


@pytest.mark.parametrize("module,other", [("topk", "als"), ("als", "topk")])
def test_the_ending_and_the_trainer_do_not_import_each_other(module, other):
    reached = _imports(_OPS / f"{module}.py")
    assert not [name for name in reached if name.endswith(f"ops.{other}")], reached


def _jitted_functions(path: pathlib.Path) -> list[str]:
    tree = ast.parse(path.read_text())
    decorated = [
        node.name
        for node in ast.walk(tree)
        if isinstance(node, ast.FunctionDef)
        and any("jax.jit" in ast.unparse(d) for d in node.decorator_list)
    ]
    called = [
        node for node in ast.walk(tree)
        if isinstance(node, ast.Call) and ast.unparse(node.func) in ("jax.jit", "jit")
    ]
    assert not called, "a program built by calling jax.jit is one this test cannot name"
    return decorated


def test_the_module_has_three_programs_and_the_trainer_none_that_serves():
    assert _jitted_functions(_OPS / "topk.py") == [fn.__name__ for fn in topk.PROGRAMS]
    assert [fn.__name__ for fn in topk.PROGRAMS] == [
        "_serve_by_index_batch", "_dot_top_k", "_gather_sum_top_k",
    ]
    trainer = (_OPS / "als.py").read_text()
    assert "top_k" not in trainer and "ServingIndex" not in trainer


def test_costmodel_prices_exactly_the_modules_programs():
    from predictionio_tpu.obs import costmodel

    kernels, batch = costmodel.topk_costs(n=256, f=8, b=4, q=2, k=4)
    assert [k["kernel"] for k in kernels] == [
        fn.__name__.lstrip("_") for fn in topk.PROGRAMS
    ]
    assert batch == 4
    assert all(k["flops"] > 0 and k["bytesAccessed"] > 0 for k in kernels)
    # priced against the chip the repository is measured on
    assert costmodel.DEFAULT_DEVICE == "tpu-v5e"
    assert costmodel.DEVICE_SPECS["tpu-v5e"].peak_bytes_per_s == 0.82e12
