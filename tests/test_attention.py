"""Ring attention + fused attention tests on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.attention import (
    attention_reference,
    fused_attention,
    ring_attention,
    ring_attention_sharded,
    ulysses_attention,
)
from predictionio_tpu.parallel.mesh import make_mesh


def qkv(B=2, H=2, L=32, D=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, L, D)).astype(np.float32))
    return mk(), mk(), mk()


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        mesh = make_mesh("sp=8")
        q, k, v = qkv()
        expected = attention_reference(q, k, v, causal=causal)
        got = ring_attention_sharded(q, k, v, mesh, axis="sp", causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)

    def test_2d_mesh_with_data_axis(self):
        mesh = make_mesh("data=2,sp=4")
        q, k, v = qkv(L=16)
        expected = attention_reference(q, k, v, causal=True)
        got = ring_attention_sharded(q, k, v, mesh, axis="sp", causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)

    def test_dp_sp_composed(self):
        # batch sharded over `data` AND sequence over `sp` in ONE shard_map
        # (dp x sp): the composition the two-tower context-parallel encoder
        # relies on — without batch_axis, GSPMD must all-gather the batch
        mesh = make_mesh("data=2,sp=4")
        q, k, v = qkv(B=4, L=16)
        expected = attention_reference(q, k, v, causal=True)
        got = ring_attention_sharded(
            q, k, v, mesh, axis="sp", causal=True, batch_axis="data"
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)

    def test_bad_length_rejected(self):
        mesh = make_mesh("sp=8")
        q, k, v = qkv(L=30)  # not divisible by 8
        with pytest.raises(ValueError):
            ring_attention_sharded(q, k, v, mesh, axis="sp")

    def test_long_sequence(self):
        mesh = make_mesh("sp=8")
        q, k, v = qkv(B=1, H=1, L=256, D=16, seed=3)
        expected = attention_reference(q, k, v, causal=True)
        got = ring_attention_sharded(q, k, v, mesh, axis="sp", causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-4)


class TestFusedAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_interpret_matches_reference(self, causal):
        q, k, v = qkv(B=1, H=2, L=16, D=8)
        expected = attention_reference(q, k, v, causal=causal)
        got = fused_attention(q, k, v, causal=causal, force_pallas=True)
        # the kernel multiplies in bf16 (f32 accumulation) — the MXU's
        # native contract; tolerance is bf16 rounding, not f32
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-2)

    def test_cpu_fallback(self):
        q, k, v = qkv(B=1, H=1, L=8, D=4)
        got = fused_attention(q, k, v)
        expected = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-6)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_tiled_kernel_matches_reference(self, causal):
        """L=1024 crosses the single-block VMEM budget, so force_pallas
        routes to the tiled flash kernel (online softmax carried across
        K-block grid steps in scratch) — the path long sequences take on
        real TPU hardware."""
        from predictionio_tpu.ops.attention import _flash_attention_pallas

        q, k, v = qkv(B=1, H=1, L=1024, D=8)
        expected = attention_reference(q, k, v, causal=causal)
        got = _flash_attention_pallas(
            q, k, v, causal=causal, interpret=True, block_q=256, block_k=256
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-2)
        # dispatch routing: force_pallas at this size must take the flash path
        got2 = fused_attention(q, k, v, causal=causal, force_pallas=True)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(expected), atol=2e-2)

    def test_kernel_path_refuses_a_long_ragged_sequence(self):
        """Past the single-block budget the flash kernel needs lengths
        that divide by 256. The kernel path says so instead of quietly
        running the jnp reference; off the chip, where the reference is
        the documented path, the same call still answers."""
        q, k, v = qkv(B=1, H=1, L=1100, D=8)
        with pytest.raises(ValueError, match="multiple of 256"):
            fused_attention(q, k, v, causal=True, force_pallas=True)
        got = fused_attention(q, k, v, causal=True)
        expected = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-6)


def segments(L, lengths, align=16):
    """[L] ids of sessions laid end to end, each from a multiple of
    ``align``; -1 between and behind them."""
    ids, at = np.full(L, -1, np.int32), 0
    for i, n in enumerate(lengths):
        ids[at : at + n] = i
        at += -(-n // align) * align
    assert at <= L
    return ids


class TestSegmentedAttention:
    """Several sequences in one row (``models/sequential``'s packed streams):
    a key is seen only from inside its own segment."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_the_reference_equals_every_segment_alone_and_zeroes_the_padding(self, causal):
        q, k, v = qkv(B=2, H=2, L=64, D=8, seed=5)
        lengths = [(20, 16, 7), (33, 14)]
        ids = np.stack([segments(64, row) for row in lengths])
        got = np.asarray(attention_reference(q, k, v, causal=causal, segment=jnp.asarray(ids)))
        for row in range(2):
            for i in range(len(lengths[row])):
                own = np.flatnonzero(ids[row] == i)
                alone = attention_reference(
                    q[row : row + 1, :, own], k[row : row + 1, :, own], v[row : row + 1, :, own],
                    causal=causal,
                )
                np.testing.assert_allclose(got[row][:, own], np.asarray(alone)[0], atol=1e-6)
            assert not got[row][:, ids[row] < 0].any()

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("kernel", ["single block", "flash 256", "flash 512"])
    def test_both_kernels_interpreted_match_the_reference(self, kernel, causal):
        from predictionio_tpu.ops.attention import (
            _flash_attention_pallas,
            _fused_attention_pallas,
        )

        L = 256 if kernel == "single block" else 1024
        q, k, v = qkv(B=2, H=2, L=L, D=8, seed=6)
        # a segment that ends inside a tile, one of exactly 64, one of one
        # position, one over a tile's edge; the second row ends in padding
        ids = np.stack([
            segments(L, (37, 64, 1, 50) if L == 256 else (300, 64, 1, 200, 130), 64),
            segments(L, (130, 60) if L == 256 else (513, 255), 64),
        ])
        ids = jnp.asarray(ids)
        want = attention_reference(q, k, v, causal=causal, segment=ids)
        if kernel == "single block":
            got = _fused_attention_pallas(q, k, v, causal, interpret=True, segment=ids)
        else:
            block = int(kernel.split()[1])
            got = _flash_attention_pallas(
                q, k, v, causal, interpret=True, block_q=block, block_k=block, segment=ids
            )
        assert not bool(jnp.isnan(got).any())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)
        # the routing: force_pallas takes the same kernels
        routed = fused_attention(q, k, v, causal=causal, force_pallas=True, segment=ids)
        np.testing.assert_allclose(np.asarray(routed), np.asarray(want), atol=2e-2)

    @pytest.mark.parametrize("path", ["reference", "single block", "flash"])
    def test_no_segment_is_what_it_was_and_one_segment_is_the_same(self, path):
        """``segment=None`` adds no operand and no operation to a path (the
        tower's and the context-parallel paths' program), and a row that is
        ONE segment gives the same numbers bit for bit."""
        from predictionio_tpu.ops.attention import _flash_attention_pallas

        L = 1024 if path == "flash" else 64
        q, k, v = qkv(B=1, H=2, L=L, D=8, seed=7)
        force = path != "reference"
        plain = jax.make_jaxpr(lambda *a: fused_attention(*a, causal=True, force_pallas=force))(q, k, v)
        one = jnp.zeros((1, L), jnp.int32)
        if path == "flash":  # at one tile: the dispatcher gives a packed row its own

            def attend(segment):
                return _flash_attention_pallas(
                    q, k, v, True, interpret=True, block_q=256, block_k=256, segment=segment
                )
        else:

            def attend(segment):
                return fused_attention(q, k, v, causal=True, force_pallas=force, segment=segment)

        np.testing.assert_array_equal(np.asarray(attend(one)), np.asarray(attend(None)))
        # and the path without a segment takes q, k and v alone
        assert len(plain.jaxpr.invars) == 3
        if force:
            (call,) = [e for e in plain.jaxpr.eqns if e.primitive.name == "pallas_call"]
            assert len(call.invars) == 3

    @pytest.mark.parametrize("causal", [False, True])
    def test_the_blocked_path_off_the_chip_matches_the_dense_reference(self, causal):
        # what `fused_attention` runs here for a packed row (the kernel's
        # schedule in jax.numpy), in float32 on both sides
        q, k, v = qkv(B=2, H=2, L=512, D=8, seed=8)
        ids = jnp.asarray(np.stack([
            segments(512, (130, 64, 1, 150), 64), segments(512, (300, 17), 64),
        ]))
        want = attention_reference(q, k, v, causal=causal, segment=ids)
        got = fused_attention(q, k, v, causal=causal, segment=ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
        # a length its blocks do not divide takes the dense form
        short = fused_attention(q[:, :, :100], k[:, :, :100], v[:, :, :100], causal=causal, segment=ids[:, :100])
        np.testing.assert_allclose(
            np.asarray(short),
            np.asarray(attention_reference(q[:, :, :100], k[:, :, :100], v[:, :, :100], causal=causal, segment=ids[:, :100])),
            atol=1e-6,
        )

    def test_a_key_block_before_every_query_of_a_block_is_skipped(self):
        from predictionio_tpu.ops.attention import _first_keys

        ids = np.stack([segments(1024, (300, 64, 1, 200, 130), 64), segments(1024, (513, 100), 64)])
        first = np.asarray(_first_keys(jnp.asarray(ids), 256)).reshape(2, 4)
        # row 0, segments from 0, 320, 384, 448 and 704: the first block of 256
        # queries sees back to 0, the second too (300 ends inside it), the third
        # to 448 (it begins inside that segment), the fourth to 704; row 1, from 0
        # and 576: 0, 0, 0 (513 reaches into the third block), then padding alone
        assert first.tolist() == [[0, 0, 448, 704], [0, 0, 0, 1024]]


class TestUlyssesAttention:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses scheme) must match
    the dense reference exactly — full sequence is reconstructed per head."""

    def test_matches_reference(self):
        q, k, v = qkv(H=8, D=16)
        out = ulysses_attention(q, k, v, make_mesh("sp=8"))
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_causal_matches_reference(self):
        q, k, v = qkv(H=8, D=16, seed=1)
        out = ulysses_attention(q, k, v, make_mesh("sp=8"), causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_matches_ring(self):
        q, k, v = qkv(H=8, D=16, seed=2)
        mesh = make_mesh("sp=8")
        np.testing.assert_allclose(
            np.asarray(ulysses_attention(q, k, v, mesh, causal=True)),
            np.asarray(ring_attention(q, k, v, mesh, causal=True)),
            atol=2e-5,
        )

    def test_head_divisibility_enforced(self):
        q, k, v = qkv(H=6)  # 6 heads on 8 devices
        with pytest.raises(ValueError, match="head count"):
            ulysses_attention(q, k, v, make_mesh("sp=8"))

    def test_dp_sp_composed(self):
        # dp x sp on one 2-D mesh (see TestRingAttention.test_dp_sp_composed)
        mesh = make_mesh("data=2,sp=4")
        q, k, v = qkv(B=4, H=4, L=16, D=16, seed=3)
        out = ulysses_attention(
            q, k, v, mesh, axis="sp", causal=True, batch_axis="data"
        )
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
