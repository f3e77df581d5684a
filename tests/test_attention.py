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


def grouped_qkv(B, H, Hkv, Lq, Lk, D=8, seed=0):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.normal(size=(B, H, Lq, D)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, Hkv, Lk, D)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, Hkv, Lk, D)), jnp.float32),
    )


def cached_ids(Lq, Lk, sessions, seed=0):
    """Queries of ``sessions`` sessions, ``Lq / sessions`` each in order (the
    last session absent: -1), against keys laid out as a batch's cache is:
    each session's keys a contiguous run from a multiple of 16, in the
    sessions' order, padding (-1) between them and behind."""
    rng = np.random.default_rng(seed)
    of_q = np.repeat(np.arange(sessions), Lq // sessions).astype(np.int32)
    of_q[-(Lq // sessions) :] = -1
    lengths = rng.integers(1, Lk // sessions - 16, sessions - 1)
    return of_q[None], segments(Lk, lengths, 16)[None]


class TestGroupedBlockCausalAndCachedKeys:
    """What a block-diffusion backbone asks of the kernels (``models/sequential``'s
    ``sdar``): grouped queries, a block-causal mask, and a batch's block
    positions against its cached keys (queries of one length, keys of
    another, ids given apart)."""

    def test_the_reference_reads_head_h_from_key_value_head_h_over_the_group(self):
        q, k, v = grouped_qkv(1, 8, 2, 32, 32, seed=1)
        got = np.asarray(attention_reference(q, k, v, causal=True, block=4))
        for h in range(8):
            alone = attention_reference(
                q[:, h : h + 1], k[:, h // 4 : h // 4 + 1], v[:, h // 4 : h // 4 + 1], causal=True, block=4
            )
            np.testing.assert_allclose(got[:, h], np.asarray(alone)[:, 0], atol=1e-6)
        # planted: h % Hkv in place of h // group reads another head
        wrong = attention_reference(q[:, 1:2], k[:, 1:2], v[:, 1:2], causal=True, block=4)
        assert np.abs(got[:, 1] - np.asarray(wrong)[:, 0]).max() > 0.1

    def test_block_causal_is_two_way_inside_a_block_and_causal_across(self):
        q, k, v = grouped_qkv(1, 2, 2, 16, 16, seed=2)
        got = np.asarray(attention_reference(q, k, v, causal=True, block=4))
        # position 5 (block 1) sees keys 0..7 and no more
        for at, last in ((5, 8), (0, 4), (15, 16), (8, 12)):
            alone = attention_reference(q[:, :, at : at + 1], k[:, :, :last], v[:, :, :last])
            np.testing.assert_allclose(got[:, :, at], np.asarray(alone)[:, :, 0], atol=1e-6)
        token_causal = np.asarray(attention_reference(q, k, v, causal=True))
        assert np.abs(got - token_causal).max() > 0.05  # planted: a token-causal mask inside the block
        np.testing.assert_allclose(got[:, :, 3::4], token_causal[:, :, 3::4], atol=1e-6)  # a block's last row

    @pytest.mark.parametrize("path", ["off the chip", "single block", "flash 256", "flash 512", "routed"])
    def test_grouped_block_causal_packed_rows_match_the_reference(self, path):
        from predictionio_tpu.ops.attention import _flash_attention_pallas, _fused_attention_pallas

        L = 256 if path == "single block" else 1024
        q, k, v = grouped_qkv(2, 8, 2, L, L, seed=3)
        ids = jnp.asarray(np.stack([
            segments(L, (37, 64, 1, 50) if L == 256 else (300, 64, 1, 200, 130), 64),
            segments(L, (130, 60) if L == 256 else (513, 255), 64),
        ]))
        want = attention_reference(q, k, v, causal=True, segment=ids, block=4)
        if path == "off the chip":
            got, atol = fused_attention(q, k, v, causal=True, segment=ids, block=4), 1e-5
        elif path == "routed":
            got, atol = fused_attention(q, k, v, causal=True, segment=ids, block=4, force_pallas=True), 2e-2
        elif path == "single block":
            got, atol = _fused_attention_pallas(q, k, v, True, interpret=True, segment=ids, block=4), 2e-2
        else:
            tile = int(path.split()[1])
            got = _flash_attention_pallas(
                q, k, v, True, interpret=True, block_q=tile, block_k=tile, segment=ids, block=4
            )
            atol = 2e-2
        assert not bool(jnp.isnan(got).any())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)
        # a key of the NEXT block seen (the block's index off by one) is another answer
        leaked = attention_reference(q, k, v, causal=True, segment=ids, block=8)
        assert np.abs(np.asarray(leaked) - np.asarray(want)).max() > 0.05

    @pytest.mark.parametrize("path", ["off the chip", "single block", "flash 128x256", "flash 256x512", "routed"])
    def test_block_positions_against_cached_keys_match_the_reference(self, path):
        from predictionio_tpu.ops.attention import _flash_attention_pallas, _fused_attention_pallas

        Lq, Lk = (128, 512) if path == "single block" else (256, 2048)
        q, k, v = grouped_qkv(1, 8, 2, Lq, Lk, seed=4)
        of_q, of_k = cached_ids(Lq, Lk, sessions=Lq // 16, seed=4)
        pair = (jnp.asarray(of_q), jnp.asarray(of_k))
        want = np.asarray(attention_reference(q, k, v, segment=pair))
        if path == "off the chip":
            got, atol = fused_attention(q, k, v, segment=pair), 1e-5
        elif path == "routed":
            got, atol = fused_attention(q, k, v, segment=pair, force_pallas=True), 2e-2
        elif path == "single block":
            got, atol = _fused_attention_pallas(q, k, v, False, interpret=True, segment=pair), 2e-2
        else:
            bq, bk = map(int, path.split()[1].split("x"))
            got = _flash_attention_pallas(
                q, k, v, False, interpret=True, block_q=bq, block_k=bk, segment=pair
            )
            atol = 2e-2
        got = np.asarray(got)
        assert not np.isnan(got).any()
        np.testing.assert_allclose(got, want, atol=atol)
        # a session's queries equal the same queries against its own keys alone
        own_q, own_k = np.flatnonzero(of_q[0] == 1), np.flatnonzero(of_k[0] == 1)
        alone = attention_reference(q[:, :, own_q], k[:, :, own_k], v[:, :, own_k])
        np.testing.assert_allclose(want[:, :, own_q], np.asarray(alone), atol=1e-5)
        # absent queries (-1) come out as 0; a key leaked from the session in front moves the rest
        assert not want[:, :, of_q[0] < 0].any()
        shifted = (pair[0], jnp.where(pair[1] >= 0, jnp.maximum(pair[1] - 1, 0), -1))
        assert np.abs(np.asarray(attention_reference(q, k, v, segment=shifted)) - want).max() > 0.05

    def test_blocks_of_keys_no_query_carries_are_neither_needed_nor_fetched(self):
        from predictionio_tpu.ops.attention import _needed_blocks

        of_q = np.repeat(np.arange(8), 4).astype(np.int32)[None]  # two tiles of 16: sessions 0-3, 4-7
        of_k = np.full((1, 512), -1, np.int32)
        for session, start in enumerate((0, 64, 128, 130, 256, 300, 320, 448)):
            of_k[0, start : start + 2] = session
        needed, fetch = _needed_blocks((jnp.asarray(of_q), jnp.asarray(of_k)), 16, 64)
        # keys in tiles of 64: sessions 0 | 1 | 2, 3 | - | 4, 5 | 6 | - | 7
        assert np.asarray(needed)[0].tolist() == [[1, 1, 1, 0, 0, 0, 0, 0], [0, 0, 0, 0, 1, 1, 0, 1]]
        # a step that computes nothing holds the nearest needed tile before it (the first, in front)
        assert np.asarray(fetch)[0].tolist() == [[0, 1, 2, 2, 2, 2, 2, 2], [4, 4, 4, 4, 4, 5, 5, 7]]

    def test_what_carries_no_order_refuses_causal_and_block_needs_it(self):
        q, k, v = grouped_qkv(1, 4, 2, 128, 256)
        pair = (jnp.zeros((1, 128), jnp.int32), jnp.zeros((1, 256), jnp.int32))
        with pytest.raises(ValueError, match="no order"):
            fused_attention(q, k, v, causal=True, segment=pair)
        with pytest.raises(ValueError, match="block"):
            fused_attention(q, k[:, :, :128], v[:, :, :128], block=4)
        with pytest.raises(ValueError, match="key/value heads"):
            fused_attention(q[:, :3], k[:, :, :128], v[:, :, :128])

    @pytest.mark.parametrize("path", ["reference", "single block", "flash"])
    def test_without_the_new_arguments_a_path_traces_to_what_it_did(self, path):
        """No grouped head, no ``block``, one array of ids: the index maps
        and the masks are the ones there were (the jaxpr of the call, kernel
        body and all, is the same text as with the arguments left out)."""
        L = 1024 if path == "flash" else 64
        q, k, v = qkv(B=1, H=2, L=L, D=8, seed=7)
        force = path != "reference"
        ids = jnp.asarray(segments(L, (L // 2, L // 4), 16)[None])
        for segment in (None, ids):
            plain = jax.make_jaxpr(
                lambda *a: fused_attention(*a, causal=True, force_pallas=force, segment=segment)
            )(q, k, v)
            named = jax.make_jaxpr(
                lambda *a: fused_attention(*a, causal=True, force_pallas=force, segment=segment, block=None)
            )(q, k, v)
            assert str(plain) == str(named)
            blocked = jax.make_jaxpr(
                lambda *a: fused_attention(*a, causal=True, force_pallas=force, segment=segment, block=4)
            )(q, k, v)
            assert str(blocked) != str(plain)


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
