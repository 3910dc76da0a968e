"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp oracles in kernels/ref.py (kernels run in interpret mode on CPU —
TPU is the target), plus error-bound property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401

from repro.core import zfp as zfp_core
from repro.kernels import ops, ref
from repro.kernels.lorenzo3d import TILE, guarded_eb, lorenzo3d_quantize, lorenzo3d_reconstruct
from repro.kernels.zfp3d import BLOCKS_PER_TILE, zfp3d_transform


def _field(shape, seed=0, scale=100.0):
    rng = np.random.default_rng(seed)
    f = rng.normal(size=shape).astype(np.float32)
    for ax in range(len(shape)):
        f = np.cumsum(f, axis=ax)
    return (f * scale / max(np.abs(f).max(), 1e-9)).astype(np.float32)


class TestLorenzo3D:
    @pytest.mark.parametrize("shape", [(8, 64, 128), (16, 64, 128), (8, 128, 256), (24, 192, 128)])
    @pytest.mark.parametrize("eb", [1e-1, 1e-3])
    def test_matches_ref(self, shape, eb):
        x = jnp.asarray(_field(shape, seed=sum(shape)))
        got = lorenzo3d_quantize(x, guarded_eb(x, eb))
        want = ref.lorenzo3d_quantize_ref(x, eb)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("eb", [1e-1, 1e-2])
    def test_roundtrip_error_bound(self, eb):
        x = jnp.asarray(_field((8, 64, 128), seed=3))
        ebi = guarded_eb(x, eb)
        d = lorenzo3d_quantize(x, ebi)
        xr = lorenzo3d_reconstruct(d, ebi)
        assert np.abs(np.asarray(xr) - np.asarray(x)).max() <= eb * (1 + 1e-5)

    def test_reconstruct_matches_ref(self):
        x = jnp.asarray(_field((8, 64, 128), seed=4))
        ebi = guarded_eb(x, 1e-2)
        d = lorenzo3d_quantize(x, ebi)
        got = lorenzo3d_reconstruct(d, ebi)
        want = ref.lorenzo3d_reconstruct_ref(d, ebi)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)

    @pytest.mark.parametrize("axis", [0, 1, 2])
    def test_prefix_sum_int32_edges(self, axis):
        """The in-kernel log-step prefix sum == jnp.cumsum bit for bit on a
        full tile, including int32 wraparound: values at and near +-2^31
        and spikes on the tile's first and last planes/rows/lanes."""
        from repro.kernels.lorenzo3d import prefix_sum

        rng = np.random.default_rng(axis)
        edge = np.array([2**31 - 1, -(2**31), 2**31 - 2, -(2**31) + 1, 1, -1, 0],
                        np.int64)
        d = rng.choice(edge, size=TILE).astype(np.int32)
        idx = [slice(None)] * 3
        for pos in (0, TILE[axis] - 1):
            idx[axis] = pos
            d[tuple(idx)] = np.int32(2**31 - 1)
        got = prefix_sum(jnp.asarray(d), axis)
        want = jnp.cumsum(jnp.asarray(d), axis=axis, dtype=jnp.int32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_reconstruct_matches_ref_at_int32_extremes(self):
        """The reconstruct kernel's 3-fold prefix sum wraps exactly like the
        reference's cumsum, tile edges included."""
        rng = np.random.default_rng(12)
        shape = (16, 64, 256)  # 2 x 1 x 2 tiles: seams on two axes
        d = rng.integers(-(2**31), 2**31, size=shape, dtype=np.int64).astype(np.int32)
        d[7:9], d[:, 63:65] = 2**31 - 1, -(2**31)
        d[..., 127:129] = 2**31 - 1
        ebi = jnp.float32(0.5)
        got = lorenzo3d_reconstruct(jnp.asarray(d), ebi)
        want = ref.lorenzo3d_reconstruct_ref(jnp.asarray(d), ebi)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_ops_end_to_end_with_padding(self):
        x = jnp.asarray(_field((10, 70, 130), seed=5))  # non-tile-multiple
        packed, padded, ebi = ops.sz_compress_kernel(x, 1e-2)
        xr = ops.sz_decompress_kernel(packed, padded, x.shape, ebi)
        assert xr.shape == x.shape
        assert np.abs(np.asarray(xr) - np.asarray(x)).max() <= 1e-2 * (1 + 1e-5)

    def test_kernel_agrees_with_core_blocked_semantics(self):
        """Tile-blocked kernel == core SZ with equivalent per-tile reset:
        residuals are identical inside any single tile."""
        x = jnp.asarray(_field(TILE, seed=6))
        ebi = guarded_eb(x, 1e-2)
        got = np.asarray(lorenzo3d_quantize(x, ebi))
        from repro.core import sz

        q = np.asarray(jnp.round(x * (1.0 / (2.0 * ebi))).astype(jnp.int32))
        want = np.asarray(sz.lorenzo_residual(jnp.asarray(q)))
        np.testing.assert_array_equal(got, want)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 1000), st.floats(min_value=1e-3, max_value=1.0))
    def test_property_bound(self, seed, eb):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=TILE).astype(np.float32) * 50)
        ebi = guarded_eb(x, eb)
        xr = lorenzo3d_reconstruct(lorenzo3d_quantize(x, ebi), ebi)
        assert np.abs(np.asarray(xr) - np.asarray(x)).max() <= eb * (1 + 1e-5)


class TestSZFused:
    """Single-pass fused encode/decode vs the XLA fallback (interpret mode)."""

    @pytest.mark.parametrize("eb", [200.0, 20.0])
    def test_byte_identical_to_fallback_on_nyx(self, eb):
        """Acceptance: fused Pallas path == fallback path, byte for byte,
        on a 64^3 Nyx field."""
        from repro.data import cosmo

        x = jnp.asarray(cosmo.nyx_fields(n=64)["baryon_density"])
        pf, pad_f, eb_f = ops.sz_compress_kernel(x, eb, path="fused")
        px, pad_x, eb_x = ops.sz_compress_kernel(x, eb, path="xla")
        assert pad_f == pad_x and pf.n == px.n
        np.testing.assert_array_equal(np.asarray(eb_f), np.asarray(eb_x))
        np.testing.assert_array_equal(np.asarray(pf.words), np.asarray(px.words))
        np.testing.assert_array_equal(np.asarray(pf.widths), np.asarray(px.widths))
        assert int(pf.total_bits) == int(px.total_bits)

    def test_cross_decode_and_bound(self):
        """Either decoder reads either stream; error bound holds."""
        x = jnp.asarray(_field((10, 70, 130), seed=11))  # non-tile-multiple
        eb = 1e-2
        packed, padded, ebi = ops.sz_compress_kernel(x, eb, path="fused")
        for path in ("fused", "xla"):
            xr = ops.sz_decompress_kernel(packed, padded, x.shape, ebi, path=path)
            assert xr.shape == x.shape
            assert np.abs(np.asarray(xr) - np.asarray(x)).max() <= eb * (1 + 1e-5)

    def test_pack_unpack_blocks_adversarial(self):
        """In-kernel block packer round-trips across the width range."""
        from repro.core import bitpack
        from repro.kernels import sz_fused

        rng = np.random.default_rng(5)
        nb = 40
        codes = np.zeros((nb, bitpack.BLOCK), np.uint32)
        for b in range(nb):
            w = b % 33  # widths 0..32
            if w:
                codes[b] = rng.integers(0, 2**w, size=bitpack.BLOCK, dtype=np.uint64)
                codes[b, 0] = 2**w - 1  # pin the block width
        u = jnp.asarray(codes, jnp.uint32)
        width = jnp.max(bitpack.bitlength(u), axis=1)
        words = sz_fused._pack_blocks(u, width)
        back = sz_fused._unpack_blocks(words, width)
        np.testing.assert_array_equal(np.asarray(back), codes)
        # payload words beyond 2*w must be zero (the stream gather skips them)
        j = np.arange(sz_fused.WORDS_PER_BLOCK)[None, :]
        np.testing.assert_array_equal(
            np.asarray(words) * (j >= 2 * np.asarray(width)[:, None]), 0
        )

    def test_tile_major_flatten_inverse(self):
        from repro.kernels import sz_fused

        a = jnp.arange(16 * 128 * 256, dtype=jnp.int32).reshape(16, 128, 256)
        flat = sz_fused.tile_major_flatten(a)
        np.testing.assert_array_equal(
            np.asarray(sz_fused.tile_major_unflatten(flat, a.shape)), np.asarray(a)
        )


class TestZFP3D:
    @pytest.mark.parametrize("nb", [256, 512, 1024])
    @pytest.mark.parametrize("scale", [1.0, 1e5, 1e-5])
    def test_matches_ref(self, nb, scale):
        rng = np.random.default_rng(nb)
        blocks = jnp.asarray((rng.normal(size=(nb, 4, 4, 4)) * scale).astype(np.float32))
        gu, ge, gt = zfp3d_transform(blocks)
        wu, we, wt = ref.zfp3d_transform_ref(blocks)
        np.testing.assert_array_equal(np.asarray(gu), np.asarray(wu))
        np.testing.assert_array_equal(np.asarray(ge), np.asarray(we))
        np.testing.assert_array_equal(np.asarray(gt), np.asarray(wt))

    def test_zero_blocks(self):
        blocks = jnp.zeros((256, 4, 4, 4), jnp.float32)
        u, e, t = zfp3d_transform(blocks)
        assert (np.asarray(e) == 0).all() and (np.asarray(t) == 0).all()

    def test_exponent_bit_trick_vs_frexp(self):
        """The IEEE (bits>>23)&0xff exponent == frexp for normal floats."""
        vals = jnp.asarray([1e-30, 1e-5, 0.5, 1.0, 1.5, 2.0, 3.99, 1e20], jnp.float32)
        bits = jax.lax.bitcast_convert_type(vals, jnp.uint32)
        e_trick = ((bits >> 23) & 0xFF).astype(jnp.int32) - 126
        _, e_frexp = jnp.frexp(vals)
        np.testing.assert_array_equal(np.asarray(e_trick), np.asarray(e_frexp))

    def test_ops_matches_core_block_transform(self):
        """Kernel path == repro.core.zfp.block_transform on a real field."""
        x = jnp.asarray(_field((32, 32, 32), seed=7, scale=1e4))
        gu, ge, gt = ops.zfp_transform_kernel(x)
        wu, we, wt = zfp_core.block_transform(x)
        np.testing.assert_array_equal(np.asarray(gu), np.asarray(wu))
        np.testing.assert_array_equal(np.asarray(ge), np.asarray(we.astype(np.uint8)))
        np.testing.assert_array_equal(np.asarray(gt), np.asarray(wt))


class TestKVCAttention:
    @pytest.mark.parametrize("b,s,h,d", [(1, 128, 4, 64), (2, 256, 8, 64), (2, 384, 2, 128)])
    def test_matches_ref(self, b, s, h, d):
        rng = np.random.default_rng(b * s)
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
        kc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        vc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        ks = jnp.asarray(rng.uniform(1e-3, 2e-2, size=(b, s, h)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(1e-3, 2e-2, size=(b, s, h)).astype(np.float32))
        idx = jnp.int32(s - 5)
        got = ops.kvc_attention(q, kc, ks, vc, vs, idx)
        want = ref.kvc_decode_attention_ref(q, kc, ks, vc, vs, idx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_mask_respects_index(self):
        """Tokens beyond `index` must not affect the output."""
        rng = np.random.default_rng(0)
        b, s, h, d = 1, 256, 4, 64
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
        kc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        vc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        ks = jnp.asarray(rng.uniform(1e-3, 1e-2, size=(b, s, h)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(1e-3, 1e-2, size=(b, s, h)).astype(np.float32))
        out1 = ops.kvc_attention(q, kc, ks, vc, vs, jnp.int32(100))
        kc2 = kc.at[:, 150:].set(99)
        out2 = ops.kvc_attention(q, kc2, ks, vc, vs, jnp.int32(100))
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)

    def test_bf16_query(self):
        rng = np.random.default_rng(1)
        b, s, h, d = 1, 128, 4, 64
        q = jnp.asarray(rng.normal(size=(b, h, d))).astype(jnp.bfloat16)
        kc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        vc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        ks = jnp.asarray(rng.uniform(1e-3, 1e-2, size=(b, s, h)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(1e-3, 1e-2, size=(b, s, h)).astype(np.float32))
        got = ops.kvc_attention(q, kc, ks, vc, vs, jnp.int32(60))
        want = ref.kvc_decode_attention_ref(q, kc, ks, vc, vs, jnp.int32(60))
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                   rtol=0.02, atol=0.02)


class TestKVCAttentionVectorIndex:
    """Per-slot (B,) lengths (continuous batching): each lane masks at its
    OWN position, and lane -1 (free slot) attends over nothing."""

    def test_vector_matches_per_row_scalar(self):
        rng = np.random.default_rng(7)
        b, s, h, d = 4, 256, 4, 64
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
        kc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        vc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        ks = jnp.asarray(rng.uniform(1e-3, 2e-2, size=(b, s, h)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(1e-3, 2e-2, size=(b, s, h)).astype(np.float32))
        lens = jnp.asarray([3, 100, 251, 17], jnp.int32)
        got = ops.kvc_attention(q, kc, ks, vc, vs, lens)
        want_vec = ref.kvc_decode_attention_ref(q, kc, ks, vc, vs, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_vec),
                                   rtol=2e-5, atol=2e-6)
        for i, n in enumerate([3, 100, 251, 17]):  # stitch scalar rows
            row = ref.kvc_decode_attention_ref(
                q[i:i + 1], kc[i:i + 1], ks[i:i + 1], vc[i:i + 1],
                vs[i:i + 1], jnp.int32(n))
            np.testing.assert_allclose(np.asarray(got[i:i + 1]),
                                       np.asarray(row), rtol=2e-5, atol=2e-6)

    def test_dead_lane_ignores_cache(self):
        """index -1: the lane's output must not depend on cache contents."""
        rng = np.random.default_rng(9)
        b, s, h, d = 2, 128, 4, 64
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
        kc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        vc = jnp.asarray(rng.integers(-127, 128, size=(b, s, h, d)).astype(np.int8))
        ks = jnp.asarray(rng.uniform(1e-3, 1e-2, size=(b, s, h)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(1e-3, 1e-2, size=(b, s, h)).astype(np.float32))
        lens = jnp.asarray([-1, 64], jnp.int32)
        out1 = ops.kvc_attention(q, kc, ks, vc, vs, lens)
        out2 = ops.kvc_attention(q, kc.at[0].set(99), ks, vc.at[0].set(-99),
                                 vs, lens)
        np.testing.assert_allclose(np.asarray(out1[1]), np.asarray(out2[1]),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out1[0]), np.asarray(out2[0]),
                                   rtol=1e-6)
