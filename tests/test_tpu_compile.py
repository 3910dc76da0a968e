"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described.  Each test lowers a kernel at the
size ``chip_smoke.py`` runs it with ``interpret=False`` and asserts the
compiled program holds the kernel (``tpu_custom_call``), so a kernel the
TPU lowering refuses — an unsupported primitive, a block shape off the
(8, 128) tiling, too much scoped VMEM — fails here instead of on the chip.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and several test workers
import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import bitpack
from repro.core import zfp as zfp_core
from repro.kernels import kvc_attention, lorenzo3d, sz_fused, zfp3d, zfp_fused

SZ_N = 256  # chip_smoke.py's SZ box
ZFP_N = 512  # chip_smoke.py's ZFP field (the paper's Nyx grid)
RATE = 8
# starcoder2-3b decode: 4 slots, 24 heads (GQA repeated), head_dim 128
KVC = dict(b=4, h=24, d=128, s=128)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_sz_fused_encode(one_chip):
    _assert_kernel(lambda x, e: sz_fused.fused_compress(x, e, interpret=False),
                   _sds(one_chip, (SZ_N,) * 3, jnp.float32),
                   _sds(one_chip, (), jnp.float32))


def test_sz_fused_decode(one_chip):
    n = SZ_N ** 3
    packed = bitpack.PackedCodes(_sds(one_chip, (n + 2,), jnp.uint32),
                                 _sds(one_chip, (n // bitpack.BLOCK,), jnp.uint8),
                                 _sds(one_chip, (), jnp.int32), n)
    _assert_kernel(lambda p, e: sz_fused.fused_decompress(p, (SZ_N,) * 3, e,
                                                          interpret=False),
                   packed, _sds(one_chip, (), jnp.float32))


def test_sz_fused_encode_batched(one_chip):
    _assert_kernel(lambda x, e: sz_fused.fused_compress_batched(x, e, interpret=False),
                   _sds(one_chip, (2,) + (SZ_N,) * 3, jnp.float32),
                   _sds(one_chip, (2,), jnp.float32))


def test_sz_fused_decode_batched(one_chip):
    n = SZ_N ** 3
    _assert_kernel(
        lambda a, w, e: sz_fused.fused_decompress_batched(a, w, (SZ_N,) * 3, e,
                                                          interpret=False),
        _sds(one_chip, (2 * (n + 2),), jnp.uint32),
        _sds(one_chip, (2, n // bitpack.BLOCK), jnp.uint8),
        _sds(one_chip, (2,), jnp.float32))


@pytest.mark.parametrize("kernel", ["quantize", "reconstruct"])
def test_lorenzo3d(one_chip, kernel):
    fn = {"quantize": lorenzo3d.lorenzo3d_quantize,
          "reconstruct": lorenzo3d.lorenzo3d_reconstruct}[kernel]
    dtype = jnp.float32 if kernel == "quantize" else jnp.int32
    _assert_kernel(lambda x, e: fn(x, e, interpret=False),
                   _sds(one_chip, (SZ_N,) * 3, dtype), _sds(one_chip, (), jnp.float32))


def test_zfp_fused_encode(one_chip):
    nb = zfp_core.n_blocks_for((ZFP_N,) * 3)
    _assert_kernel(lambda b: zfp_fused.fused_compress_cm(b, RATE, interpret=False),
                   _sds(one_chip, (64, nb), jnp.float32))


def test_zfp_fused_decode(one_chip):
    nb = zfp_core.n_blocks_for((ZFP_N,) * 3)
    wpb = zfp_core.payload_words(RATE)
    _assert_kernel(
        lambda w, e, g: zfp_fused.fused_decompress_cm(w, e, g, RATE, interpret=False),
        _sds(one_chip, (wpb, nb), jnp.uint32), _sds(one_chip, (1, nb), jnp.int32),
        _sds(one_chip, (zfp_core.N_GROUPS, nb), jnp.int32))


def test_zfp3d_transform(one_chip):
    nb = zfp_core.n_blocks_for((ZFP_N,) * 3)
    _assert_kernel(lambda b: zfp3d.zfp3d_transform_cm(b, interpret=False),
                   _sds(one_chip, (64, nb), jnp.float32))


def test_kvc_decode_attention(one_chip):
    b, h, d, s = KVC["b"], KVC["h"], KVC["d"], KVC["s"]
    _assert_kernel(
        lambda q, kc, ks, vc, vs, i: kvc_attention.kvc_decode_attention(
            q, kc, ks, vc, vs, i, interpret=False),
        _sds(one_chip, (b, h, d), jnp.bfloat16),
        _sds(one_chip, (b, s, h, d), jnp.int8), _sds(one_chip, (b, s, h), jnp.float32),
        _sds(one_chip, (b, s, h, d), jnp.int8), _sds(one_chip, (b, s, h), jnp.float32),
        _sds(one_chip, (b,), jnp.int32))
