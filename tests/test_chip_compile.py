"""Compile-only checks of the Pallas kernels for one TPU v5e chip.

The TPU compiler is installed without a chip: a described v5e topology
lets `jit(...).lower(...).compile()` raise whatever the chip's compiler
would refuse (block shapes off the (8, 128) tiling, unsupported vector
ops, too much VMEM) at no chip time. Nothing runs; results are checked
by the interpret-mode tests. The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only
the worker given this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.rmsnorm.kernel import rms_norm_pallas
from repro.kernels.rs_gf256.kernel import (LANES, TILE_BUCKETS, _matmul_tile,
                                           column_tiles)

MB = 1024 * 1024
QWEN = get_config("qwen1.5-0.5b")
CHUNK_10MB = 10 * MB                 # a 100 MB object's RS(10+2) chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # else logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                    # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _rs(m, k, width, one_chip):
    return _compile(functools.partial(_matmul_tile, interpret=False),
                    one_chip, ((m, k), jnp.uint8),
                    ((k, width // (4 * LANES), LANES), jnp.uint32))


# RS(10+2): encode (m=p=2) and decode (m=k=10) at every width bucket the
# codec dispatches, plus one whole 10 MB chunk row in a single call
@pytest.mark.parametrize("width", TILE_BUCKETS + (CHUNK_10MB,))
@pytest.mark.parametrize("m", [2, 10], ids=["encode", "decode"])
def test_rs_kernel_rs10_2(m, width, one_chip, no_persistent_cache):
    _rs(m, 10, width, one_chip)


# RS(4+2), the small geometry the tests use
@pytest.mark.parametrize("width", TILE_BUCKETS)
@pytest.mark.parametrize("m", [2, 4], ids=["encode", "decode"])
def test_rs_kernel_rs4_2(m, width, one_chip, no_persistent_cache):
    _rs(m, 4, width, one_chip)


def test_rs_codec_tiles_of_a_10mb_chunk(one_chip, no_persistent_cache):
    """The tiles the codec cuts a 10 MB chunk row into (header byte
    included) are all bucket widths the kernel compiles at."""
    tiles = column_tiles(CHUNK_10MB + 1)
    assert {b for _, _, b in tiles} <= set(TILE_BUCKETS)
    for b in sorted({b for _, _, b in tiles}):
        _rs(10, 10, b, one_chip)


def test_rmsnorm_qwen_width(one_chip, no_persistent_cache):
    d = QWEN.d_model
    _compile(functools.partial(rms_norm_pallas, interpret=False), one_chip,
             ((4096, d), jnp.bfloat16), ((d,), jnp.bfloat16))


def test_paged_attention_qwen_width(one_chip, no_persistent_cache):
    B, P, ps = 8, 8, 256
    H, K, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    pool = ((B, P, ps, K, hd), jnp.bfloat16)
    _compile(functools.partial(paged_decode_attention_pallas,
                               interpret=False), one_chip,
             ((B, H, hd), jnp.bfloat16), pool, pool,
             ((B, P), jnp.int32), ((B,), jnp.int32))
