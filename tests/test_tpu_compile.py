"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered for a described (not attached) v5e
and compiled by the TPU compiler installed here, which refuses what the
chip would refuse (unaligned blocks, too much VMEM, unsupported
primitives) — all of which interpret mode hides.  The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul, tile_legal, vmem_bytes
from repro.kernels.ssd import ssd

#: (m, n, k): 2048 tokens through mamba2-2.7b's 2560 x 10240 projection
MM = (2048, 10240, 2560)
_SIDES = (128, 256, 512, 1024)
LEGAL_TILES = [(bm, bn, bk) for bm in _SIDES for bn in _SIDES
               for bk in _SIDES if tile_legal(*MM, bm, bn, bk)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe one with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_largest_legal_tile_is_the_16_mib_one():
    biggest = max(LEGAL_TILES, key=lambda t: vmem_bytes(*t))
    assert biggest == (1024, 1024, 256)
    assert vmem_bytes(*biggest) == 16 * 2 ** 20
    # double-buffering is what excludes the tile the compiler refuses
    assert not tile_legal(*MM, 1024, 1024, 512)


@pytest.mark.parametrize("chunk", [64, 128, 256])
def test_ssd_compiles_at_mamba2_widths(one_chip, chunk):
    # mamba2-2.7b: 80 heads of 64, one group, state 128
    b, l, h, p, g, n = 1, 1024, 80, 64, 1, 128
    text = _compile_text(
        lambda x, dt, a, bb, cc: ssd(x, dt, a, bb, cc, chunk=chunk),
        one_chip, (b, l, h, p), (b, l, h), (h,), (b, l, g, n), (b, l, g, n))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block", [128, 256, 512])
def test_flash_attention_compiles_at_head_dim_128(one_chip, block):
    shape = (1, 8, 2048, 128)
    text = _compile_text(
        lambda q, k, v: flash_attention(q, k, v, bq=block, bkv=block),
        one_chip, shape, shape, shape)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tile", LEGAL_TILES, ids=str)
def test_every_legal_matmul_tile_compiles(one_chip, tile):
    m, n, k = MM
    bm, bn, bk = tile
    text = _compile_text(lambda x, y: matmul(x, y, bm=bm, bn=bn, bk=bk),
                         one_chip, (m, k), (k, n))
    assert "tpu_custom_call" in text
