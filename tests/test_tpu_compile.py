"""Compile every Pallas kernel of the main path for a described TPU v5e.

Interpret mode (``tests/test_kernels.py``) checks the kernels' numbers
but not that Mosaic accepts them: tiling, VMEM budgets and scalar stores
are only checked by the TPU compiler. These tests compile each kernel
with ``interpret=False`` at the widths of ``qwen3-1.7b`` for one chip of
a ``v5e:2x2`` topology, described rather than attached, so they need the
TPU compiler library but no chip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitpack import LANES, bitpack_2d
from repro.kernels.bitunpack import bitunpack_2d
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.l2norm import l2norm_sq_2d
from repro.kernels.paged_attention import paged_attend

# qwen3-1.7b attention geometry and its largest (d_model x d_ff) weight
HEADS, KV_HEADS, HEAD_DIM = 16, 8, 128
WEIGHT_ROWS = 2048 * 6144 // LANES
# chip_smoke.py's paged engine: 4 slots, 512 + 32 tokens in 64-token pages
SLOTS, PAGE, TABLE_WIDTH = 4, 64, 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo


@pytest.mark.parametrize("seq", [512, 8192])
def test_flash_prefill_compiles(one_chip, seq):
    # 8192: K/V of one head no longer fit the 16 MiB scoped VMEM at once
    _compile(
        lambda q, k, v: flash_prefill(q, k, v, interpret=False), one_chip,
        ((1, HEADS, seq, HEAD_DIM), jnp.float32),
        ((1, KV_HEADS, seq, HEAD_DIM), jnp.float32),
        ((1, KV_HEADS, seq, HEAD_DIM), jnp.float32),
    )


def test_paged_attend_compiles(one_chip):
    pool = (SLOTS * TABLE_WIDTH + 1, PAGE, KV_HEADS, HEAD_DIM)
    _compile(
        lambda q, k, v, table, lens: paged_attend(
            q, k, v, table, lens, interpret=False
        ),
        one_chip,
        ((SLOTS, KV_HEADS, HEADS // KV_HEADS, HEAD_DIM), jnp.float32),
        (pool, jnp.float32),
        (pool, jnp.float32),
        ((SLOTS, TABLE_WIDTH), jnp.int32),
        ((SLOTS,), jnp.int32),
    )


@pytest.mark.parametrize("round_to", [1, 2, 3])
def test_bitpack_compiles(one_chip, round_to):
    _compile(
        lambda w: bitpack_2d(w, round_to, interpret=False), one_chip,
        ((WEIGHT_ROWS, LANES), jnp.float32),
    )


@pytest.mark.parametrize("round_to", [1, 2, 3])
def test_bitunpack_compiles(one_chip, round_to):
    _compile(
        lambda p: bitunpack_2d(p, interpret=False), one_chip,
        ((round_to, WEIGHT_ROWS, LANES), jnp.uint8),
    )


def test_l2norm_compiles(one_chip):
    _compile(
        lambda w: l2norm_sq_2d(w, interpret=False), one_chip,
        ((WEIGHT_ROWS, LANES), jnp.float32),
    )
