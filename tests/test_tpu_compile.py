"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks what the kernels compute,
not whether the TPU compiler accepts their tiling. These tests compile
``flash_decode_paged``, ``flash_decode`` and the ``flash_attention``
forward at qwen1.5-0.5b's serving widths (16 query and 16 kv heads, head
dim 64, bfloat16; 8 slots of 2048 positions) for one chip of a described
``v5e:2x2`` topology, with no chip attached. Nothing runs, so nothing
about results or speed is checked here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (MAX_BLOCK_ROWS, check_page_size,
                                            flash_decode, flash_decode_paged)
from repro.kernels.flash_attention import flash_attention

SLOTS, H, K, HD, MAX_LEN = 8, 16, 16, 64, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page", [16, 128])
def test_flash_decode_paged_compiles(one_chip, page):
    M = MAX_LEN // page
    P = SLOTS * M + 1
    _compile(flash_decode_paged, one_chip,
             ((SLOTS, H, HD), jnp.bfloat16),
             ((P, page, K, HD), jnp.bfloat16),
             ((P, page, K, HD), jnp.bfloat16),
             ((SLOTS, M), jnp.int32), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("heads,kv_heads,hd", [
    (16, 16, 64),    # qwen1.5-0.5b
    (32, 8, 128),    # llama3-8b, phi3.5-moe
    (64, 8, 112),    # kimi-k2
])
def test_flash_decode_paged_compiles_at_page_limit(one_chip, heads, kv_heads,
                                                   hd):
    """The largest page ``check_page_size`` admits fits VMEM."""
    g = heads // kv_heads
    page = MAX_BLOCK_ROWS // (g * (-(-kv_heads // 8) * 8))
    check_page_size(page, heads, kv_heads)
    with pytest.raises(ValueError, match="page_size"):
        check_page_size(page + 1, heads, kv_heads)
    M, P = 2, 2 * SLOTS + 1
    _compile(flash_decode_paged, one_chip,
             ((SLOTS, heads, hd), jnp.bfloat16),
             ((P, page, kv_heads, hd), jnp.bfloat16),
             ((P, page, kv_heads, hd), jnp.bfloat16),
             ((SLOTS, M), jnp.int32), ((SLOTS,), jnp.int32))


def test_flash_decode_compiles(one_chip):
    _compile(flash_decode, one_chip,
             ((SLOTS, H, HD), jnp.bfloat16),
             ((SLOTS, MAX_LEN, K, HD), jnp.bfloat16),
             ((SLOTS, MAX_LEN, K, HD), jnp.bfloat16),
             ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("seq", [1024, 600])
def test_flash_attention_forward_compiles(one_chip, seq):
    _compile(flash_attention, one_chip,
             ((1, seq, H, HD), jnp.bfloat16),
             ((1, seq, K, HD), jnp.bfloat16),
             ((1, seq, K, HD), jnp.bfloat16))
