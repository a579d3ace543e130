"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at real widths.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: tiles not aligned to its (8, 128) layout, rank-1 blocks, more
VMEM than a kernel may use.  These tests run the real compiler on the
CPU host and check that a native kernel (``tpu_custom_call``) comes out.
They skip only where no v5e topology can be described (no TPU compiler
installed).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bfio_swap import swap_best_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # what compiles for a described chip cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (B, Hq, Hkv, hd, block, max_blocks): decode bucket 32 over 256-token rows
@pytest.mark.parametrize("B,Hq,Hkv,hd,bs,mb", [
    (32, 24, 8, 128, 16, 16),      # minitron-4b
    (32, 24, 8, 64, 16, 16),       # granite-moe-3b-a800m
], ids=["minitron-4b", "granite-moe-3b-a800m"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, B, Hq, Hkv, hd, bs,
                                              mb):
    pool = ((B * mb, bs, Hkv, hd), jnp.bfloat16)

    def step(q, kp, vp, tables, lengths):
        return paged_decode_attention_pallas(q, kp, vp, tables, lengths,
                                             block_size=bs, interpret=False)

    text = _compiled_text(step, one_chip, ((B, Hq, hd), jnp.bfloat16), pool,
                          pool, ((B, mb), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


# the engine's lookahead window H=8 gives W = 9 trajectory points
@pytest.mark.parametrize("G,N,W", [(4, 256, 9), (16, 1024, 9)])
def test_swap_kernel_compiles_for_v5e(one_chip, G, N, W):
    def search(loads, cands, assign, valid):
        return swap_best_pallas(loads, cands, assign, valid, tile_i=128,
                                tile_j=128, interpret=False)

    text = _compiled_text(search, one_chip, ((G, W), jnp.float32),
                          ((N, W), jnp.float32), ((N,), jnp.int32),
                          ((N,), jnp.bool_))
    assert "tpu_custom_call" in text
