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


# the dropless expert layer of qwen3-30b-a3b (d 2048, experts of 768,
# top-8 of 128, 32 held): a 32-row decode step and a 512-token chunk in
# a 4-row bucket
@pytest.mark.parametrize("B,S", [(32, 1), (4, 512)], ids=["decode", "chunk"])
def test_dropless_expert_layer_compiles_for_v5e(one_chip, topo, monkeypatch,
                                                B, S):
    import repro.kernels.platform as platform
    from repro.launch.mesh import _make_mesh
    from repro.models.moe import moe_ffn_dropless

    # the layer picks interpret mode from the platform this process runs
    # on; the described chip is a TPU
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    mesh = _make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    d, f, E, held = 2048, 768, 128, 32

    def layer(x, valid, router, w1, w3, w2):
        return moe_ffn_dropless(x, valid, dict(router=router, w1=w1, w3=w3,
                                               w2=w2),
                                k=8, mesh=mesh, batch_axes=("data",))

    text = _compiled_text(layer, one_chip, ((B, S, d), jnp.bfloat16),
                          ((B, S), jnp.bool_), ((d, E), jnp.float32),
                          ((held, d, f), jnp.bfloat16),
                          ((held, d, f), jnp.bfloat16),
                          ((held, f, d), jnp.bfloat16))
    # the three grouped matmuls, as the device trace names them
    assert sum(line.lstrip().startswith(("%gmm", "ROOT %gmm"))
               and "tpu_custom_call" in line
               for line in text.splitlines()) == 3


def test_paged_decode_reads_the_expert_stack_in_place(one_chip, topo,
                                                      monkeypatch):
    """Qwen3-30B-A3B's widths (32 held experts) at 2 layers: inside the
    scan over layers, each grouped matmul takes the stack of every
    layer's experts as it is, and no layer's slice of it is copied out
    (a copy moves all 32 experts where the kernel reads the hit ones)."""
    import re

    import repro.kernels.platform as platform
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import _make_mesh
    from repro.launch.serve import init_serving_params
    from repro.models import paged_decode_fn

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    mesh = _make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    cfg = ModelConfig(name="qwen3-moe-2l", family="moe", n_layers=2,
                      d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
                      d_ff=0, moe_d_ff=768, n_experts=128,
                      experts_per_token=8, experts_held=32, vocab_size=1024,
                      qk_norm=True, dtype="bfloat16")
    n, bs, nb, mb = 32, 16, 256, 8
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_serving_params(cfg, 0)))
    pool = jax.ShapeDtypeStruct((2, nb, bs, 4, 128), jnp.bfloat16,
                                sharding=one_chip)
    rows = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((n, mb), (n,), (n,), (n,), (n,))]

    def step(p, kp, vp, tables, lengths, blk, off, toks):
        return paged_decode_fn(cfg, p, kp, vp, tables, lengths, blk, off,
                               toks, block_size=bs, attn_impl="pallas",
                               mesh=mesh)

    text = jax.jit(step).lower(params, pool, pool, *rows).compile().as_text()
    gmms = [line for line in text.splitlines()
            if line.lstrip().startswith(("%gmm", "ROOT %gmm"))]
    assert len(gmms) == 3
    assert all("bf16[64," in line for line in gmms)
    assert not re.search(r"= bf16\[32,(2048,768|768,2048)\]", text)
