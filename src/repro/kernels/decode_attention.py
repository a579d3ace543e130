"""Pallas TPU kernel: one-token GQA decode attention over a ragged KV cache.

This is the synchronized-phase local compute of the paper — the per-step
worker time T_local ∝ L_g is dominated by exactly this kernel streaming the
resident KV cache.  TPU-native design:

  * grid = (batch, kv_head, kv_blocks); the kv_blocks axis is the
    *innermost sequential* grid dim, so VMEM scratch (running max / sum /
    accumulator) carries the online softmax across KV blocks
    (flash-decode);
  * KV streamed HBM->VMEM in (BLK_L, hd) tiles, 128-aligned for the MXU;
  * per-request ragged lengths arrive via scalar prefetch (SMEM) and mask
    the tail block with broadcasted iota (8x128 VREG-friendly);
  * GQA: the Gq query heads of one kv head are processed together as the
    matmul's M dim — q tile (Gq, hd) x k tile (hd, BLK_L) on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import resolve_interpret

__all__ = ["decode_attention_pallas"]

_NEG = -1e30


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, blk_l: int, n_blocks: int):
    b = pl.program_id(0)
    blk = pl.program_id(2)

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (Gq, hd)
    k = k_ref[0, :, 0].astype(jnp.float32)         # (BLK_L, hd)
    v = v_ref[0, :, 0].astype(jnp.float32)         # (BLK_L, hd)
    hd = q.shape[-1]
    scale = jax.lax.rsqrt(jnp.asarray(hd, jnp.float32))

    s = jnp.dot(q * scale, k.T,
                preferred_element_type=jnp.float32)  # (Gq, BLK_L)
    length = lengths_ref[b]
    pos = blk * blk_l + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, _NEG)

    m_prev = m_ref[...]                            # (Gq,)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])                # (Gq, BLK_L)
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(blk == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("blk_l", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, lengths, *,
                            blk_l: int = 512,
                            interpret: bool | None = None):
    """q: (B, Hq, hd); k_cache/v_cache: (B, L, Hkv, hd); lengths: (B,).

    Returns (B, Hq, hd).  ``interpret=None`` derives the mode from the
    platform (:func:`~repro.kernels.platform.resolve_interpret`).
    """
    B, Hq, hd = q.shape
    L, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    blk_l = min(blk_l, L)
    n_blocks = (L + blk_l - 1) // blk_l
    if L % blk_l != 0:
        pad = n_blocks * blk_l - L
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qg = q.reshape(B, Hkv, G, hd)

    grid = (B, Hkv, n_blocks)
    out = pl.pallas_call(
        functools.partial(_kernel, blk_l=blk_l, n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, l, L_: (b, h, 0, 0)),
                pl.BlockSpec((1, blk_l, 1, hd),
                             lambda b, h, l, L_: (b, l, h, 0)),
                pl.BlockSpec((1, blk_l, 1, hd),
                             lambda b, h, l, L_: (b, l, h, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, l, L_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(B, Hq, hd)
