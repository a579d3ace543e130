"""Pallas TPU kernel: paged one-token decode attention.

Same online-softmax structure as decode_attention.py, but the KV blocks
are fetched *indirectly* through a per-request block table (vLLM paging):
the block table arrives via scalar prefetch (SMEM) and drives the
BlockSpec index_map, so each grid step DMAs exactly one physical KV block
HBM->VMEM — no contiguous-cache materialization, no gather of the pool.

This is the TPU adaptation of paged attention: the GPU version does
per-warp pointer chasing; on TPU the indirection moves into the prefetch
-> index_map path and the MXU still sees dense tiles.

Layout.  The pool is ``(n_blocks, block, Hkv, hd)``, so one grid step
fetches a whole physical block, all KV heads at once: the tile's two
minor dims are the full ``(Hkv, hd)``, which the TPU's (8, 128) tiling
accepts for any head count (a one-head ``(1, hd)`` slice is refused).
In VMEM the block is flattened to ``(block * Hkv, hd)`` rows and every
query head scores every row in one MXU matmul; a constant
``(Hq, block * Hkv)`` mask keeps only the rows of each query head's own
KV head (GQA).  The extra products are cheap: decode attention is bound
by the KV bytes streamed, which this layout reads exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import resolve_interpret

__all__ = ["paged_decode_attention_pallas"]

_NEG = -1e30
# q @ k^T with both operands row-major: contract the last dims
_NT = (((1,), (1,)), ((), ()))


def _kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, head_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_size: int, n_blocks: int):
    b = pl.program_id(0)
    blk = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(blk * block_size < length)     # blocks past the row's end
    def _step():
        q = q_ref[0].astype(jnp.float32)                     # (Hq, hd)
        _, bs, hkv, hd = k_ref.shape
        k = k_ref[0].astype(jnp.float32).reshape(bs * hkv, hd)
        v = v_ref[0].astype(jnp.float32).reshape(bs * hkv, hd)
        scale = jax.lax.rsqrt(jnp.asarray(hd, jnp.float32))
        s = jax.lax.dot_general(q * scale, k, _NT,
                                preferred_element_type=jnp.float32)
        # row r = t * Hkv + h holds token t of this block: it is in range
        # iff blk * block + t < length, i.e. r < (length - blk*block) * Hkv
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = (head_ref[...] > 0) & (row < (length - blk * block_size) * hkv)
        s = jnp.where(keep, s, _NEG)

        m_prev = m_ref[...]                                  # (Hq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(blk == n_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _head_mask(hq: int, hkv: int, block_size: int) -> np.ndarray:
    """(Hq, block * Hkv) int32: 1 where flattened KV row ``t * Hkv + h``
    belongs to query head ``r``'s KV head ``r // (Hq // Hkv)``."""
    group = hq // hkv
    row_head = np.arange(block_size * hkv) % hkv
    return (row_head[None, :] == (np.arange(hq) // group)[:, None]
            ).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, lengths,
                                  *, block_size: int,
                                  interpret: bool | None = None):
    """q: (B, Hq, hd); k_pool/v_pool: (n_pool_blocks, block, Hkv, hd);
    block_tables: (B, max_blocks) int32 (-1 = unallocated);
    lengths: (B,).  Returns (B, Hq, hd).

    Grid = (B, max_blocks); the block-table scalar prefetch drives the
    k/v index_map, fetching physical block ``tables[b, blk]`` with all of
    its KV heads.  Rows of length 0 (batch padding) return zeros.
    ``interpret=None`` derives the mode from the platform
    (:func:`~repro.kernels.platform.resolve_interpret`).
    """
    B, Hq, hd = q.shape
    Hkv = k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    # clamp -1 entries for the DMA (positions past `lengths` are masked)
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)
    head = jnp.asarray(_head_mask(Hq, Hkv, block_size))
    kv_block = (1, block_size, Hkv, hd)

    return pl.pallas_call(
        functools.partial(_kernel, block_size=block_size,
                          n_blocks=max_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, max_blocks),
            in_specs=[
                pl.BlockSpec((1, Hq, hd), lambda b, j, T_, L_: (b, 0, 0)),
                pl.BlockSpec(kv_block,
                             lambda b, j, T_, L_: (T_[b, j], 0, 0, 0)),
                pl.BlockSpec(kv_block,
                             lambda b, j, T_, L_: (T_[b, j], 0, 0, 0)),
                pl.BlockSpec(head.shape, lambda b, j, T_, L_: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Hq, hd),
                                   lambda b, j, T_, L_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(tables, lengths.astype(jnp.int32), q, k_pool, v_pool, head)
