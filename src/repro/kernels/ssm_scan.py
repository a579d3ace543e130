"""Pallas TPU kernel: chunked gated linear-attention scan (Mamba2 SSD /
mLSTM inner loop).

The recurrence S_t = exp(a_t) S_{t-1} + g_t k_t v_t^T, y_t = q_t . S_t is
computed chunk-parallel: grid = (batch, head, chunks) with the chunk axis
innermost-sequential, carrying the (dk, dv) state in VMEM scratch.  Per
chunk the kernel does three MXU matmuls on (C, dk)x(dk, dv)-shaped tiles:

    y_intra = (tril(exp(A_t - A_s)) * g_s * (q k^T)) v      (C x C) form
    y_inter = exp(A_t) * q . S_prev
    S_new   = exp(A_C) S_prev + sum_s exp(A_C - A_s) g_s k_s v_s^T

This is the TPU-native adaptation of SSD: the GPU version leans on warp
shuffles for the inner scan; here everything is re-blocked so the chunk
matmuls are 128-aligned and the cross-chunk carry is the only sequential
dependency (VMEM-resident, no HBM round trip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import resolve_interpret

__all__ = ["ssm_chunk_scan_pallas"]


def _kernel(q_ref, k_ref, v_ref, a_ref, g_ref, y_ref, s_out_ref,
            state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0, :, 0].astype(jnp.float32)     # (C, dk)
    k = k_ref[0, :, 0].astype(jnp.float32)     # (C, dk)
    v = v_ref[0, :, 0].astype(jnp.float32)     # (C, dv)
    a = a_ref[0, :, 0].astype(jnp.float32)     # (C,)
    g = g_ref[0, :, 0].astype(jnp.float32)     # (C,)

    A = jnp.cumsum(a)                          # (C,) cumulative log decay
    # intra-chunk quadratic form
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (C, C)
    pair = jnp.clip(A[:, None] - A[None, :], -60.0, 60.0)
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    w = scores * jnp.exp(pair) * g[None, :] * (col <= row)
    y = jnp.dot(w, v, preferred_element_type=jnp.float32)          # (C, dv)

    # inter-chunk contribution from carried state
    state = state_ref[...]                     # (dk, dv)
    y += jnp.exp(jnp.clip(A, -60, 60))[:, None] * jnp.dot(
        q, state, preferred_element_type=jnp.float32)

    # state update
    A_tot = A[-1]
    wk = jnp.exp(jnp.clip(A_tot - A, -60, 60)) * g                 # (C,)
    state = jnp.exp(jnp.clip(A_tot, -60, 60)) * state + jnp.dot(
        (k * wk[:, None]).T, v, preferred_element_type=jnp.float32)
    state_ref[...] = state

    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _finish():
        s_out_ref[0, 0] = state.astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_chunk_scan_pallas(q, k, v, log_decay, gate, *, chunk: int = 128,
                          interpret: bool | None = None):
    """q, k: (B, S, H, dk); v: (B, S, H, dv); log_decay/gate: (B, S, H).

    Returns (y (B, S, H, dv), final_state (B, H, dk, dv)).
    S must be padded to a multiple of ``chunk`` by the caller (ops.py does).
    """
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    grid = (B, H, n_chunks)

    y, s_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, dk), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, dk), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, dv), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, dv), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, dv), v.dtype),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v, log_decay, gate)
    return y, s_out
