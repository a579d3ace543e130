"""Pallas TPU kernel: fused RMS norm (normalize + scale) over rows.

Row-blocked: grid over row tiles; each block loads a (BLK_R, d) tile into
VMEM, reduces in fp32 on the VPU, multiplies by the (broadcast) scale, and
writes back — one HBM round trip instead of norm+mul materializing
intermediates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret

__all__ = ["rms_norm_pallas"]


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                  # (BLK_R, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)[None, :]
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_r", "interpret"))
def rms_norm_pallas(x, scale, *, eps: float = 1e-5, blk_r: int = 256,
                    interpret: bool | None = None):
    """x: (..., d); scale: (d,).  Returns same shape/dtype as x."""
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    R = x2.shape[0]
    blk_r = min(blk_r, R)
    pad = (-R) % blk_r
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // blk_r,)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_r, d), lambda r: (r, 0)),
            pl.BlockSpec((d,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((blk_r, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(x2, scale)
    if pad:
        out = out[:R]
    return out.reshape(orig_shape)
