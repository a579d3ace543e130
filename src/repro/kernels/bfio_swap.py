"""Tiled pairwise swap-score search for BF-IO refinement.

One refinement step of the exchange argument needs, for every admitted
candidate pair (i, j) assigned to different workers, the windowed max-load
objective *after* exchanging them:

    val[i, j] = sum_h max( max_{g != g_i, g_j} loads[g, h],
                           loads[g_i, h] + c_j[h] - c_i[h],
                           loads[g_j, h] + c_i[h] - c_j[h] )

and the (i, j) minimizing it.  The dense formulation materializes an
(N, N, W) tensor per refinement iteration; this module computes the same
reduction in (TILE_I, TILE_J) blocks with a running per-row argmin so peak
memory is O(TILE_I * TILE_J * W) and the output is just two (N,) vectors:

    best_val[i] = min_j val[i, j]        best_j[i] = argmin_j val[i, j]

(first minimizer per row — the global argmin over ``best_val`` then
reproduces the dense row-major tie-breaking exactly).

Three interchangeable backends with identical semantics:

* ``swap_best_pallas`` — Pallas kernel, grid (N/TILE_I, N/TILE_J), the
  running argmin carried in the revisited output block across the inner
  j-grid dimension.  Interpret mode on CPU (correctness), native on TPU.
  The window W is looped over inside the kernel, so it needs no lane
  alignment.
* ``swap_best_xla`` — pure-XLA fallback tiled over i only (``lax.map``
  over row blocks, full j extent per block); the production CPU path.
* ``swap_best_dense`` lives in ``ref.py`` as the O(N^2 W) oracle.

The max-excluding-two-rows term uses the top-3 per window position
(computed once per call, O(G W)): the max over workers excluding rows
{g_i, g_j} is v1 unless t1 is excluded, then v2 unless t2 is excluded,
then v3 — at most two rows are ever excluded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import resolve_interpret

__all__ = ["swap_prep", "swap_best_pallas", "swap_best_xla", "swap_best"]


def swap_prep(loads, cands, assign, valid):
    """Shared O(G W + N W) prepass for all backends.

    Returns (lo, ga, adm, vtop, ttop):
      lo   : (N, W) f32  load row of each candidate's worker (0 if unadmitted)
      ga   : (N,)   i32  assigned worker (clipped to 0 for unadmitted)
      adm  : (N,)  bool  admitted mask
      vtop : (3, W) f32  top-3 load values per window position
      ttop : (2, W) i32  rows achieving top-1 / top-2
    """
    loads = jnp.asarray(loads, jnp.float32)
    cands = jnp.asarray(cands, jnp.float32)
    G = loads.shape[0]
    adm = (assign >= 0) & valid
    ga = jnp.clip(assign, 0).astype(jnp.int32)
    lo = jnp.where(adm[:, None], loads[ga], 0.0)
    idx = jnp.argsort(-loads, axis=0)                       # (G, W)
    t1, t2 = idx[0], idx[jnp.minimum(1, G - 1)]
    t3 = idx[jnp.minimum(2, G - 1)]
    v1 = jnp.take_along_axis(loads, t1[None, :], axis=0)[0]
    v2 = jnp.take_along_axis(loads, t2[None, :], axis=0)[0]
    v3 = jnp.take_along_axis(loads, t3[None, :], axis=0)[0]
    vtop = jnp.stack([v1, v2, v3])
    ttop = jnp.stack([t1, t2]).astype(jnp.int32)
    return lo, ga, adm, vtop, ttop


def _pair_vals(ci, li, gai, admi, cj, lj, gaj, admj, vtop, ttop):
    """Swap objective for an (I, J) block; shared by both tiled backends."""
    diff = cj[None, :, :] - ci[:, None, :]                  # (I, J, W)
    la = li[:, None, :] + diff                              # g_i row after swap
    lb = lj[None, :, :] - diff                              # g_j row after swap
    ga3 = gai[:, None, None]
    gb3 = gaj[None, :, None]
    t1 = ttop[0][None, None, :]
    t2 = ttop[1][None, None, :]
    e1 = (t1 != ga3) & (t1 != gb3)
    e2 = (t2 != ga3) & (t2 != gb3)
    ex = jnp.where(e1, vtop[0][None, None, :],
                   jnp.where(e2, vtop[1][None, None, :],
                             vtop[2][None, None, :]))
    val = jnp.sum(jnp.maximum(ex, jnp.maximum(la, lb)), axis=-1)
    feas = admi[:, None] & admj[None, :] & (gai[:, None] != gaj[None, :])
    return jnp.where(feas, val, jnp.inf)                    # (I, J)


def _swap_kernel(vtop_ref, ttop_ref, ci_ref, li_ref, gai_ref, admi_ref,
                 cj_ref, lj_ref, gaj_ref, admj_ref, val_ref, arg_ref, *,
                 tile_j: int, width: int):
    """One (TILE_I, TILE_J) block of the swap search, TPU layout.

    The i side arrives row-major ((TILE_I, W) and (TILE_I, 1) columns),
    the j side transposed ((W, TILE_J) and (1, TILE_J) rows), and the
    top-3 table as SMEM scalars, so every window position ``w`` is one
    2-D (TILE_I, TILE_J) plane: i on sublanes, j on lanes.  No 3-D
    (I, J, W) tensor exists — its W-minor layout would pad every (J, W)
    slab to full (8, 128) tiles.  The windowed sum folds the planes in
    order w = 0, 1, ...; XLA's CPU reduction over W (<= 16) adds in the
    same order, so interpret mode stays bit-identical to the oracles."""
    jblk = pl.program_id(1)

    @pl.when(jblk == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref[...], jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref[...])

    shape = (ci_ref.shape[0], cj_ref.shape[1])
    gi = jnp.broadcast_to(gai_ref[...], shape)
    gj = jnp.broadcast_to(gaj_ref[...], shape)
    acc = None
    for w in range(width):
        diff = cj_ref[w:w + 1, :] - ci_ref[:, w:w + 1]   # (I, J)
        la = li_ref[:, w:w + 1] + diff                    # g_i row after swap
        lb = lj_ref[w:w + 1, :] - diff                    # g_j row after swap
        t1, t2 = ttop_ref[0, w], ttop_ref[1, w]
        e1 = (t1 != gi) & (t1 != gj)
        e2 = (t2 != gi) & (t2 != gj)
        ex = jnp.where(e1, vtop_ref[0, w],
                       jnp.where(e2, vtop_ref[1, w], vtop_ref[2, w]))
        m = jnp.maximum(ex, jnp.maximum(la, lb))
        acc = m if acc is None else acc + m
    feas = ((jnp.broadcast_to(admi_ref[...], shape) > 0)
            & (jnp.broadcast_to(admj_ref[...], shape) > 0) & (gi != gj))
    val = jnp.where(feas, acc, jnp.inf)
    row_min = val.min(axis=1, keepdims=True)                 # (I, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # first minimizer of each row (argmin's tie-breaking)
    row_arg = jnp.min(jnp.where(val == row_min, col, tile_j), axis=1,
                      keepdims=True) + jblk * tile_j
    prev_v, prev_a = val_ref[...], arg_ref[...]
    better = row_min < prev_v                    # strict: keep first minimizer
    val_ref[...] = jnp.where(better, row_min, prev_v)
    arg_ref[...] = jnp.where(better, row_arg, prev_a)


def _pad_rows(x, n, fill=0):
    pad = [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


@functools.partial(jax.jit,
                   static_argnames=("tile_i", "tile_j", "interpret"))
def swap_best_pallas(loads, cands, assign, valid, *, tile_i: int = 128,
                     tile_j: int = 128, interpret: bool | None = None):
    """Pallas tiled swap search.  Returns (best_val (N,), best_j (N,)).

    On the TPU ``tile_j`` must be a multiple of 128 (it is the lane axis
    of every block) unless one tile covers all N candidates; ``tile_i``
    a multiple of 8.  ``interpret=None`` derives the mode from the
    platform (:func:`~repro.kernels.platform.resolve_interpret`)."""
    lo, ga, adm, vtop, ttop = swap_prep(loads, cands, assign, valid)
    cands = jnp.asarray(cands, jnp.float32)
    N, W = cands.shape
    tile_i, tile_j = min(tile_i, N), min(tile_j, N)
    np_i = pl.cdiv(N, tile_i) * tile_i
    np_j = pl.cdiv(N, tile_j) * tile_j
    npad = max(np_i, np_j)
    cands = _pad_rows(cands, npad)
    lo = _pad_rows(lo, npad)
    ga2 = _pad_rows(ga, npad)[:, None]
    adm2 = _pad_rows(adm.astype(jnp.int32), npad)[:, None]

    grid = (npad // tile_i, npad // tile_j)
    ispec = lambda bs: pl.BlockSpec(bs, lambda i, j, *_: (i, 0))  # noqa: E731
    jspec = lambda bs: pl.BlockSpec(bs, lambda i, j, *_: (0, j))  # noqa: E731
    ospec = pl.BlockSpec((tile_i, 1), lambda i, j, *_: (i, 0))
    vals, args = pl.pallas_call(
        functools.partial(_swap_kernel, tile_j=tile_j, width=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                ispec((tile_i, W)), ispec((tile_i, W)),
                ispec((tile_i, 1)), ispec((tile_i, 1)),
                jspec((W, tile_j)), jspec((W, tile_j)),
                jspec((1, tile_j)), jspec((1, tile_j)),
            ],
            out_specs=[ospec, ospec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(vtop, ttop, cands, lo, ga2, adm2, cands.T, lo.T, ga2.T, adm2.T)
    return vals[:N, 0], args[:N, 0]


@functools.partial(jax.jit, static_argnames=("tile_i",))
def swap_best_xla(loads, cands, assign, valid, *, tile_i: int = 128):
    """XLA fallback: same reduction tiled over i only (lax.map over row
    blocks, full j extent per block) — the production CPU path."""
    lo, ga, adm, vtop, ttop = swap_prep(loads, cands, assign, valid)
    cands = jnp.asarray(cands, jnp.float32)
    N, W = cands.shape
    if N <= tile_i:      # single block: skip the map machinery entirely
        val = _pair_vals(cands, lo, ga, adm, cands, lo, ga, adm, vtop, ttop)
        return val.min(axis=1), val.argmin(axis=1).astype(jnp.int32)
    tile_i = min(tile_i, N)
    npad = pl.cdiv(N, tile_i) * tile_i
    ci = _pad_rows(cands, npad).reshape(-1, tile_i, W)
    li = _pad_rows(lo, npad).reshape(-1, tile_i, W)
    gai = _pad_rows(ga, npad).reshape(-1, tile_i)
    admi = _pad_rows(adm, npad).reshape(-1, tile_i)

    def block(blk):
        bci, bli, bga, badm = blk
        val = _pair_vals(bci, bli, bga, badm, cands, lo, ga, adm, vtop, ttop)
        return val.min(axis=1), val.argmin(axis=1).astype(jnp.int32)

    vals, args = jax.lax.map(block, (ci, li, gai, admi))
    return vals.reshape(-1)[:N], args.reshape(-1)[:N]


def swap_best(loads, cands, assign, valid, *, backend: str = "xla", **kw):
    """Dispatch: ``backend`` in {"pallas", "xla"} (dense oracle in ref.py)."""
    if backend == "pallas":
        return swap_best_pallas(loads, cands, assign, valid, **kw)
    if backend == "xla":
        return swap_best_xla(loads, cands, assign, valid, **kw)
    raise ValueError(f"unknown swap backend {backend!r}")
