"""BF-IO as a composable, jittable JAX module.

The host-side reference solver lives in ``io_solver``; this module provides
a pure-``jax.lax`` implementation with static shapes so the balance step can
be fused into a device-side serving loop (or dispatched per-step without
host round-trips).  Construction is greedy LPT (a ``fori_loop`` over
candidates in size order); refinement is a fixed number of best-improving
pairwise swap iterations (the exchange argument of the proofs).

Refinement backends
-------------------
The swap search dominates solve cost.  Three interchangeable backends
compute the identical best-improving pair per iteration (see
``repro.kernels.bfio_swap`` for the math):

* ``method="dense"`` — the original formulation: materialize the full
  (N, N, W) post-swap tensor and take a flat argmin.  O(N^2 W) memory
  per iteration; kept as the measured pre-optimization baseline and
  small-instance oracle.
* ``method="xla"`` (default) — the same reduction tiled over candidate
  row blocks (``lax.map``, peak memory O(TILE * N * W)); the production
  CPU path.
* ``method="pallas"`` — a Pallas kernel on a (N/TILE_I, N/TILE_J) grid
  with the running per-row argmin carried in the revisited output block,
  so no pairwise tensor is ever materialized; interpreted off-TPU.

Candidate pruning
-----------------
``prune_k=K`` restricts the swap search to the top-K admitted candidates
by windowed contribution (sum over the lookahead window).  Exchanging two
admitted candidates never changes *which* candidates are admitted, so the
pruned set is computed once per solve and refinement permutes assignments
within it: pair-search cost drops from N^2 to K^2 per iteration.  Small
candidates move the windowed max least, so quality loss is bounded and
measured (see benchmarks/balancer_bench.py); ``prune_k=None`` keeps the
search exact.

Batched solving
---------------
``bfio_assign_batch`` vmaps the whole solve over a leading cluster axis —
independent (base, caps, cands) instances solved in one compiled call for
fleet-scale sweeps (G up to 1024, thousands of candidates).

Shapes (static under jit):
    base  : (G, W) f32   predicted resident-load trajectories, W = H+1
    caps  : (G,)  i32    free slots per worker
    cands : (N, W) f32   candidate contribution trajectories (zero-padded)
    valid : (N,)  bool   which candidate rows are real
Returns
    assign: (N,) i32     worker id per candidate, -1 = not admitted
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels.bfio_swap import swap_best_pallas, swap_best_xla

__all__ = ["bfio_assign", "bfio_assign_batch", "windowed_imbalance"]


def windowed_imbalance(loads: jnp.ndarray) -> jnp.ndarray:
    """J = sum_h (G * max_g loads[g,h] - sum_g loads[g,h])."""
    G = loads.shape[0]
    return jnp.sum(G * loads.max(axis=0) - loads.sum(axis=0))


def _greedy(base, caps, cands, valid, n_admit):
    G, W = base.shape
    N = cands.shape[0]
    totals = jnp.where(valid, cands.sum(axis=1), -jnp.inf)
    order = jnp.argsort(-totals)  # largest first, invalid last

    def body(t, carry):
        loads, caps_left, assign, admitted = carry
        i = order[t]
        c = cands[i]
        # top-2 per step-of-window for the exclusion trick
        top1 = loads.max(axis=0)
        arg1 = loads.argmax(axis=0)
        masked = jnp.where(
            jnp.arange(G)[:, None] == arg1[None, :], -jnp.inf, loads)
        top2 = masked.max(axis=0)
        excl = jnp.where(jnp.arange(G)[:, None] == arg1[None, :],
                         top2[None, :], top1[None, :])           # (G, W)
        scores = jnp.maximum(excl, loads + c[None, :]).sum(axis=1)
        scores = jnp.where(caps_left > 0, scores, jnp.inf)
        g = jnp.argmin(scores)
        ok = (valid[i] & (admitted < n_admit)
              & jnp.isfinite(scores[g]))
        loads = loads.at[g].add(jnp.where(ok, c, 0.0))
        caps_left = caps_left.at[g].add(jnp.where(ok, -1, 0))
        assign = assign.at[i].set(jnp.where(ok, g, -1))
        admitted = admitted + jnp.where(ok, 1, 0)
        return loads, caps_left, assign, admitted

    init = (base.astype(jnp.float32), caps.astype(jnp.int32),
            jnp.full((N,), -1, dtype=jnp.int32), jnp.int32(0))
    loads, caps_left, assign, _ = jax.lax.fori_loop(0, N, body, init)
    return loads, caps_left, assign


def _swap_once_dense(loads, cands, assign, valid):
    """One best-improving pairwise swap, dense O(N^2 W) formulation.

    The pre-optimization baseline: materializes every pairwise post-swap
    trajectory at once.  Semantically identical to the tiled backends.
    """
    G, W = loads.shape
    N = cands.shape[0]
    admitted = (assign >= 0) & valid
    # top-3 per window position, for max-excluding-two-rows
    idx = jnp.argsort(-loads, axis=0)            # (G, W)
    t1, t2, t3 = idx[0], idx[1], idx[jnp.minimum(2, G - 1)]
    v1 = jnp.take_along_axis(loads, t1[None, :], axis=0)[0]
    v2 = jnp.take_along_axis(loads, t2[None, :], axis=0)[0]
    v3 = jnp.take_along_axis(loads, t3[None, :], axis=0)[0]

    gi = assign                                   # (N,)
    lo_i = jnp.where(admitted[:, None], loads[jnp.clip(gi, 0)], 0.0)  # (N, W)

    def excl2(ga, gb):
        # max over workers excluding rows ga, gb; ga/gb: (..., ) ints
        # pick from top-3 per window position
        e1 = (t1[None, None, :] != ga[..., None]) & \
             (t1[None, None, :] != gb[..., None])
        e2 = (t2[None, None, :] != ga[..., None]) & \
             (t2[None, None, :] != gb[..., None])
        out = jnp.where(e1, v1[None, None, :],
                        jnp.where(e2, v2[None, None, :], v3[None, None, :]))
        return out

    ga = jnp.broadcast_to(gi[:, None], (N, N))
    gb = jnp.broadcast_to(gi[None, :], (N, N))
    diff = cands[None, :, :] - cands[:, None, :]   # c_j - c_i, (N, N, W)
    la_new = lo_i[:, None, :] + diff               # row of g_i after swap
    lb_new = lo_i[None, :, :] - diff               # row of g_j after swap
    mx = jnp.maximum(excl2(ga, gb), jnp.maximum(la_new, lb_new))
    # windowed sum of maxima after the swap (sum term is invariant)
    val = mx.sum(axis=2)                           # (N, N)
    feasible = (admitted[:, None] & admitted[None, :]
                & (ga != gb))
    val = jnp.where(feasible, val, jnp.inf)
    flat = jnp.argmin(val)
    bi, bj = jnp.unravel_index(flat, val.shape)
    return _apply_best(loads, cands, assign, val[bi, bj], bi, bj)


def _apply_best(loads, cands, assign, best_val, bi, bj):
    """Apply the swap (bi, bj) iff it improves the windowed max-sum."""
    cur = loads.max(axis=0).sum()
    improve = best_val < cur - 1e-6

    def apply(args):
        loads, assign = args
        ci, cj = cands[bi], cands[bj]
        gi_, gj_ = assign[bi], assign[bj]
        loads = loads.at[gi_].add(cj - ci)
        loads = loads.at[gj_].add(ci - cj)
        assign = assign.at[bi].set(gj_)
        assign = assign.at[bj].set(gi_)
        return loads, assign

    loads, assign = jax.lax.cond(improve, apply, lambda a: a, (loads, assign))
    return loads, assign, improve


def _swap_once_tiled(loads, cands, assign, valid, *, method, tile, interpret):
    """One best-improving swap via the tiled (blockwise-argmin) backends."""
    if method == "pallas":
        vals, args = swap_best_pallas(loads, cands, assign, valid,
                                      tile_i=tile, tile_j=tile,
                                      interpret=interpret)
    else:
        vals, args = swap_best_xla(loads, cands, assign, valid, tile_i=tile)
    bi = jnp.argmin(vals)
    bj = args[bi]
    return _apply_best(loads, cands, assign, vals[bi], bi, bj)


def _refine(loads, assign, cands, valid, *, swap_iters, method, tile,
            prune_k, interpret):
    """Fixed-budget swap refinement, optionally in a pruned top-K subspace.

    Swaps exchange two *admitted* candidates, so the admitted set is
    invariant under refinement and the top-K pool can be picked once.
    """
    N = cands.shape[0]
    if method == "dense":
        def body(_, carry):
            loads, assign = carry
            loads, assign, _ = _swap_once_dense(loads, cands, assign, valid)
            return loads, assign
        return jax.lax.fori_loop(0, swap_iters, body, (loads, assign))

    if prune_k is not None and prune_k <= 0:
        return loads, assign                # empty swap pool: nothing to do
    if prune_k is not None and prune_k < N:
        admitted = (assign >= 0) & valid
        totals = jnp.where(admitted, cands.sum(axis=1), -jnp.inf)
        _, pool = jax.lax.top_k(totals, prune_k)            # (K,)
        sub_cands = cands[pool]
        sub_valid = valid[pool]
        sub_assign = assign[pool]

        def body(_, carry):
            loads, sub_assign = carry
            loads, sub_assign, _ = _swap_once_tiled(
                loads, sub_cands, sub_assign, sub_valid,
                method=method, tile=tile, interpret=interpret)
            return loads, sub_assign

        loads, sub_assign = jax.lax.fori_loop(0, swap_iters, body,
                                              (loads, sub_assign))
        return loads, assign.at[pool].set(sub_assign)

    def body(_, carry):
        loads, assign = carry
        loads, assign, _ = _swap_once_tiled(
            loads, cands, assign, valid,
            method=method, tile=tile, interpret=interpret)
        return loads, assign

    return jax.lax.fori_loop(0, swap_iters, body, (loads, assign))


@functools.partial(jax.jit, static_argnames=("swap_iters", "method", "tile",
                                             "prune_k", "interpret"))
def bfio_assign(base, caps, cands, valid, n_admit, swap_iters: int = 8,
                *, method: str = "xla", tile: int = 128,
                prune_k: int | None = None, interpret: bool | None = None):
    """Jitted BF-IO assignment (greedy + fixed-budget swap refinement).

    ``method`` selects the swap-search backend ("xla" | "pallas" |
    "dense"), ``tile`` the block size, ``prune_k`` the optional top-K
    candidate pruning, ``interpret`` the Pallas interpret mode (``None``:
    derived from the platform, native on TPU).  All backends return
    identical assignments for the same inputs; ``prune_k`` trades a
    measured sliver of objective for a K^2/N^2 reduction in pair-search
    cost.
    """
    base = jnp.asarray(base, dtype=jnp.float32)
    cands = jnp.asarray(cands, dtype=jnp.float32)
    loads, caps_left, assign = _greedy(base, caps, cands, valid, n_admit)
    loads, assign = _refine(loads, assign, cands, valid,
                            swap_iters=swap_iters, method=method, tile=tile,
                            prune_k=prune_k, interpret=interpret)
    return assign


@functools.partial(jax.jit, static_argnames=("swap_iters", "method", "tile",
                                             "prune_k"))
def bfio_assign_batch(base, caps, cands, valid, n_admit, swap_iters: int = 8,
                      *, method: str = "xla", tile: int = 128,
                      prune_k: int | None = None):
    """Batched BF-IO: solve C independent cluster instances in one call.

    Shapes carry a leading cluster axis: base (C, G, W), caps (C, G),
    cands (C, N, W), valid (C, N), n_admit (C,).  Returns (C, N) i32.
    Uses the XLA tiled backend (vmap-compatible); intended for fleet
    sweeps where many clusters are balanced per barrier step.
    """
    if method == "pallas":  # pallas_call batching is not wired up
        method = "xla"
    solve = functools.partial(bfio_assign, swap_iters=swap_iters,
                              method=method, tile=tile, prune_k=prune_k)
    return jax.vmap(solve)(base, caps, cands, valid, n_admit)
