"""Parity tests for the tiled BF-IO swap kernel and the batched solver.

Three layers of agreement are pinned:
  1. kernel level — Pallas / tiled-XLA / dense-oracle swap searches return
     bit-identical (best_val, best_j) vectors;
  2. solver level — ``bfio_assign`` produces the identical assignment for
     every backend, and pruned refinement never regresses below greedy;
  3. objective level — on fully-packed parity fixtures (n == sum caps,
     G <= 4, N <= 8, where pairwise exchange is the complete move set)
     the jitted solver's windowed imbalance J matches ``solve_io`` within
     1% and respects the exchange-argument slack vs ``solve_exact``.
"""
import warnings

warnings.filterwarnings("ignore")

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import io_solver
from repro.core.balancer_jax import bfio_assign, bfio_assign_batch
from repro.kernels.bfio_swap import swap_best_pallas, swap_best_xla
from repro.kernels.ref import bfio_swap_best_ref

def _instance(rng, G, N, W, packed=False):
    base = rng.uniform(0, 10, (G, W))
    if packed:
        caps = rng.integers(1, 3, G)
        N = int(caps.sum())
    else:
        caps = rng.integers(0, 4, G)
    cands = rng.uniform(0.5, 5, (N, W))
    return base, caps, cands


def _jax_args(base, caps, cands, n_admit=None):
    n = cands.shape[0]
    U = min(n, int(caps.sum())) if n_admit is None else n_admit
    return (jnp.asarray(base, jnp.float32), jnp.asarray(caps, jnp.int32),
            jnp.asarray(cands, jnp.float32), jnp.ones(n, bool),
            jnp.int32(U))


class TestSwapKernelParity:
    @pytest.mark.parametrize("G,N,W,ti,tj", [
        (2, 5, 1, 4, 4),
        (4, 33, 3, 8, 16),     # ragged tiles
        (8, 64, 9, 16, 16),
        (3, 17, 2, 32, 32),    # single-tile (N < tile)
    ])
    def test_backends_bit_identical(self, G, N, W, ti, tj):
        rng = np.random.default_rng(G * 1000 + N)
        loads = jnp.asarray(rng.uniform(0, 10, (G, W)), jnp.float32)
        cands = jnp.asarray(rng.uniform(0, 5, (N, W)), jnp.float32)
        assign = jnp.asarray(rng.integers(-1, G, N), jnp.int32)
        valid = jnp.asarray(rng.random(N) > 0.1)

        vd, ad = bfio_swap_best_ref(loads, cands, assign, valid)
        vx, ax = swap_best_xla(loads, cands, assign, valid, tile_i=ti)
        vp, ap = swap_best_pallas(loads, cands, assign, valid,
                                  tile_i=ti, tile_j=tj)
        vd, ad = np.asarray(vd), np.asarray(ad)
        np.testing.assert_array_equal(vd, np.asarray(vx))
        np.testing.assert_array_equal(vd, np.asarray(vp))
        fin = np.isfinite(vd)  # argmin of an all-inf row is unconstrained
        np.testing.assert_array_equal(ad[fin], np.asarray(ax)[fin])
        np.testing.assert_array_equal(ad[fin], np.asarray(ap)[fin])

    def test_pallas_lane_padding(self):
        """Zero-padding the window to the 128-lane boundary must not change
        the reduction: padded positions add max(0, 0, 0) = 0 for the
        non-negative loads of this problem."""
        rng = np.random.default_rng(99)
        loads = jnp.asarray(rng.uniform(0, 10, (4, 5)), jnp.float32)
        cands = jnp.asarray(rng.uniform(0, 5, (12, 5)), jnp.float32)
        assign = jnp.asarray(rng.integers(0, 4, 12), jnp.int32)
        valid = jnp.ones(12, bool)
        pad = ((0, 0), (0, 128 - 5))
        v0, a0 = swap_best_pallas(loads, cands, assign, valid, tile_i=4,
                                  tile_j=4)
        v1, a1 = swap_best_pallas(jnp.pad(loads, pad), jnp.pad(cands, pad),
                                  assign, valid, tile_i=4, tile_j=4)
        np.testing.assert_allclose(np.asarray(v0), np.asarray(v1),
                                   rtol=1e-6)
        fin = np.isfinite(np.asarray(v0))
        np.testing.assert_array_equal(np.asarray(a0)[fin],
                                      np.asarray(a1)[fin])


class TestSolverBackendsIdentical:
    @pytest.mark.parametrize("trial", range(6))
    def test_dense_xla_pallas_same_assignment(self, trial):
        rng = np.random.default_rng(500 + trial)
        G = int(rng.integers(2, 6))
        N = int(rng.integers(2, 30))
        W = int(rng.integers(1, 5))
        base, caps, cands = _instance(rng, G, N, W)
        args = _jax_args(base, caps, cands)
        a_d = np.asarray(bfio_assign(*args, method="dense"))
        a_x = np.asarray(bfio_assign(*args, method="xla", tile=8))
        a_p = np.asarray(bfio_assign(*args, method="pallas", tile=8))
        np.testing.assert_array_equal(a_d, a_x)
        np.testing.assert_array_equal(a_d, a_p)

    def test_pruned_never_worse_than_greedy(self):
        base, caps, cands = _instance(np.random.default_rng(77), 8, 64, 4)
        args = _jax_args(base, caps, cands)
        a_greedy = np.asarray(bfio_assign(*args, swap_iters=0))
        a_pruned = np.asarray(bfio_assign(*args, method="xla", prune_k=16))
        G = base.shape[0]
        used = np.bincount(a_pruned[a_pruned >= 0], minlength=G)
        assert np.all(used <= caps)
        assert (a_pruned >= 0).sum() == (a_greedy >= 0).sum()
        assert (io_solver.objective(base, cands, a_pruned)
                <= io_solver.objective(base, cands, a_greedy) + 1e-4)


class TestObjectiveParityFixtures:
    """Fully-packed small fixtures: refinement's exchange moves are the
    complete local-search move set, so the jitted solver must land within
    1% of solve_io's windowed imbalance."""

    @pytest.mark.parametrize("trial", range(12))
    def test_within_1pct_of_solve_io(self, trial):
        rng = np.random.default_rng(2000 + trial)
        G = int(rng.integers(2, 5))
        W = int(rng.integers(1, 4))
        base, caps, cands = _instance(rng, G, 0, W, packed=True)
        n = cands.shape[0]
        if n > 8:
            caps = np.minimum(caps, 2)
            cands = cands[: int(caps.sum())]
            n = cands.shape[0]
        a_j = np.asarray(bfio_assign(*_jax_args(base, caps, cands),
                                     swap_iters=16))
        a_io = io_solver.solve_io(base, caps, cands)
        J_j = io_solver.objective(base, cands, a_j)
        J_io = io_solver.objective(base, cands, a_io)
        assert J_j <= J_io * 1.01 + 1e-9

        a_ex, v_ex = io_solver.solve_exact(base, caps, cands)
        assert J_j <= v_ex + G * W * cands.max() + 1e-9

    @pytest.mark.parametrize("method", ["xla", "pallas"])
    def test_batch_matches_single_and_solve_io(self, method):
        C, G, W = 4, 3, 2
        rng = np.random.default_rng(31)
        bases, capss, candss = [], [], []
        for _ in range(C):
            base, caps, cands = _instance(rng, G, 0, W, packed=True)
            n = int(caps.sum())
            bases.append(base)
            capss.append(caps)
            candss.append(cands)
        n_max = max(c.shape[0] for c in candss)
        base_b = jnp.asarray(np.stack(bases), jnp.float32)
        caps_b = jnp.asarray(np.stack(capss), jnp.int32)
        cands_b = jnp.zeros((C, n_max, W), jnp.float32)
        valid_b = np.zeros((C, n_max), bool)
        for c, cn in enumerate(candss):
            cands_b = cands_b.at[c, : cn.shape[0]].set(
                jnp.asarray(cn, jnp.float32))
            valid_b[c, : cn.shape[0]] = True
        n_admit = jnp.asarray([c.shape[0] for c in candss], jnp.int32)

        ab = np.asarray(bfio_assign_batch(
            base_b, caps_b, cands_b, jnp.asarray(valid_b), n_admit,
            swap_iters=16, method=method))
        for c in range(C):
            a1 = np.asarray(bfio_assign(
                base_b[c], caps_b[c], cands_b[c], jnp.asarray(valid_b[c]),
                n_admit[c], swap_iters=16))
            np.testing.assert_array_equal(ab[c], a1)
            n = candss[c].shape[0]
            a_io = io_solver.solve_io(bases[c], capss[c], candss[c])
            J_b = io_solver.objective(bases[c], candss[c], ab[c, :n])
            J_io = io_solver.objective(bases[c], candss[c], a_io)
            assert J_b <= J_io * 1.01 + 1e-9
