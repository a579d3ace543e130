"""Vectorized-engine regression tests: the ``engine_mode="vec"`` hot path
must be bit-identical on stats (steps, tokens, energy_j, avg_imbalance)
and generations to the seed ``engine_mode="ref"`` path across policies and
drift models; plus coverage for eos early-stop, over-subscribing policies,
over-long prompts, and the shared slot table."""
import warnings

warnings.filterwarnings("ignore")

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import make_policy
from repro.core.policies import Policy
from repro.core.workload import constant_drift, fractional_drift, unit_drift
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.serving import (
    EngineConfig,
    ServeRequest,
    ServingEngine,
    SlotTable,
    cap_assignment,
)
from repro.serving.engine import _decode_buckets

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")

STAT_KEYS = ("steps", "tokens", "energy_j", "avg_imbalance", "time_s")


@pytest.fixture(scope="module")
def setup():
    params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
    mesh = make_local_mesh()
    return params, mesh


def _requests(n=14, seed=3, max_new=(3, 10), tok_hi=128, plen=(4, 30)):
    rng = np.random.default_rng(seed)
    return [
        ServeRequest(
            rid=i,
            tokens=rng.integers(1, tok_hi, size=int(rng.integers(*plen))),
            max_new_tokens=int(rng.integers(*max_new)))
        for i in range(n)
    ]


def _run(params, mesh, mode, policy, reqs, *, G=2, B=4, drift=None,
         max_seq_len=64):
    eng = ServingEngine(
        CFG, params,
        EngineConfig(n_workers=G, slots_per_worker=B,
                     max_seq_len=max_seq_len, engine_mode=mode),
        policy if isinstance(policy, Policy) else make_policy(policy),
        mesh=mesh, drift=drift)
    for r in reqs:
        eng.submit(r)
    stats = eng.run(max_steps=1000)
    return eng, stats


def _assert_parity(params, mesh, policy, *, drift_factory=None, seed=3,
                   **kw):
    drift_a = drift_factory() if drift_factory else None
    drift_b = drift_factory() if drift_factory else None
    reqs_a = _requests(seed=seed)
    reqs_b = _requests(seed=seed)
    _, sa = _run(params, mesh, "ref", policy, reqs_a, drift=drift_a, **kw)
    _, sb = _run(params, mesh, "vec", policy, reqs_b, drift=drift_b, **kw)
    for k in STAT_KEYS:
        assert sa[k] == sb[k], f"{k}: ref={sa[k]} vec={sb[k]}"
    for ra, rb in zip(reqs_a, reqs_b):
        assert ra.generated == rb.generated, f"request {ra.rid} diverged"
        assert ra.worker == rb.worker


class TestRefVecParity:
    @pytest.mark.parametrize("policy", ["fcfs", "jsq", "pod2", "bfio_h0"])
    def test_policies(self, setup, policy):
        params, mesh = setup
        _assert_parity(params, mesh, policy)

    @pytest.mark.parametrize("drift_factory",
                             [unit_drift, constant_drift,
                              lambda: fractional_drift(6.0 / 38.0)])
    def test_drift_models(self, setup, drift_factory):
        params, mesh = setup
        _assert_parity(params, mesh, "bfio_h0",
                       drift_factory=drift_factory)

    def test_compact_decode_buckets_hit(self, setup):
        """A drain-heavy workload exercises the bucketed compact path."""
        params, mesh = setup
        reqs_a = _requests(n=20, seed=11, max_new=(2, 24))
        reqs_b = _requests(n=20, seed=11, max_new=(2, 24))
        _, sa = _run(params, mesh, "ref", "jsq", reqs_a, G=2, B=8)
        eng, sb = _run(params, mesh, "vec", "jsq", reqs_b, G=2, B=8)
        assert min(eng._buckets) < eng.N  # compact buckets exist
        for k in STAT_KEYS:
            assert sa[k] == sb[k]
        for ra, rb in zip(reqs_a, reqs_b):
            assert ra.generated == rb.generated


@pytest.mark.parametrize("N", [1, 3, 4, 8, 32, 64])
def test_bucket_ladder_runs_from_one_row_to_the_slots(N):
    """The row ladder a program's batch is padded to starts at one row,
    holds the slot count and climbs at most x4 a rung, so a plan of one
    or two live rows runs a one- or two-row program."""
    ladder = _decode_buckets(N)
    assert ladder[0] == 1 and ladder[-1] == N
    assert ladder == sorted(set(ladder))
    assert all(b <= 4 * a for a, b in zip(ladder, ladder[1:]))
    if N == 32:
        assert ladder == [1, 2, 4, 8, 32]


class TestEosEarlyStop:
    def test_eos_stops_generation(self, setup):
        params, mesh = setup
        probe = ServeRequest(rid=0, tokens=np.arange(1, 9),
                             max_new_tokens=12)
        _run(params, mesh, "vec", "fcfs", [probe], G=1, B=1)
        assert len(probe.generated) == 12
        # the engine checks eos on decoded tokens (positions >= 1)
        eos = probe.generated[len(probe.generated) // 2]
        expect = next(j for j in range(1, 12)
                      if probe.generated[j] == eos) + 1
        stats = {}
        for mode in ("ref", "vec"):
            r = ServeRequest(rid=0, tokens=np.arange(1, 9),
                             max_new_tokens=12, eos_id=eos)
            _, stats[mode] = _run(params, mesh, mode, "fcfs", [r],
                                  G=1, B=1)
            assert r.done
            assert len(r.generated) == expect < 12
            assert r.generated[-1] == eos
        for k in STAT_KEYS:
            assert stats["ref"][k] == stats["vec"][k]


class _RoguePolicy(Policy):
    """Assigns every waiting request to worker 0, ignoring capacities."""

    name = "rogue"

    def assign(self, ctx):
        return np.zeros(ctx.n_wait, dtype=np.int64)


class TestOverSubscription:
    @pytest.mark.parametrize("mode", ["ref", "vec"])
    def test_oversubscribing_policy_is_capped(self, setup, mode):
        params, mesh = setup
        reqs = _requests(n=8, seed=5)
        eng, _ = _run(params, mesh, mode, _RoguePolicy(), reqs, G=2, B=2)
        assert all(r.done for r in reqs)
        assert all(r.worker == 0 for r in reqs)  # excess waited, not crashed
        assert not eng.wait

    def test_table_allocate_overflow_raises(self):
        t = SlotTable(2, 2)
        with pytest.raises(RuntimeError, match="over-subscribed"):
            t.allocate(np.array([0, 0, 0]))


class TestPrefillOverflow:
    @pytest.mark.parametrize("mode", ["ref", "vec"])
    def test_long_prompt_truncated(self, setup, mode):
        params, mesh = setup
        rng = np.random.default_rng(2)
        r = ServeRequest(rid=0, tokens=rng.integers(1, 128, size=100),
                         max_new_tokens=4)
        eng, _ = _run(params, mesh, mode, "fcfs", [r], G=1, B=1,
                      max_seq_len=32)
        assert r.done and len(r.generated) == 4
        # the prompt was clamped to max_seq_len at prefill; lengths then
        # grew only by the decoded tokens (3 decode steps after the first)
        assert int(np.asarray(eng.cache["lengths"]).max()) <= 32 + 3


class TestSlotTable:
    def test_loads_counts_caps(self):
        t = SlotTable(2, 3)
        slots = t.allocate(np.array([1, 0, 1]))
        t.load[slots] = [5.0, 2.0, 3.0]
        assert np.array_equal(t.counts(), [1, 2])
        assert np.array_equal(t.loads(), [2.0, 8.0])
        assert np.array_equal(t.caps(), [2, 1])
        # slots fill each worker's range in order
        assert np.array_equal(slots, [3, 0, 4])
        t.release(slots[:1])
        assert np.array_equal(t.counts(), [1, 1])
        assert t.load[slots[0]] == 0.0

    def test_cap_assignment(self):
        caps = np.array([1, 2])
        a = np.array([0, 0, 1, -1, 1, 1])
        out = cap_assignment(a, caps)
        assert np.array_equal(out, [0, -1, 1, -1, 1, -1])
        # no-op when within capacity
        a2 = np.array([-1, 1, 0])
        assert np.array_equal(cap_assignment(a2, np.array([1, 1])), a2)
