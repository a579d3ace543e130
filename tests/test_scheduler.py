"""Direct Scheduler unit tests: the chunked-prefill budget edge cases
(budget=0, budget >= prompt, mid-chunk EOS, preempted-then-resumed chunk
accounting) and the admission block gate — previously exercised only
indirectly through the engine."""
import warnings

warnings.filterwarnings("ignore")

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import make_policy
from repro.core.policies import Policy
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.serving import (
    EngineConfig,
    FIFOPreemption,
    LIFOPreemption,
    PagedKVCache,
    PreemptContext,
    Scheduler,
    ServeRequest,
    ServingEngine,
)


class _TakeAll(Policy):
    """Admit every waiting request onto worker 0 (capacities permitting;
    cap_assignment trims the excess)."""

    name = "take-all"

    def assign(self, ctx):
        return np.zeros(ctx.n_wait, dtype=np.int64)


class _Req:
    def __init__(self, rid, n):
        self.rid = rid
        self.tokens = np.arange(1, n + 1)


def _ctx(n_wait, caps=(4,)):
    from repro.core.policies import SchedulerContext
    from repro.core.workload import unit_drift

    return SchedulerContext(
        k=0, loads=np.zeros(len(caps)),
        counts=np.zeros(len(caps), dtype=np.int64),
        caps=np.asarray(caps, dtype=np.int64),
        wait_prefill=np.ones(n_wait),
        active_worker=np.zeros(0, dtype=np.int64),
        active_w=np.zeros(0), active_age=np.zeros(0, dtype=np.int64),
        active_remaining=np.zeros(0, dtype=np.int64),
        drift=unit_drift(), rng=np.random.default_rng(0))


class TestChunkPlanning:
    def test_budget_zero_means_not_chunked(self):
        """chunk=0 (the default) is the synchronous mode: no jobs, no
        plans, ``chunked`` False — the engine routes everything through
        one-shot prefill."""
        s = Scheduler(_TakeAll())
        assert not s.chunked
        assert s.budget == 0
        assert s.plan_chunks() == []

    def test_budget_defaults_to_chunk(self):
        s = Scheduler(_TakeAll(), prefill_chunk=8)
        assert s.chunked and s.budget == 8

    def test_budget_at_least_prompt_finishes_in_one_plan(self):
        """budget >= the whole prompt: one plan covers it and advance()
        retires the job immediately (the degenerate-to-sync case)."""
        s = Scheduler(_TakeAll(), prefill_chunk=64, prefill_budget=1000)
        s.register_job(3, _Req(0, 40), np.arange(40))
        plan = s.plan_chunks()
        assert plan == [(3, 0, 40)]
        assert s.advance(3, 40) is True
        assert s.job(3) is None and s.n_prefilling == 0
        assert s.plan_chunks() == []

    def test_budget_split_fcfs_across_jobs(self):
        """The step budget is consumed FCFS in admission order; a job
        never exceeds ``chunk`` tokens per plan."""
        s = Scheduler(_TakeAll(), prefill_chunk=8, prefill_budget=12)
        s.register_job(0, _Req(0, 20), np.arange(20))
        s.register_job(1, _Req(1, 20), np.arange(20))
        assert s.plan_chunks() == [(0, 0, 8), (1, 0, 4)]
        s.advance(0, 8)
        s.advance(1, 4)
        # next step resumes at the recorded offsets
        assert s.plan_chunks() == [(0, 8, 8), (1, 4, 4)]

    def test_tail_chunk_clipped_to_remaining(self):
        s = Scheduler(_TakeAll(), prefill_chunk=16)
        s.register_job(0, _Req(0, 20), np.arange(20))
        assert s.plan_chunks() == [(0, 0, 16)]
        assert s.advance(0, 16) is False
        assert s.plan_chunks() == [(0, 16, 4)]
        assert s.advance(0, 4) is True

    def test_job_dropped_mid_stream_leaves_no_plan(self):
        """A job that disappears mid-prefill (its request finished on an
        eos first token, or it was preempted) must stop consuming budget
        so the freed budget flows to the remaining jobs."""
        s = Scheduler(_TakeAll(), prefill_chunk=8, prefill_budget=8)
        s.register_job(0, _Req(0, 32), np.arange(32))
        s.register_job(1, _Req(1, 32), np.arange(32))
        assert s.plan_chunks() == [(0, 0, 8)]
        job = s.drop_job(0)
        assert job is not None and s.job(0) is None
        assert s.plan_chunks() == [(1, 0, 8)]
        assert s.drop_job(0) is None  # idempotent

    def test_preempted_then_resumed_accounting(self):
        """Preemption mid-prefill drops the job; a swap-resume
        re-registers it at the preserved offset and the remaining chunks
        pick up exactly where the victim stopped."""
        s = Scheduler(_TakeAll(), prefill_chunk=8)
        r = _Req(0, 30)
        s.register_job(5, r, np.arange(30))
        s.advance(5, 8)
        s.advance(5, 8)
        job = s.drop_job(5)           # preempted at done=16
        assert job.done == 16 and job.remaining == 14
        assert s.plan_chunks() == []
        # resumed on a different slot with the offset preserved
        s.register_job(2, r, job.tokens, done=job.done,
                       resume_token=job.resume_token)
        assert s.plan_chunks() == [(2, 16, 8)]
        assert s.advance(2, 8) is False
        assert s.plan_chunks() == [(2, 24, 6)]
        assert s.advance(2, 6) is True

    def test_resume_token_round_trips(self):
        """A recompute-on-resume job carries the pending decode token."""
        s = Scheduler(_TakeAll(), prefill_chunk=8)
        s.register_job(0, _Req(0, 10), np.arange(10), resume_token=42)
        assert s.job(0).resume_token == 42
        job = s.drop_job(0)
        assert job.resume_token == 42


class TestQueue:
    def test_requeue_goes_to_front(self):
        s = Scheduler(_TakeAll())
        a, b, c = _Req(0, 4), _Req(1, 4), _Req(2, 4)
        s.submit(a)
        s.submit(b)
        s.requeue(c)              # preempted victim outranks arrivals
        assert s.wait == [c, a, b]


class TestBlockGate:
    def test_budget_limits_admissions_in_order(self):
        s = Scheduler(_TakeAll())
        reqs = [_Req(i, 16) for i in range(4)]
        for r in reqs:
            s.submit(r)
        out = s.admit(_ctx(4), caps=np.array([4]),
                      block_budget=2, blocks_of=lambda r: 1)
        assert [r.rid for r, _ in out] == [0, 1]
        assert [r.rid for r in s.wait] == [2, 3]

    def test_gate_is_strict_fcfs(self):
        """The first request that does not fit stops admission — no
        head-of-line bypass by smaller later requests."""
        s = Scheduler(_TakeAll())
        big, small = _Req(0, 64), _Req(1, 4)
        s.submit(big)
        s.submit(small)
        out = s.admit(_ctx(2), caps=np.array([4]),
                      block_budget=2,
                      blocks_of=lambda r: len(r.tokens) // 16)
        assert out == []
        assert s.wait == [big, small]

    def test_no_gate_admits_all(self):
        s = Scheduler(_TakeAll())
        for i in range(3):
            s.submit(_Req(i, 8))
        out = s.admit(_ctx(3), caps=np.array([4]))
        assert len(out) == 3 and not s.wait


class TestChunkPastCapacity:
    """Chunked prefill growing past the block table must freeze (the
    documented append_tokens overflow semantics), not raise."""

    def _cache(self):
        return PagedKVCache.create(
            n_layers=1, n_blocks=8, block_size=16, n_kv_heads=2,
            head_dim=4, max_requests=2, max_blocks_per_req=2)

    def test_ensure_capacity_clamps_to_table_width(self):
        kv = self._cache()
        kv.admit(0, 16)
        # grow chunk by chunk to 3 blocks' worth of tokens — one past
        # the 2-wide table; pre-clamp this raised a numpy broadcast
        # ValueError on the table-row assignment
        for new_len in (32, 48):
            kv.ensure_capacity(0, new_len)
        assert len(kv.req_blocks[0]) == 2      # table full, list frozen
        assert (kv.block_tables[0] >= 0).all()
        assert int(kv.lengths[0]) == 48        # length keeps counting
        kv.ensure_capacity(0, 49)              # idempotent once frozen
        assert len(kv.req_blocks[0]) == 2

    def test_write_token_drops_overflow_on_frozen_slot(self):
        kv = self._cache()
        kv.admit(0, 16)
        kv.ensure_capacity(0, 48)              # frozen past the table
        k = jax.numpy.ones((2, 4))
        before = kv.k_pool
        kv.write_token(0, 0, k, k)             # pos 47 -> block 2: off-table
        assert kv.k_pool is before             # dropped, no pool write
        kv.set_length(0, 32)
        kv.write_token(0, 0, k, k)             # pos 31: last in-cap slot
        blk = int(kv.block_tables[0, 1])
        assert float(kv.k_pool[0, blk, 15].sum()) != 0.0


CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")


@pytest.fixture(scope="module")
def setup():
    params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
    mesh = make_local_mesh()
    return params, mesh


class TestMidChunkEos:
    """A request whose *first* token (produced when its last prefill
    chunk completes) already meets eos or the token budget must finish at
    prefill — not burn a decode step generating a token past its
    budget."""

    def _first_token(self, params, mesh, prompt, **ec_kw):
        r = ServeRequest(rid=0, tokens=prompt, max_new_tokens=4)
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                         **ec_kw),
            make_policy("fcfs"), mesh=mesh)
        eng.submit(r)
        eng.run(max_steps=200)
        return r.generated[0]

    @pytest.mark.parametrize("chunk", [0, 8])
    def test_eos_on_first_token_finishes_at_prefill(self, setup, chunk):
        params, mesh = setup
        prompt = np.arange(1, 25)
        eos = self._first_token(params, mesh, prompt, prefill_chunk=chunk)
        r = ServeRequest(rid=1, tokens=prompt, max_new_tokens=8,
                         eos_id=eos)
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                         prefill_chunk=chunk),
            make_policy("fcfs"), mesh=mesh)
        eng.submit(r)
        stats = eng.run(max_steps=200)
        assert r.done and r.generated == [eos]
        assert stats["tokens"] == 0      # no decode step ran for it
        assert eng.scheduler.n_prefilling == 0

    @pytest.mark.parametrize("chunk", [0, 8])
    def test_max_new_one_stops_at_prefill(self, setup, chunk):
        params, mesh = setup
        r = ServeRequest(rid=0, tokens=np.arange(1, 20),
                         max_new_tokens=1)
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                         prefill_chunk=chunk),
            make_policy("fcfs"), mesh=mesh)
        eng.submit(r)
        eng.run(max_steps=200)
        assert r.done and len(r.generated) == 1

    def test_chunked_matches_sync_on_edge_requests(self, setup):
        """The two prefill schedules agree on the edge semantics."""
        params, mesh = setup
        gens = {}
        for chunk in (0, 8):
            rs = [ServeRequest(rid=i, tokens=np.arange(1, 20 + i),
                               max_new_tokens=1 + i) for i in range(4)]
            eng = ServingEngine(
                CFG, params,
                EngineConfig(n_workers=1, slots_per_worker=4,
                             max_seq_len=64, prefill_chunk=chunk),
                make_policy("fcfs"), mesh=mesh)
            for r in rs:
                eng.submit(r)
            eng.run(max_steps=500)
            gens[chunk] = [r.generated for r in rs]
        assert gens[0] == gens[8]


class TestVictimSelection:
    def test_select_victim_empty_returns_none(self):
        s = Scheduler(_TakeAll())
        ctx = PreemptContext(
            slots=np.zeros(0, dtype=np.int64),
            admit_seq=np.zeros(0, dtype=np.int64),
            kv_tokens=np.zeros(0, dtype=np.int64),
            blocks_held=np.zeros(0, dtype=np.int64),
            prefilling=np.zeros(0, dtype=bool))
        assert s.select_victim(ctx) is None

    def test_default_policy_is_lifo(self):
        assert isinstance(Scheduler(_TakeAll()).preemption, LIFOPreemption)

    def test_pluggable_policy(self):
        s = Scheduler(_TakeAll(), preemption=FIFOPreemption())
        ctx = PreemptContext(
            slots=np.array([3, 7, 1]),
            admit_seq=np.array([5, 2, 9]),
            kv_tokens=np.array([10, 20, 30]),
            blocks_held=np.array([1, 2, 3]),
            prefilling=np.zeros(3, dtype=bool))
        assert s.select_victim(ctx) == 7      # oldest admit_seq
        s2 = Scheduler(_TakeAll())
        assert s2.select_victim(ctx) == 1     # newest admit_seq (LIFO)
