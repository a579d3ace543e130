"""Cache-backend + scheduler seam tests.

The contract under test (ISSUE 3 / ROADMAP open items):

* ``cache_backend="paged"`` is a pure memory-layout change: on dense
  models it must produce bit-identical engine stats AND generations to
  ``cache_backend="slot"`` (the ``"gather"`` paged attention oracle makes
  this exact — masked positions contribute exactly zero).
* Resident KV under paging tracks actual tokens, not G*B*max_seq_len.
* Chunked prefill interleaves admission waves with decode: per-step
  prompt work is bounded by the budget and active decoders advance every
  step (never starved), while a large-enough budget degenerates to the
  synchronous schedule.
* MoE models run end to end on the paged/chunked paths, and their
  generations are asserted equal too: the serving expert layer is
  dropless (``repro.models.moe.moe_ffn_dropless``), so a row's tokens
  never depend on the other rows of its batch — across decode buckets,
  between compact and full decode, and between the slot and paged
  layouts.  Only training keeps a capacity that drops.
"""
import warnings

warnings.filterwarnings("ignore")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import make_policy
from repro.launch.mesh import make_local_mesh
from repro.models import (
    init_cache,
    init_params,
    prefill_fn,
    split_params,
    supports_paged_stack,
)
from repro.serving import (
    EngineConfig,
    PagedCacheBackend,
    ServeRequest,
    ServingEngine,
    SlotCacheBackend,
    make_cache_backend,
)

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")
MOE_CFG = ModelConfig(name="tiny-moe", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, n_experts=4,
                      experts_per_token=2, moe_d_ff=64, vocab_size=128,
                      dtype="float32")
SSM_CFG = ModelConfig(name="tiny-ssm", family="ssm", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                      ssm_state=16, dtype="float32")

STAT_KEYS = ("steps", "tokens", "energy_j", "avg_imbalance", "time_s")


@pytest.fixture(scope="module")
def setup():
    params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
    mesh = make_local_mesh()
    return params, mesh


@pytest.fixture(scope="module")
def moe_setup():
    params, _ = split_params(init_params(MOE_CFG, jax.random.PRNGKey(1)))
    mesh = make_local_mesh()
    return params, mesh


def _requests(n=14, seed=3, max_new=(3, 10), plen=(4, 30)):
    rng = np.random.default_rng(seed)
    return [
        ServeRequest(
            rid=i,
            tokens=rng.integers(1, 128, size=int(rng.integers(*plen))),
            max_new_tokens=int(rng.integers(*max_new)))
        for i in range(n)
    ]


def _run(params, mesh, policy, reqs, *, cfg=CFG, G=2, B=4, max_seq_len=64,
         max_steps=1000, **ec_kw):
    eng = ServingEngine(
        cfg, params,
        EngineConfig(n_workers=G, slots_per_worker=B,
                     max_seq_len=max_seq_len, **ec_kw),
        make_policy(policy), mesh=mesh)
    for r in reqs:
        eng.submit(r)
    stats = eng.run(max_steps=max_steps)
    return eng, stats


class TestSlotPagedParity:
    @pytest.mark.parametrize("policy", ["fcfs", "jsq", "bfio_h0"])
    def test_stats_and_generations_identical(self, setup, policy):
        params, mesh = setup
        ra, rb = _requests(), _requests()
        _, sa = _run(params, mesh, policy, ra, cache_backend="slot")
        _, sb = _run(params, mesh, policy, rb, cache_backend="paged")
        for k in STAT_KEYS:
            assert sa[k] == sb[k], f"{k}: slot={sa[k]} paged={sb[k]}"
        for a, b in zip(ra, rb):
            assert a.generated == b.generated, f"request {a.rid} diverged"
            assert a.worker == b.worker

    def test_paged_attn_ref_impl_stats_parity(self, setup):
        """The standalone jnp oracle kernel path: stats parity is exact
        (scheduling never reads token values); generations are close but
        not bit-pinned, so only stats are compared."""
        params, mesh = setup
        ra, rb = _requests(), _requests()
        _, sa = _run(params, mesh, "jsq", ra, cache_backend="slot")
        _, sb = _run(params, mesh, "jsq", rb, cache_backend="paged",
                     paged_attn_impl="ref")
        for k in STAT_KEYS:
            assert sa[k] == sb[k]

    def test_ref_engine_matches_paged_vec(self, setup):
        """Transitivity check: the seed ref engine == slot vec == paged."""
        params, mesh = setup
        ra, rb = _requests(), _requests()
        _, sa = _run(params, mesh, "fcfs", ra, engine_mode="ref")
        _, sb = _run(params, mesh, "fcfs", rb, cache_backend="paged")
        for k in STAT_KEYS:
            assert sa[k] == sb[k]
        for a, b in zip(ra, rb):
            assert a.generated == b.generated

    def test_paged_decode_logits_match_slot(self, setup):
        """Model-level oracle check: one decode step through the paged
        path reproduces the contiguous decode bit-for-bit."""
        from repro.models import decode_fn, paged_decode_fn

        params, mesh = setup
        rng = np.random.default_rng(5)
        ec = EngineConfig(n_workers=1, slots_per_worker=3, max_seq_len=64,
                          paged_block_size=16)
        slot_b = SlotCacheBackend(CFG, params, ec, mesh)
        paged_b = PagedCacheBackend(CFG, params, ec, mesh)
        lens = np.array([13, 40, 1], np.int32)
        toks = np.zeros((3, 64), np.int32)
        for i, L in enumerate(lens):
            toks[i, :L] = rng.integers(1, 128, size=L)
        batch = {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lens)}
        _, mini = prefill_fn(CFG, params, batch, max_len=64, mesh=mesh)
        src = np.arange(3)
        slot_b.write_prefill(mini, src, src)
        paged_b.write_prefill(mini, src, src)
        step_toks = np.array([7, 11, 13], np.int32)
        nxt_slot = slot_b.decode(step_toks, np.arange(3), 3)
        nxt_paged = paged_b.decode(step_toks, np.arange(3), 3)
        assert np.array_equal(nxt_slot, nxt_paged)
        # and the pallas kernel agrees with the contiguous logits closely
        logits_slot, _ = decode_fn(
            CFG, params, slot_b.cache, jnp.asarray(step_toks), mesh=mesh)
        kv = paged_b.kv
        nxt_pl, _, _, _ = paged_decode_fn(
            CFG, params, kv.k_pool, kv.v_pool,
            jnp.asarray(kv.block_tables[:3]), jnp.asarray(kv.lengths[:3]),
            jnp.full(3, paged_b.n_blocks, jnp.int32),
            jnp.zeros(3, jnp.int32), jnp.asarray(step_toks),
            block_size=16, attn_impl="pallas", mesh=mesh)
        del logits_slot  # greedy tokens are the comparable artifact
        assert np.array_equal(np.asarray(nxt_pl), nxt_slot)


class TestResidentKV:
    def test_resident_tracks_tokens_and_frees(self, setup):
        params, mesh = setup
        reqs = _requests(n=6, seed=7, plen=(4, 20))
        eng, _ = _run(params, mesh, "jsq", reqs, G=4, B=8,
                      cache_backend="paged", paged_block_size=16)
        dense = eng.backend.pool_bytes()       # slot layout pins this
        assert 0 < eng.kv_peak_bytes < 0.25 * dense
        # all requests completed -> every block returned to the pool
        assert eng.backend.resident_kv_bytes() == 0
        assert eng.backend.kv.allocator.n_free == eng.backend.n_blocks

    def test_unsupported_family_rejected(self):
        # params never touched: the backend rejects the family up front
        mesh = make_local_mesh()
        assert not supports_paged_stack(SSM_CFG)
        with pytest.raises(ValueError, match="attention-family"):
            ServingEngine(SSM_CFG, None,
                          EngineConfig(cache_backend="paged"),
                          make_policy("fcfs"), mesh=mesh)

    def test_decode_past_max_seq_len_matches_slot(self, setup):
        """A request whose decode outgrows max_seq_len: the slot layout
        silently drops the overflow KV writes and keeps decoding on the
        frozen cache; the paged backend must do the same (stop growing
        the block table) instead of overflowing it."""
        params, mesh = setup
        out = {}
        for backend in ("slot", "paged"):
            r = ServeRequest(rid=0, tokens=np.arange(1, 9),
                             max_new_tokens=40)
            _, s = _run(params, mesh, "fcfs", [r], G=1, B=1,
                        max_seq_len=32, cache_backend=backend,
                        paged_block_size=16)
            assert r.done and len(r.generated) == 40
            out[backend] = (s, r.generated)
        for k in STAT_KEYS:
            assert out["slot"][0][k] == out["paged"][0][k]
        assert out["slot"][1] == out["paged"][1]

    def test_block_size_must_divide_max_seq(self, setup):
        params, mesh = setup
        with pytest.raises(ValueError, match="divide"):
            ServingEngine(CFG, params,
                          EngineConfig(max_seq_len=64, paged_block_size=24,
                                       cache_backend="paged"),
                          make_policy("fcfs"), mesh=mesh)


class TestChunkedPrefill:
    @pytest.mark.parametrize("backend", ["slot", "paged"])
    def test_decode_never_starved_and_budget_respected(self, setup, backend):
        """An admission wave of long prompts lands while requests are
        decoding: every step processes at most `budget` prompt tokens and
        every already-decoding request advances every step."""
        params, mesh = setup
        chunk = 16
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=6, max_seq_len=64,
                         cache_backend=backend, prefill_chunk=chunk),
            make_policy("fcfs"), mesh=mesh)
        warm = _requests(n=2, seed=1, plen=(4, 8), max_new=(30, 31))
        for r in warm:
            eng.submit(r)
        for _ in range(3):
            eng.step()
        wave = _requests(n=4, seed=2, plen=(60, 61), max_new=(2, 3))
        for r in wave:
            eng.submit(r)
        while not all(r.done for r in wave):
            gen_before = [len(r.generated) for r in warm]
            info = eng.step()
            assert info["prefill_tokens"] <= chunk
            for r, before in zip(warm, gen_before):
                assert len(r.generated) == before + 1, \
                    "active decoder starved during the admission wave"
            assert eng.steps < 200
        # wave prompts were chunked: 60 tokens / 16 per step needs >= 4
        # steps per request, FCFS -> admission never ran them in one step
        assert all(r.done for r in wave)

    def test_large_budget_degenerates_to_sync_schedule(self, setup):
        """budget >= the whole wave => chunked scheduling == synchronous
        scheduling (bit-identical stats)."""
        params, mesh = setup
        ra, rb = _requests(), _requests()
        _, sa = _run(params, mesh, "jsq", ra)
        _, sb = _run(params, mesh, "jsq", rb, prefill_chunk=64,
                     prefill_budget=64 * 64)
        for k in STAT_KEYS:
            assert sa[k] == sb[k], f"{k}: sync={sa[k]} chunked={sb[k]}"

    @pytest.mark.parametrize("backend", ["slot", "paged"])
    def test_chunked_slot_paged_parity(self, setup, backend):
        """Chunked prefill itself is backend-invariant (gather oracle)."""
        params, mesh = setup
        ra, rb = _requests(seed=9), _requests(seed=9)
        _, sa = _run(params, mesh, "jsq", ra, cache_backend="slot",
                     prefill_chunk=8)
        _, sb = _run(params, mesh, "jsq", rb, cache_backend=backend,
                     prefill_chunk=8)
        for k in STAT_KEYS:
            assert sa[k] == sb[k]
        for a, b in zip(ra, rb):
            assert a.generated == b.generated

    def test_chunk_prefill_matches_full_prefill(self, setup):
        """Numerics: two chunks reproduce one-shot prefill to fp32
        tolerance (different attention kernels, same math)."""
        params, mesh = setup
        rng = np.random.default_rng(13)
        L = 24
        prompt = rng.integers(1, 128, size=L).astype(np.int32)
        batch = {"tokens": jnp.asarray(prompt[None]),
                 "lengths": jnp.asarray(np.array([L], np.int32))}
        logits_full, cache_full = prefill_fn(CFG, params, batch,
                                             max_len=64, mesh=mesh)
        ec = EngineConfig(n_workers=1, slots_per_worker=1, max_seq_len=64)
        backend = SlotCacheBackend(CFG, params, ec, mesh)
        c = 14
        toks = np.zeros((1, c), np.int32)
        toks[0, :c] = prompt[:c]
        backend.prefill_chunk(toks, np.array([0], np.int32),
                              np.array([c], np.int32), np.array([0]))
        toks2 = np.zeros((1, c), np.int32)
        toks2[0, :L - c] = prompt[c:]
        logits = backend.prefill_chunk(toks2, np.array([c], np.int32),
                                       np.array([L - c], np.int32),
                                       np.array([0]))
        np.testing.assert_allclose(logits[0], np.asarray(logits_full)[0],
                                   atol=2e-4, rtol=2e-4)
        got_k = np.asarray(backend.cache["blocks"]["k"])[:, 0, :L]
        want_k = np.asarray(cache_full["blocks"]["k"])[:, 0, :L]
        np.testing.assert_allclose(got_k, want_k, atol=2e-5)
        assert int(np.asarray(backend.cache["lengths"])[0]) == L

    def test_policy_sees_prefill_progress(self, setup):
        """SchedulerContext.active_prefill_remaining is populated under
        chunking and zero otherwise."""
        from repro.core.policies import Policy

        params, mesh = setup
        seen = []

        class Probe(Policy):
            name = "probe"

            def assign(self, ctx):
                if ctx.active_prefill_remaining is not None \
                        and len(ctx.active_prefill_remaining):
                    seen.append(ctx.active_prefill_remaining.copy())
                out = np.full(ctx.n_wait, -1, dtype=np.int64)
                caps = ctx.caps.copy()
                for i in range(ctx.n_admit):
                    g = int(np.argmax(caps))
                    if caps[g] <= 0:
                        break
                    out[i] = g
                    caps[g] -= 1
                return out

        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=4, max_seq_len=64,
                         prefill_chunk=8),
            Probe(), mesh=mesh)
        for r in _requests(n=6, seed=4, plen=(30, 40)):
            eng.submit(r)
        eng.run(max_steps=500)
        assert any((s > 0).any() for s in seen), \
            "policy never observed in-flight chunk progress"

    def test_chunked_rejected_for_non_attn_families(self):
        mesh = make_local_mesh()
        with pytest.raises(ValueError, match="chunked prefill"):
            ServingEngine(SSM_CFG, None,
                          EngineConfig(prefill_chunk=16),
                          make_policy("fcfs"), mesh=mesh)
        # a budget-only config must hit the same gate (budget implies
        # chunking), and sliding-window configs fail at construction,
        # not mid-serving
        with pytest.raises(ValueError, match="chunked prefill"):
            ServingEngine(SSM_CFG, None,
                          EngineConfig(prefill_budget=16),
                          make_policy("fcfs"), mesh=mesh)
        swin = ModelConfig(name="tiny-swin", family="dense", n_layers=2,
                           d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                           vocab_size=128, sliding_window=16,
                           dtype="float32")
        with pytest.raises(ValueError, match="sliding"):
            ServingEngine(swin, None, EngineConfig(prefill_chunk=8),
                          make_policy("fcfs"), mesh=mesh)

    @pytest.mark.parametrize("backend", ["slot", "paged"])
    def test_empty_prompt_under_chunking(self, setup, backend):
        """A zero-length prompt has no chunk work: it must take the
        synchronous prefill path instead of crashing the paged backend
        or leaving a phantom prefill job behind."""
        params, mesh = setup
        reqs = [ServeRequest(rid=0, tokens=np.array([], dtype=np.int64),
                             max_new_tokens=3),
                ServeRequest(rid=1, tokens=np.arange(1, 20),
                             max_new_tokens=3)]
        eng, _ = _run(params, mesh, "fcfs", reqs, G=1, B=2,
                      cache_backend=backend, prefill_chunk=8)
        assert all(r.done for r in reqs)
        assert eng.scheduler.n_prefilling == 0

    def test_budget_alone_enables_chunking(self, setup):
        """--prefill-budget without --prefill-chunk must not be inert."""
        params, mesh = setup
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=4, max_seq_len=64,
                         prefill_budget=8),
            make_policy("fcfs"), mesh=mesh)
        assert eng.scheduler.chunked and eng.scheduler.budget == 8
        for r in _requests(n=4, seed=2, plen=(20, 30)):
            eng.submit(r)
        info = eng.step()
        assert 0 < info["prefill_tokens"] <= 8

    def test_ref_mode_rejects_new_seams(self, setup):
        params, mesh = setup
        with pytest.raises(ValueError, match="ref"):
            ServingEngine(CFG, params,
                          EngineConfig(engine_mode="ref",
                                       cache_backend="paged"),
                          make_policy("fcfs"), mesh=mesh)
        with pytest.raises(ValueError, match="ref"):
            ServingEngine(CFG, params,
                          EngineConfig(engine_mode="ref", prefill_chunk=8),
                          make_policy("fcfs"), mesh=mesh)


class TestMoEFamily:
    """MoE engine runs on the paged/chunked paths.

    The serving expert layer is dropless, so generations hold across
    batch composition: each bucket routes its own rows only, and padding
    rows and columns route nowhere.  Stats parity holds as for dense
    models — admission, loads, and completion times never read token
    values (eos disabled).
    """

    def test_moe_paged_chunked_smoke(self, moe_setup):
        params, mesh = moe_setup
        ra = _requests(n=10, seed=6)
        rb = _requests(n=10, seed=6)
        _, sa = _run(params, mesh, "jsq", ra, cfg=MOE_CFG,
                     cache_backend="slot")
        _, sb = _run(params, mesh, "jsq", rb, cfg=MOE_CFG,
                     cache_backend="paged", prefill_chunk=16)
        assert all(r.done for r in rb)
        # scheduling metrics that ignore chunk timing shifts match only
        # when chunking is off; with chunking on we assert completion and
        # token counts (every request generated its full budget)
        assert sb["tokens"] == sa["tokens"]
        for a, b in zip(ra, rb):
            assert len(a.generated) == len(b.generated)

    def test_moe_stats_parity_without_chunking(self, moe_setup):
        params, mesh = moe_setup
        ra = _requests(n=10, seed=8)
        rb = _requests(n=10, seed=8)
        _, sa = _run(params, mesh, "jsq", ra, cfg=MOE_CFG,
                     cache_backend="slot")
        _, sb = _run(params, mesh, "jsq", rb, cfg=MOE_CFG,
                     cache_backend="paged")
        for k in STAT_KEYS:
            assert sa[k] == sb[k]
        for a, b in zip(ra, rb):
            assert a.generated == b.generated, f"request {a.rid} diverged"

    def test_moe_compact_decode_matches_full_decode(self, moe_setup):
        """Full decode (``engine_mode="ref"``: every slot, one bucket of
        G x B rows) against compact decode on the paged pool (the active
        rows in the smallest bucket, padding rows routed nowhere)."""
        params, mesh = moe_setup
        ra = _requests(n=10, seed=5)
        rb = _requests(n=10, seed=5)
        _, sa = _run(params, mesh, "fcfs", ra, cfg=MOE_CFG,
                     engine_mode="ref")
        _, sb = _run(params, mesh, "fcfs", rb, cfg=MOE_CFG,
                     cache_backend="paged")
        for k in STAT_KEYS:
            assert sa[k] == sb[k]
        for a, b in zip(ra, rb):
            assert a.generated == b.generated, f"request {a.rid} diverged"

    @pytest.mark.parametrize("backend", ["slot", "paged"])
    def test_moe_generations_hold_across_buckets(self, moe_setup, backend):
        """Each request served alone (one row in the smallest buckets)
        generates what it generates among nine others (chunked prefill,
        padding columns present)."""
        params, mesh = moe_setup
        together = _requests(n=10, seed=6)
        _run(params, mesh, "jsq", together, cfg=MOE_CFG,
             cache_backend=backend, prefill_chunk=16)
        for want in together[:3]:
            alone = _requests(n=10, seed=6)[want.rid]
            _run(params, mesh, "jsq", [alone], cfg=MOE_CFG,
                 cache_backend=backend, prefill_chunk=16)
            assert alone.generated == want.generated, want.rid


class TestBackendFactory:
    def test_make_cache_backend_names(self, setup):
        params, mesh = setup
        ec = EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64)
        assert make_cache_backend("slot", CFG, params, ec, mesh).name \
            == "slot"
        assert make_cache_backend("paged", CFG, params, ec, mesh).name \
            == "paged"
        with pytest.raises(ValueError, match="unknown cache backend"):
            make_cache_backend("mmap", CFG, params, ec, mesh)

    def test_slot_cache_property_roundtrip(self, setup):
        """engine.cache keeps working (ref path + existing tests)."""
        params, mesh = setup
        eng = ServingEngine(CFG, params,
                            EngineConfig(n_workers=1, slots_per_worker=2,
                                         max_seq_len=64),
                            make_policy("fcfs"), mesh=mesh)
        assert eng.cache is eng.backend.cache
        new = init_cache(CFG, 2, 64)
        eng.cache = new
        assert eng.backend.cache is new
