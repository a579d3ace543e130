"""Preemption + prefix-caching parity fuzz.

Randomized admission/growth/preempt/resume sequences (hypothesis when
available, seeded ``random`` fallback otherwise) hardening the paged
backend's memory-pressure subsystem against its two oracles:

(a) slot-vs-paged stats parity whenever the pool never exhausts —
    preemption machinery armed but never firing must be a no-op;
(b) generations bit-identical under swap-preemption (host-staged blocks
    restore exactly; dense decode rows are batch-composition invariant);
(c) allocator refcounts return to zero at drain — every preempt/resume/
    COW/share path hands its blocks back;
(d) prefix-cache hits never change generations on dense models (equal
    token prefix => equal KV bits, copy-on-write isolates divergence).
"""
import warnings

warnings.filterwarnings("ignore")

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import make_policy
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.serving import (
    BlockAllocator,
    EngineConfig,
    PagedKVCache,
    PrefixIndex,
    ServeRequest,
    ServingEngine,
    make_preemption_policy,
)
from repro.serving.preemption import (
    SWAP_TILE_BLOCKS,
    swap_in_blocks,
    swap_out_blocks,
)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def fuzz_seeds(n_fallback: int, max_seed: int = 10_000):
    """Property-test shim: @given(seed=...) under hypothesis, else a
    seeded parametrize sweep (deterministic CI without the dependency)."""
    if HAVE_HYPOTHESIS:
        def deco(fn):
            return settings(max_examples=n_fallback, deadline=None)(
                given(seed=st.integers(0, max_seed))(fn))
        return deco
    return pytest.mark.parametrize("seed", range(n_fallback))


CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")

STAT_KEYS = ("steps", "tokens", "energy_j", "avg_imbalance", "time_s")


@pytest.fixture(scope="module")
def setup():
    params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
    mesh = make_local_mesh()
    return params, mesh


# ----------------------------------------------------------------------
# Allocator fuzz: refcount model checked against random op sequences
# ----------------------------------------------------------------------

class TestAllocatorFuzz:
    @fuzz_seeds(8)
    def test_refcounts_match_shadow_model(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(4, 24))
        a = BlockAllocator(n)
        shadow: dict[int, int] = {}   # block -> refcount
        for _ in range(200):
            op = rng.integers(0, 3)
            if op == 0:               # alloc
                k = int(rng.integers(1, 4))
                if k > a.n_free:
                    with pytest.raises(MemoryError):
                        a.alloc(k)
                else:
                    for b in a.alloc(k):
                        assert b not in shadow
                        shadow[b] = 1
            elif op == 1 and shadow:  # add_ref
                b = int(rng.choice(list(shadow)))
                a.add_ref(b)
                shadow[b] += 1
            elif op == 2 and shadow:  # free
                b = int(rng.choice(list(shadow)))
                a.free([b])
                shadow[b] -= 1
                if shadow[b] == 0:
                    del shadow[b]
            assert a.n_free == a.n_blocks - len(shadow)
            for b, c in shadow.items():
                assert a.ref_count(b) == c
        # drain: every surviving reference released -> pool whole again
        for b, c in list(shadow.items()):
            a.free([b] * c)
        assert a.n_free == a.n_blocks
        assert (a._refs == 0).all()

    @fuzz_seeds(8)
    def test_three_state_partition_with_prefix_retention(self, seed):
        """Random alloc/register/pin/free sequences with a prefix index
        attached, against a shadow model of the persistent-evictor
        lifecycle: every block is exactly one of {free,
        cached-and-indexed, referenced}, reclaim evicts the index entry
        before the block is handed back out, and reviving a cached
        block never aliases a concurrently reclaimed one."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(4, 24))
        a = BlockAllocator(n)
        idx = PrefixIndex()
        a.prefix = idx
        refs: dict[int, int] = {}       # referenced shadow
        cached: set[int] = set()        # cached shadow
        registered: set[int] = set()    # indexed shadow
        key_n = 0
        for _ in range(300):
            op = rng.integers(0, 4)
            if op == 0:               # alloc (reclaims LRU when dry)
                k = int(rng.integers(1, 4))
                if k > a.n_free + a.n_cached:
                    with pytest.raises(MemoryError):
                        a.alloc(k)
                else:
                    reclaim = max(k - a.n_free, 0)
                    r0 = a.blocks_reclaimed
                    for b in a.alloc(k):
                        # a handed-out block can never be one some
                        # concurrent revive holds a reference to
                        assert b not in refs
                        if b in cached:          # reclaimed
                            cached.discard(b)
                            registered.discard(b)
                        # reclaim evicted the entry before reuse
                        assert not idx.contains_block(b)
                        refs[b] = 1
                    assert a.blocks_reclaimed - r0 == reclaim
            elif op == 1 and refs:    # index a referenced block
                b = int(rng.choice(list(refs)))
                if b not in registered:
                    span = (key_n,)   # unique content per entry
                    idx.register(idx.chain(None, span), None, span, b)
                    key_n += 1
                    registered.add(b)
            elif op == 2 and (refs or cached):
                # add_ref: pin a referenced block / revive a cached one
                b = int(rng.choice(list(refs) + sorted(cached)))
                v0 = a.blocks_revived
                a.add_ref(b)
                if b in cached:
                    cached.discard(b)
                    refs[b] = 1
                    assert a.blocks_revived == v0 + 1
                else:
                    refs[b] += 1
            elif op == 3 and refs:    # free (indexed last-ref -> cached)
                b = int(rng.choice(list(refs)))
                a.free([b])
                refs[b] -= 1
                if refs[b] == 0:
                    del refs[b]
                    if b in registered:
                        cached.add(b)
            # exact three-state partition after every op
            assert a.n_free == a.n_blocks - len(refs) - len(cached)
            assert a.n_cached == len(cached)
            assert len(idx) == len(registered)
            for b in cached:
                assert a.ref_count(b) == 0 and a.is_live(b)
                assert idx.contains_block(b)
            for b, c in refs.items():
                assert a.ref_count(b) == c
        # drain: every reference released; indexed blocks persist cached
        for b, c in list(refs.items()):
            a.free([b] * c)
        assert (a._refs == 0).all()
        assert a.n_free + a.n_cached == a.n_blocks
        assert a.n_cached == len(registered)
        assert len(idx) == a.n_cached

    def test_reclaim_is_lru_ordered_and_touch_refreshes(self):
        """Cached blocks are reclaimed oldest-first; touch() moves a
        block to the MRU end so a recent hit is reclaimed last."""
        a = BlockAllocator(3)
        idx = PrefixIndex()
        a.prefix = idx
        blocks = a.alloc(3)
        for i, b in enumerate(blocks):
            idx.register(idx.chain(None, (i,)), None, (i,), b)
        for b in blocks:
            a.free([b])               # cache order = free order
        assert a.n_cached == 3
        a.touch(blocks[0])            # hit: oldest becomes MRU
        got = a.alloc(2)              # reclaims the two LRU blocks
        assert got == [blocks[1], blocks[2]]
        assert not idx.contains_block(blocks[1])
        assert not idx.contains_block(blocks[2])
        assert idx.contains_block(blocks[0])
        assert a.is_live(blocks[0])

    @fuzz_seeds(4)
    def test_double_free_never_corrupts_free_list(self, seed):
        rng = np.random.default_rng(seed)
        a = BlockAllocator(8)
        live = a.alloc(5)
        freed = live.pop()
        a.free([freed])
        for _ in range(10):
            with pytest.raises(ValueError, match="double free"):
                a.free([freed])
        assert a.n_free == 4
        a.free(live)
        assert a.n_free == 8


# ----------------------------------------------------------------------
# Swap staging: tiled copies restore bit-for-bit
# ----------------------------------------------------------------------

class TestSwapStaging:
    @pytest.mark.parametrize("n_blocks", [1, 7, SWAP_TILE_BLOCKS + 3])
    def test_swap_roundtrip_bit_exact(self, n_blocks):
        """swap_out + swap_in over scattered (and re-scattered) block ids
        is the identity on content, including across tile boundaries."""
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        pool = jnp.asarray(rng.normal(size=(2, 64, 4, 2, 8)), jnp.float32)
        out_ids = rng.choice(64, size=n_blocks, replace=False)
        host = swap_out_blocks(pool, out_ids, tile=4)
        assert host.shape[1] == n_blocks
        np.testing.assert_array_equal(host, np.asarray(pool)[:, out_ids])
        in_ids = rng.choice(64, size=n_blocks, replace=False)
        pool2 = swap_in_blocks(pool, in_ids, host, tile=4)
        np.testing.assert_array_equal(
            np.asarray(pool2)[:, in_ids], host)

    def test_empty_swap(self):
        import jax.numpy as jnp

        pool = jnp.zeros((1, 4, 2, 1, 4))
        assert swap_out_blocks(pool, []) is None
        assert swap_in_blocks(pool, [], None) is pool

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="unknown preemption policy"):
            make_preemption_policy("round-robin")


# ----------------------------------------------------------------------
# Copy-on-write at the cache level (no model)
# ----------------------------------------------------------------------

class TestCopyOnWrite:
    def test_shared_partial_tail_copies_on_divergent_append(self):
        import jax.numpy as jnp

        cache = PagedKVCache.create(
            n_layers=1, n_blocks=16, block_size=8, n_kv_heads=1,
            head_dim=4, max_requests=4, max_blocks_per_req=4,
            dtype=jnp.float32)
        cache.prefix = PrefixIndex()
        cache.admit(0, 5)                       # A: one partial block
        (blk,) = cache.req_blocks[0]
        ((key, parent, span),) = cache.prefix.keys_for(
            [1, 2, 3, 4, 5], block_size=8)
        cache.prefix.register(key, parent, span, blk)
        cache.admit(1, 5, shared=(blk,))        # B shares A's tail block
        assert cache.allocator.ref_count(blk) == 2
        used_before = cache.used_blocks
        cache.append_token(1)                   # B's first divergent token
        new = cache.req_blocks[1][0]
        assert new != blk, "append into a shared block must COW"
        assert cache.allocator.ref_count(blk) == 1
        assert cache.allocator.ref_count(new) == 1
        assert cache.used_blocks == used_before + 1
        # A appends next: sole holder again, writes in place (no COW)
        cache.append_token(0)
        assert cache.req_blocks[0][0] == blk
        cache.release(0)
        cache.release(1)
        # persistent evictor (default): the indexed block survives its
        # last holder on the cached list; the COW copy (never indexed)
        # goes straight back to the free list
        assert cache.allocator.n_free == 15
        assert cache.allocator.n_cached == 1
        assert cache.allocator.is_live(blk)
        assert cache.allocator.ref_count(blk) == 0
        assert len(cache.prefix) == 1
        # reviving the cached block re-pins it for a new sharer
        cache.admit(2, 5, shared=(blk,))
        assert cache.allocator.ref_count(blk) == 1
        assert cache.allocator.n_cached == 0
        assert cache.allocator.blocks_revived == 1
        cache.release(2)

    def test_admission_scoped_evicts_with_last_holder(self):
        """evict='admission' pins the legacy lifetime: entry dies with
        the last resident holder's release."""
        cache = PagedKVCache.create(
            n_layers=1, n_blocks=16, block_size=8, n_kv_heads=1,
            head_dim=4, max_requests=4, max_blocks_per_req=4,
            prefix_evict="admission")
        cache.prefix = PrefixIndex()
        cache.admit(0, 5)
        (blk,) = cache.req_blocks[0]
        ((key, parent, span),) = cache.prefix.keys_for(
            [1, 2, 3, 4, 5], block_size=8)
        cache.prefix.register(key, parent, span, blk)
        cache.release(0)
        assert cache.allocator.n_free == 16
        assert cache.allocator.n_cached == 0
        assert len(cache.prefix) == 0           # eviction followed frees

    def test_append_demand_counts_cow_and_crossings(self):
        cache = PagedKVCache.create(
            n_layers=1, n_blocks=16, block_size=8, n_kv_heads=1,
            head_dim=4, max_requests=4, max_blocks_per_req=4)
        cache.admit(0, 8)                       # full block: next append
        slots = np.array([0])                   # crosses a boundary
        assert cache.append_demand(slots) == 1
        cache.admit(1, 5)
        (blk,) = cache.req_blocks[1]
        cache.admit(2, 5, shared=(blk,))        # shared tail: COW pending
        assert cache.append_demand(np.array([2])) == 1
        assert cache.append_demand(np.array([1])) == 1
        assert cache.append_demand(np.array([0, 1, 2])) == 3


# ----------------------------------------------------------------------
# Engine-level fuzz against the two oracles
# ----------------------------------------------------------------------

def _fuzz_requests(rng, n, vocab=128, shared_pool=None):
    reqs = []
    for i in range(n):
        if shared_pool is not None and rng.random() < 0.6:
            head = shared_pool[int(rng.integers(len(shared_pool)))]
            tail = rng.integers(1, vocab, size=int(rng.integers(1, 10)))
            tokens = np.concatenate([head, tail])
        else:
            tokens = rng.integers(1, vocab,
                                  size=int(rng.integers(1, 40)))
        reqs.append(ServeRequest(
            rid=i, tokens=tokens,
            max_new_tokens=int(rng.integers(1, 14)),
            eos_id=int(rng.integers(1, vocab)) if rng.random() < 0.2
            else -1))
    return reqs


def _clone(reqs):
    return [ServeRequest(rid=r.rid, tokens=r.tokens.copy(),
                         max_new_tokens=r.max_new_tokens, eos_id=r.eos_id)
            for r in reqs]


def _run(params, mesh, reqs, *, G, B, policy="jsq", max_seq_len=64,
         **ec_kw):
    eng = ServingEngine(
        CFG, params,
        EngineConfig(n_workers=G, slots_per_worker=B,
                     max_seq_len=max_seq_len, **ec_kw),
        make_policy(policy), mesh=mesh)
    for r in reqs:
        eng.submit(r)
    stats = eng.run(max_steps=20_000)
    return eng, stats


def _assert_drained(eng):
    """(c) three-state partition at drain: refcounts all zero, every
    block either free or cached-and-indexed, and the prefix index holds
    exactly the cached blocks (persistence: entries survive the drain,
    pinned one-to-one to LRU-cached blocks, never to recycled ones)."""
    alloc = eng.backend.kv.allocator
    assert (alloc._refs == 0).all()
    assert alloc.n_free + alloc.n_cached == alloc.n_blocks
    prefix = eng.backend.prefix
    if prefix is not None:
        assert len(prefix) == alloc.n_cached
        for b in alloc._cached:
            assert prefix.contains_block(b)
        rate = eng.stats()["prefix_hit_rate"]
        assert 0.0 <= rate <= 1.0
    else:
        assert alloc.n_cached == 0
        assert alloc.n_free == alloc.n_blocks


def _pool_for(eng, reqs, frac):
    """A pool at ``frac`` of the unconstrained peak, floored so a single
    request's lifetime demand (prompt + decode growth) always fits."""
    bs = eng.backend.block_size
    blk_bytes = eng.backend.pool_bytes() // eng.backend.n_blocks
    peak = -(-eng.kv_peak_bytes // blk_bytes)
    single = max(-(-(min(len(r.tokens), 64) + r.max_new_tokens) // bs)
                 for r in reqs)
    return max(int(peak * frac), single + 1, 2)


class TestEngineFuzz:
    @fuzz_seeds(4)
    def test_admission_growth_preempt_resume_sequences(self, setup, seed):
        params, mesh = setup
        rng = np.random.default_rng(seed)
        G = int(rng.integers(1, 3))
        B = int(rng.integers(2, 5))
        chunk = int(rng.choice([0, 8]))
        n = int(G * B * rng.uniform(1.5, 2.5))
        proto = _fuzz_requests(rng, n)

        # oracle: the contiguous slot layout (no pool, no preemption)
        ra = _clone(proto)
        _, sa = _run(params, mesh, ra, G=G, B=B, cache_backend="slot",
                     prefill_chunk=chunk)

        # (a) pool never exhausts -> armed preemption is a no-op
        rb = _clone(proto)
        eng_b, sb = _run(params, mesh, rb, G=G, B=B,
                         cache_backend="paged", prefill_chunk=chunk)
        assert eng_b.preemptions == 0
        for k in STAT_KEYS:
            assert sa[k] == sb[k], f"{k}: slot={sa[k]} paged={sb[k]}"
        for a, b in zip(ra, rb):
            assert a.generated == b.generated
        _assert_drained(eng_b)

        # (b) swap-preemption under a pool at ~half the peak demand:
        # bit-identical generations, full completion, zero recompute
        pool = _pool_for(eng_b, proto, rng.uniform(0.4, 0.7))
        rc = _clone(proto)
        eng_c, _ = _run(params, mesh, rc, G=G, B=B, cache_backend="paged",
                        prefill_chunk=chunk, paged_pool_blocks=pool,
                        preemption_mode="swap")
        assert all(r.done for r in rc)
        for a, c in zip(ra, rc):
            assert a.generated == c.generated, \
                f"request {a.rid} diverged under swap preemption"
        assert eng_c.tokens_recomputed == 0
        _assert_drained(eng_c)

        # recompute mode: completion + drain (token parity is not
        # promised — rebuilt prefill is not bit-pinned to decode)
        rd = _clone(proto)
        eng_d, _ = _run(params, mesh, rd, G=G, B=B, cache_backend="paged",
                        prefill_chunk=chunk, paged_pool_blocks=pool,
                        preemption_mode="recompute")
        assert all(r.done for r in rd)
        assert eng_d.tokens_swapped == 0
        _assert_drained(eng_d)

    @fuzz_seeds(3)
    def test_prefix_cache_never_changes_generations(self, setup, seed):
        """(d) shared-prefix workloads: hits occur, generations match the
        uncached slot oracle bit-for-bit, refcounts drain."""
        params, mesh = setup
        rng = np.random.default_rng(seed)
        G, B = 2, 4
        shared_pool = [rng.integers(1, 128, size=int(rng.integers(8, 30)))
                       for _ in range(2)]
        proto = _fuzz_requests(rng, 14, shared_pool=shared_pool)

        ra = _clone(proto)
        _, _ = _run(params, mesh, ra, G=G, B=B, cache_backend="slot")
        rb = _clone(proto)
        eng_b, sb = _run(params, mesh, rb, G=G, B=B,
                         cache_backend="paged", prefix_cache=True)
        assert sb["prefix_hits"] > 0, "shared prefixes never hit"
        for a, b in zip(ra, rb):
            assert a.generated == b.generated, \
                f"request {a.rid}: prefix-cache hit changed its output"
        _assert_drained(eng_b)

        # chunked admissions consult the index too (full-block hits are
        # pinned and the chunk job starts past them): generations still
        # bit-identical, refcounts still drain
        rc = _clone(proto)
        eng_c, _ = _run(params, mesh, rc, G=G, B=B, cache_backend="paged",
                        prefix_cache=True, prefill_chunk=8)
        for a, c in zip(ra, rc):
            assert a.generated == c.generated, \
                f"request {a.rid}: chunked prefix hit changed its output"
        _assert_drained(eng_c)

    @fuzz_seeds(2)
    def test_prefix_cache_under_pressure(self, setup, seed):
        """Sharing + swap preemption together: still bit-exact, still
        drains — COW, swap staging, and eviction compose."""
        params, mesh = setup
        rng = np.random.default_rng(seed)
        G, B = 1, 4
        shared_pool = [rng.integers(1, 128, size=20)]
        proto = _fuzz_requests(rng, 10, shared_pool=shared_pool)
        ra = _clone(proto)
        _, _ = _run(params, mesh, ra, G=G, B=B, cache_backend="slot")
        probe = _clone(proto)
        eng_p, _ = _run(params, mesh, probe, G=G, B=B,
                        cache_backend="paged")
        pool = _pool_for(eng_p, proto, 0.5)
        rb = _clone(proto)
        eng_b, _ = _run(params, mesh, rb, G=G, B=B, cache_backend="paged",
                        prefix_cache=True, paged_pool_blocks=pool,
                        preemption_mode="swap")
        assert all(r.done for r in rb)
        for a, b in zip(ra, rb):
            assert a.generated == b.generated
        _assert_drained(eng_b)


class TestChunkedPrefix:
    """Chunked-prefill admissions consulting the PrefixIndex (ROADMAP
    open item): full-block hits share the KV copy-free AND skip
    recompute of the hit prefix — a TTFT win, gens bit-identical."""

    def _shared_reqs(self, n=6, shared_len=40, sfx=4, seed=5):
        """Shared-system-prefix stream with one long-running holder:
        the index is admission-scoped (eager eviction when the last
        holder frees), so rid 0 decodes long enough that later waves
        admit while its registered prompt blocks are still resident."""
        rng = np.random.default_rng(seed)
        system = rng.integers(1, 128, size=shared_len)
        return [ServeRequest(
            rid=i,
            tokens=np.concatenate(
                [system, rng.integers(1, 128, size=sfx)]),
            max_new_tokens=24 if i == 0 else 4) for i in range(n)]

    def _run_counting(self, params, mesh, reqs, **ec_kw):
        """Like _run but also sums per-step chunk-prefill tokens."""
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                         cache_backend="paged", prefill_chunk=8, **ec_kw),
            make_policy("fcfs"), mesh=mesh)
        for r in reqs:
            eng.submit(r)
        prefill_tokens = 0
        while eng.wait or eng.table.active.any():
            prefill_tokens += eng.step()["prefill_tokens"]
        return eng, prefill_tokens

    def test_hits_skip_recompute(self, setup):
        params, mesh = setup
        proto = self._shared_reqs()
        oracle = _clone(proto)
        _run(params, mesh, oracle, G=1, B=2, cache_backend="slot")

        off = _clone(proto)
        _, toks_off = self._run_counting(params, mesh, off)
        on = _clone(proto)
        eng, toks_on = self._run_counting(params, mesh, on,
                                          prefix_cache=True)
        stats = eng.stats()
        assert stats["prefix_hits"] > 0, "chunked admissions never hit"
        # the TTFT win: hit prefixes are not re-prefilled, so the total
        # chunk-prefill volume strictly drops (2 full blocks per hit)
        assert toks_on < toks_off, (toks_on, toks_off)
        for a, b, c in zip(oracle, off, on):
            assert a.generated == b.generated == c.generated
        _assert_drained(eng)

    def test_recompute_accounting_excludes_seeded_tokens(self, setup):
        """Prefix-pinned tokens were never computed, so recompute-
        preempting a seeded mid-prefill job must not charge them to
        ``tokens_recomputed``."""
        params, mesh = setup
        reqs = self._shared_reqs(n=2)
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                         cache_backend="paged", prefill_chunk=8,
                         prefix_cache=True, preemption_mode="recompute"),
            make_policy("fcfs"), mesh=mesh)
        eng.submit(reqs[0])
        eng.step()                       # admit the holder
        while eng.scheduler.n_prefilling:
            eng.step()                   # finish + register its prompt
        eng.submit(reqs[1])
        eng.step()                       # admit: seeds 2 full blocks
        slot = reqs[1].slot
        job = eng.scheduler.job(slot)
        assert job is not None and job.seeded == 32
        assert job.done > job.seeded     # one chunk already ran
        expected = job.done - job.seeded
        before = eng.tokens_recomputed
        eng._preempt_slot(slot)
        assert eng.tokens_recomputed - before == expected
        stats = eng.run()                # requeued victim still finishes
        assert all(r.done and not r.failed for r in reqs)
        assert stats["preemptions"] == 1

    def test_full_cover_hit_leaves_final_token_computed(self, setup):
        """A prompt whose *every* block is indexed (exact multiple of
        the block size, seen before) must still compute its final
        position — the shared run is capped so the finishing chunk
        produces the logits the first token is sampled from."""
        params, mesh = setup
        rng = np.random.default_rng(9)
        prompt = rng.integers(1, 128, size=32)       # exactly 2 blocks
        proto = [ServeRequest(rid=i, tokens=prompt.copy(),
                              max_new_tokens=24 if i == 0 else 4)
                 for i in range(4)]
        oracle = _clone(proto)
        _run(params, mesh, oracle, G=1, B=2, cache_backend="slot")
        on = _clone(proto)
        eng, _ = self._run_counting(params, mesh, on, prefix_cache=True)
        assert eng.stats()["prefix_hits"] > 0
        for a, c in zip(oracle, on):
            assert a.generated == c.generated
        _assert_drained(eng)

    def test_preempt_restart_counts_admission_once(self, setup):
        """A recompute-preempted chunked job re-seeds its prefix on
        re-admission; the hit-rate counters must count the admission's
        lookup exactly once, not once per restart."""
        params, mesh = setup
        reqs = self._shared_reqs(n=2)
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                         cache_backend="paged", prefill_chunk=8,
                         prefix_cache=True, preemption_mode="recompute"),
            make_policy("fcfs"), mesh=mesh)
        eng.submit(reqs[0])
        eng.step()
        while eng.scheduler.n_prefilling:
            eng.step()
        eng.submit(reqs[1])
        eng.step()                       # admit: seeds + counts once
        q1, h1 = eng.backend.prefix.queries, eng.backend.prefix.hits
        assert q1 > 0 and h1 > 0
        slot = reqs[1].slot
        assert eng.scheduler.job(slot) is not None
        eng._preempt_slot(slot)          # restart -> re-seed (uncounted)
        eng.run()
        assert all(r.done and not r.failed for r in reqs)
        assert eng.backend.prefix.queries == q1
        assert eng.backend.prefix.hits == h1
        rate = eng.stats()["prefix_hit_rate"]
        assert 0.0 <= rate <= 1.0

    def test_hits_survive_last_holder(self, setup):
        """The lifetime bug: with every holder of a shared prefix
        finished, a later identical-prefix arrival must still hit
        (persistent LRU evictor) — admission-scoped measures zero."""
        params, mesh = setup
        proto = self._shared_reqs(n=4, seed=11)
        for r in proto:
            r.max_new_tokens = 4         # no long-running holder
        oracle = _clone(proto)
        _run(params, mesh, oracle, G=1, B=2, cache_backend="slot")
        stats, engines = {}, {}
        for mode in ("admission", "lru"):
            reqs = _clone(proto)
            eng = ServingEngine(
                CFG, params,
                EngineConfig(n_workers=1, slots_per_worker=2,
                             max_seq_len=64, cache_backend="paged",
                             prefill_chunk=8, prefix_cache=True,
                             prefix_evict=mode),
                make_policy("fcfs"), mesh=mesh)
            # staggered turns: each submitted after the previous drained
            for r in reqs:
                eng.submit(r)
                while eng.wait or eng.table.active.any():
                    eng.step()
            stats[mode], engines[mode] = eng.stats(), eng
            for a, b in zip(oracle, reqs):
                assert a.generated == b.generated
        assert stats["admission"]["prefix_hits"] == 0
        assert stats["lru"]["prefix_hits"] > 0
        assert stats["lru"]["prefix_revived"] > 0
        assert stats["lru"]["prefix_hit_rate"] > \
            stats["admission"]["prefix_hit_rate"]
        _assert_drained(engines["lru"])


class TestPressureDeterministic:
    """Non-fuzz regression anchors for the pressure machinery."""

    def test_pressure_actually_preempts(self, setup):
        """A long-decode workload through a half-sized pool must exercise
        the preemption path (not just admission gating)."""
        params, mesh = setup
        rng = np.random.default_rng(3)
        proto = [ServeRequest(rid=i,
                              tokens=rng.integers(1, 128, size=20),
                              max_new_tokens=30) for i in range(8)]
        probe = _clone(proto)
        eng_p, _ = _run(params, mesh, probe, G=1, B=4,
                        cache_backend="paged")
        pool = _pool_for(eng_p, proto, 0.5)
        rb = _clone(proto)
        eng, s = _run(params, mesh, rb, G=1, B=4, cache_backend="paged",
                      paged_pool_blocks=pool, preemption_mode="swap")
        assert eng.preemptions > 0
        assert s["tokens_swapped"] > 0
        assert all(len(r.generated) == 30 for r in rb)
        for a, b in zip(probe, rb):
            assert a.generated == b.generated
        _assert_drained(eng)

    @pytest.mark.parametrize("chunk", [0, 8])
    def test_recompute_resume_restores_overflow_length(self, setup, chunk):
        """A victim that decoded past max_seq_len on frozen KV keeps its
        RoPE position counter through a recompute rebuild — the
        max_seq_len-truncated token sequence must not reset lengths to
        the cap."""
        from repro.serving import PreemptedState

        params, mesh = setup
        r = ServeRequest(rid=0, tokens=np.arange(1, 30).astype(np.int64),
                         max_new_tokens=60)
        r.generated = [5] * 45
        r.preempted = PreemptedState(mode="recompute", length=70,
                                     next_token=5)
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                         cache_backend="paged", paged_block_size=16,
                         prefill_chunk=chunk, prefill_budget=chunk * 16),
            make_policy("fcfs"), mesh=mesh)
        eng.submit(r)
        eng.step()
        while eng.scheduler.n_prefilling:   # chunked rebuild spans steps
            eng.step()
        # rebuilt prefill covers only 64 tokens; the preempted length
        # (70) plus the finish step's decode append must be restored
        assert int(eng.backend.kv.lengths[r.slot]) == 71

    def test_growth_past_whole_pool_fails_that_request_only(self, setup):
        """A request whose decode growth exceeds the entire pool cannot
        be saved by preemption — it fails *alone* (per-request
        status/error channel) and the rest of the stream keeps serving;
        the seed raised MemoryError here and killed the engine step."""
        params, mesh = setup
        doomed = ServeRequest(rid=0, tokens=np.arange(1, 61),  # 4 blocks: fit
                              max_new_tokens=20)               # growth: no
        ok = ServeRequest(rid=1, tokens=np.arange(1, 9),
                          max_new_tokens=4)
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=256,
                         cache_backend="paged", paged_block_size=16,
                         paged_pool_blocks=4, preemption_mode="swap"),
            make_policy("fcfs"), mesh=mesh)
        eng.submit(doomed)
        eng.submit(ok)
        stats = eng.run(max_steps=20_000)    # must NOT raise
        assert doomed.status == "failed" and doomed.failed
        assert "exceeds the entire pool" in doomed.error
        assert doomed.done                   # terminal: t_finish is set
        assert eng.preemptions <= 1          # no thrash loop before failing
        assert stats["requests_failed"] == 1
        # the doomed request's blocks were released and the small request
        # completed untouched
        assert ok.status == "done" and ok.error is None
        assert len(ok.generated) == 4
        assert eng.backend.free_blocks == eng.backend.n_blocks

    def test_oversized_prompt_rejected_at_submit(self, setup):
        """Regression: a prompt that can never fit the pool used to
        surface as MemoryError mid-prefill; now submit() rejects it."""
        params, mesh = setup
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                         cache_backend="paged", paged_block_size=16,
                         paged_pool_blocks=2),
            make_policy("fcfs"), mesh=mesh)
        with pytest.raises(ValueError, match="never be admitted"):
            eng.submit(ServeRequest(rid=0, tokens=np.arange(1, 60),
                                    max_new_tokens=2))
        # a prompt that fits is accepted
        eng.submit(ServeRequest(rid=1, tokens=np.arange(1, 20),
                                max_new_tokens=2))
        assert len(eng.wait) == 1

    def test_preemption_mode_validated(self, setup):
        params, mesh = setup
        with pytest.raises(ValueError, match="preemption_mode"):
            ServingEngine(CFG, params,
                          EngineConfig(preemption_mode="drop"),
                          make_policy("fcfs"), mesh=mesh)

    def test_prefix_cache_requires_paged(self, setup):
        params, mesh = setup
        with pytest.raises(ValueError, match="prefix_cache"):
            ServingEngine(CFG, params,
                          EngineConfig(prefix_cache=True),
                          make_policy("fcfs"), mesh=mesh)


class TestDeviceLoopPool:
    def test_pooled_loop_completes_with_preemptions(self):
        from repro.serving import init_loop_state, make_device_serving_loop

        rng = np.random.default_rng(1)
        G, B, W = 4, 4, 64
        sizes = rng.uniform(5, 50, 40)
        rem = rng.integers(2, 10, 40)
        run = make_device_serving_loop(G, B, W, kv_pool=150.0)
        end = run(init_loop_state(G, B, sizes, rem, W), 400)
        assert int(end.tot_preempts) > 0
        assert int(end.slot_active.sum()) == 0
        assert int((end.wait_prefill > 0).sum()) == 0

    def test_no_pool_traces_to_original_behavior(self):
        from repro.serving import init_loop_state, make_device_serving_loop

        rng = np.random.default_rng(2)
        G, B, W = 3, 2, 32
        run = make_device_serving_loop(G, B, W)
        end = run(init_loop_state(G, B, rng.uniform(1, 9, 30),
                                  rng.integers(1, 6, 30), W), 80)
        assert int(end.tot_preempts) == 0
        assert int(end.slot_active.sum()) == 0
