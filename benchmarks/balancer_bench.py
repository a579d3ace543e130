"""Balancer + simulator hot-path benchmark -> BENCH_balancer.json.

Tracks the two hot paths this repo's scale story rests on, with the
pre-optimization implementations measured live (they are kept in-tree
precisely for this):

* **solver** — jitted BF-IO solve time, pre = ``method="dense"`` (the
  original O(N^2 W) ``_swap_once`` formulation) vs post = the tiled
  swap kernel with top-K candidate pruning (``method="xla"``,
  ``prune_k``).  Assignment quality (windowed imbalance J) is recorded
  for both so the speed/quality trade stays visible.
* **simulator** — instant-mode steps/sec, pre = ``dispatch="instant_ref"``
  (the original per-request Python loop) vs post = the vectorized
  ``dispatch="instant"`` path, with a bit-equality check on SimMetrics.
* **batch** — ``bfio_assign_batch`` (one vmapped call over C clusters)
  vs C sequential ``bfio_assign`` calls.
* **engine** — end-to-end ``ServingEngine`` steps/sec on a tiny dense
  model, pre = ``engine_mode="ref"`` (the original per-slot Python loops
  + per-request cache writes + always-decode-all-G*B) vs post =
  ``engine_mode="vec"`` (slot-table arrays, batched cache scatter,
  bucketed compact decode), with a stats-equality check (steps, tokens,
  energy_j, avg_imbalance bit-identical).
* **engine_paged** — the pluggable serving seams.  ``kind="grid"`` rows:
  ``cache_backend="slot"`` vs ``"paged"`` steps/sec with a stats-equality
  check, plus resident-KV bytes (paged peak resident vs the dense
  G*B*max_seq_len the slot layout pins — the ratio is the paging win).
  The ``kind="stall"`` row: max step wall-time while an admission wave of
  long prompts lands, synchronous prefill vs chunked
  (``prefill_chunk``) — chunking bounds the per-step prompt work so
  decode is never stalled behind a wave.
* **fleet** — the two-tier serving layer (``repro.fleet``).
  ``kind="scenario"`` rows run each named scenario trace (steady /
  flash_crowd / diurnal / agentic / long_doc) through a FleetServer of
  R engine replicas once per router, with the step-time constants in
  the attention-dominated regime (per-step wall tracks the max resident
  load, so the barrier actually prices imbalance); metrics come from
  the telemetry subsystem (mean cross-replica imbalance,
  energy-per-token including barrier idle, TTFT p95, SLO attainment).
  The CI gate: ``router="bfio"`` beats ``"round_robin"`` on both
  imbalance and energy-per-token on >= 3 of the 5 scenarios.  The
  ``kind="parity"`` row anchors the layer: ``fleet(R=1, router=*)``
  stats are bit-identical to a bare ServingEngine on the same stream.
* **fleet_scale** — the vectorized fleet hot path.  ``kind="speedup"``
  rows time the same trickle stream (sparse arrivals over mostly-idle
  replicas — the regime where fleet bookkeeping dominates) through the
  same router under ``fleet_mode="ref"`` (the original per-step O(R)
  re-gather loops, kept in-tree) vs ``"vec"`` (incrementally-updated
  per-replica arrays), with stats and per-step telemetry checked
  bit-identical.  The CI gate on the full grid: vec >= 5x ref steps/s
  at R=64 on at least one router.  The ``kind="pod"`` row runs
  R-in-the-hundreds with two-level hierarchical ``pod_bfio`` routing
  (one batched solve over all pods) vs flat round_robin: it must
  complete with zero failures and lower mean cross-replica imbalance.
* **engine_preempt** — the memory-pressure subsystem.  ``kind=
  "pressure"`` rows: the same request stream through a pool sized at
  ``pool_frac`` (0.5) of the unconstrained peak-resident demand, once per
  ``preemption_mode`` — the engine must complete everything via
  preemption (no MemoryError), swap mode bit-identical to the
  unconstrained run (``gens_equal``), with throughput plus the
  tokens-swapped vs tokens-recomputed trade recorded.  The
  ``kind="prefix"`` row: a shared-system-prompt workload with
  ``prefix_cache`` off vs on — block hit-rate, identical generations,
  and the resident-KV reduction (``kv_bytes_ratio < 1``).  The
  ``kind="persist"`` row: the same shared-prompt workload *staggered*
  (each request drains before the next arrives), once per
  ``prefix_evict`` mode — admission-scoped sharing hits nothing
  (every shared block dies with its last holder) while the persistent
  LRU evictor keeps hitting across the gaps, with identical
  generations and no extra peak resident KV.
* **fleet** (``kind="affinity"``) — the multi_turn scenario (sessions
  return for later turns after their first turn drained) under
  ``bfio`` vs ``bfio_affinity``: prefix-affinity routing sends return
  visits to the replica still holding their context blocks, and must
  cut energy-per-token at equal-or-better cross-replica imbalance.

Run:  PYTHONPATH=src python -m benchmarks.balancer_bench [--full] [--smoke]
Writes BENCH_balancer.json at the repo root (and benchmarks/results/).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SWAP_ITERS = 8
PRUNE_K = 128
W = 9  # lookahead window H=8


def _solver_case(G: int, N: int, *, measure_dense: bool, iters: int = 10,
                 seed: int = 0) -> dict:
    import jax.numpy as jnp

    from repro.core import io_solver
    from repro.core.balancer_jax import bfio_assign

    rng = np.random.default_rng(seed)
    base = jnp.asarray(rng.uniform(0, 100, (G, W)), jnp.float32)
    caps = jnp.asarray(rng.integers(4, 16, (G,)), jnp.int32)
    cands = jnp.asarray(rng.uniform(1, 50, (N, W)), jnp.float32)
    valid = jnp.ones((N,), bool)
    n_admit = jnp.int32(min(N, int(np.asarray(caps).sum())))

    def timed(swap_iters=SWAP_ITERS, **kw):
        def call():
            return bfio_assign(base, caps, cands, valid, n_admit,
                               swap_iters=swap_iters, **kw)
        a = np.asarray(call())  # warmup/compile
        t0 = time.time()
        for _ in range(iters):
            call().block_until_ready()
        us = (time.time() - t0) / iters * 1e6
        J = io_solver.objective(np.asarray(base), np.asarray(cands), a)
        return us, J

    prune = min(PRUNE_K, N)
    post_us, J_post = timed(method="xla", prune_k=prune)
    greedy_us, _ = timed(swap_iters=0)  # construction-only floor
    row = {"section": "solver", "G": G, "N": N, "W": W,
           "swap_iters": SWAP_ITERS, "prune_k": prune,
           "post_tiled_us": post_us, "J_post": J_post,
           "greedy_us": greedy_us,
           "pre_dense_us": None, "J_pre": None, "speedup": None,
           "refine_speedup": None, "quality_rel_diff": None}
    if measure_dense:
        pre_us, J_pre = timed(method="dense")
        # refinement-only ratio: subtract the shared greedy construction,
        # which no swap backend touches
        pre_ref = max(pre_us - greedy_us, 1e-9)
        post_ref = max(post_us - greedy_us, 1e-9)
        row.update(pre_dense_us=pre_us, J_pre=J_pre,
                   speedup=pre_us / post_us,
                   refine_speedup=pre_ref / post_ref,
                   quality_rel_diff=(J_post - J_pre) / max(abs(J_pre), 1e-9))
    return row


def _sim_instance(G: int, B: int, n_rounds: float, seed: int = 1):
    from repro.core import ArrivalInstance, Request

    rng = np.random.default_rng(seed)
    n = int(G * B * n_rounds)
    reqs = [
        Request(rid=i, arrival_step=int(rng.integers(0, 50)),
                prefill=float(rng.integers(1, 80)),
                decode_len=int(rng.geometric(0.1)))
        for i in range(n)
    ]
    return ArrivalInstance(requests=reqs)


def _sim_case(G: int, B: int, *, n_rounds: float = 4.0, policy: str = "jsq",
              seed: int = 1) -> dict:
    from repro.core import SimConfig, make_policy, simulate

    out = {"section": "simulator", "G": G, "B": B, "policy": policy}
    metrics = {}
    for mode, key in [("instant_ref", "pre"), ("instant", "post")]:
        inst = _sim_instance(G, B, n_rounds, seed=seed)
        t0 = time.time()
        m = simulate(inst, make_policy(policy),
                     SimConfig(G=G, B=B, dispatch=mode, max_steps=500_000))
        wall = time.time() - t0
        metrics[key] = dataclasses.asdict(m)
        out[f"{key}_steps_per_s"] = m.steps / max(wall, 1e-9)
        out[f"{key}_wall_s"] = wall
        out["steps"] = m.steps
    out["speedup"] = out["post_steps_per_s"] / out["pre_steps_per_s"]
    out["metrics_equal"] = metrics["pre"] == metrics["post"]
    return out


def _batch_case(C: int, G: int, N: int, iters: int = 5, seed: int = 2) -> dict:
    import jax.numpy as jnp

    from repro.core.balancer_jax import bfio_assign, bfio_assign_batch

    rng = np.random.default_rng(seed)
    base = jnp.asarray(rng.uniform(0, 100, (C, G, W)), jnp.float32)
    caps = jnp.asarray(rng.integers(4, 16, (C, G)), jnp.int32)
    cands = jnp.asarray(rng.uniform(1, 50, (C, N, W)), jnp.float32)
    valid = jnp.ones((C, N), bool)
    n_admit = jnp.minimum(N, caps.sum(axis=1)).astype(jnp.int32)

    prune = min(PRUNE_K, N)
    bfio_assign_batch(base, caps, cands, valid, n_admit,
                      prune_k=prune).block_until_ready()
    t0 = time.time()
    for _ in range(iters):
        bfio_assign_batch(base, caps, cands, valid, n_admit,
                          prune_k=prune).block_until_ready()
    batch_us = (time.time() - t0) / iters * 1e6

    def seq():
        for c in range(C):
            bfio_assign(base[c], caps[c], cands[c], valid[c], n_admit[c],
                        prune_k=prune).block_until_ready()
    seq()  # warmup
    t0 = time.time()
    for _ in range(iters):
        seq()
    seq_us = (time.time() - t0) / iters * 1e6
    return {"section": "batch", "C": C, "G": G, "N": N, "W": W,
            "prune_k": prune, "batch_us": batch_us, "sequential_us": seq_us,
            "speedup": seq_us / batch_us}


_ENGINE_STATE: dict = {}


def _engine_setup():
    """Tiny dense model shared by every engine case (params built once)."""
    if _ENGINE_STATE:
        return _ENGINE_STATE
    import jax

    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_params, split_params

    cfg = ModelConfig(name="bench-tiny", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=128, dtype="float32")
    params, _ = split_params(init_params(cfg, jax.random.PRNGKey(0)))
    _ENGINE_STATE.update(cfg=cfg, params=params, mesh=make_local_mesh())
    return _ENGINE_STATE


def _engine_requests(G: int, B: int, *, n_rounds: float, seed: int):
    from repro.serving import ServeRequest

    rng = np.random.default_rng(seed)
    n = int(G * B * n_rounds)
    return [
        ServeRequest(
            rid=i,
            tokens=rng.integers(1, 128, size=int(rng.integers(4, 24))),
            # geometric decode lengths: a long sparse tail, where the ref
            # engine still decodes all G*B slots every step
            max_new_tokens=int(min(3 + rng.geometric(0.12), 40)))
        for i in range(n)
    ]


def _engine_case(G: int, B: int, *, n_rounds: float = 1.5,
                 policy: str = "jsq", seed: int = 7) -> dict:
    from repro.core import make_policy
    from repro.serving import EngineConfig, ServingEngine

    st = _engine_setup()
    out = {"section": "engine", "G": G, "B": B, "policy": policy,
           "n_requests": int(G * B * n_rounds)}
    stats = {}
    for mode, key in [("ref", "pre"), ("vec", "post")]:
        ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                          engine_mode=mode)

        def one_run(rounds):
            eng = ServingEngine(st["cfg"], st["params"], ec,
                                make_policy(policy), mesh=st["mesh"])
            for r in _engine_requests(G, B, n_rounds=rounds, seed=seed):
                eng.submit(r)
            s = eng.run(max_steps=100_000)
            return s

        # warmup: compiles are cached across engine instances.  The ref
        # path's only jit is the full-batch decode, so a tiny workload
        # warms it; the vec path replays the full workload so every
        # decode/prefill bucket it will hit is compiled before timing.
        one_run(n_rounds if mode == "vec" else min(n_rounds, 0.25))
        t0 = time.time()
        s = one_run(n_rounds)
        wall = time.time() - t0
        stats[key] = s
        out[f"{key}_steps_per_s"] = s["steps"] / max(wall, 1e-9)
        out[f"{key}_wall_s"] = wall
        out["steps"] = s["steps"]
    out["speedup"] = out["post_steps_per_s"] / out["pre_steps_per_s"]
    out["metrics_equal"] = stats["pre"] == stats["post"]
    return out


def _engine_paged_case(G: int, B: int, *, n_rounds: float = 1.0,
                       policy: str = "jsq", seed: int = 7) -> dict:
    """slot-vs-paged cache backend on the vec engine: steps/s, stats
    parity, and resident-KV bytes (the paging win: peak resident KV
    tracks actual tokens, the slot layout pins G*B*max_seq_len)."""
    from repro.core import make_policy
    from repro.serving import EngineConfig, ServingEngine

    st = _engine_setup()
    out = {"section": "engine_paged", "kind": "grid", "G": G, "B": B,
           "policy": policy, "n_requests": int(G * B * n_rounds)}
    stats = {}
    for backend in ("slot", "paged"):
        ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                          cache_backend=backend, paged_block_size=16)

        def one_run(rounds):
            eng = ServingEngine(st["cfg"], st["params"], ec,
                                make_policy(policy), mesh=st["mesh"])
            for r in _engine_requests(G, B, n_rounds=rounds, seed=seed):
                eng.submit(r)
            s = eng.run(max_steps=100_000)
            return eng, s

        one_run(n_rounds)  # warmup: compile every bucket this run hits
        t0 = time.time()
        eng, s = one_run(n_rounds)
        wall = time.time() - t0
        stats[backend] = s
        out[f"{backend}_steps_per_s"] = s["steps"] / max(wall, 1e-9)
        out[f"{backend}_wall_s"] = wall
        out["steps"] = s["steps"]
        if backend == "paged":
            out["paged_kv_peak_bytes"] = int(eng.kv_peak_bytes)
            out["paged_pool_bytes"] = int(eng.backend.pool_bytes())
        else:
            out["slot_kv_bytes"] = int(eng.backend.resident_kv_bytes())
    out["speedup"] = out["paged_steps_per_s"] / out["slot_steps_per_s"]
    out["kv_bytes_ratio"] = (out["paged_kv_peak_bytes"]
                             / max(out["slot_kv_bytes"], 1))
    out["metrics_equal"] = stats["slot"] == stats["paged"]
    return out


def _engine_preempt_case(G: int, B: int, *, pool_frac: float = 0.5,
                         n_rounds: float = 1.5, policy: str = "jsq",
                         seed: int = 11) -> list[dict]:
    """Memory pressure: pool at ``pool_frac`` of the unconstrained peak
    resident demand; the engine completes the stream via preemption, swap
    mode bit-identical to unconstrained.  Returns one row per mode."""
    from repro.core import make_policy
    from repro.serving import EngineConfig, ServingEngine

    st = _engine_setup()

    def one_run(mode, pool_blocks):
        ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                          cache_backend="paged", paged_block_size=16,
                          paged_pool_blocks=pool_blocks,
                          preemption_mode=mode)
        eng = ServingEngine(st["cfg"], st["params"], ec,
                            make_policy(policy), mesh=st["mesh"])
        reqs = _engine_requests(G, B, n_rounds=n_rounds, seed=seed)
        for r in reqs:
            eng.submit(r)
        s = eng.run(max_steps=200_000)
        return eng, s, [r.generated for r in reqs]

    eng0, s0, gens0 = one_run("swap", 0)      # unconstrained baseline
    blk_bytes = eng0.backend.pool_bytes() // eng0.backend.n_blocks
    peak_blocks = -(-eng0.kv_peak_bytes // blk_bytes)
    pool = max(int(peak_blocks * pool_frac), 4)
    rows = []
    for mode in ("swap", "recompute"):
        one_run(mode, pool)  # warmup: compile every bucket this run hits
        t0 = time.time()
        eng, s, gens = one_run(mode, pool)
        wall = time.time() - t0
        rows.append({
            "section": "engine_preempt", "kind": "pressure", "G": G,
            "B": B, "policy": policy, "n_requests": int(G * B * n_rounds),
            "mode": mode, "pool_frac": pool_frac, "pool_blocks": pool,
            "peak_blocks_unconstrained": int(peak_blocks),
            "steps": s["steps"], "steps_per_s": s["steps"] / max(wall, 1e-9),
            "unconstrained_steps": s0["steps"],
            "preemptions": s["preemptions"],
            "tokens_swapped": s["tokens_swapped"],
            "tokens_recomputed": s["tokens_recomputed"],
            "completed": all(len(g) > 0 for g in gens),
            "gens_equal": gens == gens0,
        })
    return rows


def _engine_prefix_case(G: int, B: int, *, shared_len: int = 32,
                        n_rounds: float = 1.5, policy: str = "jsq",
                        seed: int = 13) -> dict:
    """Prefix caching on a shared-system-prompt workload: block hit-rate,
    identical generations, and the peak-resident-KV reduction."""
    from repro.core import make_policy
    from repro.serving import EngineConfig, ServeRequest, ServingEngine

    st = _engine_setup()
    n = int(G * B * n_rounds)

    def reqs():
        rng = np.random.default_rng(seed)
        system = rng.integers(1, 128, size=shared_len)
        return [ServeRequest(
            rid=i,
            tokens=np.concatenate(
                [system, rng.integers(1, 128,
                                      size=int(rng.integers(2, 10)))]),
            max_new_tokens=int(min(3 + rng.geometric(0.2), 20)))
            for i in range(n)]

    out = {"section": "engine_preempt", "kind": "prefix", "G": G, "B": B,
           "policy": policy, "n_requests": n, "shared_prefix_len": shared_len}
    gens = {}
    for on in (False, True):
        ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                          cache_backend="paged", paged_block_size=16,
                          prefix_cache=on)

        def one_run():
            eng = ServingEngine(st["cfg"], st["params"], ec,
                                make_policy(policy), mesh=st["mesh"])
            rs = reqs()
            for r in rs:
                eng.submit(r)
            s = eng.run(max_steps=100_000)
            return eng, s, [r.generated for r in rs]

        one_run()  # warmup
        t0 = time.time()
        eng, s, gens[on] = one_run()
        wall = time.time() - t0
        key = "on" if on else "off"
        out[f"steps_per_s_{key}"] = s["steps"] / max(wall, 1e-9)
        out[f"kv_peak_bytes_{key}"] = int(eng.kv_peak_bytes)
        if on:
            out["prefix_hits"] = s["prefix_hits"]
            out["prefix_queries"] = s["prefix_queries"]
            out["prefix_hit_rate"] = s["prefix_hit_rate"]
    out["kv_bytes_ratio"] = (out["kv_peak_bytes_on"]
                             / max(out["kv_peak_bytes_off"], 1))
    out["gens_equal"] = gens[False] == gens[True]
    return out


def _engine_persist_case(G: int, B: int, *, shared_len: int = 32,
                         n_rounds: float = 1.5, policy: str = "jsq",
                         seed: int = 17) -> dict:
    """Prefix-cache lifetime on a staggered stream: each request drains
    before the next is submitted, so under admission-scoped sharing
    every shared block dies with its last holder and the hit rate is
    exactly zero.  The persistent LRU evictor keeps refcount-0 blocks
    indexed until the pool actually needs them back, so later requests
    hit — with generations identical to the uncached run and no extra
    peak resident KV (cached blocks are reclaimable, not used)."""
    from repro.core import make_policy
    from repro.serving import EngineConfig, ServeRequest, ServingEngine

    st = _engine_setup()
    n = int(G * B * n_rounds)

    def reqs():
        rng = np.random.default_rng(seed)
        system = rng.integers(1, 128, size=shared_len)
        return [ServeRequest(
            rid=i,
            tokens=np.concatenate(
                [system, rng.integers(1, 128,
                                      size=int(rng.integers(2, 10)))]),
            max_new_tokens=int(min(3 + rng.geometric(0.2), 20)))
            for i in range(n)]

    out = {"section": "engine_preempt", "kind": "persist", "G": G,
           "B": B, "policy": policy, "n_requests": n,
           "shared_prefix_len": shared_len}
    gens = {}
    for mode in ("off", "admission", "lru"):
        ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                          cache_backend="paged", paged_block_size=16,
                          prefix_cache=(mode != "off"),
                          prefix_evict="lru" if mode == "off" else mode)

        def one_run():
            eng = ServingEngine(st["cfg"], st["params"], ec,
                                make_policy(policy), mesh=st["mesh"])
            rs = reqs()
            s = None
            for r in rs:    # staggered: drain before the next arrives
                eng.submit(r)
                s = eng.run(max_steps=100_000)
            return eng, s, [r.generated for r in rs]

        one_run()  # warmup
        t0 = time.time()
        eng, s, gens[mode] = one_run()
        wall = time.time() - t0
        out[f"steps_per_s_{mode}"] = s["steps"] / max(wall, 1e-9)
        out[f"kv_peak_bytes_{mode}"] = int(eng.kv_peak_bytes)
        if mode != "off":
            out[f"prefix_hits_{mode}"] = s["prefix_hits"]
            out[f"prefix_queries_{mode}"] = s["prefix_queries"]
            out[f"prefix_hit_rate_{mode}"] = s["prefix_hit_rate"]
        if mode == "lru":
            out["prefix_revived"] = s["prefix_revived"]
    out["kv_bytes_ratio"] = (out["kv_peak_bytes_lru"]
                             / max(out["kv_peak_bytes_off"], 1))
    out["gens_equal"] = (gens["off"] == gens["admission"]
                         == gens["lru"])
    return out


# Fleet cases run the engines' simulated clock in the attention-dominated
# regime (step wall-time tracks the max resident load instead of being
# swamped by the constant overhead), so cross-replica imbalance shows up
# in energy exactly as the paper's barrier model prices it.
FLEET_TIMING = dict(step_overhead=1e-3, t_token=2e-4)


def _fleet_case(R: int, G: int, B: int, *, n_requests: int,
                routers=("round_robin", "bfio"), load_factor: float = 0.8,
                seed: int = 0, jsonl_dir: str | None = None) -> list[dict]:
    """Scenario sweep: every named fleet scenario once per router, all
    metrics read from the telemetry subsystem."""
    from repro.fleet import (
        FleetServer,
        FleetTelemetry,
        SLOSpec,
        make_scenario,
    )
    from repro.serving import EngineConfig

    st = _engine_setup()
    ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                     **FLEET_TIMING)
    rows = []
    # the five routing scenarios; "trickle" belongs to fleet_scale
    for name in ("steady", "flash_crowd", "diurnal", "agentic",
                 "long_doc"):
        sc = make_scenario(name, n_requests=n_requests, n_replicas=R,
                           n_workers=G, slots_per_worker=B, max_seq_len=64,
                           vocab_size=128, seed=seed,
                           load_factor=load_factor, **FLEET_TIMING)
        row = {"section": "fleet", "kind": "scenario", "scenario": name,
               "R": R, "G": G, "B": B, "n_requests": sc.n_requests,
               "load_factor": load_factor}
        for router in routers:
            tel = FleetTelemetry(slo=SLOSpec(ttft_s=1.0, tpot_s=0.05))
            fs = FleetServer(st["cfg"], st["params"], ec, n_replicas=R,
                             router=router, policy="bfio_h0",
                             mesh=st["mesh"], telemetry=tel)
            fs.submit_scenario(sc)
            t0 = time.time()
            stats = fs.run(max_steps=200_000)
            wall = time.time() - t0
            s = tel.summary()
            row[f"{router}_imbalance"] = s["mean_cross_imbalance"]
            row[f"{router}_energy_per_token"] = s["energy_per_token"]
            row[f"{router}_throughput_tok_s"] = stats["throughput_tok_s"]
            row[f"{router}_ttft_p95"] = s["ttft"]["p95"]
            row[f"{router}_slo_attainment"] = s["slo_attainment"]
            row[f"{router}_completed"] = s["completed"]
            row[f"{router}_failed"] = s["failed"]
            row[f"{router}_steps"] = stats["steps"]
            row[f"{router}_wall_s"] = wall
            if jsonl_dir is not None and router == "bfio":
                tel.write_jsonl(os.path.join(
                    jsonl_dir, f"fleet_telemetry_{name}.jsonl"))
        if {"round_robin", "bfio"} <= set(routers):
            row["bfio_wins"] = bool(
                row["bfio_imbalance"] < row["round_robin_imbalance"]
                and (row["bfio_energy_per_token"]
                     < row["round_robin_energy_per_token"]))
        rows.append(row)
    return rows


def _fleet_affinity_case(R: int, G: int, B: int, *, n_requests: int,
                         seed: int = 0, scenario_seed: int = 1,
                         jsonl_dir: str | None = None) -> dict:
    """Prefix-affinity routing on the multi-turn scenario: a session's
    later turns arrive after its first turn drained, so only the
    persistent LRU evictor keeps its context blocks alive — and only
    affinity-aware routing sends the return visit to the replica that
    still holds them.  One row, ``bfio`` vs ``bfio_affinity``, on a
    deterministic trace (same shape for smoke and full)."""
    from repro.fleet import (
        FleetServer,
        FleetTelemetry,
        SLOSpec,
        make_scenario,
    )
    from repro.serving import EngineConfig

    st = _engine_setup()
    # a pool with headroom: the evictor can only pay across turn gaps
    # if cached contexts survive until the session returns
    ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                      cache_backend="paged", paged_block_size=16,
                      paged_pool_blocks=48, prefill_chunk=8,
                      prefix_cache=True)
    sc = make_scenario("multi_turn", n_requests=n_requests, n_replicas=R,
                       n_workers=G, slots_per_worker=B, max_seq_len=64,
                       vocab_size=128, seed=scenario_seed)
    row = {"section": "fleet", "kind": "affinity",
           "scenario": "multi_turn", "R": R, "G": G, "B": B,
           "n_requests": sc.n_requests}
    for router in ("bfio", "bfio_affinity"):
        tel = FleetTelemetry(slo=SLOSpec(ttft_s=1.0, tpot_s=0.05))
        fs = FleetServer(st["cfg"], st["params"], ec, n_replicas=R,
                         router=router, policy="bfio_h0",
                         mesh=st["mesh"], telemetry=tel, seed=seed)
        fs.submit_scenario(sc)
        t0 = time.time()
        stats = fs.run(max_steps=200_000)
        wall = time.time() - t0
        s = tel.summary()
        row[f"{router}_imbalance"] = s["mean_cross_imbalance"]
        row[f"{router}_energy_per_token"] = s["energy_per_token"]
        row[f"{router}_prefix_hits"] = stats["prefix_hits"]
        row[f"{router}_prefix_revived"] = stats["prefix_revived"]
        row[f"{router}_completed"] = s["completed"]
        row[f"{router}_failed"] = s["failed"]
        row[f"{router}_steps"] = stats["steps"]
        row[f"{router}_wall_s"] = wall
        if jsonl_dir is not None and router == "bfio_affinity":
            tel.write_jsonl(os.path.join(
                jsonl_dir, "fleet_telemetry_multi_turn.jsonl"))
    row["affinity_wins"] = bool(
        row["bfio_affinity_energy_per_token"]
        < row["bfio_energy_per_token"]
        and row["bfio_affinity_imbalance"] <= row["bfio_imbalance"])
    return row


def _fleet_parity_case(G: int, B: int, *, n_rounds: float = 1.5,
                       seed: int = 7) -> dict:
    """fleet(R=1, router=*) must be bit-identical to a bare engine on
    the same stream — the anchor tying the fleet layer to the
    exhaustively-tested single-engine semantics."""
    from repro.core import make_policy
    from repro.fleet import FleetServer
    from repro.serving import EngineConfig, ServingEngine

    st = _engine_setup()
    ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64)
    eng = ServingEngine(st["cfg"], st["params"], ec,
                        make_policy("bfio_h0"), mesh=st["mesh"])
    for r in _engine_requests(G, B, n_rounds=n_rounds, seed=seed):
        eng.submit(r)
    bare = eng.run(max_steps=100_000)
    routers = ("round_robin", "least_loaded", "pod2", "bfio")
    equal = True
    for router in routers:
        fs = FleetServer(st["cfg"], st["params"], ec, n_replicas=1,
                         router=router, policy="bfio_h0", mesh=st["mesh"])
        for r in _engine_requests(G, B, n_rounds=n_rounds, seed=seed):
            fs.submit(r)
        stats = fs.run(max_steps=100_000)
        equal = equal and (stats["replicas"][0] == bare)
    return {"section": "fleet", "kind": "parity", "G": G, "B": B,
            "n_requests": int(G * B * n_rounds),
            "routers": list(routers), "steps": bare["steps"],
            "stats_equal": equal}


_FLEET_SCALE_STATE: dict = {}


def _fleet_scale_setup():
    """An even smaller model than bench-tiny (1 layer, d=32): the
    fleet_scale section measures fleet-layer bookkeeping at large R,
    so per-replica model compute is pinned near the floor a CPU jit
    round-trip allows."""
    if _FLEET_SCALE_STATE:
        return _FLEET_SCALE_STATE
    import jax

    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_params, split_params

    cfg = ModelConfig(name="bench-fleet", family="dense", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                      vocab_size=128, dtype="float32")
    params, _ = split_params(init_params(cfg, jax.random.PRNGKey(0)))
    _FLEET_SCALE_STATE.update(cfg=cfg, params=params,
                              mesh=make_local_mesh())
    return _FLEET_SCALE_STATE


def _fleet_scale_server(st, ec, sc, *, R, router, mode, telemetry=None):
    from repro.fleet import FleetServer

    fs = FleetServer(st["cfg"], st["params"], ec, n_replicas=R,
                     router=router, policy="bfio_h0", mesh=st["mesh"],
                     fleet_mode=mode, telemetry=telemetry)
    fs.submit_scenario(sc)
    return fs


def _fleet_scale_speedup_case(R: int, G: int, B: int, *, n_requests: int,
                              routers, load_factor: float = 0.1,
                              repeats: int = 2,
                              seed: int = 0) -> list[dict]:
    """Ref-vs-vec fleet hot path on the trickle scenario: the same
    stream through the same router under both fleet modes.  Timed runs
    carry no telemetry and take the min wall over ``repeats`` with the
    GC parked (the stall-case idiom); stats equality is checked on the
    timed runs and per-step telemetry equality on a separate
    instrumented pair."""
    import gc

    from repro.fleet import FleetTelemetry, make_scenario
    from repro.serving import EngineConfig

    st = _fleet_scale_setup()
    ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=48,
                      prefill_chunk=16, **FLEET_TIMING)
    sc = make_scenario("trickle", n_requests=n_requests, n_replicas=R,
                       n_workers=G, slots_per_worker=B, max_seq_len=48,
                       vocab_size=128, seed=seed,
                       load_factor=load_factor, **FLEET_TIMING)
    rows = []
    for router in routers:
        # warmup: compile every shape bucket the stream hits
        _fleet_scale_server(st, ec, sc, R=R, router=router,
                            mode="vec").run(max_steps=500_000)
        walls = {}
        stats = {}
        for mode in ("ref", "vec"):
            best = float("inf")
            for _ in range(repeats):
                fs = _fleet_scale_server(st, ec, sc, R=R, router=router,
                                         mode=mode)
                gc.collect()
                gc.disable()
                try:
                    t0 = time.perf_counter()
                    stats[mode] = fs.run(max_steps=500_000)
                    best = min(best, time.perf_counter() - t0)
                finally:
                    gc.enable()
            walls[mode] = best
        tel = {}
        for mode in ("ref", "vec"):
            tel[mode] = FleetTelemetry()
            _fleet_scale_server(st, ec, sc, R=R, router=router, mode=mode,
                                telemetry=tel[mode]).run(max_steps=500_000)
        steps = stats["vec"]["steps"]
        rows.append({
            "section": "fleet_scale", "kind": "speedup",
            "scenario": "trickle", "R": R, "G": G, "B": B,
            "router": router, "n_requests": sc.n_requests,
            "load_factor": load_factor, "repeats": repeats,
            "steps": steps,
            "ref_wall_s": walls["ref"], "vec_wall_s": walls["vec"],
            "ref_steps_per_s": steps / max(walls["ref"], 1e-9),
            "vec_steps_per_s": steps / max(walls["vec"], 1e-9),
            "speedup": walls["ref"] / max(walls["vec"], 1e-9),
            "stats_equal": stats["ref"] == stats["vec"],
            "telemetry_equal": (
                tel["ref"].steps == tel["vec"].steps
                and tel["ref"].requests == tel["vec"].requests
                and tel["ref"].summary() == tel["vec"].summary()),
            "completed": stats["vec"]["completed"],
            "failed": stats["vec"]["failed"]})
    return rows


def _fleet_scale_pod_case(R: int, G: int, B: int, *, pods: int,
                          n_requests: int, load_factor: float = 0.8,
                          seed: int = 0,
                          jsonl_dir: str | None = None) -> dict:
    """Hierarchical pod routing at large R (both vec mode): flat
    round_robin vs two-level ``pod_bfio`` on the steady scenario —
    the R-in-the-hundreds deployment shape."""
    from repro.fleet import FleetTelemetry, SLOSpec, make_scenario
    from repro.serving import EngineConfig

    st = _fleet_scale_setup()
    ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                      prefill_chunk=16, **FLEET_TIMING)
    sc = make_scenario("steady", n_requests=n_requests, n_replicas=R,
                       n_workers=G, slots_per_worker=B, max_seq_len=64,
                       vocab_size=128, seed=seed,
                       load_factor=load_factor, **FLEET_TIMING)
    row = {"section": "fleet_scale", "kind": "pod", "scenario": "steady",
           "R": R, "G": G, "B": B, "pods": pods,
           "n_requests": sc.n_requests, "load_factor": load_factor}
    pod_router = f"pod_bfio_p{pods}"
    for router in ("round_robin", pod_router):
        key = "pod_bfio" if router == pod_router else router
        tel = FleetTelemetry(slo=SLOSpec(ttft_s=1.0, tpot_s=0.05))
        fs = _fleet_scale_server(st, ec, sc, R=R, router=router,
                                 mode="vec", telemetry=tel)
        t0 = time.perf_counter()
        stats = fs.run(max_steps=500_000)
        wall = time.perf_counter() - t0
        s = tel.summary()
        row[f"{key}_imbalance"] = s["mean_cross_imbalance"]
        row[f"{key}_energy_per_token"] = s["energy_per_token"]
        row[f"{key}_completed"] = s["completed"]
        row[f"{key}_failed"] = s["failed"]
        row[f"{key}_steps"] = stats["steps"]
        row[f"{key}_wall_s"] = wall
        row[f"{key}_steps_per_s"] = stats["steps"] / max(wall, 1e-9)
        if jsonl_dir is not None and router == pod_router:
            tel.write_jsonl(os.path.join(
                jsonl_dir, f"fleet_scale_pod_R{R}.jsonl"))
    row["pod_wins"] = bool(row["pod_bfio_imbalance"]
                           < row["round_robin_imbalance"])
    return row


def _fleet_async_compat_case(R: int, G: int, B: int, *, n_requests: int,
                             routers=("round_robin", "least_loaded",
                                      "pod2", "bfio"),
                             load_factor: float = 0.8,
                             seed: int = 0) -> list[dict]:
    """``AsyncFleetServer(barrier_compat=True)`` vs ``FleetServer`` on
    the same stream: the async subsystem's parity oracle — stats,
    telemetry, and generations must all be bit-identical, per router."""
    from repro.fleet import AsyncFleetServer, FleetTelemetry, make_scenario
    from repro.serving import EngineConfig

    st = _fleet_scale_setup()
    ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                      **FLEET_TIMING)
    sc = make_scenario("flash_crowd", n_requests=n_requests, n_replicas=R,
                       n_workers=G, slots_per_worker=B, max_seq_len=64,
                       vocab_size=128, seed=seed,
                       load_factor=load_factor, **FLEET_TIMING)
    rows = []
    for router in routers:
        stats, tels, gens = {}, {}, {}
        for mode in ("barrier", "compat"):
            tel = FleetTelemetry()
            if mode == "barrier":
                fs = _fleet_scale_server(st, ec, sc, R=R, router=router,
                                         mode="vec", telemetry=tel)
            else:
                fs = AsyncFleetServer(
                    st["cfg"], st["params"], ec, n_replicas=R,
                    router=router, policy="bfio_h0", mesh=st["mesh"],
                    telemetry=tel, barrier_compat=True)
                fs.submit_scenario(sc)
            stats[mode] = fs.run(max_steps=500_000)
            tels[mode] = tel
            gens[mode] = [r.generated for r in fs.requests]
        rows.append({
            "section": "fleet_async", "kind": "compat",
            "scenario": sc.name, "R": R, "G": G, "B": B,
            "router": router, "n_requests": sc.n_requests,
            "load_factor": load_factor,
            "steps": stats["barrier"]["steps"],
            "completed": stats["compat"]["completed"],
            "failed": stats["compat"]["failed"],
            "stats_equal": stats["barrier"] == stats["compat"],
            "telemetry_equal": (
                tels["barrier"].steps == tels["compat"].steps
                and tels["barrier"].requests == tels["compat"].requests
                and tels["barrier"].summary() == tels["compat"].summary()),
            "gens_equal": gens["barrier"] == gens["compat"]})
    return rows


def _fleet_async_diurnal_case(R: int, G: int, B: int, *, n_requests: int,
                              router: str = "bfio",
                              load_factor: float = 0.35,
                              target: float = 0.7,
                              interval_s: float = 0.05,
                              warmup_s: float = 0.02, seed: int = 5,
                              jsonl_dir: str | None = None) -> dict:
    """The headline claim: fixed-R barrier fleet vs autoscaled async
    fleet on the diurnal scenario, paged engines with host-staged swap
    so drain handoffs are bit-exact.  The async fleet must cut idle
    energy and energy-per-token at equal-or-better SLO attainment with
    zero failures, zero tokens lost across drains, and generations
    identical to the run that never scaled."""
    from repro.fleet import (
        AsyncFleetServer,
        FleetTelemetry,
        SLOSpec,
        TargetUtilizationAutoscaler,
        make_scenario,
    )
    from repro.serving import EngineConfig

    st = _fleet_scale_setup()
    ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                      cache_backend="paged", paged_block_size=16,
                      preemption_mode="swap", **FLEET_TIMING)
    sc = make_scenario("diurnal", n_requests=n_requests, n_replicas=R,
                       n_workers=G, slots_per_worker=B, max_seq_len=64,
                       vocab_size=128, seed=seed,
                       load_factor=load_factor, **FLEET_TIMING)
    slo = SLOSpec(ttft_s=0.5, tpot_s=0.1)

    tel_b = FleetTelemetry(slo=slo)
    fb = _fleet_scale_server(st, ec, sc, R=R, router=router, mode="vec",
                             telemetry=tel_b)
    stats_b = fb.run(max_steps=500_000)
    sum_b = tel_b.summary()

    tel_a = FleetTelemetry(slo=slo)
    auto = TargetUtilizationAutoscaler(
        r_min=1, r_max=R, target=target, interval_s=interval_s,
        warmup_s=warmup_s)
    fa = AsyncFleetServer(
        st["cfg"], st["params"], ec, n_replicas=R, router=router,
        policy="bfio_h0", mesh=st["mesh"], telemetry=tel_a,
        autoscaler=auto, max_snapshot_age=interval_s)
    fa.submit_scenario(sc)
    stats_a = fa.run(max_steps=500_000)
    sum_a = tel_a.summary()
    if jsonl_dir is not None:
        tel_a.write_jsonl(os.path.join(
            jsonl_dir, f"fleet_async_diurnal_R{R}.jsonl"))

    return {
        "section": "fleet_async", "kind": "diurnal",
        "scenario": sc.name, "R": R, "G": G, "B": B, "router": router,
        "n_requests": sc.n_requests, "load_factor": load_factor,
        "target_util": target, "interval_s": interval_s,
        "warmup_s": warmup_s,
        "barrier_idle_j": stats_b["idle_j"],
        "barrier_energy_per_token": stats_b["energy_per_token"],
        "barrier_slo_attainment": sum_b["slo_attainment"],
        "barrier_completed": stats_b["completed"],
        "barrier_failed": stats_b["failed"],
        "barrier_tokens": stats_b["tokens"],
        "barrier_steps": stats_b["steps"],
        "async_idle_j": stats_a["idle_j"],
        "async_energy_per_token": stats_a["energy_per_token"],
        "async_slo_attainment": sum_a["slo_attainment"],
        "async_completed": stats_a["completed"],
        "async_failed": stats_a["failed"],
        "async_tokens": stats_a["tokens"],
        "async_steps": stats_a["steps"],
        "idle_saving": 1.0 - (stats_a["idle_j"]
                              / max(stats_b["idle_j"], 1e-12)),
        "drain_handoffs": stats_a["drain_handoffs"],
        "tokens_lost": stats_a["drain_tokens_lost"],
        "scale_ups": stats_a["scale_ups"],
        "scale_downs": stats_a["scale_downs"],
        "r_on_mean": stats_a["r_on_mean"],
        "gens_equal": ([r.generated for r in fa.requests]
                       == [r.generated for r in fb.requests]),
    }


def _obs_case(R: int, G: int, B: int, *, n_requests: int,
              load_factor: float = 0.4, seed: int = 5,
              variants=("barrier", "async"),
              jsonl_dir: str | None = None) -> list[dict]:
    """Observability exactness + overhead: the same diurnal stream with
    the span recorder enabled vs disabled, per fleet tier.  Gates (all
    enforced by ``check_bench``):

    * the straggler ledger's attributed total equals ``stats['idle_j']``
      bit-exactly, and every telemetry row's ``idle_split`` left-folds
      to its ``idle_j`` bit-exactly;
    * the exported trace round-trips through the validating reader and
      every fleet-track request span's ``e2e_s`` equals the telemetry's
      per-request ``latency`` bit-exactly;
    * the disabled recorder buffers zero events and reproduces
      bit-identical stats and telemetry (observation is free when off);
    * the enabled recorder's wall-clock overhead is bounded (full runs
      only — smoke shapes are dispatch-jitter-dominated)."""
    import gc

    from repro.fleet import (
        AsyncFleetServer,
        FleetServer,
        FleetTelemetry,
        SLOSpec,
        TargetUtilizationAutoscaler,
        make_scenario,
    )
    from repro.obs import SpanRecorder, fold_sum, read_trace, write_trace
    from repro.serving import EngineConfig

    st = _fleet_scale_setup()
    ec = EngineConfig(n_workers=G, slots_per_worker=B, max_seq_len=64,
                      cache_backend="paged", paged_block_size=16,
                      preemption_mode="swap", **FLEET_TIMING)
    sc = make_scenario("diurnal", n_requests=n_requests, n_replicas=R,
                       n_workers=G, slots_per_worker=B, max_seq_len=64,
                       vocab_size=128, seed=seed,
                       load_factor=load_factor, **FLEET_TIMING)
    slo = SLOSpec(ttft_s=0.5, tpot_s=0.1)

    def build(variant, telemetry, recorder):
        if variant == "async":
            auto = TargetUtilizationAutoscaler(
                r_min=1, r_max=R, target=0.7, interval_s=0.05,
                warmup_s=0.02)
            fs = AsyncFleetServer(
                st["cfg"], st["params"], ec, n_replicas=R,
                router="bfio", policy="bfio_h0", mesh=st["mesh"],
                telemetry=telemetry, autoscaler=auto,
                max_snapshot_age=0.05, obs=recorder)
        else:
            fs = FleetServer(
                st["cfg"], st["params"], ec, n_replicas=R,
                router="bfio", policy="bfio_h0", mesh=st["mesh"],
                telemetry=telemetry, obs=recorder)
        fs.submit_scenario(sc)
        return fs

    def timed(variant, telemetry, recorder):
        fs = build(variant, telemetry, recorder)
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            stats = fs.run(max_steps=500_000)
            wall = time.perf_counter() - t0
        finally:
            gc.enable()
        return fs, stats, wall

    rows = []
    out_dir = jsonl_dir or tempfile.mkdtemp(prefix="bench_obs_")
    for variant in variants:
        build(variant, None, None).run(max_steps=500_000)   # warmup
        rec = SpanRecorder()
        tel_on = FleetTelemetry(slo=slo)
        fs_on, stats_on, wall_on = timed(variant, tel_on, rec)
        tel_off = FleetTelemetry(slo=slo)
        fs_off, stats_off, wall_off = timed(variant, tel_off, None)

        ledger = fs_on.straggler_ledger()
        split_sums_match = all(
            fold_sum(s["idle_split"]) == s["idle_j"]
            for s in tel_on.steps)
        # trace export -> validating reader -> span/latency equality
        trace_path = os.path.join(
            out_dir, f"obs_diurnal_{variant}_R{R}.trace")
        write_trace(rec, trace_path)
        seen = read_trace(trace_path)
        lat = {q["rid"]: q["latency"] for q in tel_on.requests}
        spans_match_latency = (
            set(seen["requests"]) == set(lat)
            and all(v["e2e_s"] == lat[rid]
                    for rid, v in seen["requests"].items()))
        tel_path = os.path.join(
            out_dir, f"obs_diurnal_{variant}_R{R}.jsonl")
        tel_on.write_jsonl(tel_path)
        # read_jsonl re-validates the stored summary on the way back in
        back = FleetTelemetry.read_jsonl(tel_path)
        telemetry_roundtrip = (
            back.steps == tel_on.steps
            and json.loads(json.dumps(tel_on.summary()))
            == back.summary())
        rows.append({
            "section": "obs", "kind": "obs", "variant": variant,
            "scenario": sc.name, "R": R, "G": G, "B": B,
            "n_requests": sc.n_requests, "load_factor": load_factor,
            "wall_s_enabled": wall_on, "wall_s_disabled": wall_off,
            "overhead_ratio": wall_on / max(wall_off, 1e-12),
            "idle_j": stats_on["idle_j"],
            "ledger_total_j": ledger["total_idle_j"],
            "ledger_matches":
                ledger["total_idle_j"] == stats_on["idle_j"],
            "split_sums_match": split_sums_match,
            "by_cause": ledger["by_cause"],
            "gating_steps": ledger["gating_steps"],
            "trough_steps": ledger["trough_steps"],
            "trace_events": rec.n_events,
            "trace_spans": len(seen["requests"]),
            "trace_events_disabled": fs_off._obs_rec.n_events,
            "trace_roundtrip": seen["n_points"] == rec.n_events,
            "spans_match_latency": spans_match_latency,
            "stats_bit_identical": stats_on == stats_off,
            "telemetry_bit_identical":
                tel_on.steps == tel_off.steps
                and tel_on.requests == tel_off.requests,
            "telemetry_roundtrip": telemetry_roundtrip,
        })
    return rows


_STALL_STATE: dict = {}


def _stall_setup():
    """A deeper model for the stall measurement: with the bench-tiny
    model a decode step is ~2 ms, the same order as CPU dispatch jitter,
    so max-vs-median ratios measure the host, not the engine."""
    if _STALL_STATE:
        return _STALL_STATE
    import jax

    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_params, split_params

    cfg = ModelConfig(name="bench-stall", family="dense", n_layers=4,
                      d_model=128, n_heads=8, n_kv_heads=4, d_ff=256,
                      vocab_size=128, dtype="float32")
    params, _ = split_params(init_params(cfg, jax.random.PRNGKey(0)))
    _STALL_STATE.update(cfg=cfg, params=params, mesh=make_local_mesh())
    return _STALL_STATE


def _engine_stall_case(G: int, B: int, *, chunk: int = 8,
                       prompt_len: int = 192, warm_n: int = 16,
                       repeats: int = 3, tiny_model: bool = False,
                       seed: int = 9) -> dict:
    """Admission-wave decode stall: a burst of long prompts lands while
    ``warm_n`` requests are decoding.  The synchronous path prefills the
    whole wave inside one barrier step (max step wall >> steady decode
    step); chunked prefill bounds per-step prompt work at the budget, so
    the max step stays within a small factor of steady state.

    The scenario is deterministic, so each timed step takes the min over
    ``repeats`` identical runs (with the GC parked) — the standard way
    to strip scheduler/GC spikes from per-step wall times on CPU.
    """
    import gc

    from repro.core import make_policy
    from repro.serving import EngineConfig, ServeRequest, ServingEngine

    st = _engine_setup() if tiny_model else _stall_setup()
    N = G * B
    warm_n = max(2, min(warm_n, N - 2))
    burst_n = N - warm_n

    def scenario(chunked: bool):
        ec = EngineConfig(n_workers=G, slots_per_worker=B,
                          max_seq_len=256,
                          prefill_chunk=chunk if chunked else 0)
        eng = ServingEngine(st["cfg"], st["params"], ec,
                            make_policy("jsq"), mesh=st["mesh"])
        rng = np.random.default_rng(seed)
        for i in range(warm_n):
            eng.submit(ServeRequest(
                rid=i, tokens=rng.integers(1, 128, size=8),
                max_new_tokens=100_000))  # decode throughout the scenario
        for _ in range(3):
            eng.step()
        gc.collect()
        gc.disable()
        try:
            steady = []
            for _ in range(20):
                t0 = time.perf_counter()
                eng.step()
                steady.append(time.perf_counter() - t0)
            burst = [ServeRequest(
                rid=100 + i, tokens=rng.integers(1, 128, size=prompt_len),
                max_new_tokens=2) for i in range(burst_n)]
            for r in burst:
                eng.submit(r)
            walls = []
            while not all(r.done for r in burst):
                t0 = time.perf_counter()
                eng.step()
                walls.append(time.perf_counter() - t0)
                if len(walls) > 20_000:
                    raise RuntimeError("admission burst never drained")
        finally:
            gc.enable()
        return np.asarray(steady), np.asarray(walls)

    def measure(chunked: bool):
        scenario(chunked)       # warmup: compile every shape it hits
        runs = [scenario(chunked) for _ in range(repeats)]
        n = min(len(w) for _, w in runs)
        walls = np.min([w[:n] for _, w in runs], axis=0)
        steady = float(np.median(np.min([s for s, _ in runs], axis=0)))
        return steady, float(walls.max()), n

    s_med, s_max, s_steps = measure(False)
    c_med, c_max, c_steps = measure(True)
    return {"section": "engine_paged", "kind": "stall", "G": G, "B": B,
            "prefill_chunk": chunk, "burst_prompts": burst_n,
            "prompt_len": prompt_len, "warm_decoders": warm_n,
            "repeats": repeats,
            "steady_step_ms_sync": s_med * 1e3,
            "burst_max_step_ms_sync": s_max * 1e3,
            "stall_x_sync": s_max / max(s_med, 1e-9),
            "burst_steps_sync": s_steps,
            "steady_step_ms_chunked": c_med * 1e3,
            "burst_max_step_ms_chunked": c_max * 1e3,
            "stall_x_chunked": c_max / max(c_med, 1e-9),
            "burst_steps_chunked": c_steps}


ALL_SECTIONS = ("solver", "simulator", "batch", "engine", "engine_paged",
                "engine_preempt", "fleet", "fleet_scale", "fleet_async",
                "obs")


def run(full: bool = False, smoke: bool = False,
        out_path: str | None = None, sections=None) -> dict:
    if sections is None:
        sections = ALL_SECTIONS
    sections = set(sections)
    unknown = sections - set(ALL_SECTIONS)
    if unknown:
        raise ValueError(f"unknown bench sections {sorted(unknown)} "
                         f"(have {list(ALL_SECTIONS)})")
    if smoke:
        solver_grid = [(4, 16)]
        sim_grid = [(8, 4)]
        batch_grid = [(2, 4, 8)]
        engine_grid = [(2, 2)]
        paged_grid = [(2, 2)]
        preempt_grid = [(2, 2)]
        prefix_grid = [(2, 2)]
        persist_grid = [(2, 2)]
        stall_shape = (2, 2)
        # min over 3 runs per step: one run's max step is whatever spike
        # a loaded host (a parallel test run) lands in its longer burst
        stall_kw = dict(chunk=16, prompt_len=64, warm_n=2, repeats=3,
                        tiny_model=True)
        fleet_shape = (4, 2, 2)       # R, G, B
        fleet_kw = dict(n_requests=32, routers=("round_robin", "bfio"))
        fleet_parity_shape = (2, 2)
        # deliberately NOT downsized for smoke: the affinity gate row is
        # a deterministic trace, cheap enough to run at its real shape
        fleet_affinity_shape = (3, 1, 2)    # R, G, B
        fleet_affinity_kw = dict(n_requests=36, seed=0, scenario_seed=1)
        fscale_shape = (8, 1, 2)      # R, G, B
        fscale_kw = dict(n_requests=24, repeats=1,
                         routers=("round_robin", "bfio"))
        fscale_pod_shape = (16, 1, 2)
        fscale_pod_kw = dict(pods=4, n_requests=48)
        fasync_compat_shape = (2, 1, 2)     # R, G, B
        fasync_compat_kw = dict(n_requests=12,
                                routers=("round_robin", "bfio"))
        fasync_diurnal_shape = (4, 2, 4)    # R, G, B
        fasync_diurnal_kw = dict(n_requests=24, load_factor=0.4)
        obs_shape = (4, 2, 4)               # R, G, B
        obs_kw = dict(n_requests=24, load_factor=0.4)
        n_rounds, iters = 2.0, 2
    else:
        solver_grid = [(G, N) for G in (64, 256, 1024)
                       for N in (64, 512, 2048)]
        sim_grid = [(64, 72), (256, 72), (1024, 72)]
        batch_grid = [(8, 64, 256)]
        engine_grid = [(G, B) for G in (4, 16, 64) for B in (8, 32)]
        paged_grid = [(G, B) for G in (4, 16, 64) for B in (8, 32)]
        preempt_grid = [(4, 8), (16, 8)]
        prefix_grid = [(4, 8)]
        persist_grid = [(4, 8)]
        stall_shape = (4, 8)
        stall_kw = dict(chunk=8, prompt_len=192, warm_n=16, repeats=7)
        fleet_shape = (4, 4, 4)
        fleet_kw = dict(
            n_requests=96,
            routers=("round_robin", "least_loaded", "pod2", "bfio"),
            jsonl_dir=os.path.join(ROOT, "benchmarks", "results"))
        fleet_parity_shape = (2, 4)
        fleet_affinity_shape = (3, 1, 2)
        fleet_affinity_kw = dict(
            n_requests=36, seed=0, scenario_seed=1,
            jsonl_dir=os.path.join(ROOT, "benchmarks", "results"))
        fscale_shape = (64, 1, 2)
        fscale_kw = dict(
            n_requests=128, repeats=2,
            routers=("round_robin", "least_loaded", "pod2", "bfio"))
        fscale_pod_shape = (256, 1, 2)
        fscale_pod_kw = dict(
            pods=16, n_requests=384,
            jsonl_dir=os.path.join(ROOT, "benchmarks", "results"))
        fasync_compat_shape = (8, 1, 2)
        fasync_compat_kw = dict(
            n_requests=48,
            routers=("round_robin", "least_loaded", "pod2", "bfio"))
        fasync_diurnal_shape = (8, 2, 4)
        fasync_diurnal_kw = dict(
            n_requests=96, load_factor=0.35,
            jsonl_dir=os.path.join(ROOT, "benchmarks", "results"))
        obs_shape = (8, 2, 4)
        obs_kw = dict(
            n_requests=96, load_factor=0.35,
            jsonl_dir=os.path.join(ROOT, "benchmarks", "results"))
        n_rounds, iters = 4.0, 10

    rows = []
    for G, N in solver_grid if "solver" in sections else []:
        # the dense baseline materializes (N, N, W) f32 tensors; skip it at
        # N=2048 (>150 MB per temporary) unless --full
        dense_ok = N <= 512 or full
        r = _solver_case(G, N, measure_dense=dense_ok,
                         iters=max(2, iters // (1 + N // 512)))
        rows.append(r)
        pre = f"{r['pre_dense_us']/1e3:8.1f}ms" if r["pre_dense_us"] else "    n/a "
        print(f"  solver G={G:<5d} N={N:<5d} pre={pre} "
              f"post={r['post_tiled_us']/1e3:8.1f}ms "
              f"speedup={r['speedup'] or float('nan'):5.1f}x "
              f"(refine-only {r['refine_speedup'] or float('nan'):5.1f}x) "
              f"dJ={r['quality_rel_diff'] if r['quality_rel_diff'] is not None else float('nan'):+.3%}",
              flush=True)
    for G, B in sim_grid if "simulator" in sections else []:
        r = _sim_case(G, B, n_rounds=n_rounds)
        rows.append(r)
        print(f"  sim    G={G:<5d} B={B:<3d} pre={r['pre_steps_per_s']:8.0f} "
              f"post={r['post_steps_per_s']:8.0f} steps/s "
              f"speedup={r['speedup']:5.1f}x equal={r['metrics_equal']}",
              flush=True)
    for C, G, N in batch_grid if "batch" in sections else []:
        r = _batch_case(C, G, N, iters=iters)
        rows.append(r)
        print(f"  batch  C={C} G={G} N={N} batch={r['batch_us']/1e3:.1f}ms "
              f"seq={r['sequential_us']/1e3:.1f}ms speedup={r['speedup']:.1f}x",
              flush=True)
    for G, B in engine_grid if "engine" in sections else []:
        r = _engine_case(G, B)
        rows.append(r)
        print(f"  engine G={G:<3d} B={B:<3d} pre={r['pre_steps_per_s']:7.1f} "
              f"post={r['post_steps_per_s']:7.1f} steps/s "
              f"speedup={r['speedup']:5.1f}x equal={r['metrics_equal']}",
              flush=True)
    for G, B in paged_grid if "engine_paged" in sections else []:
        r = _engine_paged_case(G, B)
        rows.append(r)
        print(f"  paged  G={G:<3d} B={B:<3d} "
              f"slot={r['slot_steps_per_s']:7.1f} "
              f"paged={r['paged_steps_per_s']:7.1f} steps/s "
              f"kv={r['kv_bytes_ratio']:.2f}x of dense "
              f"equal={r['metrics_equal']}", flush=True)
    for G, B in preempt_grid if "engine_preempt" in sections else []:
        for r in _engine_preempt_case(G, B):
            rows.append(r)
            print(f"  preempt G={G:<3d} B={B:<3d} mode={r['mode']:<9s} "
                  f"pool={r['pool_blocks']}/{r['peak_blocks_unconstrained']} "
                  f"blocks preempts={r['preemptions']:<4d} "
                  f"swapped={r['tokens_swapped']:<6d} "
                  f"recomputed={r['tokens_recomputed']:<6d} "
                  f"gens_equal={r['gens_equal']}", flush=True)
    for G, B in prefix_grid if "engine_preempt" in sections else []:
        r = _engine_prefix_case(G, B)
        rows.append(r)
        print(f"  prefix G={G:<3d} B={B:<3d} "
              f"hit_rate={r['prefix_hit_rate']:.2f} "
              f"kv={r['kv_bytes_ratio']:.2f}x of uncached "
              f"gens_equal={r['gens_equal']}", flush=True)
    for G, B in persist_grid if "engine_preempt" in sections else []:
        r = _engine_persist_case(G, B)
        rows.append(r)
        print(f"  persist G={G:<3d} B={B:<3d} "
              f"hit_rate adm={r['prefix_hit_rate_admission']:.2f} "
              f"lru={r['prefix_hit_rate_lru']:.2f} "
              f"revived={r['prefix_revived']} "
              f"kv={r['kv_bytes_ratio']:.2f}x of uncached "
              f"gens_equal={r['gens_equal']}", flush=True)
    if "engine_paged" in sections:
        r = _engine_stall_case(*stall_shape, **stall_kw)
        rows.append(r)
        print(f"  stall  G={r['G']} B={r['B']} "
              f"sync={r['stall_x_sync']:.1f}x "
              f"chunked={r['stall_x_chunked']:.1f}x of steady step "
              f"(burst of {r['burst_prompts']}x{r['prompt_len']}-token "
              f"prompts)", flush=True)
    if "fleet" in sections:
        wins = 0
        for r in _fleet_case(*fleet_shape, **fleet_kw):
            rows.append(r)
            wins += r["bfio_wins"]
            print(f"  fleet  {r['scenario']:<12s} R={r['R']} "
                  f"imb rr={r['round_robin_imbalance']:7.1f} "
                  f"bfio={r['bfio_imbalance']:7.1f}  "
                  f"J/tok rr={r['round_robin_energy_per_token']:.3f} "
                  f"bfio={r['bfio_energy_per_token']:.3f}  "
                  f"win={r['bfio_wins']}", flush=True)
        r = _fleet_parity_case(*fleet_parity_shape)
        rows.append(r)
        print(f"  fleet  parity R=1 vs bare engine over "
              f"{len(r['routers'])} routers: "
              f"stats_equal={r['stats_equal']}  "
              f"(bfio wins {wins}/5 scenarios)", flush=True)
        r = _fleet_affinity_case(*fleet_affinity_shape,
                                 **fleet_affinity_kw)
        rows.append(r)
        print(f"  fleet  multi_turn R={r['R']} hits "
              f"{r['bfio_prefix_hits']}->"
              f"{r['bfio_affinity_prefix_hits']} "
              f"J/tok {r['bfio_energy_per_token']:.3f}->"
              f"{r['bfio_affinity_energy_per_token']:.3f} "
              f"imb {r['bfio_imbalance']:.1f}->"
              f"{r['bfio_affinity_imbalance']:.1f} "
              f"win={r['affinity_wins']}", flush=True)
    if "fleet_scale" in sections:
        for r in _fleet_scale_speedup_case(*fscale_shape, **fscale_kw):
            rows.append(r)
            print(f"  fscale {r['router']:<13s} R={r['R']:<4d} "
                  f"ref={r['ref_steps_per_s']:7.1f} "
                  f"vec={r['vec_steps_per_s']:7.1f} steps/s "
                  f"speedup={r['speedup']:5.2f}x "
                  f"equal={r['stats_equal'] and r['telemetry_equal']}",
                  flush=True)
        r = _fleet_scale_pod_case(*fscale_pod_shape, **fscale_pod_kw)
        rows.append(r)
        print(f"  fscale pod R={r['R']} pods={r['pods']} "
              f"imb rr={r['round_robin_imbalance']:7.1f} "
              f"pod_bfio={r['pod_bfio_imbalance']:7.1f}  "
              f"failed={r['pod_bfio_failed']}  win={r['pod_wins']}",
              flush=True)
    if "fleet_async" in sections:
        for r in _fleet_async_compat_case(*fasync_compat_shape,
                                          **fasync_compat_kw):
            rows.append(r)
            print(f"  fasync compat {r['router']:<13s} R={r['R']} "
                  f"stats_equal={r['stats_equal']} "
                  f"telemetry_equal={r['telemetry_equal']} "
                  f"gens_equal={r['gens_equal']}", flush=True)
        r = _fleet_async_diurnal_case(*fasync_diurnal_shape,
                                      **fasync_diurnal_kw)
        rows.append(r)
        print(f"  fasync diurnal R={r['R']} "
              f"idle {r['barrier_idle_j']:7.1f}->{r['async_idle_j']:7.1f}J "
              f"J/tok {r['barrier_energy_per_token']:.3f}->"
              f"{r['async_energy_per_token']:.3f} "
              f"slo {r['barrier_slo_attainment']:.2f}->"
              f"{r['async_slo_attainment']:.2f} "
              f"handoffs={r['drain_handoffs']} lost={r['tokens_lost']} "
              f"gens_equal={r['gens_equal']}", flush=True)
    if "obs" in sections:
        for r in _obs_case(*obs_shape, **obs_kw):
            rows.append(r)
            exact = (r["ledger_matches"] and r["split_sums_match"]
                     and r["spans_match_latency"])
            print(f"  obs    {r['variant']:<8s} R={r['R']} "
                  f"idle={r['idle_j']:8.2f}J exact={exact} "
                  f"events={r['trace_events']:<5d} "
                  f"(off: {r['trace_events_disabled']}) "
                  f"free_when_off={r['stats_bit_identical']} "
                  f"overhead={r['overhead_ratio']:.2f}x", flush=True)

    doc = {
        "meta": {
            "bench": "balancer",
            "smoke": smoke,
            "sections": sorted(sections),
            "W": W,
            "swap_iters": SWAP_ITERS,
            "prune_k": PRUNE_K,
            "pre": "method='dense' solver / dispatch='instant_ref' simulator "
                   "/ engine_mode='ref' serving engine "
                   "(the pre-optimization implementations, kept in-tree)",
            "post": "tiled swap kernel with top-K pruning / vectorized "
                    "instant dispatch / slot-table engine with bucketed "
                    "compact decode / paged KV backend + chunked prefill "
                    "(engine_paged section) / preemption + prefix "
                    "caching under memory pressure (engine_preempt "
                    "section) / two-tier routing across engine replicas "
                    "(fleet section) / vectorized fleet hot path "
                    "(fleet_mode='vec') with hierarchical pod routing "
                    "at R in the hundreds (fleet_scale section) / "
                    "event-driven async fleet with SLO-driven "
                    "autoscaling and bit-exact drain handoff "
                    "(fleet_async section) / persistent LRU prefix "
                    "evictor + prefix-affinity fleet routing "
                    "(engine_preempt kind='persist' / fleet "
                    "kind='affinity' rows) / per-request tracing + "
                    "barrier straggler attribution with bit-exact "
                    "idle-energy decomposition and a free-when-off "
                    "recorder (obs section)",
        },
        "rows": rows,
    }
    if out_path is None and (smoke or sections != set(ALL_SECTIONS)):
        # never clobber the tracked full-grid artifact with smoke or
        # partial-section numbers
        out_path = os.path.join(tempfile.mkdtemp(prefix="bench_smoke_"),
                                "BENCH_balancer.json")
    path = out_path or os.path.join(ROOT, "BENCH_balancer.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"  wrote {path}")
    if not smoke and sections == set(ALL_SECTIONS):
        from .common import save_rows
        save_rows("balancer_bench", rows, meta=doc["meta"])
    return doc


def main(full: bool = False, smoke: bool = False,
         sections: str | None = None):
    run(full=full, smoke=smoke,
        sections=[s.strip() for s in sections.split(",") if s.strip()]
        if sections else None)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="also measure the dense baseline at N=2048")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, schema check only")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of sections to run "
                         f"(default: all of {','.join(ALL_SECTIONS)})")
    main(**vars(ap.parse_args()))
