"""End-to-end serving driver: a real (reduced) model served with batched
requests through the BF-IO-routed multi-worker engine.

Loads the granite-8b smoke variant, submits a heterogeneous batch of
requests, and runs FCFS vs BF-IO through the full engine (prefill ->
sticky placement -> barrier-stepped decode -> completion), verifying that
generated tokens are identical while efficiency differs.  The paged
backend is then driven through its full memory hierarchy:

* ``EngineConfig.prefix_cache=True`` — identical prompt prefixes share
  KV blocks (content-hash index, copy-on-write on divergence), so
  resident KV scales with *unique* content;
* ``EngineConfig.paged_pool_blocks`` undersized + ``preemption_mode=
  "swap"`` — the pool holds only half the peak demand and the engine
  preempts victims (host-side swap, LIFO) instead of raising
  ``MemoryError``, with bit-identical outputs (``"recompute"`` drops
  victims' KV and re-prefills instead — less host traffic, more FLOPs).

Finally the *fleet* tier (``repro.fleet``): the same model served by R
engine replicas behind a fleet router on a timed flash-crowd scenario —
round-robin vs two-tier BF-IO (router balances replicas, each replica's
scheduler balances its workers), with identical generations and the
efficiency gap read from the telemetry subsystem.

    PYTHONPATH=src python examples/serve_cluster.py
"""
import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core import make_policy
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.serving import EngineConfig, ServeRequest, ServingEngine

cfg = get_smoke_config("granite-8b")
params, _ = split_params(init_params(cfg, jax.random.PRNGKey(0)))
mesh = make_local_mesh()


def make_requests():
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(24):
        # bimodal prompt lengths: the regime where routing matters
        n = int(rng.integers(40, 60)) if i % 3 == 0 else int(
            rng.integers(4, 12))
        reqs.append(ServeRequest(
            rid=i, tokens=rng.integers(1, cfg.vocab_size, size=n),
            max_new_tokens=int(rng.integers(4, 12))))
    return reqs


results = {}
for policy in ["fcfs", "bfio_h0"]:
    engine = ServingEngine(
        cfg, params,
        EngineConfig(n_workers=2, slots_per_worker=4, max_seq_len=128),
        make_policy(policy), mesh=mesh)
    reqs = make_requests()
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    results[policy] = (stats, reqs)
    print(f"{policy:>8s}: {stats['tokens']} tokens, "
          f"{stats['steps']} steps, {stats['time_s']:.3f}s simulated, "
          f"imbalance {stats['avg_imbalance']:.1f}, "
          f"energy {stats['energy_j']:.1f} J")

# placement invariance: outputs must not depend on the router
gen_f = [r.generated for r in results["fcfs"][1]]
gen_b = [r.generated for r in results["bfio_h0"][1]]
assert gen_f == gen_b, "outputs must be identical across routers!"
print("\nOK: identical generations; BF-IO changed only efficiency "
      f"(imbalance /"
      f"{results['fcfs'][0]['avg_imbalance'] / max(results['bfio_h0'][0]['avg_imbalance'], 1e-9):.1f})")

# cache-backend invariance: the same requests through the paged KV cache
# (vLLM block tables + chunked prefill) must match the slot layout
# bit-for-bit — memory layout, like routing, is a pure efficiency knob
engine = ServingEngine(
    cfg, params,
    EngineConfig(n_workers=2, slots_per_worker=4, max_seq_len=128,
                 cache_backend="paged", paged_block_size=16,
                 prefill_chunk=32),
    make_policy("bfio_h0"), mesh=mesh)
reqs = make_requests()
for r in reqs:
    engine.submit(r)
paged_stats = engine.run()
assert [r.generated for r in reqs] == gen_b, \
    "paged backend diverged from the slot cache!"
assert paged_stats["tokens"] == results["bfio_h0"][0]["tokens"]
dense = engine.backend.pool_bytes()
print(f"OK: paged+chunked backend identical generations "
      f"({paged_stats['tokens']} tokens in {paged_stats['steps']} steps "
      f"— chunking spreads the admission waves); peak resident KV "
      f"{engine.kv_peak_bytes / 1e6:.2f} MB "
      f"({engine.kv_peak_bytes / dense:.0%} of the {dense / 1e6:.2f} MB "
      f"the slot layout pins)")
peak_blocks = -(-engine.kv_peak_bytes * engine.backend.n_blocks
                // max(engine.backend.pool_bytes(), 1))

# memory pressure: a pool sized at half the peak demand — the engine
# preempts (swap mode: victims' blocks staged host-side, restored
# bit-for-bit on resume) and still produces identical generations
engine = ServingEngine(
    cfg, params,
    EngineConfig(n_workers=2, slots_per_worker=4, max_seq_len=128,
                 cache_backend="paged", paged_block_size=16,
                 paged_pool_blocks=max(int(peak_blocks) // 2, 4),
                 preemption_mode="swap"),
    make_policy("bfio_h0"), mesh=mesh)
reqs = make_requests()
for r in reqs:
    engine.submit(r)
stats = engine.run(max_steps=5000)
assert [r.generated for r in reqs] == gen_b, \
    "swap preemption changed the outputs!"
assert stats["preemptions"] > 0
print(f"OK: pool at ~0.5x peak demand served everything via "
      f"{stats['preemptions']} preemptions ({stats['tokens_swapped']} KV "
      f"tokens swapped) with bit-identical generations")

# prefix caching: a shared system prompt is stored once and every
# request add-refs the shared blocks (copy-on-write on divergence)
rng = np.random.default_rng(11)
system = rng.integers(1, cfg.vocab_size, size=48)
engine = ServingEngine(
    cfg, params,
    EngineConfig(n_workers=2, slots_per_worker=4, max_seq_len=128,
                 cache_backend="paged", paged_block_size=16,
                 prefix_cache=True),
    make_policy("bfio_h0"), mesh=mesh)
reqs = [ServeRequest(rid=i,
                     tokens=np.concatenate(
                         [system,
                          rng.integers(1, cfg.vocab_size,
                                       size=int(rng.integers(4, 12)))]),
                     max_new_tokens=8) for i in range(16)]
for r in reqs:
    engine.submit(r)
stats = engine.run()
assert stats["prefix_hit_rate"] > 0
print(f"OK: prefix cache on a shared system prompt — "
      f"{stats['prefix_hits']}/{stats['prefix_queries']} block hits "
      f"({stats['prefix_hit_rate']:.0%}), peak resident KV "
      f"{engine.kv_peak_bytes / 1e6:.2f} MB")

# ----------------------------------------------------------------------
# Fleet mode: R=2 replicas behind a fleet router on a timed flash-crowd
# scenario.  Routing — like placement and memory layout above — is a
# pure efficiency knob: dense greedy decode is placement-invariant, so
# the generations must match across routers while imbalance and
# energy-per-token differ.  Metrics come from the telemetry subsystem
# (per-step per-replica records, JSONL-exportable).
# ----------------------------------------------------------------------
from repro.fleet import FleetServer, FleetTelemetry, make_scenario

scenario = make_scenario("flash_crowd", n_requests=24, n_replicas=2,
                         n_workers=2, slots_per_worker=4,
                         max_seq_len=128, vocab_size=cfg.vocab_size,
                         seed=3, step_overhead=1e-3, t_token=2e-4)
fleet_ec = EngineConfig(n_workers=2, slots_per_worker=4, max_seq_len=128,
                        step_overhead=1e-3, t_token=2e-4)
fleet_runs = {}
for router in ["round_robin", "bfio"]:
    tel = FleetTelemetry()
    fleet = FleetServer(cfg, params, fleet_ec, n_replicas=2,
                        router=router, policy="bfio_h0", mesh=mesh,
                        telemetry=tel)
    fleet.submit_scenario(scenario)
    stats = fleet.run()
    summary = tel.summary()
    fleet_runs[router] = (stats, summary,
                          [r.generated for r in fleet.requests])
    print(f"{router:>12s}: {stats['tokens']} tokens, "
          f"imbalance {stats['avg_cross_imbalance']:.1f}, "
          f"{stats['energy_per_token']:.3f} J/tok "
          f"({stats['idle_j']:.1f} J barrier idle), "
          f"TTFT p95 {summary['ttft']['p95']:.3f}s")

assert fleet_runs["round_robin"][2] == fleet_runs["bfio"][2], \
    "fleet outputs must not depend on the router!"
assert all(s["failed"] == 0 for s, _, _ in fleet_runs.values())
print("OK: fleet tier — identical generations across routers; two-tier "
      "BF-IO moved only the efficiency "
      f"(imbalance {fleet_runs['round_robin'][0]['avg_cross_imbalance']:.1f}"
      f" -> {fleet_runs['bfio'][0]['avg_cross_imbalance']:.1f})")

# ----------------------------------------------------------------------
# Scaling the replica axis (the ``fleet_scale`` regime).  The fleet hot
# path is vectorized (``fleet_mode="vec"``, the default): per-replica
# loads/counts/free-slots live in incrementally-updated numpy arrays
# instead of per-step Python gathers, so per-step fleet overhead stays
# O(touched replicas) instead of O(R).  The pre-vectorization loop stays
# live as ``fleet_mode="ref"`` and both modes must agree bit-for-bit —
# vectorization, like routing, must be a pure efficiency knob.
# ----------------------------------------------------------------------
ref_vec = {}
for fleet_mode in ["ref", "vec"]:
    fleet = FleetServer(cfg, params, fleet_ec, n_replicas=4,
                        router="bfio", policy="bfio_h0", mesh=mesh,
                        fleet_mode=fleet_mode)
    fleet.submit_scenario(scenario)
    ref_vec[fleet_mode] = (fleet.run(),
                           [r.generated for r in fleet.requests])
assert ref_vec["ref"] == ref_vec["vec"], \
    "vectorized fleet path diverged from the reference loop!"
print("OK: fleet_mode='vec' bit-identical to the ref loop at R=4 "
      f"({ref_vec['vec'][0]['steps']} steps, "
      f"{ref_vec['vec'][0]['tokens']} tokens)")

# At R in the hundreds a single global BF-IO solve per step is itself a
# bottleneck, so the router goes hierarchical: replicas are grouped into
# pods, one *batched* BF-IO solve scores all pods at once, then a
# per-pod solve places within the winner.  A predicted-output-length
# term ("oracle" reads each request's decode budget) sharpens the
# router's load estimates; heterogeneous replica classes (mixed
# worker/slot shapes) exercise capacity-aware routing.
pod_scenario = make_scenario("steady", n_requests=48, n_replicas=12,
                             n_workers=1, slots_per_worker=2,
                             max_seq_len=128, vocab_size=cfg.vocab_size,
                             seed=5, step_overhead=1e-3, t_token=2e-4)
pod_ec = EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=128,
                      step_overhead=1e-3, t_token=2e-4)
fleet = FleetServer(cfg, params, pod_ec, n_replicas=12,
                    router="pod_bfio_p4", policy="bfio_h0", mesh=mesh,
                    predictor="oracle")
fleet.submit_scenario(pod_scenario)
pod_stats = fleet.run()
assert pod_stats["failed"] == 0
print(f"OK: hierarchical pod routing (R=12, 4 pods, oracle length "
      f"predictor) — {pod_stats['tokens']} tokens, imbalance "
      f"{pod_stats['avg_cross_imbalance']:.1f}, "
      f"{pod_stats['energy_per_token']:.3f} J/tok")

# heterogeneous fleet: two small replicas (2 slots) + two large (4
# slots), grouped by class into pods behind the capacity-normalized
# pod router — under sustained pressure the large class absorbs more
# work in proportion to its capacity
import dataclasses

classes = [(2, pod_ec),
           (2, dataclasses.replace(pod_ec, slots_per_worker=4))]
fleet = FleetServer(cfg, params, pod_ec, router="pod_bfio_p2",
                    policy="bfio_h0", mesh=mesh, replica_classes=classes)
fleet.submit_scenario(make_scenario(
    "flash_crowd", n_requests=96, n_replicas=4, n_workers=1,
    slots_per_worker=3, max_seq_len=128, vocab_size=cfg.vocab_size,
    seed=9, step_overhead=1e-3, t_token=2e-4))
het_stats = fleet.run()
assert het_stats["failed"] == 0
small = sum(r["tokens"] for r in het_stats["replicas"][:2])
large = sum(r["tokens"] for r in het_stats["replicas"][2:])
assert large > small, "capacity-aware routing should favor the large class"
print(f"OK: heterogeneous fleet (2x 2-slot + 2x 4-slot pods) — "
      f"capacity-normalized routing sent {large} tokens to the large "
      f"class vs {small} to the small ({het_stats['tokens']} total, "
      f"0 failed)")

# ----------------------------------------------------------------------
# The async event-driven fleet (``repro.fleet.async_server``): replicas
# advance on their own clocks (no barrier), the router places arrivals
# against staleness-bounded load snapshots, and an autoscaler turns the
# replica count into a control variable on a diurnal trace — idle
# replicas power off through the trough and warm back up for the peak.
# Draining replicas hand resident requests off through the paged
# backend's host-staged swap path, so scaling — like every knob above —
# must be a pure efficiency decision: generations bit-identical to a
# fleet that never scaled, zero tokens recomputed.
# ----------------------------------------------------------------------
from repro.fleet import AsyncFleetServer, TargetUtilizationAutoscaler

async_ec = EngineConfig(n_workers=2, slots_per_worker=4, max_seq_len=128,
                        cache_backend="paged", paged_block_size=16,
                        preemption_mode="swap",
                        step_overhead=1e-3, t_token=2e-4)
diurnal = make_scenario("diurnal", n_requests=64, n_replicas=4,
                        n_workers=2, slots_per_worker=4, max_seq_len=128,
                        vocab_size=cfg.vocab_size, seed=5,
                        load_factor=0.4, step_overhead=1e-3,
                        t_token=2e-4)

fixed = AsyncFleetServer(cfg, params, async_ec, n_replicas=4,
                         router="bfio", policy="bfio_h0", mesh=mesh)
fixed.submit_scenario(diurnal)
fixed_stats = fixed.run()

scaled = AsyncFleetServer(
    cfg, params, async_ec, n_replicas=4, router="bfio",
    policy="bfio_h0", mesh=mesh, max_snapshot_age=0.05,
    autoscaler=TargetUtilizationAutoscaler(
        r_min=1, r_max=4, target=0.7, interval_s=0.05, warmup_s=0.02))
scaled.submit_scenario(diurnal)
scaled_stats = scaled.run()

assert [r.generated for r in scaled.requests] == \
    [r.generated for r in fixed.requests], \
    "autoscaling changed the outputs!"
assert scaled_stats["failed"] == 0
assert scaled_stats["drain_handoffs"] > 0
assert scaled_stats["drain_tokens_lost"] == 0
assert scaled_stats["idle_j"] < fixed_stats["idle_j"]
print(f"OK: async autoscaled fleet on the diurnal trough — idle energy "
      f"{fixed_stats['idle_j']:.1f} -> {scaled_stats['idle_j']:.1f} J, "
      f"{scaled_stats['energy_per_token']:.3f} vs "
      f"{fixed_stats['energy_per_token']:.3f} J/tok, mean replicas on "
      f"{scaled_stats['r_on_mean']:.2f}/4, "
      f"{scaled_stats['drain_handoffs']} drain handoff(s) with 0 tokens "
      f"recomputed and bit-identical generations")

# ----------------------------------------------------------------------
# Observability (``repro.obs``): the same diurnal run under the span
# recorder — every request's lifecycle lands in a Chrome-trace/Perfetto
# JSON on the deterministic sim clock, and each barrier step's idle
# joules are decomposed by cause into the straggler ledger.  Both are
# exact, not approximate: the ledger total folds to the fleet's
# ``idle_j`` bit-for-bit, and every trace request-span's ``e2e_s``
# equals the telemetry latency bit-for-bit.
# ----------------------------------------------------------------------
import os
import tempfile

from repro.fleet import SLOSpec
from repro.obs import SpanRecorder, fold_sum, read_trace, write_trace

rec = SpanRecorder()
tel = FleetTelemetry(slo=SLOSpec(ttft_s=0.5, tpot_s=0.1))
traced = FleetServer(cfg, params, async_ec, n_replicas=4,
                     router="bfio", policy="bfio_h0", mesh=mesh,
                     telemetry=tel, obs=rec)
traced.submit_scenario(diurnal)
traced_stats = traced.run()

ledger = traced.straggler_ledger()
assert ledger["total_idle_j"] == traced_stats["idle_j"]
assert all(fold_sum(s["idle_split"]) == s["idle_j"] for s in tel.steps)

trace_path = os.path.join(tempfile.mkdtemp(prefix="serve_cluster_"),
                          "diurnal.trace")
write_trace(rec, trace_path)
seen = read_trace(trace_path)
lat = {q["rid"]: q["latency"] for q in tel.requests}
assert set(seen["requests"]) == set(lat)
assert all(v["e2e_s"] == lat[rid] for rid, v in seen["requests"].items())

print(f"\nOK: traced diurnal run — {rec.n_events} span events across "
      f"{len(seen['requests'])} requests round-tripped through "
      f"{trace_path} (every e2e_s bit-equal to the telemetry latency); "
      f"straggler ledger folds to idle_j = {traced_stats['idle_j']:.3f} J "
      f"bit-exactly:")
print(traced.format_straggler_ledger())
