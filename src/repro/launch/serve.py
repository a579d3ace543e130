"""Serving launcher: ``python -m repro.launch.serve --arch <id> --policy
bfio_h20`` — drives the BF-IO-routed multi-worker engine end to end.

Fleet mode (``--replicas R`` with R > 1, or ``--scenario``): drives R
engine replicas behind a fleet router (``--router round_robin |
least_loaded | pod2 | bfio | pod_bfio_pP``) on a named scenario trace
(``--scenario steady | flash_crowd | diurnal | agentic | long_doc |
trickle``; omitted = the same synthetic stream as single-engine mode,
all arriving at t=0).  ``--telemetry-out run.jsonl`` streams the
telemetry subsystem's per-step / per-request records plus the summary
to JSONL.  Fleet scaling knobs:

* ``--fleet-mode vec|ref`` picks the vectorized fleet hot path
  (incrementally-updated per-replica load arrays; the default) or the
  reference per-step O(R) re-gather loop kept for the bit-identity
  bench gate — both produce identical stats and telemetry.
* ``--pods P`` with P > 1 shortcuts ``--router pod_bfio_pP``:
  two-level hierarchical routing (capacity-normalized pod pick, then
  one batched BF-IO solve across all pods) for R in the hundreds.
* ``--replica-classes 2xg1b2,2xg2b4`` builds a heterogeneous fleet —
  each ``CxgGbB`` group adds C replicas with G workers x B slots
  (overriding ``--replicas/--workers/--slots``); the router sees
  per-replica capacity and the BF-IO tier balances load against it.
* ``--predictor oracle`` feeds the router each request's decode budget
  as a predicted output length (the BF-IO growth term then prices
  decode, not just prefill).

Async / autoscaling knobs (event-driven fleet):

* ``--async`` swaps the barrier-stepped fleet for the event-driven
  :class:`~repro.fleet.async_server.AsyncFleetServer` — per-replica
  clocks, staleness-bounded routing snapshots.
* ``--autoscale util|slo`` (implies ``--async``) closes the replica-
  count control loop: ``util`` holds windowed busy-fraction near a
  target, ``slo`` scales on windowed SLO attainment.  ``--r-min`` /
  ``--r-max`` bound the fleet size (``--r-max 0`` = ``--replicas``);
  draining replicas hand resident requests off bit-exactly via the
  paged backend's host-staged swap path.
* ``--slo-ttft`` / ``--slo-tpot`` set the SLO the telemetry scorecard
  (and the ``slo`` autoscaler) attains against.

Memory-pressure knobs (``--cache-backend paged`` only):

* ``--pool-blocks N`` sizes the shared KV block pool below the
  every-slot-at-max-seq default, oversubscribing memory the way real
  engines do; on exhaustion the engine *preempts* a victim instead of
  crashing.
* ``--preemption-mode swap|recompute`` picks what happens to the
  victim's KV: staged host-side and restored bit-for-bit on resume
  (swap), or dropped and re-prefilled from prompt + generated tokens
  (recompute).  ``--preemption-policy lifo|fifo|largest`` picks the
  victim.
* ``--prefix-cache`` shares identical prompt-prefix KV blocks across
  requests (content-hash index, copy-on-write on the first divergent
  append) — resident KV then scales with *unique* prefix content.
"""
from __future__ import annotations

import argparse
import dataclasses
import re

import jax
import numpy as np

from ..configs import get_config, get_smoke_config
from ..core import make_policy
from ..fleet import (
    AsyncFleetServer,
    FleetServer,
    FleetTelemetry,
    SLOSpec,
    make_autoscaler,
    make_scenario,
)
from ..fleet.workloads import SCENARIOS as FLEET_SCENARIOS
from ..models import init_params, split_params
from ..obs import SpanRecorder, read_trace, write_trace
from ..serving import EngineConfig, ServeRequest, ServingEngine
from .compile_cache import enable_compile_cache
from .mesh import make_local_mesh


def select_model(arch: str, smoke: bool):
    """(config, mesh) the launcher serves.  ``--smoke`` alone picks the
    reduced config: the backend never does, so on the CPU the full
    config is what runs (or fails to fit) unless ``--smoke`` is given.
    The mesh is one present device (:func:`~repro.launch.mesh.
    make_local_mesh`): engine workers and fleet replicas all live on it
    (ROADMAP R4)."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    return cfg, make_local_mesh()


def init_serving_params(cfg, seed: int = 0):
    """Random weights from ``seed`` as a value tree (the logical axes are
    dropped: nothing on one device shards).  One jitted program, so each
    float32 draw fuses into its cast to ``cfg.dtype`` instead of landing
    in device memory first (minitron-4b's float32 embedding draw alone
    would be 2.9 GiB)."""
    def init(key):
        return split_params(init_params(cfg, key))[0]
    return jax.jit(init)(jax.random.PRNGKey(seed))


def parse_replica_classes(spec: str, engine_cfg):
    """``"2xg1b2,2xg2b4"`` -> [(2, ec(G=1,B=2)), (2, ec(G=2,B=4))]:
    each ``CxgGbB`` group adds C replicas with G workers x B slots,
    inheriting every other knob from the base engine config."""
    out = []
    for part in spec.split(","):
        m = re.fullmatch(r"(\d+)xg(\d+)b(\d+)", part.strip())
        if not m:
            raise ValueError(
                f"bad replica class {part!r} (want e.g. '2xg1b2')")
        count, g, b = (int(x) for x in m.groups())
        out.append((count, dataclasses.replace(
            engine_cfg, n_workers=g, slots_per_worker=b)))
    return out


def serve_fleet(args, cfg, params, engine_cfg, mesh) -> None:
    """Fleet mode: R replicas behind the router, scenario arrivals,
    telemetry export."""
    router = args.router
    if args.pods > 1:
        router = f"pod_bfio_p{args.pods}"
    classes = parse_replica_classes(args.replica_classes, engine_cfg) \
        if args.replica_classes else None
    n_replicas = sum(c for c, _ in classes) if classes \
        else args.replicas
    telemetry = FleetTelemetry(
        slo=SLOSpec(ttft_s=args.slo_ttft, tpot_s=args.slo_tpot))
    recorder = SpanRecorder() if args.trace_out else None
    common = dict(n_replicas=args.replicas, router=router,
                  policy=args.policy, mesh=mesh, telemetry=telemetry,
                  seed=args.seed, fleet_mode=args.fleet_mode,
                  replica_classes=classes, predictor=args.predictor,
                  obs=recorder)
    if args.async_fleet or args.autoscale:
        autoscaler = None
        if args.autoscale:
            r_max = args.r_max or n_replicas
            autoscaler = make_autoscaler(
                args.autoscale, r_min=args.r_min, r_max=r_max)
        fleet = AsyncFleetServer(cfg, params, engine_cfg,
                                 autoscaler=autoscaler, **common)
    else:
        fleet = FleetServer(cfg, params, engine_cfg, **common)
    if args.scenario:
        sc = make_scenario(
            args.scenario, n_requests=args.requests,
            n_replicas=n_replicas, n_workers=args.workers,
            slots_per_worker=args.slots,
            max_seq_len=engine_cfg.max_seq_len,
            vocab_size=cfg.vocab_size, seed=args.seed)
        fleet.submit_scenario(sc)
    else:
        rng = np.random.default_rng(args.seed)
        for i in range(args.requests):
            fleet.submit(ServeRequest(
                rid=i,
                tokens=rng.integers(1, cfg.vocab_size,
                                    size=int(rng.integers(4, 64))),
                max_new_tokens=args.max_new))
    stats = fleet.run()
    summary = telemetry.summary()
    print(f"[fleet] {cfg.name} R={stats['n_replicas']} "
          f"router={stats['router']} "
          f"scenario={args.scenario or 'synthetic'}: "
          f"{stats['tokens']} tokens in {stats['steps']} steps; "
          f"modelled clock: {stats['throughput_tok_s']:.1f} tok/s, "
          f"E={stats['energy_j']:.1f} J "
          f"({stats['idle_j']:.1f} J barrier idle), "
          f"{stats['energy_per_token']:.3f} J/tok, "
          f"cross-replica imbalance {stats['avg_cross_imbalance']:.1f}")
    def _s(x):     # percentiles are None when nothing completed
        return "n/a" if x is None else f"{x:.3f}s"

    print(f"[fleet] requests: {stats['completed']} done, "
          f"{stats['failed']} failed; "
          f"TTFT p95 {_s(summary['ttft']['p95'])}, "
          f"latency p95 {_s(summary['latency']['p95'])}, "
          f"SLO attainment {summary['slo_attainment']:.0%}")
    if stats.get("fleet_kind") == "async":
        print(f"[fleet] async: utilization {stats['utilization']:.0%}, "
              f"mean replicas on {stats['r_on_mean']:.2f}/"
              f"{stats['n_replicas']}, "
              f"{stats['scale_ups']} scale-ups / "
              f"{stats['scale_downs']} scale-downs, "
              f"{stats['drain_handoffs']} drain handoffs "
              f"({stats['drain_tokens_lost']} tokens recomputed)")
    if args.telemetry_out:
        telemetry.write_jsonl(args.telemetry_out)
        print(f"[fleet] telemetry -> {args.telemetry_out} "
              f"({len(telemetry.steps)} step + "
              f"{len(telemetry.requests)} request records)")
    if recorder is not None:
        write_trace(recorder, args.trace_out)
        seen = read_trace(args.trace_out)   # validate what we wrote
        print(f"[fleet] trace -> {args.trace_out} "
              f"({seen['n_points']} points, "
              f"{len(seen['requests'])} request spans)")
        ledger = fleet.straggler_ledger()
        top = max(ledger["by_cause"].items(),
                  key=lambda kv: kv[1], default=(None, 0.0))
        print(f"[fleet] straggler ledger: "
              f"{ledger['total_idle_j']:.1f} J idle attributed over "
              f"{ledger['charges']} charges; top cause {top[0]} "
              f"({top[1]:.1f} J)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--policy", default="bfio_h8")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (the only way to get "
                         "one: the backend never swaps it in)")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-backend", default="slot",
                    choices=["slot", "paged"],
                    help="KV layout: contiguous per-slot rows or "
                         "vLLM-style paged blocks")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: max prompt tokens per request "
                         "per step (0 = synchronous)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="total prompt tokens per step across requests "
                         "(0 = same as --prefill-chunk)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="paged KV pool size in blocks (0 = capacity for "
                         "every slot at max_seq_len; smaller pools "
                         "oversubscribe and trigger preemption)")
    ap.add_argument("--preemption-mode", default="swap",
                    choices=["swap", "recompute"],
                    help="victim KV handling under memory pressure: swap "
                         "to host staging (bit-exact resume) or drop and "
                         "re-prefill on resume")
    ap.add_argument("--preemption-policy", default="lifo",
                    choices=["lifo", "fifo", "largest"],
                    help="victim selection under memory pressure")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share identical prompt-prefix KV blocks across "
                         "requests (paged backend, copy-on-write)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="fleet mode: number of engine replicas behind "
                         "the fleet router (1 = bare engine)")
    ap.add_argument("--router", default="bfio",
                    help="fleet router: round_robin | least_loaded | "
                         "pod2 | bfio[_hH] | pod_bfio[_pP][_hH]")
    ap.add_argument("--fleet-mode", default="vec",
                    choices=["vec", "ref"],
                    help="fleet hot path: vectorized per-replica load "
                         "arrays (vec, default) or the reference O(R) "
                         "per-step re-gather loop (ref) — stats and "
                         "telemetry are bit-identical")
    ap.add_argument("--pods", type=int, default=1,
                    help="with P > 1, route hierarchically via "
                         "pod_bfio_pP: pick a pod by normalized load, "
                         "then one batched BF-IO solve across all pods "
                         "(overrides --router)")
    ap.add_argument("--replica-classes", default=None,
                    help="heterogeneous fleet spec, e.g. '2xg1b2,2xg2b4' "
                         "= 2 replicas of 1 worker x 2 slots + 2 of "
                         "2 x 4 (overrides --replicas/--workers/--slots "
                         "for the fleet shape)")
    ap.add_argument("--predictor", default=None,
                    choices=["oracle"],
                    help="predicted-output-length router term: 'oracle' "
                         "feeds each request's decode budget to the "
                         "BF-IO growth model")
    ap.add_argument("--async", dest="async_fleet", action="store_true",
                    help="event-driven fleet (per-replica clocks, "
                         "staleness-bounded routing) instead of the "
                         "barrier-stepped FleetServer")
    ap.add_argument("--autoscale", default=None,
                    choices=["util", "slo"],
                    help="autoscaling policy (implies --async): hold "
                         "windowed utilization near target (util) or "
                         "scale on windowed SLO attainment (slo)")
    ap.add_argument("--r-min", type=int, default=1,
                    help="autoscaler floor on active replicas")
    ap.add_argument("--r-max", type=int, default=0,
                    help="autoscaler ceiling on active replicas "
                         "(0 = --replicas)")
    ap.add_argument("--slo-ttft", type=float, default=1.0,
                    help="SLO bound on time-to-first-token (s)")
    ap.add_argument("--slo-tpot", type=float, default=0.1,
                    help="SLO bound on time-per-output-token (s)")
    ap.add_argument("--scenario", default=None,
                    choices=sorted(FLEET_SCENARIOS),
                    help="named scenario trace for fleet mode (timed "
                         "arrivals); omitted = synthetic stream at t=0")
    ap.add_argument("--telemetry-out", default=None,
                    help="write fleet telemetry (per-step, per-request, "
                         "summary) to this JSONL path")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request lifecycle spans as Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing); also prints the straggler "
                         "ledger's idle-energy attribution")
    args = ap.parse_args()

    enable_compile_cache()
    cfg, mesh = select_model(args.arch, args.smoke)
    params = init_serving_params(cfg, args.seed)
    engine_cfg = EngineConfig(
        n_workers=args.workers, slots_per_worker=args.slots,
        max_seq_len=256, cache_backend=args.cache_backend,
        prefill_chunk=args.prefill_chunk,
        prefill_budget=args.prefill_budget,
        paged_pool_blocks=args.pool_blocks,
        preemption_mode=args.preemption_mode,
        preemption_policy=args.preemption_policy,
        prefix_cache=args.prefix_cache)
    if (args.replicas > 1 or args.scenario or args.telemetry_out
            or args.replica_classes or args.pods > 1
            or args.async_fleet or args.autoscale or args.trace_out):
        serve_fleet(args, cfg, params, engine_cfg, mesh)
        return
    eng = ServingEngine(cfg, params, engine_cfg,
                        make_policy(args.policy), mesh=mesh)

    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        eng.submit(ServeRequest(
            rid=i,
            tokens=rng.integers(1, cfg.vocab_size,
                                size=int(rng.integers(4, 64))),
            max_new_tokens=args.max_new))
    stats = eng.run()
    print(f"[serve] {cfg.name} policy={stats['policy']}: "
          f"{stats['tokens']} tokens in {stats['steps']} steps; "
          f"modelled clock: {stats['throughput_tok_s']:.1f} tok/s, "
          f"E={stats['energy_j']:.1f} J, "
          f"avg imbalance {stats['avg_imbalance']:.1f}")
    if args.cache_backend == "paged":
        # what the contiguous slot layout would pin (every slot at
        # max_seq_len) — NOT the pool size, which --pool-blocks may have
        # shrunk below it
        per_block = eng.backend.pool_bytes() // eng.backend.n_blocks
        dense = per_block * eng.backend.N * eng.backend.max_blocks
        print(f"[serve] paged KV: peak resident "
              f"{eng.kv_peak_bytes / 1e6:.2f} MB "
              f"({eng.kv_peak_bytes / max(dense, 1):.1%} of the "
              f"{dense / 1e6:.2f} MB the slot layout pins)")
        if stats["preemptions"]:
            print(f"[serve] memory pressure: {stats['preemptions']} "
                  f"preemptions ({args.preemption_mode}), "
                  f"{stats['tokens_swapped']} KV tokens swapped, "
                  f"{stats['tokens_recomputed']} recomputed")
        if args.prefix_cache:
            print(f"[serve] prefix cache: {stats['prefix_hits']}/"
                  f"{stats['prefix_queries']} block hits "
                  f"({stats['prefix_hit_rate']:.1%})")


if __name__ == "__main__":
    main()
