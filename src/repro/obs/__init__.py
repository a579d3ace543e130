"""Per-request tracing, phase spans and barrier straggler attribution.

The fleet's telemetry (:mod:`repro.fleet.telemetry`) records *aggregate*
idle energy and imbalance per step.  This package is the microscope
underneath those totals — three instruments threaded through
:class:`~repro.serving.engine.ServingEngine`,
:class:`~repro.fleet.server.FleetServer`, and
:class:`~repro.fleet.async_server.AsyncFleetServer`.  Which clock each
field is on:

* the **modelled clock** (the engine's ``t_now``, ``step_overhead +
  t_token * max load`` per step; the fleet's clock): recorder points'
  ``t``, the trace's ``ts``, ``e2e_s`` / ``decode_s``, the request
  fields ``t_submit`` / ``t_first_token`` / ``t_finish``, telemetry and
  the ledger's joules.  Deterministic, never a speed;
* the **host clock** (``time.perf_counter()``): the request fields
  ``t_admit_host`` / ``t_first_chunk_host`` / ``t_first_token_host`` /
  ``t_finish_host`` (split into phases by
  :func:`repro.serving.host_phases`) and the ``host_s`` arg of every
  point the engine emits;
* the **device trace's clock** (a running ``jax.profiler``): the phase
  spans of :mod:`repro.obs.spans`.

**Phase spans** (:mod:`repro.obs.spans`, imported from there: it is
the one module here that needs jax).  ``ServingEngine.step`` and its
scheduler and cache backend open one ``TraceAnnotation`` per host phase
(names in ``SPANS``), so a profile shows which phase held the device
idle and how full each program's rows were.

**Per-request spans** (:mod:`repro.obs.trace`).  Every request emits
lifecycle point events on the deterministic sim clock (engine points
also carry ``host_s``); the exporter derives duration spans from them
and writes Chrome trace-event / Perfetto JSON (``--trace-out`` on
``launch/serve.py``; :func:`read_trace` is the validating reader).  The
span taxonomy:

* ``queued`` — the request enters the fleet (at its arrival time) or a
  bare engine's wait queue;
* ``routed`` — the fleet router assigns it to a replica;
* ``admitted`` — the engine claims a slot (sync prefill or a chunked
  prefill job); the first one stamps ``t_admit_host``;
* ``prefill-chunk`` — one chunked-prefill advance (offset + token
  count in args); the first one stamps ``t_first_chunk_host``;
* ``decode`` — the first token lands; decode begins; stamps
  ``t_first_token_host``;
* ``preempted`` — swap-out or recompute-drop under memory pressure
  (mode in args);
* ``resumed`` — a preempted victim re-enters a slot (swap-in restore or
  recompute re-admission);
* ``drain-handoff`` — a draining replica hands the request back to the
  fleet queue (async scale-down);
* ``completed`` / ``failed`` — terminal; ``completed`` stamps
  ``t_finish_host``.

The engine stamps a host field once, at its first occurrence, so a
preempted and re-admitted request keeps its first admission.

Fleet-tier events (track ``FLEET_TRACK``) are timestamped on the fleet
clock; engine-tier events on the owning replica's local clock (replicas
step independently between barriers, so the two clocks intentionally
differ — each Perfetto process row is self-consistent).  The derived
``request`` span on the fleet track carries ``e2e_s`` computed by the
same subtraction as telemetry's ``latency``, so the two are bit-equal.

The default recorder is :data:`NULL_RECORDER`, a no-op: with tracing
disabled no event is ever buffered and engine/fleet stats are
bit-identical to an uninstrumented run (gated by the ``obs`` bench
section).  The host stamps are set with or without a recorder.

**Straggler attribution** (:mod:`repro.obs.ledger`).  Each barrier step
the fleet identifies the *gating* replica (the ``argmax`` of the
per-replica step durations — the one every other replica waits for) and
decomposes each replica's barrier-idle joules by cause
(:data:`IDLE_CAUSES`):

* ``prefill_wave`` — the gating replica was processing prefill work
  (fresh admissions or chunked-prefill tokens);
* ``decode_tail`` — the gating replica was decoding a long tail;
* ``preempt_swap`` — the gating replica was preempting / swap-restoring
  victims (async: a DRAINING replica's idle);
* ``routing_miss`` — the replica sat completely idle while work waited
  elsewhere in the fleet (a routable request existed it could have
  served);
* ``warmup`` — a WARMING replica's idle draw before it joins (async
  autoscaling only);
* ``arrival_gap`` — fleet-wide idle between arrival waves (no work
  anywhere; the fast-forward branch of the barrier accounting).

Per step the split is reconciled so its left-fold sum reproduces the
step's idle joules *bit-exactly* (:func:`reconcile_split`); the
fleet-wide :class:`StragglerLedger` folds charges in the same order as
``FleetServer.idle_j``, so ``ledger.total_idle_j == fleet.idle_j`` to
the last bit.  Telemetry schema v4 surfaces the per-step split
(``idle_split``, aligned with :data:`IDLE_CAUSES`) and the gating
replica id (``gating_replica``; ``-1`` for trough and async tick rows).

Workflow: ``launch/serve.py --scenario diurnal --trace-out run.trace
--telemetry-out run.jsonl`` writes both artifacts;
``read_trace("run.trace")`` validates and summarizes the spans;
``FleetTelemetry.read_jsonl`` + ``summary()["idle_by_cause"]`` recovers
the ledger from the telemetry alone.  ``benchmarks/balancer_bench.py
--sections obs`` gates every exactness claim in CI.
"""
from .ledger import (
    IDLE_CAUSES,
    StragglerLedger,
    attribute_step_idle,
    fold_sum,
    reconcile_split,
)
from .trace import (
    FLEET_TRACK,
    NULL_RECORDER,
    NullRecorder,
    SpanRecorder,
    read_trace,
    to_chrome_trace,
    write_trace,
)

__all__ = [
    "IDLE_CAUSES", "StragglerLedger", "attribute_step_idle",
    "fold_sum", "reconcile_split",
    "FLEET_TRACK", "NULL_RECORDER", "NullRecorder", "SpanRecorder",
    "read_trace", "to_chrome_trace", "write_trace",
]
