"""Declared contracts of the ref/vec serving stack.

The passes are generic; everything repo-specific — which attributes are
step-scoped barrier state, which function pairs must keep a symmetric
ref/vec surface, which attribute names root a KV-pool object — lives
here as data.  Entries match files by *relative-path suffix*, so the
same registry drives the real tree, temp copies in mutation tests, and
the fixture corpus (tests pass their own :class:`Registry`).

Growing the system extends this file, not the passes: a new engine
stat accumulator is appended to the ``ServingEngine`` scope's
``attrs``; a new ref/vec seam adds a :class:`RefVecPair`; per-pair
``allow_ref`` / ``allow_vec`` declare the *intentional* surface
asymmetry (e.g. only the vec path touches the slot-table mirrors) so
that anything undeclared fails tier-1.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["StateScope", "VecSnapshotScope", "RefVecPair", "Registry",
           "DEFAULT_REGISTRY"]


@dataclasses.dataclass(frozen=True)
class StateScope:
    """Barrier-scope declaration (RA301): mutable per-class state that
    only ``roots``-rooted call graphs may write.  ``attrs`` are exact
    attribute names; ``attr_prefixes`` cover array families like the
    fleet's ``_snap_*`` caches."""

    file_suffix: str
    cls: str
    attrs: frozenset
    roots: frozenset                   # methods whose call graph may write
    attr_prefixes: tuple = ()

    def covers(self, attr: str) -> bool:
        return attr in self.attrs or any(
            attr.startswith(p) for p in self.attr_prefixes)


@dataclasses.dataclass(frozen=True)
class VecSnapshotScope:
    """Stale-snapshot contract (RA302): in ``cls``, methods reachable
    from ``vec_roots`` that mutate engine state (``mutators`` calls on
    anything derived from ``engines_attr``) must be followed by a
    ``refresh`` call — in the same method after the mutation, or in
    every vec-reachable caller after the call site."""

    file_suffix: str
    cls: str
    vec_roots: frozenset
    engines_attr: str = "engines"
    mutators: frozenset = frozenset({"step", "submit"})
    refresh: str = "_refresh"


@dataclasses.dataclass(frozen=True)
class RefVecPair:
    """A bit-identity-gated ref/vec seam (RA401/RA402): the two
    functions must touch the same config fields, stats/telemetry keys,
    self attributes, and shared-call keyword surface, minus the
    declared allowances.  ``cls=None`` declares a module-level pair."""

    file_suffix: str
    cls: Optional[str]
    ref: str
    vec: str
    allow_ref: frozenset = frozenset()
    allow_vec: frozenset = frozenset()


@dataclasses.dataclass(frozen=True)
class Registry:
    state_scopes: tuple = ()
    vec_scopes: tuple = ()
    pairs: tuple = ()
    # attribute names that root a shared-pool object wherever they
    # appear in a chain (RA204): self.kv.lengths[...] = x is a raw
    # pool mutation outside the owning module
    pool_roots: frozenset = frozenset({"kv", "allocator", "prefix"})
    # pool leaves that legitimately take functional re-assignment from
    # outside (jax arrays are updated by replacement) or wiring writes
    pool_functional_leaves: frozenset = frozenset(
        {"k_pool", "v_pool", "prefix"})
    # host accounting paths (RA104): step-rooted bookkeeping that must
    # stay numpy — an eager jnp op here dispatches to the device once
    # per barrier step
    host_hot: tuple = ()               # (file_suffix, qualname) pairs


_ENGINE = "serving/engine.py"
_FLEET = "fleet/server.py"
_ASYNC = "fleet/async_server.py"

DEFAULT_REGISTRY = Registry(
    state_scopes=(
        StateScope(
            file_suffix=_ENGINE, cls="ServingEngine",
            attrs=frozenset({
                "t_now", "steps", "energy_j", "imbalance_sum",
                "tokens_out", "kv_peak_bytes", "requests_failed",
                "preemptions", "tokens_swapped", "tokens_recomputed",
                "slot_tokens", "slot_load", "slot_age", "slot_max_new",
                "slot_eos", "slot_admit_seq", "_admit_seq", "slot_req",
            }),
            # the obs recorder wiring (`_obs_*`) is set once at
            # construction and only read on the hot paths
            attr_prefixes=("_obs_",),
            # submit is a documented pre-step entry point, drain the
            # fleet scale-down one; __init__ declares; everything else
            # must flow from step()/run()
            roots=frozenset({"__init__", "step", "run", "submit",
                             "drain"}),
        ),
        StateScope(
            file_suffix=_FLEET, cls="FleetServer",
            attrs=frozenset({
                "t_now", "steps", "idle_j", "imbalance_sum",
                "requests_failed", "_busy_mask", "_prev_preemptions",
                "_prev_prefix_hits", "_prev_prefix_revived",
                "_queue", "_live", "_seq",
            }),
            attr_prefixes=("_snap_", "_obs_"),
            roots=frozenset({"__init__", "step", "run", "submit",
                             "submit_scenario"}),
        ),
        StateScope(
            file_suffix=_ASYNC, cls="AsyncFleetServer",
            # inherited barrier state the async tick also writes, plus
            # the event-heap (`_ev_*`), replica-lifecycle (`_rs_*`),
            # autoscaler-window (`_as_*`), tick-accumulator (`_tick_*`)
            # and snapshot-timestamp (`_snap_*`) families
            attrs=frozenset({
                "t_now", "steps", "idle_j", "imbalance_sum",
                "_queue", "_live", "_prev_preemptions",
                "_prev_prefix_hits", "_prev_prefix_revived",
                "barrier_compat", "autoscaler",
                "max_snapshot_age", "record_routes", "route_log",
            }),
            attr_prefixes=("_ev_", "_rs_", "_as_", "_tick_", "_snap_",
                           "_obs_"),
            roots=frozenset({"__init__", "step", "run", "submit",
                             "submit_scenario"}),
        ),
    ),
    vec_scopes=(
        VecSnapshotScope(
            file_suffix=_FLEET, cls="FleetServer",
            vec_roots=frozenset({"_step_vec", "_route_vec"}),
        ),
    ),
    pairs=(
        RefVecPair(
            file_suffix=_ENGINE, cls="ServingEngine",
            ref="_decode_step_ref", vec="_decode_step_vec",
            # the seed path drives the flat cache + full-batch decode
            # directly; the vec path compacts through the backend seam
            # and the slot-table scalar mirrors, and records the backend
            # program's expert counters on its span
            allow_ref=frozenset({
                "attr:cache", "attr:params", "attr:_decode",
            }),
            allow_vec=frozenset({
                "attr:backend", "attr:_buckets", "attr:slot_age",
                "attr:slot_max_new", "attr:slot_eos", "attr:_record_moe",
            }),
        ),
        RefVecPair(
            file_suffix=_FLEET, cls="FleetServer",
            ref="_step_ref", vec="_step_vec",
            # each step drives its own route seam (checked as the
            # _route_ref/_route_vec pair below)
            allow_ref=frozenset({"attr:_route_ref"}),
            # the vec step reads the cached snapshot arrays instead of
            # re-gathering; both feed identical values to _account
            allow_vec=frozenset({"attr:_route_vec", "attr:_refresh",
                                 "attr:_busy_mask", "attr:_snap_*"}),
        ),
        RefVecPair(
            file_suffix=_FLEET, cls="FleetServer",
            ref="_route_ref", vec="_route_vec",
            # ref gathers engine state live; vec routes off snapshots
            allow_ref=frozenset({"attr:engines"}),
            allow_vec=frozenset({"attr:_refresh", "attr:_snap_*"}),
        ),
        RefVecPair(
            file_suffix=_ASYNC, cls="AsyncFleetServer",
            ref="_step_barrier", vec="_step_async",
            # the oracle side delegates wholesale to the inherited
            # barrier step; the async side's tick pipeline is its
            # declared (audited) surface — growing the tick beyond
            # these seams must be declared here
            allow_vec=frozenset({
                "attr:_next_time", "attr:_advance", "attr:_pop_events",
                "attr:_release_arrivals", "attr:_autoscale_due",
                "attr:_route_async", "attr:_start_pending",
                "attr:_record_tick",
            }),
        ),
        # the BF-IO swap-search backends (method="dense" vs the tiled
        # default) — module-level pair, gated bit-identical by
        # tests/test_bfio_swap.py
        RefVecPair(
            file_suffix="core/balancer_jax.py", cls=None,
            ref="_swap_once_dense", vec="_swap_once_tiled",
        ),
    ),
    host_hot=(
        (_ENGINE, "ServingEngine.step"),
        (_ENGINE, "ServingEngine._decode_step_ref"),
        (_ENGINE, "ServingEngine._decode_step_vec"),
        (_ENGINE, "ServingEngine.load_snapshot"),
        (_FLEET, "FleetServer._step_ref"),
        (_FLEET, "FleetServer._step_vec"),
        (_FLEET, "FleetServer._account"),
        (_FLEET, "FleetServer._dispatch"),
        (_ASYNC, "AsyncFleetServer._step_async"),
        (_ASYNC, "AsyncFleetServer._advance"),
        (_ASYNC, "AsyncFleetServer._route_async"),
        (_ASYNC, "AsyncFleetServer._record_tick"),
    ),
)
