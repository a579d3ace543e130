"""Fleet serving layer tests (repro.fleet).

The anchors the ISSUE demands:

* ``fleet(R=1, router=*)`` is bit-identical to a bare ServingEngine on
  the same stream — every router, stats compared dict-equal;
* routing is deterministic under a fixed seed (pod2 included: the fleet
  rng is owned and seeded by the server);
* scenario generators produce schema-valid, seed-reproducible streams;
* telemetry JSONL round-trips (and tampering is detected);
* per-request failure isolation at the fleet tier.
"""
import warnings

warnings.filterwarnings("ignore")

import os

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import make_policy
from repro.fleet import (
    SCENARIOS,
    FleetServer,
    FleetTelemetry,
    RouterContext,
    SLOSpec,
    make_router,
    make_scenario,
    validate_scenario,
)
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.serving import EngineConfig, ServeRequest, ServingEngine

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")
ROUTERS = ("round_robin", "least_loaded", "pod2", "bfio")


@pytest.fixture(scope="module")
def setup():
    params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
    mesh = make_local_mesh()
    return params, mesh


def _requests(seed=7, n=16):
    rng = np.random.default_rng(seed)
    return [ServeRequest(
        rid=i, tokens=rng.integers(1, 128, size=int(rng.integers(4, 24))),
        max_new_tokens=int(min(3 + rng.geometric(0.2), 20)))
        for i in range(n)]


def _ctx(loads, counts, wait_sizes, seed=0):
    loads = np.asarray(loads, dtype=np.float64)
    return RouterContext(
        k=0, loads=loads, counts=np.asarray(counts, dtype=np.int64),
        free_slots=np.full(len(loads), 4, dtype=np.int64),
        wait_sizes=np.asarray(wait_sizes, dtype=np.float64),
        rng=np.random.default_rng(seed))


# ----------------------------------------------------------------------
# Routers (unit level)
# ----------------------------------------------------------------------

class TestRouters:
    def test_round_robin_cycles(self):
        r = make_router("round_robin")
        a = r.route(_ctx([0, 0, 0], [0, 0, 0], [5, 5, 5, 5]))
        assert a.tolist() == [0, 1, 2, 0]
        a = r.route(_ctx([0, 0, 0], [0, 0, 0], [5]))
        assert a.tolist() == [1]          # counter persists across calls
        r.reset()
        assert r.route(_ctx([0, 0, 0], [0, 0, 0], [5])).tolist() == [0]

    def test_least_loaded_tracks_placements(self):
        r = make_router("least_loaded")
        # replica 1 starts lightest; after absorbing the 10 it is
        # heaviest, so the next two go to 0 then 2
        a = r.route(_ctx([4.0, 1.0, 5.0], [1, 1, 1], [10, 2, 3]))
        assert a.tolist() == [1, 0, 2]

    def test_pod_is_seed_deterministic(self):
        r = make_router("pod2")
        a = r.route(_ctx([0, 0, 0, 0], [3, 0, 1, 2], [1] * 6, seed=3))
        b = r.route(_ctx([0, 0, 0, 0], [3, 0, 1, 2], [1] * 6, seed=3))
        assert a.tolist() == b.tolist()

    def test_bfio_total_and_size_aware(self):
        r = make_router("bfio")
        # one huge + many small candidates onto two idle replicas: the
        # windowed-imbalance solve must not stack the huge one with the
        # small ones' sum exceeding balance — totals end up ~equal
        sizes = [40, 10, 10, 10, 10]
        a = r.route(_ctx([0.0, 0.0], [0, 0], sizes))
        assert a.shape == (5,) and ((a >= 0) & (a < 2)).all()
        per = [sum(s for s, g in zip(sizes, a) if g == rep)
               for rep in (0, 1)]
        assert abs(per[0] - per[1]) <= 10, per

    def test_make_router_rejects_unknown(self):
        with pytest.raises(ValueError, match="unknown fleet router"):
            make_router("zeta")

    def test_make_router_passthrough(self):
        r = make_router("bfio_h4")
        assert r.H == 4 and r.name == "bfio_h4"
        assert make_router(r) is r


# ----------------------------------------------------------------------
# fleet(R=1) == bare engine, per router
# ----------------------------------------------------------------------

class TestSingleReplicaParity:
    @pytest.mark.parametrize("router", ROUTERS)
    def test_stats_bit_identical(self, setup, router):
        params, mesh = setup
        ec = EngineConfig(n_workers=2, slots_per_worker=4, max_seq_len=64)
        eng = ServingEngine(CFG, params, ec, make_policy("bfio_h0"),
                            mesh=mesh)
        reqs = _requests()
        for r in reqs:
            eng.submit(r)
        bare = eng.run()
        bare_gens = [r.generated for r in reqs]

        fs = FleetServer(CFG, params, ec, n_replicas=1, router=router,
                         policy="bfio_h0", mesh=mesh)
        freqs = _requests()
        for r in freqs:
            fs.submit(r)
        stats = fs.run()
        assert stats["replicas"][0] == bare
        assert [r.generated for r in freqs] == bare_gens
        # fleet aggregates collapse to the single engine: no barrier
        # slack exists at R=1
        assert stats["idle_j"] == 0.0
        assert stats["energy_j"] == bare["energy_j"]
        assert stats["steps"] == bare["steps"]


# ----------------------------------------------------------------------
# Multi-replica semantics
# ----------------------------------------------------------------------

class TestFleetServer:
    @pytest.mark.parametrize("router", ROUTERS)
    def test_deterministic_under_fixed_seed(self, setup, router):
        params, mesh = setup
        ec = EngineConfig(n_workers=2, slots_per_worker=2, max_seq_len=64)

        def one():
            fs = FleetServer(CFG, params, ec, n_replicas=3, router=router,
                             policy="bfio_h0", mesh=mesh, seed=11)
            reqs = _requests(seed=3, n=18)
            for i, r in enumerate(reqs):
                fs.submit(r, arrival_time=0.02 * i)
            stats = fs.run()
            return dict(fs.assignments), stats, [r.generated for r in reqs]

        a1, s1, g1 = one()
        a2, s2, g2 = one()
        assert a1 == a2
        assert s1 == s2
        assert g1 == g2

    def test_generations_router_invariant(self, setup):
        """Dense greedy decode is placement-invariant: the router moves
        only efficiency, never outputs."""
        params, mesh = setup
        ec = EngineConfig(n_workers=2, slots_per_worker=2, max_seq_len=64)
        gens = {}
        for router in ROUTERS:
            fs = FleetServer(CFG, params, ec, n_replicas=2, router=router,
                             policy="bfio_h0", mesh=mesh)
            reqs = _requests(seed=5, n=12)
            for r in reqs:
                fs.submit(r)
            stats = fs.run()
            assert stats["completed"] == len(reqs)
            assert stats["failed"] == 0
            gens[router] = [r.generated for r in reqs]
        assert all(g == gens[ROUTERS[0]] for g in gens.values())

    def test_arrivals_respected_and_clock_advances(self, setup):
        params, mesh = setup
        ec = EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64)
        tel = FleetTelemetry()
        fs = FleetServer(CFG, params, ec, n_replicas=2,
                         router="round_robin", policy="fcfs", mesh=mesh,
                         telemetry=tel)
        reqs = _requests(seed=2, n=6)
        fs.submit(reqs[0])
        for r in reqs[1:]:
            fs.submit(r, arrival_time=5.0)   # far future: forces idling
        info = fs.step()
        # only the first request is in flight; the rest are pending
        assert info["waiting"] == 5
        stats = fs.run()
        assert stats["completed"] == 6
        assert stats["time_s"] >= 5.0        # clock rode the gap
        assert stats["idle_j"] > 0.0         # idle draw was charged
        # latency is measured from each request's own arrival, not from
        # the fleet epoch: the t=5 arrivals must not inherit the gap
        late = [r for r in tel.requests if r["rid"] != reqs[0].rid]
        assert late and all(r["latency"] < 4.0 for r in late)
        assert all(r["t_arrival"] == 5.0 for r in late)

    def test_failure_isolated_at_fleet_tier(self, setup):
        """A request the pool can never serve fails alone — the fleet
        keeps serving, and the telemetry records the error."""
        params, mesh = setup
        ec = EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=256,
                          cache_backend="paged", paged_block_size=16,
                          paged_pool_blocks=4)
        tel = FleetTelemetry()
        fs = FleetServer(CFG, params, ec, n_replicas=2, router="bfio",
                         policy="fcfs", mesh=mesh, telemetry=tel)
        doomed = ServeRequest(rid=0, tokens=np.arange(1, 61),
                              max_new_tokens=30)
        rest = [ServeRequest(rid=1 + i, tokens=np.arange(1, 9),
                             max_new_tokens=4) for i in range(4)]
        fs.submit(doomed)
        for r in rest:
            fs.submit(r)
        stats = fs.run()
        assert doomed.status == "failed"
        assert "exceeds the entire pool" in doomed.error
        assert stats["failed"] == 1
        assert stats["completed"] == 4
        assert all(r.status == "done" for r in rest)
        failed = [r for r in tel.requests if r["status"] == "failed"]
        assert len(failed) == 1 and failed[0]["rid"] == 0
        assert "exceeds the entire pool" in failed[0]["error"]

    def test_rejects_bad_replica_count(self, setup):
        params, mesh = setup
        with pytest.raises(ValueError, match="n_replicas"):
            FleetServer(CFG, params, EngineConfig(), n_replicas=0,
                        router="bfio", mesh=mesh)


# ----------------------------------------------------------------------
# Scenario trace suite
# ----------------------------------------------------------------------

class TestScenarios:
    KW = dict(n_requests=20, n_replicas=2, n_workers=2,
              slots_per_worker=2, max_seq_len=64, vocab_size=128)

    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_schema_valid(self, name):
        sc = make_scenario(name, seed=0, **self.KW)
        assert sc.n_requests == 20
        validate_scenario(sc, max_seq_len=64, vocab_size=128)
        assert sc.meta["seed"] == 0 and sc.meta["n_replicas"] == 2

    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_seed_reproducible(self, name):
        a = make_scenario(name, seed=3, **self.KW)
        b = make_scenario(name, seed=3, **self.KW)
        c = make_scenario(name, seed=4, **self.KW)
        for ra, rb in zip(a.requests, b.requests):
            assert ra.arrival_time == rb.arrival_time
            assert (ra.tokens == rb.tokens).all()
            assert ra.max_new_tokens == rb.max_new_tokens
        assert any(
            ra.arrival_time != rc.arrival_time
            or ra.tokens.shape != rc.tokens.shape
            or (ra.tokens != rc.tokens).any()
            for ra, rc in zip(a.requests, c.requests)), \
            "different seeds produced an identical stream"

    def test_agentic_shares_a_prefix(self):
        sc = make_scenario("agentic", seed=1, **self.KW)
        pl = sc.meta["shared_prefix_len"]
        head = sc.requests[0].tokens[:pl]
        assert all((r.tokens[:pl] == head).all() for r in sc.requests)

    def test_unknown_scenario_rejected(self):
        with pytest.raises(ValueError, match="unknown scenario"):
            make_scenario("weekend", seed=0, **self.KW)

    def test_scenario_runs_end_to_end(self, setup):
        params, mesh = setup
        sc = make_scenario("steady", seed=0, **self.KW)
        ec = EngineConfig(n_workers=2, slots_per_worker=2, max_seq_len=64)
        fs = FleetServer(CFG, params, ec, n_replicas=2, router="bfio",
                         policy="bfio_h0", mesh=mesh)
        fs.submit_scenario(sc)
        stats = fs.run()
        assert stats["completed"] == sc.n_requests
        assert stats["failed"] == 0


# ----------------------------------------------------------------------
# Telemetry
# ----------------------------------------------------------------------

class TestTelemetry:
    def _filled(self, setup):
        params, mesh = setup
        tel = FleetTelemetry(slo=SLOSpec(ttft_s=2.0, tpot_s=0.5))
        ec = EngineConfig(n_workers=2, slots_per_worker=2, max_seq_len=64)
        fs = FleetServer(CFG, params, ec, n_replicas=2, router="bfio",
                         policy="bfio_h0", mesh=mesh, telemetry=tel)
        reqs = _requests(seed=9, n=10)
        for i, r in enumerate(reqs):
            fs.submit(r, arrival_time=0.01 * i)
        fs.run()
        return tel

    def test_jsonl_round_trip(self, setup, tmp_path):
        tel = self._filled(setup)
        assert tel.steps and tel.requests
        path = os.path.join(tmp_path, "tel.jsonl")
        tel.write_jsonl(path)
        back = FleetTelemetry.read_jsonl(path)
        assert back.steps == tel.steps
        assert back.requests == tel.requests
        assert back.slo == tel.slo
        assert back.summary() == tel.summary()

    def test_tampered_summary_detected(self, setup, tmp_path):
        tel = self._filled(setup)
        path = os.path.join(tmp_path, "tel.jsonl")
        tel.write_jsonl(path)
        lines = open(path).read().splitlines()
        lines[1] = lines[1].replace('"tokens": ', '"tokens": 9')
        open(path, "w").write("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="does not match"):
            FleetTelemetry.read_jsonl(path)

    def test_summary_shape(self, setup):
        tel = self._filled(setup)
        s = tel.summary()
        assert s["n_requests"] == 10 and s["completed"] == 10
        assert s["failed"] == 0
        assert s["tokens"] > 0 and s["energy_j"] > 0
        assert s["energy_per_token"] > 0
        assert 0.0 <= s["slo_attainment"] <= 1.0
        for key in ("ttft", "tpot", "latency"):
            assert set(s[key]) == {"p50", "p95", "p99"}
        assert s["ttft"]["p50"] is not None
        assert s["ttft"]["p50"] <= s["ttft"]["p95"] <= s["ttft"]["p99"]

    def test_empty_percentiles_are_none(self):
        from repro.fleet import percentiles
        assert percentiles([]) == {"p50": None, "p95": None, "p99": None}
        assert percentiles([None, float("nan")])["p95"] is None

# ----------------------------------------------------------------------
# fleet_mode="ref" vs "vec" parity (the fleet_scale tentpole gate)
# ----------------------------------------------------------------------

def _run_both_modes(params, mesh, ec, sc, router, *, R=None,
                    replica_classes=None, predictor=None, seed=0):
    """Run the same scenario under both fleet modes; return
    {mode: (stats, telemetry)}."""
    out = {}
    for mode in ("ref", "vec"):
        tel = FleetTelemetry()
        fs = FleetServer(CFG, params, ec, n_replicas=R or 1,
                         router=router, policy="bfio_h0", mesh=mesh,
                         telemetry=tel, seed=seed, fleet_mode=mode,
                         replica_classes=replica_classes,
                         predictor=predictor)
        fs.submit_scenario(sc)
        out[mode] = (fs.run(), tel)
    return out


def _assert_modes_equal(out):
    s_ref, t_ref = out["ref"]
    s_vec, t_vec = out["vec"]
    assert s_ref == s_vec
    assert t_ref.steps == t_vec.steps
    assert t_ref.requests == t_vec.requests
    assert t_ref.summary() == t_vec.summary()


class TestFleetModeParity:
    PARITY_ROUTERS = ROUTERS + ("pod_bfio_p2",)

    @pytest.mark.parametrize("router", PARITY_ROUTERS)
    @pytest.mark.parametrize("R", (1, 8, 64))
    def test_ref_vec_bit_identical(self, setup, router, R):
        params, mesh = setup
        ec = EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=48)
        sc = make_scenario("trickle", n_requests=8 if R >= 64 else 14,
                           n_replicas=R, n_workers=1, slots_per_worker=2,
                           max_seq_len=48, seed=3,
                           step_overhead=1e-3, t_token=2e-4)
        out = _run_both_modes(params, mesh, ec, sc, router, R=R)
        _assert_modes_equal(out)
        assert out["vec"][0]["failed"] == 0
        assert out["vec"][0]["completed"] == sc.n_requests

    def test_rejects_bad_mode(self, setup):
        params, mesh = setup
        with pytest.raises(ValueError, match="fleet_mode"):
            FleetServer(CFG, params, EngineConfig(), n_replicas=1,
                        router="bfio", mesh=mesh, fleet_mode="fast")


# ----------------------------------------------------------------------
# Hierarchical pod routing (unit level)
# ----------------------------------------------------------------------

class TestPodRouting:
    def test_single_pod_matches_flat_bfio(self):
        from repro.fleet import BFIORouter, PodBFIORouter
        ctx = _ctx([3.0, 1.0, 4.0, 1.5], [2, 1, 3, 1],
                   [5, 9, 2, 6, 3, 7, 1])
        flat = BFIORouter().route(ctx)
        pod = PodBFIORouter(pods=1).route(ctx)
        assert np.array_equal(flat, pod)

    def test_pod_boundaries_respected(self):
        """Level 1 steers the whole batch to the lighter pod; level 2
        never places outside it."""
        from repro.fleet import PodBFIORouter
        ctx = _ctx([0.0, 0.0, 100.0, 100.0], [0, 0, 8, 8], [1, 1, 1, 1])
        out = PodBFIORouter(pods=2).route(ctx)
        assert set(out.tolist()) <= {0, 1}

    def test_uneven_pod_sizes(self):
        """R % pods != 0: contiguous pods of size ceil/floor, every
        assignment in range, both pods used under symmetric load."""
        from repro.fleet import PodBFIORouter
        r = PodBFIORouter(pods=2)
        ctx = _ctx([0.0] * 5, [0] * 5, [4.0] * 10)
        out = r.route(ctx)
        assert out.shape == (10,)
        assert ((out >= 0) & (out < 5)).all()
        assert set(out.tolist()) & {0, 1, 2}      # pod 0 = replicas 0-2
        assert set(out.tolist()) & {3, 4}         # pod 1 = replicas 3-4
        out2 = PodBFIORouter(pods=2).route(
            _ctx([0.0] * 5, [0] * 5, [4.0] * 10))
        assert np.array_equal(out, out2)          # deterministic

    def test_empty_candidates(self):
        from repro.fleet import PodBFIORouter
        out = PodBFIORouter(pods=2).route(_ctx([0.0, 0.0], [0, 0], []))
        assert out.shape == (0,)

    def test_capacity_normalized_level1(self):
        """A pod with double capacity absorbs proportionally more of a
        burst than its equal-loaded half-capacity sibling."""
        from repro.fleet import PodBFIORouter
        ctx = _ctx([0.0, 0.0], [0, 0], [1.0] * 12)
        ctx.capacity = np.array([4.0, 1.0])
        out = PodBFIORouter(pods=2).route(ctx)
        n0 = int((out == 0).sum())
        assert n0 > 12 - n0

    def test_make_router_parses_pod_bfio(self):
        from repro.fleet import PodBFIORouter, PowerOfDRouter
        r = make_router("pod_bfio_p16")
        assert isinstance(r, PodBFIORouter) and r.pods == 16
        r = make_router("pod_bfio_p8_h2")
        assert r.pods == 8 and r.H == 2
        assert r.name == "pod_bfio_p8_h2"
        assert make_router("pod_bfio").pods == 4       # default
        assert isinstance(make_router("pod2"), PowerOfDRouter)
        with pytest.raises(ValueError, match="pod_bfio suffix"):
            make_router("pod_bfio_x3")
        with pytest.raises(ValueError, match="pods"):
            make_router("pod_bfio_p0")


# ----------------------------------------------------------------------
# step() waiting count + telemetry deltas (regressions)
# ----------------------------------------------------------------------

class TestStepAccounting:
    @pytest.mark.parametrize("mode", ("ref", "vec"))
    def test_waiting_includes_replica_backlog(self, setup, mode):
        """step()['waiting'] must count the routed-but-unadmitted
        backlog queued at the replicas, not just fleet-pending arrivals
        (the old field was always 0 right after routing)."""
        params, mesh = setup
        ec = EngineConfig(n_workers=1, slots_per_worker=1, max_seq_len=64)
        fs = FleetServer(CFG, params, ec, n_replicas=1,
                         router="round_robin", policy="fcfs", mesh=mesh,
                         fleet_mode=mode)
        for i in range(5):
            fs.submit(ServeRequest(rid=i, tokens=np.arange(1, 9),
                                   max_new_tokens=4))
        info = fs.step()
        # 1 admitted into the single slot, 4 queued at the replica
        assert info["waiting"] == 4
        assert info["replica_waiting"] == [4]
        fs.run()

    @pytest.mark.parametrize("mode", ("ref", "vec"))
    def test_step_rows_carry_deltas_not_totals(self, setup, mode):
        """Per-step telemetry preemptions/prefix_hits are deltas: their
        sum equals the run total (feeding cumulative totals per row made
        the sum quadratically larger)."""
        params, mesh = setup
        ec = EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                          cache_backend="paged", paged_block_size=8,
                          prefix_cache=True)
        tel = FleetTelemetry()
        fs = FleetServer(CFG, params, ec, n_replicas=2, router="bfio",
                         policy="bfio_h0", mesh=mesh, telemetry=tel,
                         seed=0, fleet_mode=mode)
        sc = make_scenario("agentic", n_requests=12, n_replicas=2,
                           n_workers=1, slots_per_worker=2,
                           max_seq_len=64, seed=1)
        fs.submit_scenario(sc)
        stats = fs.run()
        assert stats["prefix_hits"] > 0
        assert sum(s["prefix_hits"] for s in tel.steps) \
            == stats["prefix_hits"]
        assert sum(s["preemptions"] for s in tel.steps) \
            == stats["preemptions"]
        assert all(s["prefix_hits"] >= 0 and s["preemptions"] >= 0
                   for s in tel.steps)
        assert tel.summary()["prefix_hits"] == stats["prefix_hits"]


# ----------------------------------------------------------------------
# Heterogeneous replica classes + predicted-output routing
# ----------------------------------------------------------------------

class TestHeterogeneousFleet:
    def test_replica_classes_expand_in_order(self, setup):
        params, mesh = setup
        small = EngineConfig(n_workers=1, slots_per_worker=1,
                             max_seq_len=48)
        big = EngineConfig(n_workers=2, slots_per_worker=2,
                           max_seq_len=48)
        sc = make_scenario("trickle", n_requests=10, n_replicas=3,
                           n_workers=1, slots_per_worker=2,
                           max_seq_len=48, seed=2)
        out = _run_both_modes(params, mesh, small, sc, "pod_bfio_p2",
                              replica_classes=[(1, small), (2, big)])
        _assert_modes_equal(out)
        stats = out["vec"][0]
        assert stats["n_replicas"] == 3
        assert stats["completed"] == 10 and stats["failed"] == 0
        fs = FleetServer(CFG, params, small, mesh=mesh,
                         replica_classes=[(1, small), (2, big)])
        assert fs._capacity.tolist() == [1.0, 4.0, 4.0]
        assert [e.N for e in fs.engines] == [1, 4, 4]

    def test_replica_classes_validated(self, setup):
        params, mesh = setup
        with pytest.raises(ValueError, match="count"):
            FleetServer(CFG, params, EngineConfig(), mesh=mesh,
                        replica_classes=[(0, EngineConfig())])
        with pytest.raises(ValueError, match="empty"):
            FleetServer(CFG, params, EngineConfig(), mesh=mesh,
                        replica_classes=[])

    def test_pred_weight_augments_sizes(self):
        from repro.fleet import BFIORouter
        ctx = _ctx([0.0, 0.0], [0, 0], [10.0, 10.0, 10.0])
        ctx.pred_out = np.array([100.0, 0.0, 0.0])
        plain = BFIORouter()._sizes(ctx)
        assert plain.tolist() == [10.0, 10.0, 10.0]
        weighted = BFIORouter(pred_weight=0.5)._sizes(ctx)
        assert weighted.tolist() == [60.0, 10.0, 10.0]
        # no predictor in the context -> weight is inert
        ctx.pred_out = None
        assert BFIORouter(pred_weight=0.5)._sizes(ctx).tolist() \
            == [10.0, 10.0, 10.0]

    def test_oracle_predictor_end_to_end(self, setup):
        from repro.fleet import BFIORouter
        params, mesh = setup
        ec = EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=48)
        sc = make_scenario("trickle", n_requests=10, n_replicas=2,
                           n_workers=1, slots_per_worker=2,
                           max_seq_len=48, seed=5)
        out = _run_both_modes(params, mesh, ec, sc,
                              BFIORouter(pred_weight=0.5), R=2,
                              predictor="oracle")
        _assert_modes_equal(out)
        assert out["vec"][0]["completed"] == 10

    def test_rejects_bad_predictor(self, setup):
        params, mesh = setup
        with pytest.raises(ValueError, match="predictor"):
            FleetServer(CFG, params, EngineConfig(), n_replicas=1,
                        mesh=mesh, predictor="psychic")
