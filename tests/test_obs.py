"""Observability subsystem tests (repro.obs).

The anchors the ISSUE demands:

* the straggler ledger's arithmetic is *bit-exact by construction*:
  every reconciled split left-folds to its step total, and a fleet
  run's ledger total equals ``stats["idle_j"]`` to the last bit;
* the span recorder's trace round-trips through the validating reader,
  which rejects malformed documents instead of mis-reading them;
* fleet-track request spans carry the same end-to-end latency the
  telemetry computed (same subtraction, bit-equal);
* the disabled (null) recorder buffers nothing and leaves runs
  bit-identical — observation is free when off;
* the prefix-affinity probe and the engine's admission path share one
  block-hash chain per (request, block size) — each unique prompt is
  hashed exactly once, however many routing rounds it waits through.
"""
import collections
import json
import os
import warnings

warnings.filterwarnings("ignore")

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.fleet import (
    AsyncFleetServer,
    FleetServer,
    FleetTelemetry,
    SLOSpec,
    TargetUtilizationAutoscaler,
)
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.obs import (
    IDLE_CAUSES,
    NULL_RECORDER,
    SpanRecorder,
    StragglerLedger,
    attribute_step_idle,
    fold_sum,
    read_trace,
    reconcile_split,
    to_chrome_trace,
    write_trace,
)
from repro.obs.ledger import CAUSE_INDEX, N_CAUSES
from repro.serving import EngineConfig, ServeRequest

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")
TIMING = dict(step_overhead=1e-3, t_token=2e-4)


@pytest.fixture(scope="module")
def setup():
    params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
    mesh = make_local_mesh()
    return params, mesh


def _requests(seed=7, n=12, unique_head=False):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        toks = rng.integers(1, 128, size=int(rng.integers(4, 24)))
        if unique_head:
            toks[0] = i      # distinct first token -> distinct prompts
        reqs.append(ServeRequest(
            rid=i, tokens=toks,
            max_new_tokens=int(min(3 + rng.geometric(0.2), 16))))
    return reqs


# ----------------------------------------------------------------------
# Ledger arithmetic (unit level)
# ----------------------------------------------------------------------

class TestLedgerArithmetic:
    def test_fold_sum_is_sequential_accumulation(self):
        xs = [0.1, 0.2, 0.3, 1e16, -1e16, 0.4]
        total = 0.0
        for x in xs:
            total += x
        assert fold_sum(xs) == total
        # and it genuinely differs from pairwise/compensated summation
        # on adversarial inputs (the reason the helper exists)
        assert fold_sum(xs) != 1.0

    def test_reconcile_split_exact_on_adversarial_floats(self):
        # magnitudes spanning 16 decades: per-cause sums and the
        # sequential total round differently, so the residual is real
        rng = np.random.default_rng(0)
        for _ in range(500):
            slack = rng.uniform(0.0, 1.0, size=8) * 10.0 ** \
                rng.integers(-8, 8, size=8)
            total = 0.0
            for x in slack:                # the fleet's += order
                total += float(x)
            split = np.zeros(N_CAUSES)
            for i, x in enumerate(slack):
                split[i % N_CAUSES] += float(x)
            out = reconcile_split(total, split)
            assert fold_sum(out) == total
            # the fix-up only ever moves one entry
            assert (out == split).sum() >= N_CAUSES - 1

    def test_reconcile_split_zero_and_single_entry(self):
        out = reconcile_split(0.0, np.zeros(N_CAUSES))
        assert fold_sum(out) == 0.0
        one = np.zeros(N_CAUSES)
        one[2] = 3.5
        assert fold_sum(reconcile_split(3.5, one)) == 3.5

    def test_reconcile_split_raises_when_impossible(self):
        with pytest.raises(ArithmeticError, match="failed to reconcile"):
            reconcile_split(float("nan"), np.ones(N_CAUSES))

    def test_attribute_step_idle_masked_sums_fold_to_total(self):
        rng = np.random.default_rng(3)
        slack = rng.uniform(0.0, 2.0, size=16)
        causes = rng.integers(0, N_CAUSES, size=16)
        total = 0.0
        for x in slack:
            total += float(x)
        split = attribute_step_idle(total, slack, causes)
        assert split.shape == (N_CAUSES,)
        assert fold_sum(split) == total
        # causes with no replica get exactly zero
        for c in range(N_CAUSES):
            if not (causes == c).any():
                assert split[c] == 0.0

    def test_ledger_charge_matches_sequential_total(self):
        rng = np.random.default_rng(5)
        led = StragglerLedger()
        ref = 0.0
        for k in range(40):
            slack = rng.uniform(0.0, 1.0, size=4)
            causes = rng.integers(0, N_CAUSES, size=4)
            idle = 0.0
            for x in slack:
                idle += float(x)
            ref += idle                    # FleetServer.idle_j order
            led.charge(idle, attribute_step_idle(idle, slack, causes),
                       gating=k % 3 if k % 4 else -1)
        assert led.total_idle_j == ref
        rep = led.report()
        assert rep["total_idle_j"] == ref
        assert rep["charges"] == 40
        assert rep["trough_steps"] == 10
        assert sum(rep["gating_steps"].values()) == 30
        assert set(rep["by_cause"]) == set(IDLE_CAUSES)
        # the report is JSON-native
        assert json.loads(json.dumps(rep)) == rep

    def test_charge_one_and_format(self):
        led = StragglerLedger()
        led.charge_one(2.0, CAUSE_INDEX["warmup"])
        led.charge_one(1.0, CAUSE_INDEX["decode_tail"])
        assert led.total_idle_j == 3.0
        assert led.report()["by_cause"]["warmup"] == 2.0
        txt = led.format()
        assert "warmup" in txt and "decode_tail" in txt
        assert "3.000 J" in txt


# ----------------------------------------------------------------------
# Recorder + trace export / validating reader
# ----------------------------------------------------------------------

def _record_lifecycle(rec):
    rec.point(-1, 0, "queued", 0.00, n_prompt=5)
    rec.point(-1, 0, "routed", 0.01, replica=1)
    rec.point(1, 0, "admitted", 0.01, worker=0, slot=0)
    rec.point(1, 0, "prefill-chunk", 0.02, tokens=5)
    rec.point(1, 0, "decode", 0.03)
    rec.point(1, 0, "completed", 0.10, n_generated=4)
    rec.point(-1, 0, "completed", 0.12, replica=1)
    rec.point(-1, 1, "queued", 0.05)
    rec.point(-1, 1, "failed", 0.06)


class TestTrace:
    def test_recorder_buffers_and_clears(self):
        rec = SpanRecorder()
        assert rec.enabled and rec.n_events == 0
        _record_lifecycle(rec)
        assert rec.n_events == 9
        rec.clear()
        assert rec.n_events == 0

    def test_null_recorder_is_a_noop(self):
        NULL_RECORDER.point(-1, 0, "queued", 0.0, n_prompt=3)
        NULL_RECORDER.clear()
        assert NULL_RECORDER.enabled is False
        assert NULL_RECORDER.n_events == 0
        assert NULL_RECORDER.events == ()

    def test_roundtrip_through_validating_reader(self, tmp_path):
        rec = SpanRecorder()
        _record_lifecycle(rec)
        path = os.path.join(tmp_path, "run.trace")
        doc = write_trace(rec, path)
        # every recorded point appears as an instant event, plus the
        # derived spans and process-name metadata rows
        seen = read_trace(path)
        assert seen["n_points"] == rec.n_events
        assert seen["n_events"] == len(doc["traceEvents"])
        # fleet-track request spans: rid 0 done, rid 1 failed
        assert set(seen["requests"]) == {0, 1}
        r0 = seen["requests"][0]
        assert r0["status"] == "completed"
        assert r0["e2e_s"] == 0.12 - 0.00     # exporter's subtraction
        assert seen["requests"][1]["status"] == "failed"

    def test_exporter_derives_per_track_spans(self):
        rec = SpanRecorder()
        _record_lifecycle(rec)
        doc = to_chrome_trace(rec)
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        # fleet request spans (pid 0) for rids 0+1; the replica track
        # opens with "admitted" (not "queued") so it contributes only
        # the decode span
        kinds = {(e["pid"], e["name"]) for e in spans}
        assert (0, "request") in kinds
        assert (2, "decode-span") in kinds
        assert (2, "request") not in kinds
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert names == {"fleet", "replica 1"}

    def _write(self, tmp_path, events):
        path = os.path.join(tmp_path, "bad.trace")
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        return path

    def test_reader_rejects_malformed_documents(self, tmp_path):
        ok = {"name": "queued", "ph": "i", "s": "t", "ts": 1.0,
              "pid": 0, "tid": 0}
        span = {"name": "request", "ph": "X", "ts": 0.0, "dur": 1e6,
                "pid": 0, "tid": 0, "args": {"e2e_s": 1.0,
                                             "status": "completed"}}
        cases = [
            ("no traceEvents", {"foo": []}),
            ("unknown span event", [dict(ok, name="frobbed")]),
            ("bad ts", [dict(ok, ts=-5.0)]),
            ("unknown phase", [dict(ok, ph="B")]),
            ("bad dur", [dict(span, dur=None)]),
            ("dur/e2e_s mismatch",
             [dict(span, args={"e2e_s": 2.0, "status": "completed"})]),
            ("request span without e2e_s", [dict(span, args={})]),
            ("duplicate request span", [span, dict(span)]),
        ]
        for match, events in cases:
            if isinstance(events, dict):
                path = os.path.join(tmp_path, "bad.trace")
                with open(path, "w") as f:
                    json.dump(events, f)
            else:
                path = self._write(tmp_path, events)
            with pytest.raises(ValueError, match=match):
                read_trace(path)


# ----------------------------------------------------------------------
# Fleet integration: the exactness gates on real runs
# ----------------------------------------------------------------------

def _run_fleet(setup, *, async_fleet, recorder, telemetry):
    params, mesh = setup
    ec = EngineConfig(n_workers=1, slots_per_worker=2, max_seq_len=64,
                      cache_backend="paged", paged_block_size=16,
                      preemption_mode="swap", **TIMING)
    if async_fleet:
        auto = TargetUtilizationAutoscaler(r_min=1, r_max=3, target=0.7,
                                           interval_s=0.05, warmup_s=0.02)
        fs = AsyncFleetServer(CFG, params, ec, n_replicas=3,
                              router="bfio", policy="bfio_h0", mesh=mesh,
                              telemetry=telemetry, autoscaler=auto,
                              max_snapshot_age=0.05, obs=recorder)
    else:
        fs = FleetServer(CFG, params, ec, n_replicas=3, router="bfio",
                         policy="bfio_h0", mesh=mesh,
                         telemetry=telemetry, obs=recorder)
    for i, r in enumerate(_requests(seed=11)):
        fs.submit(r, arrival_time=0.01 * i)
    stats = fs.run()
    assert stats["failed"] == 0
    return fs, stats


class TestFleetIntegration:
    @pytest.mark.parametrize("async_fleet", [False, True],
                             ids=["barrier", "async"])
    def test_ledger_total_is_bit_exact(self, setup, async_fleet):
        tel = FleetTelemetry(slo=SLOSpec(ttft_s=2.0, tpot_s=0.5))
        fs, stats = _run_fleet(setup, async_fleet=async_fleet,
                               recorder=SpanRecorder(), telemetry=tel)
        ledger = fs.straggler_ledger()
        assert stats["idle_j"] > 0
        assert ledger["total_idle_j"] == stats["idle_j"]
        # every v4 step row's split folds to its idle_j bit-exactly
        assert tel.steps
        for s in tel.steps:
            assert fold_sum(s["idle_split"]) == s["idle_j"]
        by_cause = tel.summary()["idle_by_cause"]
        assert set(by_cause) == set(IDLE_CAUSES)
        if not async_fleet:
            # barrier steps name a gating replica; its idle is zero by
            # definition, so some cause must carry the others' slack
            assert tel.summary()["gating_steps"]

    @pytest.mark.parametrize("async_fleet", [False, True],
                             ids=["barrier", "async"])
    def test_spans_match_telemetry_latency(self, setup, tmp_path,
                                           async_fleet):
        tel = FleetTelemetry(slo=SLOSpec(ttft_s=2.0, tpot_s=0.5))
        rec = SpanRecorder()
        _run_fleet(setup, async_fleet=async_fleet, recorder=rec,
                   telemetry=tel)
        path = os.path.join(tmp_path, "fleet.trace")
        write_trace(rec, path)
        seen = read_trace(path)
        assert seen["n_points"] == rec.n_events
        lat = {q["rid"]: q["latency"] for q in tel.requests}
        assert set(seen["requests"]) == set(lat)
        for rid, span in seen["requests"].items():
            assert span["e2e_s"] == lat[rid]      # bit-equal
            assert span["status"] == "completed"

    @pytest.mark.parametrize("async_fleet", [False, True],
                             ids=["barrier", "async"])
    def test_disabled_recorder_is_free(self, setup, async_fleet):
        tel_on = FleetTelemetry(slo=SLOSpec(ttft_s=2.0, tpot_s=0.5))
        fs_on, stats_on = _run_fleet(
            setup, async_fleet=async_fleet, recorder=SpanRecorder(),
            telemetry=tel_on)
        tel_off = FleetTelemetry(slo=SLOSpec(ttft_s=2.0, tpot_s=0.5))
        fs_off, stats_off = _run_fleet(
            setup, async_fleet=async_fleet, recorder=None,
            telemetry=tel_off)
        assert fs_off._obs_rec.n_events == 0
        assert stats_on == stats_off
        assert tel_on.steps == tel_off.steps
        assert tel_on.requests == tel_off.requests
        # the ledger stays on either way (it feeds telemetry v4)
        assert fs_off.straggler_ledger() == fs_on.straggler_ledger()

    def test_v4_telemetry_roundtrips_from_a_real_run(self, setup,
                                                     tmp_path):
        tel = FleetTelemetry(slo=SLOSpec(ttft_s=2.0, tpot_s=0.5))
        _run_fleet(setup, async_fleet=False, recorder=None,
                   telemetry=tel)
        path = os.path.join(tmp_path, "tel.jsonl")
        tel.write_jsonl(path)
        back = FleetTelemetry.read_jsonl(path)
        assert back.steps == tel.steps
        assert back.summary() == json.loads(json.dumps(tel.summary()))


# ----------------------------------------------------------------------
# Shared block-hash chains: one hash walk per (prompt, block size)
# ----------------------------------------------------------------------

class TestSharedPrefixChains:
    def test_each_prompt_hashed_once_across_probe_and_admission(
            self, setup, monkeypatch):
        """The affinity probe re-scores every still-queued candidate on
        every routing round; without the memoized chain each round
        re-hashes every waiting prompt.  With sharing, ``keys_for``
        runs exactly once per unique prompt — the probe's walk is the
        one the engine's admission path reuses."""
        from repro.serving.paged_cache import PrefixIndex

        params, mesh = setup
        calls = collections.Counter()
        orig = PrefixIndex.keys_for

        def spy(self, tokens, block_size):
            key = (tuple(int(t) for t in np.asarray(tokens)),
                   int(block_size))
            calls[key] += 1
            return orig(self, tokens, block_size)

        monkeypatch.setattr(PrefixIndex, "keys_for", spy)
        ec = EngineConfig(n_workers=1, slots_per_worker=1,
                          max_seq_len=64, cache_backend="paged",
                          paged_block_size=16, prefix_cache=True,
                          **TIMING)
        fs = FleetServer(CFG, params, ec, n_replicas=2,
                         router="bfio_affinity", policy="bfio_h0",
                         mesh=mesh)
        reqs = _requests(seed=13, n=10, unique_head=True)
        for r in reqs:                 # all at t=0: a persistent queue
            fs.submit(r, arrival_time=0.0)
        stats = fs.run()
        assert stats["failed"] == 0
        # two slots fleet-wide serving ten requests: the queue survived
        # many routing rounds, so the probe scored candidates repeatedly
        assert stats["steps"] > len(reqs)
        assert len(calls) >= len(reqs)
        repeats = {k: n for k, n in calls.items() if n > 1}
        assert not repeats, \
            f"prompts re-hashed despite the shared chain: {repeats}"
