"""The serving path's instrumentation, read back from a real profile.

``ServingEngine.step`` opens the phase spans of :mod:`repro.obs.spans`
(``jax.profiler.TraceAnnotation``), every jitted serving program runs
under a name of its own, and each request carries host-clock stamps of
its admission, first chunk, first token and finish.  These tests run the
tiny paged, chunked engine under ``jax.profiler`` on the CPU and read the
spans, their args and their nesting back with ``ProfileData``.
"""
import glob
import math
import os
import subprocess
import sys
import warnings

warnings.filterwarnings("ignore")

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import make_policy
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.launch.serve import format_host_phases
from repro.obs import SpanRecorder, to_chrome_trace
from repro.obs.spans import SPANS, span
from repro.serving import EngineConfig, ServeRequest, ServingEngine
from repro.serving import host_phases
from repro.serving import cache_backend, engine as engine_mod

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")
PAGED = dict(n_workers=2, slots_per_worker=2, max_seq_len=64,
             cache_backend="paged", paged_block_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def setup():
    params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
    return params, make_local_mesh()


def _requests(seed=3, n=10):
    rng = np.random.default_rng(seed)
    return [ServeRequest(rid=i, tokens=rng.integers(
                1, 128, size=int(rng.integers(6, 30))),
            max_new_tokens=int(rng.integers(2, 12))) for i in range(n)]


def _serve(setup, reqs, *, policy="bfio_h0", obs=None, **ec):
    params, mesh = setup
    eng = ServingEngine(CFG, params, EngineConfig(**dict(PAGED, **ec)),
                        make_policy(policy), mesh=mesh, obs=obs)
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=5000)
    return eng


def _profiled(setup, tmp_path, reqs, **kw):
    """Serve ``reqs`` under a running profiler; returns the engine and
    the host events of the profile as (name, start, end, stats)."""
    _serve(setup, _requests(seed=99, n=3), **kw)      # compile first
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng = _serve(setup, reqs, **kw)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                events.append((e.name, e.start_ns, e.end_ns,
                               dict(e.stats)))
    return eng, events


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    reqs = _requests()
    eng, events = _profiled(setup, tmp_path_factory.mktemp("prof"), reqs)
    spans = sorted((e for e in events if e[0].startswith("serve.")),
                   key=lambda e: (e[1], -e[2]))
    return eng, spans, events, reqs


def _parent(spans, child):
    """The innermost span that holds ``child``, or None."""
    best = None
    for s in spans:
        if s is child:
            continue
        if s[1] <= child[1] and child[2] <= s[2]:
            if best is None or s[1] >= best[1]:
                best = s
    return best


PARENT = {"serve.admit": "serve.step", "serve.place": "serve.admit",
          "serve.plan": "serve.step", "serve.chunk": "serve.step",
          "serve.chunk.launch": "serve.chunk",
          "serve.chunk.wait": "serve.chunk",
          "serve.chunk.commit": "serve.step",
          "serve.reserve": "serve.step", "serve.account": "serve.step",
          "serve.decode": "serve.step",
          "serve.decode.launch": "serve.decode",
          "serve.decode.wait": "serve.decode",
          "serve.decode.commit": "serve.step"}


def test_every_step_has_one_serve_step_holding_its_phases(traced):
    eng, spans, _, _ = traced
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == eng.steps
    for s in spans:
        if s[0] == "serve.step":
            assert _parent(spans, s) is None
            continue
        assert _parent(spans, s)[0] == PARENT[s[0]], s[0]
    for st in steps:
        inside = {s[0] for s in spans if st[1] <= s[1] and s[2] <= st[2]}
        assert {"serve.admit", "serve.reserve", "serve.account"} <= inside
        assert st[3]["waiting"] >= 0 and 0 <= st[3]["active"] <= 4
    seen = {s[0] for s in spans}
    assert seen == set(SPANS)


def test_every_name_is_declared(traced):
    """``engine.step`` is the benchmark harness's own per-step span: a
    phase span of that name would count as a second step there."""
    _, spans, events, _ = traced
    assert {s[0] for s in spans} <= set(SPANS)
    assert not any(e[0] == "engine.step" for e in events)
    assert len(SPANS) == len(set(SPANS)) and "engine.step" not in SPANS
    with pytest.raises(ValueError, match="unknown span"):
        span("serve.elsewhere")


def test_chunk_and_decode_spans_carry_rows_bucket_tokens(traced):
    _, spans, _, _ = traced
    chunks = [s[3] for s in spans if s[0] == "serve.chunk"]
    decodes = [s[3] for s in spans if s[0] == "serve.decode"]
    assert chunks and decodes
    for a in chunks:
        assert 1 <= a["rows"] <= a["bucket"]
        assert a["chunk"] == PAGED["prefill_chunk"]
        assert a["rows"] <= a["tokens"] <= a["bucket"] * a["chunk"]
    for a in decodes:
        assert 1 <= a["rows"] <= a["bucket"] and a["tokens"] == a["rows"]
    # the chunk spans count every prompt token the engine prefilled
    assert sum(a["tokens"] for a in chunks) == sum(
        len(r.tokens) for r in _requests())


def test_host_stamps_are_ordered_on_every_finished_request(setup):
    reqs = _requests(seed=5, n=12)
    _serve(setup, reqs)
    for r in reqs:
        assert r.status == "done"
        stamps = (r.t_admit_host, r.t_first_chunk_host,
                  r.t_first_token_host, r.t_finish_host)
        assert all(math.isfinite(t) for t in stamps), r.rid
        assert list(stamps) == sorted(stamps), r.rid


def test_host_phases_split_admission_to_finish(setup):
    reqs = _requests(seed=6, n=8)
    _serve(setup, reqs)
    phases = host_phases(reqs)
    assert list(phases) == ["first_chunk", "prefill", "decode"]
    assert all(d.size == len(reqs) and (d >= 0).all()
               for d in phases.values())
    total = sum(phases.values())
    want = [r.t_finish_host - r.t_admit_host for r in reqs]
    assert total == pytest.approx(want, rel=1e-9, abs=1e-12)
    line = format_host_phases(reqs)
    assert line.startswith("[serve] host clock, p50 / p90 ms: first_chunk ")
    assert "prefill" in line and "decode" in line


def test_host_phases_leave_out_what_a_request_lacks(setup):
    """The synchronous prefill path runs no chunk: only the decode phase
    has both stamps.  Requests not finished are left out."""
    reqs = _requests(seed=6, n=4)
    _serve(setup, reqs, cache_backend="slot", prefill_chunk=0)
    phases = host_phases(reqs + [ServeRequest(rid=9, tokens=[1, 2])])
    assert phases["first_chunk"].size == phases["prefill"].size == 0
    assert phases["decode"].size == len(reqs)
    assert format_host_phases([]).endswith("no finished request")


def test_the_obs_package_loads_without_jax():
    """Only the phase spans need jax; the ledger and the trace exporter
    do not, and ``repro.obs`` leaves ``repro.obs.spans`` to its users."""
    code = "import sys, repro.obs; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ))


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_preempted_request_keeps_its_first_admission(setup, mode):
    rec = SpanRecorder()
    reqs = _requests(seed=8, n=12)
    eng = _serve(setup, reqs, obs=rec, paged_pool_blocks=10,
                 preemption_mode=mode)
    assert eng.preemptions > 0
    points: dict = {}
    for _, rid, name, _, args in rec.events:
        points.setdefault(rid, []).append((name, args["host_s"]))
    preempted = [rid for rid, p in points.items()
                 if any(n == "preempted" for n, _ in p)]
    assert preempted
    for rid in preempted:
        r = reqs[rid]
        first = next(h for n, h in points[rid] if n == "admitted")
        later = [h for n, h in points[rid] if n in ("resumed", "admitted")]
        assert r.t_admit_host == first < later[-1]
        assert r.t_admit_host <= r.t_first_token_host <= r.t_finish_host


def test_points_carry_the_host_clock_beside_the_modelled_one(setup):
    rec = SpanRecorder()
    reqs = _requests(seed=4, n=4)
    _serve(setup, reqs, obs=rec)
    stamp = {"admitted": "t_admit_host", "decode": "t_first_token_host",
             "completed": "t_finish_host"}
    for _, rid, name, _, args in rec.events:
        if name in stamp:
            assert args["host_s"] == getattr(reqs[rid], stamp[name])
    doc = to_chrome_trace(rec)
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert instants and all("host_s" in e["args"] for e in instants)


def test_step_info_keys(setup):
    params, mesh = setup
    eng = ServingEngine(CFG, params, EngineConfig(**PAGED),
                        make_policy("fcfs"), mesh=mesh)
    eng.submit(_requests(n=1)[0])
    assert set(eng.step()) == {"t", "active", "waiting", "prefill_tokens",
                               "prefilling", "phase"}


PROGRAMS = [
    (lambda m: cache_backend._jitted_paged_decode(CFG, m, 8, "gather"),
     "paged_decode_step"),
    (lambda m: cache_backend._jitted_paged_chunk(CFG, m, 8),
     "paged_chunk_prefill"),
    (lambda m: cache_backend._jitted_decode_full(CFG, m),
     "slot_decode_full"),
    (lambda m: cache_backend._jitted_decode_compact(CFG, m),
     "slot_decode_compact"),
    (lambda m: cache_backend._jitted_chunk_prefill(CFG, m),
     "slot_chunk_prefill"),
    (lambda m: cache_backend._INSTALL, "install_prefill_rows"),
    (lambda m: engine_mod._jitted_decode(CFG, m), "ref_decode_step"),
    (lambda m: engine_mod._jitted_prefill(CFG, m, 64), "sync_prefill"),
]


@pytest.mark.parametrize("make,name", PROGRAMS,
                         ids=[n for _, n in PROGRAMS])
def test_each_serving_program_has_its_own_name(setup, make, name):
    assert make(setup[1]).__name__ == name


def test_profile_names_the_paged_programs(traced):
    _, _, events, _ = traced
    jitted = {e[0] for e in events if e[0].startswith("PjitFunction(")}
    assert {"PjitFunction(paged_chunk_prefill)",
            "PjitFunction(paged_decode_step)"} <= jitted
    assert not jitted & {"PjitFunction(f)", "PjitFunction(<lambda>)"}


def test_generations_and_stats_unchanged_under_a_profiler(setup, traced):
    eng_on, _, _, reqs_on = traced
    reqs_off = _requests()
    eng_off = _serve(setup, reqs_off)
    assert [r.generated for r in reqs_on] == \
        [r.generated for r in reqs_off]
    assert eng_on.stats() == eng_off.stats()


def _ladder_run(setup, monkeypatch, buckets=None):
    """Serve one fixed set of prompts on 32 paged slots, recording each
    chunk program call as (bucket rows, live rows, live rows' logits)
    and each ``serve.chunk`` span's args; ``buckets`` replaces the
    engine's row ladder."""
    params, mesh = setup
    spans = []

    def recording_span(name, **args):
        if name == "serve.chunk":
            spans.append(args)
        return span(name, **args)
    monkeypatch.setattr(engine_mod, "span", recording_span)
    eng = ServingEngine(
        CFG, params,
        EngineConfig(**dict(PAGED, n_workers=4, slots_per_worker=8)),
        make_policy("bfio_h0"), mesh=mesh)
    if buckets is not None:
        eng._buckets = buckets
    calls = []
    chunk = eng.backend.prefill_chunk

    def prefill_chunk(toks, offs, clens, slots):
        logits = chunk(toks, offs, clens, slots)
        live = slots >= 0
        calls.append((toks.shape[0], int(live.sum()), logits[live]))
        return logits
    eng.backend.prefill_chunk = prefill_chunk
    # an 8-token budget: 9 then 7 share a step (two rows), 16 takes two
    # one-row steps, then 4, 3 and the head of 12 share one (three rows)
    rng = np.random.default_rng(5)
    reqs = [ServeRequest(rid=i, tokens=rng.integers(1, 128, size=n),
                         max_new_tokens=int(rng.integers(3, 12)))
            for i, n in enumerate([9, 7, 16, 4, 3, 12])]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=5000)
    return eng, reqs, calls, spans


def test_chunk_program_runs_at_the_plans_rows_with_unchanged_tokens(
        setup, monkeypatch):
    eng, reqs, calls, spans = _ladder_run(setup, monkeypatch)
    assert eng._buckets == [1, 2, 4, 8, 32]
    for bucket, live, _ in calls:
        assert bucket == next(b for b in eng._buckets if b >= live)
    assert {live for _, live, _ in calls} >= {1, 2, 3}
    assert [(a["bucket"], a["rows"]) for a in spans] == \
        [(bucket, live) for bucket, live, _ in calls]

    # the same requests with every program padded to at least four rows
    _, reqs4, calls4, _ = _ladder_run(setup, monkeypatch, [4, 8, 32])
    assert {bucket for bucket, _, _ in calls4} == {4}
    assert [r.generated for r in reqs] == [r.generated for r in reqs4]
    assert len(calls) == len(calls4)
    for (_, live, logits), (_, live4, logits4) in zip(calls, calls4):
        assert live == live4
        np.testing.assert_allclose(logits, logits4, rtol=1e-5, atol=1e-5)
