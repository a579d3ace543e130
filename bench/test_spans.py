"""The program's phase spans and program names, read back: idle gaps
labelled by the innermost span, the six readers on a synthetic trace
whose answers are known, and ``None`` from each where a trace (the
recorded chip trace) or a log has nothing of the program's to read."""
import gzip
import math
import types

import numpy as np
import pytest

from bench import harness, readout, spans, spec, system, tiny


def _ev(name, start, end, **stats):
    # ProfileData gives stats as (name, value) pairs
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start, end_ns=end,
                                 stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def _planes():
    """Two steps: a chunk and a decode, then a decode alone.  Device idle
    inside the first ``serve.step``: 1100-3400, 6800-8700, 10300-10900
    (4800 ns); inside the second: 12100-12900, 15300-15900 (1400 ns)."""
    host = _plane("/host:CPU", {"python": [
        _ev("engine.step", 1000, 11000, step_num=0),
        _ev("serve.step", 1100, 10900, waiting=1, active=2),
        _ev("serve.admit", 1200, 2200),
        _ev("serve.place", 1300, 1800),
        _ev("serve.plan", 2200, 2600),
        _ev("serve.chunk", 2600, 7000, rows=1, bucket=4, tokens=100,
            chunk=512),
        _ev("serve.chunk.launch", 2700, 3500),
        _ev("serve.chunk.wait", 3500, 6900),
        _ev("serve.chunk.commit", 7000, 7500),
        _ev("serve.reserve", 7500, 7700, preempted=0),
        _ev("serve.account", 7700, 8000),
        _ev("serve.decode", 8000, 10500, rows=2, bucket=4, tokens=2),
        _ev("serve.decode.launch", 8000, 8800),
        _ev("serve.decode.wait", 8800, 10400),
        _ev("serve.decode.commit", 10500, 10800),
        _ev("engine.step", 12000, 16000, step_num=1),
        _ev("serve.step", 12100, 15900, waiting=0, active=3),
        _ev("serve.admit", 12200, 12400),
        _ev("serve.plan", 12400, 12500),
        _ev("serve.decode", 12500, 15500, rows=3, bucket=4, tokens=3),
        _ev("serve.decode.launch", 12500, 13000),
        _ev("serve.decode.wait", 13000, 15400),
        _ev("serve.decode.commit", 15500, 15800)]})
    ops = [_ev("%fusion.1 = bf16[4,512]{1,0} fusion()", 3400, 5000),
           _ev("%fusion.2 = bf16[4,512]{1,0} fusion()", 5000, 6800),
           _ev("%while.1 = (s32[]) while()", 8700, 10300),
           _ev("%paged_decode_attention_pallas.3 = bf16[4,8]{1,0} "
               "custom-call()", 8700, 9000),
           _ev("%fusion.3 = bf16[4,8]{1,0} fusion()", 9000, 10300),
           _ev("%fusion.3 = bf16[4,8]{1,0} fusion()", 12900, 15300),
           _ev("%copy.1 = bf16[8]{0} copy()", 16500, 17000)]
    modules = [_ev("jit_paged_chunk_prefill(123)", 3400, 6800),
               _ev("jit_paged_decode_step(456)", 8700, 10300),
               _ev("jit_paged_decode_step(456)", 12900, 15300),
               _ev("jit_f(789)", 16500, 17000)]
    other = [_ev("%fusion.9 = f32[8]{0} fusion()", 1000, 16000)]
    return [host,
            _plane("/device:TPU:0", {"XLA Ops": ops,
                                     "XLA Modules": modules}),
            _plane("/device:TPU:1", {"XLA Ops": other,
                                     "XLA Modules": []})]


@pytest.fixture
def synthetic(monkeypatch):
    found = spans.reduce(_planes())
    monkeypatch.setattr(spans, "for_run", lambda run: found)
    return found


def _run(log=None):
    return types.SimpleNamespace(cell=types.SimpleNamespace(name="c"),
                                 log=log, peak=None, trace=object(),
                                 setup_s=0.0)


def test_spans_args_modules_and_window():
    s = spans.reduce(_planes())
    assert len(s.steps) == 2 and s.steps[0].args["waiting"] == 1
    assert s.named("serve.chunk")[0].args == dict(
        rows=1, bucket=4, tokens=100, chunk=512)
    assert s.window_ns == (1000, 16000)
    assert spans.program("jit_paged_chunk_prefill(123)") == \
        "jit_paged_chunk_prefill"
    assert spans.program_seconds(s) == pytest.approx(
        {"jit_paged_chunk_prefill": 3400e-9,
         "jit_paged_decode_step": 4000e-9})
    by = spans.ops_by_program(s)
    assert by["jit_paged_decode_step"][0] == (
        "fusion.3 = bf16[4,8] fusion()", pytest.approx(3700e-9))
    assert not any(op.startswith("while") for op, _ in
                   by["jit_paged_decode_step"])


def test_idle_is_cut_at_span_edges_and_named_by_the_innermost():
    s = spans.reduce(_planes())
    assert spans.host_gap_ns(s) == pytest.approx([4800, 1400])
    idle = {k: round(v * 1e9) for k, v in spans.idle_by_phase(s).items()}
    assert idle == {"serve.step": 200 + 200, "serve.admit": 500 + 200,
                    "serve.place": 500, "serve.plan": 400 + 100,
                    "serve.chunk": 200, "serve.chunk.launch": 700,
                    "serve.chunk.wait": 100, "serve.chunk.commit": 500,
                    "serve.reserve": 200, "serve.account": 300,
                    "serve.decode.launch": 700 + 400,
                    "serve.decode.wait": 100 + 100,
                    "serve.decode": 100 + 100,
                    "serve.decode.commit": 300 + 300}
    assert sum(idle.values()) == 4800 + 1400
    text = spans.describe(s)
    assert "serve.chunk.launch" in text and "jit_paged_chunk_prefill" in text


def test_step_args_are_summed_and_printed():
    s = spans.reduce(_planes())
    args = spans.step_args(s)
    assert args["waiting"] == (0.5, 1) and args["active"] == (2.5, 3)
    assert args["serve.chunk"] == dict(calls=1, rows=1, bucket=4,
                                       tokens=100)
    assert args["serve.decode"] == dict(calls=2, rows=5, bucket=8,
                                        tokens=5)
    assert args["preempted"] == 0
    text = spans.describe(s)
    assert "waiting mean 0.50 max 1, active mean 2.50 max 3" in text
    assert "live rows 1 of 4 bucket rows (25.00%), 100 tokens" in text
    assert "live rows 5 of 8 bucket rows (62.50%), 5 tokens" in text


def test_an_op_running_from_before_the_step_counts_as_busy():
    planes = _planes()
    dev = planes[1].lines[0]
    dev.events.append(_ev("%while.2 = (s32[]) while()", 500, 1700))
    s = spans.reduce(planes)
    assert spans.host_gap_ns(s)[0] == pytest.approx(4800 - 600)


def test_readers_on_a_known_trace(synthetic):
    read = {m: spec.load_reader(m) for m in
            ("host_gap_ms", "host_gap_ms.tput", "bfio_solve_share",
             "chunk_row_fill", "chunk_device_share")}
    run = _run()
    assert read["host_gap_ms"](run) == pytest.approx(3100e-6)
    assert read["host_gap_ms.tput"](run) == pytest.approx(3100e-6)
    assert read["bfio_solve_share"](run) == pytest.approx(
        100 * 500 / (9800 + 3800))
    assert read["chunk_row_fill"](run) == pytest.approx(100 * 100 / 2048)
    assert read["chunk_device_share"](run) == pytest.approx(
        100 * 3400 / 15000)


def _log(stamps, due, end_s=20.0, open_s=10.0, setup_done=110.0):
    sched = [types.SimpleNamespace(rid=i, due_s=d, window=True)
             for i, d in enumerate(due)]
    reqs = [None if s is None else types.SimpleNamespace(t_admit_host=s)
            for s in stamps]
    return types.SimpleNamespace(schedule=sched, reqs=reqs, open_s=open_s,
                                 end_s=end_s, setup_done=setup_done)


def test_admit_wait_reads_the_engine_stamp_on_the_harness_clock():
    read = spec.load_reader("admit_wait_p90_s")
    due = [10.0 + i for i in range(10)]
    origin = 100.0                       # setup_done - open_s
    waits = [0.05 * i for i in range(8)]
    stamps = [origin + d + w for d, w in zip(due, waits)]
    stamps += [math.nan, None]           # not admitted; not yet submitted
    got = read(_run(_log(stamps, due)))
    want = np.percentile(waits + [20.0 - due[8], 20.0 - due[9]], 90)
    assert got == pytest.approx(want)
    # a request due after the part of the window read is left out
    assert read(_run(_log(stamps[:8], due[:8], end_s=14.5))) == \
        pytest.approx(np.percentile(waits[:5], 90))


RECORDED = spec.BENCH / "data" / "minitron-4b.chat.xplane.pb.gz"
SIX = ("host_gap_ms", "host_gap_ms.tput", "admit_wait_p90_s",
       "bfio_solve_share", "chunk_row_fill", "chunk_device_share")


def test_each_reader_is_none_on_the_recorded_chip_trace(tmp_path,
                                                        monkeypatch):
    """The recorded trace predates the phase spans and the program names
    (every program is ``jit_f``), and its requests had no host stamps."""
    where = tmp_path / ".bench_trace" / "c" / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(
        gzip.decompress(RECORDED.read_bytes()))
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    assert spans.load(tmp_path / ".bench_trace" / "c") is None
    assert spans.load(tmp_path / "nothing") is None
    old = types.SimpleNamespace(generated=[], slot=0)    # no t_admit_host
    log = _log([None, None], [10.0, 11.0])
    log.reqs = [old, old]
    for name in SIX:
        assert spec.load_reader(name)(_run(log)) is None, name
    # and in an untraced run there is no trace to read
    assert spans.for_run(types.SimpleNamespace(trace=None)) is None


def test_engine_stamps_admission_before_the_harness_sees_it():
    """Served through the harness on the CPU: the engine's own admission
    stamp, on the harness's clock, never falls after the end of the step
    in which the harness first saw the slot taken."""
    seed = 2**31 + 21
    cell = tiny.cell()
    harness.prepare(require_tpu=False)
    sys_ = system.build(cell.config, seed)
    harness.warm_up(sys_.engine, cell.config["vocab_size"],
                    cell.config["engine"]["prefill_chunk"],
                    cell.mix["prompt"]["min"])
    log = harness.serve(sys_.engine, cell.mix, seed, 2.0,
                        cell.config["vocab_size"],
                        counter=harness.CompileCounter())
    sys_.close()
    origin = log.setup_done - log.open_s
    seen = 0
    for rid in readout.due_in_window(log):
        r = log.reqs[rid]
        if r is None or math.isnan(log.admit_s[rid]):
            continue
        assert r.t_admit_host - origin <= log.admit_s[rid] + 1e-9
        assert r.t_admit_host - origin >= log.submit_s[rid] - 1e-9
        seen += 1
    assert seen >= 10
    run = types.SimpleNamespace(cell=cell, log=log, peak=None, trace=None,
                                setup_s=0.0)
    admit = spec.load_reader("admit_wait_p90_s")(run)
    queue = spec.load_reader("queue_wait_p90_s")(run)
    assert 0 <= admit <= queue
