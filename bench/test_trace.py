"""The trace reduction: busy time as the union of device op intervals,
idle gaps named by the host's span, kernel time by a metric's matcher."""
import types

import pytest

from bench import spec, trace
from bench.metrics import paged_attn_roofline


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 end_ns=start + dur, stats=stats)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def _planes():
    host = _plane("/host:CPU", {"python": [
        _ev("engine.step", 1000, 3000), _ev("other", 0, 10),
        _ev("engine.step", 6000, 3000)]})
    ops = [_ev("%fusion.1 = f32[8]{0} fusion()", 1500, 1000),
           _ev("%paged_decode_attention_pallas.3 = bf16[4,8]{1,0} "
               "custom-call()", 2000, 1500),
           _ev("%fusion.2 = f32[8]{0} fusion()", 6500, 500),
           _ev("%fusion.1 = f32[8]{0} fusion()", 8000, 1500),
           _ev("%while.1 = (s32[]) while()", 6400, 700),
           _ev("%before = f32[8]{0} fusion()", 0, 900)]
    device = _plane("/device:TPU:0", {"XLA Ops": ops,
                                      "XLA Modules": [_ev("jit_f", 0, 9000)]})
    return [host, device, _plane("/device:TPU:0 SparseCore", {})]


def test_busy_is_the_union_of_op_intervals_in_the_window():
    ev = trace.reduce(_planes())
    assert ev.window_s == pytest.approx(8000e-9)      # 1000 .. 9000
    # [1500, 3500] + [6400, 7100] + [8000, 9000] (clipped at the window)
    assert ev.busy_s == pytest.approx(3700e-9)
    assert ev.n_ops == 5
    assert ev.op_seconds(paged_attn_roofline.match) == pytest.approx(1500e-9)
    assert ev.count(lambda n: n.startswith("fusion.1 ")) == 2


def test_gaps_are_named_by_the_host_span():
    ev = trace.reduce(_planes())
    gaps = dict((round(s * 1e9), label) for label, s in ev.gaps)
    assert gaps[2900] == "host between steps"           # 3500 .. 6400
    assert gaps[500] == "host in engine.step 0"
    assert gaps[900] == "host in engine.step 1"              # 7100 .. 8000
    out = trace.breakdown(ev, top=2)
    assert out["device_ops"][0] == ["fusion.1 = f32[8] fusion()",
                                    pytest.approx(2500e-9)]
    assert len(out["idle_gaps"]) == 2


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([_planes()[0]])


RECORDED = spec.BENCH / "data" / "minitron-4b.chat.xplane.pb.gz"


def test_recorded_chip_trace():
    """A few steps of minitron-4b.chat recorded on one TPU v5e
    (``run.py --seconds 0.4 --trace 1``), gzipped."""
    import gzip

    import jax

    raw = gzip.decompress(RECORDED.read_bytes())
    ev = trace.reduce(jax.profiler.ProfileData.from_serialized_xspace(
        raw).planes)
    assert 0 < ev.busy_s < ev.window_s
    assert len(ev.steps) >= 2
    assert ev.count(paged_attn_roofline.match) > 0
    assert 0 < ev.op_seconds(paged_attn_roofline.match) < ev.busy_s
    out = trace.breakdown(ev)
    assert len(out["device_ops"]) == 10 and out["idle_gaps"]
    assert not any(name.startswith("while") for name, _ in out["device_ops"])
