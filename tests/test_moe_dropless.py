"""The serving expert layer's counters: against a count by hand, and as
the engine puts them on its ``serve.decode`` / ``serve.chunk`` spans."""
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import make_policy
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.models.moe import MOE_COUNTERS, moe_ffn_dropless
from repro.serving import EngineConfig, ServeRequest, ServingEngine
from repro.serving import engine as engine_mod

REPO = Path(__file__).resolve().parents[1]

MOE = ModelConfig(name="tiny-moe", family="moe", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=0, n_experts=8,
                  experts_per_token=2, moe_d_ff=16, vocab_size=128,
                  dtype="float32", qk_norm=True)
DENSE = ModelConfig(name="tiny-dense", family="dense", n_layers=2,
                    d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                    vocab_size=128, dtype="float32")


def _ffn(cfg, seed=0):
    params = jax.jit(lambda k: split_params(init_params(cfg, k))[0])(
        jax.random.PRNGKey(seed))
    return params, jax.tree.map(lambda a: a[0], params["blocks"]["ffn"])


@pytest.mark.parametrize("offset,held", [(0, 8), (2, 4), (6, 2)])
def test_counters_match_a_count_by_hand(offset, held):
    cfg = dataclasses.replace(MOE, experts_held=held, expert_offset=offset)
    _, ffn = _ffn(cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 5, cfg.d_model))
    valid = np.ones((3, 5), bool)
    valid[0, 3:] = False                # padding columns
    valid[1] = False                    # a padding row
    _, counters = jax.jit(lambda h, v, p: moe_ffn_dropless(
        h, v, p, k=2, mesh=make_local_mesh(), batch_axes=("data",),
        expert_offset=offset))(h, jnp.asarray(valid), ffn)

    logits = np.asarray(h, np.float64) @ np.asarray(ffn["router"],
                                                    np.float64)
    top = np.argsort(-logits, axis=-1)[..., :2][valid]      # (live, 2)
    load = np.zeros(held, int)
    for e in top.ravel():
        if offset <= e < offset + held:
            load[e - offset] += 1
    want = dict(moe_pairs=load.sum(), moe_hit=(load > 0).sum(),
                moe_max=load.max())
    assert dict(zip(MOE_COUNTERS, np.asarray(counters).tolist())) == want


def test_a_layer_of_a_stack_is_that_layer_alone():
    """The paged programs hand the layer the experts stacked over layers
    with the layer's index: the same output, bit for bit, and the same
    counters as the layer's own slice."""
    cfg = dataclasses.replace(MOE, n_layers=3, experts_held=4,
                              expert_offset=2)
    params = jax.jit(lambda k: split_params(init_params(cfg, k))[0])(
        jax.random.PRNGKey(3))
    stack = params["blocks"]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(5), (3, 5, cfg.d_model))
    valid = jnp.asarray(np.arange(5)[None, :] < np.array([[5], [2], [0]]))
    run = jax.jit(lambda h, v, p: moe_ffn_dropless(
        h, v, p, k=2, mesh=make_local_mesh(), batch_axes=("data",),
        expert_offset=2))
    for layer in range(cfg.n_layers):
        alone = run(h, valid, jax.tree.map(lambda a: a[layer], stack))
        router = stack["router"][layer]
        in_stack = run(h, valid, dict(stack, router=router,
                                      layer=jnp.int32(layer)))
        for a, b in zip(alone, in_stack):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_ON_TWO = """
import dataclasses, json, sys
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import _make_mesh
from repro.models import init_params, split_params
from repro.models.moe import moe_ffn_dropless
from test_moe_dropless import MOE
cfg = dataclasses.replace(MOE, experts_held=int(sys.argv[1]))
p = jax.jit(lambda k: split_params(init_params(cfg, k))[0])(
    jax.random.PRNGKey(0))
ffn = jax.tree.map(lambda a: a[0], p["blocks"]["ffn"])
h = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model))
valid = jnp.asarray(np.arange(6)[None, :] < np.array([[6], [4]]))
out = {}
for m in (1, 2):
    mesh = _make_mesh((1, m), ("data", "model"), devices=jax.devices()[:m])
    y, c = jax.jit(lambda h, v, f: moe_ffn_dropless(
        h, v, f, k=2, mesh=mesh, batch_axes=("data",)))(h, valid, ffn)
    out[m] = (np.asarray(y).tolist(), np.asarray(c).tolist())
print(json.dumps(out))
"""


@pytest.mark.parametrize("held", [4, 3], ids=["ep", "tp"])
def test_counters_on_a_two_device_model_axis(held):
    """Over a model axis of 2: EP (held experts split between the
    devices) sums each device's counts; TP (a held count that does not
    divide, the hidden dim split) takes them once.  Both read as on one
    device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"),
                                           str(REPO / "tests")]))
    proc = subprocess.run([sys.executable, "-c", _ON_TWO, str(held)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    (y1, c1), (y2, c2) = out["1"], out["2"]
    assert c2 == c1
    assert c1[0] > 0
    np.testing.assert_allclose(y2, y1, rtol=1e-5, atol=1e-5)


class _Recorder:
    """Stands in for the engine's spans: records each span's name, args
    and the metadata set on it."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **args):
        rec = dict(name=name, **args)
        self.spans.append(rec)

        @contextlib.contextmanager
        def cm():
            yield type("S", (), {"set_metadata":
                                 lambda _, **kw: rec.update(kw)})()
        return cm()

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]


def _serve(cfg, monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(engine_mod, "span", rec)
    params, _ = _ffn(cfg)
    eng = ServingEngine(cfg, params,
                        EngineConfig(n_workers=2, slots_per_worker=2,
                                     max_seq_len=64, cache_backend="paged",
                                     prefill_chunk=16),
                        make_policy("fcfs"), mesh=make_local_mesh())
    rng = np.random.default_rng(4)
    for i in range(5):
        eng.submit(ServeRequest(rid=i, tokens=rng.integers(
            1, 128, size=int(rng.integers(3, 30))), max_new_tokens=4))
    eng.run(max_steps=200)
    return rec


def test_engine_puts_the_counters_on_its_program_spans(monkeypatch):
    """Every expert held: each live token routes k pairs in each layer,
    and padding rows (decode buckets) and columns (chunks) route none."""
    rec = _serve(MOE, monkeypatch)
    k, n = MOE.experts_per_token, MOE.n_layers
    decodes, chunks = rec.named("serve.decode"), rec.named("serve.chunk")
    assert decodes and chunks
    assert any(s["bucket"] > s["rows"] for s in decodes + chunks)
    for s in decodes:
        assert s["moe_pairs"] == n * k * s["rows"]
    for s in chunks:
        assert s["moe_pairs"] == n * k * s["tokens"]
    for s in decodes + chunks:
        assert 0 < s["moe_hit"] <= n * MOE.n_experts
        assert n <= s["moe_max"] <= s["moe_pairs"]


def test_a_dense_model_records_no_counters(monkeypatch):
    rec = _serve(DENSE, monkeypatch)
    spans = rec.named("serve.decode") + rec.named("serve.chunk")
    assert spans
    assert not any(key in s for s in spans for key in MOE_COUNTERS)
