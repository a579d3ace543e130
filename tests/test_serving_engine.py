"""End-to-end serving engine tests: continuous batching, router
integration, and the placement-invariance property (a request's greedy
decode output must not depend on which worker it lands on — this is what
makes the router a pure efficiency knob, and it catches cache-copy bugs)."""
import warnings

warnings.filterwarnings("ignore")

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import make_policy
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.serving import EngineConfig, ServeRequest, ServingEngine

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")


@pytest.fixture(scope="module")
def setup():
    params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
    mesh = make_local_mesh()
    return params, mesh


def _requests(n=10, seed=3):
    rng = np.random.default_rng(seed)
    return [
        ServeRequest(rid=i,
                     tokens=rng.integers(1, 128, size=int(rng.integers(4, 30))),
                     max_new_tokens=int(rng.integers(3, 10)))
        for i in range(n)
    ]


def _run(params, mesh, policy_name, reqs):
    eng = ServingEngine(
        CFG, params,
        EngineConfig(n_workers=2, slots_per_worker=3, max_seq_len=64),
        make_policy(policy_name), mesh=mesh)
    for r in reqs:
        eng.submit(r)
    stats = eng.run(max_steps=500)
    return eng, stats


class TestEngine:
    def test_all_complete(self, setup):
        params, mesh = setup
        reqs = _requests()
        _, stats = _run(params, mesh, "fcfs", reqs)
        assert all(r.done for r in reqs)
        assert all(len(r.generated) == r.max_new_tokens for r in reqs)
        assert stats["tokens"] == sum(r.max_new_tokens - 1 for r in reqs)

    def test_latency_bookkeeping(self, setup):
        params, mesh = setup
        reqs = _requests()
        _run(params, mesh, "jsq", reqs)
        for r in reqs:
            assert r.t_first_token >= r.t_submit
            assert r.t_finish >= r.t_first_token

    def test_placement_invariance(self, setup):
        """Same requests, different routers -> identical generations."""
        params, mesh = setup
        reqs_a = _requests(seed=5)
        reqs_b = _requests(seed=5)
        _run(params, mesh, "fcfs", reqs_a)
        _run(params, mesh, "bfio_h0", reqs_b)
        for ra, rb in zip(reqs_a, reqs_b):
            assert ra.generated == rb.generated, \
                f"request {ra.rid}: output depends on placement"

    def test_bfio_reduces_imbalance(self, setup):
        params, mesh = setup
        # heterogeneous prompts: long + short mix, overloaded
        rng = np.random.default_rng(9)
        def mk():
            out = []
            for i in range(24):
                n = 50 if i % 3 == 0 else 5
                out.append(ServeRequest(
                    rid=i, tokens=rng.integers(1, 128, size=n),
                    max_new_tokens=8))
            return out
        _, s_fcfs = _run(params, mesh, "fcfs", mk())
        rng = np.random.default_rng(9)
        _, s_bfio = _run(params, mesh, "bfio_h0", mk())
        assert s_bfio["avg_imbalance"] <= s_fcfs["avg_imbalance"] * 1.05

    def test_capacity_respected(self, setup):
        params, mesh = setup
        eng = ServingEngine(
            CFG, params,
            EngineConfig(n_workers=2, slots_per_worker=2, max_seq_len=64),
            make_policy("fcfs"), mesh=mesh)
        for r in _requests(n=12, seed=1):
            eng.submit(r)
        while eng.wait or any(s is not None for s in eng.slot_req):
            eng.step()
            counts = eng._counts()
            assert counts.max() <= 2
        assert eng.steps < 300
