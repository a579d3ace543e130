"""``correct`` fails where it should: the float8 control, and the timed
path broken underneath the harness (a served token altered where it is
produced, key/value writes lost)."""
import sys
import time

import numpy as np
import pytest

from bench import harness, spec, system, tiny

SEED = 2**31 + 9


@pytest.fixture(scope="module")
def served():
    """A tiny cell served through the harness's own set-up and loop."""
    cell = tiny.cell()
    harness.prepare(require_tpu=False)
    sys_ = system.build(cell.config, SEED)
    harness.warm_up(sys_.engine, cell.config["vocab_size"],
                    cell.config["engine"]["prefill_chunk"],
                    cell.mix["prompt"]["min"])
    log = harness.serve(sys_.engine, cell.mix, SEED, 2.0,
                        cell.config["vocab_size"],
                        counter=harness.CompileCounter())
    sys_.close()
    return cell, harness.choose_sample(log, SEED)


def test_control_fails_and_the_program_passes(served):
    cell, sample = served
    ref = spec.load_reference(cell.config["reference"])
    program = max(g.max() for g in ref.served_gaps(cell.config, SEED, sample))
    control = max(g.max() for g in ref.control_gaps(cell.config, SEED,
                                                    sample))
    assert sum(s.size for _, s in sample) >= 100
    assert program < tiny.LIMIT < control, (program, control)


def test_control_run_is_not_correct():
    """The whole run with the control in the program's place, judged by
    ``check`` against the cell's limits."""
    result = harness.run(tiny.cell(), SEED, 2.0, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         control=True, out=sys.stderr)
    assert result["correct"] is False
    assert result["compared"]["logit_gap_max"]["value"] > tiny.LIMIT


def _alter_decode(engine):
    orig = engine.backend.decode

    def decode(slot_tokens, active_idx, bucket):
        return (orig(slot_tokens, active_idx, bucket) + 1) % 512
    engine.backend.decode = decode
    return engine


def _alter_first_token(engine):
    orig = engine.backend.prefill_chunk

    def prefill_chunk(toks, offs, clens, slots):
        logits = np.array(orig(toks, offs, clens, slots))
        rows = np.arange(logits.shape[0])
        wrong = (logits.argmax(-1) + 1) % logits.shape[-1]
        logits[rows, wrong] = logits.max(-1) + 1.0
        return logits
    engine.backend.prefill_chunk = prefill_chunk
    return engine


def _lose_kv_writes(engine):
    """Each decode step's key/value writes are dropped: the pools come
    back as they were before the step."""
    import jax.numpy as jnp

    backend = engine.backend
    orig = backend.decode

    def decode(slot_tokens, active_idx, bucket):
        k, v = jnp.copy(backend.kv.k_pool), jnp.copy(backend.kv.v_pool)
        out = orig(slot_tokens, active_idx, bucket)
        backend.kv.k_pool, backend.kv.v_pool = k, v
        return out
    backend.decode = decode
    return engine


@pytest.mark.parametrize("fault", [_alter_decode, _alter_first_token,
                                   _lose_kv_writes],
                         ids=["decoded-token-altered", "first-token-altered",
                              "kv-writes-lost"])
def test_broken_timed_path_is_not_correct(fault):
    result = harness.run(tiny.cell(), SEED, 2.0, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         fault=fault, out=sys.stderr)
    assert result["correct"] is False
    assert result["compared"]["logit_gap_max"]["value"] > tiny.LIMIT
