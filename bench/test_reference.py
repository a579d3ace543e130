"""The plain reference draws the served weights from the seed by itself,
and agrees with the engine's paged, chunked path on the CPU."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, tiny
from bench.references import dense_decoder as ref

SEED = 2**31 + 5
CONFIGS = sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))


@pytest.mark.parametrize("base", CONFIGS)
def test_reference_weights_are_the_served_weights(base):
    from bench import system
    from repro.launch.serve import init_serving_params

    cfg = tiny.config(base)
    params = init_serving_params(system.model_config(cfg), SEED)
    keys = ref._root_keys(SEED)
    f32 = lambda a: np.asarray(jnp.asarray(a, jnp.float32))  # noqa: E731
    np.testing.assert_array_equal(
        f32(ref._embed_table(keys[0], cfg["vocab_size"],
                             cfg["hidden_size"])), f32(params["embed"]))
    np.testing.assert_array_equal(
        f32(ref._head(keys[1], cfg["vocab_size"], cfg["hidden_size"])),
        f32(params["lm_head"]))
    blocks = params["blocks"]
    for i, lk in enumerate(ref._layer_keys(SEED, cfg["num_hidden_layers"])):
        w = ref._layer_weights(lk, ref._dims(cfg))
        for name in ("wq", "wk", "wv", "wo"):
            np.testing.assert_array_equal(w[name],
                                          f32(blocks["attn"][name][i]))
        for name in w.keys() - {"wq", "wk", "wv", "wo"}:
            np.testing.assert_array_equal(w[name],
                                          f32(blocks["ffn"][name][i]))
        for norm in ("norm1", "norm2"):
            assert np.all(f32(blocks[norm][i]) == 1.0)
    assert np.all(f32(params["final_norm"]) == 1.0)


def test_reference_agrees_with_the_paged_chunked_engine():
    cell = tiny.cell(per_layer=("prefill_wall_share", "queue_wait_p90_s"))
    result = harness.run(cell, SEED, 2.0, False, t_start=time.perf_counter(),
                         require_tpu=False, out=sys.stderr)
    assert result["correct"], result
    assert result["compared"]["logit_gap_max"]["value"] < tiny.LIMIT
    assert result["attempted"] > 10 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    m = result["metrics"]
    assert set(m) == {"setup_s", "ttft_p50_s", "itl_p99_ms"}
    assert all(v["value"] > 0 for v in m.values())
    assert result["device"]["platform"] == jax.devices()[0].platform
