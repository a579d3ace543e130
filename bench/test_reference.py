"""The plain reference draws the served weights from the seed by itself,
and agrees with the engine's paged, chunked path on the CPU."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, system, tiny

SEED = 2**31 + 5
CONFIGS = sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))


@pytest.mark.parametrize("base", CONFIGS)
def test_reference_weights_are_the_served_weights(base):
    from repro.launch.serve import init_serving_params

    cfg = tiny.config(base)
    params = init_serving_params(system.model_config(cfg), SEED)
    want = spec.load_reference(cfg["reference"]).weights(cfg, SEED)
    assert jax.tree.structure(want) == jax.tree.structure(params)
    f32 = lambda a: np.asarray(jnp.asarray(a, jnp.float32))  # noqa: E731
    for (path, w), p in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(params)):
        np.testing.assert_array_equal(f32(w), f32(p),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("base", CONFIGS)
def test_reference_agrees_with_the_paged_chunked_engine(base):
    cell = tiny.cell(base, per_layer=("prefill_wall_share",
                                      "queue_wait_p90_s"))
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
