"""Training substrate tests: optimizer math, checkpointing round-trip, and
end-to-end loss decrease on a tiny model."""
import warnings

warnings.filterwarnings("ignore")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.data import token_batches
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, loss_fn, split_params
from repro.training import (
    AdamWConfig,
    adamw_update,
    init_opt_state,
    load_checkpoint,
    save_checkpoint,
)
from repro.training.optimizer import cosine_schedule, global_norm
from repro.training.train_loop import make_train_step, train

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                  dtype="float32")


class TestOptimizer:
    def test_adamw_decreases_quadratic(self):
        cfg = AdamWConfig(lr=0.1, warmup_steps=0, weight_decay=0.0)
        params = {"w": jnp.array([3.0, -2.0])}
        state = init_opt_state(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state = adamw_update(cfg, params, grads, state)
        assert float(jnp.abs(params["w"]).max()) < 0.05

    def test_grad_clipping(self):
        cfg = AdamWConfig(lr=1e-3, clip_norm=1.0, warmup_steps=0)
        params = {"w": jnp.zeros(4)}
        state = init_opt_state(params)
        huge = {"w": jnp.full(4, 1e9)}
        p2, s2 = adamw_update(cfg, params, huge, state)
        # clipped grad -> m bounded by (1-b1) * clip_norm
        assert float(jnp.abs(s2.m["w"]).max()) <= 0.1 + 1e-6

    def test_schedule_warmup_and_decay(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_frac=0.1)
        lr0 = float(cosine_schedule(cfg, jnp.asarray(0)))
        lr_w = float(cosine_schedule(cfg, jnp.asarray(10)))
        lr_end = float(cosine_schedule(cfg, jnp.asarray(100)))
        assert lr0 == 0.0
        assert lr_w == pytest.approx(1.0)
        assert lr_end == pytest.approx(0.1, rel=1e-3)

    def test_weight_decay_pulls_to_zero(self):
        cfg = AdamWConfig(lr=0.1, weight_decay=1.0, warmup_steps=0)
        params = {"w": jnp.array([5.0])}
        state = init_opt_state(params)
        zero = {"w": jnp.zeros(1)}
        for _ in range(50):
            params, state = adamw_update(cfg, params, zero, state)
        assert float(params["w"][0]) < 5.0

    def test_global_norm(self):
        assert float(global_norm({"a": jnp.array([3.0]),
                                  "b": jnp.array([4.0])})) == pytest.approx(5.0)


class TestGradAccum:
    def test_accum_matches_full_batch(self):
        """grad_accum=2 must give the same update as the full batch."""
        mesh = make_local_mesh()
        params, _ = split_params(init_params(CFG, jax.random.PRNGKey(0)))
        batch = next(token_batches(vocab_size=64, batch=4, seq_len=16,
                                   n_batches=1, seed=0))
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        ocfg = AdamWConfig(warmup_steps=0)
        s1 = make_train_step(CFG, ocfg, mesh=mesh, grad_accum=1,
                             compute_dtype="float32")
        s2 = make_train_step(CFG, ocfg, mesh=mesh, grad_accum=2,
                             compute_dtype="float32")
        opt = init_opt_state(params)
        l1, p1, _ = s1(params, opt, batch)
        l2, p2, _ = s2(params, opt, batch)
        assert float(l1) == pytest.approx(float(l2), rel=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": {"c": jnp.ones(4, jnp.int32)}}
        save_checkpoint(str(tmp_path), 7, tree)
        like = jax.tree.map(jnp.zeros_like, tree)
        loaded, step = load_checkpoint(str(tmp_path), like)
        assert step == 7
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(loaded)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_latest_of_many(self, tmp_path):
        tree = {"w": jnp.zeros(2)}
        for s in (1, 5, 3):
            save_checkpoint(str(tmp_path), s, tree)
        _, step = load_checkpoint(str(tmp_path), tree)
        assert step == 5

    def test_shape_mismatch_raises(self, tmp_path):
        save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros(2)})
        with pytest.raises(ValueError):
            load_checkpoint(str(tmp_path), {"w": jnp.zeros(3)})


class TestEndToEnd:
    def test_loss_decreases(self):
        """~60 steps on a memorizable stream: loss must drop clearly."""
        mesh = make_local_mesh()
        params, _ = split_params(init_params(CFG, jax.random.PRNGKey(1)))
        batches = list(token_batches(vocab_size=64, batch=8, seq_len=32,
                                     n_batches=8, seed=1)) * 8
        params, losses = train(
            CFG, params=params, batches=batches,
            opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=64),
            mesh=mesh, log_every=0)
        assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])
