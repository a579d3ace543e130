"""Train a ~small LM for a few hundred steps on synthetic data — exercises
the full training substrate (model, AdamW, schedule, checkpointing).

    PYTHONPATH=src python examples/train_small.py
"""
import tempfile

import jax

from repro.configs.base import ModelConfig
from repro.data import token_batches
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, split_params
from repro.training import AdamWConfig, load_checkpoint, train

cfg = ModelConfig(
    name="demo-120m", family="dense",
    n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, d_ff=1024,
    vocab_size=2048, dtype="float32",
)
print(f"model: {cfg.n_params()/1e6:.1f}M params")

params, _ = split_params(init_params(cfg, jax.random.PRNGKey(0)))
mesh = make_local_mesh()

STEPS = 200
batches = token_batches(vocab_size=cfg.vocab_size, batch=8, seq_len=64,
                        n_batches=STEPS, seed=0)
with tempfile.TemporaryDirectory() as ckpt_dir:
    params, losses = train(
        cfg, params=params, batches=batches,
        opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=STEPS),
        mesh=mesh, log_every=25, ckpt_dir=ckpt_dir, ckpt_every=100)
    restored, step = load_checkpoint(ckpt_dir, {"params": params,
                                                "opt_m": params,
                                                "opt_v": params})
    print(f"checkpoint restored from step {step}")
print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} "
      f"({'OK' if losses[-1] < losses[0] else 'NO PROGRESS'})")
