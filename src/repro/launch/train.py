"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Trains on one present device (:func:`~repro.launch.mesh.make_local_mesh`);
``--smoke`` alone picks the reduced config, whatever the backend.  The
production meshes are compile-only (``launch/dryrun.py``)."""
from __future__ import annotations

import argparse

import jax
import numpy as np

from ..configs import get_config, get_smoke_config
from ..data import token_batches
from ..models import init_params, split_params
from ..training import AdamWConfig, train
from .compile_cache import enable_compile_cache
from .mesh import make_local_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced variant (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh()

    print(f"[train] {cfg.name}: {cfg.n_params()/1e6:.1f}M params, "
          f"mesh {dict(mesh.shape)}")
    params, _ = split_params(init_params(cfg, jax.random.PRNGKey(0)))

    def batches():
        for b in token_batches(vocab_size=cfg.vocab_size, batch=args.batch,
                               seq_len=args.seq, n_batches=args.steps):
            if cfg.family == "vlm":
                b["patches"] = np.zeros(
                    (args.batch, cfg.patch_tokens, cfg.d_model), np.float32)
            if cfg.family == "audio":
                b["frames"] = np.zeros(
                    (args.batch, cfg.encoder_seq, cfg.d_model), np.float32)
            yield b

    opt = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                      total_steps=args.steps)
    _, losses = train(cfg, params=params, batches=batches(), opt_cfg=opt,
                      mesh=mesh, ckpt_dir=args.ckpt_dir,
                      ckpt_every=max(args.steps // 2, 1)
                      if args.ckpt_dir else 0)
    print(f"[train] done: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"over {len(losses)} steps")


if __name__ == "__main__":
    main()
