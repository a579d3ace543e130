"""Production meshes and sharding rules.

``make_local_mesh`` builds the one-device mesh everything that runs uses;
``make_production_mesh`` the 16x16 single-pod (256 chips) or 2x16x16
multi-pod (512 chips) mesh that ``dryrun.py`` compiles for.  Both are
functions, so importing this module never touches jax device state.

``ShardingRules`` maps the *logical* parameter axes emitted by the model
init (repro.models.layers.Param) to physical mesh axes, divisibility-aware
per architecture:

  * attention is sharded by (q+kv) heads when both divide the model axis,
    else by head_dim (always 128/64 -> divisible) — the head_dim variant is
    what keeps qwen2-72b's 8 KV heads sharded 16 ways at decode;
  * MoE experts shard over model when E % M == 0 (qwen3: 128/16), else the
    per-expert hidden dim (granite-moe: 40 experts, f=512/16=32);
  * train mode adds FSDP: the d_model ("embed") axis of every weight is
    sharded over "data", giving ZeRO-sharded optimizer state;
  * activations carry P(batch, None, "model") through the layer scan so
    the residual stash stays bounded (5 GB, not 80 GB, for qwen2-72b).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "batch_axes_for",
    "ShardingRules",
    "param_shardings",
    "batch_shardings",
    "cache_shardings",
    "activation_spec",
]


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Compile-only: ``launch/dryrun.py`` lowers onto it over fake host
    devices.  Nothing that runs builds it."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh():
    """(1, 1) ("data", "model") mesh on the first device present: one
    chip on a TPU host, the CPU under ``JAX_PLATFORMS=cpu``.

    The serving engine and fleet keep every worker and replica on one
    device (ROADMAP R4), so this is the mesh the launchers, tests and
    benchmarks run on.  ``Auto`` axes: with JAX's default ``Explicit``
    axes, ``shard_map`` outputs carry a sharding that the MoE layer's
    next gather rejects."""
    return _make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def batch_axes_for(mesh, global_batch: Optional[int] = None) -> tuple:
    """Mesh axes used for batch sharding: ("pod","data") when the pod axis
    exists; trimmed so the product divides the global batch."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    if global_batch is None:
        return tuple(axes)
    # drop axes (outermost first) until divisible
    while axes:
        prod = int(np.prod([mesh.shape[a] for a in axes]))
        if global_batch % prod == 0:
            return tuple(axes)
        axes.pop(0)
    return ()


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Resolved logical-axis -> mesh-axes mapping for (config, mesh)."""

    table: dict

    @classmethod
    def build(cls, cfg: ModelConfig, mesh, *, mode: str = "serve",
              attn_pref: str = "auto") -> "ShardingRules":
        """attn_pref:
        * "auto": heads-first for train/prefill (score tiles stay sharded,
          no per-tile psum; replicated KV weights are small), hd-first for
          serve (the KV *cache* must shard — replicating qwen2-72b's cache
          is 43 GB/chip);
        * "heads_first" / "hd_first": force a variant (perf experiments).
        """
        M = int(mesh.shape.get("model", 1))
        D = int(mesh.shape.get("data", 1))

        def div(n, m=M):
            return m > 1 and n % m == 0

        if attn_pref == "auto":
            attn_pref = "hd_first" if mode == "serve" else "heads_first"

        # attention sharding variant
        if div(cfg.n_heads) and div(cfg.n_kv_heads):
            heads, kv_heads, hd = "model", "model", None
        elif attn_pref == "heads_first" and div(cfg.n_heads):
            heads, kv_heads, hd = "model", None, None
        elif div(cfg.hd):
            heads, kv_heads, hd = None, None, "model"
        elif div(cfg.n_heads):
            heads, kv_heads, hd = "model", None, None
        else:
            heads = kv_heads = hd = None

        # MoE sharding variant (EP vs TP-within-expert) — must agree with
        # repro.models.moe's ep_mode switch (over the experts held)
        if div(cfg.n_held):
            experts, expert_mlp = "model", None
        elif cfg.is_moe and div(cfg.moe_d_ff):
            experts, expert_mlp = None, "model"
        else:
            experts = expert_mlp = None

        di = cfg.d_inner
        table = {
            "layers": None,
            "vocab": "model" if div(cfg.vocab_size) else None,
            "embed": "data" if (mode == "train" and div(cfg.d_model, D))
                     else None,
            "heads": heads,
            "kv_heads": kv_heads,
            "hd": hd,
            "hd2": None,
            "mlp": "model" if div(cfg.d_ff or 0) else None,
            "experts": experts,
            "expert_mlp": expert_mlp,
            "ssm_in": None,
            "ssm_inner": "model" if div(di) else None,
            "ssm_inner2": "model" if div(di) else None,
            "ssm_heads": None,
            "ssm_heads2": None,
            "gates": None,
            "conv_k": None,
            "enc_seq": None,
        }
        return cls(table=table)

    def spec_for(self, axes: tuple) -> P:
        phys = []
        used = set()
        for a in axes:
            m = self.table.get(a)
            if m is not None and m in used:
                m = None  # a mesh axis can appear only once per spec
            if m is not None:
                used.add(m)
            phys.append(m)
        # trim trailing Nones
        while phys and phys[-1] is None:
            phys.pop()
        return P(*phys)


def param_shardings(axes_tree, cfg: ModelConfig, mesh, *,
                    mode: str = "serve", attn_pref: str = "auto"):
    """NamedSharding tree matching the params tree (from split_params)."""
    rules = ShardingRules.build(cfg, mesh, mode=mode, attn_pref=attn_pref)

    def one(axes):
        return NamedSharding(mesh, rules.spec_for(tuple(axes)))

    return jax.tree.map(one, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def activation_spec(cfg: ModelConfig, mesh, global_batch: int):
    """Sharding for the residual stream (B, S, d) through the scan."""
    baxes = batch_axes_for(mesh, global_batch)
    M = int(mesh.shape.get("model", 1))
    d_ok = M > 1 and cfg.d_model % M == 0
    spec = P(baxes if baxes else None, None, "model" if d_ok else None)
    return NamedSharding(mesh, spec)


def batch_shardings(batch_specs: dict, mesh, global_batch: int):
    """Shardings for a train/prefill batch dict: batch dim sharded."""
    baxes = batch_axes_for(mesh, global_batch)
    b = baxes if baxes else None

    def one(leaf):
        spec = [b] + [None] * (len(leaf.shape) - 1)
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, batch_specs)


def cache_shardings(cache_specs, cfg: ModelConfig, mesh, global_batch: int,
                    kv_shard: str = "heads"):
    """Shardings for the decode cache pytree (leaves stacked on a leading
    layer axis; batch is dim 1).

    kv_shard="heads": KV head/hd dims per the rules (baseline);
    kv_shard="length": the KV length dim is sharded over the model axis
    (distributed flash-decode; see attention.decode_attention_lsharded)."""
    rules = ShardingRules.build(cfg, mesh, mode="serve")
    baxes = batch_axes_for(mesh, global_batch)
    b = baxes if baxes else None
    kv = rules.table["kv_heads"]
    hd = rules.table["hd"]
    M = int(mesh.shape.get("model", 1))

    def one(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        nd = len(leaf.shape)
        if nd == 1:            # lengths (B,)
            return NamedSharding(mesh, P(b))
        if "k" in names or "v" in names:       # (L, B, Lkv, Hkv, hd)
            if (kv_shard == "length" and nd >= 3
                    and leaf.shape[2] % max(M, 1) == 0 and M > 1):
                spec = [None, b, "model", None, None][:nd]
            else:
                spec = [None, b, None, kv, hd][:nd]
        elif "state" in names:                  # (L, B, H, dk, dv)
            spec = [None, b, None, None, None][:nd]
        elif "conv" in names:                   # (L, B, K-1, di)
            ssm_in = rules.table["ssm_inner"]
            spec = [None, b, None, ssm_in][:nd]
        elif "hcnm" in names:                   # (L, B, H, hd)
            spec = [None, b, None, None][:nd]
        else:
            spec = [None, b] + [None] * (nd - 2)
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache_specs)
