"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Router (top-k over E experts) runs replicated in the pjit program; the
expert compute runs inside ``shard_map`` so the dispatch locality is
explicit:

* **EP mode** (E divisible by the model-axis size): each model shard owns
  E_loc = E/M experts; every shard gathers the tokens routed to *its*
  experts from its data shard into a fixed-capacity buffer
  (E_loc, C, d), runs the expert SwiGLU as a batched matmul (MXU-friendly),
  scatters weighted outputs back, and a single ``psum`` over the model axis
  combines contributions (disjoint across shards).  This all-reduce is
  exactly the paper's synchronized EP phase — the barrier the scheduler's
  imbalance reduction protects.

* **TP mode** (E not divisible, e.g. granite-moe's 40 experts on 16-way
  model): every shard holds all experts with the hidden dim f sharded; the
  same dispatch code runs with E_loc = E, and the psum combines the
  f-partial products.

Token overflow beyond capacity C is dropped (standard Switch behaviour);
capacity has a floor so decode batches don't drop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import linear

__all__ = ["router_topk", "aux_load_balance_loss", "moe_ffn"]


def router_topk(x, w_router, k: int):
    """x: (B, S, d); w_router: (d, E).  Returns (probs, top_w, top_idx)."""
    logits = linear(x, w_router).astype(jnp.float32)   # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, k)           # (B, S, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return probs, top_w.astype(x.dtype), top_idx


def aux_load_balance_loss(probs, top_idx, n_experts: int):
    """Switch-style load-balance loss: E * sum_e f_e * p_e."""
    E = n_experts
    # fraction of token-slots dispatched to e
    counts = jnp.zeros((E,), jnp.float32).at[top_idx.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    p = probs.reshape(-1, E).mean(axis=0)
    return E * jnp.sum(f * p)


def _local_moe(x, top_idx, top_w, w1, w3, w2, *, E: int, k: int,
               capacity: int, ep_mode: bool, model_axis: str):
    """Per-device block (inside shard_map).

    x: (B_loc, S, d); top_idx/top_w: (B_loc, S, k);
    EP: w1 (E_loc, d, f) local experts; TP: w1 (E, d, f_loc)."""
    Bl, S, d = x.shape
    T = Bl * S
    E_loc = w1.shape[0]
    e0 = (jax.lax.axis_index(model_axis) * E_loc) if ep_mode else 0

    x2 = x.reshape(T, d)
    flat_e = top_idx.reshape(-1)                        # (T*k,)
    flat_w = top_w.reshape(-1)
    local = (flat_e >= e0) & (flat_e < e0 + E_loc)
    le = jnp.where(local, flat_e - e0, E_loc)           # E_loc = trash bucket
    oh = jax.nn.one_hot(le, E_loc + 1, dtype=jnp.int32)  # (T*k, E_loc+1)
    pos = jnp.cumsum(oh, axis=0) - 1
    pos_e = jnp.take_along_axis(pos, le[:, None], axis=1)[:, 0]
    ok = local & (pos_e < capacity)
    slot = jnp.where(ok, le * capacity + pos_e, E_loc * capacity)
    n_slots = E_loc * capacity

    tok_id = jnp.arange(T * k, dtype=jnp.int32) // k
    tok_for_slot = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].set(
        jnp.where(ok, tok_id, 0))
    gate_for_slot = jnp.zeros((n_slots + 1,), x.dtype).at[slot].set(
        jnp.where(ok, flat_w, 0.0))
    filled = jnp.zeros((n_slots + 1,), jnp.bool_).at[slot].set(ok)

    # gather tokens -> (E_loc, C, d)
    buf = x2[tok_for_slot[:n_slots]].reshape(E_loc, capacity, d)
    # expert SwiGLU, batched over local experts
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w3)) \
        * jnp.einsum("ecd,edf->ecf", buf, w1)
    y = jnp.einsum("ecf,efd->ecd", h, w2)               # (E_loc, C, d)
    y = y.reshape(n_slots, d)
    w_slot = (gate_for_slot[:n_slots]
              * filled[:n_slots].astype(x.dtype))[:, None]
    out = jnp.zeros((T, d), y.dtype).at[tok_for_slot[:n_slots]].add(
        y * w_slot)
    out = jax.lax.psum(out, model_axis)
    return out.reshape(Bl, S, d).astype(x.dtype)


def moe_ffn(
    x, params, *,
    n_experts: int,
    k: int,
    mesh,
    batch_axes,
    capacity_factor: float = 1.25,
    min_capacity: int = 4,
    model_axis: str = "model",
):
    """Top-k MoE FFN.  x: (B, S, d).  params: router (d,E), w1/w3 (E,d,f),
    w2 (E,f,d).  Returns (out, aux_loss)."""
    B, S, d = x.shape
    probs, top_w, top_idx = router_topk(x, params["router"], k)
    aux = aux_load_balance_loss(probs, top_idx, n_experts)

    msize = mesh.shape[model_axis]
    ep_mode = (n_experts % msize == 0) and msize > 1
    E_loc = n_experts // msize if ep_mode else n_experts
    dsize = 1
    for a in batch_axes:
        dsize *= mesh.shape[a]
    T_loc = max(B // max(dsize, 1), 1) * S
    capacity = max(int(capacity_factor * T_loc * k / n_experts) + 1,
                   min_capacity)

    if ep_mode:
        w13_spec = P(model_axis, None, None)     # experts sharded
        w2_spec = P(model_axis, None, None)
    else:
        w13_spec = P(None, None, model_axis)     # hidden dim sharded (TP)
        w2_spec = P(None, model_axis, None)
    bspec = P(batch_axes, None, None)
    ispec = P(batch_axes, None, None)

    fn = functools.partial(_local_moe, E=n_experts, k=k, capacity=capacity,
                           ep_mode=ep_mode, model_axis=model_axis)
    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(bspec, ispec, ispec, w13_spec, w13_spec, w2_spec),
        out_specs=bspec,
        check_vma=False,
    )(x, top_idx, top_w, params["w1"], params["w3"], params["w2"])
    return out, aux
