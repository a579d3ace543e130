"""Mixture-of-Experts FFN: top-k routing and expert parallelism, in two
forms.

* **Training** (``moe_ffn``, called by ``loss_fn``): a fixed capacity
  of ``capacity_factor * T * k / E`` slots per expert (at least
  ``min_capacity``); pairs that overflow are *dropped* (standard Switch
  behaviour), so rows of one batch change each other's outputs.
* **Serving** (``moe_ffn_dropless``, every prefill, chunk and decode
  program): no capacity and no drop.  Pairs are sorted by expert and a
  grouped matmul (``jax.experimental.pallas.ops.tpu.megablox.gmm``) runs
  over the held experts, so a row's output never depends on the other
  rows of its batch.  Rows and columns marked not ``valid`` (a bucket's
  padding rows, a chunk's padding columns) join no group.  The paged
  programs hand it the expert weights stacked over layers with the
  layer's index, and the kernel reads that layer's hit experts in
  place: a per-layer slice would be copied whole out of the stack first,
  since a kernel takes whole buffers.

**Held experts.**  A chip holds ``ModelConfig.n_held`` of the
``n_experts`` experts, from index ``expert_offset`` (all of them by
default): the router keeps all ``n_experts`` outputs and its top-k,
and the layer computes only its own experts' part of the result.  What
the other experts would add is left out; on a deployment the chips
holding them add it (expert parallelism across chips).

Both forms run their expert compute inside ``shard_map`` over the mesh,
so dispatch locality is explicit:

* **EP mode** (held experts divisible by the model-axis size): each
  model shard owns ``E_loc = held / M`` of the held experts, computes
  their part, and one ``psum`` over the model axis combines the
  disjoint contributions.  This all-reduce is exactly the paper's
  synchronized EP phase — the barrier the scheduler's imbalance
  reduction protects.
* **TP mode** (otherwise, e.g. granite-moe's 40 experts on 16-way
  model): every shard holds all held experts with the hidden dim f
  sharded; the same code runs and the ``psum`` combines the f-partial
  products (every shard routes alike, so the counters are not summed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.sharding import PartitionSpec as P

from ..kernels.platform import resolve_interpret
from .layers import linear

__all__ = ["router_topk", "aux_load_balance_loss", "moe_ffn",
           "moe_ffn_dropless", "MOE_COUNTERS"]

# what ``moe_ffn_dropless`` counts per call, in this order: live
# token-expert pairs routed to held experts, held experts hit, and the
# most pairs on one held expert
MOE_COUNTERS = ("moe_pairs", "moe_hit", "moe_max")
_TM = 128          # rows of one grouped-matmul tile


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


def _local_moe(x, top_idx, top_w, w1, w3, w2, *, k: int, e0: int,
               capacity: int, ep_mode: bool, model_axis: str):
    """Per-device block (inside shard_map).

    x: (B_loc, S, d); top_idx/top_w: (B_loc, S, k);
    EP: w1 (E_loc, d, f) local experts; TP: w1 (E_held, d, f_loc)."""
    Bl, S, d = x.shape
    T = Bl * S
    E_loc = w1.shape[0]
    if ep_mode:
        e0 = e0 + jax.lax.axis_index(model_axis) * E_loc

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


def _expert_specs(mesh, n_held: int, model_axis: str):
    """(ep_mode, weight specs) of the held experts on ``mesh``."""
    msize = mesh.shape[model_axis]
    ep_mode = (n_held % msize == 0) and msize > 1
    if ep_mode:
        return ep_mode, (P(model_axis, None, None),) * 3
    # hidden dim sharded (TP)
    return ep_mode, (P(None, None, model_axis), P(None, None, model_axis),
                     P(None, model_axis, None))


def moe_ffn(
    x, params, *,
    n_experts: int,
    k: int,
    mesh,
    batch_axes,
    capacity_factor: float = 1.25,
    min_capacity: int = 4,
    model_axis: str = "model",
    expert_offset: int = 0,
):
    """Top-k MoE FFN with a capacity per expert (training).  x: (B, S, d).
    params: router (d, E), w1/w3 (E_held, d, f), w2 (E_held, f, d).
    Returns (out, aux_loss)."""
    B, S, d = x.shape
    probs, top_w, top_idx = router_topk(x, params["router"], k)
    aux = aux_load_balance_loss(probs, top_idx, n_experts)

    ep_mode, (w1_spec, w3_spec, w2_spec) = _expert_specs(
        mesh, params["w1"].shape[0], model_axis)
    dsize = 1
    for a in batch_axes:
        dsize *= mesh.shape[a]
    T_loc = max(B // max(dsize, 1), 1) * S
    capacity = max(int(capacity_factor * T_loc * k / n_experts) + 1,
                   min_capacity)
    bspec = P(batch_axes, None, None)

    fn = functools.partial(_local_moe, k=k, e0=expert_offset,
                           capacity=capacity, ep_mode=ep_mode,
                           model_axis=model_axis)
    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(bspec, bspec, bspec, w1_spec, w3_spec, w2_spec),
        out_specs=bspec,
        check_vma=False,
    )(x, top_idx, top_w, params["w1"], params["w3"], params["w2"])
    return out, aux


def _grouped(lhs, rhs, sizes):
    """Rows of ``lhs`` in group g times ``rhs[g]``, in float32; rows of
    the trailing group (``sizes[-1]``, which ``rhs`` has no matrix for)
    come out as zeros and cost no tile."""
    m, kk = lhs.shape
    n = rhs.shape[2]
    return gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
               tiling=(_TM, min(kk, 2048), min(n, 2048)),
               interpret=resolve_interpret(None))


def _local_dropless(x, valid, top_idx, top_w, layer, w1, w3, w2, *,
                    e0: int, ep_mode: bool, model_axis: str, batch_axes):
    """Per-device block of the dropless layer (inside shard_map).

    x: (B_loc, S, d); valid: (B_loc, S); top_idx/top_w: (B_loc, S, k);
    w1/w3 (L, E_loc, d, f), w2 (L, E_loc, f, d): the held experts of L
    layers, of which layer ``layer`` runs.  Returns (out (B_loc, S, d),
    counters (3,) int32)."""
    Bl, S, d = x.shape
    k = top_idx.shape[-1]
    T = Bl * S
    L, E_loc = w1.shape[:2]
    if ep_mode:
        e0 = e0 + jax.lax.axis_index(model_axis) * E_loc

    flat_e = top_idx.reshape(-1)                         # (T*k,)
    tok = jnp.arange(T * k, dtype=jnp.int32) // k
    held = (valid.reshape(-1)[tok] & (flat_e >= e0)
            & (flat_e < e0 + E_loc))
    group = jnp.where(held, flat_e - e0, E_loc)          # E_loc: no group
    order = jnp.argsort(group, stable=True)
    # rows padded to whole tiles; the padding joins the trailing group
    M = -(-T * k // _TM) * _TM
    per_expert = jnp.bincount(group, length=E_loc + 1).astype(jnp.int32)
    # groups over the whole stack: the layer's experts from layer * E_loc,
    # every other layer's empty, and a trailing group of the rest
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((L * E_loc + 1,), jnp.int32), per_expert[:E_loc],
        (layer * E_loc,))
    sizes = sizes.at[L * E_loc].set(per_expert[E_loc] + M - T * k)
    rows = jnp.pad(tok[order], (0, M - T * k))
    xs = x.reshape(T, d)[rows]                           # (M, d) by expert

    def grouped(lhs, w):
        return _grouped(lhs, w.reshape((L * E_loc,) + w.shape[2:]), sizes)

    a = grouped(xs, w1)
    b = grouped(xs, w3)
    y = grouped((jax.nn.silu(b) * a).astype(x.dtype), w2)

    inv = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    w = jnp.where(held, top_w.reshape(-1), 0.0).reshape(T, k)
    y_pairs = jnp.where(held[:, None], y[inv], 0.0).reshape(T, k, d)
    out = jnp.einsum("tkd,tk->td", y_pairs, w)
    out = jax.lax.psum(out, model_axis)

    per_expert = per_expert[:E_loc]
    if batch_axes:
        per_expert = jax.lax.psum(per_expert, batch_axes)
    pairs = per_expert.sum()
    hit = (per_expert > 0).sum(dtype=jnp.int32)
    most = per_expert.max()
    if ep_mode:     # each model shard counted its own experts
        pairs = jax.lax.psum(pairs, model_axis)
        hit = jax.lax.psum(hit, model_axis)
        most = jax.lax.pmax(most, model_axis)
    counters = jnp.stack([pairs, hit, most]).astype(jnp.int32)
    return out.reshape(Bl, S, d).astype(x.dtype), counters


def moe_ffn_dropless(x, valid, params, *, k: int, mesh, batch_axes,
                     model_axis: str = "model", expert_offset: int = 0):
    """Top-k MoE FFN for serving: no capacity, no drop.  x: (B, S, d);
    valid: (B, S) bool, the tokens that route (padding does not).
    params: router (d, E) over every expert, w1/w3 (E_held, d, f), w2
    (E_held, f, d) of the held experts from ``expert_offset``; or, with
    ``params["layer"]`` (an int32 scalar), w1/w3/w2 stacked over layers
    ((L, E_held, d, f), ...), of which that layer's run.

    Routing as Qwen3-MoE and Mixtral publish it: softmax over all E
    router outputs in float32, the top k, renormalised to sum to 1.
    Returns (out, counters): counters (3,) int32 as ``MOE_COUNTERS``
    names them, over the whole batch."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top_w, top_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    top_w = top_w / top_w.sum(-1, keepdims=True)

    w1, w3, w2 = (params[n] for n in ("w1", "w3", "w2"))
    layer = params.get("layer")
    if layer is None:           # one layer's experts: a stack of one
        layer = jnp.zeros((), jnp.int32)
        w1, w3, w2 = w1[None], w3[None], w2[None]
    ep_mode, specs = _expert_specs(mesh, w1.shape[1], model_axis)
    bspec = P(batch_axes, None, None)
    fn = functools.partial(_local_dropless, e0=expert_offset,
                           ep_mode=ep_mode, model_axis=model_axis,
                           batch_axes=tuple(batch_axes))
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(bspec, P(batch_axes, None), bspec, bspec, P())
        + tuple(P(None, *s) for s in specs),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, valid, top_idx, top_w, layer, w1, w3, w2)
