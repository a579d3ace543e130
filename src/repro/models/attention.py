"""Attention: blocked causal (flash-style scan over KV chunks), sliding
window, cross attention, and cached one-token decode with ragged lengths.

All paths are GQA-native: queries are shaped (B, S, Hkv, Gq, hd) inside the
einsums so the KV tensors are never materialized at Hq width (for qwen2-72b
decode that avoids an 8x KV blow-up).  The blocked implementation keeps the
materialized score tile at (B, Hkv, Gq, Sq, kv_chunk) instead of
(B, H, S, S), so 32k prefill lowers with bounded memory.  On TPU the
one-token decode path is served by the Pallas ``decode_attention`` kernel
(repro.kernels); the jnp path here is the oracle and the dry-run path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["causal_attention", "chunk_attention", "cross_attention",
           "decode_attention"]

_NEG = -1e30


def _group_q(q, n_kv: int):
    """(B, Sq, Hq, hd) -> (B, Sq, Hkv, G, hd)."""
    b, s, hq, hd = q.shape
    assert hq % n_kv == 0, (hq, n_kv)
    return q.reshape(b, s, n_kv, hq // n_kv, hd)


def _mask_tile(sqc, ckv, q_pos0, kv_start, *, skv_valid,
               sliding_window):
    """Static causal/window/padding mask for one (Sqc, C) tile (fp32 add)."""
    q_pos = q_pos0 + jnp.arange(sqc)[:, None]          # (Sqc, 1)
    kv_pos = kv_start + jnp.arange(ckv)[None, :]       # (1, C)
    mask = (kv_pos <= q_pos) & (kv_pos < skv_valid)
    if sliding_window:
        mask &= kv_pos > (q_pos - sliding_window)
    return mask                                         # (Sqc, C) bool


def _flash_fwd_scan(qf, k, v, bias, q_pos0, sliding_window, kv_chunk,
                    n_kv, skv_valid):
    """Forward online-softmax pass; returns (out fp32, L logsumexp)."""
    b, sqc, hkv, g, hd = qf.shape

    def body(carry, ci):
        m, l, acc = carry
        start = ci * kv_chunk
        kc = jax.lax.dynamic_slice_in_dim(k, start, kv_chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, kv_chunk, axis=1)
        bc = jax.lax.dynamic_slice_in_dim(bias, start, kv_chunk, axis=1)
        s = jnp.einsum("bqhgd,bchd->bhgqc", qf.astype(kc.dtype), kc,
                       preferred_element_type=jnp.float32)
        kv_pos = start + jnp.arange(kv_chunk)
        q_pos = q_pos0 + jnp.arange(sqc)
        mask = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos[None, :]
                                                      < skv_valid)
        if sliding_window:
            mask &= kv_pos[None, :] > (q_pos[:, None] - sliding_window)
        s = jnp.where(mask[None, None, None], s, _NEG)
        s = s + bc[:, None, None, None, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqc,bchd->bhgqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sqc), _NEG, dtype=jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sqc), dtype=jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sqc, hd), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n_kv))
    out = acc / jnp.maximum(l[..., None], 1e-30)       # (B,Hkv,G,Sqc,hd)
    L = m + jnp.log(jnp.maximum(l, 1e-30))             # logsumexp per query
    return out, L


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attend(qf, k, v, bias, q_pos0, sliding_window, kv_chunk, n_kv,
                  skv_valid):
    """Flash attention for one query chunk (fp32 qf pre-scaled).

    Memory-bounded in both directions: the backward pass recomputes each
    (Sqc x C) tile instead of saving it — without this, differentiating
    through the online-softmax scan stores every tile and the "blocked"
    attention silently costs O(S^2) memory again.
    bias: (B, Skv_pad) additive fp32 (0 / -1e30) — carries ragged lengths.
    Returns (B, Sqc, Hkv, G, hd) fp32.
    """
    out, _ = _flash_fwd_scan(qf, k, v, bias, q_pos0, sliding_window,
                             kv_chunk, n_kv, skv_valid)
    return out.transpose(0, 3, 1, 2, 4)


def _flash_fwd(qf, k, v, bias, q_pos0, sliding_window, kv_chunk, n_kv,
               skv_valid):
    out, L = _flash_fwd_scan(qf, k, v, bias, q_pos0, sliding_window,
                             kv_chunk, n_kv, skv_valid)
    return out.transpose(0, 3, 1, 2, 4), (qf, k, v, bias, out, L)


def _flash_bwd(q_pos0, sliding_window, kv_chunk, n_kv, skv_valid, res, g_out):
    qf, k, v, bias, out, L = res            # out: (B,Hkv,G,Sqc,hd)
    b, sqc, hkv, gq, hd = g_out.shape
    dout = g_out.astype(jnp.float32).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,Sqc,hd)
    D = jnp.sum(dout * out, axis=-1)                            # (B,Hkv,G,Sqc)
    q_pos = q_pos0 + jnp.arange(sqc)

    def body(dq, ci):
        start = ci * kv_chunk
        kc = jax.lax.dynamic_slice_in_dim(k, start, kv_chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, kv_chunk, axis=1)
        bc = jax.lax.dynamic_slice_in_dim(bias, start, kv_chunk, axis=1)
        s = jnp.einsum("bqhgd,bchd->bhgqc", qf, kc.astype(jnp.float32))
        kv_pos = start + jnp.arange(kv_chunk)
        mask = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos[None, :]
                                                      < skv_valid)
        if sliding_window:
            mask &= kv_pos[None, :] > (q_pos[:, None] - sliding_window)
        s = jnp.where(mask[None, None, None], s, _NEG)
        s = s + bc[:, None, None, None, :]
        p = jnp.exp(s - L[..., None])                   # (B,Hkv,G,Sqc,C)
        dp = jnp.einsum("bhgqd,bchd->bhgqc", dout, vc.astype(jnp.float32))
        ds = p * (dp - D[..., None])
        dq = dq + jnp.einsum("bhgqc,bchd->bqhgd", ds, kc.astype(jnp.float32))
        dk_c = jnp.einsum("bhgqc,bqhgd->bchd", ds, qf)
        dv_c = jnp.einsum("bhgqc,bhgqd->bchd", p, dout)
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros_like(qf)
    dq, (dk_chunks, dv_chunks) = jax.lax.scan(body, dq0, jnp.arange(n_kv))
    # (n_kv, B, C, Hkv, hd) -> (B, n_kv*C, Hkv, hd)
    dk = dk_chunks.transpose(1, 0, 2, 3, 4).reshape(k.shape)
    dv = dv_chunks.transpose(1, 0, 2, 3, 4).reshape(v.shape)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros_like(bias))


_flash_attend.defvjp(_flash_fwd, _flash_bwd)


def _attend_q_chunk(qf, k, v, *, q_pos0: int, skv_valid: int,
                    sliding_window: int, kv_chunk: int,
                    lengths: Optional[jnp.ndarray], n_kv: int):
    """Online-softmax attention of one query chunk against k[:, :n_kv*C].

    qf: (B, Sq_c, Hkv, G, hd) pre-scaled fp32; k/v padded to kv_chunk
    multiples.  Returns fp32 (B, Sq_c, Hkv, G, hd)."""
    b = qf.shape[0]
    skv_pad = k.shape[1]
    if lengths is not None:
        bias = jnp.where(jnp.arange(skv_pad)[None, :] < lengths[:, None],
                         0.0, _NEG).astype(jnp.float32)
    else:
        bias = jnp.zeros((b, skv_pad), jnp.float32)
    return _flash_attend(qf, k, v, bias, q_pos0, sliding_window, kv_chunk,
                         n_kv, skv_valid)


def causal_attention(
    q, k, v,
    *,
    q_offset: int = 0,
    sliding_window: int = 0,
    kv_chunk: int = 512,
    q_chunk: int = 512,
    lengths: Optional[jnp.ndarray] = None,
):
    """Two-level blocked causal self-attention with online softmax.

    q: (B, Sq, Hq, hd);  k, v: (B, Skv, Hkv, hd), Hq % Hkv == 0.
    Query chunks are a *python* loop so each chunk's KV scan stops at the
    causal frontier (static trip count, no wasted FLOPs); KV chunks are a
    ``lax.scan``.  Peak score tile: (B, Hkv, G, q_chunk, kv_chunk) — this
    is what keeps 32k prefill and 4k train inside HBM even when the score
    tensor has no sharded dimension (head_dim-sharded configs).
    q_offset: absolute position of q[0]; lengths: (B,) valid kv lengths.
    Returns (B, Sq, Hq, hd).
    """
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    kv_chunk = min(kv_chunk, skv)
    # keep the unrolled q loop small for very long sequences
    n_q_target = max(1, sq // q_chunk)
    if n_q_target > 16:
        q_chunk = sq // 16
    q_chunk = min(q_chunk, sq)

    pad_kv = (-skv) % kv_chunk
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = _group_q(q, hkv).astype(jnp.float32) * scale  # (B,Sq,Hkv,G,hd)

    outs = []
    for start in range(0, sq, q_chunk):
        stop = min(start + q_chunk, sq)
        qc = qf[:, start:stop]
        # causal frontier: this chunk never reads past q_offset+stop
        if sliding_window:
            lo = max(0, (q_offset + start - sliding_window + 1)
                     // kv_chunk * kv_chunk)
        else:
            lo = 0
        hi_tok = min(q_offset + stop, skv)
        n_kv = max(1, -(-(hi_tok - lo) // kv_chunk))
        k_sl = k[:, lo:lo + n_kv * kv_chunk]
        v_sl = v[:, lo:lo + n_kv * kv_chunk]
        o = _attend_q_chunk(
            qc, k_sl, v_sl, q_pos0=q_offset + start - lo,
            skv_valid=skv - lo, sliding_window=sliding_window,
            kv_chunk=kv_chunk,
            lengths=None if lengths is None else lengths - lo,
            n_kv=n_kv)
        outs.append(o)
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.reshape(b, sq, hq, hd).astype(q.dtype)


def chunk_attention(q, k_cache, v_cache, *, q_pos, kv_len):
    """Mid-prefill chunk attention with per-row query offsets.

    One prompt *chunk* (chunked prefill) attends against everything written
    to the row's KV cache so far — earlier chunks plus this chunk's own KV,
    which the caller has already scattered into the cache.

    q: (B, C, Hq, hd) — the chunk's queries, right-padded per row;
    k_cache, v_cache: (B, L, Hkv, hd) — the full per-row cache buffers;
    q_pos: (B, C) absolute position of each query token;
    kv_len: (B,) valid cache length *including* this chunk.

    Unlike :func:`causal_attention` the query offset is per-row (rows of a
    chunk batch sit at different prefill depths), so the causal frontier is
    ``kv_pos <= q_pos[b, i]``.  Padded query columns produce garbage rows
    that the caller drops.  Direct (non-flash) fp32 softmax: chunk sizes
    are bounded by the scheduler's per-step budget, so the score tile is
    (B, Hkv, G, C, L) with small C.
    """
    b, c, hq, hd = q.shape
    L, hkv = k_cache.shape[1], k_cache.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = _group_q(q, hkv).astype(jnp.float32) * scale    # (B,C,Hkv,G,hd)
    s = jnp.einsum("bqhgd,blhd->bhgql", qf,
                   k_cache.astype(jnp.float32))          # (B,Hkv,G,C,L)
    kv_pos = jnp.arange(L)
    mask = ((kv_pos[None, None, :] <= q_pos[:, :, None])
            & (kv_pos[None, None, :] < kv_len[:, None, None]))  # (B,C,L)
    s = jnp.where(mask[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgql,blhd->bhgqd", p,
                     v_cache.astype(jnp.float32))
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, c, hq, hd)
    return out.astype(q.dtype)


def cross_attention(q, k, v, *, lengths: Optional[jnp.ndarray] = None):
    """Non-causal attention over a (fixed) encoder sequence.

    q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd)."""
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = _group_q(q, hkv).astype(jnp.float32) * scale
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    if lengths is not None:
        mask = jnp.arange(skv)[None, :] < lengths[:, None]   # (B, Skv)
        s = jnp.where(mask[:, None, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, hd)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     sliding_window: int = 0, rolling: bool = False):
    """One-token attention against a KV cache with per-request lengths.

    q: (B, Hq, hd) — the new token's queries.
    k_cache, v_cache: (B, L, Hkv, hd); lengths: (B,) ints — the number of
    tokens generated so far *including* the new token (whose KV must
    already be written).

    ``rolling=True`` marks a ring-buffer cache (sliding-window archs): all
    L slots are valid once lengths >= L, and positional correctness comes
    from RoPE applied at write time.
    """
    b, hq, hd = q.shape
    L, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = (q.reshape(b, hkv, g, hd).astype(jnp.float32)
          * scale).astype(k_cache.dtype)
    s = jnp.einsum("bhgd,blhd->bhgl", qf, k_cache,
                   preferred_element_type=jnp.float32)  # (B,Hkv,G,L)
    pos = jnp.arange(L)[None, :]                       # (1, L)
    if rolling:
        mask = pos < jnp.minimum(lengths, L)[:, None]
    else:
        mask = pos < lengths[:, None]
        if sliding_window:
            mask &= pos >= (lengths[:, None] - sliding_window)
    s = jnp.where(mask[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgl,blhd->bhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, hd).astype(q.dtype)


def decode_attention_lsharded(q, k_cache, v_cache, lengths, *, mesh,
                              batch_axes=("data",), model_axis="model"):
    """Distributed flash-decode: KV cache sharded along the LENGTH axis.

    Each model shard attends q (replicated, tiny) against its local KV
    slice and the partial (m, l, acc) statistics are merged with an
    online-softmax combine — the only collectives are psums of
    (B, Hq)-sized stats and the (B, Hq, hd) accumulator, instead of the
    per-layer weight regathers / score psums that head_dim sharding
    forces (RoPE splits head_dim, so hd-sharded weights get re-gathered
    every layer).

    q: (B, Hq, hd); k_cache/v_cache: (B, L, Hkv, hd) with L sharded over
    ``model_axis``; lengths: (B,).  Returns (B, Hq, hd), replicated over
    the model axis.
    """
    from jax.sharding import PartitionSpec as P

    b_spec = batch_axes if batch_axes else None
    L = k_cache.shape[1]
    msize = mesh.shape[model_axis]
    assert L % msize == 0, (L, msize)
    l_loc = L // msize

    def local_fn(q, k, v, lengths):
        # q: (B, Hq, hd) replicated over model; k/v: (B, L_loc, Hkv, hd)
        b, hq, hd = q.shape
        hkv = k.shape[2]
        g = hq // hkv
        idx = jax.lax.axis_index(model_axis)
        offset = idx * l_loc
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        qf = (q.reshape(b, hkv, g, hd).astype(jnp.float32)
              * scale).astype(k.dtype)
        s = jnp.einsum("bhgd,blhd->bhgl", qf, k,
                       preferred_element_type=jnp.float32)  # (B,Hkv,G,Lloc)
        pos = offset + jnp.arange(l_loc)[None, :]      # (1, L_loc)
        mask = pos < lengths[:, None]
        s = jnp.where(mask[:, None, None, :], s, _NEG)
        m = s.max(axis=-1)                             # (B,Hkv,G)
        p = jnp.exp(s - m[..., None])
        l_sum = p.sum(axis=-1)
        acc = jnp.einsum("bhgl,blhd->bhgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        # online-softmax merge across shards (tiny collectives)
        m_all = jax.lax.pmax(m, model_axis)
        alpha = jnp.exp(jnp.clip(m - m_all, -60.0, 0.0))
        l_tot = jax.lax.psum(l_sum * alpha, model_axis)
        acc_tot = jax.lax.psum(acc * alpha[..., None], model_axis)
        out = acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
        return out.reshape(b, hq, hd).astype(q.dtype)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(b_spec, None, None),
                  P(b_spec, model_axis, None, None),
                  P(b_spec, model_axis, None, None),
                  P(b_spec)),
        out_specs=P(b_spec, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, lengths)
