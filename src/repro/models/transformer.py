"""Model assembly: parameter init, train forward, prefill, and decode for
all six architecture families (dense / moe / ssm / hybrid / vlm / audio).

Design points:
  * scan-over-layers with stacked parameters (small HLO, bounded compile
    time at 88 layers) + ``jax.checkpoint`` per layer (remat);
  * GQA attention with blocked causal kernel (attention.py);
  * heterogeneous stacks (xLSTM m/s interleave, Zamba2 mamba+shared-attn)
    are grouped: homogeneous runs are scanned, the interleaving is a small
    python loop over groups;
  * caches are plain dict pytrees, stacked along the scan axis, threaded
    through ``lax.scan`` as xs/ys;
  * every function is mesh-agnostic except MoE (shard_map inside) — pass a
    (1,1) mesh for CPU tests.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from . import attention as attn_lib
from . import ssm as ssm_lib
from .layers import (
    Param,
    apply_rope,
    cross_entropy_loss,
    dense_init,
    embed_init,
    linear,
    make_rope,
    norm_init,
    rms_norm,
    swiglu,
)
from .moe import moe_ffn, moe_ffn_dropless

PyTree = Any


# ==========================================================================
# Parameter initialization
# ==========================================================================

def _attn_init(key, cfg: ModelConfig, *, cross: bool = False,
               dtype=jnp.float32) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, hq, hd), ("embed", "heads", "hd"),
                         dtype=dtype),
        "wk": dense_init(ks[1], (d, hkv, hd), ("embed", "kv_heads", "hd"),
                         dtype=dtype),
        "wv": dense_init(ks[2], (d, hkv, hd), ("embed", "kv_heads", "hd"),
                         dtype=dtype),
        "wo": dense_init(ks[3], (hq, hd, d), ("heads", "hd", "embed"),
                         scale=1.0 / np.sqrt(hq * hd), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd, ("hd",))
        p["k_norm"] = norm_init(hd, ("hd",))
    if cfg.qkv_bias:
        p["bq"] = Param(jnp.zeros((hq, hd), dtype), ("heads", "hd"))
        p["bk"] = Param(jnp.zeros((hkv, hd), dtype), ("kv_heads", "hd"))
        p["bv"] = Param(jnp.zeros((hkv, hd), dtype), ("kv_heads", "hd"))
    return p


def _mlp_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(ks[0], (d, f), ("embed", "mlp"), dtype=dtype),
        "w_out": dense_init(ks[2], (f, d), ("mlp", "embed"), dtype=dtype),
    }
    if cfg.mlp_variant == "swiglu":
        p["w_gate"] = dense_init(ks[1], (d, f), ("embed", "mlp"),
                                 dtype=dtype)
    return p


def _mlp_forward(cfg: ModelConfig, p, x):
    if cfg.mlp_variant == "swiglu":
        return swiglu(x, p["w_in"], p["w_gate"], p["w_out"])
    return linear(jax.nn.gelu(linear(x, p["w_in"])), p["w_out"])


def _moe_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    """The router over all ``n_experts`` and the held experts' weights.

    Expert ``e`` is drawn from ``fold_in(key, e)``, so a chip's held
    experts are the same values as those rows of the whole layer."""
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    held = jnp.arange(cfg.expert_offset, cfg.expert_offset + cfg.n_held)

    def experts(k, shape, axes):
        draw = jax.vmap(lambda e: dense_init(jax.random.fold_in(k, e),
                                             shape, axes, dtype=dtype).value)
        return Param(draw(held), ("experts",) + axes)

    return {
        "router": dense_init(ks[0], (d, E), ("embed", "experts"),
                             dtype=jnp.float32),
        "w1": experts(ks[1], (d, f), ("embed", "expert_mlp")),
        "w3": experts(ks[2], (d, f), ("embed", "expert_mlp")),
        "w2": experts(ks[3], (f, d), ("expert_mlp", "embed")),
    }


def _mamba_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    H = cfg.n_ssm_heads
    K = cfg.ssm_conv_width
    ks = jax.random.split(key, 6)
    return {
        # in_proj -> [z (di), x (di), B (N), C (N), dt (H)]
        "w_in": dense_init(ks[0], (d, 2 * di + 2 * N + H),
                           ("embed", "ssm_in"), dtype=dtype),
        "conv_w": Param(0.1 * jax.random.normal(ks[1], (K, di),
                                                dtype=jnp.float32)
                        .astype(dtype), ("conv_k", "ssm_inner")),
        "a_log": Param(jnp.log(jnp.linspace(1.0, float(max(H, 2)), H)),
                       ("ssm_heads",)),
        "dt_bias": Param(jnp.zeros((H,), jnp.float32), ("ssm_heads",)),
        "d_skip": Param(jnp.ones((H,), jnp.float32), ("ssm_heads",)),
        "norm": norm_init(di, ("ssm_inner",)),
        "w_out": dense_init(ks[2], (di, d), ("ssm_inner", "embed"),
                            dtype=dtype),
    }


def _mlstm_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    H = cfg.n_ssm_heads
    K = cfg.ssm_conv_width
    ks = jax.random.split(key, 8)
    return {
        "w_up": dense_init(ks[0], (d, 2 * di), ("embed", "ssm_in"),
                           dtype=dtype),
        "conv_w": Param(0.1 * jax.random.normal(ks[1], (K, di),
                                                jnp.float32).astype(dtype),
                        ("conv_k", "ssm_inner")),
        "wq": dense_init(ks[2], (di, di), ("ssm_inner", "ssm_inner2"),
                         dtype=dtype),
        "wk": dense_init(ks[3], (di, di), ("ssm_inner", "ssm_inner2"),
                         dtype=dtype),
        "wv": dense_init(ks[4], (di, di), ("ssm_inner", "ssm_inner2"),
                         dtype=dtype),
        "w_gates": dense_init(ks[5], (di, 2 * H), ("ssm_inner", "ssm_heads2"),
                              dtype=jnp.float32),
        "norm": norm_init(di, ("ssm_inner",)),
        "w_out": dense_init(ks[6], (di, d), ("ssm_inner", "embed"),
                            dtype=dtype),
    }


def _slstm_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d = cfg.d_model
    H = cfg.n_ssm_heads
    hd = d // H
    ks = jax.random.split(key, 4)
    return {
        "w_x": dense_init(ks[0], (d, H, 4, hd),
                          ("embed", "ssm_heads", "gates", "hd"), dtype=dtype),
        "b_x": Param(jnp.zeros((H, 4, hd), jnp.float32),
                     ("ssm_heads", "gates", "hd")),
        "r_h": Param(
            (0.5 / np.sqrt(hd)) * jax.random.normal(
                ks[1], (H, 4, hd, hd), jnp.float32).astype(dtype),
            ("ssm_heads", "gates", "hd", "hd2")),
        "w_ffn_in": dense_init(ks[2], (d, 2 * d), ("embed", "mlp"),
                               dtype=dtype),
        "w_ffn_out": dense_init(ks[3], (2 * d, d), ("mlp", "embed"),
                                dtype=dtype),
    }


def _block_init(key, cfg: ModelConfig, kind: str, dtype) -> dict:
    """One decoder block of the given kind with its norms."""
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    if kind == "attn":
        p = {"norm1": norm_init(d), "attn": _attn_init(k1, cfg, dtype=dtype),
             "norm2": norm_init(d)}
        p["ffn"] = _moe_init(k2, cfg, dtype) if cfg.is_moe \
            else _mlp_init(k2, cfg, dtype)
        return p
    if kind == "mamba":
        return {"norm1": norm_init(d), "ssm": _mamba_init(k1, cfg, dtype)}
    if kind == "mlstm":
        return {"norm1": norm_init(d), "ssm": _mlstm_init(k1, cfg, dtype)}
    if kind == "slstm":
        return {"norm1": norm_init(d), "ssm": _slstm_init(k1, cfg, dtype),
                "norm2": norm_init(d)}
    raise ValueError(kind)


def _stack_init(key, cfg: ModelConfig, kind: str, n: int, dtype) -> dict:
    """n stacked blocks (leading scan axis on every leaf).

    One vmapped draw per weight over the layer keys: the same values as
    n per-layer draws stacked, without n separate blocks plus their
    stacked copy in memory, and one block's program to compile, not n."""
    keys = jax.random.split(key, n)
    stacked = jax.vmap(lambda k: _block_init(k, cfg, kind, dtype))(keys)
    return jax.tree.map(lambda p: Param(p.value, ("layers",) + p.axes),
                        stacked, is_leaf=lambda x: isinstance(x, Param))


def layer_pattern(cfg: ModelConfig) -> list[tuple[str, str, int]]:
    """Describe the decoder stack as homogeneous groups:
    list of (group_name, kind, n_blocks)."""
    if cfg.family in ("dense", "moe", "vlm"):
        return [("blocks", "attn", cfg.n_layers)]
    if cfg.family == "audio":
        return [("blocks", "attn", cfg.n_layers)]  # decoder; encoder separate
    if cfg.family == "ssm":
        # xLSTM [7:1]: every cfg.slstm_every-th block is sLSTM
        out = []
        run = 0
        gi = 0
        for i in range(cfg.n_layers):
            is_s = cfg.slstm_every and ((i + 1) % cfg.slstm_every == 0)
            if is_s:
                if run:
                    out.append((f"m{gi}", "mlstm", run))
                out.append((f"s{gi}", "slstm", 1))
                run = 0
                gi += 1
            else:
                run += 1
        if run:
            out.append((f"m{gi}", "mlstm", run))
        return out
    if cfg.family == "hybrid":
        # Zamba2: groups of attn_every mamba blocks + 1 *shared* attn block
        n_groups = cfg.n_layers // (cfg.attn_every + 1)
        rest = cfg.n_layers - n_groups * (cfg.attn_every + 1)
        out = []
        for gi in range(n_groups):
            out.append((f"m{gi}", "mamba", cfg.attn_every))
            out.append((f"shared{gi}", "shared_attn", 1))
        if rest:
            out.append(("m_tail", "mamba", rest))
        return out
    raise ValueError(cfg.family)


def init_params(cfg: ModelConfig, key) -> PyTree:
    """Full Param tree for the model."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 16)
    params: dict = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model,
                            dtype=dtype),
        "final_norm": norm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size),
                                       ("embed", "vocab"), dtype=dtype)
    groups = layer_pattern(cfg)
    shared_done = False
    for gi, (gname, kind, n) in enumerate(groups):
        k = jax.random.fold_in(keys[2], gi)
        if kind == "shared_attn":
            if not shared_done:
                params["shared_attn"] = _block_init(k, cfg, "attn", dtype)
                shared_done = True
            continue
        params[gname] = _stack_init(k, cfg, kind, n, dtype)
    if cfg.family == "audio":
        # encoder stack (non-causal attention + MLP) + learned positions
        params["enc_blocks"] = _stack_init(keys[3], cfg, "attn",
                                           cfg.encoder_layers, dtype)
        params["enc_pos"] = Param(
            0.01 * jax.random.normal(keys[4], (cfg.encoder_seq, cfg.d_model),
                                     jnp.float32).astype(dtype),
            ("enc_seq", "embed"))
        params["enc_norm"] = norm_init(cfg.d_model)
        # decoder cross-attention (one per decoder layer, stacked)
        cross = [
            {"norm": norm_init(cfg.d_model),
             "attn": _attn_init(jax.random.fold_in(keys[5], i), cfg,
                                cross=True, dtype=dtype)}
            for i in range(cfg.n_layers)
        ]
        params["cross_blocks"] = jax.tree.map(
            lambda *xs: Param(jnp.stack([x.value for x in xs]),
                              ("layers",) + xs[0].axes),
            *cross, is_leaf=lambda x: isinstance(x, Param))
    if cfg.family == "vlm":
        params["projector"] = _mlp_init(keys[6], cfg, dtype)
    return params


# ==========================================================================
# Block forward functions
# ==========================================================================

def _head_norm(cfg: ModelConfig, ap, name: str, t):
    """Qwen3's QK-norm (``name`` ``q_norm`` or ``k_norm``): RMSNorm of
    each head of q or k over ``head_dim`` with its own scale, before
    RoPE; ``t`` unchanged without ``cfg.qk_norm``."""
    return rms_norm(t, ap[name], cfg.norm_eps) if cfg.qk_norm else t


def _ffn(cfg: ModelConfig, p, h, *, valid, mesh, batch_axes):
    """The FFN half of an attention block on the serving paths: the
    dense MLP, or the dropless expert layer over the ``valid`` tokens
    (a thunk, so that dense programs build no mask).  Returns (y,
    counters): counters as ``moe.MOE_COUNTERS``, None for a dense MLP."""
    if not cfg.is_moe:
        return _mlp_forward(cfg, p["ffn"], h), None
    return moe_ffn_dropless(h, valid(), p["ffn"], k=cfg.experts_per_token,
                            mesh=mesh, batch_axes=batch_axes,
                            expert_offset=cfg.expert_offset)


def _attn_forward(cfg: ModelConfig, p, x, *, sin, cos, mode: str,
                  cache=None, lengths=None, q_offset=0, mesh=None,
                  batch_axes=("data",), cross_kv=None, enc_lengths=None,
                  rolling=False, kv_shard="none"):
    """Self-attention block (+ FFN).  Returns (x, new_cache, aux)."""
    B = x.shape[0]
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    ap = p["attn"]
    q = linear(h, ap["wq"], ap.get("bq"))          # (B, S, Hq, hd)
    k = linear(h, ap["wk"], ap.get("bk"))          # (B, S, Hkv, hd)
    v = linear(h, ap["wv"], ap.get("bv"))
    q = apply_rope(_head_norm(cfg, ap, "q_norm", q), sin, cos)
    k = apply_rope(_head_norm(cfg, ap, "k_norm", k), sin, cos)

    new_cache = None
    if mode == "decode":
        # write new kv at position lengths-1 (lengths already incremented)
        pos = lengths - 1
        L = cache["k"].shape[1]
        if rolling:
            pos = pos % L
        bidx = jnp.arange(B)
        k_cache = cache["k"].at[bidx, pos].set(k[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[bidx, pos].set(v[:, 0].astype(cache["v"].dtype))
        if kv_shard == "length" and not rolling:
            o = attn_lib.decode_attention_lsharded(
                q[:, 0], k_cache, v_cache, lengths, mesh=mesh,
                batch_axes=batch_axes)[:, None]
        else:
            o = attn_lib.decode_attention(q[:, 0], k_cache, v_cache,
                                          lengths,
                                          sliding_window=cfg.sliding_window,
                                          rolling=rolling)[:, None]
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        o = attn_lib.causal_attention(
            q, k, v, q_offset=q_offset,
            sliding_window=cfg.sliding_window,
            lengths=lengths if mode == "prefill" else None)
        if mode == "prefill":
            L = cache["k"].shape[1] if cache is not None else k.shape[1]
            S = k.shape[1]
            if L == S:
                new_cache = {"k": k, "v": v}
            else:
                kc = jnp.zeros((B, L) + k.shape[2:], k.dtype)
                new_cache = {"k": kc.at[:, :S].set(k),
                             "v": kc.at[:, :S].set(v)}
    x = x + linear(o.reshape(o.shape[:-2] + (-1,)),
                   ap["wo"].reshape(-1, cfg.d_model))

    aux = jnp.zeros((), jnp.float32)
    if cross_kv is not None:
        cp = p["cross"]
        hc = rms_norm(x, cp["norm"], cfg.norm_eps)
        qc = linear(hc, cp["attn"]["wq"], cp["attn"].get("bq"))
        oc = attn_lib.cross_attention(qc, cross_kv["k"], cross_kv["v"],
                                      lengths=enc_lengths)
        x = x + linear(oc.reshape(oc.shape[:-2] + (-1,)),
                       cp["attn"]["wo"].reshape(-1, cfg.d_model))

    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if cfg.is_moe and mode == "train":
        y, aux = moe_ffn(h2, p["ffn"], n_experts=cfg.n_experts,
                         k=cfg.experts_per_token, mesh=mesh,
                         batch_axes=batch_axes,
                         capacity_factor=cfg.capacity_factor,
                         expert_offset=cfg.expert_offset)
    else:
        # serving: a prefill's padding columns route nowhere
        def valid():
            if mode == "prefill" and lengths is not None:
                return jnp.arange(x.shape[1])[None, :] < lengths[:, None]
            return jnp.ones(x.shape[:2], bool)
        y, _ = _ffn(cfg, p, h2, valid=valid, mesh=mesh,
                    batch_axes=batch_axes)
    return x + y, new_cache, aux


def _mamba_forward(cfg: ModelConfig, p, x, *, mode: str, cache=None,
                   lengths=None):
    """Mamba2 (SSD) block.  Returns (x, new_cache).

    ``lengths`` (prefill): padding steps get dt=0, which zeroes both the
    decay exponent and the input gate — the state is untouched beyond the
    true prompt length."""
    d, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    P_hd = di // H
    sp = p["ssm"]
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    proj = linear(h, sp["w_in"])          # (..., 2di+2N+H)
    z, xs, Bm, Cm, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + sp["dt_bias"].astype(jnp.float32))  # (...,H)
    a = -jnp.exp(sp["a_log"].astype(jnp.float32))              # (H,)
    if mode != "decode" and lengths is not None:
        valid = (jnp.arange(x.shape[1])[None, :]
                 < lengths[:, None]).astype(jnp.float32)
        dt = dt * valid[..., None]

    if mode == "decode":
        xc, conv_state = ssm_lib.causal_conv1d_step(
            xs[:, 0], sp["conv_w"], cache["conv"])
        xc = jax.nn.silu(xc)
        xh = xc.reshape(-1, H, P_hd)
        y, state = ssm_lib.linear_attention_step(
            jnp.broadcast_to(Cm[:, 0, None, :], Cm.shape[:1] + (H, N)),
            jnp.broadcast_to(Bm[:, 0, None, :], Bm.shape[:1] + (H, N)),
            xh, dt[:, 0] * a[None, :], dt[:, 0], cache["state"])
        y = y + sp["d_skip"].astype(y.dtype)[None, :, None] * xh
        y = y.reshape(y.shape[0], 1, di)
        new_cache = {"conv": conv_state, "state": state}
        zz = z
    else:
        xc, conv_state = ssm_lib.causal_conv1d(
            xs, sp["conv_w"],
            lengths=lengths if mode == "prefill" else None)
        xc = jax.nn.silu(xc)
        Bt, S = x.shape[0], x.shape[1]
        xh = xc.reshape(Bt, S, H, P_hd)
        y, state = ssm_lib.chunked_linear_attention(
            Cm[:, :, None, :], Bm[:, :, None, :], xh,
            dt * a[None, None, :], dt, chunk=128)
        y = y + sp["d_skip"].astype(y.dtype)[None, None, :, None] * xh
        y = y.reshape(Bt, S, di)
        new_cache = {"conv": conv_state, "state": state} \
            if mode == "prefill" else None
        zz = z
    y = rms_norm(y * jax.nn.silu(zz), sp["norm"], cfg.norm_eps)
    return x + linear(y, sp["w_out"]), new_cache


def _mlstm_forward(cfg: ModelConfig, p, x, *, mode: str, cache=None,
                   lengths=None):
    d, di, H = cfg.d_model, cfg.d_inner, cfg.n_ssm_heads
    hd = di // H
    sp = p["ssm"]
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    up = linear(h, sp["w_up"])
    xm, z = jnp.split(up, 2, axis=-1)

    if mode == "decode":
        xc, conv_state = ssm_lib.causal_conv1d_step(
            xm[:, 0], sp["conv_w"], cache["conv"])
        xc = jax.nn.silu(xc)
        q = linear(xc, sp["wq"]).reshape(-1, H, hd)
        k = linear(xc, sp["wk"]).reshape(-1, H, hd) / np.sqrt(hd)
        v = linear(xc, sp["wv"]).reshape(-1, H, hd)
        gates = linear(xc, sp["w_gates"]).astype(jnp.float32)
        i_pre, f_pre = jnp.split(gates, 2, axis=-1)       # (B, H)
        y, state = ssm_lib.linear_attention_step(
            q, k, v, jax.nn.log_sigmoid(f_pre), jax.nn.sigmoid(i_pre),
            cache["state"], normalize=True)
        y = y.reshape(-1, 1, di)
        new_cache = {"conv": conv_state, "state": state}
        zz = z
    else:
        xc, conv_state = ssm_lib.causal_conv1d(
            xm, sp["conv_w"],
            lengths=lengths if mode == "prefill" else None)
        xc = jax.nn.silu(xc)
        Bt, S = x.shape[0], x.shape[1]
        q = linear(xc, sp["wq"]).reshape(Bt, S, H, hd)
        k = linear(xc, sp["wk"]).reshape(Bt, S, H, hd) / np.sqrt(hd)
        v = linear(xc, sp["wv"]).reshape(Bt, S, H, hd)
        gates = linear(xc, sp["w_gates"]).astype(jnp.float32)
        i_pre, f_pre = jnp.split(gates, 2, axis=-1)       # (B, S, H)
        log_f = jax.nn.log_sigmoid(f_pre)
        i_g = jax.nn.sigmoid(i_pre)
        if lengths is not None:
            valid = (jnp.arange(S)[None, :]
                     < lengths[:, None]).astype(jnp.float32)[..., None]
            log_f = log_f * valid   # decay 1 on padding
            i_g = i_g * valid       # no input on padding
        y, state = ssm_lib.chunked_linear_attention(
            q, k, v, log_f, i_g, chunk=128, normalize=True)
        y = y.reshape(Bt, S, di)
        new_cache = {"conv": conv_state, "state": state} \
            if mode == "prefill" else None
        zz = z
    y = rms_norm(y * jax.nn.silu(zz), sp["norm"], cfg.norm_eps)
    return x + linear(y, sp["w_out"]), new_cache


def _slstm_forward(cfg: ModelConfig, p, x, *, mode: str, cache=None,
                   lengths=None):
    d = cfg.d_model
    H = cfg.n_ssm_heads
    hd = d // H
    sp = p["ssm"]
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    xg = linear(h, sp["w_x"].reshape(d, -1)).reshape(
        h.shape[:-1] + (H, 4, hd)) + sp["b_x"].astype(h.dtype)
    if mode == "decode":
        y, state = ssm_lib.slstm_step(xg[:, 0], sp["r_h"], cache["hcnm"])
        y = y[:, None]
        new_cache = {"hcnm": state}
    else:
        valid = None
        if lengths is not None:
            valid = jnp.arange(xg.shape[1])[None, :] < lengths[:, None]
        y, state = ssm_lib.slstm_scan(xg, sp["r_h"], valid=valid)
        new_cache = {"hcnm": state} if mode == "prefill" else None
    y = y.reshape(y.shape[:2] + (d,))
    x = x + y
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    ff = linear(jax.nn.gelu(linear(h2, sp["w_ffn_in"])), sp["w_ffn_out"])
    return x + ff, new_cache


# ==========================================================================
# Stack execution
# ==========================================================================

def _tree_slice(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _block_forward(cfg, kind, p, x, *, mode, cache, common):
    """Dispatch one block.  Returns (x, new_cache, aux)."""
    if kind in ("attn", "shared_attn"):
        return _attn_forward(cfg, p, x, mode=mode, cache=cache, **common)
    lengths = common.get("lengths")
    if kind == "mamba":
        x, nc = _mamba_forward(cfg, p, x, mode=mode, cache=cache,
                               lengths=lengths)
    elif kind == "mlstm":
        x, nc = _mlstm_forward(cfg, p, x, mode=mode, cache=cache,
                               lengths=lengths)
    elif kind == "slstm":
        x, nc = _slstm_forward(cfg, p, x, mode=mode, cache=cache,
                               lengths=lengths)
    else:
        raise ValueError(kind)
    return x, nc, jnp.zeros((), jnp.float32)


def _empty_cache_block(cfg: ModelConfig, kind: str, batch: int,
                       max_len: int, dtype) -> Optional[dict]:
    hkv, hd = cfg.n_kv_heads, cfg.hd
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    K = cfg.ssm_conv_width
    if kind in ("attn", "shared_attn"):
        L = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        z = jnp.zeros((batch, L, hkv, hd), dtype)
        return {"k": z, "v": z}
    if kind == "mamba":
        return {"conv": jnp.zeros((batch, K - 1, di), dtype),
                "state": jnp.zeros((batch, H, N, di // H), jnp.float32)}
    if kind == "mlstm":
        hd_i = di // H
        return {"conv": jnp.zeros((batch, K - 1, di), dtype),
                "state": jnp.zeros((batch, H, hd_i, hd_i + 1), jnp.float32)}
    if kind == "slstm":
        hd_s = cfg.d_model // H
        z = jnp.zeros((batch, H, hd_s), jnp.float32)
        return {"hcnm": (z, z, z, jnp.full((batch, H, hd_s), -1e30,
                                           jnp.float32))}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> dict:
    """Decode cache pytree: per group, stacked along the scan axis."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    cache: dict = {"lengths": jnp.zeros((batch,), jnp.int32)}
    for gname, kind, n in layer_pattern(cfg):
        blk = _empty_cache_block(cfg, kind, batch, max_len, dtype)
        cache[gname] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), blk)
    if cfg.family == "audio":
        # cross-attention KV computed at prefill: (layers, B, S_enc, Hkv, hd)
        z = jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq, cfg.n_kv_heads,
                       cfg.hd), dtype)
        cache["cross_kv"] = {"k": z, "v": z}
        cache["enc_lengths"] = jnp.full((batch,), cfg.encoder_seq, jnp.int32)
    return cache


def _run_stack(cfg: ModelConfig, params, x, *, mode: str, cache, common,
               remat: bool = True):
    """Run all groups; returns (x, new_cache, aux_total).

    ``cache`` entries (stacked per group) are threaded through lax.scan.
    """
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    cross_all = common.pop("cross_all", None)
    act_spec = common.pop("act_spec", None)

    def _constrain(t):
        if act_spec is None:
            return t
        return jax.lax.with_sharding_constraint(t, act_spec)

    x = _constrain(x)

    for gname, kind, n in layer_pattern(cfg):
        gp = params["shared_attn"] if kind == "shared_attn" \
            else params[gname]
        gcache = cache.get(gname) if cache is not None else None

        if kind == "shared_attn":
            # single application, weights shared across groups
            c_in = _tree_slice(gcache, 0) if gcache is not None else None
            x, nc, aux = _block_forward(cfg, kind, gp, x, mode=mode,
                                        cache=c_in, common=dict(common))
            x = _constrain(x)
            aux_total = aux_total + aux
            if nc is not None:
                new_cache[gname] = jax.tree.map(lambda a: a[None], nc)
            continue

        if n == 1:
            p0 = _tree_slice(gp, 0)
            c0 = _tree_slice(gcache, 0) if gcache is not None else None
            if cross_all is not None:
                p0 = dict(p0)
                # cross handled only in audio path below (per-layer index)
            x, nc, aux = _block_forward(cfg, kind, p0, x, mode=mode,
                                        cache=c0, common=dict(common))
            x = _constrain(x)
            aux_total = aux_total + aux
            if nc is not None:
                new_cache[gname] = jax.tree.map(lambda a: a[None], nc)
            continue

        def layer(carry, xs):
            xx, aux_acc = carry
            p, c = xs
            xx, nc, aux = _block_forward(cfg, kind, p, xx, mode=mode,
                                         cache=c, common=dict(common))
            return (_constrain(xx), aux_acc + aux), nc

        fn = jax.checkpoint(layer) if remat else layer
        (x, aux_total), ncs = jax.lax.scan(
            fn, (x, aux_total), (gp, gcache))
        if mode != "train" and ncs is not None:
            new_cache[gname] = ncs
    return x, new_cache, aux_total


def _run_stack_audio(cfg: ModelConfig, params, x, *, mode: str, cache,
                     common, cross_kv, enc_lengths, remat: bool = True):
    """Decoder stack with per-layer cross attention (audio family)."""
    aux_total = jnp.zeros((), jnp.float32)
    act_spec = common.pop("act_spec", None)

    def _constrain(t):
        if act_spec is None:
            return t
        return jax.lax.with_sharding_constraint(t, act_spec)

    x = _constrain(x)
    gp = params["blocks"]
    cp = params["cross_blocks"]
    gcache = cache.get("blocks") if cache is not None else None

    def layer(carry, xs):
        xx, aux_acc = carry
        p, cb, c, ckv = xs
        p = dict(p)
        p["cross"] = cb
        cm = dict(common)
        cm["cross_kv"] = ckv
        cm["enc_lengths"] = enc_lengths
        xx, nc, aux = _attn_forward(cfg, p, xx, mode=mode, cache=c, **cm)
        return (_constrain(xx), aux_acc + aux), nc

    fn = jax.checkpoint(layer) if remat else layer
    (x, aux_total), ncs = jax.lax.scan(
        fn, (x, aux_total), (gp, cp, gcache, cross_kv))
    new_cache = {"blocks": ncs} if mode != "train" and ncs is not None else {}
    return x, new_cache, aux_total


def _encode_audio(cfg: ModelConfig, params, frames, remat: bool = True):
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend): frames (B, S_enc, d)."""
    # follow the parameter compute dtype (mixed-precision train casts)
    x = frames.astype(params["enc_pos"].dtype) + params["enc_pos"][None]
    sin, cos = make_rope(jnp.arange(x.shape[1]), cfg.hd, cfg.rope_theta)
    sin, cos = sin[None], cos[None]

    def layer(xx, p):
        h = rms_norm(xx, p["norm1"], cfg.norm_eps)
        q = linear(h, p["attn"]["wq"], p["attn"].get("bq"))
        k = linear(h, p["attn"]["wk"], p["attn"].get("bk"))
        v = linear(h, p["attn"]["wv"], p["attn"].get("bv"))
        o = attn_lib.cross_attention(q, k, v)  # full bidirectional
        xx = xx + linear(o.reshape(o.shape[:-2] + (-1,)),
                         p["attn"]["wo"].reshape(-1, cfg.d_model))
        h2 = rms_norm(xx, p["norm2"], cfg.norm_eps)
        y = _mlp_forward(cfg, p["ffn"], h2)
        return xx + y, None

    fn = jax.checkpoint(layer) if remat else layer
    x, _ = jax.lax.scan(lambda c, p: fn(c, p), x, params["enc_blocks"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _cross_kv_from_encoder(cfg: ModelConfig, params, enc_out):
    """Precompute per-decoder-layer cross K/V: (layers, B, S_enc, Hkv, hd)."""
    def one(cb):
        k = linear(enc_out, cb["attn"]["wk"], cb["attn"].get("bk"))
        v = linear(enc_out, cb["attn"]["wv"], cb["attn"].get("bv"))
        return {"k": k, "v": v}

    return jax.vmap(one, in_axes=0, out_axes=0)(params["cross_blocks"])


# ==========================================================================
# Model-level API
# ==========================================================================

def _embed_tokens(cfg, params, tokens):
    return params["embed"][tokens]


def _lm_logits(cfg, params, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, params["embed"])
    return linear(x, params["lm_head"])


def _chunked_lm_loss(cfg, params, x, targets, mask, *, chunk: int = 256):
    """Fused lm_head + cross entropy, scanned over sequence chunks with
    remat, so the fp32 (B, S, V) logits tensor is never materialized (a
    256k-vocab model at B_loc=16, S=4096 would need ~67 GB otherwise)."""
    B, S, _ = x.shape
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    if S % chunk != 0 or S <= chunk:
        logits = _lm_logits(cfg, params, x)
        return cross_entropy_loss(logits, targets, mask)
    n = S // chunk

    def body(carry, i):
        nll_sum, m_sum = carry
        xs = jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=1)
        ts = jax.lax.dynamic_slice_in_dim(targets, i * chunk, chunk, axis=1)
        ms = jax.lax.dynamic_slice_in_dim(mask, i * chunk, chunk, axis=1)
        logits = _lm_logits(cfg, params, xs).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
        msf = ms.astype(jnp.float32)
        return (nll_sum + jnp.sum((logz - gold) * msf),
                m_sum + jnp.sum(msf)), None

    (nll, m), _ = jax.lax.scan(jax.checkpoint(body),
                               (jnp.zeros((), jnp.float32),
                                jnp.zeros((), jnp.float32)),
                               jnp.arange(n))
    return nll / jnp.maximum(m, 1.0)


def _prepare_inputs(cfg: ModelConfig, params, batch):
    """Embed tokens; splice in frontend embeddings for vlm/audio."""
    x = _embed_tokens(cfg, params, batch["tokens"])
    if cfg.family == "vlm" and "patches" in batch:
        proj = batch["patches"]
        pr = params["projector"]
        proj = _mlp_forward(cfg, pr, proj)
        # patches occupy the first patch_tokens positions of the sequence
        npt = proj.shape[1]
        x = jnp.concatenate([proj.astype(x.dtype), x[:, npt:]], axis=1)
    return x


def loss_fn(cfg: ModelConfig, params, batch, *, mesh=None,
            batch_axes=("data",), act_spec=None, remat: bool = True):
    """Next-token LM loss.  batch: tokens (B,S), targets (B,S), mask (B,S),
    plus 'patches' (vlm) or 'frames' (audio)."""
    x = _prepare_inputs(cfg, params, batch)
    B, S = batch["tokens"].shape
    sin, cos = make_rope(jnp.arange(S), cfg.hd, cfg.rope_theta)
    common = dict(sin=sin[None], cos=cos[None], mesh=mesh,
                  batch_axes=batch_axes, lengths=None, q_offset=0,
                  act_spec=act_spec)
    if cfg.family == "audio":
        enc = _encode_audio(cfg, params, batch["frames"], remat=remat)
        cross_kv = _cross_kv_from_encoder(cfg, params, enc)
        x, _, aux = _run_stack_audio(
            cfg, params, x, mode="train", cache=None, common=common,
            cross_kv=cross_kv,
            enc_lengths=batch.get("enc_lengths"), remat=remat)
    else:
        x, _, aux = _run_stack(cfg, params, x, mode="train", cache=None,
                               common=common, remat=remat)
    loss = _chunked_lm_loss(cfg, params, x, batch["targets"],
                            batch.get("mask"))
    return loss + cfg.router_aux_weight * aux


def prefill_fn(cfg: ModelConfig, params, batch, *, max_len: int,
               mesh=None, batch_axes=("data",), act_spec=None,
               remat: bool = True):
    """Prefill: run the prompt, build the decode cache.

    batch: tokens (B, S), lengths (B,) true prompt lengths; returns
    (last_logits (B, V), cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    lengths = batch.get("lengths",
                        jnp.full((B,), S, jnp.int32)).astype(jnp.int32)
    x = _prepare_inputs(cfg, params, batch)
    sin, cos = make_rope(jnp.arange(S), cfg.hd, cfg.rope_theta)
    common = dict(sin=sin[None], cos=cos[None], mesh=mesh,
                  batch_axes=batch_axes, lengths=lengths, q_offset=0,
                  act_spec=act_spec)
    cache = init_cache(cfg, B, max_len)
    if cfg.family == "audio":
        enc = _encode_audio(cfg, params, batch["frames"], remat=remat)
        cross_kv = _cross_kv_from_encoder(cfg, params, enc)
        cache["cross_kv"] = cross_kv
        x, nc, _ = _run_stack_audio(
            cfg, params, x, mode="prefill", cache=cache, common=common,
            cross_kv=cross_kv, enc_lengths=cache["enc_lengths"], remat=remat)
    else:
        x, nc, _ = _run_stack(cfg, params, x, mode="prefill", cache=cache,
                              common=common, remat=remat)
    for k, v in nc.items():
        cache[k] = v
    cache["lengths"] = lengths
    # logits at the last valid position of each row
    idx = jnp.clip(lengths - 1, 0, S - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return _lm_logits(cfg, params, x_last), cache


# ==========================================================================
# Chunked prefill + paged decode (attention-family stacks)
#
# These paths serve the pluggable cache backends of
# :mod:`repro.serving.cache_backend`:
#
#   * ``chunk_prefill_fn``       — process ONE prompt chunk per row against
#     contiguous per-slot cache rows (slot backend, chunked prefill);
#   * ``paged_decode_fn``        — one-token decode reading/writing KV
#     through a vLLM-style block table over a shared pool;
#   * ``paged_chunk_prefill_fn`` — prompt chunks over the paged pool.
#
# All three support only homogeneous attention stacks (dense / moe / vlm
# decoders: ``layer_pattern == [("blocks", "attn", n)]``); SSM/hybrid
# recurrences and audio cross-attention carry extra cache state that has
# no paged layout yet.
# ==========================================================================

def supports_paged_stack(cfg: ModelConfig) -> bool:
    """True iff the decoder is a single homogeneous attention stack whose
    KV cache is pure (k, v) pairs — the families the chunked/paged serving
    paths can drive."""
    return (cfg.family in ("dense", "moe", "vlm")
            and not cfg.sliding_window
            and layer_pattern(cfg) == [("blocks", "attn", cfg.n_layers)])


def _require_paged_stack(cfg: ModelConfig, what: str) -> None:
    if not supports_paged_stack(cfg):
        raise ValueError(
            f"{what} supports only attention-family models without a "
            f"sliding window (dense/moe/vlm), got family={cfg.family!r} "
            f"sliding_window={cfg.sliding_window}")


def _chunk_qkv(cfg: ModelConfig, p, xx, sin, cos):
    """Pre-attention half of an attn block: norm + q/k/v projection + rope."""
    h = rms_norm(xx, p["norm1"], cfg.norm_eps)
    ap = p["attn"]
    q = apply_rope(_head_norm(cfg, ap, "q_norm",
                              linear(h, ap["wq"], ap.get("bq"))), sin, cos)
    k = apply_rope(_head_norm(cfg, ap, "k_norm",
                              linear(h, ap["wk"], ap.get("bk"))), sin, cos)
    v = linear(h, ap["wv"], ap.get("bv"))
    return q, k, v


def _chunk_finish(cfg: ModelConfig, p, xx, o, *, valid, mesh, batch_axes):
    """Post-attention half: output projection + (MoE-)FFN residual.
    Returns (xx, counters) as ``_ffn``."""
    ap = p["attn"]
    xx = xx + linear(o.reshape(o.shape[:-2] + (-1,)),
                     ap["wo"].reshape(-1, cfg.d_model))
    h2 = rms_norm(xx, p["norm2"], cfg.norm_eps)
    y, counters = _ffn(cfg, p, h2, valid=valid, mesh=mesh,
                       batch_axes=batch_axes)
    return xx + y, counters


def _serve_blocks(cfg: ModelConfig, blocks):
    """(xs, unpack): the stacked blocks as a serving scan's xs, and the
    function that makes one step's slice a layer's params.  An expert
    stack keeps its experts' weights out of the xs, whole, and the scan
    carries each layer's index in their place: the grouped matmul then
    reads the layer's hit experts in place, where a per-layer slice of
    the stack is a copy of all of them.  Dense blocks pass unchanged."""
    if not cfg.is_moe:
        return blocks, lambda p: p
    ffn = blocks["ffn"]
    experts = {n: ffn[n] for n in ("w1", "w3", "w2")}
    rest = dict(blocks, ffn={"router": ffn["router"]})

    def unpack(xs):
        p, layer = xs
        return dict(p, ffn=dict(p["ffn"], layer=layer, **experts))

    layers = jnp.arange(ffn["router"].shape[0], dtype=jnp.int32)
    return (rest, layers), unpack


def _layer_sums(counters):
    """A scan's per-layer expert counters (layers, 3) summed over the
    layers; None for a dense stack."""
    return None if counters is None else counters.sum(0)


def chunk_prefill_fn(cfg: ModelConfig, params, cache, tokens, offsets,
                     chunk_lens, *, mesh=None, batch_axes=("data",)):
    """Incremental prefill: run ONE chunk of each row's prompt against its
    (already partially filled) contiguous cache row.

    cache: gathered per-row cache slices {"lengths", "blocks": {"k", "v"}}
    with k/v of shape (layers, n, L, Hkv, hd); tokens: (n, C) right-padded
    chunk tokens; offsets: (n,) absolute position of each row's chunk
    start; chunk_lens: (n,) valid tokens in this chunk (0 marks a padding
    row — its cache writes are dropped and its logits are garbage).

    Returns (last_logits (n, V) at each row's final chunk position,
    updated cache slices with ``lengths = offsets + chunk_lens``).
    """
    _require_paged_stack(cfg, "chunk_prefill_fn")
    n, C = tokens.shape
    x = _embed_tokens(cfg, params, tokens)
    posmat = offsets[:, None] + jnp.arange(C)[None, :]          # (n, C)
    sin, cos = make_rope(posmat, cfg.hd, cfg.rope_theta)
    kv_len = offsets + chunk_lens
    L = cache["blocks"]["k"].shape[2]
    # padding columns scatter to position L -> dropped by JAX semantics
    wpos = jnp.where(jnp.arange(C)[None, :] < chunk_lens[:, None],
                     posmat, L)
    bidx = jnp.arange(n)

    def valid():
        return jnp.arange(C)[None, :] < chunk_lens[:, None]

    blocks, unpack = _serve_blocks(cfg, params["blocks"])

    def layer(xx, xs):
        p, c = xs
        p = unpack(p)
        q, k, v = _chunk_qkv(cfg, p, xx, sin, cos)
        kc = c["k"].at[bidx[:, None], wpos].set(k.astype(c["k"].dtype))
        vc = c["v"].at[bidx[:, None], wpos].set(v.astype(c["v"].dtype))
        o = attn_lib.chunk_attention(q, kc, vc, q_pos=posmat, kv_len=kv_len)
        xx, _ = _chunk_finish(cfg, p, xx, o, valid=valid, mesh=mesh,
                              batch_axes=batch_axes)
        return xx, {"k": kc, "v": vc}

    x, ncs = jax.lax.scan(layer, x, (blocks, cache["blocks"]))
    idx = jnp.clip(chunk_lens - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    new_cache = dict(cache)
    new_cache["blocks"] = ncs
    new_cache["lengths"] = kv_len.astype(jnp.int32)
    return _lm_logits(cfg, params, x_last), new_cache


def _paged_contiguous(pool, bt, n, L):
    """Gather a request-major contiguous view (n, L, Hkv, hd) of a paged
    pool (n_blocks, block, Hkv, hd) through clipped block tables."""
    return pool[bt].reshape(n, L, *pool.shape[2:])


def paged_decode_fn(cfg: ModelConfig, params, k_pool, v_pool, tables,
                    lengths, blk, off, tokens, *, block_size: int,
                    attn_impl: str = "gather", mesh=None,
                    batch_axes=("data",)):
    """One decode step over a paged KV cache (vLLM block tables).

    k_pool/v_pool: (layers, n_blocks, block, Hkv, hd); tables: (n,
    max_blocks) int32 (-1 = unallocated); lengths: (n,) already counting
    the new token; blk/off: (n,) physical (block, offset) of the new
    token's KV (callers pass ``blk == n_blocks`` for padding rows, whose
    writes are then dropped).  tokens: (n,) int32.

    attn_impl:
      * ``"gather"`` (default) — materialize each row's blocks as a
        contiguous view and reuse :func:`attention.decode_attention`.
        Because masked positions contribute exactly zero, this is
        bit-identical to the contiguous slot cache whenever
        ``max_blocks * block_size`` equals the slot cache length — the
        parity oracle the engine tests rely on.
      * ``"ref"``    — :func:`repro.serving.paged_cache
        .paged_decode_attention_ref`, the standalone jnp oracle.
      * ``"pallas"`` — the TPU kernel
        (:mod:`repro.kernels.paged_attention`), which streams physical
        blocks via scalar-prefetched block tables and never materializes
        the contiguous view.

    Returns (next_tokens (n,) int32 greedy, k_pool, v_pool, counters):
    counters (3,) int32, the expert layers' ``moe.MOE_COUNTERS`` summed
    over layers (padding rows, ``lengths == 0``, route nowhere); None
    for a dense model.
    """
    _require_paged_stack(cfg, "paged_decode_fn")
    if attn_impl not in ("gather", "ref", "pallas"):
        raise ValueError(f"unknown attn_impl {attn_impl!r}")
    n = tokens.shape[0]
    x = _embed_tokens(cfg, params, tokens[:, None])
    pos = lengths - 1
    sin, cos = make_rope(pos[:, None], cfg.hd, cfg.rope_theta)
    nb_pool = k_pool.shape[1]
    bt = jnp.clip(tables, 0, nb_pool - 1)
    L = tables.shape[1] * block_size

    blocks, unpack = _serve_blocks(cfg, params["blocks"])

    def layer(xx, xs):
        p, kp, vp = xs
        p = unpack(p)
        q, k, v = _chunk_qkv(cfg, p, xx, sin, cos)
        kp = kp.at[blk, off].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[blk, off].set(v[:, 0].astype(vp.dtype))
        if attn_impl == "pallas":
            from ..kernels.paged_attention import \
                paged_decode_attention_pallas
            o = paged_decode_attention_pallas(
                q[:, 0], kp, vp, tables, lengths,
                block_size=block_size)[:, None]
        elif attn_impl == "ref":
            from ..serving.paged_cache import paged_decode_attention_ref
            o = paged_decode_attention_ref(
                q[:, 0], kp, vp, tables, lengths, block_size)[:, None]
        else:
            kc = _paged_contiguous(kp, bt, n, L)
            vc = _paged_contiguous(vp, bt, n, L)
            o = attn_lib.decode_attention(q[:, 0], kc, vc, lengths)[:, None]
        xx, counters = _chunk_finish(cfg, p, xx, o,
                                     valid=lambda: lengths[:, None] > 0,
                                     mesh=mesh, batch_axes=batch_axes)
        return xx, (kp, vp, counters)

    x, (kps, vps, counters) = jax.lax.scan(
        layer, x, (blocks, k_pool, v_pool))
    nxt = jnp.argmax(_lm_logits(cfg, params, x[:, 0]), -1).astype(jnp.int32)
    return nxt, kps, vps, _layer_sums(counters)


def paged_chunk_prefill_fn(cfg: ModelConfig, params, k_pool, v_pool, tables,
                           tokens, offsets, chunk_lens, wblk, woff, *,
                           block_size: int, mesh=None,
                           batch_axes=("data",)):
    """Chunked prefill over the paged pool: scatter each chunk's KV into
    the rows' blocks, then attend through a gathered contiguous view.

    wblk/woff: (n, C) physical (block, offset) of every chunk token
    (padding columns carry ``wblk == n_blocks`` -> dropped writes).
    Returns (last_logits (n, V), k_pool, v_pool, counters): counters as
    :func:`paged_decode_fn`'s, over the columns within ``chunk_lens``.
    """
    _require_paged_stack(cfg, "paged_chunk_prefill_fn")
    n, C = tokens.shape
    x = _embed_tokens(cfg, params, tokens)
    posmat = offsets[:, None] + jnp.arange(C)[None, :]
    sin, cos = make_rope(posmat, cfg.hd, cfg.rope_theta)
    kv_len = offsets + chunk_lens
    nb_pool = k_pool.shape[1]
    bt = jnp.clip(tables, 0, nb_pool - 1)
    L = tables.shape[1] * block_size

    blocks, unpack = _serve_blocks(cfg, params["blocks"])

    def layer(xx, xs):
        p, kp, vp = xs
        p = unpack(p)
        q, k, v = _chunk_qkv(cfg, p, xx, sin, cos)
        kp = kp.at[wblk, woff].set(k.astype(kp.dtype))
        vp = vp.at[wblk, woff].set(v.astype(vp.dtype))
        kc = _paged_contiguous(kp, bt, n, L)
        vc = _paged_contiguous(vp, bt, n, L)
        o = attn_lib.chunk_attention(q, kc, vc, q_pos=posmat, kv_len=kv_len)
        xx, counters = _chunk_finish(
            cfg, p, xx, o,
            valid=lambda: jnp.arange(C)[None, :] < chunk_lens[:, None],
            mesh=mesh, batch_axes=batch_axes)
        return xx, (kp, vp, counters)

    x, (kps, vps, counters) = jax.lax.scan(
        layer, x, (blocks, k_pool, v_pool))
    idx = jnp.clip(chunk_lens - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return _lm_logits(cfg, params, x_last), kps, vps, _layer_sums(counters)


def decode_fn(cfg: ModelConfig, params, cache, tokens, *, mesh=None,
              batch_axes=("data",), kv_shard="none"):
    """One decode step.  tokens: (B,) int32 — the tokens sampled last step.
    Returns (logits (B, V), new cache)."""
    B = tokens.shape[0]
    lengths = cache["lengths"] + 1
    x = _embed_tokens(cfg, params, tokens[:, None])
    pos = lengths - 1
    sin, cos = make_rope(pos[:, None], cfg.hd, cfg.rope_theta)  # (B,1,hd/2)
    rolling = bool(cfg.sliding_window)
    common = dict(sin=sin, cos=cos, mesh=mesh, batch_axes=batch_axes,
                  lengths=lengths, q_offset=0, rolling=rolling,
                  kv_shard=kv_shard)
    new_cache = dict(cache)
    if cfg.family == "audio":
        x, nc, _ = _run_stack_audio(
            cfg, params, x, mode="decode", cache=cache, common=common,
            cross_kv=cache["cross_kv"], enc_lengths=cache["enc_lengths"],
            remat=False)
    else:
        x, nc, _ = _run_stack(cfg, params, x, mode="decode", cache=cache,
                              common=common, remat=False)
    for k, v in nc.items():
        new_cache[k] = v
    new_cache["lengths"] = lengths
    return _lm_logits(cfg, params, x[:, 0]), new_cache
