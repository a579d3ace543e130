"""Plain float32 reference of a dense decoder, for ``correct``.

A pre-norm decoder as the configuration file states it (Mistral and
Llama, ``transformers``' ``MistralForCausalLM``): RMSNorm (scales of
ones), grouped-query attention with rotary positions on whole heads
(rotate-half) and no biases, causal softmax in float32, a SwiGLU MLP,
an untied head.  Plain ``jax.numpy`` at
``Precision.HIGHEST``, one layer at a time so that it fits beside
nothing else at full width; no kernel, cache or batching of requests.

Weights: the configuration serves random bfloat16 weights drawn from
``--seed``.  This module draws the same values from the seed itself,
following the recipe the configuration's weights are made by (a
truncated normal of scale 1/sqrt(fan-in) per matrix, a normal embedding
over sqrt(hidden); the key tree below), and computes with them in
float32.  It imports nothing of the program and takes nothing it made.
``weights`` gives them whole, in the program's parameter tree, for the
test that holds the two draws equal.

``served_gaps``: for each request (prompt, served tokens), run the whole
sequence once and read, at every served position, how far the served
token's logit lies below the best logit there.  ``control_gaps`` reads
the same for the token that the reference computed in float8 (e4m3,
scaled per row, per output column and per key/value head) puts first:
the control that must come out as not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import head_dim

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e30
_F8_MAX = 448.0          # largest float8_e4m3fn


# ----------------------------------------------------------------------
# weights from the seed
# ----------------------------------------------------------------------

def _tnormal(key, shape, fan_in):
    scale = 1.0 / np.sqrt(fan_in)
    v = scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                            dtype=jnp.float32)
    return v.astype(jnp.bfloat16).astype(jnp.float32)


def _root_keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 16)


@functools.partial(jax.jit, static_argnames=("shape",))
def _layer_weights(layer_key, shape):
    d, hq, hkv, hd, f = shape
    k_attn, k_mlp = jax.random.split(layer_key)
    ka = jax.random.split(k_attn, 8)
    km = jax.random.split(k_mlp, 3)
    w = dict(wq=_tnormal(ka[0], (d, hq, hd), d),
             wk=_tnormal(ka[1], (d, hkv, hd), d),
             wv=_tnormal(ka[2], (d, hkv, hd), d),
             wo=(1.0 / np.sqrt(hq * hd) * jax.random.truncated_normal(
                 ka[3], -2.0, 2.0, (hq, hd, d), dtype=jnp.float32)
                 ).astype(jnp.bfloat16).astype(jnp.float32),
             w_in=_tnormal(km[0], (d, f), d),
             w_gate=_tnormal(km[1], (d, f), d),
             w_out=_tnormal(km[2], (f, d), f))
    return w


def _layer_keys(seed: int, n_layers: int):
    return jax.random.split(jax.random.fold_in(_root_keys(seed)[2], 0),
                            n_layers)


@functools.partial(jax.jit, static_argnames=("vocab", "d"))
def _embed_table(key, vocab, d):
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32)
            / np.sqrt(d)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("vocab", "d"))
def _head(key, vocab, d):
    return _tnormal(key, (d, vocab), d)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _q8(a, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "q_block"))
def _layer(x, w, dims, fp8: bool, q_block: int):
    """One decoder layer over one sequence ``x`` (S, d)."""
    eps, theta = dims
    S = x.shape[0]
    hkv = w["wk"].shape[1]
    hq, hd = w["wq"].shape[1], w["wq"].shape[2]
    g = hq // hkv
    qa = (lambda a: _q8(a, -1)) if fp8 else (lambda a: a)
    qw = (lambda a: _q8(a, 0)) if fp8 else (lambda a: a)

    def mm(a, b, spec):
        return jnp.einsum(spec, qa(a), qw(b), precision=_HI)

    pos = jnp.arange(S)
    h = _rms(x, eps)
    q = _rope(mm(h, w["wq"], "sd,dhk->shk"), pos, theta)
    k = _rope(mm(h, w["wk"], "sd,dhk->shk"), pos, theta)
    v = mm(h, w["wv"], "sd,dhk->shk")
    if fp8:
        q, k, v = qa(q), qa(k), qa(v)
    q = q.reshape(S, hkv, g, hd) / np.sqrt(hd)
    outs = []
    for b0 in range(0, S, q_block):
        qb = q[b0:b0 + q_block]
        s = jnp.einsum("qhgk,thk->hgqt", qb, k, precision=_HI)
        causal = pos[None, :] <= (b0 + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where(causal[None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hgqt,thk->qhgk", p, v, precision=_HI))
    o = jnp.concatenate(outs, 0).reshape(S, hq * hd)
    x = x + mm(o, w["wo"].reshape(hq * hd, -1), "sk,kd->sd")
    h2 = _rms(x, eps)
    a = jax.nn.silu(mm(h2, w["w_gate"], "sd,df->sf")) \
        * mm(h2, w["w_in"], "sd,df->sf")
    return x + mm(a, w["w_out"], "sf,fd->sd")


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _block_logits(x, idx, head, eps, fp8: bool):
    """Logits at positions ``idx`` of a final hidden state ``x`` (with
    ``fp8``, the head comes already rounded by ``_q8(head, 0)``)."""
    h = _rms(x[idx], eps)
    if fp8:
        h = _q8(h, -1)
    return jnp.einsum("pd,dv->pv", h, head, precision=_HI)


@functools.partial(jax.jit, static_argnames=("eps",))
def _block_gaps(x, idx, tok, head, eps):
    lg = _block_logits(x, idx, head, eps, False)
    return lg.max(-1) - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnames=("eps",))
def _block_argmax(x, idx, head, eps):
    return _block_logits(x, idx, head, eps, True).argmax(-1)


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], head_dim(cfg),
            cfg["intermediate_size"])


def _check(cfg: dict) -> None:
    ok = (cfg["model_type"] in ("mistral", "llama")
          and cfg["hidden_act"] == "silu"
          and cfg.get("sliding_window") is None
          and cfg.get("rope_scaling") is None
          and not cfg.get("attention_bias", False)
          and not cfg.get("mlp_bias", False)
          and not cfg["tie_word_embeddings"])
    if not ok:
        raise ValueError(f"dense_decoder has no path for {cfg['name']}")


def weights(cfg: dict, seed: int) -> dict:
    """The float32 weights this module draws from ``seed``, whole and in
    the program's parameter tree (each block's leaves stacked over
    layers); for tests at a small size, since the forward draws one
    layer at a time."""
    _check(cfg)
    d, n, vocab = (cfg["hidden_size"], cfg["num_hidden_layers"],
                   cfg["vocab_size"])
    keys = _root_keys(seed)
    layers = [_layer_weights(lk, _dims(cfg)) for lk in _layer_keys(seed, n)]
    attn = ("wq", "wk", "wv", "wo")

    def stacked(names):
        return {k: jnp.stack([w[k] for w in layers]) for k in names}

    return {"embed": _embed_table(keys[0], vocab, d).astype(jnp.float32),
            "lm_head": _head(keys[1], vocab, d),
            "final_norm": jnp.ones((d,), jnp.float32),
            "blocks": {"attn": stacked(attn),
                       "ffn": stacked(layers[0].keys() - set(attn)),
                       "norm1": jnp.ones((n, d), jnp.float32),
                       "norm2": jnp.ones((n, d), jnp.float32)}}


def _padded(n: int, limit: int, step: int = 1024) -> int:
    """Sequences run at a few padded lengths, so few programs compile."""
    return min(limit, -(-n // min(step, limit)) * min(step, limit))


def final_hidden(cfg: dict, seed: int, sample: list, *, fp8: bool = False,
                 q_block: int = 1024) -> list:
    """Last layer's hidden states of each request's sequence.

    ``sample``: (prompt, served) pairs.  The sequence run is the prompt
    and every served token but the last, padded to a multiple of 1,024
    (at most the engine's ``max_seq_len``); position ``p`` predicts
    token ``p + 1``."""
    _check(cfg)
    limit = cfg["engine"]["max_seq_len"]
    dims = (cfg["rms_norm_eps"], float(cfg["rope_theta"]))
    keys = _root_keys(seed)
    table = _embed_table(keys[0], cfg["vocab_size"], cfg["hidden_size"])
    xs = []
    for prompt, served in sample:
        ids = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        if ids.size > limit:
            raise ValueError(f"sequence of {ids.size} > max_seq_len {limit}")
        ids = np.pad(ids, (0, _padded(ids.size, limit) - ids.size))
        x = table[jnp.asarray(ids)].astype(jnp.float32)
        xs.append(_q8(x, -1) if fp8 else x)
    del table
    shape = _dims(cfg)
    for lk in _layer_keys(seed, cfg["num_hidden_layers"]):
        w = _layer_weights(lk, shape)
        xs = [_layer(x, w, dims, fp8, min(q_block, x.shape[0])) for x in xs]
        del w
    return xs


def _served_blocks(prompt, served, block: int):
    """Positions predicting each served token, and the tokens, padded to
    whole blocks (the padding is cut off after)."""
    n = served.size
    m = -(-n // block) * block
    idx = np.minimum(prompt.size - 1 + np.arange(m), prompt.size - 1 + n - 1)
    tok = np.pad(served, (0, m - n))
    return idx.astype(np.int32), tok.astype(np.int32), n


def served_gaps(cfg: dict, seed: int, sample: list,
                block: int = 256) -> list:
    """Per request: at each served position, the best reference logit
    minus the served token's reference logit (>= 0)."""
    xs = final_hidden(cfg, seed, sample)
    head = _head(_root_keys(seed)[1], cfg["vocab_size"], cfg["hidden_size"])
    eps = cfg["rms_norm_eps"]
    out = []
    for x, (prompt, served) in zip(xs, sample):
        idx, tok, n = _served_blocks(prompt, served, block)
        gaps = [_block_gaps(x, jnp.asarray(idx[b:b + block]),
                            jnp.asarray(tok[b:b + block]), head, eps)
                for b in range(0, idx.size, block)]
        out.append(np.asarray(jnp.concatenate(gaps))[:n])
    return out


def control_gaps(cfg: dict, seed: int, sample: list,
                 block: int = 256) -> list:
    """As ``served_gaps``, for the token that the float8 reference puts
    first at each served position, judged by the float32 reference."""
    xs = final_hidden(cfg, seed, sample)
    xs8 = final_hidden(cfg, seed, sample, fp8=True)
    head = _head(_root_keys(seed)[1], cfg["vocab_size"], cfg["hidden_size"])
    head8 = jax.jit(_q8, static_argnums=1)(head, 0)
    eps = cfg["rms_norm_eps"]
    out = []
    for x, x8, (prompt, served) in zip(xs, xs8, sample):
        idx, _, n = _served_blocks(prompt, served, block)
        gaps = []
        for b in range(0, idx.size, block):
            i = jnp.asarray(idx[b:b + block])
            tok = _block_argmax(x8, i, head8, eps)
            gaps.append(_block_gaps(x, i, tok, head, eps))
        out.append(np.asarray(jnp.concatenate(gaps))[:n])
    return out
