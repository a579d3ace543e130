"""Plain float32 reference of a sparse-expert decoder, for ``correct``.

Qwen3-MoE as ``transformers``' ``Qwen3MoeForCausalLM`` writes it, for
the configuration file's shapes:

* attention: ``h = RMSNorm(x)``; ``q = RMSNorm_hd(h Wq)``, ``k =
  RMSNorm_hd(h Wk)`` per head over ``head_dim`` (QK-norm, each with its
  own scale), ``v = h Wv``; rotary positions on whole heads
  (rotate-half); causal grouped-query softmax at ``1/sqrt(head_dim)``
  in float32; ``x += o Wo``;
* experts: ``h = RMSNorm(x)``, ``p = softmax(h Wg)`` over all
  ``router_experts`` router outputs in float32, the top
  ``num_experts_per_tok`` of ``p`` renormalised to sum to 1; each chosen
  expert computes ``down(silu(gate(h)) * up(h))`` and ``x +=`` their
  weighted sum; no shared expert, no dense layer;
* a final RMSNorm and an untied head.

Plain ``jax.numpy`` at ``Precision.HIGHEST``, one layer at a time; no
kernel, cache or batching of requests, and every held expert computed
densely for every token, weighted by its gate (zero where not chosen).

Departures from the published model, as the configuration states them:

* **held experts**: the file's ``num_experts`` experts from
  ``expert_offset`` are the ones computed; the router still has all
  ``router_experts`` outputs and picks its top-k over all of them, and
  what the other experts would add is left out, as on the chip that
  holds this share (the program does the same);
* **depth**: the file's ``num_hidden_layers``, the first pipeline stage;
* **embedding and head**: both present, so that this is a whole forward
  pass from tokens to logits, though the deployment's first stage would
  hand its hidden state to the second;
* norm, q-norm and k-norm scales are ones (the program's initial
  weights), and the weights are random.

Weights: drawn from the seed by the program's key tree (``weights``
gives them whole, in the program's parameter tree, for the test that
holds the two draws equal); expert ``e`` of a layer from
``fold_in(key, e)``.  Imports nothing of the program.

``served_gaps`` and ``control_gaps`` as ``dense_decoder`` has them: the
control is this forward computed in float8 (e4m3, scaled per row, per
output column and per key/value head), routing included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.dense_decoder import (_HI, _NEG, _block_argmax,
                                            _block_gaps, _embed_table, _head,
                                            _padded, _q8, _rms, _rope,
                                            _root_keys, _served_blocks,
                                            _tnormal)
from bench.spec import head_dim


def _check(cfg: dict) -> None:
    ok = (cfg["model_type"] == "qwen3_moe"
          and cfg["hidden_act"] == "silu"
          and cfg.get("norm_topk_prob") is True
          and cfg.get("decoder_sparse_step", 1) == 1
          and not cfg.get("mlp_only_layers")
          and cfg.get("sliding_window") is None
          and cfg.get("rope_scaling") is None
          and not cfg.get("attention_bias", False)
          and not cfg["tie_word_embeddings"])
    if not ok:
        raise ValueError(f"moe_decoder has no path for {cfg['name']}")


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], head_dim(cfg),
            cfg["moe_intermediate_size"], cfg["router_experts"],
            cfg.get("expert_offset", 0), cfg["num_experts"])


@functools.partial(jax.jit, static_argnames=("shape",))
def _layer_weights(layer_key, shape):
    d, hq, hkv, hd, f, E, e0, held = shape
    k_attn, k_mlp = jax.random.split(layer_key)
    ka = jax.random.split(k_attn, 8)
    km = jax.random.split(k_mlp, 4)
    ids = jnp.arange(e0, e0 + held)

    def experts(key, shape, fan_in):
        return jax.vmap(lambda e: _tnormal(jax.random.fold_in(key, e),
                                           shape, fan_in))(ids)

    return dict(
        wq=_tnormal(ka[0], (d, hq, hd), d),
        wk=_tnormal(ka[1], (d, hkv, hd), d),
        wv=_tnormal(ka[2], (d, hkv, hd), d),
        wo=(1.0 / np.sqrt(hq * hd) * jax.random.truncated_normal(
            ka[3], -2.0, 2.0, (hq, hd, d), dtype=jnp.float32)
            ).astype(jnp.bfloat16).astype(jnp.float32),
        # the router is kept in float32 (no bfloat16 rounding)
        router=1.0 / np.sqrt(d) * jax.random.truncated_normal(
            km[0], -2.0, 2.0, (d, E), dtype=jnp.float32),
        w1=experts(km[1], (d, f), d),
        w3=experts(km[2], (d, f), d),
        w2=experts(km[3], (f, d), f))


def _layer_keys(seed: int, n_layers: int):
    return jax.random.split(jax.random.fold_in(_root_keys(seed)[2], 0),
                            n_layers)


def expert_gates(h, router, k: int, e0: int, held: int):
    """(S, held) gate of each held expert for each token: its
    renormalised top-k probability, zero where not chosen."""
    p = jax.nn.softmax(jnp.einsum("sd,de->se", h, router, precision=_HI),
                       axis=-1)
    top_p, top_i = jax.lax.top_k(p, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(top_i - e0, held, dtype=jnp.float32)
    return jnp.einsum("sk,ske->se", top_p, chosen, precision=_HI)


def _same(a):
    return a


def expert_layer(h, w, k: int, e0: int, qa=_same, qw=_same, qe=_same):
    """The held experts' part of one expert layer over ``h`` (S, d).
    ``qa`` rounds activations, ``qw`` the router, ``qe`` the experts'
    matrices (the float8 control); identity by default."""
    gates = expert_gates(qa(h), qw(w["router"]), k, e0, w["w1"].shape[0])
    a = jnp.einsum("sd,edf->esf", qa(h), qe(w["w1"]), precision=_HI)
    b = jnp.einsum("sd,edf->esf", qa(h), qe(w["w3"]), precision=_HI)
    mid = jax.nn.silu(b) * a * gates.T[:, :, None]
    return jnp.einsum("esf,efd->sd", qa(mid), qe(w["w2"]), precision=_HI)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "q_block"))
def _layer(x, w, dims, fp8: bool, q_block: int):
    """One decoder layer over one sequence ``x`` (S, d)."""
    eps, theta, k_top, e0 = dims
    S = x.shape[0]
    hkv = w["wk"].shape[1]
    hq, hd = w["wq"].shape[1], w["wq"].shape[2]
    g = hq // hkv
    qa = (lambda a: _q8(a, -1)) if fp8 else _same
    qw = (lambda a: _q8(a, 0)) if fp8 else _same
    qe = (lambda a: _q8(a, 1)) if fp8 else _same

    def mm(a, b, spec):
        return jnp.einsum(spec, qa(a), qw(b), precision=_HI)

    pos = jnp.arange(S)
    h = _rms(x, eps)
    q = _rope(_rms(mm(h, w["wq"], "sd,dhk->shk"), eps), pos, theta)
    k = _rope(_rms(mm(h, w["wk"], "sd,dhk->shk"), eps), pos, theta)
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
    return x + expert_layer(_rms(x, eps), w, k_top, e0, qa, qw, qe)


def weights(cfg: dict, seed: int) -> dict:
    """The float32 weights this module draws from ``seed``, whole and in
    the program's parameter tree (each block's leaves stacked over
    layers); for tests at a small size."""
    _check(cfg)
    d, n, vocab = (cfg["hidden_size"], cfg["num_hidden_layers"],
                   cfg["vocab_size"])
    hd = head_dim(cfg)
    keys = _root_keys(seed)
    layers = [_layer_weights(lk, _dims(cfg)) for lk in _layer_keys(seed, n)]

    def stacked(names):
        return {k: jnp.stack([w[k] for w in layers]) for k in names}

    attn = stacked(("wq", "wk", "wv", "wo"))
    attn["q_norm"] = attn["k_norm"] = jnp.ones((n, hd), jnp.float32)
    return {"embed": _embed_table(keys[0], vocab, d).astype(jnp.float32),
            "lm_head": _head(keys[1], vocab, d),
            "final_norm": jnp.ones((d,), jnp.float32),
            "blocks": {"attn": attn,
                       "ffn": stacked(("router", "w1", "w3", "w2")),
                       "norm1": jnp.ones((n, d), jnp.float32),
                       "norm2": jnp.ones((n, d), jnp.float32)}}


def final_hidden(cfg: dict, seed: int, sample: list, *, fp8: bool = False,
                 q_block: int = 1024) -> list:
    """Last layer's hidden states of each request's sequence, as
    ``dense_decoder.final_hidden`` (prompt and every served token but the
    last, padded to a multiple of 1,024; position ``p`` predicts token
    ``p + 1``).  Padding positions come after every real one, so the
    causal mask keeps them out of the real positions' results."""
    _check(cfg)
    limit = cfg["engine"]["max_seq_len"]
    dims = (cfg["rms_norm_eps"], float(cfg["rope_theta"]),
            cfg["num_experts_per_tok"], cfg.get("expert_offset", 0))
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
