"""Operations and bytes of the serving path, from a configuration's shapes.

Weights are served in bfloat16 and the norms' scales in float32, as the
program stores them (``spec.W_BYTES`` and the rest).  What differs by
architecture (a layer's matrices, the head, the weights a decode step
reads, the cache a token keeps, attention's operations) comes from
``archs/<model_type>.py`` (``system.arch``); this module composes it
into a step's count.  Counts are of useful work: rows of a step's bucket
that are padding, and key/value positions past a row's length, are not
counted.
"""
from __future__ import annotations

from .spec import KV_BYTES, head_dim
from .system import arch


def decode_step_flops(cfg: dict, ctx: list) -> float:
    """A decode step over rows whose KV lengths are ``ctx``."""
    a, n = arch(cfg), cfg["num_hidden_layers"]
    matmul = 2 * (n * a.layer_matrix_params(cfg) + a.head_params(cfg))
    attn = n * sum(a.attention_flops(cfg, c) for c in ctx)
    return float(matmul * len(ctx) + attn)


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Matrix work of ``tokens`` prompt tokens through every layer (the
    head runs once per row and is left out, as is attention)."""
    return float(2 * cfg["num_hidden_layers"]
                 * arch(cfg).layer_matrix_params(cfg) * tokens)


def decode_step_bytes(cfg: dict, ctx: list) -> float:
    """The weights the step's rows read, plus the live keys and values of
    each row."""
    a = arch(cfg)
    return float(a.decode_weight_bytes(cfg, len(ctx))
                 + a.kv_bytes_per_token(cfg) * sum(ctx))


def paged_attn_call(cfg: dict, ctx: list) -> tuple[float, float]:
    """(flops, bytes) of one layer's paged-attention call over rows of KV
    lengths ``ctx``: live keys and values, and q in and out."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    a = arch(cfg)
    flops = sum(a.attention_flops(cfg, c) for c in ctx)
    kv = sum(2 * c * hkv * hd * KV_BYTES for c in ctx)
    q_out = len(ctx) * 2 * hq * hd * KV_BYTES
    return float(flops), float(kv + q_out)


def least_seconds(flops: float, nbytes: float, peak) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak.flops_per_s, nbytes / peak.hbm_bytes_per_s)
