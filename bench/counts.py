"""Operations and bytes of the serving path, from a configuration's shapes.

Weights are served in bfloat16 (2 bytes) and the norms' scales in float32
(4 bytes), as the program stores them.  A decode step streams every
weight except the embedding table, of which it reads only the rows of
its tokens.  Counts are of useful work: rows of a step's bucket that are
padding, and key/value positions past a row's length, are not counted.
"""
from __future__ import annotations

from .spec import head_dim

KV_BYTES = 2          # bfloat16 keys and values
W_BYTES = 2           # bfloat16 matrices
NORM_BYTES = 4        # float32 norm scales


def layer_matrix_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    return 2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * f


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def streamed_weight_bytes(cfg: dict) -> int:
    """Bytes of every weight a decode step reads: all but the embedding
    table (untied head assumed)."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    layers = n * (layer_matrix_params(cfg) * W_BYTES + 2 * d * NORM_BYTES)
    return layers + head_params(cfg) * W_BYTES + d * NORM_BYTES


def kv_bytes_per_token(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * head_dim(cfg) * KV_BYTES)


def attention_flops(cfg: dict, ctx: int) -> int:
    """One query token of one layer against ``ctx`` keys: q.k and p.v."""
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) * ctx


def decode_step_flops(cfg: dict, ctx: list) -> float:
    """A decode step over rows whose KV lengths are ``ctx``."""
    matmul = 2 * (cfg["num_hidden_layers"] * layer_matrix_params(cfg)
                  + head_params(cfg))
    attn = cfg["num_hidden_layers"] * sum(attention_flops(cfg, c)
                                          for c in ctx)
    return float(matmul * len(ctx) + attn)


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Matrix work of ``tokens`` prompt tokens through every layer (the
    head runs once per row and is left out, as is attention)."""
    return float(2 * cfg["num_hidden_layers"] * layer_matrix_params(cfg)
                 * tokens)


def decode_step_bytes(cfg: dict, ctx: list) -> float:
    """Weights streamed once, plus the live keys and values of each row."""
    return float(streamed_weight_bytes(cfg)
                 + kv_bytes_per_token(cfg) * sum(ctx))


def paged_attn_call(cfg: dict, ctx: list) -> tuple[float, float]:
    """(flops, bytes) of one layer's paged-attention call over rows of KV
    lengths ``ctx``: live keys and values, and q in and out."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    flops = sum(attention_flops(cfg, c) for c in ctx)
    kv = sum(2 * c * hkv * hd * KV_BYTES for c in ctx)
    q_out = len(ctx) * 2 * hq * hd * KV_BYTES
    return float(flops), float(kv + q_out)


def least_seconds(flops: float, nbytes: float, peak) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak.flops_per_s, nbytes / peak.hbm_bytes_per_s)
