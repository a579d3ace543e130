"""Mistral (``transformers``' ``MistralConfig``), which the program runs
as its dense decoder: pre-norm RMSNorm, grouped-query attention with full
rotary positions and no biases, a SwiGLU MLP, an untied head."""
from __future__ import annotations

from bench.spec import KV_BYTES, NORM_BYTES, W_BYTES, head_dim, no_path

# the file's hidden_act -> the program's mlp_variant
_MLP = {"silu": "swiglu"}

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=512)


def check(cfg: dict) -> None:
    # decode_weight_bytes and the dense reference take the head as a
    # matrix of its own, apart from the embedding: no tied head
    ok = (cfg["hidden_act"] in _MLP
          and cfg.get("sliding_window") is None
          and not cfg.get("attention_bias", False)
          and not cfg.get("mlp_bias", False)
          and cfg.get("rope_scaling") is None
          and not cfg["tie_word_embeddings"]
          and cfg["torch_dtype"] == "bfloat16")
    if not ok:
        raise no_path(cfg)


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        mlp_variant=_MLP[cfg["hidden_act"]], dtype=cfg["torch_dtype"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"])


def layer_matrix_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    return 2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * f


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def decode_weight_bytes(cfg: dict, rows: int) -> int:
    """Every weight but the embedding table, of which a step reads only
    its tokens' rows; the same for any ``rows``."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    layers = n * (layer_matrix_params(cfg) * W_BYTES + 2 * d * NORM_BYTES)
    return layers + head_params(cfg) * W_BYTES + d * NORM_BYTES


def kv_bytes_per_token(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * head_dim(cfg) * KV_BYTES)


def attention_flops(cfg: dict, ctx: int) -> int:
    """q.k and p.v."""
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) * ctx
