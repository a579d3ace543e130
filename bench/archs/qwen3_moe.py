"""Qwen3-MoE (``transformers``' ``Qwen3MoeForCausalLM``), which the
program runs as its ``moe`` family: Mistral's pre-norm decoder with
QK-norm (RMSNorm of each head of q and k over ``head_dim``, before
RoPE) and, in every layer, a sparse expert layer in place of the MLP:
softmax over all router outputs in float32, the top
``num_experts_per_tok``, renormalised (``norm_topk_prob``), each chosen
expert a SwiGLU of width ``moe_intermediate_size``.  No shared expert,
no dense layer, an untied head.

The file's ``num_experts`` counts the experts this chip holds, from
``expert_offset``; ``router_experts`` is the router's width, the
published expert count.  A token passes through the held experts it is
routed to, so the per-token counts here are expectations under uniform
routing (each of the ``router_experts`` equally likely, ``k`` distinct
per token): ``k x held / router_experts`` held experts per token, and a
step of ``rows`` rows hits ``held x (1 - (1 - k / router_experts) **
rows)`` of them.  ``gmm_call`` counts the grouped matmul's work from
what the program counted instead (``moe_pairs``, ``moe_hit``).
"""
from __future__ import annotations

from bench.archs.mistral import attention_flops, head_params  # noqa: F401
from bench.spec import KV_BYTES, NORM_BYTES, W_BYTES, head_dim, no_path

ROUTER_BYTES = 4     # the program keeps the router in float32

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
            router_experts=8, num_experts=4, num_experts_per_tok=2,
            vocab_size=512)


def check(cfg: dict) -> None:
    ok = (cfg["hidden_act"] == "silu"
          and cfg.get("shared_expert_intermediate_size", 0) == 0
          and not cfg.get("mlp_only_layers")
          and cfg.get("decoder_sparse_step", 1) == 1
          and cfg.get("norm_topk_prob") is True
          and cfg.get("sliding_window") is None
          and not cfg.get("use_sliding_window", False)
          and cfg.get("rope_scaling") is None
          and not cfg.get("attention_bias", False)
          and not cfg["tie_word_embeddings"]
          and cfg["torch_dtype"] == "bfloat16"
          and 0 <= cfg.get("expert_offset", 0)
          and cfg.get("expert_offset", 0) + cfg["num_experts"]
          <= cfg["router_experts"]
          and cfg["num_experts_per_tok"] <= cfg["router_experts"])
    if not ok:
        raise no_path(cfg)


# what the program must have for this file: QK-norm and held experts
_NEEDS = ("qk_norm", "experts_held", "expert_offset")


def model_config(cfg: dict):
    import dataclasses

    from repro.configs.base import ModelConfig

    if not set(_NEEDS) <= {f.name for f in dataclasses.fields(ModelConfig)}:
        raise no_path(cfg)
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=0, moe_d_ff=cfg["moe_intermediate_size"],
        n_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], qk_norm=True,
        dtype=cfg["torch_dtype"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"])


def _attention_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    return 2 * d * hq * hd + 2 * d * hkv * hd


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_layer_params(cfg: dict) -> int:
    """Matrix parameters one layer holds: attention, the router over
    every expert, and the held experts."""
    return (_attention_params(cfg)
            + cfg["hidden_size"] * cfg["router_experts"]
            + cfg["num_experts"] * expert_params(cfg))


def experts_per_token(cfg: dict) -> float:
    """Held experts one token passes through, expected under uniform
    routing."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_experts"])


def experts_hit(cfg: dict, rows: int) -> float:
    """Held experts that ``rows`` tokens hit, expected under uniform
    routing."""
    miss = 1 - cfg["num_experts_per_tok"] / cfg["router_experts"]
    return cfg["num_experts"] * (1 - miss ** rows)


def layer_matrix_params(cfg: dict) -> float:
    """Expected under uniform routing: attention, the router, and
    ``experts_per_token`` experts."""
    return (_attention_params(cfg)
            + cfg["hidden_size"] * cfg["router_experts"]
            + experts_per_token(cfg) * expert_params(cfg))


def _norm_bytes(cfg: dict) -> int:
    """A layer's two norms and its q and k norms, float32."""
    return (2 * cfg["hidden_size"] + 2 * head_dim(cfg)) * NORM_BYTES


def decode_weight_bytes(cfg: dict, rows: int) -> float:
    """Attention, router and norms of every layer, the experts that
    ``rows`` rows are expected to hit, the head and the final norm; not
    the embedding table, of which a step reads only its tokens' rows."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = (_attention_params(cfg) * W_BYTES
             + d * cfg["router_experts"] * ROUTER_BYTES)
    experts = experts_hit(cfg, rows) * expert_params(cfg) * W_BYTES
    return (n * (dense + experts + _norm_bytes(cfg))
            + head_params(cfg) * W_BYTES + d * NORM_BYTES)


def kv_bytes_per_token(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * head_dim(cfg) * KV_BYTES)


def gmm_call(cfg: dict, pairs: int, hit: int) -> tuple[float, float]:
    """(flops, bytes) of the grouped matmul (gate, up, down) over
    ``pairs`` token-expert pairs on ``hit`` held experts: ``6 d f`` per
    pair; the hit experts' weights once, and each pair's row in and its
    result out."""
    d = cfg["hidden_size"]
    flops = 6 * d * cfg["moe_intermediate_size"] * pairs
    nbytes = hit * expert_params(cfg) * W_BYTES + pairs * 2 * d * W_BYTES
    return float(flops), float(nbytes)
