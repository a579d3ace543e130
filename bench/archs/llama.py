"""Llama (``transformers``' ``LlamaConfig``): Mistral's layer equations,
so Mistral's mapping and counts."""
from bench.archs.mistral import (TINY, attention_flops, check,  # noqa: F401
                                 decode_weight_bytes, head_params,
                                 kv_bytes_per_token, layer_matrix_params,
                                 model_config)
