"""Pallas TPU kernels for the perf-critical compute layers, with jnp
oracles (ref.py) and jit'd wrappers (ops.py)."""
from .bfio_swap import swap_best, swap_best_pallas, swap_best_xla  # noqa: F401
from .ops import decode_attention, rms_norm, ssm_chunk_scan  # noqa: F401
from .paged_attention import paged_decode_attention_pallas  # noqa: F401
from .platform import on_tpu, resolve_interpret  # noqa: F401
