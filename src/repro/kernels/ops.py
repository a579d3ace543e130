"""Jit'd public wrappers for the Pallas kernels.

``use_pallas`` selects the kernel path; the kernels derive their interpret
mode from the platform (:func:`~repro.kernels.platform.resolve_interpret`):
interpreted on CPU (Python emulation of the kernel body — correctness
validation), compiled natively on TPU.  The jnp oracles in ``ref.py`` are
the default path for dry-run lowering (the roofline is derived from the
XLA program; the Pallas kernels are the deployment hot path).
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_pallas
from .rms_norm import rms_norm_pallas
from .ssm_scan import ssm_chunk_scan_pallas

__all__ = ["decode_attention", "ssm_chunk_scan", "rms_norm"]


def decode_attention(q, k_cache, v_cache, lengths, *,
                     use_pallas: bool = False, blk_l: int = 512):
    """One-token GQA decode attention (see decode_attention.py)."""
    if use_pallas:
        return decode_attention_pallas(q, k_cache, v_cache, lengths,
                                       blk_l=blk_l)
    return ref.decode_attention_ref(q, k_cache, v_cache, lengths)


def ssm_chunk_scan(q, k, v, log_decay, gate, *, use_pallas: bool = False,
                   chunk: int = 128):
    """Gated linear-attention scan (see ssm_scan.py)."""
    if use_pallas:
        B, S, H, dk = q.shape
        pad = (-S) % chunk
        if pad:
            def padseq(x):
                widths = [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)
                return jnp.pad(x, widths)
            q, k, v = padseq(q), padseq(k), padseq(v)
            # padding steps: no decay, no input
            log_decay = padseq(log_decay)
            gate = padseq(gate)
        y, state = ssm_chunk_scan_pallas(q, k, v, log_decay, gate,
                                         chunk=chunk)
        return y[:, :S], state
    return ref.ssm_chunk_scan_ref(q, k, v, log_decay, gate)


def rms_norm(x, scale, *, eps: float = 1e-5, use_pallas: bool = False):
    """Fused RMS norm (see rms_norm.py)."""
    if use_pallas:
        return rms_norm_pallas(x, scale, eps=eps)
    return ref.rms_norm_ref(x, scale, eps=eps)
