"""Where the Pallas kernels run: natively on the TPU, interpreted elsewhere."""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["on_tpu", "resolve_interpret"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one place a Pallas kernel's interpret mode comes from.

    ``None`` derives it from the platform: native on the TPU, interpreted
    everywhere else.  An explicit bool wins — parity tests force ``True``,
    and compile-only tests for a described (not attached) chip force
    ``False``, since the process itself still runs on the CPU."""
    return (not on_tpu()) if interpret is None else bool(interpret)
