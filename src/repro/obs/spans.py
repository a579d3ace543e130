"""Phase spans of ``ServingEngine.step`` on the device trace's clock.

Each span is a :class:`jax.profiler.TraceAnnotation`: with a profiler
running it lands on the profile's host plane, on the same clock as the
device's ``XLA Ops`` and ``XLA Modules`` lines, so a device idle gap
can be read against the host phase that held it.  With no profiler
running a span costs about a microsecond and records nothing.

Every span name the program uses is in :data:`SPANS`; nesting per step:

* ``serve.step`` — the whole step; args ``waiting``, ``active`` (the
  queue and the slots held at entry);

  * ``serve.admit`` — context build, pool gate, slot claim;

    * ``serve.place`` — the routing policy's assignment (the BF-IO
      solve);

  * ``serve.plan`` — chunk planning with its block demand and
    preemption loop;
  * ``serve.chunk`` — one prefill-chunk program; args ``rows`` (live),
    ``bucket``, ``tokens``, ``chunk``;

    * ``serve.chunk.launch`` — batch build, host-to-device transfers,
      dispatch;
    * ``serve.chunk.wait`` — the host blocked on the device's result;

  * ``serve.chunk.commit`` — per-row advance and first tokens;
  * ``serve.reserve`` — decode block reservation (paged); arg
    ``preempted``;
  * ``serve.account`` — loads, imbalance, the modelled clock and
    energy;
  * ``serve.decode`` — one decode program; args ``rows``, ``bucket``,
    ``tokens``;

    * ``serve.decode.launch`` / ``serve.decode.wait`` — as for chunks;

  * ``serve.decode.commit`` — bookkeeping after the tokens return.
"""
from __future__ import annotations

import jax

__all__ = ["SPANS", "span"]

SPANS = (
    "serve.step",
    "serve.admit",
    "serve.place",
    "serve.plan",
    "serve.chunk",
    "serve.chunk.launch",
    "serve.chunk.wait",
    "serve.chunk.commit",
    "serve.reserve",
    "serve.account",
    "serve.decode",
    "serve.decode.launch",
    "serve.decode.wait",
    "serve.decode.commit",
)
_NAMES = frozenset(SPANS)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A phase span named ``name`` (one of :data:`SPANS`) carrying
    ``args`` as its stats; use as a context manager.  Args known only
    after the work go in through ``set_metadata`` on the span."""
    if name not in _NAMES:
        raise ValueError(f"unknown span {name!r}; add it to SPANS")
    return jax.profiler.TraceAnnotation(name, **args)
