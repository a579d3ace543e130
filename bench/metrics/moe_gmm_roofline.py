"""moe_gmm_roofline (%): the expert layers' grouped matmul's least time
over its device time in the traced window.

Least time: for each traced ``serve.decode`` and ``serve.chunk`` span
that carries the program's expert counters (``moe_pairs`` and
``moe_hit``, each summed over layers), max(flops / peak, bytes / HBM)
of the work they count (``archs/<model_type>.py``'s ``gmm_call``: 6 d f
per pair; the hit experts' weights, and each pair's row in and out);
summing over layers first makes it a lower bound of the per-layer sum.
Device time: the Pallas kernel's calls (``matcher``), which read the hit
experts' weights from the stack over layers in HBM.  A kernel handed a
per-layer copy of the weights instead may find it in VMEM, where XLA
can place a buffer of up to about 100 MB on a v5e, and then reads above
100%: the weights crossed HBM in the copy, not in the kernel.  None
where no span carries the counters (a dense model, or a program without
them) or no such op ran.
"""
import re

from bench import counts, spans
from bench.system import arch

# the Pallas kernel of jax.experimental.pallas.ops.tpu.megablox, named
# after its jitted function in the device trace: "gmm.3 = ..."
_KERNEL = re.compile(r"^gmm(\.\d+)? = ")
PROGRAMS = ("serve.chunk", "serve.decode")


def matcher(name: str) -> bool:
    """Picks the grouped matmul's kernel calls in a device trace."""
    return bool(_KERNEL.match(name))


def counted_calls(found) -> list:
    """The args of the traced program spans that carry expert counters."""
    return [s.args for name in PROGRAMS for s in found.named(name)
            if "moe_pairs" in s.args]


def read(run):
    if run.trace is None or run.peak is None:
        return None
    found = spans.for_run(run)
    device_s = run.trace.op_seconds(matcher)
    if found is None or device_s <= 0:
        return None
    calls = counted_calls(found)
    if not calls:
        return None
    cfg = run.cell.config
    gmm_call = arch(cfg).gmm_call
    least = sum(counts.least_seconds(
        *gmm_call(cfg, a["moe_pairs"], a["moe_hit"]), run.peak)
        for a in calls)
    return 100.0 * least / device_s
