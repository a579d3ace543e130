"""paged_attn_roofline (%): the paged decode attention kernel's least time
over its device time in the traced steps.

Least time: for each layer's call, max(flops / peak, bytes / HBM) over
the live tokens of each row (``counts.paged_attn_call``), never over
the kernel's grid, so any implementation is read against the same work.
Device time: the trace's ops that ``match`` picks.
"""
import re

from bench import counts

# the Pallas kernel's op in the device trace, named after the function
# that makes the pallas_call: "paged_decode_attention_pallas.5 = ..."
_NAME = re.compile(r"^paged_decode_attention_pallas(\.\d+)? = ")


def match(name) -> bool:
    return bool(_NAME.match(name))


def read(run):
    if run.trace is None or run.peak is None:
        return None
    device_s = run.trace.op_seconds(match)
    if device_s <= 0:
        return None
    t0, t1 = run.log.trace_span
    steps = [s for s in run.log.steps
             if s["t0"] >= t0 and s["t1"] <= t1 and s["decode_ctx"]]
    least = run.cell.config["num_hidden_layers"] * sum(
        counts.least_seconds(*counts.paged_attn_call(run.cell.config,
                                                     s["decode_ctx"]),
                             run.peak) for s in steps)
    return 100.0 * least / device_s
