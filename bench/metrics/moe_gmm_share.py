"""moe_gmm_share (%): device time of the expert layers' grouped matmul
(``moe_gmm_roofline.matcher``: the Pallas kernel's calls) over the traced
window.  None where no such op ran."""
from bench.metrics.moe_gmm_roofline import matcher


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    device_s = run.trace.op_seconds(matcher)
    return 100.0 * device_s / run.trace.window_s if device_s > 0 else None
