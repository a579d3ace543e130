"""step_mfu (%): the whole steps' share of the chip's peak over the
window: model FLOPs of every window step -- 2 x matrix parameters per
decoded row and per prefilled token, plus attention over each decoded
row's live context (``counts``) -- over the window's wall time on the
host clock times the peak.  Attention inside prefill chunks is not
counted (the log does not see each chunk's offset), so the share is a
lower bound."""
from bench import counts, readout


def read(run):
    if run.peak is None:
        return None
    flops = sum(counts.decode_step_flops(run.cell.config, s["decode_ctx"])
                + counts.prefill_flops(run.cell.config, s["prefill_tokens"])
                for s in readout.window_steps(run.log))
    return 100.0 * flops / (readout.window_wall(run.log)
                            * run.peak.flops_per_s)
