"""decode_step_roofline (%): over the window's decode-only steps, the
least time the chip could take -- max(flops / peak, (streamed weights +
live keys and values) / HBM bandwidth), from ``counts`` -- over their
wall time on the host clock."""
from bench import counts, readout


def read(run):
    if run.peak is None:
        return None
    steps = readout.decode_steps(run.log)
    wall = sum(s["t1"] - s["t0"] for s in steps)
    if not steps or wall <= 0:
        return None
    least = sum(counts.least_seconds(
        counts.decode_step_flops(run.cell.config, s["decode_ctx"]),
        counts.decode_step_bytes(run.cell.config, s["decode_ctx"]), run.peak)
        for s in steps)
    return 100.0 * least / wall
