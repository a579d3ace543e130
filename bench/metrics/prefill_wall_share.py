"""prefill_wall_share (%): share of the window's wall time spent in engine
steps that ran a prefill chunk (``prefill_tokens > 0``)."""
from bench import readout


def read(run):
    steps = readout.window_steps(run.log)
    busy = sum(s["t1"] - s["t0"] for s in steps if s["prefill_tokens"])
    return 100.0 * busy / readout.window_wall(run.log)
