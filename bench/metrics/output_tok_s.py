"""output_tok_s (tokens/s): tokens emitted in the window over the
window's whole wall time, idle included."""
from bench import readout


def read(run):
    return readout.tokens_in_window(run.log) / readout.window_wall(run.log)
