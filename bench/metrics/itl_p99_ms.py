"""itl_p99_ms (ms): 99th percentile of every gap between two tokens of
one request whose later token came in the window."""
from bench import readout


def read(run):
    p = readout.percentile(readout.inter_token_gaps(run.log), 99)
    return None if p is None else 1e3 * p
