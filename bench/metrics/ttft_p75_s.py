"""ttft_p75_s (s): 75th percentile of the time to first token of the
requests due in the window, timed from when each was due
(``readout.ttft``).  The window's 41 requests hold ten beyond it."""
from bench import readout


def read(run):
    return readout.percentile(readout.ttft(run.log), 75)
