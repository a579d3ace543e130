"""ttft_p50_s (s): median time to first token of the requests due in the
window, timed from when each was due (``readout.ttft``)."""
from bench import readout


def read(run):
    return readout.percentile(readout.ttft(run.log), 50)
