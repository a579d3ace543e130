"""queue_wait_p90_s (s): 90th percentile, over the requests due in the
window, of the time from when each was due to the end of the step in
which it held a slot; one not admitted by the window's end counts at
its age then."""
import math

from bench import readout


def read(run):
    log = run.log
    waits = []
    for rid in readout.due_in_window(log):
        t = log.admit_s[rid]
        t = log.end_s if math.isnan(t) or t > log.end_s else t
        waits.append(t - log.schedule[rid].due_s)
    return readout.percentile(waits, 90)
