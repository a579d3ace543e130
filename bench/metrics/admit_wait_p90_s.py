"""admit_wait_p90_s (s): 90th percentile, over the requests due in the
window (before the trace, in a traced run), of the time from when each
was due to the engine's own host-clock stamp of its first admission
(``ServeRequest.t_admit_host``); one not admitted by the window's end
counts at its age then, as in ``queue_wait_p90_s``.  None where the
requests carry no such stamp."""
import math

from bench import readout


def read(run):
    log = run.log
    due = readout.due_in_window(log)
    if not any(hasattr(log.reqs[rid], "t_admit_host") for rid in due):
        return None
    # the open loop's time 0 on the host clock (time.perf_counter())
    origin = log.setup_done - log.open_s
    waits = []
    for rid in due:
        r = log.reqs[rid]
        t = math.nan if r is None else r.t_admit_host - origin
        t = log.end_s if math.isnan(t) or t > log.end_s else t
        waits.append(t - log.schedule[rid].due_s)
    return readout.percentile(waits, 90)
