"""host_gap_ms (ms): median over the traced steps of each ``serve.step``
span minus the union of the first device's ops inside it: the host time
in ``ServingEngine.step`` during which the device had nothing to run
(``spans.host_gap_ns``).  None where the trace has no phase spans."""
import statistics

from bench import spans


def read(run):
    found = spans.for_run(run)
    gaps = spans.host_gap_ns(found) if found is not None else []
    return statistics.median(gaps) / 1e6 if gaps else None
