"""bfio_solve_share (%): the routing policy's share of the engine's host
steps in the trace: the summed ``serve.place`` spans (the policy's
assignment, the BF-IO solve for ``bfio_h8``) over the summed
``serve.step`` spans.  None where the trace has no phase spans."""
from bench import spans


def read(run):
    found = spans.for_run(run)
    if found is None:
        return None
    step = sum(s.dur_ns for s in found.steps)
    place = sum(s.dur_ns for s in found.named("serve.place"))
    return 100.0 * place / step if step > 0 else None
