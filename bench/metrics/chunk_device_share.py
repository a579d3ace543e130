"""chunk_device_share (%): device time of the paged chunk program
(``XLA Modules`` events named ``jit_paged_chunk_prefill``) over the
traced window.  None where no program of that name ran."""
from bench import spans

PROGRAM = "jit_paged_chunk_prefill"


def read(run):
    found = spans.for_run(run)
    if found is None or found.window_s() <= 0:
        return None
    device_s = spans.program_seconds(found).get(PROGRAM, 0.0)
    return 100.0 * device_s / found.window_s() if device_s > 0 else None
