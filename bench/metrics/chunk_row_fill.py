"""chunk_row_fill (%): how full the chunk program's rows are: the prompt
tokens the traced ``serve.chunk`` spans carried (``tokens``) over the
token slots their programs ran (``bucket`` x ``chunk``).  None where the
trace has no phase spans or no chunk ran."""
from bench import spans


def read(run):
    found = spans.for_run(run)
    if found is None:
        return None
    chunks = [s.args for s in found.named("serve.chunk")]
    slots = sum(a.get("bucket", 0) * a.get("chunk", 0) for a in chunks)
    if slots <= 0:
        return None
    return 100.0 * sum(a.get("tokens", 0) for a in chunks) / slots
