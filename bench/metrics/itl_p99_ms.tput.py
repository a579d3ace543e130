"""itl_p99_ms.tput (ms): ``itl_p99_ms`` in the cells judged by
``output_tok_s``: above the knee the tail swings with the queue, so it
is read as a layer's number there, in the part of the window before
the trace."""
from bench import spec

read = spec.load_reader("itl_p99_ms")
