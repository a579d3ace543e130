"""host_gap_ms.tput (ms): ``host_gap_ms`` in the cells judged by
``output_tok_s`` (above the knee)."""
from bench import spec

read = spec.load_reader("host_gap_ms")
