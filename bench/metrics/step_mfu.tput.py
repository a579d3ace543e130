"""step_mfu.tput (%): ``step_mfu`` in the cells judged by
``output_tok_s`` (above the knee)."""
from bench import spec

read = spec.load_reader("step_mfu")
