"""paged_attn_roofline.tput (%): ``paged_attn_roofline`` in the cells
judged by ``output_tok_s`` (above the knee)."""
from bench import spec

read = spec.load_reader("paged_attn_roofline")
