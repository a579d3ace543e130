"""A tiny cell for the CPU tests: a configuration cut to its
architecture's ``TINY`` sizes (``archs/<model_type>.py``), through the
same harness, engine settings and reference as the benchmark's cells.

The paged decode runs the ``gather`` path here (the Pallas kernel in
interpret mode is too slow for a test); every other setting is the
chip cell's, scaled down."""
from __future__ import annotations

import json

from . import spec, system

BASE = "mistral-7b-v0.3-h16"
MIX = {"arrivals": {"process": "poisson", "rate_per_s": 20.0},
       "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                  "min": 8, "max": 160},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                  "min": 2, "max": 64},
       "preroll_s": 1.0}
# the tiny cell's limit on the widest logit gap, between its two
# readings (test_control.py): bfloat16 serving reads under 0.01 on these
# seeds, the float8 control above 0.1
LIMIT = 0.05


def config(base: str = BASE, **engine) -> dict:
    cfg = json.loads((spec.BENCH / "configs" / f"{base}.json").read_text())
    cfg.update(system.arch(cfg).TINY, max_position_embeddings=256)
    cfg["engine"] = dict(cfg["engine"], max_seq_len=256,
                         paged_attn_impl="gather", paged_pool_blocks=256,
                         prefill_chunk=32, **engine)
    return cfg


def cell(base: str = BASE, per_layer=()) -> spec.Cell:
    bench = spec.load_benchmark()
    e2e = [m for m in bench["end_to_end"]
           if m["name"] in ("setup_s", "ttft_p50_s", "itl_p99_ms")]
    pl = [m for m in bench["per_layer"] if m["name"] in per_layer]
    return spec.Cell(name=f"tiny-{base}", chips=1, config=config(base),
                     mix=MIX, limits={"logit_gap_max": LIMIT}, end_to_end=e2e,
                     per_layer=pl)
