#!/usr/bin/env python3
"""Readings for the limit of ``correct``: the program's and the control's.

    python3 bench/calibrate.py --workload mistral-7b-v0.3-h16.longdoc \
        --seeds 11 12 13 --control-seeds 11 12 13 --seconds 20

For each seed, in one process: weights from the seed, a pre-roll and a
window at the cell's own load through the timed path, then the sample
``correct`` compares, with the float32 reference's gap of each served
token (the program's reading) and, on the control seeds, the gap of the
token that the float8 reference puts first at the same positions (the
control's reading).  Prints one JSON line per seed.  Not part of a
benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _summary(gaps) -> dict:
    import numpy as np

    from bench.harness import gap_numbers

    g = np.concatenate(gaps)
    return dict(gap_numbers(gaps), p99=float(np.percentile(g, 99)),
                nonzero=int(np.sum(g > 0)), tokens=int(g.size),
                per_request_max=[float(x.max()) for x in gaps])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=20.0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec, system

    cell = spec.resolve(args.workload)
    _, counter = harness.prepare(True, cell.chips)
    ref = spec.load_reference(cell.config["reference"])
    vocab = cell.config["vocab_size"]
    for seed in args.seeds:
        t = time.perf_counter()
        sys_ = system.build(cell.config, seed)
        harness.warm_up(sys_.engine, vocab,
                        cell.config["engine"]["prefill_chunk"],
                        cell.mix["prompt"]["min"])
        log = harness.serve(sys_.engine, cell.mix, seed, args.seconds, vocab,
                            counter=counter)
        sample = harness.choose_sample(log, seed)
        sys_.close()
        sys_ = None
        gc.collect()
        t_ref = time.perf_counter()
        row = dict(workload=cell.name, seed=seed, requests=len(sample),
                   program=_summary(ref.served_gaps(cell.config, seed,
                                                    sample)))
        row["reference_s"] = time.perf_counter() - t_ref
        if seed in args.control_seeds:
            row["control"] = _summary(ref.control_gaps(cell.config, seed,
                                                       sample))
        row["wall_s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
