#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload mistral-7b-v0.3-h16.longdoc --seed 7 \
        --seconds 30 --trace 0 [--control 1]

from the root of a checkout.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of
the window's first seconds.  The last line of standard output is the
result: ``{"correct", "attempted", "failed", "metrics", "device",
["breakdown",] "compared"}``; the last lines of standard error give each
number compared with its limit.  Exits non-zero, with no result, where
JAX's first device is not a TPU or there are fewer chips than the cell
asks for.  ``--control 1`` decides ``correct`` on the control instead
(the reference computed in float8, put in the program's place): such a
run must report ``correct`` false.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec

    cell = spec.resolve(args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, control=bool(args.control))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
