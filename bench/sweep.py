#!/usr/bin/env python3
"""Find a cell's knee once: serve its mix at several fixed rates.

    python3 bench/sweep.py --workload mistral-7b-v0.3-h16.longdoc \
        --rates 1.0 1.5 2.0 --seconds 30 --seed 1

One process: the weights and programs are made once, and each rate gets
a fresh engine, a pre-roll and a window.  Prints one JSON line per rate:
the rate offered, the tails, tokens/s, and how the backlog (requests
waiting for a slot or for the rest of their prompt's prefill) moved
across the window: the knee is the highest rate at which it does not
grow.  Not part of a benchmark run.
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
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import harness, readout, spec, system

    cell = spec.resolve(args.workload)
    dev, counter = harness.prepare(True, cell.chips)
    sys_ = system.build(cell.config, args.seed)
    vocab = cell.config["vocab_size"]
    for rate in args.rates:
        engine = sys_.fresh_engine()
        harness.warm_up(engine, vocab,
                        cell.config["engine"]["prefill_chunk"],
                        cell.mix["prompt"]["min"])
        mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"],
                                           rate_per_s=rate))
        t = time.perf_counter()
        log = harness.serve(engine, mix, args.seed, args.seconds, vocab,
                            counter=counter)
        steps = readout.window_steps(log)
        third = max(len(steps) // 3, 1)
        wait = [s["waiting"] for s in steps]
        backlog = [s["waiting"] + s["prefilling"] for s in steps]
        dec = readout.decode_steps(log)
        ttft = readout.ttft(log)
        gaps = readout.inter_token_gaps(log)
        row = dict(
            workload=cell.name, rate=rate, seconds=args.seconds,
            due=len(readout.due_in_window(log)),
            finished=int(np.sum((log.done_s > log.open_s)
                                & (log.done_s <= log.end_s))),
            ttft_p50_s=readout.percentile(ttft, 50),
            ttft_p80_s=readout.percentile(ttft, 80),
            itl_p50_ms=1e3 * readout.percentile(gaps, 50),
            itl_p99_ms=1e3 * readout.percentile(gaps, 99),
            output_tok_s=readout.tokens_in_window(log)
            / readout.window_wall(log),
            waiting_first_third=float(np.mean(wait[:third])),
            waiting_last_third=float(np.mean(wait[-third:])),
            waiting_at_close=steps[-1]["waiting"] if steps else 0,
            backlog_first_third=float(np.mean(backlog[:third])),
            backlog_last_third=float(np.mean(backlog[-third:])),
            active_mean=float(np.mean([s["active"] for s in steps])),
            decode_step_ms=1e3 * float(np.mean(
                [s["t1"] - s["t0"] for s in dec])) if dec else None,
            decode_rows_mean=float(np.mean(
                [len(s["decode_ctx"]) for s in dec])) if dec else None,
            prefill_step_ms=1e3 * float(np.mean(
                [s["t1"] - s["t0"] for s in steps
                 if s["prefill_tokens"]])) if steps else None,
            steps=len(steps), preemptions=int(engine.preemptions),
            wall_s=time.perf_counter() - t,
            peak_bytes=(dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
