"""One run of one cell: set-up, open-loop window, metrics, correctness.

``run(cell, seed, seconds, trace)`` is the whole of a run; ``bench/run.py``
is its command line.  The order is fixed:

1. set-up (``setup_s`` runs from process start to the window's open):
   weights from the seed on the device, a warm-up that compiles every
   program the window drives (the objects it made are then frozen out
   of the collector's reach), then a pre-roll of the cell's own arrival
   schedule until the batch is in steady state;
2. the window: each loop submits every request that is due and calls
   ``ServingEngine.step()``, which returns host numpy, so each step ends
   synchronised; the step's end is stamped on the host clock, and first
   tokens and token gaps are read from the growth of ``len(generated)``;
   the longest steps are printed with the host usage in them;
3. ``memory_peak_bytes``, then the metrics, each from its reader under
   ``metrics/`` (the end-to-end ones untraced, the per-layer ones in a
   traced run);
4. the program's state is freed and the plain reference is run over a
   sample of the finished requests (``correct``).
"""
from __future__ import annotations

import gc
import math
import resource
import shutil
import sys
import time

import numpy as np

from . import readout
from . import spec as specs
from . import traffic

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
TRACE_SECONDS = 6.0


class CompileCounter:
    """Counts JAX's tracings and backend compiles (``jax.monitoring``)."""

    def __init__(self) -> None:
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        self.count += event in COMPILE_EVENTS


class GcClock:
    """Pauses of Python's cyclic collector (``gc.callbacks``): when each
    ended and how long it took, in seconds on the host clock."""

    def __init__(self) -> None:
        self.pauses: list[tuple[float, float]] = []
        self._t0 = None

    def __call__(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        elif self._t0 is not None:
            self.pauses.append((now, now - self._t0))
            self._t0 = None


class Log:
    """What the open loop saw: one record per step and per request, on the
    host clock, in seconds after the schedule opened."""

    def __init__(self, schedule: list) -> None:
        self.schedule = schedule
        self.steps: list[dict] = []
        self.submit_s = np.full(len(schedule), np.nan)
        self.admit_s = np.full(len(schedule), np.nan)
        self.token_s: list[list] = [[] for _ in schedule]
        self.done_s = np.full(len(schedule), np.nan)
        self.failed = np.zeros(len(schedule), bool)
        self.reqs: list = [None] * len(schedule)
        self.open_s = math.nan
        self.close_s = math.nan
        self.end_s = math.nan
        self.trace_span = None     # (start_s, stop_s) of the traced part
        self.setup_done = math.nan  # perf_counter() at the window's open


class OpenLoop:
    """Drives the engine on an arrival schedule; time 0 is ``origin``."""

    def __init__(self, engine, log: Log, make_request, clock=time.perf_counter):
        self.engine = engine
        self.log = log
        self.make_request = make_request
        self.clock = clock
        self.origin = clock()
        self.next = 0
        self.live: dict[int, int] = {}    # rid -> len(generated) so far

    def now(self) -> float:
        return self.clock() - self.origin

    def _submit_due(self, now: float) -> None:
        sched = self.log.schedule
        while self.next < len(sched) and sched[self.next].due_s <= now:
            q = sched[self.next]
            r = self.make_request(q.rid, q.prompt, q.max_new_tokens)
            self.engine.submit(r)
            self.log.reqs[q.rid] = r
            self.log.submit_s[q.rid] = self.now()
            self.live[q.rid] = 0
            self.next += 1

    def run_until(self, stop_s: float, annotate=None) -> None:
        """Step until the clock passes ``stop_s``; idle waits sleep until
        the next request is due."""
        log = self.log
        sched = log.schedule
        while True:
            now = self.now()
            if now >= stop_s:
                return
            self._submit_due(now)
            if not self.live:
                nxt = sched[self.next].due_s if self.next < len(sched) \
                    else math.inf
                time.sleep(max(min(nxt, stop_s) - self.now(), 0.0))
                continue
            t0, u0 = self.now(), resource.getrusage(resource.RUSAGE_SELF)
            if annotate is None:
                info = self.engine.step()
            else:
                with annotate(len(log.steps)):
                    info = self.engine.step()
            t1, u1 = self.now(), resource.getrusage(resource.RUSAGE_SELF)
            self._record(t0, t1, info)
            # what the process did in the step, to tell a stall's cause
            log.steps[-1]["host"] = dict(
                cpu_s=u1.ru_utime + u1.ru_stime - u0.ru_utime - u0.ru_stime,
                majflt=u1.ru_majflt - u0.ru_majflt,
                minflt=u1.ru_minflt - u0.ru_minflt,
                nivcsw=u1.ru_nivcsw - u0.ru_nivcsw)

    def _record(self, t0: float, t1: float, info: dict) -> None:
        log = self.log
        decode_ctx = []
        for rid in list(self.live):
            r = log.reqs[rid]
            before, after = self.live[rid], len(r.generated)
            if np.isnan(log.admit_s[rid]) and r.slot >= 0:
                log.admit_s[rid] = t1
            if after > before:
                log.token_s[rid].extend([t1] * (after - before))
                n_prompt = len(log.schedule[rid].prompt)
                # token k > 0 was decoded over prompt + k tokens of KV
                decode_ctx.extend(n_prompt + k
                                  for k in range(max(before, 1), after))
                self.live[rid] = after
            if r.status == "done" or r.status == "failed":
                log.done_s[rid] = t1
                log.failed[rid] = r.status == "failed"
                del self.live[rid]
        log.steps.append(dict(
            t0=t0, t1=t1, prefill_tokens=int(info["prefill_tokens"]),
            decode_ctx=decode_ctx, active=int(info["active"]),
            waiting=int(info["waiting"]),
            prefilling=int(info["prefilling"])))


def chunk_rows_max(chunk: int, min_prompt: int, slots: int) -> int:
    """The most rows a step's prefill plan can hold.  A plan takes the
    rest of at most one partly done prompt, then whole prompts, then
    part of one more, within ``chunk`` tokens (``Scheduler.plan_chunks``):
    1 + (rows - 2) x ``min_prompt`` + 1 <= ``chunk``."""
    return min(slots, 2 + (chunk - 2) // min_prompt)


def warm_up(engine, vocab: int, chunk: int, min_prompt: int) -> None:
    """Run the engine through every program the window can drive, on
    requests of its own: each decode bucket (the engine's 4, 8 and its
    32 slots), and each prefill-chunk bucket up to the one that holds
    ``chunk_rows_max`` rows for the mix's shortest prompt."""
    from .system import new_request as make_request

    rng = np.random.default_rng(0)
    rid = -1

    def wave(lengths, max_new: int) -> None:
        nonlocal rid
        reqs = []
        for n in lengths:
            reqs.append(make_request(rid, rng.integers(
                1, vocab, size=int(n), dtype=np.int32), max_new))
            engine.submit(reqs[-1])
            rid -= 1
        while any(r.status not in ("done", "failed") for r in reqs):
            engine.step()
        if any(r.status == "failed" for r in reqs):
            raise RuntimeError("a warm-up request failed")

    buckets = engine._buckets
    # prompts of one whole chunk each: one chunk row a step, while the
    # rows decoding climb to one past the second largest decode bucket
    n = buckets[-2] + 1
    wave([chunk] * n, n + 2)
    # a plan of m rows: one prompt's last token, m - 2 whole prompts of
    # the shortest length, and the first token(s) of one more
    rows = chunk_rows_max(chunk, min_prompt, engine.N)
    for lo, hi in zip(buckets, buckets[1:]):
        if rows > lo:
            m = min(hi, rows)
            wave([chunk + 1] + [min_prompt] * (m - 1), 2)


def prepare(require_tpu: bool = True, chips: int = 1):
    """The device, after checking it; the compile cache and counter on."""
    import jax

    dev = jax.devices()[0]
    if require_tpu and (dev.platform != "tpu" or len(jax.devices()) < chips):
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX found "
                         f"{len(jax.devices())} {dev.platform!r} device(s)")
    if dev.platform == "tpu":
        enable_compile_cache(jax)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    return dev, counter


def serve(engine, mix: dict, seed: int, seconds: float, vocab: int, *,
          counter: CompileCounter, trace_dir=None) -> Log:
    """Pre-roll, then the window (its first seconds traced where
    ``trace_dir`` is given); raises where anything compiles inside it."""
    import jax

    from . import system

    log = Log(traffic.schedule(mix, seed, seconds, vocab))
    loop = OpenLoop(engine, log, system.new_request)
    loop.run_until(float(mix["preroll_s"]))
    log.open_s = loop.now()
    log.close_s = log.open_s + seconds
    log.setup_done = time.perf_counter()
    c0 = counter.count
    if trace_dir is None:
        loop.run_until(log.close_s)
        log.end_s = max(loop.now(), log.close_s)
    else:
        # the window's last seconds are traced; the host-clock readers
        # read the part before them, and stopping the profiler (seconds
        # of host work) falls after the window has closed
        loop.run_until(log.close_s - min(TRACE_SECONDS, seconds / 2))
        log.end_s = loop.now()
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans: annotations only
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        t_trace0 = loop.now()
        loop.run_until(log.close_s,
                       lambda i: jax.profiler.StepTraceAnnotation(
                           "engine.step", step_num=i))
        log.trace_span = (t_trace0, loop.now())
        jax.profiler.stop_trace()
    if counter.count != c0:
        raise RuntimeError(f"bench: {counter.count - c0} compilation(s) "
                           "inside the measured window")
    return log


def run(cell: specs.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, fault=None,
        control: bool = False, out=sys.stderr) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``fault`` (tests only) wraps the engine after it is built, to break
    the timed path underneath the harness.  ``control`` puts the
    reference computed in float8 in the program's place for ``correct``:
    the run must then come out as not correct."""
    import jax

    from . import system

    dev, counter = prepare(require_tpu, cell.chips)
    cfg = cell.config
    vocab = cfg["vocab_size"]
    sys_ = system.build(cfg, seed)
    engine = sys_.engine if fault is None else fault(sys_.engine)
    t_weights = time.perf_counter() - t_start
    warm_up(engine, vocab, cfg["engine"]["prefill_chunk"],
            cell.mix["prompt"]["min"])
    t_warm = time.perf_counter() - t_start
    compiles_setup = counter.count
    # what set-up made (JAX's caches, the compiled programs, the weights'
    # handles) is kept out of the collector's reach: a full collection
    # over it pauses the loop for up to a second, at a random step
    gc.collect()
    gc.freeze()
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    trace_dir = specs.ROOT / ".bench_trace" / cell.name if trace else None
    log = serve(engine, cell.mix, seed, seconds, vocab, counter=counter,
                trace_dir=trace_dir)
    setup_s = log.setup_done - t_start
    peak = dev.memory_stats().get("peak_bytes_in_use", 0) \
        if dev.platform == "tpu" else 0
    lateness = [log.submit_s[r] - log.schedule[r].due_s
                for r in readout.due_in_window(log)
                if not np.isnan(log.submit_s[r])]
    print(f"bench: {cell.name} seed {seed}: weights {t_weights:.3f} s, "
          f"warm-up done {t_warm:.3f} s ({compiles_setup} compile events), "
          f"window opens {setup_s:.3f} s; {len(log.steps)} steps in all; "
          f"generator late p50 {readout.percentile(lateness, 50)} s max "
          f"{max(lateness, default=None)} s", file=out)

    gc.callbacks.remove(gc_clock)
    window_gc = [p for at, p in gc_clock.pauses
                 if log.setup_done <= at <= log.setup_done + seconds]
    walls = sorted(((s["t1"] - s["t0"], s) for s in readout.window_steps(log)),
                   key=lambda x: -x[0])[:3]
    print(f"bench: in the window {len(window_gc)} collections, longest "
          f"{max(window_gc, default=0.0):.4f} s; longest steps "
          + ", ".join(f"{w:.4f} s at {s['t0'] - log.open_s:.3f} s (prefill "
                      f"{s['prefill_tokens']}, decode {len(s['decode_ctx'])}"
                      f", waiting {s['waiting']}, host {s.get('host')})"
                      for w, s in walls), file=out)

    metrics, device, breakdown = _read_metrics(cell, log, dev, setup_s,
                                               trace_dir, out)
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()), memory_peak_bytes=int(peak),
                  **device)
    attempted, failed = readout.attempted_failed(log)
    _print_summary(cell, log, out)

    # correctness: free the program's state, then the reference
    sample = choose_sample(log, seed)
    engine = sys_ = None
    gc.unfreeze()
    gc.collect()
    compared = check(cell, seed, sample, out, control=control)
    result = dict(correct=all(v <= lim for v, lim in compared.values()),
                  attempted=attempted, failed=failed,
                  metrics={k: dict(value=v, unit=u)
                           for k, (v, u) in metrics.items()},
                  device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: dict(value=v, limit=lim)
                          for k, (v, lim) in compared.items()}
    return result


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    apart from any the environment names; every program is kept, however
    fast it compiled, and none is evicted."""
    path = specs.ROOT / ".bench_cache" / "jax"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(path)


def _read_metrics(cell, log, dev, setup_s, trace_dir, out):
    """The cell's end-to-end metrics (untraced run) or its per-layer ones
    (traced run), each from its reader under ``metrics/``; a reader that
    finds nothing to read returns None and its metric is left out."""
    from . import peaks
    from . import trace as tr

    events = tr.load(trace_dir) if trace_dir is not None else None
    run = specs.Run(cell=cell, log=log, peak=peaks.for_device(dev.device_kind)
                    if dev.platform == "tpu" else None,
                    trace=events, setup_s=setup_s)
    found = {}
    for m in (cell.per_layer if events is not None else cell.end_to_end):
        value = specs.load_reader(m["name"])(run)
        if value is not None:
            found[m["name"]] = (float(value), m["unit"])
    if events is None:
        return found, {}, None
    print(f"bench: trace {trace_dir}: {events.n_ops} device ops, busy "
          f"{events.busy_s:.6f} s of {events.window_s:.6f} s", file=out)
    return (found, dict(busy_s=events.busy_s, window_s=events.window_s),
            tr.breakdown(events))


def _print_summary(cell, log: Log, out) -> None:
    steps = [s for s in log.steps if s["t0"] >= log.open_s]
    done = np.isfinite(log.done_s) & ~log.failed
    print(f"bench: window {log.open_s:.3f}-{log.end_s:.3f} s: "
          f"{len(steps)} steps, "
          f"{sum(len(s['decode_ctx']) for s in steps)} decode rows, "
          f"{sum(s['prefill_tokens'] for s in steps)} prefill tokens; "
          f"{int(done.sum())} requests finished in all, "
          f"{int(log.failed.sum())} failed; waiting at close "
          f"{steps[-1]['waiting'] if steps else 0}", file=out)


def choose_sample(log: Log, seed: int, min_tokens: int = 256,
                  min_requests: int = 6, max_requests: int = 12) -> list:
    """Finished requests for the reference: the one with the most served
    tokens, then others drawn from the seed until at least
    ``min_requests`` requests and ``min_tokens`` served tokens, or
    ``max_requests``.  Returns (prompt, served) pairs."""
    done = [i for i, r in enumerate(log.reqs)
            if r is not None and r.status == "done"]
    if not done:
        return []
    longest = max(done, key=lambda i: (len(log.reqs[i].generated), -i))
    rest = [i for i in done if i != longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    pick = [longest]
    tokens = len(log.reqs[longest].generated)
    for j in order:
        if (tokens >= min_tokens and len(pick) >= min_requests) \
                or len(pick) >= max_requests:
            break
        pick.append(rest[j])
        tokens += len(log.reqs[rest[j]].generated)
    return [(np.asarray(log.schedule[i].prompt, np.int32),
             np.asarray(log.reqs[i].generated, np.int32)) for i in pick]


def gap_numbers(gaps: list) -> dict:
    """The numbers a sample's logit gaps give: the widest, and the mean
    over every served token."""
    g = np.concatenate(gaps)
    return {"logit_gap_max": float(g.max()),
            "logit_gap_mean": float(g.mean())}


def check(cell, seed: int, sample: list, out, control: bool = False) -> dict:
    """The numbers compared, each with its limit: name -> (value, limit).
    Numbers that the cell's limits file does not name are printed, not
    compared.  With ``control``, the numbers are those of the tokens the
    float8 reference puts first at the served positions."""
    limits = cell.limits
    if not sample:
        print("bench: no finished request to check", file=out)
        return {k: (math.inf, v) for k, v in limits.items()}
    ref = specs.load_reference(cell.config["reference"])
    t = time.perf_counter()
    gaps = ref.control_gaps if control else ref.served_gaps
    numbers = gap_numbers(gaps(cell.config, seed, sample))
    print(f"bench: {'control' if control else 'reference'} over {len(sample)} requests, "
          f"{sum(s.size for _, s in sample)} served tokens, longest context "
          f"{max(p.size + s.size for p, s in sample)}: "
          f"{time.perf_counter() - t:.3f} s", file=out)
    for name, value in numbers.items():
        if name not in limits:
            print(f"bench: {name} {value!r} (not compared)", file=out)
    compared = {k: (numbers[k], lim) for k, lim in limits.items()}
    for name, (value, limit) in compared.items():
        print(f"correct: {name} {value!r} limit {limit!r}", file=out)
    return compared
