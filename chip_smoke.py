#!/usr/bin/env python3
"""Chip smoke test: the serving path on one TPU at minitron-4b's full width.

    python chip_smoke.py            # from the repository root, on a TPU host

Builds minitron-4b (32 layers, d_model 3072, 24/8 heads, 256k vocabulary;
random bf16 weights from a seed) through the launcher's own functions
(``repro.launch.serve.select_model`` / ``init_serving_params``) and serves
the same 32 seeded requests (prompts of 4-64 tokens, 16 new tokens each)
three times:

1. ``slot``  — ``ServingEngine``, contiguous KV, ``bfio_h8`` placement;
2. ``paged`` — the paged KV backend with the native Pallas decode kernel,
   whose output is checked against ``paged_decode_attention_ref`` on the
   chip, on the engine's live pools, within ``KERNEL_TOL``;
3. ``fleet`` — a two-replica ``FleetServer`` behind the ``bfio`` router,
   whose ``bfio_assign_batch`` solve runs on the device.

Each phase runs twice on a fresh engine: the first run compiles (its
compile seconds come from JAX's compile events), the second is the steady
wall clock, timed up to ``block_until_ready`` on the KV state.  Engine
times, energies and throughputs on the modelled clock (an A100 power
model) are printed labelled as modelled; they are not chip measurements.

Exits non-zero, with no ``ok`` line, unless JAX's first device is a TPU,
and if any phase raises, any request fails or the kernel check misses.
The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything runs in this one process: it holds the chip.
"""
from __future__ import annotations

import gc
import importlib.metadata
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "minitron-4b"
N_REQUESTS = 32
MAX_NEW = 16
SEED = 0
# bf16 tolerance of the paged kernel against the float32 oracle:
# |kernel - ref| <= KERNEL_TOL * (1 + |ref|) elementwise (bf16 keeps 8
# significant bits, 2^-8 ~ 3.9e-3, and the kernel rounds its output to
# bf16 after a differently ordered float32 softmax)
KERNEL_TOL = 2e-2
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_GiB = 2 ** 30


class CompileClock:
    """Sums JAX's trace / lower / backend-compile durations and counts
    backend compiles, from ``jax.monitoring`` events."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == _COMPILE_EVENTS[-1]


def kv_arrays(engine) -> list:
    """The device arrays holding an engine's KV state."""
    kv = getattr(engine.backend, "kv", None)
    if kv is not None:
        return [kv.k_pool, kv.v_pool]
    return jax.tree.leaves(engine.backend.cache)


def peak_gib(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / _GiB


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    n_dev = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={n_dev}")
    print(f"versions: jax {jax.__version__}, jaxlib "
          f"{importlib.metadata.version('jaxlib')}, libtpu "
          f"{importlib.metadata.version('libtpu')}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.core import make_policy
    from repro.fleet import FleetServer
    from repro.kernels.paged_attention import paged_decode_attention_pallas
    from repro.kernels.platform import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import init_serving_params, select_model
    from repro.serving import EngineConfig, ServeRequest, ServingEngine
    from repro.serving.paged_cache import paged_decode_attention_ref

    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    t0 = time.perf_counter()
    cfg, mesh = select_model(ARCH, smoke=False)
    params = init_serving_params(cfg, SEED)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} hd {cfg.hd} vocab "
          f"{cfg.vocab_size}: {n_params / 1e9:.3f} B params "
          f"({sum(x.nbytes for x in jax.tree.leaves(params)) / _GiB:.3f}"
          f" GiB {cfg.dtype}); init {time.perf_counter() - t0:.3f} s "
          f"(compile {clock.seconds:.3f} s); peak HBM {peak_gib(dev):.3f} "
          f"GiB")

    def requests() -> list:
        rng = np.random.default_rng(SEED)
        return [ServeRequest(
            rid=i, tokens=rng.integers(1, cfg.vocab_size,
                                       size=int(rng.integers(4, 65))),
            max_new_tokens=MAX_NEW) for i in range(N_REQUESTS)]

    def engine_cfg(backend: str) -> EngineConfig:
        return EngineConfig(n_workers=4, slots_per_worker=8,
                            max_seq_len=256, cache_backend=backend,
                            paged_attn_impl="pallas")

    kernel = {}

    def check_kernel(eng) -> None:
        """Paged kernel vs the float32 oracle on the engine's live
        pools, block tables and lengths (first and last layer)."""
        kv, bs = eng.backend.kv, eng.backend.block_size
        act = eng.table.active_indices()
        tables = jnp.asarray(kv.block_tables[act])
        lengths = jnp.asarray(kv.lengths[act])
        q = jax.random.normal(jax.random.PRNGKey(SEED),
                              (act.size, cfg.n_heads, cfg.hd), jnp.bfloat16)
        worst = 0.0
        for layer in (0, cfg.n_layers - 1):
            kp, vp = kv.k_pool[layer], kv.v_pool[layer]
            lowered = paged_decode_attention_pallas.lower(
                q, kp, vp, tables, lengths, block_size=bs)
            if "tpu_custom_call" not in lowered.as_text():
                raise RuntimeError("paged kernel is not a native TPU kernel")
            out = paged_decode_attention_pallas(q, kp, vp, tables, lengths,
                                                block_size=bs)
            with jax.default_matmul_precision("highest"):
                ref = paged_decode_attention_ref(
                    q.astype(jnp.float32), kp.astype(jnp.float32),
                    vp.astype(jnp.float32), tables, lengths, bs)
            err = np.abs(np.asarray(out, np.float32) - np.asarray(ref))
            ratio = err / (KERNEL_TOL * (1.0 + np.abs(np.asarray(ref))))
            worst = max(worst, float(ratio.max()))
            kernel[f"max_abs_err_layer{layer}"] = float(err.max())
        kernel.update(rows=int(act.size),
                      max_len=int(np.asarray(lengths).max()),
                      worst_err_over_tol=worst, ok=worst <= 1.0)

    def serve(name: str, build, hook=None) -> dict:
        """Two runs of the phase on fresh servers: compile, then steady."""
        runs = []
        for run in ("compile", "steady"):
            reqs = requests()
            c0, n0 = clock.seconds, clock.compiles
            t = time.perf_counter()
            server = build()
            for r in reqs:
                server.submit(r)
            if hook is not None and run == "compile":
                for _ in range(4):        # every request still in flight
                    server.step()
                hook(server)
            stats = server.run()
            engines = getattr(server, "engines", [server])
            jax.block_until_ready([kv_arrays(e) for e in engines])
            wall = time.perf_counter() - t
            failed = [r.rid for r in reqs if r.status != "done"
                      or len(r.generated) != MAX_NEW]
            runs.append(dict(
                wall_s=wall, compile_s=clock.seconds - c0,
                compiles=clock.compiles - n0,
                tokens=sum(len(r.generated) for r in reqs),
                completed=sum(r.status == "done" for r in reqs),
                failed=len(failed), gens=[list(r.generated) for r in reqs],
                stats=stats))
            del server, engines
            gc.collect()                  # free this run's KV first
            if failed:
                raise RuntimeError(f"[{name}] {run} run: requests "
                                   f"{failed} did not complete")
        warm, steady = runs
        if warm["gens"] != steady["gens"]:
            raise RuntimeError(f"[{name}] the two runs generated "
                               "different tokens")
        s = steady["stats"]
        out = dict(compile_s=warm["compile_s"], compile_wall_s=warm["wall_s"],
                   steady_wall_s=steady["wall_s"],
                   steady_compiles=steady["compiles"],
                   tokens=steady["tokens"],
                   steady_tok_per_s=steady["tokens"] / steady["wall_s"],
                   completed=steady["completed"], failed=steady["failed"],
                   peak_hbm_gib=peak_gib(dev),
                   modelled_time_s=s["time_s"],
                   modelled_energy_j=s["energy_j"])
        print(f"[{name}] compile {out['compile_s']:.3f} s (first run "
              f"{out['compile_wall_s']:.3f} s wall); steady "
              f"{out['steady_wall_s']:.4f} s wall for {out['tokens']} "
              f"tokens ({out['steady_tok_per_s']:.1f} tok/s, "
              f"{out['steady_compiles']} compiles); "
              f"{out['completed']}/{N_REQUESTS} completed, "
              f"{out['failed']} failed; peak HBM "
              f"{out['peak_hbm_gib']:.3f} GiB")
        print(f"[{name}] modelled clock (A100 power model, not a chip "
              f"measurement): {s['steps']} steps, {s['time_s']:.4f} s, "
              f"{s['energy_j']:.2f} J")
        return out

    policy = "bfio_h8"
    phases = {}
    phases["slot"] = serve("slot", lambda: ServingEngine(
        cfg, params, engine_cfg("slot"), make_policy(policy), mesh=mesh))
    if resolve_interpret(None):
        raise RuntimeError("Pallas kernels would run interpreted on a TPU")
    phases["paged"] = serve("paged", lambda: ServingEngine(
        cfg, params, engine_cfg("paged"), make_policy(policy), mesh=mesh),
        hook=check_kernel)
    print(f"[paged] kernel vs paged_decode_attention_ref (float32), "
          f"tolerance {KERNEL_TOL} * (1 + |ref|): {json.dumps(kernel)}")
    if not kernel.get("ok"):
        raise RuntimeError("paged kernel check missed its tolerance")
    phases["paged"]["kernel"] = kernel
    phases["fleet"] = serve("fleet", lambda: FleetServer(
        cfg, params, engine_cfg("slot"), n_replicas=2, router="bfio",
        policy=policy, mesh=mesh, seed=SEED))
    print("summary: " + json.dumps(dict(
        arch=cfg.name, device_kind=dev.device_kind, phases=phases,
        peak_hbm_gib=peak_gib(dev), kernel_tol=KERNEL_TOL)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
