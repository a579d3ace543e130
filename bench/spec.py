"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (``workloads`` entry) resolves to ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json`` (the limits of
its ``correct``); a metric to its reader ``metrics/<metric>.py``, which
defines ``read(run) -> float | None``; a configuration's reference to
``references/<reference>.py``, which defines ``served_gaps``,
``control_gaps`` and ``weights``; its ``model_type`` to
``archs/<model_type>.py`` (``system.arch``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# bytes of one element, as the program stores it
KV_BYTES = 2          # bfloat16 keys and values
W_BYTES = 2           # bfloat16 matrices
NORM_BYTES = 4        # float32 norm scales


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list


@dataclasses.dataclass
class Run:
    """What a metric reader reads: the cell (its configuration is
    ``cell.config``), the open loop's log, the chip's peaks and, in a
    traced run, the reduced trace (``trace.Events``)."""
    cell: Cell
    log: object
    peak: object
    trace: object
    setup_s: float


def head_dim(cfg: dict) -> int:
    """A configuration's head size: its ``head_dim`` where the file gives
    one, else hidden size over heads (as Mistral and Llama derive it)."""
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def no_path(cfg: dict) -> ValueError:
    return ValueError(f"{cfg['name']}: the program has no path for this "
                      "configuration as its file states it")


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str) -> Cell:
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((ROOT / conf["file"]).read_text()),
        mix=json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(path.parts[-2:]).replace("-", "_")
        .replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """``read`` of ``metrics/<metric>.py``."""
    return _load(BENCH / "metrics" / f"{metric}.py").read


def load_reference(name: str):
    return _load(BENCH / "references" / f"{name}.py")
