"""``BENCHMARK.json`` keeps to its contract, every name in it resolves to
its files, and the entry refuses to measure without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_./-]+$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_sizes():
    assert set(BENCH) == KEYS["top"]
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[part]:
            extra = {"workloads"} if part in ("end_to_end", "per_layer") \
                else set()
            assert KEYS[part] <= set(entry) <= KEYS[part] | extra, entry
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert len(BENCH["command"]) <= 32
    assert all(_line(w) for w in BENCH["command"])


def test_names_units_and_strings():
    names = [e["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in BENCH[part]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in BENCH[part]]
        assert len(seen) == len(set(seen))
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert _line(c["why"]) and _line(c["source"]) and len(c["reduced"]) <= 16
    for w in BENCH["workloads"]:
        assert _line(w["why"]) and w["chips"] in (1, 4)
    for m in BENCH["per_layer"]:
        assert _line(m["layer"])


def test_metric_sources_and_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = spec.resolve(cell)
    assert c.config["name"] == {w["name"]: w for w in
                                BENCH["workloads"]}[cell]["config"]
    assert c.mix["arrivals"]["rate_per_s"] > 0
    assert c.limits and all(k in ("logit_gap_max", "logit_gap_mean")
                            and v > 0 for k, v in c.limits.items())
    spec.load_reference(c.config["reference"])
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.load_reader(m["name"]))


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert "workloads" not in moved or cell in moved["workloads"], \
                (m["name"], cell)


def test_configs_and_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert used == {c["name"] for c in BENCH["configs"]}
    assert len(files) == len(set(files))
    for f in files:
        assert f.split("/")[0] in BENCH["paths"]
    for path in BENCH["paths"]:
        assert FILE.match(path) and ".." not in path
        for p in (spec.ROOT / path).rglob("*"):
            if "__pycache__" not in p.parts:
                assert FILE.match(str(p.relative_to(spec.ROOT))), p
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def _run_entry(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script), "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_refuses_without_a_tpu():
    proc = _run_entry(spec.ROOT, spec.BENCH / "run.py")
    assert proc.returncode != 0
    assert not proc.stdout.strip()
    assert "needs 1 TPU chip" in proc.stderr


def test_entry_fails_with_only_the_benchmark(tmp_path):
    """A directory holding BENCHMARK.json and bench/ alone (no program)."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_entry(tmp_path, tmp_path / "bench" / "run.py")
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    json.loads((tmp_path / "BENCHMARK.json").read_text())
