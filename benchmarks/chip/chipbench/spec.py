"""Finding a cell's files by name: ``BENCHMARK.json`` names the cells and
metrics; everything else sits in files named after them."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from . import circuit

HERE = Path(__file__).resolve().parent.parent  # benchmarks/chip
ROOT = HERE.parent.parent  # the checkout
BENCHMARK = ROOT / "BENCHMARK.json"


def benchmark(path: Path = BENCHMARK) -> dict:
    return json.loads(Path(path).read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, base: Path = HERE) -> dict:
    return json.loads((Path(base) / "configs" / f"{name}.json").read_text())


def traffic(name: str, base: Path = HERE) -> circuit.Circuit:
    return circuit.load(Path(base) / "traffic" / f"{name}.json")


def limits(cell: str, base: Path = HERE) -> dict:
    """{number: {"limit": x, ...}} for the numbers compared in ``cell``."""
    raw = json.loads((Path(base) / "limits" / f"{cell}.json").read_text())
    return {k: v for k, v in raw.items() if isinstance(v, dict) and "limit" in v}


def _in_cell(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def end_to_end(bench: dict, cell: str) -> list[dict]:
    """The cell's end-to-end metrics: those without a ``workloads`` list, or listing it."""
    return [m for m in bench["end_to_end"] if "workloads" not in m or cell in m["workloads"]]


def per_layer(bench: dict, cell: str) -> list[dict]:
    """The per-layer metrics the cell reports: those listing it, and those
    without a list whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"] if _in_cell(m, cell, e2e)]


def reader(name: str, base: Path = HERE):
    """``read(summary) -> float | None`` from ``metrics/<name>.py``."""
    path = Path(base) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
