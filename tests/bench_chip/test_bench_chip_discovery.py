"""A configuration, a traffic mix, a metric and its limits added as new files
are found by name, with no edit to the harness."""

from __future__ import annotations

import json
import shutil
import time

import numpy as np
import pytest
from bench_chip_util import BENCH
from chipbench import cell as C
from chipbench import circuit, spec
from chipbench import trace as tr

from repro.fhe.context import ExecPolicy

CELL = "matmul.square"


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    d = tmp_path_factory.mktemp("newcell")
    for sub in ("configs", "traffic", "metrics", "limits"):
        (d / sub).mkdir()
    shutil.copy(BENCH / "kernel_families.json", d / "kernel_families.json")
    (d / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": CELL, "config": "matmul", "traffic": "square", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "jobs_per_s", "unit": "jobs/s", "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "jobs_seen", "unit": "jobs", "better": "higher", "source": "device_trace",
                       "layer": "test", "moves": "jobs_per_s", "workloads": [CELL]}],
    }))
    (d / "configs" / "matmul.json").write_text(json.dumps({
        "preset": "matmul", "scheme": "ckks", "n": 8192, "L": 2, "dnum": 3, "alpha": 1, "t": None,
        "security_bits": 80, "source": "test", "reduced": {}}))
    (d / "traffic" / "square.json").write_text(json.dumps({
        "why": "x*x + 1/4", "pool": 2,
        "inputs": [{"name": "x", "kind": "ct", "depth_used": 0,
                    "values": {"dist": "uniform", "lo": -1, "hi": 1}}],
        "ops": [{"out": "y", "op": "mul", "args": ["x", "x"]},
                {"out": "z", "op": "add_const", "args": ["y"], "const": 0.25}],
        "outputs": ["z"]}))
    (d / "metrics" / "jobs_seen.py").write_text("def read(s):\n    return float(s.jobs)\n")
    (d / "limits" / f"{CELL}.json").write_text(json.dumps({"max_err": {"limit": 0.01}}))
    return d


def test_new_files_are_found_by_name(base):
    bench = spec.benchmark(base / "BENCHMARK.json")
    assert spec.workload(bench, CELL)["config"] == "matmul"
    assert spec.config("matmul", base)["preset"] == "matmul"
    assert spec.traffic("square", base).outputs == ("z",)
    assert spec.limits(CELL, base) == {"max_err": {"limit": 0.01}}
    assert [m["name"] for m in spec.per_layer(bench, CELL)] == ["jobs_seen"]
    summary = tr.summarize({"host": [["job", 0, 10], ["job", 10, 10]],
                            "device": [["/device:TPU:0", "op", "mod", 1, 2]]},
                           tr.load_families(base / "kernel_families.json"), 0.0, {})
    assert spec.reader("jobs_seen", base)(summary) == 2.0


def test_new_cell_runs_end_to_end(base):
    c = C.Cell(CELL, base=base, bench_path=base / "BENCHMARK.json", policy=ExecPolicy(backend="ref"))
    logs = []
    rec = C.run(c, 2**31 + 11, 0.01, False, time.perf_counter(), "unused", log=logs.append)
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 1
    assert set(rec["metrics"]) == {"jobs_per_s", "setup_s"}
    assert rec["checks"]["max_err"]["value"] < 1e-3
    assert logs[0]["warmup"]["ks_levels"] == [2]


def test_inputs_repeat_for_a_seed_and_differ_across_seeds():
    circ = spec.traffic("match")
    a, _ = circuit.draw_values(circ, 64, np.random.default_rng(9))
    b, _ = circuit.draw_values(circ, 64, np.random.default_rng(9))
    c, _ = circuit.draw_values(circ, 64, np.random.default_rng(10))
    assert all(np.array_equal(a[0][k], b[0][k]) for k in a[0])
    assert any(not np.array_equal(a[0][k], c[0][k]) for k in a[0])


def test_onehot_inputs_hold_one_row_per_slot():
    pool, pts = circuit.draw_values(spec.traffic("aggregate"), 128, np.random.default_rng(3))
    masks = np.stack([pool[0][f"m{i}"] for i in range(64)])
    assert np.all(masks.sum(axis=0) == 1) and len(pts) == 64


@pytest.mark.parametrize("ops,match", [
    ([{"out": "y", "op": "pow", "args": ["x"]}], "unknown op"),
    ([{"out": "y", "op": "mul", "args": ["x", "w"]}], "before they exist"),
    ([{"out": "y", "op": "add_const", "args": ["x"]}], "needs const"),
    ([{"out": "y", "op": "mul", "args": ["x"]}], "takes 2 args"),
])
def test_malformed_traffic_is_refused(tmp_path, ops, match):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"why": "bad", "pool": 1, "outputs": ["y"], "ops": ops,
                             "inputs": [{"name": "x", "kind": "ct", "depth_used": 0,
                                         "values": {"dist": "bits"}}]}))
    with pytest.raises(ValueError, match=match):
        circuit.load(p)
