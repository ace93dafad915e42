"""Traffic written only as data reaches every op the interpreter knows:
rotations and BSGS plaintext matvecs (with exactly the Galois keys they need),
several clients per job, and BGV's exact arithmetic mod t.  Each new cell
below is a configuration, a traffic file and a limits file in a temporary
directory, run at N=2^10 in the Pallas interpreter through the benchmark's
own job and check."""

from __future__ import annotations

import json
import shutil

import numpy as np
import pytest
from bench_chip_util import BENCH, control_check, run_once, small_cell
from chipbench import circuit

CELLS = {
    "dblookup.rotmat": {
        "config": "dblookup",
        "traffic": {
            "why": "rotate, BSGS matvec over 6 diagonals, sum; two clients per job", "pool": 3, "clients": 2,
            "inputs": [{"name": "x", "kind": "ct", "depth_used": 0,
                        "values": {"dist": "uniform", "lo": -1, "hi": 1}},
                       {"name": "W", "kind": "diags", "count": 6,
                        "values": {"dist": "uniform", "lo": -0.5, "hi": 0.5}}],
            "ops": [{"out": "r", "op": "rotate", "args": ["x"], "const": 3},
                    {"out": "y", "op": "matvec", "args": ["x", "W"], "n1": 2},
                    {"out": "z", "op": "add", "args": ["r", "y"]}],
            "outputs": ["z"]},
        "limit": 0.05,
    },
    "exact_count.mix": {
        "config": "exact_count",
        "traffic": {
            "why": "BGV: ct x ct product, modulus switch, add", "pool": 2,
            "inputs": [{"name": "a{i}", "for": 2, "kind": "ct", "depth_used": 0,
                        "values": {"dist": "ints", "lo": 0, "hi": 65536}}],
            "ops": [{"out": "m", "op": "mul", "args": ["a0", "a1"]},
                    {"out": "s", "op": "add", "args": ["m", "a0"]},
                    {"out": "d", "op": "rescale", "args": ["s"]}],
            "outputs": ["d"]},
        "limit": 0.0,
    },
}


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    d = tmp_path_factory.mktemp("ops")
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir()
    shutil.copy(BENCH / "kernel_families.json", d / "kernel_families.json")
    shutil.copy(BENCH / "configs" / "dblookup.json", d / "configs" / "dblookup.json")
    (d / "configs" / "exact_count.json").write_text(json.dumps({
        "preset": "exact_count", "scheme": "bgv", "n": 8192, "L": 4, "dnum": 3, "alpha": 2, "t": 65536,
        "security_bits": 80, "source": "test", "reduced": {}}))
    workloads = []
    for name, c in CELLS.items():
        traffic = name.split(".")[1]
        (d / "traffic" / f"{traffic}.json").write_text(json.dumps(c["traffic"]))
        (d / "limits" / f"{name}.json").write_text(json.dumps({"max_err": {"limit": c["limit"]}}))
        workloads.append({"name": name, "config": c["config"], "traffic": traffic, "chips": 1, "why": "test"})
    (d / "BENCHMARK.json").write_text(json.dumps({
        "workloads": workloads, "per_layer": [],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
                        "source": "host_clock"}]}))
    return d


@pytest.fixture(scope="module")
def cells(base):
    return {name: small_cell(name, base=base) for name in CELLS}


def test_keys_are_exactly_the_rotations_used(cells):
    c = cells["dblookup.rotmat"]
    assert c.circuit.rotations == (1, 2, 3, 4)  # rotate 3; babies {1}, giants {2, 4}
    assert len(c.ctx.keys.gks) == 4
    assert cells["exact_count.mix"].ctx.keys.gks == {}


@pytest.mark.parametrize("name,seed", [("dblookup.rotmat", 5), ("dblookup.rotmat", 2**31 + 5),
                                       ("exact_count.mix", 6), ("exact_count.mix", 2**31 + 6)])
def test_data_only_cell_decrypts_to_the_reference(cells, name, seed):
    rec = run_once(cells[name], seed)
    assert rec["correct"], rec["checks"]
    if CELLS[name]["limit"] == 0.0:
        assert rec["checks"]["max_err"]["value"] == 0.0
    else:
        assert rec["checks"]["max_err"]["value"] < CELLS[name]["limit"] / 10


@pytest.mark.parametrize("name", list(CELLS))
def test_data_only_cell_control_is_not_correct(cells, name):
    res = control_check(cells[name], 8)
    assert res["failed"] == res["checked"] == 1


def test_a_job_serves_its_clients_in_turn(cells):
    c = cells["dblookup.rotmat"]
    assert [c.served(j) for j in range(3)] == [[0, 1], [2, 0], [1, 2]]


def test_negacyclic_reference_wraps_with_a_sign():
    ev = circuit.NumpyEval(t=16)
    x = np.array([0, 0, 0, 1])  # x^3
    assert list(ev.mul(x, x)) == [0, 0, 16 - 1, 0]  # x^6 = -x^2 mod x^4 + 1
    assert list(ev.mul(np.array([3, 1, 0, 0]), np.array([5, 0, 0, 2]))) == [(15 - 2) % 16, 5, 0, 6]
