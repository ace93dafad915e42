"""The lstm.matvec cell rehearsed on the CPU at N=2^10 with the preset's L and
dnum, every kernel in the Pallas interpreter, through the benchmark's own job
and check: sound runs decrypt to the numpy reference, and the control and each
planted fault come out as not correct."""

from __future__ import annotations

import pytest
from bench_chip_util import FAULTS, control_check, plant, run_once, small_cell

CELL = "lstm.matvec"


@pytest.fixture(scope="module")
def cell():
    return small_cell(CELL)


@pytest.mark.parametrize("seed", [7, 2**31 + 3])
def test_rehearsal_decrypts_to_the_reference(cell, seed):
    rec = run_once(cell, seed)
    assert rec["correct"], rec["checks"]
    assert rec["checks"]["max_err"]["value"] < rec["checks"]["max_err"]["limit"] / 10


def test_control_is_not_correct(cell):
    res = control_check(cell, 8)
    assert res["failed"] == res["checked"] == 1
    assert not res["max_err"] <= res["limit"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    plant(fault, monkeypatch)
    rec = run_once(cell, 9)
    assert not rec["correct"]
    assert not rec["checks"]["max_err"]["value"] <= rec["checks"]["max_err"]["limit"]
