"""Minimum key-switch bytes, the peak table, and the refusals of ``run.py``."""

from __future__ import annotations

import json
import subprocess
import sys
import types

import pytest
from bench_chip_util import BENCH, REPO
from chipbench import cell as C
from chipbench import spec, yardstick


@pytest.mark.parametrize("n,level,alpha,want", [
    # lstm, top level: l+1 = 14, beta = 2, ext = 21 limbs -> (14 + 28 + 2*21) limbs of N words
    (1 << 16, 13, 7, (14 + 28 + 42) * 65536 * 4),  # 22,020,096 B
    # lstm, level 9: beta = ceil(10/7) = 2 -> (10 + 20 + 2*17)
    (1 << 16, 9, 7, (10 + 20 + 34) * 65536 * 4),
    # dblookup, top level: l+1 = 9, beta = 3, ext = 12 -> (9 + 18 + 3*12)
    (1 << 14, 8, 3, (9 + 18 + 36) * 16384 * 4),  # 4,128,768 B
    # dblookup, level 5: beta = 2 -> (6 + 12 + 2*9)
    (1 << 14, 5, 3, (6 + 12 + 18) * 16384 * 4),
])
def test_ks_min_bytes(n, level, alpha, want):
    assert yardstick.ks_min_bytes(n, level, alpha) == want


def test_ks_min_bytes_worked_values():
    assert yardstick.ks_min_bytes(1 << 16, 13, 7) == 22_020_096
    assert yardstick.ks_min_bytes(1 << 14, 8, 3) == 4_128_768


def test_peaks_by_device_kind():
    row = yardstick.peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9 and row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12 and row["hbm_bytes"] == 16e9
    assert "Google Cloud" in row["source"]


@pytest.mark.parametrize("kind", ["TPU v9 imaginary", "cpu", "source"])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError, match="not in peaks.json"):
        yardstick.peaks(kind)


def load_run():
    """``benchmarks/chip/run.py`` under a module name of its own."""
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("chipbench_run_main", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


def test_run_refuses_a_device_kind_without_peaks(monkeypatch, capsys):
    R = load_run()

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary")
    monkeypatch.setattr(R, "claim_tpu", lambda chips: [fake] * chips)
    with pytest.raises(SystemExit) as e:
        R.main(["--workload", "dblookup.match", "--seed", "5", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert "not in peaks.json" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "dblookup.aggregate",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "run.py: no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_a_configuration_that_disagrees_with_its_preset_is_refused():
    cfg = spec.config("dblookup")
    C.build_params(cfg)
    with pytest.raises(ValueError, match="disagrees with the preset"):
        C.build_params(dict(cfg, n=1 << 13))


def test_every_cell_names_files_that_exist():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        assert (BENCH / "configs" / f"{w['config']}.json").is_file()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert "max_err" in spec.limits(w["name"])
        for m in spec.per_layer(bench, w["name"]):
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for c in bench["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
