"""Trace reduction on a synthetic two-job trace (``trace_fixture.json``).

Window [0, 2000] ns.  Device intervals [200,300] and [280,380] overlap, so the
union is 180 + 50 + 100 + 200 = 530 ns: idle share 73.5%.  The two events
outside the window do not count.  Idle gaps and the host span around each
gap's middle: [0,200] and [380,600] in the first mul (200 + 220 ns),
[650,1500] between ops inside the second job (850), [1600,1700] in the second
mul (100), [1900,2000] after the last op (100).
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest
from bench_chip_util import BENCH
from chipbench import spec
from chipbench import trace as tr

FIXTURE = json.loads((Path(__file__).parent / "trace_fixture.json").read_text())
FAMILIES = tr.load_families(BENCH / "kernel_families.json")
PEAKS = {"hbm_bytes_per_s": 819e9}
KS_BYTES = 61425.0  # 2 jobs x 61425 B at 819 GB/s = 150 ns: half of the 300 ns keyswitch time


@pytest.fixture(scope="module")
def summary():
    return tr.summarize(FIXTURE, FAMILIES, KS_BYTES, PEAKS)


def test_window_jobs_and_op_count(summary):
    assert summary.jobs == 2
    assert summary.window_s == pytest.approx(2000e-9)
    assert summary.device_ops == 5


def test_busy_is_the_union_of_device_intervals(summary):
    assert summary.busy_s == pytest.approx(530e-9)


def test_family_sums(summary):
    assert summary.family_s["keyswitch"] == pytest.approx(300e-9)
    assert summary.family_s["poly"] == pytest.approx(200e-9)


def test_gaps_go_to_the_enclosing_host_span(summary):
    gaps = dict(summary.gap_top)
    assert gaps == pytest.approx({"job_glue": 950e-9, "mul": 520e-9})
    assert sum(gaps.values()) == pytest.approx(summary.window_s - summary.busy_s)


def test_top_device_ops(summary):
    assert summary.device_top[0] == ["jit_fused_moddown_pallas", pytest.approx(200e-9)]
    assert [k for k, _ in summary.device_top] == [
        "jit_fused_moddown_pallas", "jit_fused_ks_pallas", "jit_ntt_pallas",
        "jit_mulmod_pallas", "jit_concatenate"]


@pytest.mark.parametrize("name,want", [
    ("device_ops_per_job", 2.5),
    ("device_idle_pct", 73.5),
    ("ks_device_ms_per_job", 1.5e-4),
    ("poly_device_ms_per_job", 1.0e-4),
    ("keyswitch_roofline", 50.0),
])
def test_metric_readers(summary, name, want):
    assert spec.reader(name)(summary) == pytest.approx(want)


def test_readers_with_nothing_to_read_return_nothing():
    events = {"host": FIXTURE["host"],
              "device": [e for e in FIXTURE["device"] if "concatenate" in e[2]]}
    s = tr.summarize(events, FAMILIES, 0.0, PEAKS)
    for name in ("ks_device_ms_per_job", "keyswitch_roofline", "poly_device_ms_per_job"):
        assert spec.reader(name)(s) is None


def test_busy_is_averaged_over_chips():
    dev = [["/device:TPU:0", "a", "m", 0, 1000], ["/device:TPU:1", "b", "m", 0, 500]]
    s = tr.summarize({"host": [["job", 0, 1000]], "device": dev}, FAMILIES, 0.0, PEAKS)
    assert s.busy_s == pytest.approx(750e-9)
    assert dict(s.gap_top) == pytest.approx({"job_glue": 250e-9})


def test_a_trace_without_jobs_or_device_ops_is_refused():
    with pytest.raises(ValueError, match="no job span"):
        tr.summarize({"host": [], "device": FIXTURE["device"]}, FAMILIES, 0.0, PEAKS)
    with pytest.raises(ValueError, match="no device op"):
        tr.summarize({"host": FIXTURE["host"], "device": []}, FAMILIES, 0.0, PEAKS)


@pytest.mark.parametrize("intervals,union,gaps", [
    ([], 0.0, [(0, 10)]),
    ([(2, 4), (3, 5), (7, 8)], 4.0, [(0, 2), (5, 7), (8, 10)]),
    ([(0, 10), (1, 2)], 10.0, []),
])
def test_union_and_gaps(intervals, union, gaps):
    assert tr.union_ns(intervals) == union
    assert tr.idle_gaps(intervals, 0, 10) == gaps
