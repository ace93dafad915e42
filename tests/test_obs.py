"""Observability suite (`repro.obs`): tracer determinism (same-seed fleet
runs export byte-identical Chrome JSON), zero-overhead disable (no events, no
timeline change), structural validity of every seam's output under chaos
(balanced B/E and async spans through preemption, crashes, gang aborts),
exporter/validator contracts on hand-built traces, metrics-registry
semantics, per-chip shed/fault attribution consistency, and the perf-history
append + trailing-median regression check."""

import json
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro import serve
from repro.core import hardware as H
from repro.core import jobs as J
from repro.core import planner as PL
from repro.core.simulator import lanes_whole_chip, simulate_stream
from repro.fhe import params as P
from repro.obs import (
    MetricsRegistry,
    Tracer,
    append_rows,
    check_regression,
    dumps_chrome_trace,
    load_history,
    parse_row_name,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.serve.faults import FaultPlan

# cheap presets only (service sims are memoised per (chip, workload, kind))
SHALLOW = ("matmul", "lola_mnist_plain", "dblookup")
DEEP = ("lstm",)

RETRY = serve.RetryPolicy(max_attempts=3, backoff_base=1_000.0,
                          backoff_factor=2.0, backoff_cap=64_000.0)


def _random_jobs(seed: int, n: int = 24, deep_frac: float = 0.25) -> list:
    rng = random.Random(seed)
    jobs, t = [], 0
    for i in range(n):
        t += rng.randint(1_000, 40_000)
        pool = DEEP if rng.random() < deep_frac else SHALLOW
        jobs.append(J.make_job(rng.choice(pool), priority=rng.randint(0, 2),
                               arrival_cycle=t, job_id=i, tenant_id=i % 3))
    return jobs


def _faults() -> FaultPlan:
    return (FaultPlan.single_crash(chip=1, at=2.0e5, down=8.0e5)
            .merged(FaultPlan.straggler(chip=0, at=1.0e5, span=6.0e5))
            .merged(FaultPlan.flaky(chip=2, times=(3.0e5,))))


def _fleet(tracer=None, seed: int = 11, n_chips: int = 3):
    return serve.serve_cluster(_random_jobs(seed), H.FLASH_FHE,
                               n_chips=n_chips, router="jsq", seed=3,
                               gang_max_chips=2, faults=_faults(),
                               retry=RETRY, tracer=tracer)


# ---------------------------------------------------------------------------
# tracer core: disabled no-op, track interning, span balance
# ---------------------------------------------------------------------------


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    assert not tr
    tr.name_process(1, "chip")
    tr.complete("seg", 0.0, 5.0)
    tr.begin("down")
    tr.end("down")
    tr.instant("shed")
    tr.counter("backlog", {"total": 1.0})
    tr.job_begin(0, "matmul")
    tr.job_end(0, "matmul", "DONE")
    with tr.span("nested"):
        pass
    assert tr.events == []
    assert tr.process_names == {}


def test_track_ids_interned_per_registration_order():
    tr = Tracer()
    assert tr.track(1, "chip") == 0
    assert tr.track(1, "affiliation-0") == 1
    assert tr.track(2, "chip") == 0           # tids are per-process
    assert tr.track(1, "chip") == 0           # interned, not re-allocated
    assert tr.thread_names[(1, 1)] == "affiliation-0"


def test_span_closes_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("route", pid=0, tid=0):
            raise RuntimeError("boom")
    assert [e["ph"] for e in tr.events] == ["B", "E"]
    assert validate_chrome_trace(to_chrome_trace(tr)) == []


def test_bound_clock_is_default_timestamp_source():
    tr = Tracer()
    t = {"now": 0.0}
    tr.bind_clock(lambda: t["now"])
    tr.instant("a")
    t["now"] = 42.0
    tr.instant("b")
    tr.instant("c", ts=7.0)                   # explicit ts wins — but note it
    assert [e["ts"] for e in tr.events] == [0.0, 42.0, 7.0]


# ---------------------------------------------------------------------------
# exporter + validator contracts
# ---------------------------------------------------------------------------


def test_export_shape_and_metadata_first():
    tr = Tracer()
    tr.name_process(1, "chip0")
    tid = tr.track(1, "chip")
    tr.complete("seg", 10.0, 20.0, pid=1, tid=tid)
    obj = to_chrome_trace(tr)
    assert obj["metadata"] == {"clock": "sim-cycles"}
    phases = [e["ph"] for e in obj["traceEvents"]]
    assert phases[: phases.index("X")] == ["M"] * phases.index("X")
    names = [e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] in ("process_name", "thread_name")]
    assert names == ["chip0", "chip"]
    # canonical dumps round-trips and is stable across identical recordings
    assert json.loads(dumps_chrome_trace(tr)) == json.loads(dumps_chrome_trace(tr))


def test_validator_catches_structural_problems():
    unbalanced = Tracer()
    unbalanced.begin("down", ts=1.0, pid=1)
    assert any("unclosed" in p
               for p in validate_chrome_trace(to_chrome_trace(unbalanced)))

    negative = Tracer()
    negative.complete("seg", 10.0, 5.0, pid=1)          # end < start
    assert any("dur" in p
               for p in validate_chrome_trace(to_chrome_trace(negative)))

    crossed = Tracer()
    crossed.begin("a", ts=0.0, pid=1)
    crossed.events.append({"ph": "E", "name": "b", "ts": 1.0, "pid": 1,
                           "tid": 0})
    assert any("closes" in p
               for p in validate_chrome_trace(to_chrome_trace(crossed)))

    orphan = Tracer()
    orphan.job_end(7, "matmul", "DONE", ts=0.0)          # e before b
    assert any("async" in p
               for p in validate_chrome_trace(to_chrome_trace(orphan)))

    # the exporter's stable ts-sort repairs recording order, so non-monotone
    # timestamps can only reach the validator in an externally-built dict
    def _inst(name, ts, tid):
        return {"ph": "i", "name": name, "ts": ts, "pid": 1, "tid": tid,
                "s": "t", "args": {}}
    skewed = {"traceEvents": [_inst("late", 10.0, 0), _inst("early", 5.0, 0)]}
    assert any("monotone" in p for p in validate_chrome_trace(skewed))
    # separate tracks are independent clocks
    split = {"traceEvents": [_inst("late", 10.0, 0), _inst("early", 5.0, 1)]}
    assert validate_chrome_trace(split) == []


# ---------------------------------------------------------------------------
# seam: core simulator
# ---------------------------------------------------------------------------


def test_simulator_tracing_unchanged_cycles_and_valid_trace():
    p = P.workload_params("lola_mnist_plain")
    instrs = PL.workload_stream("lola_mnist_plain", p, mode="hw")
    chip = H.FLASH_FHE
    base = simulate_stream(instrs, chip, lanes_whole_chip(chip))
    tr = Tracer()
    traced = simulate_stream(instrs, chip, lanes_whole_chip(chip), tracer=tr)
    assert traced.cycles == base.cycles               # observation changes nothing
    assert tr.events
    # a second invocation lands on a fresh process, so per-track timestamps
    # stay monotone even though both timelines start at ts 0
    simulate_stream(instrs, chip, lanes_whole_chip(chip), tracer=tr)
    assert len({e["pid"] for e in tr.events}) == 2
    assert validate_chrome_trace(to_chrome_trace(tr)) == []


# ---------------------------------------------------------------------------
# seam: fleet serving — determinism, zero overhead, chaos validity
# ---------------------------------------------------------------------------


def test_fleet_trace_byte_identical_across_same_seed_runs(tmp_path):
    tr1, tr2 = Tracer(), Tracer()
    _fleet(tr1)
    _fleet(tr2)
    blob1, blob2 = dumps_chrome_trace(tr1), dumps_chrome_trace(tr2)
    assert blob1 == blob2
    assert validate_chrome_trace(to_chrome_trace(tr1)) == []
    path = write_chrome_trace(tr1, str(tmp_path / "fleet.json"))
    assert open(path).read() == blob1


def test_disabled_tracer_does_not_change_the_timeline():
    tr = Tracer()
    traced = _fleet(tr)
    bare = _fleet(tracer=None)
    off = _fleet(Tracer(enabled=False))
    for other in (bare, off):
        assert other.makespan == traced.makespan
        assert [(je.job.job_id, je.state, je.completion) for je in other.jobs] \
            == [(je.job.job_id, je.state, je.completion) for je in traced.jobs]
    assert traced.fault_counts == bare.fault_counts


def test_fleet_trace_covers_every_seam():
    tr = Tracer()
    res = _fleet(tr)
    names = {e["name"] for e in tr.events}
    assert {"routed", "down", "backlog_cycles"} <= names
    assert any(e["ph"] == "i" and e["name"] == "retry" for e in tr.events)
    # every job's async span opened and closed exactly once (retries reuse it)
    begins = [e["id"] for e in tr.events if e["ph"] == "b"]
    ends = [e["id"] for e in tr.events if e["ph"] == "e"]
    assert sorted(begins) == sorted(ends) == sorted(range(len(res.jobs)))
    # chips appear as processes 1..n, the router as process 0
    assert set(tr.process_names) == {0, 1, 2, 3}


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_chips=st.integers(min_value=2, max_value=4))
def test_trace_structurally_valid_under_chaos(seed, n_chips):
    """Preemption, crash-requeue, gang abort, retries — whatever the chaos
    config produces, the exported spans balance and timestamps stay monotone."""
    jobs = _random_jobs(seed, 12)
    cfg = serve.FaultConfig(seed=seed, horizon_cycles=4e6, mtbf_cycles=1.2e6,
                            mttr_cycles=2e5, transient_rate=1.0, slow_rate=0.5,
                            slow_span_cycles=3e5, slow_factor=2.0)
    tr = Tracer()
    serve.serve_cluster(jobs, H.FLASH_FHE, n_chips=n_chips, router="jsq",
                        faults=cfg, retry=RETRY, tracer=tr)
    assert validate_chrome_trace(to_chrome_trace(tr)) == []


# ---------------------------------------------------------------------------
# metrics registry + per-chip attribution
# ---------------------------------------------------------------------------


def test_metrics_registry_semantics():
    reg = MetricsRegistry()
    c = reg.counter("serve.shed", labels=("reason", "chip"))
    c.inc(reason="timeout", chip=1)
    c.inc(2, reason="timeout", chip=2)
    c.inc(reason="token_bucket", chip=-1)
    assert c.total() == 4.0
    assert c.group_sum("reason") == {"timeout": 3.0, "token_bucket": 1.0}
    assert c.by_label("chip")["1"] == {("timeout",): 1.0}
    with pytest.raises(ValueError):
        c.inc(reason="timeout")                       # missing label
    with pytest.raises(ValueError):
        c.inc(-1.0, reason="timeout", chip=1)         # counters only go up
    assert reg.counter("serve.shed", labels=("reason", "chip")) is c
    with pytest.raises(ValueError):
        reg.counter("serve.shed", labels=("reason",))  # label-set mismatch

    g = reg.gauge("backlog")
    g.set(5.0)
    g.max(3.0)
    g.max(9.0)
    g.add(1.0)
    assert g.value() == 10.0

    h = reg.histogram("lat", buckets=(10.0, 100.0))
    for v in (5.0, 50.0, 500.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3
    assert snap["sum"] == 555.0
    assert h.mean == 185.0
    assert reg.snapshot()["histograms"]["lat"]["count"] == 3


def test_cluster_books_live_in_metrics_and_sum_per_chip():
    res = _fleet()
    # derived views agree with each other and with validate()'s invariants
    assert sum(res.shed_reasons.values()) \
        == sum(v for c in res.shed_reasons_by_chip.values() for v in c.values())
    agg = {}
    for counts in res.fault_counts_by_chip.values():
        for k, v in counts.items():
            agg[k] = agg.get(k, 0) + v
    assert agg == res.fault_counts
    assert res.fault_counts_by_chip[1]["crashes"] == 1     # scripted plan
    assert res.fault_counts_by_chip[0]["slow_windows"] == 1
    # the registry snapshot travels on the result
    assert "serve.jobs_completed" in res.metrics["counters"]
    n_done = sum(1 for je in res.jobs if je.completion is not None)
    assert res.metrics["histograms"]["serve.turnaround_cycles"]["count"] == n_done
    res.validate()


def test_door_sheds_attributed_to_no_chip():
    jobs = _random_jobs(5, 20)
    adm = serve.AdmissionConfig(tenant_rate_per_mcycle=0.5, tenant_burst=1.0)
    res = serve.serve_cluster(jobs, H.FLASH_FHE, n_chips=2, router="jsq",
                              admission=adm)
    assert res.shed_reasons.get("token_bucket", 0) > 0
    assert set(res.shed_reasons_by_chip) == {-1}           # door, not a chip
    res.validate()


# ---------------------------------------------------------------------------
# perf history
# ---------------------------------------------------------------------------


def test_parse_row_name_three_way_split():
    assert parse_row_name("cluster.shallow.jsq.chips4.p99") \
        == ("cluster", "shallow.jsq.chips4", "p99")
    assert parse_row_name("bench.metric") == ("bench", "", "metric")
    assert parse_row_name("metric") == ("metric", "", "metric")


def test_history_append_and_load_roundtrip(tmp_path):
    path = str(tmp_path / "h.json")
    assert load_history(path) == []
    n = append_rows(path, [("b.s.lat", 10.0), ("b.s.note", "text")],
                    commit="abc1234", date="2026-08-09")
    assert n == 1                                          # non-numeric skipped
    rows = load_history(path)
    assert rows == [{"bench": "b", "scenario": "s", "metric": "lat",
                     "value": 10.0, "commit": "abc1234", "date": "2026-08-09"}]
    append_rows(path, [("b.s.lat", 11.0)], commit="def", date="2026-08-10")
    assert [r["value"] for r in load_history(path)] == [10.0, 11.0]


def _rows(metric, values):
    return [{"bench": "b", "scenario": "s", "metric": metric, "value": v}
            for v in values]


def test_check_regression_median_band():
    assert check_regression(_rows("lat", [100, 102, 98, 101])) == []
    problems = check_regression(_rows("lat", [100, 102, 98, 150]))
    assert len(problems) == 1 and "b.s.lat" in problems[0]
    # symmetric: a too-good improvement is also a behaviour change
    assert check_regression(_rows("lat", [100, 102, 98, 50]))
    # single-row groups and wall-clock metrics pass vacuously
    assert check_regression(_rows("lat", [100])) == []
    assert check_regression(_rows("wall_ms", [100, 500])) == []
    assert check_regression(_rows("total_seconds", [100, 500])) == []
    # the window bounds the baseline: old outliers age out of the median
    vals = [1000] + [100] * 8 + [101]
    assert check_regression(_rows("lat", vals), window=8) == []


def test_repo_history_file_is_clean():
    """The committed BENCH_HISTORY.json must parse and pass its own gate."""
    rows = load_history("BENCH_HISTORY.json")
    assert rows, "BENCH_HISTORY.json missing or empty"
    assert check_regression(rows) == []
