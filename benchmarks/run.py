"""Benchmark harness: one entry per paper table/figure.

Emits ``name,value,derived`` CSV rows (derived=1 marks numbers reconstructed
from the paper's reported ratios rather than simulated from architecture).

  python -m benchmarks.run                 # full paper-figure suite + all benches
  python -m benchmarks.run --smoke         # fast CI pass: fused-KS + hoisting row
                                           #   + fleet scale-out/hetero/gang smoke
  python -m benchmarks.run --out FILE.csv  # also write the rows to FILE.csv
"""

from __future__ import annotations

import argparse
import sys
import time

from . import fusedks_bench


class _Emitter:
    def __init__(self, out_path: str | None):
        self._fh = open(out_path, "w") if out_path else None
        self.rows: list[tuple[str, object]] = []  # every emitted (name, value)

    def __call__(self, name: str, value, derived: int = 0):
        self.rows.append((name, value))
        if isinstance(value, float):
            value = f"{value:.6g}"
        row = f"{name},{value},{derived}"
        print(row)
        if self._fh:
            self._fh.write(row + "\n")

    def close(self):
        if self._fh:
            self._fh.close()


def emit_fusedks(emit, smoke: bool, iters: int) -> None:
    """Fused vs staged key-switch: the dispatch-count/wall-clock comparison."""
    for cfg, row in fusedks_bench.run(smoke=smoke, iters=iters).items():
        for key in (
            "bitexact", "dispatches_fused", "dispatches_staged",
            "dispatch_reduction", "wall_ms_fused", "wall_ms_staged",
        ):
            emit(f"fusedks.{cfg}.{key}", row[key])


def emit_hoisting(emit, smoke: bool, iters: int) -> None:
    """Hoisted vs per-rotation rotations: amortisation rows.

    --smoke runs one SMALL group config only (seconds) — the N=2^14 CtS-stage
    gate configs are owned by the dedicated hoisting-smoke CI job
    (`benchmarks.hoisting_bench --smoke`), which is also the only place the
    gates can actually fail the build; duplicating the heavy run here would
    cost minutes per push for an advisory CSV row."""
    from . import hoisting_bench

    if smoke:
        rows = [hoisting_bench.bench_group(1 << 10, 8, 2, 12, iters=iters)]
    else:
        rows = hoisting_bench.run(smoke=False, iters=iters)
    for r in rows:
        for key in ("bitexact", "ext_ntt_hoisted", "ext_ntt_staged",
                    "dispatch_ratio", "wall_ms_hoisted", "wall_ms_staged",
                    "wall_speedup"):
            emit(f"hoisting.{r['config']}.{key}", r[key])
    if not smoke:
        failures = hoisting_bench.check_gates(rows)
        emit("hoisting.gates_dispatch_and_wallclock", int(not failures))


def emit_serving(emit, smoke: bool) -> None:
    """Multi-tenant serving: SLO metrics per (scenario, chip) + claim check."""
    from . import serving_bench

    rows = serving_bench.run(smoke=smoke)
    for r in rows:
        prefix = f"serving.{r['scenario']}.{r['chip']}"
        for key in ("latency_p50_cycles", "latency_p99_cycles", "queue_p99_cycles",
                    "makespan_mcycles", "throughput_jobs_per_mcycle",
                    "util_mean", "fairness_jain", "n_preemptions"):
            emit(f"{prefix}.{key}", r[key])
    failures = serving_bench.check_paper_claim(rows)
    emit("serving.claim_flash_beats_craterlake", int(not failures))


def emit_multischeme(emit, smoke: bool) -> None:
    """Mixed CKKS+BGV serving: per-(scenario, chip) SLOs + the scheme gates."""
    from . import multischeme_bench

    rows = multischeme_bench.run(smoke=smoke)
    for r in rows:
        prefix = f"multischeme.{r['scenario']}.{r['chip']}"
        for key in ("n_ckks", "n_bgv", "latency_p99_shallow_cycles",
                    "latency_p99_cycles", "makespan_mcycles", "util_mean",
                    "n_preemptions"):
            emit(f"{prefix}.{key}", r[key])
    failures = multischeme_bench.check_paper_claim(rows)
    emit("multischeme.claim_flash_beats_craterlake", int(not failures))


def emit_cluster(emit, smoke: bool) -> None:
    """Fleet scale-out + heterogeneous/gang scenarios: throughput/p99 per
    (scenario, fleet, router, chips, gang) row, plus the four gates."""
    from . import cluster_bench

    rows = cluster_bench.run(smoke=smoke)
    for r in rows:
        prefix = (f"cluster.{r['scenario']}.{r['fleet']}.{r['router']}"
                  f".chips{int(r['n_chips'])}.gang{int(r['gang'])}")
        for key in ("latency_p99_cycles", "latency_p99_deep_cycles",
                    "queue_p99_cycles", "makespan_mcycles",
                    "throughput_jobs_per_mcycle", "chip_util_imbalance",
                    "fairness_jain_chips", "n_cold_starts", "n_gang_jobs"):
            emit(f"{prefix}.{key}", r[key])
    failures = cluster_bench.check_gates(rows)
    emit("cluster.gates_scaleout_hetero_gang", int(not failures))


def emit_overload(emit, smoke: bool) -> None:
    """Overload/admission SLO table: goodput, drop rate, per-kind p99, and
    peak backlog per (chips, load, admission) diurnal run, plus the admission
    gates (flat tail + goodput floor with admission, divergence without)."""
    from . import overload_bench

    rows = overload_bench.run(smoke=smoke)
    for r in rows:
        prefix = (f"overload.{r['scenario']}.chips{int(r['n_chips'])}"
                  f".load{r['load_x']:g}.adm{int(r['admission'])}")
        for key in ("goodput_frac", "drop_rate", "drop_rate_shallow", "drop_rate_deep",
                    "latency_p99_shallow_cycles", "latency_p99_deep_cycles",
                    "peak_backlog_mcycles", "fairness_jain",
                    "time_to_shed_p99_cycles", "n_completed_shallow"):
            emit(f"{prefix}.{key}", r[key])
    failures = overload_bench.check_gates(rows)
    emit("overload.gates_flat_tail_goodput_divergence", int(not failures))


def emit_faults(emit, smoke: bool) -> None:
    """Fault-tolerance table: goodput/loss/retry/availability per scenario
    (fault-free baseline, crash with and without recovery, flaky, straggler),
    plus the recovery gates (goodput floor through the outage, loss
    divergence without recovery, retries recorded)."""
    from . import fault_bench

    rows = fault_bench.run(smoke=smoke)
    for r in rows:
        prefix = f"faults.{r['scenario']}.chips{int(r['n_chips'])}"
        for key in ("goodput_frac", "n_failed", "retries_total",
                    "n_retried_jobs", "wasted_mcycles",
                    "checkpoint_saved_mcycles", "availability",
                    "downtime_mcycles", "latency_p99_shallow_cycles"):
            emit(f"{prefix}.{key}", r[key])
    failures = fault_bench.check_gates(rows)
    emit("faults.gates_goodput_loss_divergence", int(not failures))


def emit_paper_figs(emit) -> None:
    from . import paper_figs

    fig9 = paper_figs.fig9_single_workload()
    emit("fig9.deep_geomean_vs_craterlake", fig9["deep_geomean_vs_craterlake"])
    emit("fig9.deep_geomean_vs_f1plus", fig9["deep_geomean_vs_f1plus"])
    for w, row in fig9["rows"].items():
        emit(f"fig9.{w}.flash_fhe_ms", row["flash_fhe_ms"])
        emit(f"fig9.{w}.craterlake_over_ff", row["craterlake_over_ff"])
        emit(f"fig9.{w}.f1plus_over_ff", row["f1plus_over_ff"])

    fig10 = paper_figs.fig10_7nm()
    emit("fig10.ff_logreg_ms", fig10["ff_logreg_ms"])
    emit("fig10.ff_resnet20_ms", fig10["ff_resnet20_ms"])
    emit("fig10.ark_logreg_ms", fig10["ark_logreg_ms_derived"], 1)
    emit("fig10.perf_per_area_vs_ark_logreg", fig10["perf_per_area_vs_ark_logreg"], 1)

    fig11 = paper_figs.fig11_ntt_hmul()
    emit("fig11.ntt_ops_per_s", fig11["ntt_ops_per_s"])
    emit("fig11.hmul_ops_per_s", fig11["hmul_ops_per_s"])
    emit("fig11.tensorfhe_ntt_ops_per_s", fig11["tensorfhe_ntt_derived"], 1)

    fig12 = paper_figs.fig12_multi_shallow()
    emit("fig12.peak_multi_job_speedup", fig12["peak_speedup"])
    for k, v in fig12["per_job_count"].items():
        emit(f"fig12.jobs{k}.makespan_speedup", v["makespan_speedup"])

    fig8 = paper_figs.fig8_cache_sweep()
    emit("fig8.dnum1_saturates_at_320MB", int(fig8["dnum1_saturates_at_320MB"]))
    for dnum, curve in fig8["curves_ms"].items():
        for cap, ms in curve.items():
            emit(f"fig8.{dnum}.cache{cap}MB_ms", ms)

    t3 = paper_figs.table3_area()
    emit("table3.total_14nm_mm2", t3["total_14nm_mm2"])
    emit("table3.swift_logic_fraction", t3["swift_logic_fraction"])
    emit("table3.claim_under_7pct", int(t3["claim_under_7pct"]))

    fig13 = paper_figs.fig13_power()
    emit("fig13.total_w", fig13["total_w"])
    emit("fig13.vs_craterlake", fig13["vs_craterlake"])

    pre = paper_figs.preemption_study()
    emit("preemption.shallow_turnaround_speedup", pre["shallow_avg_turnaround_speedup"])

    perf = paper_figs.perf_beyond_paper()
    for w, row in perf.items():
        emit(f"perf.{w}.baseline_ms", row["baseline_ms"])
        emit(f"perf.{w}.optimized_ms", row["optimized_ms"])
        emit(f"perf.{w}.speedup", row["speedup"])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI pass: fused-vs-staged key-switch (small ring) "
                         "+ a small hoisted-rotation group row (the N=2^14 "
                         "CtS-stage GATES run only in benchmarks.hoisting_bench) "
                         "+ fleet scale-out/hetero/gang smoke (all four cluster "
                         "gates enforced) + mixed CKKS/BGV serving smoke (scheme "
                         "gates enforced) + diurnal overload/admission smoke "
                         "(flat-tail/goodput/divergence gates enforced) + "
                         "fault-tolerance smoke (recovery goodput/loss gates "
                         "enforced)")
    ap.add_argument("--out", default=None, help="also write CSV rows to this file")
    ap.add_argument("--iters", type=int, default=3, help="timing iterations per config")
    ap.add_argument("--history", nargs="?", const="BENCH_HISTORY.json", default=None,
                    metavar="FILE",
                    help="append every emitted row to the perf-history JSON "
                         "(default FILE: BENCH_HISTORY.json); run "
                         "tools/bench_history.py check-regression afterwards "
                         "to compare against the trailing median")
    args = ap.parse_args(argv)

    emit = _Emitter(args.out)
    t0 = time.time()
    try:
        emit_fusedks(emit, smoke=args.smoke, iters=args.iters)
        emit_hoisting(emit, smoke=args.smoke, iters=args.iters)
        emit_cluster(emit, smoke=args.smoke)
        emit_multischeme(emit, smoke=args.smoke)
        emit_overload(emit, smoke=args.smoke)
        emit_faults(emit, smoke=args.smoke)
        if not args.smoke:
            emit_paper_figs(emit)
            emit_serving(emit, smoke=False)
        emit("bench.total_seconds", time.time() - t0)
    finally:
        emit.close()
    if args.history:
        from repro.obs import history
        n = history.append_rows(args.history, emit.rows)
        print(f"# appended {n} rows to {args.history}", file=sys.stderr)


if __name__ == "__main__":
    main(sys.argv[1:])
