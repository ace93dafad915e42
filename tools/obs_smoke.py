"""Observability smoke: trace a seeded faulty fleet run, verify, export.

The scenario exercises every tracer seam at once — a 4-chip FLASH-FHE fleet
with cross-chip deep gangs, a mid-run chip crash + recovery, a straggler
window, transient job failures, retries, and admission — then checks the
four properties CI gates on:

  1. **determinism** — two runs with the same seed export byte-identical
     Chrome trace JSON (the tracer records only sim-clock timestamps);
  2. **structural validity** — ``validate_chrome_trace`` finds balanced B/E
     stacks, balanced async spans, monotone per-track timestamps, and only
     known phases;
  3. **zero-overhead disable** — the same run without a tracer produces the
     identical ``ClusterResult`` timeline (makespan and per-job completions);
  4. **consistent books** — per-chip shed/fault attributions sum to the
     fleet-global counters (also asserted inside ``ClusterResult.validate``).

It then writes the trace artifact (open it at https://ui.perfetto.dev),
appends the scenario's headline metrics to the perf history, and runs the
regression check over the file.

    PYTHONPATH=src python tools/obs_smoke.py [--trace-out FILE] [--history FILE]
"""

from __future__ import annotations

import argparse
import random
import sys

from repro import serve
from repro.core import jobs as J
from repro.core.hardware import FLASH_FHE
from repro.obs import (
    Tracer,
    dumps_chrome_trace,
    history,
    to_chrome_trace,
    validate_chrome_trace,
)
from repro.serve.faults import FaultPlan, RetryPolicy

SHALLOW = ("matmul", "lola_mnist_plain", "dblookup")
SEED = 20260809


def make_jobs(seed: int, n: int = 48, deep_frac: float = 0.3) -> list:
    rng = random.Random(seed)
    jobs, t = [], 0
    for i in range(n):
        t += rng.randint(1_000, 30_000)
        wl = "lstm" if rng.random() < deep_frac else rng.choice(SHALLOW)
        jobs.append(J.make_job(wl, priority=rng.randint(0, 2), arrival_cycle=t,
                               job_id=i, tenant_id=i % 3))
    return jobs


def fault_plan() -> FaultPlan:
    return (FaultPlan.single_crash(chip=1, at=2.0e5, down=1.0e6)
            .merged(FaultPlan.straggler(chip=0, at=1.0e5, span=8.0e5, factor=2.0))
            .merged(FaultPlan.flaky(chip=2, times=(3.0e5, 6.0e5))))


def run_fleet(tracer=None):
    return serve.serve_cluster(
        make_jobs(SEED), FLASH_FHE, n_chips=4, router="jsq", seed=3,
        gang_max_chips=2, faults=fault_plan(), retry=RetryPolicy(),
        tracer=tracer, validate=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-out", default="obs_smoke_trace.json")
    ap.add_argument("--history", default="BENCH_HISTORY.json")
    args = ap.parse_args(argv)
    failures: list[str] = []

    tr1 = Tracer()
    res = run_fleet(tr1)
    tr2 = Tracer()
    run_fleet(tr2)
    blob1, blob2 = dumps_chrome_trace(tr1), dumps_chrome_trace(tr2)
    if blob1 != blob2:
        failures.append("same-seed traces are not byte-identical")
    print(f"trace: {len(tr1.events)} events, {len(blob1)} bytes")

    problems = validate_chrome_trace(to_chrome_trace(tr1))
    if problems:
        failures.append(f"trace fails validation: {problems[:5]}")
    else:
        print("trace validates: balanced spans, monotone timestamps")

    bare = run_fleet(tracer=None)
    if bare.makespan != res.makespan:
        failures.append(
            f"disabled tracer changed the timeline: makespan "
            f"{bare.makespan} != {res.makespan}")
    traced_done = sorted((je.job.job_id, je.completion) for je in res.jobs
                         if je.completion is not None)
    bare_done = sorted((je.job.job_id, je.completion) for je in bare.jobs
                       if je.completion is not None)
    if traced_done != bare_done:
        failures.append("disabled tracer changed per-job completions")
    else:
        print(f"zero-overhead check: {len(bare_done)} completions identical "
              "with tracing off")

    # the fault scenario must actually have exercised the seams it claims to
    fc = res.fault_counts
    for key in ("crashes", "transients", "retries"):
        if fc.get(key, 0) < 1:
            failures.append(f"scenario recorded no {key} — seams untested")
    if not res.gangs:
        failures.append("scenario placed no cross-chip gang")

    with open(args.trace_out, "w") as fh:
        fh.write(blob1)
    print(f"wrote {args.trace_out} — open in https://ui.perfetto.dev")

    n_done = sum(1 for je in res.jobs if je.completion is not None)
    rows = [
        ("obs.traced_fleet.makespan_mcycles", res.makespan / 1e6),
        ("obs.traced_fleet.n_completed", float(n_done)),
        ("obs.traced_fleet.n_trace_events", float(len(tr1.events))),
        ("obs.traced_fleet.retries", float(fc.get("retries", 0))),
        ("obs.traced_fleet.jobs_lost", float(fc.get("jobs_lost", 0))),
    ]
    n = history.append_rows(args.history, rows)
    print(f"appended {n} rows to {args.history}")
    problems = history.check_regression(history.load_history(args.history))
    if problems:
        failures.append(f"perf history regressions: {problems}")
    else:
        print("perf history: newest rows within tolerance of trailing median")

    if failures:
        print("\nOBS SMOKE FAILURES:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("\nobs smoke: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
