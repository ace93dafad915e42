"""Where a cell's host time goes: the program's own spans in a traced window.

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> --seconds <s> [--out <file.jsonl>]

One process on one chip, set up as ``run.py`` sets up a run: the cell's keys,
the seed's inputs and one warm-up job.  Then two windows of ``--seconds``
each, the first untraced and the second under the profiler, as ``run.py
--trace 0`` and ``--trace 1`` run them.  Prints, one JSON record per line:

* ``tracing_cost``: ``job_ms_p50`` of both windows and their ratio;
* ``device_names``: the device op and XLA module names of the kernels
  (``_pallas`` modules) in the traced window, by device time;
* ``program_trace``: the program's spans reduced over the traced window
  (``chipbench.program.record``): host ms and count per job of each span
  name, table builds, the share of job time that ``fhe.*`` spans cover, the
  device's idle time by the innermost span around it, and the per-layer
  readings of the spans;
* ``trace``: the per-layer metrics of ``BENCHMARK.json`` read from the same
  window, for comparison with a ``run.py --trace 1`` line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also append each record to this JSONL file")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    import run as R
    from chipbench import program, spec, yardstick
    from chipbench import trace as tr

    workload = spec.workload(spec.benchmark(), args.workload)
    devs = R.claim_tpu(int(workload["chips"]))
    kind = devs[0].device_kind
    R.use_compile_cache()
    sys.path.insert(0, str(R.ROOT / "src"))
    from chipbench import cell as C

    def emit(rec: dict) -> None:
        R.emit(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps({"workload": args.workload, "seed": args.seed, **rec}) + "\n")

    # The traced window is the harness's own; only its trace file is read once more.
    load_xplane = tr.load_xplane
    tr.load_xplane = lambda path, names: {**load_xplane(path, names), "program": program.load_spans(path)}

    c = C.Cell(args.workload)
    envs, _, _ = c.inputs(args.seed)
    _, ev = c.job(envs, 0)
    ks_bytes = sum(yardstick.ks_min_bytes(c.params.n, lv, c.params.alpha, reads_input=r) for lv, r in ev.ks)
    plain = C.window(c, envs, args.seconds, False)
    traced = C.window(c, envs, args.seconds, True)
    p50 = [1e3 * statistics.median(w.lat_s) for w in (plain, traced)]
    emit({"tracing_cost": {"job_ms_p50_untraced": p50[0], "job_ms_p50_traced": p50[1],
                           "ratio": p50[1] / p50[0], "jobs": [len(plain.outs), len(traced.outs)],
                           "compiles": [plain.compiles, traced.compiles], "kind": kind}})

    dev_s: dict[tuple, float] = {}
    for _, name, module, _, d in traced.events["device"]:
        if "_pallas" in module:
            dev_s[(name, module)] = dev_s.get((name, module), 0.0) + d * 1e-9
    emit({"device_names": [[n, m, s] for (n, m), s in sorted(dev_s.items(), key=lambda kv: -kv[1])[:40]]})

    p = program.summarize(traced.events, traced.events["program"])
    emit({"program_trace": program.record(p)})
    s = tr.summarize(traced.events, tr.load_families(spec.HERE / "kernel_families.json"), ks_bytes,
                     yardstick.peaks(kind))
    emit({"trace": {m["name"]: spec.reader(m["name"])(s) for m in spec.per_layer(c.bench, c.name)}
          | {"busy_s": s.busy_s, "window_s": s.window_s, "idle_gaps": s.gap_top}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
