"""Correctness readings of one cell on the chip: the program's and the control's.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11,12,13 [--out chiprun_out/x.jsonl]

One process, one set-up (the key seed is fixed), then for each seed: the
seed's inputs, ``cell.CHECKED_JOBS`` jobs through the timed path, and two
readings of the same outputs against the plain reference:

* ``program``: the decrypted outputs as the program produced them, the number
  each benchmark run compares (``max_err``);
* ``control``: the same outputs with every ciphertext word carried in float32
  (24-bit significand) instead of the exact 30-bit residues the configuration
  states, the precision step a later kernel change could be tempted by.

The limits in ``limits/<cell>.json`` are set between the largest program
reading and the smallest control reading.  The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--out", help="also append each reading to this JSONL file")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    sys.path.insert(0, str(HERE))
    import run as R
    from chipbench import spec

    workload = spec.workload(spec.benchmark(), args.workload)
    devs = R.claim_tpu(int(workload["chips"]))
    R.use_compile_cache()
    sys.path.insert(0, str(R.ROOT / "src"))
    from chipbench import cell as C
    from chipbench.control import float32_words

    c = C.Cell(args.workload)
    warm = False
    for seed in seeds:
        envs, pool_vals, pt_vals = c.inputs(seed)
        if not warm:
            c.job(envs, 0)
            warm = True
        outs = [c.job(envs, j)[0] for j in range(C.CHECKED_JOBS)]
        prog = C.check(c, outs, pool_vals, pt_vals, seed)
        ctrl = C.check(c, outs, pool_vals, pt_vals, seed, transform=float32_words)
        rec = {"workload": args.workload, "seed": seed, "kind": devs[0].device_kind,
               "program_max_err": prog["max_err"], "control_max_err": ctrl["max_err"],
               "limit": prog["limit"], "program_failed": prog["failed"], "control_failed": ctrl["failed"],
               "elapsed_s": time.perf_counter() - T_START}
        R.emit(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
