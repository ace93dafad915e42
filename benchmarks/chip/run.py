"""Chip benchmark: one run of one cell of ``BENCHMARK.json`` on a TPU.

    python3 benchmarks/chip/run.py --workload <config>.<traffic> --seed <n> --seconds <s> --trace <0|1>

Claims the TPU and exits non-zero without printing a result when there is
none, when it has fewer chips than the cell asks for, or when its device kind
is not in ``peaks.json``.  Builds the configuration's parameters and keys, the
traffic's encrypted inputs from ``--seed`` and one warm-up job (all of that is
``setup_s``), then runs jobs back to back for ``--seconds``.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1`` it
runs the window under the profiler and reports the per-layer metrics read
from the trace.  Either way it decrypts a seeded sample of the window's jobs,
compares them with the plain numpy reference of the circuit, and prints each
number compared beside its limit as the last lines on standard error.

Lines before the last on standard output are JSON progress records (warm-up
launch counts, compile counts, set-up of a first run in this checkout); the
last line is the result.  The persistent compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
CACHE = ROOT / ".jax_cache"


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def claim_tpu(chips: int):
    """The TPU devices; exits non-zero when there are fewer than ``chips``.
    An explicit platform list never falls back to the CPU."""
    import jax

    jax.config.update("jax_platforms", "tpu,cpu")
    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        sys.exit(f"run.py: no TPU: {e}")
    if len(devs) < chips:
        sys.exit(f"run.py: the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def use_compile_cache() -> tuple[str, bool]:
    """The persistent compile cache directory, and whether it held nothing yet
    (the first run in this checkout, which compiles)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    first = not (Path(path).is_dir() and any(Path(path).iterdir()))
    return path, first


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        sys.exit("run.py: --seed must be >= 0 and --seconds > 0")

    sys.path.insert(0, str(HERE))
    from chipbench import spec, yardstick

    bench = spec.benchmark()
    workload = spec.workload(bench, args.workload)
    devs = claim_tpu(int(workload["chips"]))
    kind = devs[0].device_kind
    try:
        yardstick.peaks(kind)
    except KeyError as e:
        sys.exit(f"run.py: {e}")
    cache, first = use_compile_cache()

    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import cell as C

    c = C.Cell(args.workload)
    rec = C.run(c, args.seed, args.seconds, bool(args.trace), T_START, kind, log=emit)
    emit({"setup": {"setup_s": rec["setup_s"], "first_run_in_checkout": first, "compile_cache": cache}})
    device = {"platform": devs[0].platform, "kind": kind, "count": int(workload["chips"]),
              "memory_peak_bytes": rec["memory_peak_bytes"], **rec["device_extra"]}
    line = {"correct": rec["correct"], "attempted": rec["attempted"], "failed": rec["failed"],
            "metrics": rec["metrics"], "device": device}
    if rec["breakdown"] is not None:
        line["breakdown"] = rec["breakdown"]
    line["checks"] = rec["checks"]
    for name, chk in rec["checks"].items():
        print(f"check {name}: {chk['value']!r} limit {chk['limit']!r}", file=sys.stderr, flush=True)
    emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
