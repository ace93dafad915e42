"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` turns the JAX profiler's ``.xplane.pb`` into plain events:
``device`` holds ``[plane, name, module, start_ns, dur_ns]`` for every op
execution on a TPU plane's "XLA Ops" line, and ``host`` holds
``[name, start_ns, dur_ns]`` for the benchmark's own ``TraceAnnotation`` spans
(``job`` and one span per circuit op).  Both are on the profiler's clock.
``summarize`` reduces those events, over the window from the first job's
start to the last job's end, to a ``TraceSummary``.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import re
from pathlib import Path

JOB_SPAN = "job"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def load_xplane(path: Path, span_names) -> dict:
    """Plain events from one ``.xplane.pb``: device op executions and the host
    spans whose name is in ``span_names``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    device, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ())
            )
            starts = [m[0] for m in modules]
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                stats = dict(e.stats)
                module = stats.get("hlo_module")
                if module is None:
                    k = bisect.bisect_right(starts, e.start_ns) - 1
                    module = modules[k][2] if k >= 0 and e.start_ns < modules[k][1] else ""
                device.append([plane.name, e.name, str(module), float(e.start_ns), float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events if e.name in span_names)
    return {"device": device, "host": host}


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    gaps, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    return [(s, e) for s, e in gaps if e > s]


def family_of(families: dict, name: str, module: str) -> str | None:
    """The first family one of whose patterns occurs in the op or module name."""
    for fam, patterns in families.items():
        if any(p in module or p in name for p in patterns):
            return fam
    return None


@dataclasses.dataclass
class TraceSummary:
    jobs: int
    window_s: float
    busy_s: float  # union of device op intervals in the window, averaged over the chips
    device_ops: int  # op executions in the window, all chips
    family_s: dict  # family -> summed device seconds in the window
    ks_bytes_per_job: float  # minimum key-switch HBM bytes of one job
    peaks: dict
    device_top: list  # [[name, seconds], ...] the device ops that took most time
    gap_top: list  # [[host span, seconds], ...] idle time by what the host was doing


def summarize(events: dict, families: dict, ks_bytes_per_job: float, peaks: dict,
              top: int = 10) -> TraceSummary:
    """Reduce plain events over the jobs' window; raises when the trace holds
    no job span or no device op, since then nothing was measured."""
    jobs = sorted((s, s + d) for name, s, d in events["host"] if name == JOB_SPAN)
    if not jobs:
        raise ValueError("trace holds no job span")
    lo, hi = jobs[0][0], max(e for _, e in jobs)
    planes: dict[str, list] = {}
    fam_ns: dict[str, float] = {f: 0.0 for f in families}
    by_name: dict[str, float] = {}
    count = 0
    for plane, name, module, s, d in events["device"]:
        if s < lo or s >= hi:
            continue
        count += 1
        planes.setdefault(plane, []).append((s, min(s + d, hi)))
        fam = family_of(families, name, module)
        if fam is not None:
            fam_ns[fam] += d
        key = module or name
        by_name[key] = by_name.get(key, 0.0) + d
    if not count:
        raise ValueError("no device op ran in the traced window")
    busy = sum(union_ns(iv) for iv in planes.values()) / len(planes)

    ops = sorted((s, s + d, name) for name, s, d in events["host"] if name != JOB_SPAN)
    op_starts = [o[0] for o in ops]
    job_starts = [j[0] for j in jobs]
    gap_ns: dict[str, float] = {}
    for iv in planes.values():
        for s, e in idle_gaps(iv, lo, hi):
            mid = 0.5 * (s + e)
            k = bisect.bisect_right(op_starts, mid) - 1
            if k >= 0 and mid < ops[k][1]:
                owner = ops[k][2]
            else:
                j = bisect.bisect_right(job_starts, mid) - 1
                owner = "job_glue" if j >= 0 and mid < jobs[j][1] else "between_jobs"
            gap_ns[owner] = gap_ns.get(owner, 0.0) + (e - s) / len(planes)

    def top_list(d):
        return [[k, v * 1e-9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return TraceSummary(
        jobs=len(jobs), window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9, device_ops=count,
        family_s={f: v * 1e-9 for f, v in fam_ns.items()}, ks_bytes_per_job=ks_bytes_per_job,
        peaks=peaks, device_top=top_list(by_name), gap_top=top_list(gap_ns),
    )


def load_families(path: Path) -> dict:
    raw = json.loads(Path(path).read_text())
    return {k: list(v) for k, v in raw.items() if k != "why"}
