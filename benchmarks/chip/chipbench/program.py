"""The program's own host spans in a profiler trace, reduced over the jobs' window.

The program writes a ``jax.profiler.TraceAnnotation`` at each layer boundary
(``repro.kernels.dispatch``): ``fhe.<method>`` per HE op, ``ks.<stage>`` per
key-switch stage, ``kernel.<op>`` per kernel launch, ``h2d`` per
host-to-device upload and ``table.<name>`` per table build.  They sit in the
profiler's host plane, on the clock of the device planes.  ``load_spans``
reads them from an ``.xplane.pb`` as ``[name, start_ns, dur_ns]``;
``summarize`` reduces them, with the ``job`` spans and device events that
``trace.load_xplane`` reads from the same file, over the window from the
first job's start to the last job's end.

A program without these spans yields none, and every reading that needs them
is None.
"""

from __future__ import annotations

import dataclasses
import heapq
from pathlib import Path

from . import trace as tr

PREFIXES = ("fhe.", "ks.", "kernel.", "table.")
UPLOAD = "h2d"
CHILD_KINDS = ("ks", "kernel", UPLOAD, "table")  # what an fhe.* span's self time leaves out
JOB_GLUE, BETWEEN_JOBS = "job_glue", "between_jobs"


def is_program(name: str) -> bool:
    return name == UPLOAD or name.startswith(PREFIXES)


def kind(name: str) -> str:
    """``fhe``, ``ks``, ``kernel``, ``h2d`` or ``table``."""
    return name.split(".", 1)[0]


def load_spans(path: Path) -> list:
    """The program's spans in one ``.xplane.pb``: ``[name, start_ns, dur_ns]``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if is_program(e.name)]


def overlap_ns(a, b) -> float:
    """Length of union(a) ∩ union(b) for lists of ``(start, end)`` intervals."""
    return tr.union_ns(a) + tr.union_ns(b) - tr.union_ns(list(a) + list(b))


def innermost(spans, lo: float, hi: float) -> list[tuple[float, float, str | None]]:
    """``[lo, hi]`` cut at every span boundary, each piece named after the
    innermost ``(start, end, name)`` span covering it (the latest started one
    still open), or None where none does."""
    spans = sorted((max(s, lo), min(e, hi), n) for s, e, n in spans if e > lo and s < hi)
    points = sorted({lo, hi, *(s for s, _, _ in spans), *(e for _, e, _ in spans)})
    open_, i, out = [], 0, []
    for a, b in zip(points, points[1:]):
        while i < len(spans) and spans[i][0] <= a:
            s, e, n = spans[i]
            heapq.heappush(open_, (-s, e, i, n))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        out.append((a, b, open_[0][3] if open_ else None))
    return out


@dataclasses.dataclass
class ProgramSummary:
    jobs: int
    window_s: float
    counts: dict  # span name -> spans that start in the window
    host_s: dict  # span name -> union of its spans' time in the window
    kind_s: dict  # span kind -> union of that kind's spans' time in the window
    op_self_s: float  # fhe.* time that no ks/kernel/h2d/table span covers
    span_coverage: float  # share of the job spans' time that fhe.* spans cover
    idle_by_span: list  # [[span name, seconds], ...] device idle time by innermost host span


def summarize(events: dict, program: list, top: int = 15) -> ProgramSummary:
    """Reduce the program's spans over the window of the ``job`` spans in
    ``events["host"]``; device idle gaps come from ``events["device"]``.
    Raises as ``trace.summarize`` does when nothing was measured."""
    jobs = sorted((s, s + d) for name, s, d in events["host"] if name == tr.JOB_SPAN)
    if not jobs:
        raise ValueError("trace holds no job span")
    lo, hi = jobs[0][0], max(e for _, e in jobs)
    inside = [(n, max(s, lo), min(s + d, hi), s) for n, s, d in program if s + d > lo and s < hi]

    counts: dict[str, int] = {}
    by_name: dict[str, list] = {}
    by_kind: dict[str, list] = {}
    for n, s, e, s0 in inside:
        if s0 >= lo:
            counts[n] = counts.get(n, 0) + 1
        by_name.setdefault(n, []).append((s, e))
        by_kind.setdefault(kind(n), []).append((s, e))
    fhe = by_kind.get("fhe", [])
    child = [iv for k in CHILD_KINDS for iv in by_kind.get(k, [])]
    job_ns = tr.union_ns(jobs)

    planes: dict[str, list] = {}
    for plane, _, _, s, d in events["device"]:
        if lo <= s < hi:
            planes.setdefault(plane, []).append((s, min(s + d, hi)))
    if not planes:
        raise ValueError("no device op ran in the traced window")
    pieces = innermost([(s, e, n) for n, s, e, _ in inside] + [(s, e, JOB_GLUE) for s, e in jobs], lo, hi)
    idle: dict[str, float] = {}
    for iv in planes.values():
        k = 0
        for gs, ge in tr.idle_gaps(iv, lo, hi):
            while pieces[k][1] <= gs:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < ge:
                a, b, owner = pieces[j]
                owner = owner or BETWEEN_JOBS
                idle[owner] = idle.get(owner, 0.0) + (min(b, ge) - max(a, gs)) / len(planes)
                j += 1

    return ProgramSummary(
        jobs=len(jobs), window_s=(hi - lo) * 1e-9, counts=counts,
        host_s={n: tr.union_ns(iv) * 1e-9 for n, iv in by_name.items()},
        kind_s={k: tr.union_ns(iv) * 1e-9 for k, iv in by_kind.items()},
        op_self_s=(tr.union_ns(fhe + child) - tr.union_ns(child)) * 1e-9 if fhe else 0.0,
        span_coverage=overlap_ns(jobs, fhe) / job_ns if job_ns > 0 else 0.0,
        idle_by_span=[[n, v * 1e-9] for n, v in sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    )


def _per_job_ms(seconds: float, p: ProgramSummary):
    return 1e3 * seconds / p.jobs if seconds > 0 else None


def _per_job_count(count: int, p: ProgramSummary):
    return count / p.jobs if count else None


READINGS = {
    "kernel_launches_per_job": lambda p: _per_job_count(
        sum(v for n, v in p.counts.items() if kind(n) == "kernel"), p),
    "launch_host_ms_per_job": lambda p: _per_job_ms(p.kind_s.get("kernel", 0.0), p),
    "h2d_uploads_per_job": lambda p: _per_job_count(p.counts.get(UPLOAD, 0), p),
    "op_self_host_ms_per_job": lambda p: _per_job_ms(p.op_self_s, p),
    "ks_host_ms_per_job": lambda p: _per_job_ms(p.kind_s.get("ks", 0.0), p),
}


def readings(p: ProgramSummary) -> dict:
    """Each per-layer reading of the program's spans; None where it has nothing to read."""
    return {name: read(p) for name, read in READINGS.items()}


def record(p: ProgramSummary) -> dict:
    """The ``program_trace`` progress record of a traced window."""
    return {
        "jobs": p.jobs,
        "spans": {n: {"host_ms_per_job": 1e3 * p.host_s[n] / p.jobs, "per_job": p.counts.get(n, 0) / p.jobs}
                  for n in sorted(p.host_s)},
        "table_builds": sum(v for n, v in p.counts.items() if kind(n) == "table"),
        "span_coverage": p.span_coverage,
        "idle_by_span": p.idle_by_span,
        "readings": readings(p),
    }
