"""The program's spans reduced over a window (``chipbench.program``), on a
synthetic trace (``trace_fixture_program.json``) worked by hand.

Window [0, 2000] ns: job 1 is [0, 1000], job 2 is [1200, 2000], nothing runs
between them.  Spans are cut to the window: ``fhe.rescale`` [-300, 50]
keeps [0, 50] and is not counted (it started before the window); the
``kernel.mulmod`` at -200 and the ``kernel.ntt`` at 2050 lie outside.

Counted in the window: 5 ``kernel.*`` (2 fusedks, mulmod, addmod,
fused_moddown), 2 ``h2d``, 1 ``table.ks``.  Unions: ``kernel.*`` 150 + 100 +
50 + 100 + 50 = 450 ns; ``ks.*`` 250 + 250 + 150 = 650 ns; ``fhe.*`` 50 + 600 +
150 + 600 = 1400 ns, of which ks/kernel/h2d/table spans cover 250 + 100 + 50 +
50 + 250 + 150 = 850 ns, so 550 ns is the op layer's own.  Jobs last 1800 ns,
1400 ns of it inside ``fhe.*``.

Device busy [250,300], [500,520], [820,840], [1450,1480], [1720,1740]: 140 ns,
so 1860 ns idle.  Cut at the innermost span around each piece, e.g. the gap
[300, 500] straddles kernel.fusedks 50, ks.accumulate 50, fhe.mul 50,
kernel.mulmod 10 + 30 and h2d 10.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest
from bench_chip_util import REPO  # noqa: F401  (puts benchmarks/chip on the path)
from chipbench import program
from chipbench import trace as tr

FIXTURE = json.loads((Path(__file__).parent / "trace_fixture_program.json").read_text())
IDLE_NS = {
    "job_glue": 400, "fhe.mul": 400, "ks.accumulate": 250, "between_jobs": 200,
    "kernel.fusedks": 150, "fhe.add": 100, "ks.moddown": 100, "kernel.mulmod": 70,
    "fhe.rescale": 50, "table.ks": 50, "h2d": 30, "kernel.addmod": 30, "kernel.fused_moddown": 30,
}


@pytest.fixture(scope="module")
def summary():
    return program.summarize(FIXTURE, FIXTURE["program"])


@pytest.mark.parametrize("name,want", [
    ("kernel_launches_per_job", 2.5),
    ("launch_host_ms_per_job", 2.25e-4),
    ("h2d_uploads_per_job", 1.0),
    ("op_self_host_ms_per_job", 2.75e-4),
    ("ks_host_ms_per_job", 3.25e-4),
])
def test_readings(summary, name, want):
    assert program.readings(summary)[name] == pytest.approx(want)


def test_idle_time_goes_to_the_innermost_span(summary):
    idle = dict(summary.idle_by_span)
    assert idle == pytest.approx({k: v * 1e-9 for k, v in IDLE_NS.items()})
    assert sum(idle.values()) == pytest.approx(1860e-9)


def test_record_counts_coverage_and_table_builds(summary):
    rec = program.record(summary)
    assert rec["jobs"] == 2
    assert rec["table_builds"] == 1
    assert rec["span_coverage"] == pytest.approx(1400 / 1800)
    assert rec["spans"]["fhe.mul"] == pytest.approx({"host_ms_per_job": 6e-4, "per_job": 1.0})
    assert rec["spans"]["fhe.rescale"] == pytest.approx({"host_ms_per_job": 2.5e-5, "per_job": 0.0})
    assert "kernel.ntt" not in rec["spans"]
    assert len(rec["idle_by_span"]) == len(IDLE_NS)


def test_a_program_without_spans_reads_nothing():
    p = program.summarize(FIXTURE, [])
    assert all(v is None for v in program.readings(p).values())
    assert dict(p.idle_by_span) == pytest.approx({"job_glue": 1660e-9, "between_jobs": 200e-9})
    assert p.span_coverage == 0.0


def test_a_trace_without_jobs_or_device_ops_is_refused():
    with pytest.raises(ValueError, match="no job span"):
        program.summarize({"host": [], "device": FIXTURE["device"]}, FIXTURE["program"])
    with pytest.raises(ValueError, match="no device op"):
        program.summarize({"host": FIXTURE["host"], "device": []}, FIXTURE["program"])


def test_pieces_name_the_latest_started_open_span():
    spans = [(0, 10, "outer"), (2, 8, "inner"), (4, 6, "innermost"), (12, 14, "late")]
    assert program.innermost(spans, 0, 15) == [
        (0, 2, "outer"), (2, 4, "inner"), (4, 6, "innermost"), (6, 8, "inner"),
        (8, 10, "outer"), (10, 12, None), (12, 14, "late"), (14, 15, None)]


def test_profiler_round_trip_keeps_the_host_events_and_adds_the_program_spans(tmp_path):
    """A real CPU trace of one HE op: ``load_xplane`` still reads only the
    benchmark's spans, and ``load_spans`` finds the program's nested inside."""
    import jax
    import numpy as np

    from repro.fhe import keys as K
    from repro.fhe import params as P
    from repro.fhe.context import ExecPolicy, FheContext

    p = P.make_params(1 << 10, 2, 1, check_security=False)
    ctx = FheContext(params=p, keys=K.full_keyset(p, seed=0), policy=ExecPolicy(backend="kernel"))
    a = ctx.encrypt(ctx.encode(np.full(p.slots, 0.5)))
    ctx.add(a, a)  # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.JOB_SPAN):
            with jax.profiler.TraceAnnotation("add"):
                jax.block_until_ready(ctx.add(a, a).c0)
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")

    host = tr.load_xplane(pb, {tr.JOB_SPAN, "add"})["host"]
    assert sorted(name for name, _, _ in host) == ["add", "job"]
    spans = program.load_spans(pb)
    names = [name for name, _, _ in spans]
    assert names.count("fhe.add") == 1 and names.count("kernel.addmod") == 2 and "h2d" in names
    (job,) = [(s, s + d) for name, s, d in host if name == tr.JOB_SPAN]
    assert all(job[0] <= s and s + d <= job[1] for _, s, d in spans)
