"""A BSGS matvec is one compiled program (``linear._bsgs_program``).

The program is ``linear._bsgs_body`` traced once per static shape (params,
policy, level, n1, diagonal indices, scale, key shapes, default device), so
it must give the eager body's bits, count the eager body's kernel launches
and planner records, and carry its tables, encoded diagonals and Galois keys
as arguments rather than as constants embedded in the HLO.  Small rings
(N=2^9, L=3) keep the CPU compiles quick; the compiled-vs-eager matrix runs
the ``ref`` pipeline, the uint64 oracle, and the rest the fused pipeline.
"""

import collections
import warnings

import jax
import numpy as np
import pytest

from repro.fhe import keys as K
from repro.fhe import linear
from repro.fhe import ops
from repro.fhe import params as P
from repro.fhe import trace
from repro.fhe.context import ExecPolicy, FheContext
from repro.kernels import dispatch

L, N1 = 3, 4
DIAGS = {
    "dense": tuple(range(16)),  # 3 hoisted babies, 3 giants
    "sparse": (0, 1, 9, 13),  # one baby: "auto" key-switches it alone; giants 2 and 3
}


@pytest.fixture(scope="module")
def keysets():
    """{dnum: (params, KeySet)}, built on first use."""
    cache = {}

    def get(dnum):
        if dnum not in cache:
            p = P.make_params(1 << 9, L, dnum, check_security=False)
            rots = tuple(range(1, N1)) + tuple(range(N1, 16, N1))
            cache[dnum] = (p, K.full_keyset(p, seed=dnum, rotations=rots, conjugate=False))
        return cache[dnum]

    return get


def _setup(p, ks, backend, hoisting="auto", diags="dense", seed=3):
    ctx = FheContext(params=p, keys=ks, policy=ExecPolicy(backend=backend, hoisting=hoisting))
    rng = np.random.default_rng(seed)
    x = rng.uniform(-1, 1, p.slots)
    w = {d: rng.uniform(-0.25, 0.25, p.slots) for d in DIAGS[diags]}
    ct = ctx.encrypt(ctx.encode(x), seed=seed)
    return ctx, ct, x, w


def _plan(p, w):
    return linear.plan_diags({d: v.astype(np.complex128) for d, v in w.items()}, p, n1=N1)


def _eager(ctx, ct, plan):
    return linear._bsgs_body(ctx, ct, *linear._bsgs_operands(ctx, ct, plan, ctx.params.scale))


def _same(x, y):
    return (np.array_equal(np.asarray(x.c0), np.asarray(y.c0))
            and np.array_equal(np.asarray(x.c1), np.asarray(y.c1))
            and (x.level, x.scale) == (y.level, y.scale))


def _program(ctx, ct, plan):
    shape, _, baby_ksks, giant_ksks = linear._bsgs_operands(ctx, ct, plan, ctx.params.scale)
    return linear._bsgs_program(ctx.params, ctx.policy, ct.level, shape, float(ctx.params.scale),
                                tuple(k.shape for k in (*baby_ksks, *giant_ksks)),
                                dispatch.default_device(), ops._rescale)


@pytest.mark.parametrize("dnum, hoisting, diags", [
    (d, h, s) for d in (2, 3) for h in ("auto", "never") for s in DIAGS
])
def test_compiled_bsgs_is_bitexact_vs_eager(keysets, dnum, hoisting, diags):
    p, ks = keysets(dnum)
    ctx, ct, x, w = _setup(p, ks, "ref", hoisting, diags)
    plan = _plan(p, w)
    got = ctx.apply_bsgs(ct, plan)
    assert _same(got, _eager(ctx, ct, plan))
    want = sum(v * np.roll(x, -d) for d, v in w.items())
    assert np.max(np.abs(ctx.decrypt_decode(got) - want)) < 1e-3


@pytest.fixture
def spans(monkeypatch):
    """[(name, names of the spans open around it, outermost first)]."""
    log, stack = [], []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append((self.name, tuple(stack)))
            stack.append(self.name)

        def __exit__(self, *exc):
            stack.pop()

    monkeypatch.setattr(dispatch, "TraceAnnotation", Recorder)
    return log


@pytest.fixture(scope="module")
def fused(keysets):
    p, ks = keysets(2)
    ctx, ct, x, w = _setup(p, ks, "fused")
    return ctx, ct, x, w


def test_warm_bsgs_is_one_call_that_builds_nothing(fused, spans):
    ctx, ct, _, w = fused
    ctx.apply_bsgs(ct, _plan(ctx.params, w))
    before = linear.bsgs_program_stats()
    spans.clear()
    ctx.apply_bsgs(ct, _plan(ctx.params, w))  # fresh arrays of the same values, as each job builds
    after = linear.bsgs_program_stats()
    assert after["builds"] == before["builds"]
    assert after["programs"] == before["programs"]
    assert after["calls"] == before["calls"] + 1
    assert spans == [("fhe.apply_bsgs", ())]


def test_new_weights_reuse_the_program_and_give_the_new_result(fused, spans):
    """The diagonals are arguments, not constants: other values of the same
    shape run through the same program, encoded on a miss."""
    ctx, ct, x, w = fused
    ctx.apply_bsgs(ct, _plan(ctx.params, w))
    builds = linear.bsgs_program_stats()["builds"]
    rng = np.random.default_rng(8)
    w2 = {d: rng.uniform(-0.25, 0.25, ctx.params.slots) for d in w}
    plan = _plan(ctx.params, w2)
    spans.clear()
    got = ctx.apply_bsgs(ct, plan)
    assert linear.bsgs_program_stats()["builds"] == builds
    assert {name for name, _ in spans} - {"h2d", "kernel.ntt"} == {"fhe.apply_bsgs", "table.diag"}
    assert all(outer == ("fhe.apply_bsgs",) for name, outer in spans if name == "table.diag")
    assert _same(got, _eager(ctx, ct, plan))
    want = sum(v * np.roll(x, -d) for d, v in w2.items())
    assert np.max(np.abs(ctx.decrypt_decode(got) - want)) < 1e-3


def test_warm_bsgs_replays_the_eager_counts_and_trace(fused):
    ctx, ct, _, w = fused
    plan = _plan(ctx.params, w)
    ctx.apply_bsgs(ct, plan)
    with dispatch.count_dispatches() as eager_counts, trace.capture_trace() as eager_trace:
        _eager(ctx, ct, plan)
    with dispatch.count_dispatches() as counts, trace.capture_trace() as instrs:
        ctx.apply_bsgs(ct, plan)
    assert counts == eager_counts
    assert instrs == eager_trace
    ops_seen = collections.Counter(i.op for i in instrs)
    assert ops_seen["LOAD_PT"] == len(w)
    assert ops_seen["LOAD_KSK"] == 3 + 3  # each hoisted baby's key and each giant's


@pytest.mark.parametrize("backend", ["ref", "fused"])
def test_lowered_bsgs_captures_no_large_constant(keysets, backend):
    """Tables, diagonals and keys are arguments: lowering embeds under 64 KiB."""
    p, ks = keysets(2)
    ctx, ct, _, w = _setup(p, ks, backend)
    plan = _plan(p, w)
    ctx.apply_bsgs(ct, plan)
    prog = _program(ctx, ct, plan)
    _, pts, baby_ksks, giant_ksks = linear._bsgs_operands(ctx, ct, plan, p.scale)
    xs = (ct.c0, ct.c1, *(pt.data for pt in pts), *baby_ksks, *giant_ksks)
    old = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 65536)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            lowered = prog.run.lower(prog.consts, *xs)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", old)
    assert lowered.as_text().startswith("module @jit_ckks_bsgs")


def test_bsgs_inside_a_callers_jit_runs_the_body_inline(keysets):
    """Under a caller's ``jax.jit`` the ciphertext is a tracer, so the body
    runs inline and builds no program; the result matches the compiled op."""
    p, ks = keysets(2)
    ctx, ct, _, w = _setup(p, ks, "ref", seed=5)
    plan = _plan(p, w)
    want = ctx.apply_bsgs(ct, plan)  # also leaves the diagonals' encodings resident
    builds = linear.bsgs_program_stats()["builds"]

    @jax.jit
    def run(c0, c1):
        out = ctx.apply_bsgs(ops.Ciphertext(c0, c1, ct.level, ct.scale), plan)
        return out.c0, out.c1

    c0, c1 = run(ct.c0, ct.c1)
    assert linear.bsgs_program_stats()["builds"] == builds
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(want.c0))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(want.c1))
