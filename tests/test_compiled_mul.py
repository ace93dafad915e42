"""A CKKS ct×ct multiply is one compiled program (``ops._mul_program``).

The program is ``ops._mul_eager`` traced once per (params, policy, input
levels, ``rescale_after``, default device), so it must give the eager body's
bits, count the eager body's kernel launches and planner records, and carry
its tables and key as arguments rather than as constants embedded in the HLO.
Small rings (N=2^9, L=2) keep the Pallas interpreter and the CPU compiles quick.
Every compiled-vs-eager case here runs the ``ref`` pipeline, the uint64 oracle.
"""

import collections
import warnings

import jax
import numpy as np
import pytest

from repro.core import executor
from repro.fhe import keys as K
from repro.fhe import ops
from repro.fhe import params as P
from repro.fhe import trace
from repro.fhe.context import ExecPolicy, FheContext
from repro.kernels import dispatch

L = 2


@pytest.fixture(scope="module")
def keysets():
    """{dnum: (params, KeySet)}, built on first use."""
    cache = {}

    def get(dnum):
        if dnum not in cache:
            p = P.make_params(1 << 9, L, dnum, check_security=False)
            cache[dnum] = (p, K.full_keyset(p, seed=dnum))
        return cache[dnum]

    return get


def _pair(ctx, seed=3):
    rng = np.random.default_rng(seed)
    za, zb = (rng.uniform(-1, 1, ctx.params.slots) for _ in range(2))
    a, b = (ctx.encrypt(ctx.encode(z), seed=seed + i) for i, z in enumerate((za, zb)))
    return a, b, za, zb


def _same(x, y):
    return (np.array_equal(np.asarray(x.c0), np.asarray(y.c0))
            and np.array_equal(np.asarray(x.c1), np.asarray(y.c1))
            and (x.level, x.scale) == (y.level, y.scale))


def check_bitexact(keysets, backend, dnum, levels, rescale_after, op):
    """Compiled ≡ eager, bit for bit.  Unequal levels multiply a level-L input
    by a level-(L-1) one; a square then squares the level-(L-1) input."""
    p, ks = keysets(dnum)
    ctx = FheContext(params=p, keys=ks, policy=ExecPolicy(backend=backend))
    a, b, _, _ = _pair(ctx)
    if levels == "unequal":
        b = ctx.level_drop(b, L - 1)
    if op == "square":
        x = b if levels == "unequal" else a
        got = ctx.square(x, rescale_after=rescale_after)
        want = ops._mul_eager(ctx, x, x, ks.rlk, rescale_after)
    else:
        got = ctx.mul(a, b, rescale_after=rescale_after)
        want = ops._mul_eager(ctx, a, b, ks.rlk, rescale_after)
    assert _same(got, want)


# The fused pipeline's half of this matrix is ``test_compiled_mul_fused.py``:
# each case compiles its own program, and a file runs on one test worker.
CASES = pytest.mark.parametrize("dnum, levels, rescale_after, op", [
    (d, lv, r, op) for d in (1, 2, 3) for lv in ("equal", "unequal")
    for r in (True, False) for op in ("mul", "square")
])


@CASES
def test_compiled_mul_is_bitexact_vs_eager(keysets, dnum, levels, rescale_after, op):
    check_bitexact(keysets, "ref", dnum, levels, rescale_after, op)


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
    ctx = FheContext(params=p, keys=ks, policy=ExecPolicy(backend="fused"))
    return (ctx, *_pair(ctx))


def test_warm_mul_is_one_call_that_builds_nothing(fused, spans):
    ctx, a, b, _, _ = fused
    ctx.mul(a, b)
    before = ops.mul_program_stats()
    spans.clear()
    ctx.mul(a, b)
    after = ops.mul_program_stats()
    assert after["builds"] == before["builds"]
    assert after["programs"] == before["programs"]
    assert after["calls"] == before["calls"] + 1
    assert spans == [("fhe.mul", ())]


def test_warm_mul_replays_the_eager_counts_and_trace(fused):
    ctx, a, b, _, _ = fused
    with dispatch.count_dispatches() as eager_counts, trace.capture_trace() as eager_trace:
        ops._mul_eager(ctx, a, b, ctx.keys.rlk)
    ctx.mul(a, b)
    with dispatch.count_dispatches() as counts, trace.capture_trace() as instrs:
        ctx.mul(a, b)
    assert counts == eager_counts
    assert instrs == eager_trace
    assert collections.Counter(i.op for i in instrs)["LOAD_KSK"] == 1


@pytest.mark.parametrize("backend", ["ref", "fused"])
def test_lowered_mul_captures_no_large_constant(keysets, backend):
    """Tables and the key are arguments: lowering embeds under 64 KiB."""
    p, ks = keysets(2)
    ctx = FheContext(params=p, keys=ks, policy=ExecPolicy(backend=backend))
    a, b, _, _ = _pair(ctx)
    ctx.mul(a, b)
    prog = ops._mul_program(p, ctx.policy, (a.level, b.level), True, ks.rlk.k.shape,
                            dispatch.default_device())
    old = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 65536)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            prog.run.lower(prog.consts, a.c0, a.c1, b.c0, b.c1, ks.rlk.k)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", old)


def test_one_program_serves_two_key_sets(keysets):
    """The key is an argument, not a cached constant: each key set's product
    decrypts under its own secret key through the same program."""
    p, ks1 = keysets(2)
    ks2 = K.full_keyset(p, seed=11)
    outs = []
    for ks in (ks1, ks2):
        ctx = FheContext(params=p, keys=ks, policy=ExecPolicy(backend="fused"))
        a, b, za, zb = _pair(ctx, seed=5)
        outs.append((ctx, ctx.mul(a, b), za * zb))
        if len(outs) == 1:
            built = ops.mul_program_stats()["builds"]
    assert ops.mul_program_stats()["builds"] == built
    for ctx, out, want in outs:
        assert np.max(np.abs(np.asarray(ctx.decrypt_decode(out)) - want)) < 1e-3
    (ctx1, out1, want1), (ctx2, _, _) = outs
    assert np.max(np.abs(np.asarray(ctx2.decrypt_decode(out1)) - want1)) > 1.0


def test_parallel_shallow_mul_runs_the_body_inline(keysets):
    """Under ``jit(shard_map(...))`` the arguments are tracers, so the body
    runs inline; each job still matches the compiled single-job multiply."""
    p, ks = keysets(2)
    ctx = FheContext(params=p, keys=ks, policy=ExecPolicy(backend="ref"))
    pairs = [_pair(ctx, seed=s)[:2] for s in (7, 9)]
    builds = ops.mul_program_stats()["builds"]
    got = executor.parallel_shallow_mul(p, ks, pairs)
    assert ops.mul_program_stats()["builds"] == builds
    for (a, b), out in zip(pairs, got):
        assert _same(out, ctx.mul(a, b))
