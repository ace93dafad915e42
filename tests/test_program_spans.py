"""The program's spans on the profiler clock (``repro.kernels.dispatch``).

Every kernel runs in the Pallas interpreter at N=2^10 (``backend="kernel"``,
the pipeline a TPU runs by default), with ``TraceAnnotation`` replaced by a
recorder that notes each span's name and the spans open around it.  A
ciphertext multiply is one compiled program (``ops._mul_program``), so its
``ks.*`` and ``kernel.*`` spans fire while it is built; a rotation still runs
eagerly, launch by launch.
"""

import collections

import jax
import numpy as np
import pytest

from repro.fhe import keys as K
from repro.fhe import ntt as nttmod
from repro.fhe import ops as fhe_ops
from repro.fhe import params as P
from repro.fhe.context import ExecPolicy, FheContext
from repro.kernels import dispatch
from repro.kernels.fusedks import ops as fops
from repro.kernels.modops import ops as mo
from repro.kernels.ntt import ops as ntt_ops

PROGRAM_PREFIXES = ("fhe.", "ks.", "kernel.", "h2d", "table.")


@pytest.fixture(scope="module")
def kctx():
    p = P.make_params(1 << 10, 3, 2, check_security=False)
    ctx = FheContext(params=p, keys=K.full_keyset(p, seed=0, rotations=(1,)),
                     policy=ExecPolicy(backend="kernel"))
    rng = np.random.default_rng(5)
    a, b = (ctx.encrypt(ctx.encode(rng.uniform(-1, 1, p.slots)), seed=s) for s in (1, 2))
    return ctx, a, b


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


def test_mul_spans_nest_by_layer(kctx, spans):
    """Warm, a rotation nests fhe ⊃ ks ⊃ kernel (a multiply, compiled, opens
    these spans only while it is built: ``test_table_spans_fire_on_cache_misses_only``)."""
    ctx, a, b = kctx
    ctx.rotate(a, 1)
    spans.clear()
    ctx.rotate(a, 1)  # warm: every table is built
    assert spans[0] == ("fhe.rotate", ())
    assert all(outer[:1] == ("fhe.rotate",) for _, outer in spans[1:])
    assert all(name.startswith(PROGRAM_PREFIXES) for name, _ in spans)
    nest = {(name, outer[-1]) for name, outer in spans if outer}
    assert ("ks.accumulate", "fhe.rotate") in nest
    assert ("ks.moddown", "fhe.rotate") in nest
    assert ("kernel.fusedks", "ks.accumulate") in nest
    assert ("kernel.fused_moddown", "ks.moddown") in nest
    assert not any(name == "h2d" and any(o.startswith("kernel.") for o in outer)
                   for name, outer in spans)



def test_kernel_spans_match_dispatch_counts(kctx, spans):
    """An eager op counts one dispatch per ``kernel.*`` span; a warm multiply
    opens none and replays the eager body's count."""
    ctx, a, b = kctx
    ctx.rotate(a, 1)
    spans.clear()
    with dispatch.count_dispatches() as counts:
        ctx.rotate(a, 1)
    launched = collections.Counter(n.removeprefix("kernel.") for n, _ in spans if n.startswith("kernel."))
    assert launched == counts
    assert counts["fusedks"] == 1 and counts["fused_moddown"] == 1

    ctx.mul(a, b)
    spans.clear()
    fhe_ops._mul_eager(ctx, a, b, ctx.keys.rlk)
    eager = collections.Counter(n.removeprefix("kernel.") for n, _ in spans if n.startswith("kernel."))
    spans.clear()
    with dispatch.count_dispatches() as counts:
        ctx.mul(a, b)
    assert not any(n.startswith("kernel.") for n, _ in spans)
    assert counts == eager
    assert counts["fusedks"] == 1 and counts["fused_moddown"] == 1


def test_table_spans_fire_on_cache_misses_only(kctx, spans):
    ctx, a, b = kctx
    for cached in (fops.ks_tables, fops.moddown_tables, ntt_ops.kernel_tables, mo._limb_tables,
                   fhe_ops._rescale_tables, nttmod.subplan, fhe_ops._mul_program):
        cached.cache_clear()
    ctx.mul(a, b)
    built = {n for n, _ in spans if n.startswith("table.")}
    assert {"table.ks", "table.moddown", "table.ntt", "table.limbs", "table.rescale",
            "table.mul_program"} <= built
    nest = {(name, outer[-1]) for name, outer in spans if outer}
    assert ("table.mul_program", "fhe.mul") in nest
    assert ("ks.accumulate", "table.mul_program") in nest
    assert ("kernel.fusedks", "ks.accumulate") in nest
    assert ("kernel.fused_moddown", "ks.moddown") in nest
    spans.clear()
    ctx.mul(a, b)
    assert [n for n, _ in spans if n.startswith("table.")] == []


def test_launch_refuses_an_unnamed_kernel():
    with pytest.raises(KeyError):
        dispatch.launch("mystery")


def test_upload_is_one_h2d_span_and_casts_device_values_in_place(spans):
    x = dispatch.upload([1, 2, 3], np.uint32)
    assert x.dtype == np.uint32 and spans == [("h2d", ())]
    y = dispatch.upload(x, np.uint64)
    assert y.dtype == np.uint64 and len(spans) == 1
    np.testing.assert_array_equal(np.asarray(y), [1, 2, 3])


def _op(ctx, a, b, name):
    """A call of op ``name`` whose inputs are made before it runs."""
    if name == "rescale":
        prod = ctx.mul(a, b, rescale_after=False)
        return lambda: ctx.rescale(prod)
    if name == "mul_plain":
        pt = ctx.encode(np.full(ctx.params.slots, 0.25))
        return lambda: ctx.mul_plain(a, pt, rescale_after=False)
    return {
        "mul": lambda: ctx.mul(a, b),
        "add": lambda: ctx.add(a, b),
        "mul_const_exact": lambda: ctx.mul_const_exact(a, 0.5, ctx.params.scale),
        "add_const": lambda: ctx.add_const(a, 0.5),
        "sub": lambda: ctx.sub(a, b),
        "force_to": lambda: ctx.force_to(a, a.level - 1, a.scale),
    }[name]


@pytest.mark.parametrize("op", ["mul", "mul_plain", "add", "sub", "rescale"])
def test_warm_ops_upload_nothing_and_build_no_table(kctx, spans, op):
    """Per-limb constants stay on the device: a cold call uploads only while it
    builds a table, and a warm call neither uploads nor builds."""
    ctx, a, b = kctx
    run = _op(ctx, a, b, op)
    spans.clear()
    run()
    assert all(any(o.startswith("table.") for o in outer) for name, outer in spans if name == "h2d")
    spans.clear()
    run()
    assert [n for n, _ in spans if n == "h2d" or n.startswith("table.")] == []


@pytest.mark.parametrize("op", ["mul", "rescale", "mul_const_exact", "mul_plain", "add_const", "sub",
                                "force_to"])
def test_warm_ops_move_data_to_the_device_only_through_upload(kctx, op, monkeypatch):
    """Under ``disallow_explicit`` every host-to-device transfer fails, those
    through ``dispatch.upload`` excepted: implicit ones (Python scalars in
    eager ops, eager indexing) and stray ``jnp.asarray`` of host values alike."""
    ctx, a, b = kctx
    run = _op(ctx, a, b, op)
    expect = run()
    put = jax.device_put

    def allowed(*args, **kwargs):
        with jax.transfer_guard_host_to_device("allow"):
            return put(*args, **kwargs)

    monkeypatch.setattr(jax, "device_put", allowed)
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        got = run()
    np.testing.assert_array_equal(np.asarray(got.c0), np.asarray(expect.c0))
    np.testing.assert_array_equal(np.asarray(got.c1), np.asarray(expect.c1))
