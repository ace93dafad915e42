"""The chip's own pipeline, op by op, against the uint64 oracle: CKKS.

On a TPU ``ExecPolicy()`` resolves to the fused key-switch with Pallas
pointwise and NTT stages; off the chip that pipeline is ``backend="kernel"``,
run here in the Pallas interpreter.  Each case runs one op the benchmark's
cells run on the same encrypted inputs under ``backend="kernel"`` and under
``backend="ref"`` and asserts that ``c0``, ``c1``, ``level`` and ``scale`` are
identical.  The ring is N=2^10, L=5 with dnum 2 (the ``lstm`` cells'
key-switch shape) and dnum 3 (``dblookup``'s), scaled down in N and L.
Seeds are fixed, so every case is deterministic.  The BGV half is
``test_chip_pipeline_ops_bgv.py``: a file runs on one test worker.
"""

import types

import numpy as np
import pytest

from repro.fhe import keys as K
from repro.fhe import linear
from repro.fhe import params as P
from repro.fhe.context import ExecPolicy, FheContext

L = 5
ROTS = (1, 2, 3)


@pytest.fixture(scope="module")
def setups():
    """{dnum: (kernel ctx, ref ctx, inputs)}, built on first use from one key
    set, the inputs encrypted once under the oracle."""
    cache = {}

    def get(dnum):
        if dnum not in cache:
            p = P.make_params(1 << 10, L, dnum, check_security=False)
            ks = K.full_keyset(p, seed=dnum, rotations=ROTS, conjugate=True)
            kernel, ref = (FheContext(params=p, keys=ks, policy=ExecPolicy(backend=b))
                           for b in ("kernel", "ref"))
            cache[dnum] = (kernel, ref, _inputs(ref, np.random.default_rng(3)))
        return cache[dnum]

    return get


def _inputs(ref, rng):
    p = ref.params
    za, zb, zc = (rng.uniform(-0.5, 0.5, p.slots) for _ in range(3))
    a = ref.encrypt(ref.encode(za), seed=5)
    b = ref.encrypt(ref.encode(zb), seed=6)
    pt = ref.encode(zc)
    m = np.zeros((p.slots, p.slots))
    for d in range(4):  # a banded matrix: baby steps {0, 1}, giant step 2
        m[np.arange(p.slots), (np.arange(p.slots) + d) % p.slots] = rng.normal(size=p.slots) * 0.2
    return types.SimpleNamespace(
        a=a, b=b, pt=pt,
        b_low=ref.level_drop(b, L - 1),
        product=ref.mul_plain(a, pt, rescale_after=False),  # at scale Δ², for rescale
        plan=linear.plan_matrix(m, n1=2, tol=1e-12),
    )


OPS = {
    "add": lambda c, x: c.add(x.a, x.b),
    "sub": lambda c, x: c.sub(x.a, x.b),
    "negate": lambda c, x: c.negate(x.a),
    "add_const": lambda c, x: c.add_const(x.a, 0.25),
    "add_plain": lambda c, x: c.add_plain(x.a, x.pt),
    "mul_const_exact": lambda c, x: c.mul_const_exact(x.a, 0.37, x.a.scale),
    "mul_plain": lambda c, x: c.mul_plain(x.a, x.pt, rescale_after=False),
    "rescale": lambda c, x: c.rescale(x.product),
    "mul_equal_levels": lambda c, x: c.mul(x.a, x.b),
    "mul_unequal_levels": lambda c, x: c.mul(x.a, x.b_low),
    "square": lambda c, x: c.square(x.a),
    "rotate": lambda c, x: c.rotate(x.a, 3),
    "rotate_hoisted_group": lambda c, x: c.rotate_hoisted_group(x.a, ROTS),
    "conjugate": lambda c, x: c.conjugate(x.a),
    "force_to": lambda c, x: c.force_to(x.a, L - 2, x.a.scale),
    "apply_bsgs": lambda c, x: c.apply_bsgs(x.a, x.plan),
}

CASES = [(d, op) for d in (2, 3) for op in OPS]


def _cts(out):
    """An op's ciphertexts in a fixed order (a hoisted group returns a dict)."""
    return [out[r] for r in sorted(out)] if isinstance(out, dict) else [out]


@pytest.mark.parametrize("dnum, op", CASES, ids=[f"dnum{d}-{op}" for d, op in CASES])
def test_kernel_pipeline_bitexact_vs_oracle(setups, dnum, op):
    kernel, ref, x = setups(dnum)
    got, want = _cts(OPS[op](kernel, x)), _cts(OPS[op](ref, x))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g.c0), np.asarray(w.c0))
        assert np.array_equal(np.asarray(g.c1), np.asarray(w.c1))
        assert (g.level, g.scale) == (w.level, w.scale)
