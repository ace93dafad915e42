"""The chip's own pipeline, op by op, against the uint64 oracle: BGV.

The BGV half of ``test_chip_pipeline_ops.py``: each op runs on the same
encrypted inputs under ``backend="kernel"`` (the pipeline a TPU resolves
``ExecPolicy()`` to, here in the Pallas interpreter) and under
``backend="ref"``, and ``c0``, ``c1`` and ``level`` must be identical (a BGV
ciphertext carries no scale).  The shape is the ``test_bgv.py`` fixture's:
N=2^9, L=5, dnum=2, t=2^16.  Seeds are fixed.
"""

import numpy as np
import pytest

from repro.fhe import keys as K
from repro.fhe import params as P
from repro.fhe.context import ExecPolicy, FheContext

T = 1 << 16

OPS = {
    "add": lambda c, a, b: c.add(a, b),
    "sub": lambda c, a, b: c.sub(a, b),
    "negate": lambda c, a, b: c.negate(a),
    "mul": lambda c, a, b: c.mul(a, b),
    "square": lambda c, a, b: c.square(a),
    "mod_switch": lambda c, a, b: c.mod_switch(a),
}


@pytest.fixture(scope="module")
def setup():
    """(kernel ctx, ref ctx, a, b): one key set, inputs encrypted under the oracle."""
    p = P.make_params(1 << 9, 5, 2, check_security=False, plain_modulus=T)
    ks = K.full_keyset(p, seed=0)
    kernel, ref = (FheContext(params=p, keys=ks, policy=ExecPolicy(backend=b))
                   for b in ("kernel", "ref"))
    rng = np.random.default_rng(3)
    a, b = (ref.encrypt(ref.encode(rng.integers(0, T, size=p.n).astype(np.int64)), seed=s)
            for s in (5, 6))
    return kernel, ref, a, b


@pytest.mark.parametrize("op", OPS)
def test_kernel_pipeline_bitexact_vs_oracle(setup, op):
    kernel, ref, a, b = setup
    got, want = OPS[op](kernel, a, b), OPS[op](ref, a, b)
    assert np.array_equal(np.asarray(got.c0), np.asarray(want.c0))
    assert np.array_equal(np.asarray(got.c1), np.asarray(want.c1))
    assert got.level == want.level
