"""Rotation noise stays flat in N: the key-switch's basis conversions sum
centred residues (``rns.centring_tables``), so a rotation decodes about as
well as a fresh encryption at every ring degree.  With [0, b) residues the
ratio grew with N (5.3, 9.1, 15.6, 24.5 at N = 2^10 .. 2^13, dnum=2)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.fhe import keys as K
from repro.fhe import params as P
from repro.fhe import rns
from repro.fhe.context import ExecPolicy, FheContext
from repro.kernels.bconv import ops as bconv_ops


@pytest.mark.parametrize("logn,dnum", [(10, 2), (11, 2), (12, 2), (13, 2), (13, 1)])
def test_rotation_decodes_within_4x_of_a_fresh_encryption(logn, dnum):
    p = P.make_params(1 << logn, 4, dnum, check_security=False)
    ctx = FheContext(params=p, keys=K.full_keyset(p, seed=0, rotations=(1,)),
                     policy=ExecPolicy(backend="ref"))
    z = np.random.default_rng(logn).uniform(-1, 1, p.slots)
    ct = ctx.encrypt(ctx.encode(z), seed=logn + 5)
    fresh = np.max(np.abs(ctx.decrypt_decode(ct) - z))
    rotated = np.max(np.abs(ctx.decrypt_decode(ctx.rotate(ct, 1)) - np.roll(z, -1)))
    assert rotated <= 4 * fresh, (rotated, fresh)


@pytest.mark.parametrize("backend", ["ref", "kernel"])
def test_centred_conversion_sums_centred_residues(backend):
    """conv_centred(x) = Σ_i ỹ_i·(B/b_i) mod c_j, with ỹ_i the representative
    of [x_i·(B/b_i)⁻¹]_{b_i} in [−⌊b_i/2⌋, ⌈b_i/2⌉), checked in Python integers."""
    p = P.make_params(1 << 9, 5, 2, check_security=False)
    src, dst = p.q_primes[:3], p.q_primes[3:] + p.p_primes
    rng = np.random.default_rng(1)
    x = np.stack([rng.integers(0, b, size=p.n, dtype=np.uint64) for b in src]).astype(np.uint32)
    got = np.asarray(bconv_ops.conv_centred(jnp.asarray(x), src, dst, backend=backend))
    B = rns.product(src)
    for col in (0, 1, p.n // 2, p.n - 1):
        ys = [int(x[i, col]) * pow(B // b, -1, b) % b for i, b in enumerate(src)]
        yc = [y - b if y >= b - b // 2 else y for y, b in zip(ys, src)]
        v = sum(y * (B // b) for y, b in zip(yc, src))
        assert v % B == sum(int(x[i, col]) * (B // b) * pow(B // b, -1, b) for i, b in enumerate(src)) % B
        assert abs(v) <= len(src) * B // 2
        assert [int(got[j, col]) for j in range(len(dst))] == [v % c for c in dst]
