"""Kernel-vs-oracle tests for BConv and fused pointwise modops."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.fhe import modmath as mm
from repro.kernels.bconv import ops as bconv_ops
from repro.kernels.modops import ops as modops


PRIMES = mm.gen_ntt_primes(30, 8, 2 << 16) + mm.gen_ntt_primes(26, 8, 2 << 16)


@pytest.mark.parametrize("k,m,n", [(3, 2, 256), (8, 5, 512), (13, 7, 4096), (60, 8, 4096)])
def test_bconv_kernel_matches_ref(k, m, n):
    rng = np.random.default_rng(k * 1000 + m)
    assert k + m <= len(PRIMES) or k > 8  # reuse primes for big k
    bs = [PRIMES[i % 8] for i in range(k)]
    cs = np.array(PRIMES[8 : 8 + m], np.uint32)
    xhat = np.stack([rng.integers(0, b, size=n, dtype=np.uint32) for b in bs])
    w = np.stack([rng.integers(0, cs, dtype=np.uint32) for _ in range(k)])  # (k, m)
    got_k = bconv_ops.bconv(jnp.asarray(xhat), jnp.asarray(w), cs, backend="kernel")
    got_r = bconv_ops.bconv(jnp.asarray(xhat), jnp.asarray(w), cs, backend="ref")
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(got_r))
    # independent check against slow exact host computation on a few columns
    for col in (0, n // 2, n - 1):
        for j in range(m):
            expect = sum(int(xhat[i, col]) * int(w[i, j]) for i in range(k)) % int(cs[j])
            assert int(got_r[j, col]) == expect


@pytest.mark.parametrize("shape", [(2, 256), (3, 4096), (2, 3, 1024)])
def test_pointwise_ops_kernel_matches_ref(shape):
    rng = np.random.default_rng(42)
    l = shape[-2]
    qs = np.array(PRIMES[:l], np.uint32)
    a = (rng.integers(0, 1 << 31, size=shape + (0,)[:0]).astype(np.uint64) % qs.reshape((1,) * (len(shape) - 2) + (l, 1))).astype(np.uint32)
    b = (rng.integers(0, 1 << 31, size=shape).astype(np.uint64) % qs.reshape((1,) * (len(shape) - 2) + (l, 1))).astype(np.uint32)
    a = a.reshape(shape)
    mk = modops.pointwise_mulmod(jnp.asarray(a), jnp.asarray(b), qs, backend="kernel")
    mr = modops.pointwise_mulmod(jnp.asarray(a), jnp.asarray(b), qs, backend="ref")
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    ak = modops.pointwise_addmod(jnp.asarray(a), jnp.asarray(b), qs, backend="kernel")
    ar = modops.pointwise_addmod(jnp.asarray(a), jnp.asarray(b), qs, backend="ref")
    np.testing.assert_array_equal(np.asarray(ak), np.asarray(ar))
    sk = modops.pointwise_submod(jnp.asarray(a), jnp.asarray(b), qs, backend="kernel")
    sr = modops.pointwise_submod(jnp.asarray(a), jnp.asarray(b), qs, backend="ref")
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


POINTWISE = {
    "mulmod": (modops.pointwise_mulmod, lambda a, b, q: a * b % q),
    "addmod": (modops.pointwise_addmod, lambda a, b, q: (a + b) % q),
    "submod": (modops.pointwise_submod, lambda a, b, q: (a + q - b) % q),
}


@pytest.mark.parametrize("op", list(POINTWISE))
@pytest.mark.parametrize("l", [1, 5])
@pytest.mark.parametrize("lead", [(), (2,), (2, 2)])
def test_pointwise_kernel_indexes_constants_by_limb(op, l, lead):
    """Each (batch row, limb) program reads its own limb's constants: the
    kernel matches the oracle and exact host arithmetic on every leading batch."""
    fn, exact = POINTWISE[op]
    rng = np.random.default_rng([l, len(lead)])
    qs = np.array(PRIMES[6 : 6 + l], np.uint64)  # both prime widths once l > 2
    q = qs[:, None]
    a, b = (rng.integers(0, 1 << 62, size=lead + (l, 256), dtype=np.uint64) % q for _ in range(2))
    got = fn(jnp.asarray(a.astype(np.uint32)), jnp.asarray(b.astype(np.uint32)), qs, backend="kernel")
    want = fn(jnp.asarray(a.astype(np.uint32)), jnp.asarray(b.astype(np.uint32)), qs, backend="ref")
    assert got.shape == lead + (l, 256)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), exact(a, b, q).astype(np.uint32))


def test_limb_constants_built_inside_jit_stay_device_arrays():
    """A limb set first met while tracing an enclosing ``jax.jit`` caches
    device arrays, which later eager calls use as they are."""
    import jax

    qs = np.array(PRIMES[9:12], np.uint64)
    modops._limb_tables.cache_clear()
    a = jnp.asarray(np.arange(3 * 256, dtype=np.uint32).reshape(3, 256))
    traced = jax.jit(lambda x: modops.pointwise_mulmod(x, x, qs, backend="ref"))(a)
    assert all(isinstance(c, jax.Array) and not isinstance(c, jax.core.Tracer)
               for c in modops.limb_constants(qs))
    eager = modops.pointwise_mulmod(a, a, qs, backend="ref")
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(eager))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_bconv_exact_crt_property(seed):
    """BConv of x in basis B to C equals x + u·B for small u ≥ 0 (CRT property)."""
    rng = np.random.default_rng(seed)
    bs = PRIMES[:3]
    cs = PRIMES[8:10]
    B = int(np.prod([int(b) for b in bs], dtype=object))
    x = int(rng.integers(0, min(B, 1 << 60)))
    bhat_inv = [pow(B // b, -1, b) for b in bs]
    xhat = np.array([[x % b * bhat_inv[i] % b] for i, b in enumerate(bs)], np.uint32)
    w = np.array([[(B // b) % c for c in cs] for b in bs], np.uint32)
    got = np.asarray(bconv_ops.bconv(jnp.asarray(xhat), jnp.asarray(w), np.array(cs, np.uint32), backend="ref"))
    # exact value mod c_j must be (x + u·B) mod c_j for some 0 ≤ u < 3
    ok = False
    for u in range(len(bs)):
        if all(int(got[j, 0]) == (x + u * B) % c for j, c in enumerate(cs)):
            ok = True
            break
    assert ok
