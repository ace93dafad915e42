"""BSGS plaintext matvec with server-resident encoded diagonals.

One LSTM gate's matvec shape at N=2^10: 128 diagonals, n1 = 8 (7 hoisted baby
rotations, 15 giant ones).  The diagonals' encodings stay on the device
between applications, keyed by content: a second application encodes and
uploads nothing, and a changed diagonal is encoded afresh.
"""

import jax
import numpy as np
import pytest

from repro.fhe import encoder, linear
from repro.fhe import keys as K
from repro.fhe import params as P
from repro.fhe.context import ExecPolicy, FheContext
from repro.kernels import dispatch

COUNT, N1, W = 128, 8, 0.0884  # U(−1/√128, 1/√128): nn.LSTM's init at hidden size 128


@pytest.fixture(scope="module")
def setup():
    p = P.make_params(1 << 10, 3, 2, check_security=False)
    rots = tuple(range(1, N1)) + tuple(range(N1, COUNT, N1))
    ks = K.full_keyset(p, seed=4, rotations=rots)
    rng = np.random.default_rng(11)
    x = rng.uniform(-1, 1, p.slots)
    w = rng.uniform(-W, W, (COUNT, p.slots))
    ctx = FheContext(params=p, keys=ks, policy=ExecPolicy(backend="kernel"))
    ct = ctx.encrypt(ctx.encode(x), seed=3)
    return p, ctx, ct, x, w


def _plan(p, w):
    return linear.plan_diags({d: w[d].astype(np.complex128) for d in range(COUNT)}, p, n1=N1)


def _reference(x, w):
    return sum(w[d] * np.roll(x, -d) for d in range(COUNT))


def _tolerance(p, ctx, ct, x, w) -> float:
    """Each product w_d·rot_b(x) carries the rotation's noise (≤ 4× a fresh
    encryption's, tests/test_rotation_noise.py) times |w_d|, plus the
    encoding rounding of w_d (≤ N/(2Δ), times |x| ≤ 1); the giant rotations
    act at scale Δ², where their noise is 1/Δ of that, and the rescale's
    rounding is below N/(2Δ) per component."""
    fresh = np.max(np.abs(ctx.decrypt_decode(ct) - x))
    rounding = encoder.max_encode_error(p.n, p.scale)
    return float(np.sum(np.max(np.abs(w), axis=1)) * 4 * fresh + (COUNT + 2) * rounding)


@pytest.mark.parametrize("backend", ["auto", "kernel"])
def test_apply_bsgs_matches_the_numpy_reference(setup, backend):
    p, ctx, ct, x, w = setup
    ctx = ctx.with_policy(backend=backend)
    out = ctx.apply_bsgs(ct, _plan(p, w))
    err = np.max(np.abs(ctx.decrypt_decode(out) - _reference(x, w)))
    assert err <= _tolerance(p, ctx, ct, x, w), err
    assert out.level == ct.level - 1


@pytest.fixture
def spans(monkeypatch):
    log = []

    class Recorder:
        def __init__(self, name):
            log.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(dispatch, "TraceAnnotation", Recorder)
    return log


def test_second_application_reads_resident_encodings(setup, spans):
    p, ctx, ct, x, w = setup
    first = ctx.apply_bsgs(ct, _plan(p, w))
    before = ctx.diag_cache.stats()
    spans.clear()
    second = ctx.apply_bsgs(ct, _plan(p, w))  # a fresh plan of fresh arrays, as each job builds
    after = ctx.diag_cache.stats()
    assert [n for n in spans if n == "h2d" or n.startswith("table.")] == []
    assert after["hits"] - before["hits"] == COUNT and after["misses"] == before["misses"]
    assert after["entries"] >= COUNT and after["bytes"] >= COUNT * ct.c0.nbytes
    np.testing.assert_array_equal(np.asarray(second.c0), np.asarray(first.c0))
    np.testing.assert_array_equal(np.asarray(second.c1), np.asarray(first.c1))


def test_warm_application_moves_nothing_to_the_device(setup, monkeypatch):
    p, ctx, ct, _, w = setup
    ctx.apply_bsgs(ct, _plan(p, w))
    with jax.transfer_guard_host_to_device("disallow"):
        ctx.apply_bsgs(ct, _plan(p, w))


def test_a_changed_diagonal_misses_and_gives_the_new_result(setup, spans):
    p, ctx, ct, x, w = setup
    plan = _plan(p, w)
    ctx.apply_bsgs(ct, plan)
    w2 = w.copy()
    w2[37] = np.random.default_rng(5).uniform(-W, W, p.slots)
    plan.diags[37][:] = w2[37]  # the same array object, new values
    spans.clear()
    out = ctx.apply_bsgs(ct, plan)
    assert spans.count("table.diag") == 1
    err = np.max(np.abs(ctx.decrypt_decode(out) - _reference(x, w2)))
    assert err <= _tolerance(p, ctx, ct, x, w2), err


def test_cache_is_lru_bounded_by_bytes():
    a = lambda v: np.full(4, v, np.uint32)  # 16 bytes each
    cache = linear.DiagCache(max_bytes=40)
    cache.put("a", a(1))
    cache.put("b", a(2))
    assert cache.get("a") is not None  # a is now the most recent
    cache.put("c", a(3))  # evicts b
    assert cache.get("b") is None and cache.get("c") is not None
    assert cache.stats() == {"entries": 2, "bytes": 32, "hits": 2, "misses": 1}
    cache.put("big", np.zeros(16, np.uint32))  # larger than the whole budget: not kept
    assert cache.get("big") is None and cache.stats()["entries"] == 2
