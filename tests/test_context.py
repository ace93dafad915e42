"""FheContext / ExecPolicy: the evaluation-context API.

Three contracts pinned here:

  * **shim lifecycle** — every retired free-function tranche
    (linear/polyeval/bootstrap, and now the ``fhe.ops`` kwarg-threading
    entry points) raises ``AttributeError`` with the context migration hint,
    never silently delegating; the context methods carry the full numerics
    contract (cross-backend bit-exactness, hypothesis-driven);
  * **policy identity** — ``ExecPolicy.policy_key()`` distinguishes every
    (scheme, backend, hoisting, numerics) combination and is what keys the
    serving service-time memo (no mode aliasing);
  * **planning** — ``plan_matrix``/``choose_n1`` pick the baby-step count
    from the hoisting-aware cost model (n1 = 16 for the radix-32 CtS stage
    shape the hoisting bench measures, vs the classic √n without hoisting).
"""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hardware as H
from repro.core import jobs as J
from repro.core import planner as PL
from repro.fhe import keys as K
from repro.fhe import linear, ops, polyeval
from repro.fhe import params as P
from repro.fhe.context import BACKENDS, HOISTING_MODES, NUMERICS_MODES, ExecPolicy, FheContext
from repro.serve import policy as SP

ROTS = (1, 2, 3, 4, 5)


def _ct_equal(a, b) -> bool:
    return bool(jnp.array_equal(a.c0, b.c0)) and bool(jnp.array_equal(a.c1, b.c1))


@pytest.fixture(scope="module")
def cset():
    p = P.make_params(1 << 9, 5, 2, check_security=False)
    ks = K.full_keyset(p, seed=0, rotations=ROTS, conjugate=True)
    ctx = FheContext(params=p, keys=ks)
    rng = np.random.default_rng(3)
    za = rng.normal(size=p.slots) * 0.3
    zb = rng.normal(size=p.slots) * 0.3
    ct_a = ctx.encrypt(ctx.encode(za))
    ct_b = ctx.encrypt(ctx.encode(zb), seed=23)
    return p, ks, ctx, ct_a, ct_b, za, zb


# ---------------------------------------------------------------------------
# numerics contract: every (backend, hoisting) combination ≡ ref/never, bit-exact
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(backend=st.sampled_from(("ref", "fused")),
       hoisting=st.sampled_from(HOISTING_MODES),
       r=st.sampled_from(ROTS))
def test_ops_backends_bitexact_vs_reference(cset, backend, hoisting, r):
    p, ks, _, ct_a, ct_b, _, _ = cset
    ctx = FheContext(params=p, keys=ks,
                     policy=ExecPolicy(backend=backend, hoisting=hoisting))
    ref = FheContext(params=p, keys=ks,
                     policy=ExecPolicy(backend="ref", hoisting="never"))
    pairs = [
        (ctx.add(ct_a, ct_b), ref.add(ct_a, ct_b)),
        (ctx.sub(ct_a, ct_b), ref.sub(ct_a, ct_b)),
        (ctx.negate(ct_a), ref.negate(ct_a)),
        (ctx.mul(ct_a, ct_b), ref.mul(ct_a, ct_b)),
        (ctx.square(ct_a), ref.square(ct_a)),
        (ctx.rotate(ct_a, r), ref.rotate(ct_a, r)),
        (ctx.conjugate(ct_a), ref.conjugate(ct_a)),
        (ctx.rescale(ct_a), ref.rescale(ct_a)),
        (ctx.add_const(ct_a, 0.25), ref.add_const(ct_a, 0.25)),
        (ctx.mul_const(ct_a, 0.5), ref.mul_const(ct_a, 0.5)),
    ]
    for got, want in pairs:
        assert _ct_equal(got, want)
        assert got.level == want.level and got.scale == want.scale


@settings(max_examples=4, deadline=None)
@given(backend=st.sampled_from(("ref", "fused")),
       hoisting=st.sampled_from(HOISTING_MODES))
def test_encode_encrypt_decrypt_backends_bitexact(cset, backend, hoisting):
    p, ks, _, _, _, za, _ = cset
    ctx = FheContext(params=p, keys=ks,
                     policy=ExecPolicy(backend=backend, hoisting=hoisting))
    ref = FheContext(params=p, keys=ks,
                     policy=ExecPolicy(backend="ref", hoisting="never"))
    pt = ctx.encode(za)
    pt_r = ref.encode(za)
    assert bool(jnp.array_equal(pt.data, pt_r.data))
    ct = ctx.encrypt(pt, seed=5)
    ct_r = ref.encrypt(pt_r, seed=5)
    assert _ct_equal(ct, ct_r)
    got = ctx.decrypt_decode(ct)
    want = ref.decrypt_decode(ct_r)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(got - za).max() < 1e-3


@settings(max_examples=6, deadline=None)
@given(backend=st.sampled_from(("ref", "fused")),
       hoisting=st.sampled_from(HOISTING_MODES))
def test_apply_bsgs_modes_bitexact_and_correct(cset, backend, hoisting):
    """The linear-transform shims retired; the context path carries the whole
    contract now: every (backend, hoisting) combination is bit-exact against
    the reference mode and numerically matches the plain matvec."""
    p, ks, _, ct_a, _, za, _ = cset
    rng = np.random.default_rng(11)
    m = np.zeros((p.slots, p.slots))
    for d in range(4):
        m[np.arange(p.slots), (np.arange(p.slots) + d) % p.slots] = rng.normal(size=p.slots) * 0.2
    plan = linear.plan_matrix(m, n1=2, tol=1e-12)
    assert plan.rotations() <= set(ROTS)  # keys for every needed rotation exist
    ctx = FheContext(params=p, keys=ks,
                     policy=ExecPolicy(backend=backend, hoisting=hoisting))
    got = ctx.apply_bsgs(ct_a, plan)
    base = FheContext(params=p, keys=ks,
                      policy=ExecPolicy(backend="ref", hoisting="never"))
    assert _ct_equal(got, base.apply_bsgs(ct_a, plan))
    np.testing.assert_allclose(np.asarray(ctx.decrypt_decode(got)).real,
                               m @ za, atol=5e-3)


def test_real_imag_part_correct(cset):
    p, _, ctx, ct_a, _, za, _ = cset
    np.testing.assert_allclose(np.asarray(ctx.decrypt_decode(ctx.real_part(ct_a))).real,
                               za, atol=1e-3)
    np.testing.assert_allclose(np.asarray(ctx.decrypt_decode(ctx.imag_part(ct_a))).real,
                               np.zeros(p.slots), atol=1e-3)


def test_eval_poly_parity(cset):
    """ctx.eval_poly ≡ explicit basis + ctx.eval_chebyshev, and both match
    the numpy Chebyshev evaluation."""
    p, ks, ctx, ct_a, _, za, _ = cset
    coeffs = np.array([0.1, 0.8, 0.0, -0.2])
    got = ctx.eval_poly(ct_a, coeffs)
    basis = ctx.chebyshev_basis(ct_a, len(coeffs) - 1)
    want = ctx.eval_chebyshev(basis, coeffs)
    assert _ct_equal(got, want)
    assert got.scale == want.scale and got.level == want.level
    np.testing.assert_allclose(np.asarray(ctx.decrypt_decode(got)).real,
                               np.polynomial.chebyshev.Chebyshev(coeffs)(za), atol=1e-3)


def test_force_to_add_any_exactness(cset):
    p, _, ctx, ct_a, ct_b, za, zb = cset
    lo = ctx.mul(ct_a, ct_a)  # one level down, scale back at ≈ 2^30
    forced = ctx.force_to(ct_b, lo.level, lo.scale)
    assert forced.level == lo.level and forced.scale == lo.scale
    np.testing.assert_allclose(np.asarray(ctx.decrypt_decode(forced)).real, zb, atol=1e-3)
    got = ctx.add_any(lo, ct_b)  # aligns the fresh ct down to lo's level
    np.testing.assert_allclose(np.asarray(ctx.decrypt_decode(got)).real,
                               za * za + zb, atol=2e-3)


def test_hoisting_modes_bitexact_through_context(cset):
    """All three hoisting modes agree through the context API (group sharing
    included) — the context must not change the numerics contract."""
    _, _, ctx, ct_a, _, _, _ = cset
    base = {r: ctx.with_policy(hoisting="never").rotate(ct_a, r) for r in ROTS}
    always = ctx.with_policy(hoisting="always")
    group = always.rotate_hoisted_group(ct_a, ROTS)
    for r in ROTS:
        assert _ct_equal(base[r], always.rotate(ct_a, r))
        assert _ct_equal(base[r], group[r])


# ---------------------------------------------------------------------------
# policy identity: policy_key never aliases
# ---------------------------------------------------------------------------


def test_policy_key_distinguishes_every_combination():
    keys = set()
    combos = list(itertools.product(BACKENDS, HOISTING_MODES, NUMERICS_MODES))
    for backend, hoisting, numerics in combos:
        keys.add(ExecPolicy(backend=backend, hoisting=hoisting,
                            numerics=numerics).policy_key())
    assert len(keys) == len(combos), "policy_key aliases distinct policies"


def test_policy_validation():
    with pytest.raises(ValueError):
        ExecPolicy(backend="vectorized")
    with pytest.raises(ValueError):
        ExecPolicy(hoisting="sometimes")
    with pytest.raises(ValueError):
        ExecPolicy(numerics="double_hoist")  # future mode: not implemented yet


def test_service_memo_keys_on_policy():
    """Distinct ExecPolicies must occupy distinct service-time memo entries —
    the serving regression the policy_key contract exists for."""
    job = J.make_job("lola_mnist_plain")
    fused_never = SP.job_service_sim(job, H.FLASH_FHE,
                                     policy=ExecPolicy(backend="fused", hoisting="never"))
    fused_always = SP.job_service_sim(job, H.FLASH_FHE,
                                      policy=ExecPolicy(backend="fused", hoisting="always"))
    staged_never = SP.job_service_sim(job, H.FLASH_FHE,
                                      policy=ExecPolicy(backend="staged", hoisting="never"))
    assert fused_never is not fused_always
    assert fused_never is not staged_never
    # staged pipeline pays working-set round-trips the fused one doesn't
    assert staged_never.cycles > fused_never.cycles
    # legacy bool spelling lands on the same entries (one source of truth)
    assert SP.job_service_sim(job, H.FLASH_FHE, hoist=False) is fused_never
    assert SP.job_service_sim(job, H.FLASH_FHE, hoist=True) is fused_always
    assert SP.exec_policy_from_hoist(True).policy_key() == (
        "ckks", "fused", "always", "standard")


def test_workload_stream_policy_mirrors_legacy_flags():
    """workload_stream(policy=) must reproduce the legacy hoist-bool streams
    (fused pipeline) exactly, and a staged policy must add WS boundaries."""
    p = P.workload_params("lola_mnist_plain")
    for hoist in (False, True):
        legacy = PL.workload_stream("lola_mnist_plain", p, mode="hw", hoist=hoist)
        policy = PL.workload_stream(
            "lola_mnist_plain", p, mode="hw",
            policy=ExecPolicy(backend="fused",
                              hoisting="always" if hoist else "never"))
        assert [(i.op, i.n, i.limbs) for i in legacy] == [
            (i.op, i.n, i.limbs) for i in policy]
    staged = PL.workload_stream("lola_mnist_plain", p, mode="hw",
                                policy=ExecPolicy(backend="staged"))
    fused = PL.workload_stream("lola_mnist_plain", p, mode="hw",
                               policy=ExecPolicy(backend="fused"))
    n_ws = lambda s: sum(1 for i in s if i.op == "STORE_WS")
    assert n_ws(staged) > n_ws(fused)


# ---------------------------------------------------------------------------
# context ergonomics: with_policy, hooks, keys
# ---------------------------------------------------------------------------


def test_with_policy_scoped_override(cset):
    _, ks, ctx, _, _, _, _ = cset
    fast = ctx.with_policy(backend="fused", hoisting="always")
    assert fast.keys is ks and fast.params is ctx.params
    assert fast.policy.backend == "fused" and ctx.policy.backend == "auto"
    replaced = ctx.with_policy(policy=ExecPolicy(backend="ref"))
    assert replaced.policy.backend == "ref"
    with pytest.raises(TypeError):
        ctx.with_policy(policy=ExecPolicy(), backend="ref")


def test_keyless_context_rejects_key_ops(cset):
    p, _, _, ct_a, _, _, _ = cset
    bare = FheContext(params=p)
    with pytest.raises(ValueError, match="KeySet"):
        bare.rotate(ct_a, 1)
    with pytest.raises(ValueError, match="KeySet"):
        bare.mul(ct_a, ct_a)
    # key-less ops still work
    assert _ct_equal(bare.add(ct_a, ct_a), bare.add(ct_a, ct_a))


# ---------------------------------------------------------------------------
# hoisting-aware BSGS planning
# ---------------------------------------------------------------------------


def test_choose_n1_shifts_under_hoisting():
    """The radix-32 CtS stage shape (63 diagonals) at the hoisting bench's
    parameters: classic balance point n1 = 8 unhoisted, n1 = 16 hoisted —
    the value the bench used to hand-pick."""
    p = P.make_params(1 << 14, 3, 3, check_security=False)
    assert linear.choose_n1(range(63), p, p.L, hoisted=False) == 8
    assert linear.choose_n1(range(63), p, p.L, hoisted=True) == 16
    # the hoisted optimum never costs more than the unhoisted plan's split
    c_h = linear.bsgs_rotation_cost(range(63), 16, p, p.L, hoisted=True)
    c_u = linear.bsgs_rotation_cost(range(63), 8, p, p.L, hoisted=False)
    assert c_h < c_u


def test_plan_matrix_uses_cost_model_with_params():
    p = P.make_params(1 << 9, 5, 2, check_security=False)
    rng = np.random.default_rng(0)
    m = rng.normal(size=(p.slots, p.slots))
    classic = linear.plan_matrix(m)
    assert classic.n1 == 16  # √256, the historical default — unchanged
    modeled = linear.plan_matrix(m, params=p, hoisting=False)
    assert modeled.n1 == linear.choose_n1(range(p.slots), p, p.L, hoisted=False)
    hoisted = linear.plan_matrix(m, params=p, hoisting=True)
    assert hoisted.n1 >= modeled.n1  # babies get cheaper, never scarcer
    forced = linear.plan_matrix(m, n1=4, params=p, hoisting=True)
    assert forced.n1 == 4  # explicit n1 always wins


def test_context_plan_matrix_follows_policy(cset):
    p, _, ctx, ct_a, _, _, _ = cset
    rng = np.random.default_rng(5)
    m = np.zeros((p.slots, p.slots))
    for d in range(6):
        m[np.arange(p.slots), (np.arange(p.slots) + d) % p.slots] = rng.normal(size=p.slots)
    plan_h = ctx.with_policy(hoisting="always").plan_matrix(m, tol=1e-12)
    plan_n = ctx.with_policy(hoisting="never").plan_matrix(m, tol=1e-12)
    assert plan_h.n1 >= plan_n.n1
    # both plans compute the same transform
    got_h = ctx.with_policy(hoisting="always").apply_bsgs(ct_a, plan_h)
    got_n = ctx.with_policy(hoisting="never").apply_bsgs(ct_a, plan_n)
    dec_h = ctx.decrypt_decode(got_h)
    dec_n = ctx.decrypt_decode(got_n)
    assert np.abs(np.asarray(dec_h) - np.asarray(dec_n)).max() < 1e-3


def test_plan_diags_banded():
    p = P.make_params(1 << 9, 5, 2, check_security=False)
    diags = {d: np.ones(p.slots, np.complex128) for d in range(7)}
    plan = linear.plan_diags(diags, p, hoisting=True)
    assert set(plan.diags) == set(range(7))
    assert plan.n1 == linear.choose_n1(range(7), p, p.L, hoisted=True)


# ---------------------------------------------------------------------------
# deprecation surface
# ---------------------------------------------------------------------------


def test_retired_names_raise_plain_attribute_error():
    """Retirement complete (docs/context_api.md step 5): the transitional
    ``__getattr__`` stub tables are deleted, so every legacy free-function
    name raises a PLAIN AttributeError — no migration-hint string and no
    module ``__getattr__`` left behind in the four op modules."""
    from repro.fhe import bootstrap

    retired = [
        (linear, "apply_bsgs"), (linear, "apply_bsgs_pair"),
        (linear, "real_part"), (linear, "imag_part"),
        (polyeval, "force_to"), (polyeval, "add_any"),
        (polyeval, "eval_chebyshev"),
        (bootstrap, "bootstrap"), (bootstrap, "mod_raise"),
        (bootstrap, "coeff_to_slot"), (bootstrap, "eval_mod"),
        (bootstrap, "slot_to_coeff"),
    ]
    retired += [(ops, name) for name in (
        "encode", "encode_const", "decode", "encrypt", "decrypt",
        "decrypt_decode", "add", "sub", "negate", "add_plain", "add_const",
        "mul_plain", "mul_const", "mul_const_exact", "mul", "square",
        "rescale", "rotate", "rotate_hoisted", "rotate_hoisted_group",
        "conjugate")]
    for mod, _ in retired:
        assert not hasattr(mod, "__getattr__"), f"{mod.__name__} keeps a stub"
    for mod, name in retired:
        with pytest.raises(AttributeError) as exc:
            getattr(mod, name)
        assert "docs/context_api.md" not in str(exc.value)
    with pytest.raises(AttributeError):
        linear.no_such_function  # unknown names still raise plainly
    with pytest.raises(AttributeError):
        ops.no_such_function
    # non-retired ops module members stay importable (level_drop is API)
    assert callable(ops.level_drop)
