"""First-class evaluation contexts: the primary public API of ``repro.fhe``.

Historically every homomorphic op took loose execution kwargs — a kernel
``backend`` (fused/staged/ref/kernel/auto), a rotation ``hoisting`` mode
(never/auto/always), and the planner's ``fused=`` mirror — threaded through
~40 signatures across ``ops``/``linear``/``bootstrap``/``polyeval`` and the
serving memo keys.  ``FheContext`` replaces that threading with one immutable
object bundling the three things an evaluation needs:

  * ``CkksParams``  — the cryptographic parameter set,
  * ``KeySet``      — public/secret/relinearisation/Galois keys (optional for
                      key-less ops like ``add``),
  * ``ExecPolicy``  — *how* to execute: kernel backend, hoisting mode, the
                      numerics mode (future: double-hoisting keeps BSGS inner
                      products in the extended basis — not bit-exact, so it is
                      a policy field, not a kwarg).

Ops are implemented ONCE, against a context (the ``_impl`` functions in
``ops``/``linear``/``bootstrap``/``polyeval``); the legacy module-level free
functions are deprecated shims that build an equivalent context and delegate.
``ExecPolicy.policy_key()`` is the single source of truth wherever a policy
must act as a cache key: the serving service-time memo
(``repro.serve.policy.job_service_sim``) and the planner's mirrored trace
shapes (``repro.core.planner.workload_stream(policy=...)``).

Quick use::

    from repro.fhe import FheContext, ExecPolicy, keys as K, params as P

    p = P.make_params(1 << 9, 6, 2, check_security=False)
    ctx = FheContext(params=p, keys=K.full_keyset(p, rotations=(1,)))

    ct = ctx.encrypt(ctx.encode(x))
    ct = ctx.rotate(ctx.mul(ct, ct), 1)
    y = ctx.decrypt_decode(ct)

    fast = ctx.with_policy(backend="fused", hoisting="always")  # scoped override
"""

from __future__ import annotations

import dataclasses

from repro.kernels import dispatch

from . import bgv as _bgv
from . import bootstrap as _bootstrap
from . import keyswitch, linear, ops, polyeval
from .keys import KeySet, SwitchingKey
from .params import CkksParams

BACKENDS = ("fused", "kernel", "staged", "ref", "auto")
HOISTING_MODES = ops.HOISTING_MODES  # ("never", "auto", "always")
# "standard" is today's exact-arithmetic pipeline; "double_hoist" (Bossuat et
# al.: ModDown once per giant group, ext-basis plaintext muls) is the next
# planned mode — it changes the noise profile, so it must be opted into here
# rather than through yet another kwarg thread.
NUMERICS_MODES = ("standard",)
# Scheme axis: CKKS (approximate complex arithmetic) and BGV (exact integer
# arithmetic mod t) share the whole RNS/NTT/key-switch substrate but expand to
# different instruction streams, so the scheme is part of the policy identity.
SCHEMES = ("ckks", "bgv")


@dataclasses.dataclass(frozen=True)
class ExecPolicy:
    """How to execute: every evaluation-shaping knob, in one immutable value.

    ``policy_key()`` is the canonical cache identity — two policies with equal
    keys are guaranteed to produce identical instruction streams and cycle
    counts, and distinct (scheme, backend, hoisting, numerics) tuples never
    alias.
    """

    backend: str = "auto"  # kernel pipeline: fused | kernel | staged | ref | auto
    hoisting: str = "auto"  # rotation key-switch shape: never | auto | always
    numerics: str = "standard"  # exactness class (future: double_hoist)
    scheme: str = "ckks"  # which scheme's op expansions run: ckks | bgv

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown key-switch backend {self.backend!r}")
        if self.hoisting not in HOISTING_MODES:
            raise ValueError(f"unknown hoisting mode {self.hoisting!r}")
        if self.numerics not in NUMERICS_MODES:
            raise ValueError(
                f"unknown numerics mode {self.numerics!r}; available: {NUMERICS_MODES}"
            )
        if self.scheme not in SCHEMES:
            raise ValueError(f"unknown scheme {self.scheme!r}; available: {SCHEMES}")

    # -- identity -----------------------------------------------------------

    def policy_key(self) -> tuple[str, str, str, str]:
        """Hashable identity for memo keys (serving service times, planner
        stream caches).  The scheme leads: a BGV and a CKKS job with otherwise
        identical knobs run different op expansions and must never share a
        cached service time."""
        return (self.scheme, self.backend, self.hoisting, self.numerics)

    def replace(self, **changes) -> "ExecPolicy":
        return dataclasses.replace(self, **changes)

    def for_scheme(self, scheme: str) -> "ExecPolicy":
        """This policy re-tagged for ``scheme`` (identity when it already
        matches) — the serving layer derives per-job effective policies this
        way, so one engine can price mixed CKKS+BGV traffic distinctly."""
        return self if scheme == self.scheme else dataclasses.replace(self, scheme=scheme)

    # -- resolved views -----------------------------------------------------

    @property
    def stage(self) -> str:
        """Pointwise-stage backend this policy resolves to."""
        return keyswitch.resolve_pipeline(self.backend)[1]

    @property
    def plan_fused(self) -> bool:
        """Does this policy run the fused key-switch pipeline?  Drives the
        planner's working-set boundary records (``fused=`` mirror)."""
        return keyswitch.resolve_pipeline(self.backend)[0] == "fused"

    @property
    def plan_hoist(self) -> bool:
        """Does this policy hoist BSGS baby-step groups?  ``auto`` counts as
        hoisted: every multi-rotation group shares its ModUp."""
        return self.hoisting != "never"


def _spanned(fn):
    """Run a context method under its ``fhe.<method>`` host span."""
    return dispatch.spanned(f"fhe.{fn.__name__}")(fn)


@dataclasses.dataclass(frozen=True)
class FheContext:
    """Immutable (params, keys, policy) bundle — the context every op runs in.

    All methods delegate to the single context-consuming implementations in
    ``ops``/``linear``/``bootstrap``/``polyeval``; the legacy free functions
    are deprecated shims over the same implementations.  Contexts are cheap
    values: ``with_policy`` derives a scoped override sharing params and keys.
    """

    params: CkksParams
    keys: KeySet | None = None
    policy: ExecPolicy = ExecPolicy()

    def __post_init__(self):
        # The scheme is ground truth on the params (plain_modulus set ⇔ BGV);
        # the policy's scheme tag is derived state for cache identity.  Align
        # it here so ``ctx.policy_key()`` is correctly scheme-tagged without
        # every construction site having to thread ``scheme=`` by hand.
        object.__setattr__(self, "policy", self.policy.for_scheme(self.params.scheme))

    # -- derivation ---------------------------------------------------------

    def with_policy(self, policy: ExecPolicy | None = None, **changes) -> "FheContext":
        """A context with an overridden policy (same params/keys).

        Either pass a full ``ExecPolicy`` or field overrides:
        ``ctx.with_policy(backend="fused", hoisting="always")``.
        """
        if policy is not None and changes:
            raise TypeError("pass either a policy or field overrides, not both")
        new = policy if policy is not None else self.policy.replace(**changes)
        return dataclasses.replace(self, policy=new)

    def with_keys(self, keys: KeySet) -> "FheContext":
        return dataclasses.replace(self, keys=keys)

    def policy_key(self) -> tuple[str, str, str, str]:
        return self.policy.policy_key()

    # -- resolved execution knobs (used by the impl layer) ------------------

    @property
    def scheme(self) -> str:
        """The scheme this context evaluates ("ckks" or "bgv") — always equal
        to ``params.scheme`` (aligned at construction)."""
        return self.policy.scheme

    @property
    def backend(self) -> str:
        """Key-switch pipeline choice, passed to the ``keyswitch`` layer."""
        return self.policy.backend

    @property
    def stage(self) -> str:
        """Resolved pointwise-stage backend for elementwise/NTT kernels."""
        return self.policy.stage

    def require_keys(self) -> KeySet:
        if self.keys is None:
            raise ValueError(
                "this operation needs a KeySet; build the context with keys= "
                "or derive one via ctx.with_keys(...)"
            )
        return self.keys

    # -- encode / encrypt / decrypt -----------------------------------------

    @_spanned
    def encode(self, z, level: int | None = None, scale: float | None = None):
        if self.scheme == "bgv":
            return _bgv._encode(self, z, level)
        return ops._encode(self, z, level, scale)

    @_spanned
    def encode_const(self, c, level: int, scale: float) -> "ops.Plaintext":
        return ops._encode_const(self, c, level, scale)

    @_spanned
    def decode(self, pt):
        if self.scheme == "bgv":
            return _bgv._decode(self, pt)
        return ops._decode(self, pt)

    @_spanned
    def encrypt(self, pt, seed: int = 17):
        if self.scheme == "bgv":
            return _bgv._encrypt(self, self.require_keys().pk, pt, seed)
        return ops._encrypt(self, self.require_keys().pk, pt, seed)

    @_spanned
    def decrypt(self, ct):
        if self.scheme == "bgv":
            return _bgv._decrypt(self, self.require_keys().sk, ct)
        return ops._decrypt(self, self.require_keys().sk, ct)

    @_spanned
    def decrypt_decode(self, ct):
        sk = self.require_keys().sk
        if self.scheme == "bgv":
            return _bgv._decode(self, _bgv._decrypt(self, sk, ct))
        return ops._decode(self, ops._decrypt(self, sk, ct))

    # -- additive ops -------------------------------------------------------

    @_spanned
    def add(self, a, b):
        if self.scheme == "bgv":
            return _bgv._add(self, a, b)
        return ops._add(self, a, b)

    @_spanned
    def sub(self, a, b):
        if self.scheme == "bgv":
            return _bgv._sub(self, a, b)
        return ops._sub(self, a, b)

    @_spanned
    def negate(self, a):
        if self.scheme == "bgv":
            return _bgv._negate(self, a)
        return ops._negate(self, a)

    @_spanned
    def add_plain(self, a, pt):
        return ops._add_plain(self, a, pt)

    @_spanned
    def add_const(self, a, c):
        return ops._add_const(self, a, c)

    @_spanned
    def level_drop(self, ct, level: int):
        return ops.level_drop(ct, level)

    # -- multiplicative ops -------------------------------------------------

    @_spanned
    def mul_plain(self, a, pt, rescale_after: bool = True):
        return ops._mul_plain(self, a, pt, rescale_after)

    @_spanned
    def mul_const(self, a, c, rescale_after: bool = True):
        return ops._mul_const(self, a, c, rescale_after)

    @_spanned
    def mul_const_exact(self, a, c, target_scale: float):
        return ops._mul_const_exact(self, a, c, target_scale)

    @_spanned
    def mul(self, a, b, rlk: SwitchingKey | None = None, rescale_after: bool = True):
        """Ciphertext-ciphertext multiplication with relinearisation.  Under a
        BGV context, ``rescale_after`` means "modulus-switch one level down
        after the product" (the BGV analogue of the CKKS rescale)."""
        rlk = rlk if rlk is not None else self.require_keys().rlk
        if self.scheme == "bgv":
            return _bgv._mul(self, a, b, rlk, mod_switch_after=rescale_after)
        return ops._mul(self, a, b, rlk, rescale_after)

    @_spanned
    def square(self, a, rlk: SwitchingKey | None = None, rescale_after: bool = True):
        rlk = rlk if rlk is not None else self.require_keys().rlk
        if self.scheme == "bgv":
            return _bgv._mul(self, a, a, rlk, mod_switch_after=rescale_after)
        return ops._mul(self, a, a, rlk, rescale_after)

    @_spanned
    def rescale(self, ct):
        if self.scheme == "bgv":
            raise ValueError("BGV has no rescale; use ctx.mod_switch(ct) instead")
        return ops._rescale(self, ct)

    @_spanned
    def mod_switch(self, ct):
        """BGV modulus switch: drop the last chain prime, preserving the
        message mod t exactly (q_ℓ ≡ 1 mod t on the shared chain)."""
        if self.scheme != "bgv":
            raise ValueError("mod_switch is a BGV op; use ctx.rescale for CKKS")
        return _bgv._mod_switch(self, ct)

    # -- rotations / conjugation --------------------------------------------

    @_spanned
    def rotate(self, ct, r: int):
        """Cyclic slot rotation by r; the policy's hoisting mode picks the
        key-switch shape ("always" routes a single rotation through the
        hoisted path — bit-exact either way)."""
        return ops._rotate(self, ct, r, self.require_keys())

    @_spanned
    def rotate_hoisted(self, ct, r: int, hoisted=None):
        return ops._rotate_hoisted(self, ct, r, self.require_keys(), hoisted)

    @_spanned
    def rotate_hoisted_group(self, ct, rots) -> dict:
        return ops._rotate_hoisted_group(self, ct, rots, self.require_keys())

    @_spanned
    def conjugate(self, ct):
        return ops._conjugate(self, ct, self.require_keys())

    # -- linear transforms ---------------------------------------------------

    def plan_matrix(self, m, n1: int | None = None, tol: float = 0.0,
                    level: int | None = None) -> "linear.BsgsPlan":
        """BSGS plan for a dense matrix; when ``n1`` is not forced, the baby
        count comes from the hoisting-aware cost model (under a hoisting
        policy, baby steps are nearly free, so the optimum shifts upward)."""
        return linear.plan_matrix(
            m, n1=n1, tol=tol, params=self.params,
            level=self.params.L if level is None else level,
            hoisting=self.policy.plan_hoist,
        )

    @_spanned
    def apply_bsgs(self, ct, plan: "linear.BsgsPlan", scale: float | None = None):
        return linear._apply_bsgs(self, ct, plan, scale)

    @_spanned
    def apply_bsgs_pair(self, ct, plans, scale: float | None = None):
        return (
            linear._apply_bsgs(self, ct, plans[0], scale),
            linear._apply_bsgs(self, ct, plans[1], scale),
        )

    @_spanned
    def real_part(self, ct):
        return linear._real_part(self, ct)

    @_spanned
    def imag_part(self, ct):
        return linear._imag_part(self, ct)

    # -- polynomial evaluation ----------------------------------------------

    @_spanned
    def force_to(self, ct, level: int, scale: float):
        return polyeval._force_to(self, ct, level, scale)

    @_spanned
    def add_any(self, a, b):
        return polyeval._add_any(self, a, b)

    @_spanned
    def chebyshev_basis(self, x, degree: int) -> "polyeval.ChebyshevBasis":
        return polyeval.ChebyshevBasis(self, x, degree)

    @_spanned
    def eval_poly(self, ct, coeffs, degree: int | None = None):
        """Σ c_i·T_i(ct) in the Chebyshev basis (exact scale discipline)."""
        import numpy as np

        degree = len(np.asarray(coeffs)) - 1 if degree is None else degree
        basis = polyeval.ChebyshevBasis(self, ct, degree)
        return polyeval._eval_chebyshev(self, basis, coeffs)

    @_spanned
    def eval_chebyshev(self, basis: "polyeval.ChebyshevBasis", coeffs):
        return polyeval._eval_chebyshev(self, basis, coeffs)

    # -- bootstrapping -------------------------------------------------------

    @_spanned
    def bootstrap(self, bctx: "_bootstrap.BootstrapContext", ct, post_scale: float | None = None):
        """Refresh an exhausted ciphertext through ``bctx``'s precomputed
        plans/keys under THIS context's execution policy."""
        return _bootstrap._bootstrap(self._bootstrap_ctx(bctx), bctx, ct, post_scale)

    @_spanned
    def mod_raise(self, bctx, ct):
        return _bootstrap._mod_raise(self._bootstrap_ctx(bctx), bctx, ct)

    @_spanned
    def coeff_to_slot(self, bctx, ct):
        return _bootstrap._coeff_to_slot(self._bootstrap_ctx(bctx), bctx, ct)

    @_spanned
    def eval_mod(self, bctx, ct, coeff_scale: float):
        return _bootstrap._eval_mod(self._bootstrap_ctx(bctx), bctx, ct, coeff_scale)

    @_spanned
    def slot_to_coeff(self, bctx, a0, a1):
        return _bootstrap._slot_to_coeff(self._bootstrap_ctx(bctx), bctx, a0, a1)

    def _bootstrap_ctx(self, bctx) -> "FheContext":
        """This policy over the bootstrap context's params/keys (the plans are
        precomputed against those — a mismatched KeySet would be unsound)."""
        assert bctx.params == self.params, (
            "BootstrapContext params differ from this FheContext's params"
        )
        if self.keys is bctx.keys:
            return self
        return dataclasses.replace(self, keys=bctx.keys)
