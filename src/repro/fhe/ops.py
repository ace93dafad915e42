"""CKKS homomorphic operations over eval-domain RNS ciphertexts.

Ciphertexts are pairs of (level+1, N) uint32 eval-domain polynomials with a
tracked floating-point scale (Lattigo-style scale management).  All heavy ops
dispatch through the kernel wrappers (Pallas on TPU, u64 oracle elsewhere) and
record trace instructions for the core scheduler/simulator.

Execution choices (kernel backend, rotation-hoisting mode, numerics mode) are
owned by ``repro.fhe.context.FheContext`` — every op here is implemented ONCE
as a context-consuming ``_impl`` function, and the context's methods
(``ctx.add``, ``ctx.rotate``, ...) are the primary API.  The module-level free
functions that took a loose ``backend=`` kwarg are **retired** (retirement
plan step 3, docs/context_api.md): the old names resolve to a module
``__getattr__`` stub that raises with the migration hint.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import dispatch
from repro.kernels.modops import ops as mo

from . import encoder, keyswitch, poly, trace
from .keys import KeySet, PublicKey, SecretKey, SwitchingKey
from .params import CkksParams

HOISTING_MODES = ("never", "auto", "always")


@dataclasses.dataclass
class Ciphertext:
    c0: jnp.ndarray  # (level+1, N) uint32, eval domain
    c1: jnp.ndarray
    level: int
    scale: float

    @property
    def nbytes(self) -> int:
        return int(self.c0.nbytes + self.c1.nbytes)


@dataclasses.dataclass
class Plaintext:
    data: jnp.ndarray  # (level+1, N) uint32, eval domain
    level: int
    scale: float


def _qs(params: CkksParams, level: int) -> np.ndarray:
    return np.array(params.q_primes[: level + 1], np.uint64)


# ---------------------------------------------------------------------------
# encode / encrypt / decrypt — context implementations
# ---------------------------------------------------------------------------


def _encode(ctx, z, level: int | None = None, scale: float | None = None) -> Plaintext:
    params = ctx.params
    level = params.L if level is None else level
    scale = params.scale if scale is None else scale
    primes = params.q_primes[: level + 1]
    coeffs = encoder.encode(np.asarray(z), params.n, scale, primes)
    data = poly.to_eval(coeffs, params, poly.q_idx(params, level), ctx.stage)
    return Plaintext(data=data, level=level, scale=scale)


def _encode_const(ctx, c, level: int, scale: float) -> Plaintext:
    params = ctx.params
    primes = params.q_primes[: level + 1]
    coeffs = encoder.encode_const(c, params.n, scale, primes)
    data = poly.to_eval(coeffs, params, poly.q_idx(params, level), ctx.stage)
    return Plaintext(data=data, level=level, scale=scale)


def _decode(ctx, pt: Plaintext) -> np.ndarray:
    params = ctx.params
    coeffs = poly.to_coeff(pt.data, params, poly.q_idx(params, pt.level), ctx.stage)
    limbs = min(pt.level + 1, 4)
    return encoder.decode(np.asarray(coeffs), params.q_primes[: pt.level + 1], pt.scale, max_limbs=limbs)


def _encrypt(ctx, pk: PublicKey, pt: Plaintext, seed: int = 17) -> Ciphertext:
    params = ctx.params
    rng = np.random.default_rng(seed)
    level = pt.level
    idx = poly.q_idx(params, level)
    qs = _qs(params, level)
    bk = ctx.stage
    v = poly.to_eval(
        poly.to_rns_signed(poly.sample_ternary(rng, params.n, params.n // 2), params.q_primes[: level + 1]),
        params, idx, bk,
    )
    e0 = poly.to_eval(
        poly.to_rns_signed(poly.sample_gaussian(rng, params.n), params.q_primes[: level + 1]), params, idx, bk
    )
    e1 = poly.to_eval(
        poly.to_rns_signed(poly.sample_gaussian(rng, params.n), params.q_primes[: level + 1]), params, idx, bk
    )
    trace.record("PMULT", params.n, 2 * (level + 1))
    c0 = mo.pointwise_addmod(
        mo.pointwise_addmod(mo.pointwise_mulmod(v, pk.b[: level + 1], qs, backend=bk), e0, qs, backend=bk),
        pt.data, qs, backend=bk,
    )
    c1 = mo.pointwise_addmod(mo.pointwise_mulmod(v, pk.a[: level + 1], qs, backend=bk), e1, qs, backend=bk)
    return Ciphertext(c0=c0, c1=c1, level=level, scale=pt.scale)


def _decrypt(ctx, sk: SecretKey, ct: Ciphertext) -> Plaintext:
    params = ctx.params
    qs = _qs(params, ct.level)
    bk = ctx.stage
    trace.record("PMULT", params.n, ct.level + 1)
    m = mo.pointwise_addmod(
        ct.c0, mo.pointwise_mulmod(ct.c1, sk.s_eval[: ct.level + 1], qs, backend=bk), qs, backend=bk
    )
    return Plaintext(data=m, level=ct.level, scale=ct.scale)


# ---------------------------------------------------------------------------
# additive ops — context implementations
# ---------------------------------------------------------------------------


def _align(params: CkksParams, a: Ciphertext, b: Ciphertext) -> tuple[Ciphertext, Ciphertext]:
    """Drop the deeper ciphertext to the shallower level. Scales must match closely."""
    lv = min(a.level, b.level)
    a = level_drop(a, lv)
    b = level_drop(b, lv)
    assert abs(a.scale / b.scale - 1.0) < 1e-9, f"scale mismatch {a.scale} vs {b.scale}"
    return a, b


def level_drop(ct: Ciphertext, level: int) -> Ciphertext:
    if level == ct.level:
        return ct
    assert level < ct.level
    return Ciphertext(c0=poly.limbs(ct.c0, 0, level + 1), c1=poly.limbs(ct.c1, 0, level + 1),
                      level=level, scale=ct.scale)


def _add(ctx, a: Ciphertext, b: Ciphertext) -> Ciphertext:
    params = ctx.params
    a, b = _align(params, a, b)
    qs = _qs(params, a.level)
    bk = ctx.stage
    trace.record("PADD", params.n, 2 * (a.level + 1))
    return Ciphertext(
        c0=mo.pointwise_addmod(a.c0, b.c0, qs, backend=bk),
        c1=mo.pointwise_addmod(a.c1, b.c1, qs, backend=bk),
        level=a.level, scale=a.scale,
    )


def _sub(ctx, a: Ciphertext, b: Ciphertext) -> Ciphertext:
    params = ctx.params
    a, b = _align(params, a, b)
    qs = _qs(params, a.level)
    bk = ctx.stage
    trace.record("PSUB", params.n, 2 * (a.level + 1))
    return Ciphertext(
        c0=mo.pointwise_submod(a.c0, b.c0, qs, backend=bk),
        c1=mo.pointwise_submod(a.c1, b.c1, qs, backend=bk),
        level=a.level, scale=a.scale,
    )


def _negate(ctx, a: Ciphertext) -> Ciphertext:
    params = ctx.params
    qs = _qs(params, a.level)
    bk = ctx.stage
    z = jnp.zeros_like(a.c0)
    trace.record("PSUB", params.n, 2 * (a.level + 1))
    return Ciphertext(
        c0=mo.pointwise_submod(z, a.c0, qs, backend=bk),
        c1=mo.pointwise_submod(z, a.c1, qs, backend=bk),
        level=a.level, scale=a.scale,
    )


def _add_plain(ctx, a: Ciphertext, pt: Plaintext) -> Ciphertext:
    params = ctx.params
    assert pt.level >= a.level
    qs = _qs(params, a.level)
    trace.record("PADD", params.n, a.level + 1)
    return Ciphertext(
        c0=mo.pointwise_addmod(a.c0, poly.limbs(pt.data, 0, a.level + 1), qs, backend=ctx.stage),
        c1=a.c1, level=a.level, scale=a.scale,
    )


def _add_const(ctx, a: Ciphertext, c) -> Ciphertext:
    pt = _encode_const(ctx, c, a.level, a.scale)
    return _add_plain(ctx, a, pt)


# ---------------------------------------------------------------------------
# multiplicative ops — context implementations
# ---------------------------------------------------------------------------


def _mul_plain(ctx, a: Ciphertext, pt: Plaintext, rescale_after: bool = True) -> Ciphertext:
    params = ctx.params
    assert pt.level >= a.level
    qs = _qs(params, a.level)
    bk = ctx.stage
    trace.record("PMULT", params.n, 2 * (a.level + 1))
    d = poly.limbs(pt.data, 0, a.level + 1)
    out = Ciphertext(
        c0=mo.pointwise_mulmod(a.c0, d, qs, backend=bk),
        c1=mo.pointwise_mulmod(a.c1, d, qs, backend=bk),
        level=a.level, scale=a.scale * pt.scale,
    )
    return _rescale(ctx, out) if rescale_after else out


def _mul_const(ctx, a: Ciphertext, c, rescale_after: bool = True) -> Ciphertext:
    pt = _encode_const(ctx, c, a.level, ctx.params.scale)
    return _mul_plain(ctx, a, pt, rescale_after)


def _mul_const_exact(ctx, a: Ciphertext, c, target_scale: float) -> Ciphertext:
    """a·c with the constant's encoding scale chosen so the rescaled result has
    exactly ``target_scale`` — the anchor that keeps scale bookkeeping from
    drifting through multiplicative trees (see polyeval)."""
    params = ctx.params
    q = float(params.q_primes[a.level])
    enc_scale = target_scale * q / a.scale
    assert enc_scale > 256.0, f"enc_scale underflow ({enc_scale}); scale drift upstream"
    pt = _encode_const(ctx, c, a.level, enc_scale)
    out = _mul_plain(ctx, a, pt, rescale_after=True)
    return Ciphertext(out.c0, out.c1, out.level, target_scale)


def _mul(ctx, a: Ciphertext, b: Ciphertext, rlk: SwitchingKey,
         rescale_after: bool = True) -> Ciphertext:
    """Full homomorphic multiplication with relinearisation (key-switch of d2)."""
    params = ctx.params
    a, b = _align_mul(params, a, b)
    qs = _qs(params, a.level)
    bk = ctx.stage
    trace.record("PMULT", params.n, 4 * (a.level + 1))
    d0 = mo.pointwise_mulmod(a.c0, b.c0, qs, backend=bk)
    d2 = mo.pointwise_mulmod(a.c1, b.c1, qs, backend=bk)
    cross1 = mo.pointwise_mulmod(a.c0, b.c1, qs, backend=bk)
    cross2 = mo.pointwise_mulmod(a.c1, b.c0, qs, backend=bk)
    trace.record("PADD", params.n, a.level + 1)
    d1 = mo.pointwise_addmod(cross1, cross2, qs, backend=bk)
    ks0, ks1 = keyswitch.key_switch(d2, params, a.level, rlk, ctx.backend)
    trace.record("PADD", params.n, 2 * (a.level + 1))
    out = Ciphertext(
        c0=mo.pointwise_addmod(d0, ks0, qs, backend=bk),
        c1=mo.pointwise_addmod(d1, ks1, qs, backend=bk),
        level=a.level, scale=a.scale * b.scale,
    )
    return _rescale(ctx, out) if rescale_after else out


def _align_mul(params: CkksParams, a: Ciphertext, b: Ciphertext):
    lv = min(a.level, b.level)
    return level_drop(a, lv), level_drop(b, lv)


def _rescale(ctx, ct: Ciphertext) -> Ciphertext:
    """Divide by q_ℓ and drop a level (eval-domain RNS rescale)."""
    params = ctx.params
    lv = ct.level
    assert lv >= 1, "cannot rescale at level 0"
    q_last = int(params.q_primes[lv])
    qs_rem = _qs(params, lv - 1)
    rem_primes = params.q_primes[:lv]
    bk = ctx.stage
    qinv = np.array([pow(q_last % int(q), -1, int(q)) for q in rem_primes], np.uint64)
    qinv_b = dispatch.upload(qinv[:, None], np.uint32)
    qs_col = dispatch.upload(qs_rem[:, None])
    q_dev = dispatch.upload(q_last, np.uint64)
    half = dispatch.upload(q_last // 2, np.uint64)

    def _one(c):
        # iNTT the dropped limb, re-embed its (centred) coefficients in every
        # remaining basis, NTT back, subtract, multiply by q_ℓ^{-1}.
        last_coeff = poly.to_coeff(poly.limbs(c, lv, lv + 1), params, (lv,), bk)
        v = lax.index_in_dim(last_coeff, 0, keepdims=False).astype(jnp.uint64)
        centered = jnp.where(v > half, v + qs_col - q_dev, v)
        rem = (centered % qs_col).astype(jnp.uint32)
        rem_eval = poly.to_eval(rem, params, poly.q_idx(params, lv - 1), bk)
        trace.record("PSUB", params.n, lv)
        diff = mo.pointwise_submod(poly.limbs(c, 0, lv), rem_eval, qs_rem, backend=bk)
        trace.record("PMULT", params.n, lv)
        return mo.pointwise_mulmod(diff, jnp.broadcast_to(qinv_b, diff.shape), qs_rem, backend=bk)

    return Ciphertext(c0=_one(ct.c0), c1=_one(ct.c1), level=lv - 1, scale=ct.scale / q_last)


# ---------------------------------------------------------------------------
# rotations / conjugation — context implementations
# ---------------------------------------------------------------------------


def _rotate(ctx, ct: Ciphertext, r: int, keys: KeySet) -> Ciphertext:
    """Cyclic left-rotation of the slot vector by r (σ_{5^r} + key switch).

    The policy's hoisting mode selects the key-switch shape: "never"/"auto"
    run the standard per-rotation ModUp (a single rotation has nothing to
    amortise); "always" routes through the hoisted path — bit-exact either
    way.  Groups of rotations of the same ciphertext should use
    ``rotate_hoisted_group`` to actually share the ModUp.
    """
    params = ctx.params
    if r % params.slots == 0:
        return ct
    if ctx.policy.hoisting == "always":
        return _rotate_hoisted(ctx, ct, r, keys)
    return _rotate_standard(ctx, ct, r, keys)


def _rotate_standard(ctx, ct: Ciphertext, r: int, keys: KeySet) -> Ciphertext:
    """Per-rotation key switch regardless of the policy's hoisting mode —
    the path for rotations of *distinct* ciphertexts (e.g. BSGS giant steps),
    which can never share a ModUp."""
    params = ctx.params
    if r % params.slots == 0:
        return ct
    t = pow(5, r % params.slots, 2 * params.n)
    return _apply_galois(ctx, ct, t, keys)


def _rotate_hoisted(ctx, ct: Ciphertext, r: int, keys: KeySet,
                    hoisted: keyswitch.HoistedDigits | None = None) -> Ciphertext:
    """Hoisted rotation: reuse (or build) the ModUp decomposition of ct.c1.

    Pass ``hoisted=keyswitch.hoisted_mod_up(ct.c1, ...)`` to amortise the
    ModUp across several calls on the same ciphertext; each call then costs
    only KSK-MAC + ModDown + one automorphism.  Bit-exact vs ``rotate``.
    """
    params = ctx.params
    if r % params.slots == 0:
        return ct
    t = pow(5, r % params.slots, 2 * params.n)
    hd = hoisted if hoisted is not None else keyswitch.hoisted_mod_up(
        ct.c1, params, ct.level, ctx.backend
    )
    c0, c1 = keyswitch.rotate_hoisted(ct.c0, hd, t, keys, params, ct.level, ctx.backend)
    return Ciphertext(c0=c0, c1=c1, level=ct.level, scale=ct.scale)


def _rotate_hoisted_group(ctx, ct: Ciphertext, rots, keys: KeySet) -> dict[int, Ciphertext]:
    """Halevi–Shoup hoisting: ONE ModUp shared by every rotation in ``rots``.

    The fused pipeline batches the whole group: one ModUp launch, one Galois
    KSK-MAC launch covering every rotation's key (hoisted digits resident in
    VMEM), and one batched ModDown pair launch — O(β + k) extended-basis NTTs
    for k rotations instead of O(k·β).  Returns {r: rotated ciphertext} keyed
    by the input rotation values; each entry is bit-exact vs ``rotate``.
    """
    params = ctx.params
    backend = ctx.backend
    uniq: dict[int, int] = {}  # r mod slots → galois element
    for r in rots:
        rm = r % params.slots
        if rm and rm not in uniq:
            uniq[rm] = pow(5, rm, 2 * params.n)
    if not uniq:
        return {r: ct for r in rots}
    lv = ct.level
    hd = keyswitch.hoisted_mod_up(ct.c1, params, lv, backend)
    ksk_stack = jnp.stack(
        [keyswitch.hoisted_ksk(params, keys, t, lv) for t in uniq.values()]
    )
    accs = keyswitch.hoisted_galois_ks(hd, ksk_stack, params, lv, backend)
    ks = keyswitch.mod_down_group(accs, params, lv, backend)
    by_rm: dict[int, Ciphertext] = {}
    for i, (rm, t) in enumerate(uniq.items()):
        c0, c1 = keyswitch.permute_last(ct.c0, ks[i, 0], ks[i, 1], t, params, lv, backend)
        by_rm[rm] = Ciphertext(c0=c0, c1=c1, level=lv, scale=ct.scale)
    return {r: (by_rm[r % params.slots] if r % params.slots else ct) for r in rots}


def _conjugate(ctx, ct: Ciphertext, keys: KeySet) -> Ciphertext:
    t = 2 * ctx.params.n - 1
    return _apply_galois(ctx, ct, t, keys)


def _apply_galois(ctx, ct: Ciphertext, t: int, keys: KeySet) -> Ciphertext:
    """Key-switched automorphism σ_t, permute-last formulation.

    The key-switch runs against the σ_t^{-1}-pre-permuted Galois key and the
    shared ``keyswitch.permute_last`` epilogue lands the result.  This is the
    same per-digit math as the hoisted path — ``rotate`` and
    ``rotate_hoisted``/``rotate_hoisted_group`` are bit-exact against each
    other — and the trace shape matches the classic permute-first pipeline
    (2×AUTO + key-switch + PADD).
    """
    params = ctx.params
    lv = ct.level
    ksk_pre = keyswitch.hoisted_ksk(params, keys, t, lv)
    ks0, ks1 = keyswitch.key_switch_selected(ct.c1, params, lv, ksk_pre, ctx.backend)
    c0, c1 = keyswitch.permute_last(ct.c0, ks0, ks1, t, params, lv, ctx.backend)
    return Ciphertext(c0=c0, c1=c1, level=lv, scale=ct.scale)

