"""Hybrid key switching — the iNTT→BConv→NTT pipeline the paper accelerates.

`key_switch(d, level, ...)` homomorphically maps a polynomial d (eval domain,
basis q_0..q_ℓ) multiplied by s' into a pair under s:

    1. INTT d over the active basis                       (iNTT stage)
    2. per digit j < β(ℓ): prescale by [B̂_i^{-1}]_{b_i},
       BConv digit → {q_0..q_ℓ} ∪ {p_0..p_α-1}            (BConv stage)
    3. NTT each converted digit over the extended basis   (NTT stage)
    4. accumulate  Σ_j  d̂_j ∘ ksk_j                       (MAC stage)
    5. ModDown by P: INTT(P limbs) → BConv P→Q → NTT → subtract, ×[P^{-1}]_q

Two pipeline shapes execute the same math:

  * **fused** — stages 2–4 run as ONE `pallas_call` per key-switch (and one
    more for the ModDown tails of both accumulators) via
    ``repro.kernels.fusedks``; intermediates stay in VMEM, and the trace
    carries the fused per-stage records with no working-set boundaries.
    This is FLASH-FHE's fused key-switch datapath.
  * **staged** — one kernel launch per stage per digit (the F1+-style
    software pipeline); every stage boundary emits STORE_WS/LOAD_WS trace
    records because the intermediate polynomial round-trips through
    HBM-equivalent buffers between launches.

``backend`` selects both the pipeline and the stage numerics:
  "fused"/"kernel" → fused Pallas pipeline (interpreted off-TPU);
  "staged"         → staged pipeline, per-stage auto backends;
  "ref"            → staged pipeline, u64 oracle stages (jit-traceable);
  "auto"           → fused on TPU, staged-ref elsewhere (CPU tests stay fast).

Every stage records trace instructions; this function *is* the workload the
bootstrappable clusters are shaped around.  Each stage entry point
runs under a ``ks.<stage>`` host span on the profiler's clock
(``repro.kernels.dispatch``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import dispatch, tpu
from repro.kernels.bconv import ops as bconv_ops
from repro.kernels.fusedks import ops as fused_ops
from repro.kernels.hoistrot import ops as hoist_ops
from repro.kernels.modops import ops as mo
from repro.kernels.ntt import ops as ntt_ops

from . import poly, rns, trace
from .keys import KeySet, SwitchingKey
from .params import CkksParams


def resolve_pipeline(backend: str) -> tuple[str, str]:
    """Map a backend choice to (pipeline, stage_backend)."""
    if backend == "fused":
        return "fused", "auto"
    if backend == "kernel":
        return "fused", "kernel"
    if backend == "staged":
        return "staged", "auto"
    if backend == "ref":
        return "staged", "ref"
    if backend == "auto":
        return ("fused", "auto") if tpu.on_tpu() else ("staged", "ref")
    raise ValueError(f"unknown key-switch backend {backend!r}")


def _boundary(n: int, limbs: int) -> None:
    """A staged-dispatch boundary: the intermediate round-trips through memory."""
    trace.record("STORE_WS", n, limbs)
    trace.record("LOAD_WS", n, limbs)


@functools.lru_cache(maxsize=2048)
@dispatch.spanned("table.ks_digit")
def _digit_tables(params: CkksParams, level: int, j: int):
    """(src_idx, bhat_inv, w, dst_primes) for digit j at ``level``."""
    digit_idx = tuple(i for i in params.digit(j) if i <= level)
    src = poly.primes_for(params, digit_idx)
    dst_idx = poly.ext_idx(params, level)
    dst = poly.primes_for(params, dst_idx)
    bhat_inv, w = rns.bconv_tables(src, dst)
    return digit_idx, dispatch.upload(bhat_inv), dispatch.upload(w), np.array(dst, np.uint64)


@functools.lru_cache(maxsize=512)
@dispatch.spanned("table.ks_moddown")
def _moddown_tables(params: CkksParams, level: int):
    p_primes = poly.primes_for(params, poly.p_idx(params))
    q_primes = poly.primes_for(params, poly.q_idx(params, level))
    bhat_inv, w = rns.bconv_tables(p_primes, q_primes)
    P = rns.product(p_primes)
    pinv = np.array([pow(P % int(q), -1, int(q)) for q in q_primes], np.uint64)
    return (dispatch.upload(bhat_inv), dispatch.upload(w), np.array(q_primes, np.uint64),
            dispatch.upload(pinv[:, None], np.uint32))


def _scale_limbs(x, consts, qs, backend):
    """x ∘ diag(consts) per limb — consts: (k,) broadcast over N."""
    trace.record("PMULT", x.shape[-1], x.shape[-2])
    c = jnp.broadcast_to(dispatch.upload(consts, np.uint32)[:, None], x.shape)
    return mo.pointwise_mulmod(x, c, qs, backend=backend)


def _select_ksk(ksk: SwitchingKey, params: CkksParams, level: int, beta: int):
    """(β, 2, |ext|, N): key limbs restricted to active + special moduli."""
    k = lax.slice_in_dim(ksk.k, 0, beta)
    return jnp.concatenate(
        [lax.slice_in_dim(k, 0, level + 1, axis=2),
         lax.slice_in_dim(k, params.L + 1, None, axis=2)], axis=2
    )


def _record_fused_digits(params: CkksParams, level: int) -> None:
    """Trace the fused per-digit pipeline (planner `key_switch(fused=True)`)."""
    n = params.n
    m = len(poly.ext_idx(params, level))
    for j in range(params.beta(level)):
        k = len(tuple(i for i in params.digit(j) if i <= level))
        trace.record("PMULT", n, k, fused=True)
        trace.record("BCONV", n, k, dst=m, fused=True)
        trace.record("NTT", n, m, fused=True)
        trace.record("PMULT", n, 2 * m, mac=True, fused=True)
        trace.record("PADD", n, 2 * m, mac=True, fused=True)


def _record_fused_moddown(params: CkksParams, level: int) -> None:
    n, nq, a = params.n, level + 1, params.alpha
    trace.record("INTT", n, a)
    trace.record("PMULT", n, a, fused=True)
    trace.record("BCONV", n, a, dst=nq, fused=True)
    trace.record("NTT", n, nq, fused=True)
    trace.record("PSUB", n, nq, mac=True, fused=True)
    trace.record("PMULT", n, nq, mac=True, fused=True)


def mod_down(acc_ext, params: CkksParams, level: int, backend: str = "auto"):
    """Extended-basis eval-domain poly → q-basis, divided (rounded) by P.

    Staged pipeline for one accumulator; the fused path batches both
    accumulators through ``mod_down_pair`` instead.
    """
    _, stage = resolve_pipeline(backend)
    n = params.n
    nq = level + 1
    alpha = params.alpha
    q_part, p_part = acc_ext[:nq], acc_ext[nq:]
    bhat_inv, w, q_np, pinv = _moddown_tables(params, level)
    p_np = np.array(poly.primes_for(params, poly.p_idx(params)), np.uint64)

    p_coeff = poly.to_coeff(p_part, params, poly.p_idx(params), stage)
    xhat = _scale_limbs(p_coeff, bhat_inv, p_np, stage)
    _boundary(n, alpha)
    trace.record("BCONV", n, alpha, dst=nq)
    conv = bconv_ops.bconv(xhat, w, q_np, backend=stage)
    _boundary(n, nq)
    conv_eval = poly.to_eval(conv, params, poly.q_idx(params, level), stage)
    _boundary(n, nq)
    trace.record("PSUB", n, nq, mac=True)
    diff = mo.pointwise_submod(q_part, conv_eval, q_np, backend=stage)
    _boundary(n, nq)
    trace.record("PMULT", n, nq, mac=True)
    pinv_b = jnp.broadcast_to(pinv, diff.shape)
    return mo.pointwise_mulmod(diff, pinv_b, q_np, backend=stage)


@dispatch.spanned("ks.moddown")
def mod_down_pair(acc0, acc1, params: CkksParams, level: int, backend: str = "auto"):
    """ModDown both MAC accumulators; fused path shares one kernel launch."""
    pipeline, stage = resolve_pipeline(backend)
    if pipeline != "fused":
        return (
            mod_down(acc0, params, level, backend),
            mod_down(acc1, params, level, backend),
        )
    nq = level + 1
    _record_fused_moddown(params, level)
    _record_fused_moddown(params, level)
    m = acc0.shape[0]
    p_part = jnp.stack([poly.limbs(acc0, nq, m), poly.limbs(acc1, nq, m)])
    plan = poly.plan_for(params, poly.p_idx(params))
    p_coeff = ntt_ops.ntt_inv(p_part, plan, stage)
    q_part = jnp.stack([poly.limbs(acc0, 0, nq), poly.limbs(acc1, 0, nq)])
    out = fused_ops.mod_down_digits(p_coeff, q_part, params, level, backend="kernel")
    return lax.index_in_dim(out, 0, keepdims=False), lax.index_in_dim(out, 1, keepdims=False)


def key_switch(d_eval, params: CkksParams, level: int, ksk: SwitchingKey, backend: str = "auto"):
    """d (eval, basis q_0..q_ℓ) ⊗ s' → (ks0, ks1) eval over q_0..q_ℓ under s."""
    ksk_sel = _select_ksk(ksk, params, level, params.beta(level))
    return key_switch_selected(d_eval, params, level, ksk_sel, backend)


def key_switch_selected(d_eval, params: CkksParams, level: int, ksk_sel, backend: str = "auto"):
    """``key_switch`` over pre-selected key limbs ksk_sel: (β, 2, m, N).

    The rotation path hands in σ_t^{-1}-pre-permuted Galois keys here (see
    ``hoisted_ksk``) so the standard and hoisted pipelines run the *same*
    per-digit math and stay bit-exact against each other."""
    acc0, acc1 = key_switch_accumulate(d_eval, params, level, ksk_sel, backend)
    return mod_down_pair(acc0, acc1, params, level, backend)


@dispatch.spanned("ks.accumulate")
def key_switch_accumulate(d_eval, params: CkksParams, level: int, ksk_sel,
                          backend: str = "auto"):
    """Stages 1–4 of a key switch: decompose d into digits and MAC against the
    key, returning both raw accumulators (eval domain, extended basis Q∪P)
    *before* ModDown.

    This seam exists so BGV relinearisation (``repro.fhe.bgv``) can wrap the
    shared ModDown in its t-scaling sandwich; the CKKS path goes straight to
    ``mod_down_pair``.
    """
    pipeline, stage = resolve_pipeline(backend)
    n = params.n
    beta = params.beta(level)
    ext = poly.ext_idx(params, level)
    ext_primes = np.array(poly.primes_for(params, ext), np.uint64)
    m = len(ext)

    trace.record("LOAD_KSK", n, beta * 2 * m)
    d_coeff = poly.to_coeff(d_eval, params, poly.q_idx(params, level), stage)

    if pipeline == "fused":
        # stages 2–4 for all β digits and both key components: ONE launch
        _record_fused_digits(params, level)
        return fused_ops.key_switch_digits(d_coeff, ksk_sel, params, level, backend="kernel")

    acc0 = jnp.zeros((m, n), jnp.uint32)
    acc1 = jnp.zeros((m, n), jnp.uint32)
    for j in range(beta):
        digit_idx, bhat_inv, w, dst = _digit_tables(params, level, j)
        k = len(digit_idx)
        src_np = np.array(poly.primes_for(params, digit_idx), np.uint64)
        dj = d_coeff[digit_idx[0] : digit_idx[-1] + 1]
        xhat = _scale_limbs(dj, bhat_inv, src_np, stage)
        _boundary(n, k)
        trace.record("BCONV", n, k, dst=m)
        dj_ext = bconv_ops.bconv(xhat, w, dst, backend=stage)
        _boundary(n, m)
        dj_eval = poly.to_eval(dj_ext, params, ext, stage)
        _boundary(n, m)
        trace.record("PMULT", n, 2 * m, mac=True)
        t0 = mo.pointwise_mulmod(dj_eval, ksk_sel[j, 0], ext_primes, backend=stage)
        t1 = mo.pointwise_mulmod(dj_eval, ksk_sel[j, 1], ext_primes, backend=stage)
        _boundary(n, 2 * m)
        trace.record("PADD", n, 2 * m, mac=True)
        acc0 = mo.pointwise_addmod(acc0, t0, ext_primes, backend=stage)
        acc1 = mo.pointwise_addmod(acc1, t1, ext_primes, backend=stage)
    return acc0, acc1


# ---------------------------------------------------------------------------
# hoisted (Halevi–Shoup) rotation key-switching
# ---------------------------------------------------------------------------
#
# The ModUp half of a key-switch (iNTT → digit decompose → prescale → BConv →
# NTT into the extended basis) depends only on the input polynomial — never on
# the Galois element — so k rotations of the same ciphertext can share ONE
# ModUp and pay only KSK-MAC + ModDown each: O(β + k) forward NTTs through the
# extended basis instead of O(k·β).
#
# The automorphism is folded instead of applied per digit: with keys
# pre-permuted by σ_t^{-1} (cached per KeySet in ``hoisted_ksk``),
#
#   KS(σ_t(d)) = σ_t( ModDown( Σ_j D_j(d) ∘ σ_t^{-1}(ksk_j) ) )
#
# because σ_t commutes exactly (bit-exactly, per-residue) with every stage:
# it is a pure slot permutation in the eval domain, a signed coefficient
# permutation in the coefficient domain, and every ModUp/ModDown stage is a
# per-coefficient-index linear map over the limbs.  So the whole MAC + ModDown
# runs in the σ_t^{-1} frame and ONE permutation per output component lands
# the result — that single AUTO also absorbs the σ_t(c0) term: the final
# ciphertext is (σ_t(c0 + ks0'), σ_t(ks1')).


@dataclasses.dataclass
class HoistedDigits:
    """Reusable ModUp decomposition of one eval-domain polynomial.

    ``digits`` is (β, m, N) uint32 over the extended basis (eval domain) —
    the rotation-independent half of a key-switch, shared by every rotation
    of a hoisted group.
    """

    digits: jnp.ndarray
    level: int

    @property
    def beta(self) -> int:
        return int(self.digits.shape[0])


def _record_modup_digits(params: CkksParams, level: int) -> None:
    """Trace the fused ModUp pipeline (planner ``mod_up(fused=True)``)."""
    n = params.n
    m = len(poly.ext_idx(params, level))
    for j in range(params.beta(level)):
        k = len(tuple(i for i in params.digit(j) if i <= level))
        trace.record("PMULT", n, k, fused=True)
        trace.record("BCONV", n, k, dst=m, fused=True)
        trace.record("NTT", n, m, fused=True)


@dispatch.spanned("ks.modup")
def hoisted_mod_up(d_eval, params: CkksParams, level: int, backend: str = "auto") -> HoistedDigits:
    """ModUp once: d (eval, q_0..q_ℓ) → reusable extended-basis digits.

    The returned digits are materialised (they round-trip to the later MAC
    launches — the trace carries one STORE_WS/LOAD_WS pair of β·m limbs),
    amortising the β forward NTTs across every rotation that reuses them.
    """
    pipeline, stage = resolve_pipeline(backend)
    n = params.n
    beta = params.beta(level)
    ext = poly.ext_idx(params, level)
    m = len(ext)
    d_coeff = poly.to_coeff(d_eval, params, poly.q_idx(params, level), stage)

    if pipeline == "fused":
        _record_modup_digits(params, level)
        digits = hoist_ops.mod_up_digits(d_coeff, params, level, backend="kernel")
    else:
        rows = []
        for j in range(beta):
            digit_idx, bhat_inv, w, dst = _digit_tables(params, level, j)
            k = len(digit_idx)
            src_np = np.array(poly.primes_for(params, digit_idx), np.uint64)
            dj = d_coeff[digit_idx[0] : digit_idx[-1] + 1]
            xhat = _scale_limbs(dj, bhat_inv, src_np, stage)
            _boundary(n, k)
            trace.record("BCONV", n, k, dst=m)
            dj_ext = bconv_ops.bconv(xhat, w, dst, backend=stage)
            _boundary(n, m)
            rows.append(poly.to_eval(dj_ext, params, ext, stage))
        digits = jnp.stack(rows)
    _boundary(n, beta * m)  # hoisted digits round-trip to the MAC launches
    return HoistedDigits(digits=digits, level=level)


# Each cached entry is a full (β, 2, m, N) key copy — comparable to the
# level-restricted key itself — so the per-KeySet cache is LRU-bounded BY
# BYTES (an entry count would still admit ~β·m·N-sized blowups at production
# parameters: one N=2^16 deep entry is >100 MB).  An entry larger than the
# whole budget is simply not cached.
HOIST_KSK_CACHE_BYTES = 256 * 2**20


def hoisted_ksk(params: CkksParams, keys: KeySet, t: int, level: int):
    """σ_t^{-1}-pre-permuted Galois key, restricted to the active basis.

    (β, 2, m, N) uint32 — LRU-cached per KeySet/(t, level): the permutation
    is a keygen-time precompute, not per-rotation work (no trace records).
    """
    cache = keys.hoist_cache
    hit = cache.get((t, level))
    if hit is not None:
        cache[(t, level)] = cache.pop((t, level))  # move to MRU position
        return hit
    sel = _select_ksk(keys.galois(t), params, level, params.beta(level))
    tinv = pow(t, -1, 2 * params.n)
    pre = jnp.take(sel, poly._eval_perm(params.n, tinv), axis=-1)
    if int(pre.nbytes) <= HOIST_KSK_CACHE_BYTES:
        while cache and sum(int(v.nbytes) for v in cache.values()) + int(pre.nbytes) > (
            HOIST_KSK_CACHE_BYTES
        ):
            cache.pop(next(iter(cache)))  # evict LRU (dicts preserve insertion order)
        cache[(t, level)] = pre
    return pre


@dispatch.spanned("ks.mac")
def hoisted_galois_ks(hd: HoistedDigits, ksk_stack, params: CkksParams, level: int,
                      backend: str = "auto"):
    """KSK inner products for a whole rotation group, σ_t^{-1} frame.

    ksk_stack: (R, β, 2, m, N) pre-permuted key limbs (``hoisted_ksk``).
    Returns (R, 2, m, N) accumulator pairs; the fused pipeline issues ONE
    batched MAC launch with the hoisted digits VMEM-resident.
    """
    pipeline, stage = resolve_pipeline(backend)
    n = params.n
    beta = params.beta(level)
    m = int(hd.digits.shape[1])
    fused = pipeline == "fused"
    for _ in range(ksk_stack.shape[0]):
        trace.record("LOAD_KSK", n, beta * 2 * m)
        for _j in range(beta):
            trace.record("PMULT", n, 2 * m, mac=True, fused=fused)
            if not fused:
                _boundary(n, 2 * m)
            trace.record("PADD", n, 2 * m, mac=True, fused=fused)
    # non-fused: per-op MAC at the resolved stage backend, mirroring
    # key_switch_selected's staged pipeline (stage="auto" uses per-op kernels
    # on TPU, the u64 oracle elsewhere)
    return hoist_ops.galois_mac(
        hd.digits, ksk_stack, params, level,
        backend="kernel" if fused else stage, staged=not fused,
    )


@dispatch.spanned("ks.moddown_group")
def mod_down_group(accs, params: CkksParams, level: int, backend: str = "auto"):
    """ModDown every accumulator pair of a hoisted group.

    accs: (R, 2, m, N) → (R, 2, level+1, N).  The fused pipeline batches all
    2·R tails through ONE P-block iNTT + ONE ModDown launch.
    """
    pipeline, _stage = resolve_pipeline(backend)
    nrot = accs.shape[0]
    if pipeline != "fused":
        return jnp.stack([
            jnp.stack([mod_down(accs[i, c], params, level, backend) for c in range(2)])
            for i in range(nrot)
        ])
    nq = level + 1
    for _ in range(2 * nrot):
        _record_fused_moddown(params, level)
    p_part = lax.slice_in_dim(accs, nq, None, axis=2).reshape(2 * nrot, params.alpha, params.n)
    plan = poly.plan_for(params, poly.p_idx(params))
    p_coeff = ntt_ops.ntt_inv(p_part, plan, _stage)
    q_part = lax.slice_in_dim(accs, 0, nq, axis=2).reshape(2 * nrot, nq, params.n)
    out = fused_ops.mod_down_digits(p_coeff, q_part, params, level, backend="kernel")
    return out.reshape(nrot, 2, nq, params.n)


def permute_last(c0_eval, ks0, ks1, t: int, params: CkksParams, level: int,
                 backend: str = "auto"):
    """The shared rotation epilogue: c0 + ks0, then ONE σ_t per component.

    ``ks0``/``ks1`` come from a key-switch against the σ_t^{-1}-pre-permuted
    key (``hoisted_ksk``), so the single automorphism here lands the rotated
    ciphertext — it also absorbs the σ_t(c0) term.  Every rotation path
    (standard, single-hoisted, group-hoisted) MUST end through this helper:
    the trace shape ([PADD, AUTO, AUTO], matching the planner) and the
    bit-exactness of hoisted vs standard both hang on the three paths doing
    literally the same thing.
    """
    _pipeline, stage = resolve_pipeline(backend)
    n = params.n
    qs = np.array(params.q_primes[: level + 1], np.uint64)
    trace.record("PADD", n, level + 1)
    s0 = mo.pointwise_addmod(dispatch.upload(c0_eval, np.uint32), ks0, qs, backend=stage)
    return poly.automorphism_eval(s0, n, t), poly.automorphism_eval(ks1, n, t)


def rotate_hoisted(c0_eval, hd: HoistedDigits, t: int, keys: KeySet, params: CkksParams,
                   level: int, backend: str = "auto"):
    """One key-switched automorphism σ_t over a hoisted decomposition.

    Runs only KSK-MAC + ModDown (+ the folded automorphism) — the expensive
    ModUp was paid once when ``hd`` was built.  Returns the rotated
    ciphertext's (c0, c1) eval-domain polynomials; bit-exact against the
    un-hoisted ``ctx.rotate`` path.
    """
    ksk_stack = hoisted_ksk(params, keys, t, level)[None]
    accs = hoisted_galois_ks(hd, ksk_stack, params, level, backend)
    ks = mod_down_group(accs, params, level, backend)
    return permute_last(c0_eval, ks[0, 0], ks[0, 1], t, params, level, backend)
