"""Staged oracle for the fused key-switch pipeline.

Composes the per-stage reference ops (u64 XLA paths) exactly as the staged
dispatcher in ``repro.fhe.keyswitch`` does, but with no trace recording — this
is the bit-exactness target the fused kernel is tested against, mirroring how
``ntt/ref.py`` and ``bconv/ref.py`` serve their kernels.  Each conversion is
``bconv.ops.conv_centred``; the ModDown's [P⁻¹]_q is lru-cached per (params,
level).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from repro.fhe import poly, rns
from repro.fhe.params import CkksParams
from repro.kernels.bconv import ops as bconv_ops
from repro.kernels.modops import ops as mo
from repro.kernels.ntt import ops as ntt_ops


def mod_up_digit_ref(d_coeff, params: CkksParams, level: int, j: int):
    """Digit j of (level+1, N) coefficient limbs, raised to the extended basis
    by the centred conversion and NTT'd: (m, N) eval domain."""
    alpha = params.alpha
    lo, hi = j * alpha, min((j + 1) * alpha, level + 1)
    ext = poly.ext_idx(params, level)
    src = poly.primes_for(params, tuple(range(lo, hi)))
    dj_ext = bconv_ops.conv_centred(d_coeff[lo:hi], src, poly.primes_for(params, ext), backend="ref")
    return ntt_ops.ntt_fwd(dj_ext, poly.plan_for(params, ext), "ref")


@functools.lru_cache(maxsize=256)
def _moddown_ref_tables(params: CkksParams, level: int):
    p_primes = poly.primes_for(params, poly.p_idx(params))
    q_primes = poly.primes_for(params, poly.q_idx(params, level))
    P = rns.product(p_primes)
    pinv = np.array([pow(P % int(q), -1, int(q)) for q in q_primes], np.uint64)
    return p_primes, np.array(q_primes, np.uint64), jnp.asarray(pinv[:, None].astype(np.uint32))


def key_switch_digits_ref(d_coeff, ksk_sel, params: CkksParams, level: int):
    ext = poly.ext_idx(params, level)
    ext_primes = np.array(poly.primes_for(params, ext), np.uint64)
    n = params.n
    acc0 = jnp.zeros((len(ext), n), jnp.uint32)
    acc1 = jnp.zeros((len(ext), n), jnp.uint32)
    for j in range(params.beta(level)):
        dj_eval = mod_up_digit_ref(d_coeff, params, level, j)
        t0 = mo.pointwise_mulmod(dj_eval, ksk_sel[j, 0], ext_primes, backend="ref")
        t1 = mo.pointwise_mulmod(dj_eval, ksk_sel[j, 1], ext_primes, backend="ref")
        acc0 = mo.pointwise_addmod(acc0, t0, ext_primes, backend="ref")
        acc1 = mo.pointwise_addmod(acc1, t1, ext_primes, backend="ref")
    return acc0, acc1


def mod_down_digits_ref(p_coeff, q_part, params: CkksParams, level: int):
    p_primes, q_np, pinv = _moddown_ref_tables(params, level)
    plan = poly.plan_for(params, poly.q_idx(params, level))
    outs = []
    for c in range(p_coeff.shape[0]):
        conv = bconv_ops.conv_centred(p_coeff[c], p_primes, q_np, backend="ref")
        conv_eval = ntt_ops.ntt_fwd(conv, plan, "ref")
        diff = mo.pointwise_submod(q_part[c], conv_eval, q_np, backend="ref")
        pinv_b = jnp.broadcast_to(pinv, diff.shape)
        outs.append(mo.pointwise_mulmod(diff, pinv_b, q_np, backend="ref"))
    return jnp.stack(outs)
