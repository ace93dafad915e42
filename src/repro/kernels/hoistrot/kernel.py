"""Pallas TPU kernels for hoisted (Halevi–Shoup) rotation key-switching.

A key-switched rotation splits into a ModUp half (digit decompose → prescale →
BConv → NTT into the extended basis) and an apply half (KSK-MAC + ModDown).
The ModUp half depends only on the input polynomial — never on the Galois
element — so a group of rotations of the same ciphertext can share ONE ModUp.
Two kernels realise that split:

  * ``hoist_modup_pallas`` — the fused prescale→BConv→NTT pipeline of
    ``kernels.fusedks`` with the MAC epilogue removed: grid = (ext_limb e,
    digit j), one launch raises all β digits to the extended basis and
    *materialises* them (β, m, N) instead of folding them into accumulators.

  * ``hoist_mac_pallas`` — the batched Galois apply: grid = (ext_limb e,
    rotation r) with r innermost, so the hoisted digit block for limb e
    ((β, N) words) is copied into VMEM once and stays resident while every
    rotation of the group streams its switching key through the MAC.  Keys
    arrive pre-permuted by σ_t^{-1} (see ``fhe.keyswitch.hoisted_ksk``), which
    turns the per-digit automorphism into a single post-ModDown permutation
    and keeps this kernel a pure Montgomery multiply-accumulate.

Scoped VMEM the TPU compiler reports for a v5e (blocks plus the body's
scratch): ``hoist_modup`` 1.48 MiB at dblookup and 8.79 MiB at lstm (the
fused shape rule of ``kernels.fusedks`` bounds it); ``hoist_mac`` over four
rotations 1.84 / 6.21 MiB.  Both are inside the 16 MiB default limit.

Per-rotation work after hoisting is one (β, 2, N) key stream + 2N MACs per
extended limb — no NTT, no BConv.  The β forward NTTs of the ModUp are
paid once per group instead of once per rotation: O(β + k) vs O(k·β).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tpu
from repro.kernels.fusedks.kernel import _modup_limb, _ntt_specs
from repro.kernels.ntt.kernel import _addmod, _montmul, limb_scalars


def _modup_body(sc_ref, dsc_ref, wm_ref, xd_ref, twa_ref, v2_ref, v1_ref, t_ref, o_ref):
    e, j = pl.program_id(0), pl.program_id(1)
    o_ref[...] = _modup_limb(
        sc_ref, dsc_ref, wm_ref, xd_ref, twa_ref, v2_ref, v1_ref, t_ref, j, e
    )[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def hoist_modup_pallas(xd, sc, dsc, wm, twa, v2, v1, t, *, interpret):
    """Raise all β digits of one polynomial to the extended basis: ONE launch.

    Same inputs as ``fusedks.fused_ks_pallas`` minus the key material:
    xd (β, k, N2, N1) zero-padded digit source limbs (coeff domain), the flat
    SMEM scalar tables, and the extended-basis NTT tables.
    Returns (β, m, N1, N2) uint32 — the hoisted digits, eval domain, reusable
    by every rotation of the group.
    """
    beta, k, n2, n1 = xd.shape
    m = twa.shape[0]
    return tpu.call(
        _modup_body,
        (sc, dsc, wm, xd, twa, v2, v1, t),
        grid=(m, beta),
        in_specs=[tpu.smem(), tpu.smem(), tpu.smem(),
                  pl.BlockSpec((None, k, n2, n1), lambda e, j: (j, 0, 0, 0))]
        + _ntt_specs(n1, n2, lambda e, j: e),
        out_specs=pl.BlockSpec((None, None, n1, n2), lambda e, j: (j, e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((beta, m, n1, n2), jnp.uint32),
        name="hoistmodup",
        interpret=interpret,
    )


def _mac_body(sc_ref, dig_ref, ksk_ref, o_ref):
    q, qinv, r2, _ = limb_scalars(sc_ref, pl.program_id(0))
    acc0 = acc1 = None
    for j in range(dig_ref.shape[0]):  # β is static — the loop unrolls inside one program
        x = dig_ref[j]
        t0 = _montmul(_montmul(x, ksk_ref[j, 0], q, qinv), r2, q, qinv)
        t1 = _montmul(_montmul(x, ksk_ref[j, 1], q, qinv), r2, q, qinv)
        if acc0 is None:
            acc0, acc1 = t0, t1
        else:
            acc0 = _addmod(acc0, t0, q)
            acc1 = _addmod(acc1, t1, q)
    o_ref[0] = acc0
    o_ref[1] = acc1


@functools.partial(jax.jit, static_argnames=("interpret",))
def hoist_mac_pallas(dig, ksk, sc, *, interpret):
    """Every rotation of one hoisted group in a single launch.

    dig: (β, m, N1, N2) hoisted digits (eval domain, extended basis) — the
         limb-e block is VMEM-resident across all R rotations (r is the inner
         grid axis, so its block index is constant while r sweeps);
    ksk: (R, β, 2, m, N1, N2) σ_t^{-1}-pre-permuted switching-key limbs;
    sc:  (m·NSC,) extended-basis limb scalars (SMEM).
    Returns (R, 2, m, N1, N2): one MAC accumulator pair per rotation, still
    in the σ_t^{-1} frame (the caller ModDowns, then applies the permutation
    once).
    """
    beta, m, n1, n2 = dig.shape
    nrot = ksk.shape[0]
    return tpu.call(
        _mac_body,
        (sc, dig, ksk),
        grid=(m, nrot),
        in_specs=[
            tpu.smem(),
            pl.BlockSpec((beta, None, n1, n2), lambda e, r: (0, e, 0, 0)),  # resident per e
            pl.BlockSpec((None, beta, 2, None, n1, n2), lambda e, r: (r, 0, 0, e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 2, None, n1, n2), lambda e, r: (r, 0, e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nrot, 2, m, n1, n2), jnp.uint32),
        name="hoistmac",
        interpret=interpret,
    )
