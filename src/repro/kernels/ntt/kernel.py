"""Pallas TPU kernel: four-step negacyclic NTT as MXU matmuls.

This is the TPU-native re-think of FLASH-FHE's (i)NTT circuits (DESIGN.md §2):

* the paper's R-point NTT *circuit* becomes an R×R modular **matmul on the MXU**.
  Both operands are split into 8-bit limbs held exactly in bf16 (0..255 needs
  8 significant bits); each limb product runs bf16×bf16 with f32 accumulation,
  exact while 255²·K < 2^24, i.e. for K ≤ 256 (the largest four-step factor).
  Each product is cast to int32 at once, the ≤ 4 products of one limb diagonal
  are summed in int32 (< 2^26), and the seven diagonals are recombined with
  Montgomery constants 2^(8s)·R mod q on the VPU.  (The v5e MXU refuses int32
  operands; bf16 is its native type.)
* the paper's L1 transpose becomes an in-VMEM 2-D transpose: a limb arrives in
  natural coefficient order as an (N2, N1) tile and is transposed to the
  (N1, N2) layout the two matmuls want; the inverse transposes back on the way
  out.  Slots come out in natural order as an (N1, N2) tile.
* multi-entrance/exit: the same kernel body is instantiated per ring degree
  (N1×N2 ∈ {16..256}×{128,256}); parallel small-point NTTs ride the
  (limb, batch) grid — batch innermost, so a limb's tables stay in VMEM while
  the batch streams through.

Per-limb scalars (q, -q⁻¹ mod 2³², R² mod q and the seven 2^(8s)·R mod q)
live in one flat uint32 SMEM table of ``NSC`` words per limb (``limb_scalars``).

Scoped VMEM, as the TPU compiler reports it for a v5e (double-buffered blocks
plus the body's scratch): 1.23 MiB forward / 1.25 MiB inverse at N=2^14,
5.79 / 5.62 MiB at N=2^16 (4.00 MiB of it blocks) — inside the 16 MiB default
scoped limit, so no ``vmem_limit_bytes`` is set.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.fhe.ntt import NDIAG, NLIMB8
from repro.kernels import tpu

# Layout of one limb's row in the flat SMEM scalar table.
Q, QINV, R2, CM = 0, 1, 2, 3  # CM .. CM+NDIAG-1: Montgomery 2^(8s) mod q
NSC = CM + NDIAG


def limb_scalars(sc_ref, l):
    """(q, -q⁻¹, R² mod q, [2^(8s)·R mod q]) of limb ``l`` from the SMEM table."""
    base = l * NSC
    cm = [sc_ref[base + CM + s] for s in range(NDIAG)]
    return sc_ref[base + Q], sc_ref[base + QINV], sc_ref[base + R2], cm


def _mulhi32(a, b):
    al = a & 0xFFFF
    ah = a >> 16
    bl = b & 0xFFFF
    bh = b >> 16
    t = al * bl
    u = ah * bl + (t >> 16)
    v = al * bh + (u & 0xFFFF)
    return ah * bh + (u >> 16) + (v >> 16)


def _montmul(a, b, q, qinv_neg):
    t_lo = a * b
    t_hi = _mulhi32(a, b)
    m = t_lo * qinv_neg
    mq_hi = _mulhi32(m, q)
    res = t_hi + mq_hi + (t_lo != 0).astype(jnp.uint32)
    return jnp.where(res >= q, res - q, res)


def _addmod(a, b, q):
    s = a + b
    return jnp.where(s >= q, s - q, s)


def _limbs(x):
    """uint32 words < 2^31 → NLIMB8 bf16 matrices of their 8-bit limbs (exact)."""
    return [
        ((x >> (8 * k)) & 0xFF).astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
        for k in range(NLIMB8)
    ]


def _mod_matmul(lhs, rhs, cm, q, qinv):
    """(lhs @ rhs) mod q from NLIMB8-long lists of bf16 limb matrices.

    One limb diagonal s = a + b at a time: its ≤ 4 exact MXU products are
    summed in int32, then folded in as Montgomery·2^(8s)."""
    acc = None
    for s in range(NDIAG):
        d = None
        for a in range(max(0, s - NLIMB8 + 1), min(s, NLIMB8 - 1) + 1):
            p = jnp.dot(lhs[a], rhs[s - a], preferred_element_type=jnp.float32)
            p = p.astype(jnp.int32)
            d = p if d is None else d + p
        t = _montmul(d.astype(jnp.uint32), cm[s], q, qinv)
        acc = t if acc is None else _addmod(acc, t, q)
    return acc


def ntt_fwd_tile(r, twa, v2, v1, tm, cm, q, qinv):
    """Forward NTT of one limb.  r: (N2, N1) coefficients, r[i, j] = a[N1·i + j].

    Returns the (N1, N2) slots, X[N2·k1 + k2] at [k1, k2].  ``v2``/``v1`` are
    the NLIMB8 bf16 limb matrices of the symmetric row/column NTT matrices."""
    a = _montmul(r.T, twa, q, qinv)  # A[n1, n2] = a[n1 + N1·n2], psi-twisted
    b = _mod_matmul(_limbs(a), v2, cm, q, qinv)  # row NTTs: A @ V2
    b = _montmul(b, tm, q, qinv)  # inter-step twiddle w^(n1·k2)
    return _mod_matmul(v1, _limbs(b), cm, q, qinv)  # column NTTs: V1 @ B


def ntt_inv_tile(x, twia, v2i, v1i, tim, cm, q, qinv):
    """Inverse of ``ntt_fwd_tile``: (N1, N2) slots → (N2, N1) coefficients."""
    c = _mod_matmul(v1i, _limbs(x), cm, q, qinv)  # contract k1 with V1⁻¹
    c = _montmul(c, tim, q, qinv)  # w^(-n1·k2)
    a = _mod_matmul(_limbs(c), v2i, cm, q, qinv)  # contract k2 with V2⁻¹
    return _montmul(a, twia, q, qinv).T  # psi^(-i)·N⁻¹ twist, back to (N2, N1)


def _ntt_body(sc_ref, x_ref, tw_ref, v2_ref, v1_ref, t_ref, o_ref, *, inverse):
    q, qinv, _, cm = limb_scalars(sc_ref, pl.program_id(0))
    v2 = [v2_ref[k] for k in range(NLIMB8)]
    v1 = [v1_ref[k] for k in range(NLIMB8)]
    tile = ntt_inv_tile if inverse else ntt_fwd_tile
    o_ref[...] = tile(x_ref[...], tw_ref[...], v2, v1, t_ref[...], cm, q, qinv)


@functools.partial(jax.jit, static_argnames=("inverse", "interpret"))
def ntt_pallas(x, sc, tw, v2, v1, t, *, inverse, interpret):
    """x: (B, L, N2, N1) coefficients (forward) or (B, L, N1, N2) slots (inverse).

    sc: (L·NSC,) uint32 SMEM scalars; tw/t: (L, N1, N2) uint32 Montgomery
    twist and twiddle; v2/v1: (L, NLIMB8, N2, N2)/(L, NLIMB8, N1, N1) bf16.
    Returns the other layout: (B, L, N1, N2) slots or (B, L, N2, N1) coeffs."""
    bsz, nlimb = x.shape[:2]
    n1, n2 = tw.shape[1:]
    out = (n2, n1) if inverse else (n1, n2)
    return tpu.call(
        functools.partial(_ntt_body, inverse=inverse),
        (sc, x, tw, v2, v1, t),
        grid=(nlimb, bsz),
        in_specs=[
            tpu.smem(),
            pl.BlockSpec((None, None) + x.shape[2:], lambda l, b: (b, l, 0, 0)),
            pl.BlockSpec((None, n1, n2), lambda l, b: (l, 0, 0)),  # twist
            pl.BlockSpec((None, NLIMB8, n2, n2), lambda l, b: (l, 0, 0, 0)),  # V2 limbs
            pl.BlockSpec((None, NLIMB8, n1, n1), lambda l, b: (l, 0, 0, 0)),  # V1 limbs
            pl.BlockSpec((None, n1, n2), lambda l, b: (l, 0, 0)),  # inter-step twiddle
        ],
        out_specs=pl.BlockSpec((None, None) + out, lambda l, b: (b, l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, nlimb) + out, jnp.uint32),
        name="intt_pallas" if inverse else "ntt_pallas",
        interpret=interpret,
    )
