"""Pallas TPU kernel: the fused prescale→BConv→NTT→KSK-MAC key-switch pipeline.

This is the kernel-level realisation of FLASH-FHE's fused key-switch datapath
(the iNTT→BConv→NTT pipeline the bootstrappable clusters are built around).
The staged software path launches one kernel per stage per digit, so every
intermediate polynomial round-trips through HBM-equivalent host buffers; here
the whole per-digit pipeline runs inside one ``pallas_call`` and intermediates
never leave VMEM:

  grid = (ext_limb e, digit j) — j innermost, so each output limb's pair of
  accumulators stays resident in VMEM while all β digits stream through it.
  One program:

    1. prescale   x̂_i = x_i ∘ [B̂_i⁻¹]_{b_i} + ⌊b_i/2⌋  (Montgomery mul + add/row)
    2. BConv row  y_e = Σ_i x̂_i · (B̂_i mod c_e) − C_e  (one Montgomery mul/row)
    3. NTT        ŷ_e = NTT_{c_e}(y_e)               (four-step MXU matmuls)
    4. KSK MAC    acc_{0,1}[e] += ŷ_e ∘ ksk_{j,{0,1}}[e]   (both components)

Stages 1–2 run on the VPU: one output row of a BConv is a k-term dot product,
too thin for the MXU.  The weights arrive in Montgomery form, so each term is
one Montgomery multiply of x̂_i (< b_i < 2^31) by [B̂_i·R]_{c_e}.  The added
⌊b_i/2⌋ and the subtracted C_e = Σ_i ⌊b_i/2⌋·B̂_i mod c_e make the conversion
sum each row's centred representative (``fhe.rns.centring_tables``), so the
key-switch noise has mean zero and does not grow with N.  Digits are
zero-padded to a uniform row count k = α (padded rows carry a dummy modulus and
zero weights, exact no-ops), so all β digits and both key components ride one
grid.  A second entry point runs the same pipeline with a ModDown epilogue —
(q_part − ŷ) ∘ P⁻¹ — for a batch of accumulators.

Layout: digit rows arrive in natural coefficient order as (N2, N1) tiles (the
NTT transposes in VMEM), key/accumulator limbs as (N1, N2) slot tiles.  All
scalars (per-limb moduli and Montgomery constants, per-row prescale constants,
BConv weights) are flat uint32 SMEM tables.

VMEM: a program holds the (k, N2, N1) digit block, the bf16 limb matrices
of one limb's NTT, two twiddle tiles and the key/accumulator tiles, each
double-buffered, and the body's scratch on top.  The TPU compiler's scoped
VMEM figures for ``fused_ks_pallas`` on a v5e are blocks + scratch: 1.62 +
0.23 MiB at dblookup (N=2^14, k=3); 8.50 + 1.79 MiB at lstm (N=2^16, k=7);
13.50 + 1.79 = 15.29 MiB at logreg (k=17); 16.29 MiB at k=19, which it refuses
against its 16 MiB default scoped limit.  The ModDown and hoisted-ModUp
entries hold 1.0 / 1.5 MiB fewer block bytes at N=2^16 and the same scratch.
``fused_vmem_bytes`` is the block sum (exact at N=2^14 and 2^16) plus eight
uint32 tiles (2 MiB at N=2^16) for the scratch, so at N=2^16 it admits
exactly the digit sizes the compiler fits, k ≤ 18 (``test_tpu_compile``
holds it to that).  ``fusedks.ops`` applies it when it builds a shape's
tables: a bound over ``tpu.VMEM_SCOPED_LIMIT`` (dnum=1 at L=57: 36 MiB)
raises an error naming ``backend="staged"``; nothing is decided by catching
a compile failure, and no kernel raises ``vmem_limit_bytes``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.fhe.ntt import NLIMB8
from repro.kernels import tpu
from repro.kernels.ntt.kernel import _addmod, _montmul, limb_scalars, ntt_fwd_tile

# per (digit, row) prescale constants in the flat SMEM table
DB, DBINV, DBH, DHALF = 0, 1, 2, 3  # b, -b⁻¹ mod 2³², [B̂⁻¹]·R mod b, ⌊b/2⌋
NDSC = 4


def fused_vmem_bytes(k: int, n1: int, n2: int) -> int:
    """Scoped VMEM bound of one fused program (digit rows k): its
    double-buffered blocks plus eight uint32 tiles for the body's scratch."""
    n = n1 * n2
    digit = k * n * 4
    ntt = NLIMB8 * (n1 * n1 + n2 * n2) * 2 + 2 * n * 4  # bf16 V1/V2 + two twiddles
    key_acc = 2 * (2 * n * 4)  # two key tiles in, two accumulator tiles out
    scratch = 8 * n * 4
    return 2 * (digit + ntt + key_acc) + scratch


def _bconv_row(x_ref, dsc_ref, wm_ref, j, e, q, qinv):
    """Stages 1+2 for digit j → ext limb e: the centred Σ_i x̂_i·B̂_i mod c_e,
    on the VPU.  ``wm`` holds k+1 rows of m words per digit: the k weight rows,
    then the row of c_e − C_e."""
    k = x_ref.shape[0]
    m = wm_ref.shape[0] // ((dsc_ref.shape[0] // (NDSC * k)) * (k + 1))
    y = None
    for i in range(k):
        row = j * k + i
        b = dsc_ref[row * NDSC + DB]
        xh = _montmul(x_ref[i], dsc_ref[row * NDSC + DBH], b, dsc_ref[row * NDSC + DBINV])
        xh = _addmod(xh, dsc_ref[row * NDSC + DHALF], b)
        t = _montmul(xh, wm_ref[(row + j) * m + e], q, qinv)
        y = t if y is None else _addmod(y, t, q)
    return _addmod(y, wm_ref[(j * (k + 1) + k) * m + e], q)


def _modup_limb(sc_ref, dsc_ref, wm_ref, x_ref, twa_ref, v2_ref, v1_ref, t_ref, j, e):
    """Stages 1–3: digit j's rows → ŷ_e, the NTT of its BConv onto limb e."""
    q, qinv, r2, cm = limb_scalars(sc_ref, e)
    y = _bconv_row(x_ref, dsc_ref, wm_ref, j, e, q, qinv)
    v2 = [v2_ref[s] for s in range(NLIMB8)]
    v1 = [v1_ref[s] for s in range(NLIMB8)]
    return ntt_fwd_tile(y, twa_ref[...], v2, v1, t_ref[...], cm, q, qinv), q, qinv, r2


def _fused_ks_body(sc_ref, dsc_ref, wm_ref, xd_ref, twa_ref, v2_ref, v1_ref, t_ref,
                   ksk_ref, o_ref):
    e, j = pl.program_id(0), pl.program_id(1)  # j innermost: accumulates into o_ref
    yhat, q, qinv, r2 = _modup_limb(
        sc_ref, dsc_ref, wm_ref, xd_ref, twa_ref, v2_ref, v1_ref, t_ref, j, e
    )
    # stage 4: plain products ŷ∘ksk via Montgomery double-multiply, accumulate
    t0 = _montmul(_montmul(yhat, ksk_ref[0], q, qinv), r2, q, qinv)
    t1 = _montmul(_montmul(yhat, ksk_ref[1], q, qinv), r2, q, qinv)

    @pl.when(j == 0)
    def _():
        o_ref[0] = t0
        o_ref[1] = t1

    @pl.when(j > 0)
    def _():
        o_ref[0] = _addmod(o_ref[0], t0, q)
        o_ref[1] = _addmod(o_ref[1], t1, q)


def _ntt_specs(n1, n2, limb):
    """BlockSpecs of one limb's forward-NTT tables; ``limb`` maps grid → limb."""
    return [
        pl.BlockSpec((None, n1, n2), lambda *g: (limb(*g), 0, 0)),  # twist
        pl.BlockSpec((None, NLIMB8, n2, n2), lambda *g: (limb(*g), 0, 0, 0)),  # V2
        pl.BlockSpec((None, NLIMB8, n1, n1), lambda *g: (limb(*g), 0, 0, 0)),  # V1
        pl.BlockSpec((None, n1, n2), lambda *g: (limb(*g), 0, 0)),  # inter-step twiddle
    ]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_ks_pallas(xd, sc, dsc, wm, twa, v2, v1, t, ksk, *, interpret):
    """All β digits × both key components of one key-switch in one launch.

    xd:  (β, k, N2, N1) digit source limbs (coeff domain, rows zero-padded)
    sc:  (m·NSC,) ext-basis limb scalars; dsc: (β·k·NDSC,) prescale constants;
    wm:  (β·(k+1)·m,) per digit, k rows of Montgomery BConv weights
         [B̂_i·R]_{c_e}, then one row of centring corrections c_e − C_e
    twa/v2/v1/t: ext-basis forward NTT tables, leading (m, ...) axis
    ksk: (β, 2, m, N1, N2) switching-key limbs (eval domain)
    Returns (m, 2, N1, N2): the two MAC accumulators over the extended basis.
    """
    beta, k, n2, n1 = xd.shape
    m = twa.shape[0]
    return tpu.call(
        _fused_ks_body,
        (sc, dsc, wm, xd, twa, v2, v1, t, ksk),
        grid=(m, beta),
        in_specs=[tpu.smem(), tpu.smem(), tpu.smem(),
                  pl.BlockSpec((None, k, n2, n1), lambda e, j: (j, 0, 0, 0))]
        + _ntt_specs(n1, n2, lambda e, j: e)
        + [pl.BlockSpec((None, 2, None, n1, n2), lambda e, j: (j, 0, e, 0, 0))],
        out_specs=pl.BlockSpec((None, 2, n1, n2), lambda e, j: (e, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 2, n1, n2), jnp.uint32),
        name="fused_ks_pallas",
        interpret=interpret,
    )


def _fused_moddown_body(sc_ref, dsc_ref, wm_ref, pinv_ref, pc_ref, twa_ref, v2_ref,
                        v1_ref, t_ref, qp_ref, o_ref):
    e = pl.program_id(1)
    yhat, q, qinv, _ = _modup_limb(
        sc_ref, dsc_ref, wm_ref, pc_ref, twa_ref, v2_ref, v1_ref, t_ref, 0, e
    )
    # ModDown epilogue: (q_part − BConv_P→Q(⌊·⌉)) ∘ P⁻¹, still in VMEM
    d = qp_ref[...]
    diff = jnp.where(d >= yhat, d - yhat, d + q - yhat)
    o_ref[...] = _montmul(diff, pinv_ref[e], q, qinv)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_moddown_pallas(pc, sc, dsc, wm, pinv, twa, v2, v1, t, qpart, *, interpret):
    """Fused prescale→BConv→NTT→(sub, ×P⁻¹) for a batch of accumulators.

    pc:    (C, k, N2, N1) P-block coefficients of the accumulators after the
           iNTT (C = 2 for one key-switch's pair; C = 2·R when a hoisted
           rotation group ModDowns every rotation's pair in one launch)
    sc:    (m·NSC,) q-basis limb scalars; dsc/wm: prescale constants and
           Montgomery BConv weights (and centring row) of the special block
    pinv:  (m,) Montgomery [P⁻¹]_{q_e};  qpart: (C, m, N1, N2) eval q limbs
    NTT tables carry the q-basis (m = level+1 limbs).  Returns (C, m, N1, N2).
    """
    nb, k, n2, n1 = pc.shape
    m = twa.shape[0]
    return tpu.call(
        _fused_moddown_body,
        (sc, dsc, wm, pinv, pc, twa, v2, v1, t, qpart),
        grid=(nb, m),
        in_specs=[tpu.smem()] * 4
        + [pl.BlockSpec((None, k, n2, n1), lambda c, e: (c, 0, 0, 0))]
        + _ntt_specs(n1, n2, lambda c, e: e)
        + [pl.BlockSpec((None, None, n1, n2), lambda c, e: (c, e, 0, 0))],
        out_specs=pl.BlockSpec((None, None, n1, n2), lambda c, e: (c, e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, m, n1, n2), jnp.uint32),
        name="fused_moddown_pallas",
        interpret=interpret,
    )
