"""Public fused key-switch ops: table building + kernel/ref dispatch.

``key_switch_digits`` covers the per-digit prescale→BConv→NTT→MAC region of a
hybrid key-switch (everything between the shared iNTT and ModDown);
``mod_down_digits`` covers the prescale→BConv→NTT→(sub, ×P⁻¹) region of
ModDown for both accumulators.  Backends:

  * "kernel" — the fused Pallas pipeline, ONE launch per region
    (interpreted off-TPU, so CPU tests exercise the same program);
  * "ref"    — the staged oracle in ``ref`` (one launch per stage per digit);
  * "auto"   — kernel on TPU, ref elsewhere (repo-wide convention).

Tables are cached per (params, level): digit spans, per-row prescale
constants and Montgomery BConv weights (flat SMEM tables), and the
extended-basis NTT tables — all the state the fused kernel streams per grid
step.  Building them first applies the one shape rule (``_check_fits``): a
digit size whose VMEM bound (``kernel.fused_vmem_bytes``) exceeds
``tpu.VMEM_SCOPED_LIMIT`` raises and names ``backend="staged"``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.fhe import modmath as mm
from repro.fhe import ntt as nttmod
from repro.fhe import poly, rns
from repro.fhe.params import CkksParams
from repro.kernels import dispatch, tpu
from repro.kernels.ntt import ops as ntt_ops

from . import kernel as _k
from . import ref as _ref


_PAD_MOD = 3  # dummy odd modulus for zero-padded source rows (exact no-op)


def _check_fits(k: int, n: int) -> None:
    """The stated rule for fused shapes: refuse, loudly, a program over budget."""
    need = _k.fused_vmem_bytes(k, *nttmod.fourstep_split(n))
    if need > tpu.VMEM_SCOPED_LIMIT:
        raise ValueError(
            f"fused key-switch at N={n} with {k}-row digits needs {need} B of "
            f"scoped VMEM, over the {tpu.VMEM_SCOPED_LIMIT} B limit; "
            "run this shape with backend='staged'"
        )


@dataclasses.dataclass(frozen=True)
class KsTables:
    """Per-(params, level) constants for the fused key-switch kernel."""

    beta: int
    k: int  # rows per digit block (α; short digits are zero-padded)
    m: int
    spans: tuple[tuple[int, int], ...]  # (lo, hi) master-chain slice per digit
    dsc: jnp.ndarray  # (β·k·NDSC,) prescale constants, layout ``kernel.DB..DBH``
    wm: jnp.ndarray  # (β·k·m,) Montgomery BConv weights [B̂_i·R]_{c_e}
    ntt: ntt_ops.KernelTables  # forward NTT over the destination basis


def _prescale_tables(digits: list[tuple[int, ...]], dst_primes, k: int):
    """Flat (dsc, wm) SMEM tables for the digit list, rows zero-padded to k."""
    nd = len(digits)
    dst = np.array(dst_primes, np.uint64)
    dsc = np.zeros((nd, k, _k.NDSC), np.uint32)
    dsc[..., _k.DB] = _PAD_MOD
    dsc[..., _k.DBINV] = mm.MontConstants(_PAD_MOD).qinv_neg
    wm = np.zeros((nd, k, len(dst)), np.uint32)
    for j, src in enumerate(digits):
        n = len(src)
        bhat_inv, wj = rns.bconv_tables(src, tuple(int(c) for c in dst_primes))
        dsc[j, :n, _k.DB] = np.array(src, np.uint32)
        dsc[j, :n, _k.DBINV] = mm.mont_constants_array(list(src))["qinv_neg"]
        dsc[j, :n, _k.DBH] = [(int(bhat_inv[i]) << 32) % int(b) for i, b in enumerate(src)]
        wm[j, :n] = (np.asarray(wj, np.uint64) << np.uint64(32)) % dst
    return dispatch.upload(dsc.reshape(-1)), dispatch.upload(wm.reshape(-1))


@functools.lru_cache(maxsize=256)
@dispatch.spanned("table.ks")
def ks_tables(params: CkksParams, level: int) -> KsTables:
    alpha = params.alpha
    _check_fits(alpha, params.n)
    beta = params.beta(level)
    ext = poly.ext_idx(params, level)
    spans, digits = [], []
    for j in range(beta):
        lo, hi = j * alpha, min((j + 1) * alpha, level + 1)
        spans.append((lo, hi))
        digits.append(poly.primes_for(params, tuple(range(lo, hi))))
    plan = poly.plan_for(params, ext)
    dsc, wm = _prescale_tables(digits, poly.primes_for(params, ext), alpha)
    return KsTables(
        beta=beta, k=alpha, m=len(ext), spans=tuple(spans), dsc=dsc, wm=wm,
        ntt=ntt_ops.kernel_tables(plan, len(ext), inverse=False),
    )


@dataclasses.dataclass(frozen=True)
class ModDownTables:
    k: int
    m: int
    dsc: jnp.ndarray
    wm: jnp.ndarray
    pinv: jnp.ndarray  # (m,) Montgomery [P⁻¹]_{q_e}
    ntt: ntt_ops.KernelTables  # forward NTT over the q basis


@functools.lru_cache(maxsize=256)
@dispatch.spanned("table.moddown")
def moddown_tables(params: CkksParams, level: int) -> ModDownTables:
    p_primes = poly.primes_for(params, poly.p_idx(params))
    _check_fits(len(p_primes), params.n)
    q_primes = poly.primes_for(params, poly.q_idx(params, level))
    plan = poly.plan_for(params, poly.q_idx(params, level))
    dsc, wm = _prescale_tables([p_primes], q_primes, len(p_primes))
    P = rns.product(p_primes)
    pinv = np.array([(pow(P % int(q), -1, int(q)) << 32) % int(q) for q in q_primes], np.uint32)
    return ModDownTables(
        k=len(p_primes), m=len(q_primes), dsc=dsc, wm=wm, pinv=dispatch.upload(pinv),
        ntt=ntt_ops.kernel_tables(plan, len(q_primes), inverse=False),
    )


def pack_digits(d_coeff, tb: KsTables):
    """(nq, N) coefficient limbs → (β, k, N2, N1) zero-padded digit blocks.

    Digit j holds limbs [j·k, (j+1)·k) and only the last one is short, so
    zero rows appended after the last limb complete it.  Static slices and a
    pad with an uploaded zero keep this free of implicit index transfers."""
    pad = tb.beta * tb.k - d_coeff.shape[0]
    xd = lax.pad(d_coeff, dispatch.upload(0, np.uint32), [(0, pad, 0), (0, 0, 0)])
    return xd.reshape(tb.beta, tb.k, tb.ntt.n2, tb.ntt.n1)


def key_switch_digits(d_coeff, ksk_sel, params: CkksParams, level: int, backend: str = "auto"):
    """Σ_j NTT(BConv(d̂_j)) ∘ ksk_j over the extended basis, both components.

    d_coeff: (level+1, N) coefficient-domain limbs; ksk_sel: (β, 2, m, N)
    eval-domain key limbs restricted to the active extended basis.
    Returns (acc0, acc1), each (m, N) uint32 eval-domain.
    """
    if tpu.resolve(backend) == "ref":
        return _ref.key_switch_digits_ref(d_coeff, ksk_sel, params, level)
    with dispatch.launch("fusedks"):
        tb = ks_tables(params, level)
        nt = tb.ntt
        xd = pack_digits(dispatch.upload(d_coeff, np.uint32), tb)
        ksk = dispatch.upload(ksk_sel, np.uint32).reshape(tb.beta, 2, tb.m, nt.n1, nt.n2)
        out = _k.fused_ks_pallas(
            xd, nt.sc, tb.dsc, tb.wm, nt.tw, nt.v2, nt.v1, nt.t, ksk,
            interpret=not tpu.on_tpu(),
        ).reshape(tb.m, 2, params.n)
        return (lax.index_in_dim(out, 0, axis=1, keepdims=False),
                lax.index_in_dim(out, 1, axis=1, keepdims=False))


def mod_down_digits(p_coeff, q_part, params: CkksParams, level: int, backend: str = "auto"):
    """Fused ModDown tail for a batch of accumulators.

    p_coeff: (C, α, N) coefficient-domain P-block limbs (post-iNTT);
    q_part: (C, level+1, N) eval-domain q limbs.  Returns (C, level+1, N).
    C = 2 for one key-switch's accumulator pair; a hoisted rotation group
    passes C = 2·R to ModDown every rotation's pair in one launch.
    """
    if tpu.resolve(backend) == "ref":
        return _ref.mod_down_digits_ref(p_coeff, q_part, params, level)
    with dispatch.launch("fused_moddown"):
        tb = moddown_tables(params, level)
        nt = tb.ntt
        nb = p_coeff.shape[0]
        pc = dispatch.upload(p_coeff, np.uint32).reshape(nb, tb.k, nt.n2, nt.n1)
        qp = dispatch.upload(q_part, np.uint32).reshape(nb, tb.m, nt.n1, nt.n2)
        out = _k.fused_moddown_pallas(
            pc, nt.sc, tb.dsc, tb.wm, tb.pinv, nt.tw, nt.v2, nt.v1, nt.t, qp,
            interpret=not tpu.on_tpu(),
        )
        return out.reshape(nb, tb.m, params.n)
