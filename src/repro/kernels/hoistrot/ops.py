"""Public hoisted-rotation ops: shared ModUp + batched Galois MAC dispatch.

``mod_up_digits`` raises all β digits of one polynomial to the extended basis
(one launch, digits materialised for reuse); ``galois_mac`` applies every
Galois key of a rotation group against those digits in a single launch.
Backends follow the repo convention:

  * "kernel" — the Pallas pipelines (interpreted off-TPU);
  * "ref"    — staged u64 oracle in ``ref``;
  * "auto"   — kernel on TPU, ref elsewhere.

Tables are shared with ``kernels.fusedks`` — the ModUp half of a hoisted
rotation is exactly the fused key-switch digit region minus the MAC epilogue,
so the per-(params, level) constants (digit spans, prescale constants, BConv
weights, extended-basis NTT plan) are the same cache.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.fhe.params import CkksParams
from repro.kernels import dispatch, tpu
from repro.kernels.fusedks import ops as fused_ops

from . import kernel as _k
from . import ref as _ref


def mod_up_digits(d_coeff, params: CkksParams, level: int, backend: str = "auto"):
    """prescale→BConv→NTT for all β digits of one polynomial, ONE launch.

    d_coeff: (level+1, N) coefficient-domain limbs.  Returns (β, m, N) uint32
    eval-domain digits over the extended basis — the reusable ModUp half of a
    key-switch (rotation-independent, shared by a whole hoisted group).
    """
    if tpu.resolve(backend) == "ref":
        return _ref.mod_up_digits_ref(d_coeff, params, level)
    tb = fused_ops.ks_tables(params, level)
    nt = tb.ntt
    xd = fused_ops.pack_digits(jnp.asarray(d_coeff, jnp.uint32), tb, params.n)
    dispatch.record("hoistmodup")
    out = _k.hoist_modup_pallas(
        xd, nt.sc, tb.dsc, tb.wm, nt.tw, nt.v2, nt.v1, nt.t, interpret=not tpu.on_tpu(),
    )
    return out.reshape(tb.beta, tb.m, params.n)


def galois_mac(dig, ksk, params: CkksParams, level: int, backend: str = "auto",
               staged: bool = False):
    """KSK inner products of one hoisted group: all rotations, ONE launch.

    dig: (β, m, N) hoisted digits (eval, extended basis); ksk: (R, β, 2, m, N)
    σ_t^{-1}-pre-permuted key limbs.  Returns (R, 2, m, N) accumulator pairs.
    ``staged=True`` forces the per-op composition with ``backend`` as the
    stage for every pointwise op (the staged pipeline's semantics) instead of
    the single batched launch.
    """
    if staged:
        return _ref.galois_mac_ref(dig, ksk, params, level, stage=backend)
    if tpu.resolve(backend) == "ref":
        return _ref.galois_mac_ref(dig, ksk, params, level)
    tb = fused_ops.ks_tables(params, level)
    nt = tb.ntt
    beta, m, n = dig.shape
    nrot = ksk.shape[0]
    dispatch.record("hoistmac")
    out = _k.hoist_mac_pallas(
        jnp.asarray(dig, jnp.uint32).reshape(beta, m, nt.n1, nt.n2),
        jnp.asarray(ksk, jnp.uint32).reshape(nrot, beta, 2, m, nt.n1, nt.n2),
        nt.sc, interpret=not tpu.on_tpu(),
    )
    return out.reshape(nrot, 2, m, n)
