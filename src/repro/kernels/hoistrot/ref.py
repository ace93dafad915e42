"""Staged oracle for the hoisted-rotation kernels.

Composes the per-stage reference ops exactly as the staged dispatcher in
``repro.fhe.keyswitch`` does (no trace recording) — the bit-exactness target
for ``hoist_modup_pallas``/``hoist_mac_pallas``, mirroring ``fusedks/ref.py``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.fhe import poly
from repro.fhe.params import CkksParams
from repro.kernels.fusedks.ref import mod_up_digit_ref
from repro.kernels.modops import ops as mo


def mod_up_digits_ref(d_coeff, params: CkksParams, level: int):
    """(level+1, N) coeff limbs → (β, m, N) eval-domain extended-basis digits."""
    return jnp.stack([mod_up_digit_ref(d_coeff, params, level, j)
                      for j in range(params.beta(level))])


def galois_mac_ref(dig, ksk, params: CkksParams, level: int, stage: str = "ref"):
    """Σ_j dig_j ∘ ksk_{r,j} per rotation: (R, β, 2, m, N) keys → (R, 2, m, N).

    ``stage`` is the per-op backend for every pointwise MAC (the staged
    pipeline threads its resolved stage here; "ref" is the u64 oracle)."""
    ext = poly.ext_idx(params, level)
    ext_primes = np.array(poly.primes_for(params, ext), np.uint64)
    m, n = dig.shape[1], dig.shape[2]
    outs = []
    for r in range(ksk.shape[0]):
        acc0 = jnp.zeros((m, n), jnp.uint32)
        acc1 = jnp.zeros((m, n), jnp.uint32)
        for j in range(params.beta(level)):
            t0 = mo.pointwise_mulmod(dig[j], ksk[r, j, 0], ext_primes, backend=stage)
            t1 = mo.pointwise_mulmod(dig[j], ksk[r, j, 1], ext_primes, backend=stage)
            acc0 = mo.pointwise_addmod(acc0, t0, ext_primes, backend=stage)
            acc1 = mo.pointwise_addmod(acc1, t1, ext_primes, backend=stage)
        outs.append(jnp.stack([acc0, acc1]))
    return jnp.stack(outs)
