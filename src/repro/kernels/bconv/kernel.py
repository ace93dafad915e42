"""Pallas TPU kernel: fast basis conversion (BConv) as a modular MXU matmul.

The paper's BConv unit is l_sub = 60 parallel modular-multiply lanes feeding
adder trees; on TPU the natural substrate is again the MXU.  out = Wᵀ·x̂ mod c
is computed by 8-bit limb decomposition of both operands, fed to the MXU as
bf16 (exact for 0..255) with f32 accumulation: a limb product sums K8 terms
≤ 255², exact below 2^24 for K8 ≤ 256.  Products are cast to int32, the ≤ 4
of one limb diagonal summed (< 2^26), and the seven diagonals recombined with
Montgomery constants 2^(8s)·R mod c_j (``ntt.kernel._mod_matmul``).

Grid: (coefficient blocks,).  Per program: x̂ (K8, NB) uint32, the limbs of
Wᵀ (NLIMB8, M8, K8) bf16 precomputed by the wrapper, and per-row constants as
(M8, 1) columns.  K8/M8 are the 8-padded limb counts (zero rows/cols are
exact no-ops).  Scoped VMEM the TPU compiler reports for a v5e at NB = 4096:
1.94 MiB at dblookup (K8=8, M8=16), 2.69 MiB at lstm (K8=8, M8=24) — inside
the 16 MiB default, so no ``vmem_limit_bytes`` is set.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.fhe.ntt import NDIAG, NLIMB8
from repro.kernels import tpu
from repro.kernels.ntt.kernel import _limbs, _mod_matmul


def _bconv_kernel_body(x_ref, wl_ref, c_ref, q_ref, qinv_ref, o_ref):
    wl = [wl_ref[k] for k in range(NLIMB8)]  # (M8, K8) bf16 limbs of Wᵀ
    cm = [c_ref[s] for s in range(NDIAG)]  # (M8, 1) each
    # (M8, K8) @ (K8, NB) → (M8, NB) mod c_j
    o_ref[...] = _mod_matmul(wl, _limbs(x_ref[...]), cm, q_ref[...], qinv_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def bconv_pallas(xhat, wl, c_mont, q, qinv, *, interpret):
    """xhat: (K8, N) u32; wl: (NLIMB8, M8, K8) bf16 limbs of Wᵀ;
    c_mont: (NDIAG, M8, 1); q/qinv: (M8, 1)."""
    k8, n = xhat.shape
    m8 = wl.shape[1]
    nb = min(n, 4096)
    assert n % nb == 0
    return tpu.call(
        _bconv_kernel_body,
        (xhat, wl, c_mont, q, qinv),
        grid=(n // nb,),
        in_specs=[
            pl.BlockSpec((k8, nb), lambda i: (0, i)),
            pl.BlockSpec((NLIMB8, m8, k8), lambda i: (0, 0, 0)),
            pl.BlockSpec((NDIAG, m8, 1), lambda i: (0, 0, 0)),
            pl.BlockSpec((m8, 1), lambda i: (0, 0)),
            pl.BlockSpec((m8, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m8, nb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m8, n), jnp.uint32),
        name="bconv_pallas",
        interpret=interpret,
    )
