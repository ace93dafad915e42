"""Pallas TPU kernel: fused pointwise RNS ops on the VPU.

HMUL's pointwise limb products are the paper's swift-cluster "Modular Mul/Add"
datapath.  One kernel invocation fuses the Montgomery double-multiply
(a·b·R^{-1}, then ·R² ⇒ plain product) so each limb element makes one VMEM
round trip instead of two.

Grid: one program per limb row.  A row of N words arrives as an
(N/128, 128) tile (full last two dims, so any power-of-two N ≥ 128 tiles
legally); the row's modulus and Montgomery constants are SMEM scalars.
The TPU compiler reports 1.18 MiB of scoped VMEM for ``mulmod`` at N=2^16 on
a v5e (default limit 16 MiB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tpu
from repro.kernels.ntt.kernel import _addmod, _montmul

LANES = 128


def _mul_body(q_ref, qinv_ref, r2_ref, a_ref, b_ref, o_ref):
    i = pl.program_id(0)
    q, qinv = q_ref[i], qinv_ref[i]
    t = _montmul(a_ref[...], b_ref[...], q, qinv)
    o_ref[...] = _montmul(t, r2_ref[i], q, qinv)


def _add_body(q_ref, a_ref, b_ref, o_ref):
    o_ref[...] = _addmod(a_ref[...], b_ref[...], q_ref[pl.program_id(0)])


def _sub_body(q_ref, a_ref, b_ref, o_ref):
    q = q_ref[pl.program_id(0)]
    a = a_ref[...]
    b = b_ref[...]
    o_ref[...] = jnp.where(a >= b, a - b, a + q - b)


def _rowwise(name, body, scalars, a, b, interpret):
    """Run ``body`` over (rows, N) operands, one limb row per program."""
    rows, n = a.shape
    tile = (n // LANES, LANES)
    spec = pl.BlockSpec((None,) + tile, lambda i: (i, 0, 0))
    out = tpu.call(
        body,
        scalars + (a.reshape((rows,) + tile), b.reshape((rows,) + tile)),
        grid=(rows,),
        in_specs=[tpu.smem()] * len(scalars) + [spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows,) + tile, jnp.uint32),
        name=name,
        interpret=interpret,
    )
    return out.reshape(rows, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mulmod_pallas(a, b, q, qinv, r2, *, interpret):
    """a, b: (rows, N) uint32; q/qinv/r2: (rows,) uint32 per-row constants."""
    return _rowwise("mulmod", _mul_body, (q, qinv, r2), a, b, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def addmod_pallas(a, b, q, *, interpret):
    return _rowwise("addmod", _add_body, (q,), a, b, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def submod_pallas(a, b, q, *, interpret):
    return _rowwise("submod", _sub_body, (q,), a, b, interpret)
