"""Pallas TPU kernel: fused pointwise RNS ops on the VPU.

HMUL's pointwise limb products are the paper's swift-cluster "Modular Mul/Add"
datapath.  One kernel invocation fuses the Montgomery double-multiply
(a·b·R^{-1}, then ·R² ⇒ plain product) so each limb element makes one VMEM
round trip instead of two.

Grid: one program per (batch row, limb).  A limb of N words arrives as an
(N/128, 128) tile (full last two dims, so any power-of-two N ≥ 128 tiles
legally); its modulus and Montgomery constants are read from the (l,) SMEM
tables at the limb's grid index, so one table serves every batch row.
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
    j = pl.program_id(1)
    q, qinv = q_ref[j], qinv_ref[j]
    t = _montmul(a_ref[...], b_ref[...], q, qinv)
    o_ref[...] = _montmul(t, r2_ref[j], q, qinv)


def _add_body(q_ref, a_ref, b_ref, o_ref):
    o_ref[...] = _addmod(a_ref[...], b_ref[...], q_ref[pl.program_id(1)])


def _sub_body(q_ref, a_ref, b_ref, o_ref):
    q = q_ref[pl.program_id(1)]
    a = a_ref[...]
    b = b_ref[...]
    o_ref[...] = jnp.where(a >= b, a - b, a + q - b)


def _rowwise(name, body, scalars, a, b, interpret):
    """Run ``body`` over (..., l, N) operands, one program per (batch row, limb)."""
    l, n = a.shape[-2:]
    tile = (n // LANES, LANES)
    rows = (-1, l) + tile
    a4, b4 = a.reshape(rows), b.reshape(rows)
    spec = pl.BlockSpec((None, None) + tile, lambda r, j: (r, j, 0, 0))
    out = tpu.call(
        body,
        scalars + (a4, b4),
        grid=a4.shape[:2],
        in_specs=[tpu.smem()] * len(scalars) + [spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a4.shape, jnp.uint32),
        name=name,
        interpret=interpret,
    )
    return out.reshape(a.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mulmod_pallas(a, b, q, qinv, r2, *, interpret):
    """a, b: (..., l, N) uint32; q/qinv/r2: (l,) uint32 per-limb constants."""
    return _rowwise("mulmod_pallas", _mul_body, (q, qinv, r2), a, b, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def addmod_pallas(a, b, q, *, interpret):
    return _rowwise("addmod_pallas", _add_body, (q,), a, b, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def submod_pallas(a, b, q, *, interpret):
    return _rowwise("submod_pallas", _sub_body, (q,), a, b, interpret)
