"""Public fused pointwise RNS ops (limb-wise, arbitrary leading batch)."""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from repro.fhe import modmath as mm
from repro.kernels import dispatch, tpu

from . import kernel as _k
from . import ref as _ref


@functools.lru_cache(maxsize=1024)
def _mont_cached(qs: tuple[int, ...]) -> dict:
    return mm.mont_constants_array(list(qs))


def _launch(kernel, a, b, consts):
    """Run a row kernel over (..., l, N) operands: one row per (batch, limb),
    each per-limb constant tiled over the flattened leading batch."""
    l, n = a.shape[-2:]
    reps = a.size // (l * n)
    rows = [jnp.tile(jnp.asarray(c, jnp.uint32).reshape(-1), reps) for c in consts]
    out = kernel(a.reshape(-1, n), b.reshape(-1, n), *rows, interpret=not tpu.on_tpu())
    return out.reshape(a.shape)


def pointwise_mulmod(a, b, qs, qinv=None, r2=None, backend: str = "auto"):
    """(a ∘ b) mod q per limb.  a, b: (..., l, N) uint32; qs: (l,).

    Montgomery constants are derived (and cached) from ``qs`` when the caller
    does not supply them, so any call site can reach the kernel path.
    """
    dispatch.record("mulmod")
    if tpu.resolve(backend) == "ref":
        return _ref.mulmod_ref(a, b, jnp.asarray(qs, jnp.uint32))
    if qinv is None or r2 is None:
        consts = _mont_cached(tuple(int(q) for q in np.asarray(qs).tolist()))
        qinv, r2 = consts["qinv_neg"], consts["r2"]
    return _launch(_k.mulmod_pallas, a, b, (qs, qinv, r2))


def pointwise_addmod(a, b, qs, backend: str = "auto"):
    dispatch.record("addmod")
    if tpu.resolve(backend) == "ref":
        return _ref.addmod_ref(a, b, jnp.asarray(qs, jnp.uint32))
    return _launch(_k.addmod_pallas, a, b, (qs,))


def pointwise_submod(a, b, qs, backend: str = "auto"):
    dispatch.record("submod")
    if tpu.resolve(backend) == "ref":
        return _ref.submod_ref(a, b, jnp.asarray(qs, jnp.uint32))
    return _launch(_k.submod_pallas, a, b, (qs,))
