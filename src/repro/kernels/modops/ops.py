"""Public fused pointwise RNS ops (limb-wise, arbitrary leading batch)."""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import numpy as np

from repro.fhe import modmath as mm
from repro.kernels import dispatch, tpu

from . import kernel as _k
from . import ref as _ref


class LimbConstants(NamedTuple):
    """Per-limb constants of one limb set, each (l,) uint32 on the device."""

    q: jax.Array  # moduli
    qinv_neg: jax.Array  # -q⁻¹ mod 2^32
    r2: jax.Array  # R² mod q, R = 2^32


@functools.lru_cache(maxsize=1024)
@dispatch.spanned("table.limbs")
def _limb_tables(qs: tuple[int, ...], device) -> LimbConstants:
    """Built once per limb set and default ``device`` (the key places the
    arrays: ``upload`` puts them on the default device of the building call)."""
    consts = mm.mont_constants_array(list(qs))
    return LimbConstants(*(dispatch.upload(consts[k]) for k in LimbConstants._fields))


def limb_constants(qs) -> LimbConstants:
    """The device-resident constants of the limb set ``qs`` ((l,) primes)."""
    return _limb_tables(tuple(np.asarray(qs).tolist()), dispatch.default_device())


def pointwise_mulmod(a, b, qs, backend: str = "auto"):
    """(a ∘ b) mod q per limb.  a, b: (..., l, N) uint32; qs: (l,) primes."""
    with dispatch.launch("mulmod"):
        c = limb_constants(qs)
        if tpu.resolve(backend) == "ref":
            return _ref.mulmod_ref(a, b, c.q)
        return _k.mulmod_pallas(a, b, c.q, c.qinv_neg, c.r2, interpret=not tpu.on_tpu())


def pointwise_addmod(a, b, qs, backend: str = "auto"):
    with dispatch.launch("addmod"):
        c = limb_constants(qs)
        if tpu.resolve(backend) == "ref":
            return _ref.addmod_ref(a, b, c.q)
        return _k.addmod_pallas(a, b, c.q, interpret=not tpu.on_tpu())


def pointwise_submod(a, b, qs, backend: str = "auto"):
    with dispatch.launch("submod"):
        c = limb_constants(qs)
        if tpu.resolve(backend) == "ref":
            return _ref.submod_ref(a, b, c.q)
        return _k.submod_pallas(a, b, c.q, interpret=not tpu.on_tpu())
