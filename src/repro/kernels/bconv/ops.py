"""Public BConv ops: the plain conversion (pads limb counts to multiples of 8
and dispatches kernel/ref) and the centred conversion the key-switch runs."""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fhe import modmath as mm
from repro.fhe import rns
from repro.fhe.ntt import NDIAG, NLIMB8
from repro.kernels import dispatch, tpu
from repro.kernels.modops import ops as mo

from . import kernel as _k
from . import ref as _ref


def _pad8(v: int) -> int:
    return (v + 7) // 8 * 8


def bconv(xhat, w, cs, backend: str = "auto"):
    """Fast basis conversion.

    xhat: (k, N) uint32 — input limbs already scaled by [B̂_i^{-1}]_{b_i};
    w:    (k, m) uint32 — W[i, j] = B̂_i mod c_j;
    cs:   (m,)  target moduli.
    Returns (m, N) uint32.
    """
    with dispatch.launch("bconv"):
        if tpu.resolve(backend) == "ref":
            return _ref.bconv_ref(xhat, w, dispatch.upload(cs, np.uint32))

        k, n = xhat.shape
        m = w.shape[1]
        k8, m8 = _pad8(k), _pad8(m)
        cs_np = np.asarray(cs, np.uint64)
        cs_pad = np.concatenate([cs_np, np.full(m8 - m, 3, np.uint64)])  # dummy odd modulus
        consts = mm.mont_constants_array(cs_pad.tolist())
        c_mont = np.zeros((NDIAG, m8, 1), np.uint32)
        for j, cj in enumerate(cs_pad):
            c_mont[:, j, 0] = [((1 << (8 * s)) << 32) % int(cj) for s in range(NDIAG)]
        xp = jnp.zeros((k8, n), jnp.uint32).at[:k].set(xhat.astype(jnp.uint32))
        wt = jnp.zeros((m8, k8), jnp.uint32).at[:m, :k].set(dispatch.upload(w, np.uint32).T)
        wl = jnp.stack([(wt >> (8 * s)) & 0xFF for s in range(NLIMB8)]).astype(jnp.bfloat16)
        out = _k.bconv_pallas(
            xp,
            wl,
            dispatch.upload(c_mont),
            dispatch.upload(consts["q"].reshape(m8, 1)),
            dispatch.upload(consts["qinv_neg"].reshape(m8, 1)),
            interpret=not tpu.on_tpu(),
        )
        return out[:m]


class ConvTables(NamedTuple):
    """Device constants of one centred conversion src → dst (``rns`` tables)."""

    bhat_inv: jax.Array  # (k, 1) [B̂_i⁻¹]_{b_i}
    half: jax.Array  # (k, 1) ⌊b_i/2⌋
    w: jax.Array  # (k, m) B̂_i mod c_j
    corr: jax.Array  # (m, 1) Σ_i ⌊b_i/2⌋·B̂_i mod c_j


@functools.lru_cache(maxsize=1024)
@dispatch.spanned("table.bconv")
def _conv_tables(src: tuple[int, ...], dst: tuple[int, ...], device) -> ConvTables:
    """Built once per (src, dst) and default ``device``, like ``mo.limb_constants``."""
    bhat_inv, w = rns.bconv_tables(src, dst)
    half, corr = rns.centring_tables(src, dst)
    return ConvTables(dispatch.upload(bhat_inv[:, None]), dispatch.upload(half[:, None]),
                      dispatch.upload(w), dispatch.upload(corr[:, None]))


def conv_centred(x, src, dst, backend: str = "auto"):
    """Centred fast basis conversion of coefficient limbs x: (k, N) over the
    primes ``src`` → (m, N) over ``dst``.

    Each row enters as the centred representative of [x_i·B̂_i⁻¹]_{b_i}
    (``rns.centring_tables``), so the converted integer has mean zero.  The
    staged form of the region the fused key-switch kernels run in one program:
    prescale, centre, convert, correct.
    """
    tb = _conv_tables(tuple(int(b) for b in src), tuple(int(c) for c in dst),
                      dispatch.default_device())
    y = mo.pointwise_mulmod(x, jnp.broadcast_to(tb.bhat_inv, x.shape), src, backend=backend)
    y = mo.pointwise_addmod(y, jnp.broadcast_to(tb.half, x.shape), src, backend=backend)
    conv = bconv(y, tb.w, dst, backend=backend)
    return mo.pointwise_submod(conv, jnp.broadcast_to(tb.corr, conv.shape), dst, backend=backend)
