"""Public BConv op: pads limb counts to multiples of 8 and dispatches kernel/ref."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.fhe import modmath as mm
from repro.fhe.ntt import NDIAG, NLIMB8
from repro.kernels import dispatch, tpu

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
    dispatch.record("bconv")
    if tpu.resolve(backend) == "ref":
        return _ref.bconv_ref(xhat, w, jnp.asarray(cs, jnp.uint32))

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
    wt = jnp.zeros((m8, k8), jnp.uint32).at[:m, :k].set(jnp.asarray(w, jnp.uint32).T)
    wl = jnp.stack([(wt >> (8 * s)) & 0xFF for s in range(NLIMB8)]).astype(jnp.bfloat16)
    out = _k.bconv_pallas(
        xp,
        wl,
        jnp.asarray(c_mont),
        jnp.asarray(consts["q"].reshape(m8, 1)),
        jnp.asarray(consts["qinv_neg"].reshape(m8, 1)),
        interpret=not tpu.on_tpu(),
    )
    return out[:m]
