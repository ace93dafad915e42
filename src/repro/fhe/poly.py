"""Polynomial-domain helpers shared by the CKKS ops.

A polynomial is a (limbs, N) uint32 jnp array of RNS residues, either in
coefficient domain or evaluation (NTT) domain.  Which master-chain limbs a
tensor carries is tracked by the caller via index tuples from `q_idx`/`ext_idx`;
NTT plans restricted to those limbs come from `fhe.ntt.subplan`.

Every domain crossing records an instruction into the ambient trace — these are
exactly the (i)NTT pipeline occupancies the core scheduler/simulator replays.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import dispatch
from repro.kernels.ntt import ops as ntt_ops

from . import ntt as nttmod
from . import trace
from .params import CkksParams


def q_idx(params: CkksParams, level: int) -> tuple[int, ...]:
    """Master-chain indices of the ciphertext basis at ``level``."""
    return tuple(range(level + 1))

def p_idx(params: CkksParams) -> tuple[int, ...]:
    """Master-chain indices of the special (key) modulus block."""
    return tuple(range(params.L + 1, params.L + 1 + params.alpha))

def ext_idx(params: CkksParams, level: int) -> tuple[int, ...]:
    """Extended basis {q_0..q_level} ∪ {p_0..p_α-1}."""
    return q_idx(params, level) + p_idx(params)


@functools.lru_cache(maxsize=4096)
def plan_for(params: CkksParams, idx: tuple[int, ...]) -> nttmod.NttPlan:
    return nttmod.subplan(params.n, params.all_primes, idx)


def limbs(x, lo: int, hi: int):
    """Limbs [lo, hi) of a (limbs, ...) array: a static slice, so the eager
    call transfers no index to the device; the whole array is returned as is."""
    return x if (lo, hi) == (0, x.shape[0]) else lax.slice_in_dim(x, lo, hi)


def primes_for(params: CkksParams, idx: tuple[int, ...]) -> tuple[int, ...]:
    allp = params.all_primes
    return tuple(allp[i] for i in idx)


def to_eval(x, params: CkksParams, idx: tuple[int, ...], backend: str = "auto"):
    """Coefficient → evaluation domain over the limb subset ``idx``."""
    trace.record("NTT", params.n, len(idx))
    return ntt_ops.ntt_fwd(dispatch.upload(x, np.uint32), plan_for(params, idx), backend)


def to_coeff(x, params: CkksParams, idx: tuple[int, ...], backend: str = "auto"):
    """Evaluation → coefficient domain over the limb subset ``idx``."""
    trace.record("INTT", params.n, len(idx))
    return ntt_ops.ntt_inv(dispatch.upload(x, np.uint32), plan_for(params, idx), backend)


@functools.lru_cache(maxsize=512)
@dispatch.spanned("table.eval_perm")
def _eval_perm(n: int, t: int):
    return dispatch.upload(nttmod.galois_eval_perm(n, t))


def automorphism_eval(x, n: int, t: int):
    """σ_t in the evaluation domain — a pure slot permutation (paper's AUTO unit)."""
    trace.record("AUTO", n, x.shape[-2] if x.ndim >= 2 else 1)
    return jnp.take(x, _eval_perm(n, t), axis=-1)


def sample_ternary(rng: np.random.Generator, n: int, h: int) -> np.ndarray:
    """Ternary secret with hamming weight h (int64 coefficients in {-1,0,1})."""
    s = np.zeros(n, np.int64)
    pos = rng.choice(n, size=h, replace=False)
    s[pos] = rng.choice(np.array([-1, 1]), size=h)
    return s


def sample_gaussian(rng: np.random.Generator, n: int, sigma: float = 3.2) -> np.ndarray:
    return np.rint(rng.normal(0.0, sigma, size=n)).astype(np.int64)


def to_rns_signed(v: np.ndarray, primes) -> np.ndarray:
    """Signed int64 coefficients → (limbs, N) uint32 residues."""
    out = np.empty((len(primes), v.shape[-1]), np.uint32)
    for i, p in enumerate(primes):
        out[i] = np.mod(v, np.int64(p)).astype(np.uint32)
    return out
