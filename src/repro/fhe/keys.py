"""Key generation: secret/public keys and hybrid key-switching keys.

Hybrid KSK layout (Han–Ki / Lattigo convention, DESIGN.md §6): the chain
q_0..q_L is partitioned into dnum digits of ≤ α consecutive primes.  The key for
digit j encrypts  P·F_j·s'  under s over the extended basis Q∪P, where
F_j = Q̂_j·[Q̂_j^{-1}]_{Q_j}  satisfies  F_j ≡ 1 (mod q∈D_j), ≡ 0 (mod q∉D_j).
Level restriction is pure limb-dropping — the congruences hold per limb.

Keygen is host precompute: every NTT and pointwise op here runs the uint64
oracle (``backend="ref"``) explicitly, so keys are the same bits whichever
device evaluates later, and keygen runs under ``jax.default_device(cpu)``
on a machine whose default backend is a TPU.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from . import poly, trace
from .params import CkksParams


@dataclasses.dataclass
class SecretKey:
    s_coeff: np.ndarray  # (N,) int64 ternary
    s_eval: jnp.ndarray  # (L+1+α, N) uint32, eval domain over the master chain


@dataclasses.dataclass
class PublicKey:
    b: jnp.ndarray  # (L+1, N) eval domain over Q
    a: jnp.ndarray


@dataclasses.dataclass
class SwitchingKey:
    """(dnum, 2, L+1+α, N) uint32 — eval domain over the full extended basis."""

    k: jnp.ndarray

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.k.shape)) * 4


@dataclasses.dataclass
class KeySet:
    sk: SecretKey
    pk: PublicKey
    rlk: SwitchingKey
    gks: dict[int, SwitchingKey]  # galois element t → key for σ_t(s) → s
    # (t, level) → σ_t^{-1}-pre-permuted level-restricted key, filled lazily by
    # ``keyswitch.hoisted_ksk`` — a keygen-time precompute for hoisted rotations
    hoist_cache: dict = dataclasses.field(default_factory=dict, repr=False, compare=False)

    def galois(self, t: int) -> SwitchingKey:
        if t not in self.gks:
            raise KeyError(f"galois key for t={t} not generated")
        return self.gks[t]


def _uniform_rns(rng: np.random.Generator, primes, n: int) -> np.ndarray:
    out = np.empty((len(primes), n), np.uint32)
    for i, p in enumerate(primes):
        out[i] = rng.integers(0, int(p), size=n, dtype=np.uint64).astype(np.uint32)
    return out


def keygen(params: CkksParams, seed: int = 0, h: int | None = None) -> SecretKey:
    rng = np.random.default_rng(seed)
    if h is None:
        h = min(192, params.n // 4)
    s = poly.sample_ternary(rng, params.n, h)
    all_primes = params.all_primes
    s_rns = poly.to_rns_signed(s, all_primes)
    idx = tuple(range(len(all_primes)))
    s_eval = poly.to_eval(s_rns, params, idx, "ref")
    return SecretKey(s_coeff=s, s_eval=s_eval)


def _err_scale(params: CkksParams) -> int:
    """Error multiplier for key material: BGV keys carry t·e errors (message in
    the low-order bits), CKKS keys plain e."""
    return int(params.plain_modulus) if params.plain_modulus is not None else 1


def pkgen(params: CkksParams, sk: SecretKey, seed: int = 1) -> PublicKey:
    rng = np.random.default_rng(seed)
    qp = params.q_primes
    idx = poly.q_idx(params, params.L)
    a = jnp.asarray(_uniform_rns(rng, qp, params.n))
    e_coeff = _err_scale(params) * poly.sample_gaussian(rng, params.n)
    e = poly.to_eval(poly.to_rns_signed(e_coeff, qp), params, idx, "ref")
    s_q = sk.s_eval[: params.L + 1]
    from repro.kernels.modops import ops as mo

    qs = np.array(qp, np.uint64)
    b = mo.pointwise_submod(e, mo.pointwise_mulmod(a, s_q, qs, backend="ref"), qs, backend="ref")
    return PublicKey(b=b, a=a)


def kskgen(params: CkksParams, sk: SecretKey, s_prime_eval: jnp.ndarray, seed: int) -> SwitchingKey:
    """Key switching s' → s.  s_prime_eval: (L+1+α, N) over the master chain."""
    from repro.kernels.modops import ops as mo

    rng = np.random.default_rng(seed)
    all_primes = params.all_primes
    n = params.n
    L, alpha = params.L, params.alpha
    next_ = len(all_primes)
    idx_full = tuple(range(next_))
    qs = np.array(all_primes, np.uint64)
    P = 1
    for p in params.p_primes:
        P *= int(p)

    dnum = params.num_digits
    out = np.empty((dnum, 2, next_, n), np.uint32)
    for j in range(dnum):
        digit = params.digit(j)
        Qj = 1
        for i in digit:
            Qj *= int(all_primes[i])
        Q = 1
        for i in range(L + 1):
            Q *= int(all_primes[i])
        Qhat = Q // Qj
        Fj = Qhat * pow(Qhat, -1, Qj)  # ≡ 1 mod Q_j, ≡ 0 mod q∉D_j
        PFj = P * Fj
        pfj_limbs = np.array([PFj % int(p) for p in all_primes], np.uint64)

        a = jnp.asarray(_uniform_rns(rng, all_primes, n))
        e_coeff = _err_scale(params) * poly.sample_gaussian(rng, n)
        e = poly.to_eval(poly.to_rns_signed(e_coeff, all_primes), params, idx_full, "ref")
        # b = -a·s + e + PFj·s'  (eval domain, per limb)
        asq = mo.pointwise_mulmod(a, sk.s_eval, qs, backend="ref")
        pf = mo.pointwise_mulmod(
            s_prime_eval, jnp.asarray(pfj_limbs[:, None] % qs[:, None], jnp.uint32), qs,
            backend="ref",
        )
        b = mo.pointwise_submod(mo.pointwise_addmod(e, pf, qs, backend="ref"), asq, qs, backend="ref")
        out[j, 0] = np.asarray(b)
        out[j, 1] = np.asarray(a)
    trace.record("KSKGEN", n, dnum * 2 * next_)
    return SwitchingKey(k=jnp.asarray(out))


def relin_keygen(params: CkksParams, sk: SecretKey, seed: int = 2) -> SwitchingKey:
    from repro.kernels.modops import ops as mo

    qs = np.array(params.all_primes, np.uint64)
    s2 = mo.pointwise_mulmod(sk.s_eval, sk.s_eval, qs, backend="ref")
    return kskgen(params, sk, s2, seed)


def galois_keygen(params: CkksParams, sk: SecretKey, t: int, seed: int = 3) -> SwitchingKey:
    s_t = poly.automorphism_eval(sk.s_eval, params.n, t)
    return kskgen(params, sk, s_t, seed + t)


def galois_elements(params: CkksParams, rotations: tuple[int, ...] = (),
                    conjugate: bool = False) -> tuple[int, ...]:
    """Deduplicated Galois elements a rotation set needs keys for.

    Rotations congruent mod ``slots`` share one element, so precomputing this
    union (e.g. over every BSGS plan of a bootstrapping context) is what keeps
    keygen from over-generating switching keys."""
    ts = {pow(5, r % params.slots, 2 * params.n) for r in rotations if r % params.slots}
    if conjugate:
        ts.add(2 * params.n - 1)
    return tuple(sorted(ts))


def full_keyset(
    params: CkksParams,
    seed: int = 0,
    rotations: tuple[int, ...] = (),
    conjugate: bool = False,
    h: int | None = None,
) -> KeySet:
    """Generate sk/pk/rlk plus exactly one Galois key per needed element."""
    sk = keygen(params, seed, h=h)
    pk = pkgen(params, sk, seed + 1)
    rlk = relin_keygen(params, sk, seed + 2)
    gks: dict[int, SwitchingKey] = {
        t: galois_keygen(params, sk, t, seed + 100)
        for t in galois_elements(params, rotations, conjugate)
    }
    return KeySet(sk=sk, pk=pk, rlk=rlk, gks=gks)
