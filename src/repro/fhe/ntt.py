"""Negacyclic NTT plans for RNS-CKKS.

The ring is Z_q[x]/(x^N + 1).  With psi a primitive 2N-th root of unity mod q and
w = psi^2, the negacyclic NTT is a twist by psi^i followed by a cyclic N-point NTT;
slot j of the result is the evaluation a(psi^(2j+1)) (natural order).

Two executable forms share these plans:
  * ``repro.kernels.ntt.ref``    — uint64 iterative radix-2 oracle (fast on CPU/XLA);
  * ``repro.kernels.ntt.kernel`` — Pallas four-step kernel: an N1-point NTT is an
    N1×N1 modular *matmul* on the MXU (8-bit limbs as bf16 operands, exact f32
    accumulation, Montgomery recombination).  N = N1·N2 mirrors the paper's
    256×256 (bootstrappable, N=2^16) and 128×128 (swift, N=2^14) circuits.

Plans are cached per (N, primes).  All tables are host numpy; ops convert lazily.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np
from ml_dtypes import bfloat16

from . import modmath as mm

NLIMB8 = 4  # number of 8-bit limbs covering q < 2^31
NDIAG = 2 * NLIMB8 - 1


def fourstep_split(n: int) -> tuple[int, int]:
    """N = N1·N2 with N2 ≥ 128 (lane-aligned) and N1 the 'circuit' size.

    2^16 → 256×256 (bootstrappable circuit), 2^14 → 128×128 (swift circuit),
    2^11 → 16×128, matching the paper's multi-entrance/exit decomposition.
    """
    logn = n.bit_length() - 1
    assert 1 << logn == n and logn >= 8, f"N={n} must be a power of two ≥ 256"
    log2_n2 = max(7, (logn + 1) // 2)
    n2 = 1 << log2_n2
    return n // n2, n2


def _pow_table(w: int, n: int, q: int) -> np.ndarray:
    """[w^0, ..., w^(n-1)] mod q as uint64, via log-doubling."""
    t = np.ones(n, dtype=np.uint64)
    if n == 1:
        return t
    t[1] = w % q
    filled = 2
    step = np.uint64(w % q)
    qq = np.uint64(q)
    while filled < n:
        take = min(filled, n - filled)
        # two exact sub-2^62 steps: t[i]·w^(filled-1) then ·w
        block = (t[:take] * t[filled - 1]) % qq
        block = (block * step) % qq
        t[filled : filled + take] = block
        filled += take
    return t


def bit_reverse_indices(n: int) -> np.ndarray:
    logn = n.bit_length() - 1
    idx = np.arange(n)
    rev = np.zeros(n, dtype=np.int64)
    for b in range(logn):
        rev |= ((idx >> b) & 1) << (logn - 1 - b)
    return rev


def _to_mont(v: np.ndarray, q: int) -> np.ndarray:
    """Plain u64 values < q → Montgomery form (v·2^32 mod q) as uint32."""
    return (((v.astype(np.uint64)) << np.uint64(32)) % np.uint64(q)).astype(np.uint32)


def _limbs8(v: np.ndarray) -> np.ndarray:
    """(..., ) u64 values < 2^31 → (NLIMB8, ...) 8-bit limbs, exact in bf16 (MXU operands)."""
    v = v.astype(np.uint64)
    return np.stack(
        [((v >> np.uint64(8 * k)) & np.uint64(0xFF)).astype(bfloat16) for k in range(NLIMB8)],
        axis=0,
    )


@dataclasses.dataclass(frozen=True, eq=False)
class NttPlan:
    """All tables for one ring degree N over one RNS prime chain.

    Plans compare and hash by identity: they come from the cached
    ``build_plan``/``subplan``, and their arrays make field equality moot."""

    n: int
    n1: int
    n2: int
    qs: np.ndarray  # (L,) uint32
    qinv_neg: np.ndarray  # (L,) uint32
    r2: np.ndarray  # (L,) uint32
    # --- reference (u64) tables ---
    w_pows: np.ndarray  # (L, N)  powers of w
    winv_pows: np.ndarray  # (L, N)
    psi_pows: np.ndarray  # (L, N)  twist
    psiinv_ninv: np.ndarray  # (L, N)  psi^{-i}·N^{-1}
    # --- four-step kernel tables (plain-value limb matrices + mont twiddles) ---
    v2_limbs: np.ndarray  # (L, NLIMB8, N2, N2) bf16   row NTT matrix
    v1_limbs: np.ndarray  # (L, NLIMB8, N1, N1) bf16   col NTT matrix
    v2i_limbs: np.ndarray
    v1i_limbs: np.ndarray
    t_mont: np.ndarray  # (L, N1, N2) uint32  inter-step twiddle w^(n1·k2)·R
    ti_mont: np.ndarray  # (L, N1, N2) uint32  inverse twiddle
    twa_mont: np.ndarray  # (L, N1, N2) uint32  fwd twist psi^(n1+N1·n2)·R in A-layout
    twia_mont: np.ndarray  # (L, N1, N2) uint32  inv twist·N^{-1} in A-layout
    c_mont: np.ndarray  # (L, NDIAG) uint32   mont form of 2^(8s)

    @property
    def num_limbs(self) -> int:
        return len(self.qs)


@functools.lru_cache(maxsize=32)
def build_plan(n: int, primes: tuple[int, ...]) -> NttPlan:
    n1, n2 = fourstep_split(n)
    L = len(primes)
    qs = np.array(primes, np.uint32)
    consts = mm.mont_constants_array(primes)

    w_pows = np.zeros((L, n), np.uint64)
    winv_pows = np.zeros((L, n), np.uint64)
    psi_pows = np.zeros((L, n), np.uint64)
    psiinv_ninv = np.zeros((L, n), np.uint64)
    v2_limbs = np.zeros((L, NLIMB8, n2, n2), bfloat16)
    v1_limbs = np.zeros((L, NLIMB8, n1, n1), bfloat16)
    v2i_limbs = np.zeros((L, NLIMB8, n2, n2), bfloat16)
    v1i_limbs = np.zeros((L, NLIMB8, n1, n1), bfloat16)
    t_mont = np.zeros((L, n1, n2), np.uint32)
    ti_mont = np.zeros((L, n1, n2), np.uint32)
    twa_mont = np.zeros((L, n1, n2), np.uint32)
    twia_mont = np.zeros((L, n1, n2), np.uint32)
    c_mont = np.zeros((L, NDIAG), np.uint32)

    i1 = np.arange(n1)
    i2 = np.arange(n2)
    for li, q in enumerate(primes):
        psi = mm.root_of_unity(2 * n, q)
        psi_inv = pow(psi, -1, q)
        w = psi * psi % q
        w_inv = pow(w, -1, q)
        n_inv = pow(n, -1, q)

        wp = _pow_table(w, n, q)
        wip = _pow_table(w_inv, n, q)
        pp = _pow_table(psi, n, q)
        pip = _pow_table(psi_inv, n, q)
        w_pows[li] = wp
        winv_pows[li] = wip
        psi_pows[li] = pp
        psiinv_ninv[li] = (pip * np.uint64(n_inv)) % np.uint64(q)

        # V matrices: V2[a, b] = w_{N2}^(a·b);   w_{N2} = w^(N/N2)
        e2 = (np.outer(i2, i2) % n2).astype(np.int64)
        e1 = (np.outer(i1, i1) % n1).astype(np.int64)
        w2p = _pow_table(pow(w, n // n2, q), n2, q)
        w1p = _pow_table(pow(w, n // n1, q), n1, q)
        w2ip = _pow_table(pow(w_inv, n // n2, q), n2, q)
        w1ip = _pow_table(pow(w_inv, n // n1, q), n1, q)
        v2_limbs[li] = _limbs8(w2p[e2])
        v1_limbs[li] = _limbs8(w1p[e1])
        v2i_limbs[li] = _limbs8(w2ip[e2])
        v1i_limbs[li] = _limbs8(w1ip[e1])

        # inter-step twiddles T[n1,k2] = w^(n1·k2)
        et = (np.outer(i1, i2) % n).astype(np.int64)
        t_mont[li] = _to_mont(wp[et], q)
        ti_mont[li] = _to_mont(wip[et], q)

        # twists in A-layout: A[a, b] ↔ coefficient index a + N1·b
        idx_a = (i1[:, None] + n1 * i2[None, :]) % n
        twa_mont[li] = _to_mont(pp[idx_a], q)
        twia_mont[li] = _to_mont(((pip[idx_a] * np.uint64(n_inv)) % np.uint64(q)), q)

        c_mont[li] = _to_mont(
            np.array([(1 << (8 * s)) % q for s in range(NDIAG)], np.uint64), q
        )

    return NttPlan(
        n=n,
        n1=n1,
        n2=n2,
        qs=qs,
        qinv_neg=consts["qinv_neg"],
        r2=consts["r2"],
        w_pows=w_pows,
        winv_pows=winv_pows,
        psi_pows=psi_pows,
        psiinv_ninv=psiinv_ninv,
        v2_limbs=v2_limbs,
        v1_limbs=v1_limbs,
        v2i_limbs=v2i_limbs,
        v1i_limbs=v1i_limbs,
        t_mont=t_mont,
        ti_mont=ti_mont,
        twa_mont=twa_mont,
        twia_mont=twia_mont,
        c_mont=c_mont,
    )


_PER_LIMB_FIELDS = (
    "qs", "qinv_neg", "r2", "w_pows", "winv_pows", "psi_pows", "psiinv_ninv",
    "v2_limbs", "v1_limbs", "v2i_limbs", "v1i_limbs",
    "t_mont", "ti_mont", "twa_mont", "twia_mont", "c_mont",
)


@functools.lru_cache(maxsize=1024)
def subplan(n: int, primes: tuple[int, ...], idx: tuple[int, ...]) -> NttPlan:
    """A view of build_plan(n, primes) restricted to the limb subset ``idx``.

    Ciphertexts live on arbitrary sub-chains of the master prime chain (levels,
    key-switch digits, the special-modulus block); this selects the matching
    rows of every per-limb table.  Cached — the set of distinct subsets during a
    workload is O(L·dnum).
    """
    base = build_plan(n, primes)
    sel = np.array(idx, np.int64)
    return dataclasses.replace(base, **{f: getattr(base, f)[sel] for f in _PER_LIMB_FIELDS})


def galois_eval_perm(n: int, t: int) -> np.ndarray:
    """Permutation p with NTT(σ_t(a))[j] = NTT(a)[p[j]] (natural slot order).

    σ_t : a(x) → a(x^t), t odd.  Slot j evaluates at psi^(2j+1), so
    σ_t(a)(psi^(2j+1)) = a(psi^(t(2j+1))) = slot ((t(2j+1) mod 2N) - 1)/2 of a.
    """
    assert t % 2 == 1
    j = np.arange(n, dtype=np.int64)
    src = ((t * (2 * j + 1)) % (2 * n) - 1) // 2
    return src.astype(np.int32)


def galois_coeff_map(n: int, t: int) -> tuple[np.ndarray, np.ndarray]:
    """Coefficient-domain σ_t: out[(t·i mod 2N) fold] = sign·a[i].

    Returns (dst_index, sign) arrays over source index i; sign ∈ {+1 (0), -1 (1)}.
    """
    i = np.arange(n, dtype=np.int64)
    e = (t * i) % (2 * n)
    dst = np.where(e < n, e, e - n)
    neg = (e >= n).astype(np.int64)
    return dst.astype(np.int32), neg.astype(np.int32)
