"""Homomorphic linear transforms via the BSGS diagonal method.

M·v = Σ_g rot_{g·n1}( Σ_b  rot_{-g·n1}(diag_{g·n1+b}(M)) ∘ rot_b(v) )

Baby rotations rot_b(v) are shared across giants, so an n×n dense transform
costs ≈ 2√n key-switched rotations + n plaintext multiplies — the dominant
workload of CoeffToSlot/SlotToCoeff in bootstrapping (paper §3.3: rotation-
heavy deep pipelines).

The diagonals' encodings stay on the device between applications
(``FheContext.diag_cache``, a ``DiagCache``), as a server holds its weight
matrix encoded: an application encodes only what the cache misses, and every
ct×pt product, add, rotation and rescale still runs.

Execution policy comes from ``repro.fhe.context.FheContext`` —
``ctx.apply_bsgs``/``ctx.plan_matrix`` are the primary API, and
``plan_matrix`` picks the baby-step count n1 from a hoisting-aware cost model
(under hoisting, baby steps are nearly free — see ``choose_n1``).  The
deprecated module-level free functions taking ``backend=``/``hoisting=``
kwargs were retired (docs/context_api.md); only the pure planning helpers
remain at module level.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import math
from typing import NamedTuple

import jax
import numpy as np
from jax import lax

from repro.kernels import dispatch
from repro.kernels.ntt import ops as ntt_ops

from . import encoder, keyswitch, ops, poly, trace
from .params import CkksParams


@dataclasses.dataclass
class BsgsPlan:
    n1: int  # baby-step count
    diags: dict[int, np.ndarray]  # d → diag_d(M) (length n complex)
    _rot_cache: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False
    )

    def baby_steps(self) -> tuple[int, ...]:
        """Sorted non-zero baby rotations {d mod n1} — one hoisting group."""
        hit = self._rot_cache.get("babies")
        if hit is None:
            hit = tuple(sorted({d % self.n1 for d in self.diags} - {0}))
            self._rot_cache["babies"] = hit
        return hit

    def giant_steps(self) -> tuple[int, ...]:
        """Sorted non-zero giant rotations {(d // n1) · n1}."""
        hit = self._rot_cache.get("giants")
        if hit is None:
            hit = tuple(sorted({(d // self.n1) * self.n1 for d in self.diags} - {0}))
            self._rot_cache["giants"] = hit
        return hit

    def rotations(self) -> frozenset[int]:
        """Slot rotations whose Galois keys the transform needs (cached —
        keygen and every apply call share one computation)."""
        hit = self._rot_cache.get("all")
        if hit is None:
            hit = frozenset(self.baby_steps()) | frozenset(self.giant_steps())
            self._rot_cache["all"] = hit
        return hit


# ---------------------------------------------------------------------------
# BSGS planning: the hoisting-aware n1 cost model
# ---------------------------------------------------------------------------


def bsgs_rotation_cost(diag_indices, n1: int, params: CkksParams, level: int,
                       hoisted: bool) -> float:
    """Key-switch cost of a BSGS split, in limb-NTT-equivalents.

    The model counts the (i)NTT limb-transforms each rotation path issues —
    the planner's own instruction shapes, collapsed to the dominant unit:

      * a full key-switched rotation (unhoisted baby, or any giant — giants
        act on *different* partial sums, so they can never share a ModUp):
        ModUp (1 iNTT over nq limbs + β forward NTTs over m = nq+α limbs)
        plus two ModDown tails (each α iNTT + nq NTT limbs);
      * a hoisted baby: only the two ModDown tails — the group's single ModUp
        is charged once.

    Plaintext multiplies are diagonal-count work, identical for every n1, so
    they cancel out of the argmin and are omitted.
    """
    nq = level + 1
    alpha = params.alpha
    beta = params.beta(level)
    m = nq + alpha
    full = nq + beta * m + 2 * (alpha + nq)  # ModUp + 2× ModDown
    baby_hoisted = 2 * (alpha + nq)  # MAC rides the exit; ModDown dominates
    babies = len({d % n1 for d in diag_indices} - {0})
    giants = len({(d // n1) * n1 for d in diag_indices} - {0})
    if not hoisted:
        return (babies + giants) * full
    modup_once = nq + beta * m if babies else 0.0
    return modup_once + babies * baby_hoisted + giants * full


def choose_n1(diag_indices, params: CkksParams, level: int, hoisted: bool) -> int:
    """Baby-step count minimising the rotation cost model over powers of two.

    Without hoisting the optimum sits at the classic ≈ √(#diags) balance
    point.  With hoisting, baby steps cost only a ModDown each (the ModUp is
    shared), so the optimum shifts toward more babies / fewer giants — e.g.
    the radix-32 CtS stage (63 diagonals) moves from n1 = 8 to n1 = 16, the
    value ``benchmarks/hoisting_bench.py`` exploits.
    """
    diag_indices = tuple(diag_indices)
    if not diag_indices:
        return 1
    top = 1 << max(0, (max(diag_indices)).bit_length())
    candidates = []
    n1 = 1
    while n1 <= max(2, top):
        candidates.append(n1)
        n1 <<= 1
    return min(
        candidates,
        key=lambda c: (bsgs_rotation_cost(diag_indices, c, params, level, hoisted), c),
    )


def plan_matrix(m: np.ndarray, n1: int | None = None, tol: float = 0.0,
                params: CkksParams | None = None, level: int | None = None,
                hoisting: bool = False) -> BsgsPlan:
    """Extract (optionally sparse) diagonals of an n×n matrix for BSGS.

    n1 selection, in priority order: an explicit ``n1``; the hoisting-aware
    cost model when ``params`` is given (``choose_n1`` — pass
    ``hoisting=True`` when the transform will run under a hoisting policy);
    otherwise the classic ≈ √n power of two.
    """
    n = m.shape[0]
    assert m.shape == (n, n)
    idx = np.arange(n)
    diags = {}
    mx = np.abs(m).max() or 1.0
    for d in range(n):
        u = m[idx, (idx + d) % n]
        if tol == 0.0 or np.abs(u).max() > tol * mx:
            diags[int(d)] = u.astype(np.complex128)
    if n1 is None:
        if params is not None:
            n1 = choose_n1(diags, params, params.L if level is None else level, hoisting)
        else:
            n1 = max(1, 1 << int(round(math.log2(math.sqrt(n)))))  # ≈ √n, power of two
    return BsgsPlan(n1=n1, diags=diags)


def plan_diags(diags: dict[int, np.ndarray], params: CkksParams, level: int | None = None,
               hoisting: bool = False, n1: int | None = None) -> BsgsPlan:
    """BSGS plan straight from a diagonal dict (for banded transforms whose
    dense matrix is too large to materialise), n1 from the cost model."""
    if n1 is None:
        n1 = choose_n1(diags, params, params.L if level is None else level, hoisting)
    return BsgsPlan(n1=n1, diags=dict(diags))


# ---------------------------------------------------------------------------
# server-resident encoded diagonals
# ---------------------------------------------------------------------------

# One lstm.matvec job applies 2 × 128 diagonals at 14 limbs of 2^16 words:
# 0.94 GB resident.  The bound leaves room for that and evicts the least
# recently used encoding beyond it.
DIAG_CACHE_BYTES = 2 * 2**30


class DiagCache:
    """Device-resident eval-domain encodings of BSGS diagonals, LRU-bounded by bytes.

    The key is the diagonal's content (a digest of its dtype, shape and
    bytes), its pre-rotation, and the level, scale and limb set of the
    encoding, plus the default device: never the array's identity, so a
    changed diagonal misses and is encoded afresh.  An entry larger than the
    whole budget is not kept.
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self.nbytes = 0
        self.hits = 0
        self.misses = 0

    def get(self, key):
        data = self._entries.get(key)
        if data is None:
            self.misses += 1
        else:
            self._entries.move_to_end(key)
            self.hits += 1
        return data

    def put(self, key, data) -> None:
        size = int(data.nbytes)
        if key in self._entries or size > self.max_bytes:  # a repeated diagonal, or too big
            return
        while self.nbytes + size > self.max_bytes:
            _, old = self._entries.popitem(last=False)
            self.nbytes -= int(old.nbytes)
        self._entries[key] = data
        self.nbytes += size

    def stats(self) -> dict:
        """{"entries", "bytes", "hits", "misses"}: what is resident now, and
        the lookups since the process started."""
        return {"entries": len(self._entries), "bytes": self.nbytes,
                "hits": self.hits, "misses": self.misses}


def _digest(v: np.ndarray) -> bytes:
    v = np.ascontiguousarray(v)
    h = hashlib.sha1(f"{v.dtype.str}{v.shape}".encode())
    h.update(v)
    return h.digest()


@dispatch.spanned("table.diag")
def _encode_batch(ctx, values: list[np.ndarray], level: int, scale: float):
    """Slot vectors → (count, level+1, N) eval-domain encodings: one upload, one NTT."""
    params = ctx.params
    primes = params.q_primes[: level + 1]
    coeffs = np.stack([encoder.encode(v, params.n, scale, primes) for v in values])
    plan_q = poly.plan_for(params, poly.q_idx(params, level))
    return ntt_ops.ntt_fwd(dispatch.upload(coeffs, np.uint32), plan_q, ctx.stage)


def _encoded_diags(ctx, plan: BsgsPlan, ds: list[int], rot: int, level: int,
                   scale: float) -> list[ops.Plaintext]:
    """Plaintexts of the diagonals ``ds`` pre-rotated by ``rot`` slots.

    Hits come from ``ctx.diag_cache``; the misses are encoded together, one upload
    and one NTT, under a ``table.diag`` span.
    """
    params = ctx.params
    primes = params.q_primes[: level + 1]
    keys = [(_digest(plan.diags[d]), rot, level, float(scale), primes,
             dispatch.default_device()) for d in ds]
    found = [ctx.diag_cache.get(k) for k in keys]
    miss = [i for i, f in enumerate(found) if f is None]
    if miss:
        data = _encode_batch(ctx, [np.roll(plan.diags[ds[i]], rot) for i in miss], level, scale)
        for j, i in enumerate(miss):
            found[i] = lax.index_in_dim(data, j, keepdims=False)
            ctx.diag_cache.put(keys[i], found[i])
    return [ops.Plaintext(data=f, level=level, scale=scale) for f in found]


# ---------------------------------------------------------------------------
# context implementations
# ---------------------------------------------------------------------------


class BsgsShape(NamedTuple):
    """What a compiled BSGS application depends on besides its operands."""

    n1: int
    groups: tuple[tuple[int, tuple[int, ...]], ...]  # (giant g, its diagonals g·n1 + b), by g
    babies: tuple[int, ...]  # baby rotations b that key-switch (b ≢ 0 mod slots)
    giants: tuple[int, ...]  # giants g whose partial sum rotates by g·n1 (≢ 0 mod slots)
    hoisted: bool  # the babies share one ModUp


def _galois_element(params: CkksParams, r: int) -> int:
    return pow(5, r % params.slots, 2 * params.n)


def _bsgs_operands(ctx, ct: ops.Ciphertext, plan: BsgsPlan, scale: float):
    """The host prelude of ``_apply_bsgs``: the application's static shape,
    its encoded diagonals (in the order of ``shape.groups``, through
    ``ctx.diag_cache``) and the pre-permuted Galois keys of its babies and
    rotated giants (``keyswitch.hoisted_ksk``)."""
    params = ctx.params
    keys = ctx.require_keys()
    hoisting = ctx.policy.hoisting
    lv = ct.level
    by_giant: dict[int, list[int]] = {}
    for d in plan.diags:
        by_giant.setdefault(d // plan.n1, []).append(d)
    shape = BsgsShape(
        n1=plan.n1,
        groups=tuple((g, tuple(ds)) for g, ds in sorted(by_giant.items())),
        babies=tuple(b for b in plan.baby_steps() if b % params.slots),
        giants=tuple(g for g in sorted(by_giant) if (g * plan.n1) % params.slots),
        hoisted=hoisting == "always" or (hoisting == "auto" and len(plan.baby_steps()) >= 2),
    )
    pts = [pt for g, ds in shape.groups
           for pt in _encoded_diags(ctx, plan, ds, g * plan.n1, lv, scale)]  # pre-rotated diagonals
    ksk = lambda r: keyswitch.hoisted_ksk(params, keys, _galois_element(params, r), lv)
    baby_ksks = [ksk(b) for b in shape.babies]
    giant_ksks = [ksk(g * plan.n1) for g in shape.giants]
    return shape, pts, baby_ksks, giant_ksks


def _bsgs_body(ctx, ct: ops.Ciphertext, shape: BsgsShape, pts: list[ops.Plaintext],
               baby_ksks, giant_ksks) -> ops.Ciphertext:
    """Σ_g rot_{g·n1}(Σ_b pt_{g·n1+b} ∘ rot_b(ct)), then one rescale, over the
    operands ``_bsgs_operands`` resolved: the eager body of the op, which
    ``_bsgs_program`` compiles."""
    params = ctx.params
    lv = ct.level
    baby_ts = [_galois_element(params, b) for b in shape.babies]
    if shape.hoisted:
        rotated = ops._galois_group(ctx, ct, baby_ts, baby_ksks)
    else:
        rotated = [ops._galois_with(ctx, ct, t, k) for t, k in zip(baby_ts, baby_ksks)]
    babies = dict(zip(shape.babies, rotated))  # b = 0 and b ≡ 0 mod slots: ct itself

    pts, giant_ksks = iter(pts), dict(zip(shape.giants, giant_ksks))
    total: ops.Ciphertext | None = None
    for g, ds in shape.groups:
        for _ in ds:
            trace.record("LOAD_PT", params.n, lv + 1)
        acc: ops.Ciphertext | None = None
        for d in ds:
            term = ops._mul_plain(ctx, babies.get(d % shape.n1, ct), next(pts), rescale_after=False)
            acc = term if acc is None else ops._add(ctx, acc, term)
        if g in giant_ksks:
            t = _galois_element(params, g * shape.n1)
            acc = ops._galois_with(ctx, acc, t, giant_ksks[g])
        total = acc if total is None else ops._add(ctx, total, acc)

    return ops._rescale(ctx, total)


def _apply_bsgs(ctx, ct: ops.Ciphertext, plan: BsgsPlan,
                scale: float | None = None) -> ops.Ciphertext:
    """Homomorphic M·v.  Consumes one level (single rescale at the end).

    The policy's hoisting mode controls the baby-step rotations (the dominant
    key-switch cost): "auto"/"always" share ONE ModUp across the whole baby
    group (Halevi–Shoup; "auto" falls back to per-rotation key-switching when
    the group has fewer than two rotations), "never" key-switches each baby
    separately.  All modes are bit-exact against each other.  Giant-step
    rotations apply to *different* ciphertexts (the per-group partial sums),
    so they cannot share a ModUp and always run the standard path.

    One device dispatch: ``_bsgs_body`` compiled once per static shape
    (``_bsgs_program``), bit-exact against running it eagerly; the diagonals
    and keys are arguments of every call.  Inside an enclosing trace (a
    caller's ``jax.jit``) the body runs inline instead.
    """
    params = ctx.params
    scale = params.scale if scale is None else scale
    lv = ct.level
    shape, pts, baby_ksks, giant_ksks = _bsgs_operands(ctx, ct, plan, scale)
    xs = (ct.c0, ct.c1, *(pt.data for pt in pts), *baby_ksks, *giant_ksks)
    if any(isinstance(x, jax.core.Tracer) for x in xs):
        return _bsgs_body(ctx, ct, shape, pts, baby_ksks, giant_ksks)
    prog = _bsgs_program(params, ctx.policy, lv, shape, float(scale),
                         tuple(k.shape for k in (*baby_ksks, *giant_ksks)),
                         dispatch.default_device(), ops._rescale)
    c0, c1 = prog(*xs)
    return ops.Ciphertext(c0=c0, c1=c1, level=lv - 1,
                          scale=ct.scale * scale / int(params.q_primes[lv]))


@functools.lru_cache(maxsize=64)
@dispatch.spanned("table.bsgs_program")
def _bsgs_program(params: CkksParams, policy, level: int, shape: BsgsShape, scale: float,
                  key_shapes, device, rescale) -> ops._Program:
    """``_bsgs_body`` compiled under a key-less context of ``params`` and
    ``policy`` at ``level``: never keyed on the diagonals' values or on the
    key set, so one program serves any weights and keys of this shape.
    ``rescale`` is the ``ops._rescale`` the body traces, looked up per call:
    a replaced one (a fault planted under the benchmark's check) builds a
    program of its own instead of finding the one traced before."""
    from .context import FheContext  # context imports this module

    ctx = FheContext(params=params, policy=policy)
    n_pts = sum(len(ds) for _, ds in shape.groups)
    n_babies = len(shape.babies)

    def body(c0, c1, *rest):
        pts = [ops.Plaintext(x, level, scale) for x in rest[:n_pts]]
        ksks = rest[n_pts:]
        out = _bsgs_body(ctx, ops.Ciphertext(c0, c1, level, 1.0), shape, pts,
                         ksks[:n_babies], ksks[n_babies:])
        return out.c0, out.c1

    limbs = (level + 1, params.n)
    return ops._compile_body("ckks_bsgs", body, [limbs] * (2 + n_pts) + list(key_shapes))


def bsgs_program_stats() -> dict:
    """{"programs": cached, "calls": compiled applications run, "builds": programs traced}."""
    info = _bsgs_program.cache_info()
    return {"programs": info.currsize, "calls": info.hits + info.misses, "builds": info.misses}


def _real_part(ctx, ct: ops.Ciphertext) -> ops.Ciphertext:
    """(ct + conj(ct)) / 2 — scale the ½ into the bookkeeping (free)."""
    s = ops._add(ctx, ct, ops._conjugate(ctx, ct, ctx.require_keys()))
    return ops.Ciphertext(s.c0, s.c1, s.level, s.scale * 2.0)


def _imag_part(ctx, ct: ops.Ciphertext) -> ops.Ciphertext:
    """(ct − conj(ct)) / 2i — fold 1/(2i) into a plaintext mul."""
    d = ops._sub(ctx, ct, ops._conjugate(ctx, ct, ctx.require_keys()))
    return ops._mul_const(ctx, d, -0.5j, rescale_after=True)

