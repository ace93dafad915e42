"""Fused key-switch pipeline: bit-exactness, dispatch counts, trace shape,
and simulator accounting — the kernel-level half of the paper's fused
iNTT→BConv→NTT claim."""

import collections

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hardware as H
from repro.core import planner as PL
from repro.core.simulator import lanes_deep, simulate_stream
from repro.fhe import keys as K
from repro.fhe import keyswitch as KS
from repro.fhe import params as P
from repro.fhe import poly, trace
from repro.kernels import dispatch
from repro.kernels.fusedks import ops as fops

BOUNDARY = ("STORE_WS", "LOAD_WS")


def _sig(instrs, skip=()):
    return collections.Counter((i.op, i.n, i.limbs) for i in instrs if i.op not in skip)


@pytest.fixture(scope="module", params=[1, 2, 3], ids=lambda d: f"dnum{d}")
def ks_setup(request):
    p = P.make_params(1 << 9, 5, request.param, check_security=False)
    sk = K.keygen(p, 0)
    rlk = K.relin_keygen(p, sk)
    return p, rlk


def _rand_eval(p, level, seed=3):
    rng = np.random.default_rng(seed)
    qs = np.array(p.q_primes[: level + 1], np.uint64)
    d = rng.integers(0, 1 << 31, size=(level + 1, p.n)) % qs[:, None]
    return jnp.asarray(d.astype(np.uint32))


# ---------------------------------------------------------------------------
# bit-exactness: fused Pallas pipeline vs staged u64 oracle
# ---------------------------------------------------------------------------


def test_fused_key_switch_bitexact_across_levels(ks_setup):
    p, rlk = ks_setup
    levels = sorted({p.L, min(p.L, p.alpha - 1), min(p.L, p.alpha), 0})
    for level in levels:
        d = _rand_eval(p, level, seed=11 + level)
        f0, f1 = KS.key_switch(d, p, level, rlk, backend="fused")
        r0, r1 = KS.key_switch(d, p, level, rlk, backend="ref")
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(r0))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(r1))


def test_fused_digit_region_bitexact(ks_setup):
    """The prescale→BConv→NTT→MAC region alone, before ModDown."""
    p, rlk = ks_setup
    level = p.L
    d = _rand_eval(p, level, seed=7)
    d_coeff = poly.to_coeff(d, p, poly.q_idx(p, level), "ref")
    ksk_sel = KS._select_ksk(rlk, p, level, p.beta(level))
    a0, a1 = fops.key_switch_digits(d_coeff, ksk_sel, p, level, backend="kernel")
    b0, b1 = fops.key_switch_digits(d_coeff, ksk_sel, p, level, backend="ref")
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(b0))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(b1))


def test_fused_moddown_bitexact(ks_setup):
    p, rlk = ks_setup
    level = p.L
    rng = np.random.default_rng(5)
    ext = poly.ext_idx(p, level)
    primes = np.array(poly.primes_for(p, ext), np.uint64)
    acc = rng.integers(0, 1 << 31, size=(2, len(ext), p.n)) % primes[None, :, None]
    acc0, acc1 = jnp.asarray(acc[0].astype(np.uint32)), jnp.asarray(acc[1].astype(np.uint32))
    f0, f1 = KS.mod_down_pair(acc0, acc1, p, level, backend="fused")
    r0 = KS.mod_down(acc0, p, level, backend="ref")
    r1 = KS.mod_down(acc1, p, level, backend="ref")
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(r0))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(r1))


def test_staged_backends_agree(ks_setup):
    """staged (auto stage kernels) == ref (u64 oracle stages)."""
    p, rlk = ks_setup
    d = _rand_eval(p, p.L, seed=13)
    s0, s1 = KS.key_switch(d, p, p.L, rlk, backend="staged")
    r0, r1 = KS.key_switch(d, p, p.L, rlk, backend="ref")
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(r0))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(r1))


# ---------------------------------------------------------------------------
# dispatch counts: the measurable fusion win
# ---------------------------------------------------------------------------


def test_fused_issues_fewer_dispatches(ks_setup):
    p, rlk = ks_setup
    d = _rand_eval(p, p.L, seed=2)
    with dispatch.count_dispatches() as cf:
        KS.key_switch(d, p, p.L, rlk, backend="fused")
    with dispatch.count_dispatches() as cs:
        KS.key_switch(d, p, p.L, rlk, backend="staged")
    beta = p.beta(p.L)
    # fused: shared iNTT + one fused digit launch + batched P-block iNTT +
    # one fused ModDown launch
    assert dispatch.total(cf) == 4
    assert cf["fusedks"] == 1 and cf["fused_moddown"] == 1
    # staged: 9 launches per digit (prescale, centre, BConv, correct, NTT,
    # two MACs, two accumulates) + 2×8 ModDown + shared iNTT
    assert dispatch.total(cs) == 9 * beta + 17
    assert dispatch.total(cf) < dispatch.total(cs)


# ---------------------------------------------------------------------------
# trace shape: boundary instructions & planner parity
# ---------------------------------------------------------------------------


def test_fused_stream_has_no_ws_boundaries(ks_setup):
    p, rlk = ks_setup
    d = _rand_eval(p, p.L, seed=4)
    with trace.capture_trace() as tf:
        KS.key_switch(d, p, p.L, rlk, backend="fused")
    with trace.capture_trace() as ts:
        KS.key_switch(d, p, p.L, rlk, backend="ref")
    n_f = sum(1 for i in tf if i.op in BOUNDARY)
    n_s = sum(1 for i in ts if i.op in BOUNDARY)
    beta = p.beta(p.L)
    assert n_f == 0
    assert n_s == 2 * (4 * beta + 2 * 4)  # 4 boundaries/digit + 4 per ModDown
    assert n_f < n_s
    # identical mathematical work on both streams
    assert _sig(tf) == _sig(ts, skip=BOUNDARY)


def test_planner_parity_both_pipelines(ks_setup):
    p, rlk = ks_setup
    pp = PL.PlanParams.of(p)
    for level in (p.L, p.alpha - 1):
        d = _rand_eval(p, level, seed=6)
        with trace.capture_trace() as tf:
            KS.key_switch(d, p, level, rlk, backend="fused")
        with trace.capture_trace() as ts:
            KS.key_switch(d, p, level, rlk, backend="staged")
        assert _sig(tf) == _sig(PL.key_switch(pp, level, fused=True))
        assert _sig(ts) == _sig(PL.key_switch(pp, level, fused=False))


# ---------------------------------------------------------------------------
# simulator accounting: fused_keyswitch vs the captured streams
# ---------------------------------------------------------------------------


def test_simulator_accounts_fused_stream(ks_setup):
    p, rlk = ks_setup
    d = _rand_eval(p, p.L, seed=8)
    with trace.capture_trace() as tf:
        KS.key_switch(d, p, p.L, rlk, backend="fused")
    with trace.capture_trace() as ts:
        KS.key_switch(d, p, p.L, rlk, backend="staged")
    chip = H.FLASH_FHE
    lanes = lanes_deep(chip)
    rf = simulate_stream(list(tf), chip, lanes)
    rs = simulate_stream(list(ts), chip, lanes)
    # same functional-unit work either way — fusion changes movement, not math
    for unit in ("ntt", "bconv", "modmul"):
        assert rf.unit_cycles[unit] == pytest.approx(rs.unit_cycles[unit])
    # the staged stream pays the boundary round-trips through HBM
    assert rs.hbm_bytes > rf.hbm_bytes
    assert rs.cycles >= rf.cycles
    # boundary traffic == Σ working-set bytes of the explicit records
    extra = sum(
        i.limbs * i.n * chip.word_bytes for i in ts if i.op in BOUNDARY
    )
    assert rs.hbm_bytes - rf.hbm_bytes == pytest.approx(extra)


# ---------------------------------------------------------------------------
# the fused shape rule: digits over the scoped VMEM limit are refused loudly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preset", ["resnet20", "packed_bootstrap"])
def test_fused_tables_refuse_shapes_over_vmem(preset):
    p = P.workload_params(preset)  # dnum=1: one 42- or 58-row digit at N=2^16
    for build in (fops.ks_tables, fops.moddown_tables):
        with pytest.raises(ValueError, match="backend='staged'"):
            build(p, p.L)
