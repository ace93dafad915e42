"""Shared by the chip benchmark's CPU tests: where its files are, and the
cells rebuilt at a small ring for the Pallas interpreter."""

from __future__ import annotations

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_cell(name: str, n: int = 1 << 10, backend: str = "kernel", base: Path | None = None):
    """Cell ``name`` at ring degree ``n`` with the preset's L, dnum and plain
    modulus, every kernel in the Pallas interpreter (``backend="kernel"``).
    ``base`` is a directory laid out like ``benchmarks/chip`` with its own
    ``BENCHMARK.json``."""
    import pytest
    from chipbench import cell as C

    from repro.fhe import params as P
    from repro.fhe.context import ExecPolicy

    full = C.build_params

    def build(cfg):
        p = full(cfg)
        return P.make_params(n, p.L, p.dnum, security_bits=p.security_bits, check_security=False,
                             plain_modulus=p.plain_modulus)

    where = {} if base is None else {"base": base, "bench_path": base / "BENCHMARK.json"}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(C, "build_params", build)
        return C.Cell(name, policy=ExecPolicy(backend=backend), **where)


FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def plant(fault: str, monkeypatch) -> None:
    """Break the timed path underneath the harness.

    * ``state_unchanged``: every rescale drops the last limb and relabels the
      scale but leaves the message undivided, as if the step had not run;
    * ``half_batch``: every modular add computes only the first half of its
      limbs and passes the rest of its first operand through;
    * ``answer_altered``: every rescale's output has one word of c0 changed.
    """
    import jax.numpy as jnp

    from repro.fhe import ops
    from repro.kernels.modops import ops as mo

    if fault == "state_unchanged":
        def rescale(ctx, ct):
            lv = ct.level
            q = float(ctx.params.q_primes[lv])
            return ops.Ciphertext(ct.c0[:lv], ct.c1[:lv], lv - 1, ct.scale / q)
        monkeypatch.setattr(ops, "_rescale", rescale)
    elif fault == "half_batch":
        full = mo.pointwise_addmod

        def addmod(a, b, qs, backend="auto"):
            out = full(a, b, qs, backend=backend)
            h = out.shape[-2] // 2
            return jnp.concatenate([out[..., :h, :], jnp.asarray(a)[..., h:, :]], axis=-2)
        monkeypatch.setattr(mo, "pointwise_addmod", addmod)
    elif fault == "answer_altered":
        full = ops._rescale

        def rescale(ctx, ct):
            out = full(ctx, ct)
            return ops.Ciphertext(out.c0.at[0, 0].add(1), out.c1, out.level, out.scale)
        monkeypatch.setattr(ops, "_rescale", rescale)
    else:
        raise ValueError(fault)


def run_once(cell, seed: int) -> dict:
    """One run of ``cell`` with a window of one job, as ``run.py`` drives it."""
    import time

    from chipbench import cell as C

    return C.run(cell, seed, 1e-3, False, time.perf_counter(), "cpu", log=lambda rec: None)


def control_check(cell, seed: int) -> dict:
    """The control's reading: one job's outputs with their words carried in float32."""
    from chipbench import cell as C
    from chipbench.control import float32_words

    envs, pool_vals, pt_vals = cell.inputs(seed)
    outs = [cell.job(envs, 0)[0]]
    return C.check(cell, outs, pool_vals, pt_vals, seed, transform=float32_words)
