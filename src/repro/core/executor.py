"""shard_map executor: affiliation = device group (DESIGN.md §2 mapping).

The paper's scheduler runs one shallow FHE job per cluster affiliation; on the
TPU mesh each affiliation maps to a device group along the `data` axis, and up
to 8 shallow jobs execute *numerically in parallel* under one jitted
shard_map program.  On CPU (1 device) the same program degrades gracefully.

The executable program is the real CKKS pipeline (pointwise Montgomery ops,
(i)NTT, BConv key-switch) traced through repro.fhe — scales/levels are static,
so the whole multi-job step jits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.fhe import ops
from repro.fhe.context import ExecPolicy, FheContext
from repro.fhe.keys import KeySet
from repro.fhe.params import CkksParams


def affiliation_mesh(n_groups: int | None = None) -> Mesh:
    """1-D mesh over available devices: one group per affiliation."""
    devs = np.array(jax.devices())
    if n_groups is None:
        n_groups = len(devs)
    assert len(devs) % n_groups == 0
    return Mesh(devs[: n_groups].reshape(n_groups), ("aff",))


def _stack_jobs(cts: list[ops.Ciphertext]):
    return (
        jnp.stack([c.c0 for c in cts]),
        jnp.stack([c.c1 for c in cts]),
    )


def parallel_shallow_mul(
    params: CkksParams,
    keys: KeySet,
    pairs: list[tuple[ops.Ciphertext, ops.Ciphertext]],
    mesh: Mesh | None = None,
) -> list[ops.Ciphertext]:
    """Execute one homomorphic multiplication per job, jobs sharded over
    affiliations (the paper's multi-job scheduling, run for real)."""
    if mesh is None:
        mesh = affiliation_mesh()
    n_jobs = len(pairs)
    n_aff = mesh.devices.size
    assert n_jobs % n_aff == 0, f"{n_jobs} jobs must tile {n_aff} affiliations"
    level = pairs[0][0].level
    scale = pairs[0][0].scale
    for a, b in pairs:
        assert a.level == b.level == level and a.scale == b.scale == scale

    a0, a1 = _stack_jobs([p[0] for p in pairs])
    b0, b1 = _stack_jobs([p[1] for p in pairs])
    rlk = keys.rlk.k
    ctx = FheContext(params=params, keys=keys, policy=ExecPolicy(backend="ref"))

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("aff"), P("aff"), P("aff"), P("aff"), P()),
        out_specs=(P("aff"), P("aff")),
        check_vma=False,
    )
    def run(a0s, a1s, b0s, b1s, rlk_arr):
        outs0, outs1 = [], []
        local = a0s.shape[0]
        for j in range(local):  # static per-affiliation job loop
            cta = ops.Ciphertext(a0s[j], a1s[j], level, scale)
            ctb = ops.Ciphertext(b0s[j], b1s[j], level, scale)
            kk = keys.rlk.__class__(k=rlk_arr)
            out = ctx.mul(cta, ctb, rlk=kk, rescale_after=True)
            outs0.append(out.c0)
            outs1.append(out.c1)
        return jnp.stack(outs0), jnp.stack(outs1)

    o0, o1 = jax.jit(run)(a0, a1, b0, b1, rlk)
    out_scale = scale * scale / float(params.q_primes[level])
    return [
        ops.Ciphertext(o0[j], o1[j], level - 1, out_scale) for j in range(n_jobs)
    ]


def lower_multi_job_step(params: CkksParams, keys: KeySet, mesh: Mesh, jobs_per_aff: int = 1):
    """Lower (without executing) the multi-job step for dry-run analysis."""
    n_aff = mesh.devices.size
    n_jobs = n_aff * jobs_per_aff
    shape = (n_jobs, params.L + 1, params.n)
    spec = jax.ShapeDtypeStruct(shape, jnp.uint32)

    level = params.L
    scale = params.scale
    rlk = keys.rlk.k
    ctx = FheContext(params=params, keys=keys, policy=ExecPolicy(backend="ref"))

    def run(a0, a1, b0, b1):
        def body(a0s, a1s, b0s, b1s):
            outs0, outs1 = [], []
            for j in range(jobs_per_aff):
                cta = ops.Ciphertext(a0s[j], a1s[j], level, scale)
                ctb = ops.Ciphertext(b0s[j], b1s[j], level, scale)
                out = ctx.mul(cta, ctb, rescale_after=True)
                outs0.append(out.c0)
                outs1.append(out.c1)
            return jnp.stack(outs0), jnp.stack(outs1)

        f = jax.shard_map(body, mesh=mesh, in_specs=(P("aff"),) * 4,
                          out_specs=(P("aff"), P("aff")), check_vma=False)
        return f(a0, a1, b0, b1)

    return jax.jit(run).lower(spec, spec, spec, spec)
