"""Public NTT ops: jit'd wrappers over the Pallas kernel / u64 reference.

``backend``:
  * "kernel" — the Pallas four-step MXU kernel (interpreted off-TPU);
  * "ref"    — vectorised uint64 XLA path (fast on CPU; exact oracle);
  * "auto"   — kernel on TPU, ref elsewhere (keeps CPU tests fast while the
               TPU target exercises the MXU datapath).
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np

from repro.fhe.ntt import NttPlan
from repro.kernels import dispatch, tpu

from . import kernel as _k
from . import ref as _ref


@dataclasses.dataclass(frozen=True)
class KernelTables:
    """One direction's four-step tables for a limb set, on the device."""

    n1: int
    n2: int
    sc: jnp.ndarray  # (L·NSC,) uint32 SMEM scalars, layout ``kernel.limb_scalars``
    tw: jnp.ndarray  # (L, N1, N2) uint32 Montgomery twist
    v2: jnp.ndarray  # (L, NLIMB8, N2, N2) bf16
    v1: jnp.ndarray  # (L, NLIMB8, N1, N1) bf16
    t: jnp.ndarray  # (L, N1, N2) uint32 Montgomery inter-step twiddle


def scalar_table(plan: NttPlan, l: int | None = None) -> np.ndarray:
    """(l·NSC,) uint32 per-limb scalars of the first ``l`` limbs of ``plan``."""
    l = plan.num_limbs if l is None else l
    cols = [plan.qs[:l], plan.qinv_neg[:l], plan.r2[:l]] + [
        plan.c_mont[:l, s] for s in range(plan.c_mont.shape[1])
    ]
    return np.stack(cols, axis=1).astype(np.uint32).reshape(-1)


@functools.lru_cache(maxsize=64)
@dispatch.spanned("table.ntt")
def kernel_tables(plan: NttPlan, l: int, inverse: bool) -> KernelTables:
    """Device tables for the first ``l`` limbs of ``plan`` (plans hash by identity)."""
    pick = (lambda fwd, inv: dispatch.upload((inv if inverse else fwd)[:l]))
    return KernelTables(
        n1=plan.n1, n2=plan.n2,
        sc=dispatch.upload(scalar_table(plan, l)),
        tw=pick(plan.twa_mont, plan.twia_mont),
        v2=pick(plan.v2_limbs, plan.v2i_limbs),
        v1=pick(plan.v1_limbs, plan.v1i_limbs),
        t=pick(plan.t_mont, plan.ti_mont),
    )


def _run_kernel(x, plan: NttPlan, inverse: bool):
    l = x.shape[-2]
    lead = x.shape[:-2]
    tb = kernel_tables(plan, l, inverse)
    # natural order either way: coefficients as (N2, N1) tiles, slots as (N1, N2)
    tile = (tb.n1, tb.n2) if inverse else (tb.n2, tb.n1)
    xb = dispatch.upload(x, np.uint32).reshape((-1, l) + tile)
    out = _k.ntt_pallas(
        xb, tb.sc, tb.tw, tb.v2, tb.v1, tb.t, inverse=inverse, interpret=not tpu.on_tpu(),
    )
    return out.reshape(lead + (l, plan.n))


def ntt_fwd(x, plan: NttPlan, backend: str = "auto"):
    """Coefficients → NTT slots (natural order).  x: (..., l, N) uint32."""
    with dispatch.launch("ntt"):
        if tpu.resolve(backend) == "kernel":
            return _run_kernel(x, plan, inverse=False)
        return _ref.ntt_fwd_ref(x, plan)


def ntt_inv(x, plan: NttPlan, backend: str = "auto"):
    """NTT slots → coefficients.  x: (..., l, N) uint32."""
    with dispatch.launch("intt"):
        if tpu.resolve(backend) == "kernel":
            return _run_kernel(x, plan, inverse=True)
        return _ref.ntt_inv_ref(x, plan)
