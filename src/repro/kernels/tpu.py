"""What every Pallas kernel of this package shares: the platform rule and the call.

* ``on_tpu()`` is the one place a kernel wrapper asks where it runs.  On a TPU
  the kernels compile through Mosaic; anywhere else they run in the Pallas
  interpreter (``interpret=not on_tpu()``), so CPU tests drive the same
  program.  ``resolve("auto")`` is "kernel" on a TPU and the uint64 oracle
  ("ref") elsewhere.
* ``call`` traces ``pl.pallas_call`` with 64-bit types off, named after the
  jitted wrapper that calls it (``fused_ks_pallas``, ``mulmod_pallas``, ...;
  ``ntt_pallas`` and ``intt_pallas`` for the two NTT directions), so a
  profiler trace attributes the kernel by its op name inside a larger
  compiled program as well as by the wrapper's own module when eager.  ``repro.fhe``
  enables x64 process-wide for its uint64 oracle, and under x64 the grid
  indices and index-map results become i64, which Mosaic cannot lower.  Every
  kernel here is uint32/bf16 already, so nothing else changes.
* No kernel sets ``vmem_limit_bytes``: at every preset that runs, the TPU
  compiler fits each kernel's double-buffered blocks and scratch in the v5e
  default scoped VMEM limit, ``VMEM_SCOPED_LIMIT`` (16 MiB).  The figures it
  reports are in each kernel's docstring.
* ``smem()`` places a whole 1-D uint32 table of per-limb scalars (moduli,
  Montgomery constants, BConv weights) in scalar memory; kernels read it at
  ``program_id``-derived offsets.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_SCOPED_LIMIT = 16 * 2**20


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(backend: str) -> str:
    """Map "auto" to "kernel" on a TPU and "ref" elsewhere."""
    if backend == "auto":
        return "kernel" if on_tpu() else "ref"
    return backend


def smem() -> pl.BlockSpec:
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def call(kernel, args, *, name: str, grid, in_specs, out_specs, out_shape, interpret: bool):
    """``pl.pallas_call(kernel, ...)(*args)`` traced with 32-bit index types,
    under the stable kernel ``name`` (after the wrapper, as above)."""
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, interpret=interpret, name=name,
        )(*args)
