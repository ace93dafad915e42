"""Kernel-dispatch seam: launch counts and the program's spans on the profiler clock.

Counting.  Every public op wrapper (ntt, bconv, modops, fusedks, hoistrot)
opens ``with launch(op):`` around its body, which records one dispatch per
device-kernel launch it issues.  The fused key-switch pipeline's whole point
is collapsing the staged per-digit launch train (prescale, BConv, NTT, two
MACs, two accumulates — each a separate launch whose intermediates round-trip
through HBM-equivalent buffers) into one `pallas_call`; ``count_dispatches``
lets benchmarks and tests *measure* that collapse instead of asserting it.

Spans.  Each layer boundary writes a ``jax.profiler.TraceAnnotation`` with a
constant name, so it lands in the profiler's host plane on the same clock as
the device planes.  An annotation records only while a profiler trace runs;
otherwise it costs a fraction of a microsecond.  Spans of one call nest by
time on the calling thread:

* ``fhe.<method>``: one public ``FheContext`` method, end to end on the host;
* ``ks.<stage>``: one key-switch stage of ``repro.fhe.keyswitch``
  (``accumulate``, ``moddown``, ``modup``, ``mac``, ``moddown_group``, and
  ``permute``, the rotation epilogue);
* ``kernel.<op>``: the host side of one kernel launch (``launch``): table
  lookups, reshapes, uploads of operands still on the host, and the jitted
  kernel call;
* ``h2d``: one host-to-device transfer of a numpy or Python value (``upload``);
* ``table.<name>``: one build of a cached table, inside the ``lru_cache``d
  function that builds it, so it fires only on a cache miss; ``table.diag``
  is one batch of BSGS diagonals encoded on a miss of ``FheContext.diag_cache``.

Counts and spans happen at Python call time, so inside an enclosing
`jax.jit` both fire at trace time only (once per compilation): the counts are
then the static dispatch count of the compiled program, and the spans cover
tracing, not execution.  A CKKS ct×ct multiply is such a program
(``repro.fhe.ops._mul_program``): its spans fire while it is built, under a
``table.mul_program`` span, and each call ``replay``s the counts of that build.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

KERNEL_OPS = (
    "mulmod", "addmod", "submod", "ntt", "intt", "bconv",
    "fusedks", "fused_moddown", "hoistmodup", "hoistmac",
)
_KERNEL_SPANS = {op: f"kernel.{op}" for op in KERNEL_OPS}

_COUNTS: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "kernel_dispatch_counts", default=None
)


def spanned(name: str):
    """Decorator: every call of the function runs under the host span ``name``.
    Under ``functools.lru_cache`` it fires on cache misses only."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with TraceAnnotation(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def launch(op: str) -> TraceAnnotation:
    """Count one dispatch of kernel ``op`` (one of ``KERNEL_OPS``) when a
    counter is active; returns its ``kernel.<op>`` span for ``with``."""
    c = _COUNTS.get()
    if c is not None:
        c[op] = c.get(op, 0) + 1
    return TraceAnnotation(_KERNEL_SPANS[op])


def upload(x, dtype=None):
    """``x`` on the default device as ``dtype`` (default: its own).  A numpy
    or Python value is one explicit ``jax.device_put`` under an ``h2d`` span,
    made at once even inside a ``jax.jit`` trace, so a cached table built
    there holds a device array and not a tracer; a value already on a device
    is only cast."""
    if isinstance(x, jax.Array):
        return jnp.asarray(x, dtype)
    with TraceAnnotation("h2d"), jax.ensure_compile_time_eval():
        return jax.device_put(np.asarray(x, dtype))


def default_device():
    """The device set by an enclosing ``jax.default_device`` (None outside one):
    part of the key of a cache of device arrays, so a table built under a
    host-CPU oracle block is not handed to the accelerator's kernels."""
    return jax.config.jax_default_device


@contextlib.contextmanager
def count_dispatches():
    """Collect {op: dispatch_count} for every kernel launched in the block."""
    token = _COUNTS.set({})
    try:
        yield _COUNTS.get()
    finally:
        _COUNTS.reset(token)


def replay(counts: dict) -> None:
    """Add ``counts`` to the active counter, if any: a compiled program counts
    the launches its trace made on every call."""
    c = _COUNTS.get()
    if c is not None:
        for op, n in counts.items():
            c[op] = c.get(op, 0) + n


def total(counts: dict) -> int:
    return sum(counts.values())
