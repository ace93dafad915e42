"""Pallas TPU kernels for the FHE hot spots the paper accelerates.

Each kernel package ships three files:
  kernel.py — ``pl.pallas_call`` body with explicit BlockSpec VMEM tiling; compiles
              through Mosaic for a TPU v5e at the published presets (see
              ``tests/test_tpu_compile.py``);
  ops.py    — public wrapper: builds the tables, compiles the kernel on a TPU and
              runs it in the Pallas interpreter anywhere else (``tpu.on_tpu()``);
  ref.py    — pure-jnp uint64 oracle used by tests as the ground truth.

``tpu`` holds what they share: the platform rule, the x64-free ``pallas_call``,
SMEM scalar tables, the scoped-VMEM limit and the compile-cache helper.
"""
