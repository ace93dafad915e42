"""The compiled CKKS ct×ct multiply on the fused pipeline, bit for bit
against its eager body: the cases of ``test_compiled_mul.py``'s ``ref``
matrix, with the fused key-switch kernels in the Pallas interpreter.
"""

from test_compiled_mul import CASES, check_bitexact, keysets  # noqa: F401  (keysets is a fixture)


@CASES
def test_compiled_fused_mul_is_bitexact_vs_eager(keysets, dnum, levels, rescale_after, op):
    check_bitexact(keysets, "fused", dnum, levels, rescale_after, op)
