"""Poly kernels: summed device time of the ``poly`` kernel family (NTT, BConv,
pointwise modular ops) per job."""


def read(s):
    t = s.family_s.get("poly", 0.0)
    return 1e3 * t / s.jobs if t > 0 else None
