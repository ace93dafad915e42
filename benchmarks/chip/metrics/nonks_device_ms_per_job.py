"""BSGS inner products: device time per job outside the ``keyswitch`` kernel
family (busy time minus key-switch kernel time): the ct x pt products, adds,
NTTs, automorphisms and layout copies around the rotations' key-switches."""


def read(s):
    t = s.busy_s - s.family_s.get("keyswitch", 0.0)
    return 1e3 * t / s.jobs if t > 0 else None
