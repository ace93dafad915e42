"""Key-switch layer: summed device time of the ``keyswitch`` kernel family per job."""


def read(s):
    t = s.family_s.get("keyswitch", 0.0)
    return 1e3 * t / s.jobs if t > 0 else None
