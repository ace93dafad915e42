"""Key-switch kernels: the least time the job's key-switches could take at peak
HBM bandwidth (their minimum bytes, ``yardstick.ks_min_bytes``) over the device
time of the ``keyswitch`` family.  A memory bound only: the v5e publishes no
peak for 32-bit modular multiplication, so no compute bound is taken."""


def read(s):
    t = s.family_s.get("keyswitch", 0.0)
    if t <= 0 or s.ks_bytes_per_job <= 0:
        return None
    return 100.0 * (s.ks_bytes_per_job * s.jobs / s.peaks["hbm_bytes_per_s"]) / t
