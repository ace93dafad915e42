"""HE op layer: device op executions in the traced window per job.

Counts what ran on the device, not what Python dispatched, so it reads the
same whether or not the ops are jitted; a fused or batched program lowers it.
"""


def read(s):
    return s.device_ops / s.jobs
