"""repro: FLASH-FHE on TPU — heterogeneous JAX framework for mixed FHE workloads.

Layout:
  repro.fhe        CKKS scheme (modmath/rns/ntt/keys/ops/keyswitch/bootstrap)
  repro.kernels    Pallas TPU kernels (+ jit wrappers + pure-jnp oracles)
  repro.core       the paper's contribution: heterogeneous clusters + multi-job scheduler
  repro.serve      discrete-event multi-tenant serving (§4.2 online policy, traffic, SLOs)
  repro.obs        observability: span tracing, Perfetto export, metrics, perf history
"""

__version__ = "1.0.0"
