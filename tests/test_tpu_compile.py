"""The main-path Pallas kernels compile for a TPU v5e at published shapes.

Each case lowers one kernel with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology — nothing is attached and nothing runs — at the
``dblookup`` (N=2^14, 9 q limbs + 3 special) or ``lstm`` (N=2^16, 21 extended
limbs) top-level shape, and asserts the compiled module holds a Mosaic kernel
(``tpu_custom_call``).  This catches what interpret mode cannot: block shapes
off the (8, 128) tiling, MXU operand types, scoped-VMEM overflow, and i64
index maps under ``jax_enable_x64``.  A last test holds the fused kernels'
VMEM shape rule (``fusedks.kernel.fused_vmem_bytes``) to the compiler on both
sides of the 16 MiB limit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fhe import params as P
from repro.fhe import poly
from repro.fhe.ntt import NDIAG, NLIMB8, fourstep_split
from repro.kernels import tpu
from repro.kernels.bconv import kernel as bconv_k
from repro.kernels.fusedks import kernel as fused_k
from repro.kernels.fusedks import ops as fused_ops
from repro.kernels.hoistrot import kernel as hoist_k
from repro.kernels.modops import kernel as modops_k
from repro.kernels.ntt import kernel as ntt_k
from repro.kernels.ntt import ops as ntt_ops

PRESETS = ("dblookup", "lstm")
ROTATIONS = 4  # a rotate_hoisted_group over 1..4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def _ntt(p):
    ext = poly.ext_idx(p, p.L)
    m = len(ext)
    cases = []
    for inverse in (False, True):
        tb = ntt_ops.kernel_tables(poly.plan_for(p, ext), m, inverse)
        tile = (tb.n1, tb.n2) if inverse else (tb.n2, tb.n1)
        cases.append((ntt_k.ntt_pallas, (_u32(2, m, *tile), tb.sc, tb.tw, tb.v2, tb.v1, tb.t),
                      dict(inverse=inverse)))
    return cases


def _bconv(p):
    m8 = -(-len(poly.ext_idx(p, p.L)) // 8) * 8
    k8 = -(-p.alpha // 8) * 8
    wl = jax.ShapeDtypeStruct((NLIMB8, m8, k8), jnp.bfloat16)
    return [(bconv_k.bconv_pallas, (_u32(k8, p.n), wl, _u32(NDIAG, m8, 1), _u32(m8, 1),
                                    _u32(m8, 1)), {})]


def _modops(p):
    l = p.L + 1
    a = _u32(2, l, p.n)  # a ciphertext's two components over the top-level limbs
    return [(modops_k.mulmod_pallas, (a, a, _u32(l), _u32(l), _u32(l)), {}),
            (modops_k.addmod_pallas, (a, a, _u32(l)), {}),
            (modops_k.submod_pallas, (a, a, _u32(l)), {})]


def _fused_ks(p):
    tb = fused_ops.ks_tables(p, p.L)
    nt = tb.ntt
    return [(fused_k.fused_ks_pallas,
             (_u32(tb.beta, tb.k, nt.n2, nt.n1), nt.sc, tb.dsc, tb.wm, nt.tw, nt.v2, nt.v1,
              nt.t, _u32(tb.beta, 2, tb.m, nt.n1, nt.n2)), {})]


def _fused_moddown(p):
    tb = fused_ops.moddown_tables(p, p.L)
    nt = tb.ntt
    return [(fused_k.fused_moddown_pallas,
             (_u32(2, tb.k, nt.n2, nt.n1), nt.sc, tb.dsc, tb.wm, tb.pinv, nt.tw, nt.v2,
              nt.v1, nt.t, _u32(2, tb.m, nt.n1, nt.n2)), {})]


def _hoist_modup(p):
    tb = fused_ops.ks_tables(p, p.L)
    nt = tb.ntt
    return [(hoist_k.hoist_modup_pallas,
             (_u32(tb.beta, tb.k, nt.n2, nt.n1), nt.sc, tb.dsc, tb.wm, nt.tw, nt.v2, nt.v1,
              nt.t), {})]


def _hoist_mac(p):
    tb = fused_ops.ks_tables(p, p.L)
    nt = tb.ntt
    return [(hoist_k.hoist_mac_pallas,
             (_u32(tb.beta, tb.m, nt.n1, nt.n2),
              _u32(ROTATIONS, tb.beta, 2, tb.m, nt.n1, nt.n2), nt.sc), {})]


KERNELS = {
    "ntt": _ntt, "bconv": _bconv, "modops": _modops, "fused_ks": _fused_ks,
    "fused_moddown": _fused_moddown, "hoist_modup": _hoist_modup, "hoist_mac": _hoist_mac,
}


@pytest.mark.parametrize("preset", PRESETS)
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_kernel_compiles_for_v5e(kernel, preset, one_chip):
    for fn, args, kw in KERNELS[kernel](P.workload_params(preset)):
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in args]
        compiled = fn.lower(*shapes, interpret=False, **kw).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k, fits", [(17, True), (18, True), (19, False)])
def test_fused_shape_rule_matches_compiler(k, fits, one_chip):
    """At N=2^16 the fused shape rule admits exactly the digit sizes the compiler
    fits in its default scoped VMEM.  k=17 is logreg (β=2, 51 extended limbs)."""
    n1, n2 = fourstep_split(1 << 16)
    beta, m = 2, 3 * k
    assert (fused_k.fused_vmem_bytes(k, n1, n2) <= tpu.VMEM_SCOPED_LIMIT) == fits

    def spec(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = fused_k.fused_ks_pallas.lower(
        spec((beta, k, n2, n1)), spec((m * ntt_k.NSC,)), spec((beta * k * fused_k.NDSC,)),
        spec((beta * (k + 1) * m,)), spec((m, n1, n2)), spec((m, NLIMB8, n2, n2), jnp.bfloat16),
        spec((m, NLIMB8, n1, n1), jnp.bfloat16), spec((m, n1, n2)),
        spec((beta, 2, m, n1, n2)), interpret=False,
    )
    if fits:
        assert "tpu_custom_call" in lowered.compile().as_text()
    else:
        with pytest.raises(Exception, match="scoped vmem"):
            lowered.compile()
