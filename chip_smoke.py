"""On-chip smoke check: the FHE main path on one TPU, bit-exact against the oracle.

Drives ``FheContext`` at three published presets, each at its paper
parameters (N, L, dnum, t) from ``repro.fhe.params.WORKLOAD_PRESETS``:

  dblookup     shallow CKKS, N=2^14, L=8,  dnum=3
  lstm         deep CKKS,    N=2^16, L=13, dnum=2, 128-bit
  exact_count  BGV,          N=2^13, L=4,  dnum=3, t=2^16

For each preset: host keygen (relinearisation keys, plus rotations 1..4 for
CKKS) on the CPU device; then encrypt, mul (relinearise + rescale, or
modulus-switch for BGV), for CKKS one rotate and one rotate_hoisted_group over
the four rotations, and decrypt_decode.  The same ops run three times:

  * backend="ref" on the host CPU device — the uint64 oracle;
  * backend="fused", hoisting="always" on the TPU — fusedks / fused_moddown /
    hoist_modup / hoist_mac kernels;
  * backend="staged" on the TPU — the per-stage ntt / bconv / modops kernels.

Every TPU ciphertext's c0/c1 words must equal the oracle's bit for bit; CKKS
decode error after encrypt and mul must stay under ``DECODE_TOL``; BGV decode
must equal the plaintext product exactly; rotation decode error is printed,
not gated.  Each phase's kernel launch counts must show the kernels of its
pipeline.  Any failure exits non-zero, and so does a machine without a TPU:
the script claims the TPU before any FHE work and never falls back.

Lines before the last are JSON objects: the run header, then one per
(preset, pipeline, phase) with smoke-run wall times (first call, compile
included; then a warm repeat, both up to ``block_until_ready``) — smoke
timings, not benchmark metrics.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

Usage (repo root, one TPU chip):  python chip_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PRESETS = ("dblookup", "lstm", "exact_count")
ROTATIONS = (1, 2, 3, 4)
DECODE_TOL = 5e-3  # CPU oracle: 2.4e-4 at dblookup, 1.4e-3 at lstm (N=2^16)
SEED = 0


def claim_tpu():
    """The TPU (default) and host CPU devices; exits if there is no TPU."""
    import jax

    jax.config.update("jax_platforms", "tpu,cpu")  # an explicit list never falls back
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: default device is {dev.platform!r}, not a TPU")
    return dev, jax.devices("cpu")[0]


def use_compile_cache() -> str:
    """The persistent compilation cache directory: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else the fixed ``<repo>/.jax_cache``."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    return str(ROOT / ".jax_cache")


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def block(out):
    """Wait for every device array in an op's result."""
    import jax

    if isinstance(out, dict):
        for v in out.values():
            block(v)
    elif hasattr(out, "c0"):
        jax.block_until_ready((out.c0, out.c1))
    else:
        jax.block_until_ready(out)
    return out


def keys_on(keys, dev):
    """A copy of a KeySet committed to ``dev`` (fresh hoisted-key cache)."""
    import jax

    from repro.fhe import keys as K

    put = lambda x: jax.device_put(x, dev)
    return K.KeySet(
        sk=K.SecretKey(s_coeff=keys.sk.s_coeff, s_eval=put(keys.sk.s_eval)),
        pk=K.PublicKey(b=put(keys.pk.b), a=put(keys.pk.a)),
        rlk=K.SwitchingKey(k=put(keys.rlk.k)),
        gks={t: K.SwitchingKey(k=put(g.k)) for t, g in keys.gks.items()},
    )


def phases(ctx, z):
    """(name, thunk) pairs of the smoke run; later thunks read earlier results."""
    res = {}
    ckks = ctx.scheme == "ckks"

    def put(name, fn):
        def run():
            res[name] = fn()
            return res[name]
        return name, run

    out = [
        put("encrypt", lambda: ctx.encrypt(ctx.encode(z))),
        put("mul", lambda: ctx.mul(res["encrypt"], res["encrypt"])),
    ]
    if ckks:
        out += [
            put("rotate", lambda: ctx.rotate(res["mul"], 1)),
            put("rotate_hoisted_group",
                lambda: ctx.rotate_hoisted_group(res["mul"], ROTATIONS)),
        ]
    out.append(put("decrypt_decode", lambda: ctx.decrypt_decode(res["mul"])))
    return out, res


def words(out):
    """Host copies of every ciphertext word in an op's result."""
    import numpy as np

    if isinstance(out, dict):
        return [w for r in sorted(out) for w in words(out[r])]
    if hasattr(out, "c0"):
        return [np.asarray(out.c0), np.asarray(out.c1)]
    return [np.asarray(out)]


def devices_of(out):
    if isinstance(out, dict):
        return set().union(*(devices_of(v) for v in out.values()))
    if hasattr(out, "c0"):
        return set(out.c0.devices()) | set(out.c1.devices())
    return set()


# kernels each (pipeline, scheme) must launch somewhere in its run; BGV has no rotations
STAGED = ("ntt", "intt", "bconv", "mulmod", "addmod", "submod")
REQUIRED = {
    "fused": {"ckks": ("fusedks", "fused_moddown", "hoistmodup", "hoistmac"),
              "bgv": ("fusedks", "fused_moddown")},
    "staged": {"ckks": STAGED, "bgv": STAGED},
}


def decode_checks(preset, params, z, decoded) -> bool:
    """CKKS decode error vs numpy (gated for encrypt/mul), BGV exact."""
    import numpy as np

    if params.scheme == "bgv":
        t, n = params.plain_modulus, params.n
        full = np.convolve(z, z)  # terms < 2^32, N of them: exact in int64
        want = (full[:n] - np.concatenate([full[n:], [0]])) % t
        exact = bool(np.array_equal(np.asarray(decoded["mul"]), want))
        emit(preset=preset, check="bgv_decode_exact", ok=exact)
        return exact
    ok = True
    for name, want in (("encrypt", z), ("mul", z * z)):
        err = float(np.max(np.abs(np.asarray(decoded[name]) - want)))
        good = err < DECODE_TOL
        emit(preset=preset, check=f"decode_err_{name}", value=err, tol=DECODE_TOL, ok=good)
        ok &= good
    err = float(np.max(np.abs(np.asarray(decoded["rotate"]) - np.roll(z * z, -1))))
    emit(preset=preset, check="decode_err_rotate", value=err, gated=False)
    return ok


def run_preset(preset, tpu_dev, cpu_dev) -> bool:
    import jax
    import numpy as np

    from repro.fhe import keys as K
    from repro.fhe import params as P
    from repro.fhe.context import ExecPolicy, FheContext
    from repro.kernels import dispatch

    params = P.workload_params(preset)
    ckks = params.scheme == "ckks"
    rng = np.random.default_rng(SEED)
    if ckks:
        z = rng.normal(size=params.slots) * 0.4
    else:
        z = rng.integers(0, params.plain_modulus, size=params.n)

    t0 = time.perf_counter()
    with jax.default_device(cpu_dev):
        keys = K.full_keyset(params, seed=SEED, rotations=ROTATIONS if ckks else ())
        block(keys.rlk.k)
    emit(preset=preset, phase="host_keygen", n=params.n, L=params.L, dnum=params.dnum,
         t=params.plain_modulus, smoke_wall_s=time.perf_counter() - t0)

    # the oracle, on the host CPU device, backend="ref" given explicitly
    ref_ctx = FheContext(params=params, keys=keys,
                         policy=ExecPolicy(backend="ref", hoisting="always"))
    with jax.default_device(cpu_dev):
        ref_phases, ref_res = phases(ref_ctx, z)
        for _, run in ref_phases:
            block(run())
        decoded = {"mul": ref_res["decrypt_decode"]}
        if ckks:
            decoded["encrypt"] = ref_ctx.decrypt_decode(ref_res["encrypt"])
            decoded["rotate"] = ref_ctx.decrypt_decode(ref_res["rotate"])
    ok = decode_checks(preset, params, z, decoded)
    on_cpu = {cpu_dev}
    placed = all(devices_of(v) <= on_cpu for v in ref_res.values())
    emit(preset=preset, check="oracle_on_cpu", ok=placed)
    ok &= placed

    keys_tpu = keys_on(keys, tpu_dev)
    for backend in ("fused", "staged"):
        ctx = FheContext(params=params, keys=keys_tpu,
                         policy=ExecPolicy(backend=backend, hoisting="always"))
        steps, res = phases(ctx, z)
        launches: dict = {}
        for name, run in steps:
            with dispatch.count_dispatches() as counts:
                t0 = time.perf_counter()
                block(run())
                first = time.perf_counter() - t0
            t0 = time.perf_counter()
            block(run())
            warm = time.perf_counter() - t0
            for op, c in counts.items():
                launches[op] = launches.get(op, 0) + c
            same = all(np.array_equal(a, b) for a, b in
                       zip(words(res[name]), words(ref_res[name]), strict=True))
            on_tpu = devices_of(res[name]) <= {tpu_dev}
            emit(preset=preset, pipeline=backend, phase=name, smoke_wall_first_s=first,
                 smoke_wall_warm_s=warm, launches=dict(sorted(counts.items())),
                 bitexact_vs_ref=same, on_tpu=on_tpu)
            ok &= same and on_tpu
        missing = [k for k in REQUIRED[backend][params.scheme] if not launches.get(k)]
        emit(preset=preset, pipeline=backend, check="kernels_launched",
             missing=missing, ok=not missing)
        ok &= not missing
    return ok


def main() -> int:
    tpu_dev, cpu_dev = claim_tpu()
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.fhe import ntt
    from repro.fhe import params as P
    from repro.kernels import tpu
    from repro.kernels.fusedks import kernel as fk

    cache = use_compile_cache()
    if tpu.resolve("auto") != "kernel":
        sys.exit("chip_smoke: backend='auto' does not resolve to the compiled kernels")
    rule = {}
    for preset in PRESETS:
        p = P.workload_params(preset)
        rule[preset] = fk.fused_vmem_bytes(p.alpha, *ntt.fourstep_split(p.n))
    emit(jax=jax.__version__, device_kind=tpu_dev.device_kind, compile_cache=cache,
         fused_shape_rule=f"fused blocks <= {tpu.VMEM_SCOPED_LIMIT} B or raise",
         fused_block_bytes=rule, decode_tol=DECODE_TOL)
    from repro.core import executor

    mesh = executor.affiliation_mesh()  # the multi-job mesh still builds on one chip
    ok = mesh.devices.size == len(jax.devices())
    emit(check="affiliation_mesh", devices=int(mesh.devices.size), ok=ok)
    for preset in PRESETS:
        ok &= run_preset(preset, tpu_dev, cpu_dev)
    if not ok:
        print("chip_smoke: FAILED (see the lines with false checks)", file=sys.stderr)
        return 1
    emit(ok=True, device={"platform": tpu_dev.platform, "kind": tpu_dev.device_kind,
                          "count": len(jax.devices())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
