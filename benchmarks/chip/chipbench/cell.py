"""One run of one cell: set-up, warm-up, the measured window, the check.

The system under test is ``repro.fhe.FheContext`` under the default
``ExecPolicy()``: on a TPU the fused key-switch pipeline and the Pallas
kernels.  Keys come from a fixed key seed, as a server holds one client's
evaluation keys across its requests; inputs, plaintexts and encryption
randomness come from ``--seed``.  The window runs jobs back to back, one
client in a closed loop, each timed from its first op call to
``block_until_ready`` on its result.
"""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import statistics
import tempfile
import time
from pathlib import Path

import numpy as np

from . import circuit, spec, yardstick
from . import trace as tr

KEY_SEED = 20250131
CHECKED_JOBS = 8  # jobs of a run decrypted and compared with the reference, drawn from the seed


def build_params(cfg: dict):
    """The preset's ``CkksParams``; refuses a configuration file that states
    other parameters than the preset has."""
    from repro.fhe import params as P

    p = P.workload_params(cfg["preset"])
    have = {"scheme": p.scheme, "n": p.n, "L": p.L, "dnum": p.dnum, "alpha": p.alpha,
            "t": p.plain_modulus, "security_bits": p.security_bits}
    wrong = {k: (cfg.get(k), v) for k, v in have.items() if cfg.get(k) != v}
    if wrong:
        raise ValueError(f"configuration {cfg['preset']!r} disagrees with the preset (file, preset): {wrong}")
    return p


def block(x):
    """Wait for every device array under ``x`` (ciphertexts, plaintexts, dicts, lists)."""
    import jax

    if isinstance(x, dict):
        for v in x.values():
            block(v)
    elif isinstance(x, (list, tuple)):
        for v in x:
            block(v)
    elif hasattr(x, "c0"):
        jax.block_until_ready((x.c0, x.c1))
    elif hasattr(x, "data"):
        jax.block_until_ready(x.data)
    return x


@contextlib.contextmanager
def compiles_counted():
    """Count XLA backend compilations inside the block: ``with ... as n: ...; n[0]``."""
    import jax
    from jax._src import dispatch as jdispatch

    n = [0]

    def hear(event, duration, **kwargs):
        if event == jdispatch.BACKEND_COMPILE_EVENT:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        yield n
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)


class Cell:
    """What a run holds across its jobs: the cell's files, params, keys on the
    default device, and the context the jobs run in."""

    def __init__(self, name: str, base: Path = spec.HERE, bench_path: Path = spec.BENCHMARK,
                 policy=None):
        import jax

        from repro.fhe import keys as K
        from repro.fhe.context import ExecPolicy, FheContext

        self.bench = spec.benchmark(bench_path)
        self.base = Path(base)
        self.workload = spec.workload(self.bench, name)
        self.name = name
        self.cfg = spec.config(self.workload["config"], base)
        self.circuit = spec.traffic(self.workload["traffic"], base)
        self.limits = spec.limits(name, base)
        if "max_err" not in self.limits:
            raise ValueError(f"limits/{name}.json states no max_err limit")
        self.params = p = build_params(self.cfg)
        if self.circuit.max_depth_used > p.L:
            raise ValueError(f"traffic {self.circuit.name!r} starts below level 0 at L={p.L}")
        if self.circuit.rotations and p.scheme == "bgv":
            raise ValueError(f"traffic {self.circuit.name!r} rotates slots, which BGV here does not pack")
        with jax.default_device(jax.devices("cpu")[0]):  # host precompute, uint64 oracle
            ks = K.full_keyset(p, seed=KEY_SEED, rotations=self.circuit.rotations)
        dev = jax.devices()[0]
        put = lambda x: jax.device_put(x, dev)
        keys = K.KeySet(
            sk=K.SecretKey(s_coeff=ks.sk.s_coeff, s_eval=put(ks.sk.s_eval)),
            pk=K.PublicKey(b=put(ks.pk.b), a=put(ks.pk.a)),
            rlk=K.SwitchingKey(k=put(ks.rlk.k)),
            gks={g: K.SwitchingKey(k=put(k.k)) for g, k in ks.gks.items()},
        )
        block([keys.sk.s_eval, keys.pk.b, keys.pk.a, keys.rlk.k, *(k.k for k in keys.gks.values())])
        self.ctx = FheContext(params=p, keys=keys, policy=policy or ExecPolicy())

    def inputs(self, seed: int):
        """The client's encrypted input sets and the server's plaintexts, from
        ``seed``: (one env per pool entry, their slot values, the plaintexts' values)."""
        ctx, L = self.ctx, self.params.L
        rng = np.random.default_rng(seed)
        pool_vals, pt_vals = circuit.draw_values(self.circuit, self.width, rng)
        level = {i.name: L - i.depth_used for i in self.circuit.inputs}
        kind = {i.name: i.kind for i in self.circuit.inputs}
        pts = {nm: ctx.encode(v, level=level[nm]) if kind[nm] == "pt" else v for nm, v in pt_vals.items()}
        envs = []
        for vals in pool_vals:
            env = dict(pts)
            for nm, v in vals.items():
                env[nm] = ctx.encrypt(ctx.encode(v, level=level[nm]), seed=int(rng.integers(2**63)))
            envs.append(env)
        block(envs)
        return envs, pool_vals, pt_vals

    @property
    def width(self) -> int:
        """Values per input: slots under CKKS, coefficients under BGV."""
        return self.params.n if self.params.scheme == "bgv" else self.params.slots

    def served(self, j: int) -> list[int]:
        """The pool entries job ``j`` serves, one per client."""
        c = self.circuit.clients
        return [(j * c + k) % self.circuit.pool for k in range(c)]

    def job(self, envs: list, j: int, annotate=None):
        """Job ``j``: the circuit for each of its clients, waited for; returns
        (one output dict per client, the evaluator)."""
        ev = circuit.FheEval(self.ctx)
        outs = [circuit.evaluate(self.circuit, ev, envs[k], annotate) for k in self.served(j)]
        block(outs)
        return outs, ev

    def reference(self, env: dict) -> dict:
        return circuit.evaluate(self.circuit, circuit.NumpyEval(self.params.plain_modulus), env)

    def error(self, outs: dict, ref: dict) -> float:
        """Widest gap between a decrypted output value and the reference; NaN
        counts as failing.  Under BGV the gap is taken mod t, centred."""
        t = self.params.plain_modulus
        gaps = []
        for nm in self.circuit.outputs:
            d = np.asarray(self.ctx.decrypt_decode(outs[nm])) - ref[nm]  # complex slots under CKKS
            if t is not None:
                d = (d.astype(np.int64) + t // 2) % t - t // 2
            gaps.append(float(np.max(np.abs(d))))
        return worst(gaps)


def worst(values):
    """The largest value, NaN above every number."""
    return max(values, key=lambda e: (e != e, e))


def check(cell: Cell, outs: list, pool_vals, pt_vals, seed: int, transform=None) -> dict:
    """Decrypt a seeded sample of the window's jobs and compare each with the
    plain reference.  ``transform`` (a control) rewrites each output first."""
    n = len(outs)
    pick = sorted(np.random.default_rng([seed, 7]).choice(n, size=min(n, CHECKED_JOBS), replace=False))
    refs: dict[int, dict] = {}
    errs = []
    limit = float(cell.limits["max_err"]["limit"])
    failed = 0
    for j in pick:
        job_errs = []
        for k, o in zip(cell.served(j), outs[j]):
            if k not in refs:
                refs[k] = cell.reference({**pt_vals, **pool_vals[k]})
            if transform is not None:
                o = {nm: transform(c) for nm, c in o.items()}
            job_errs.append(cell.error(o, refs[k]))
        errs += job_errs
        failed += any(not e <= limit for e in job_errs)
    return {"max_err": worst(errs), "limit": limit, "checked": len(pick), "failed": failed}


@dataclasses.dataclass
class Window:
    outs: list
    lat_s: list
    window_s: float
    compiles: int
    events: dict | None


def window(cell: Cell, envs: list, seconds: float, trace: bool) -> Window:
    """Jobs back to back until ``seconds`` have passed; the last job started
    in time runs to its end and counts."""
    import jax

    annotate = jax.profiler.TraceAnnotation if trace else None
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    outs, lat = [], []
    try:
        if trace:
            jax.profiler.start_trace(tdir)
        try:
            with compiles_counted() as compiles:
                t_w = time.perf_counter()
                while True:
                    t0 = time.perf_counter()
                    with annotate(tr.JOB_SPAN) if trace else contextlib.nullcontext():
                        out, _ = cell.job(envs, len(outs), annotate)
                    t1 = time.perf_counter()
                    outs.append(out)
                    lat.append(t1 - t0)
                    if t1 - t_w >= seconds:
                        break
        finally:
            if trace:
                jax.profiler.stop_trace()
        events = None
        if trace:
            (pb,) = Path(tdir).glob("plugins/profile/*/*.xplane.pb")
            events = tr.load_xplane(pb, {tr.JOB_SPAN, *circuit.OPS})
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    return Window(outs, lat, t1 - t_w, compiles[0], events)


def end_to_end(w: Window, setup_s: float) -> dict:
    lat_ms = np.asarray(w.lat_s) * 1e3
    return {
        "jobs_per_s": len(w.outs) / w.window_s,
        "job_ms_p50": float(statistics.median(lat_ms)),
        "job_ms_p90": float(np.percentile(lat_ms, 90)),
        "setup_s": setup_s,
    }


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float, device_kind: str,
        log=print) -> dict:
    """Set-up after ``cell``, warm-up, window, check; the run's record.

    ``t_start`` is when the process started, so ``setup_s`` covers imports,
    keygen, the encryption of the inputs and the warm-up job."""
    import jax

    from repro.kernels import dispatch

    envs, pool_vals, pt_vals = cell.inputs(seed)
    with dispatch.count_dispatches() as launches, compiles_counted() as warm_compiles:
        _, ev = cell.job(envs, 0)  # warm-up: every shape the window uses
    setup_s = time.perf_counter() - t_start
    ks_bytes = sum(yardstick.ks_min_bytes(cell.params.n, lv, cell.params.alpha, reads_input=r)
                   for lv, r in ev.ks)
    log({"warmup": {"kernel_launches": dispatch.total(launches), "by_kernel": dict(sorted(launches.items())),
                    "compiles": warm_compiles[0], "ks_levels": ev.ks_levels, "ks_min_bytes": ks_bytes}})

    w = window(cell, envs, seconds, trace)
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log({"window": {"jobs": len(w.outs), "window_s": w.window_s, "compiles": w.compiles,
                    "job_ms_min": 1e3 * min(w.lat_s), "job_ms_max": 1e3 * max(w.lat_s)}})

    bench = cell.bench
    metrics, breakdown, dev_extra = {}, None, {}
    if trace:
        s = tr.summarize(w.events, tr.load_families(cell.base / "kernel_families.json"), ks_bytes,
                         yardstick.peaks(device_kind))
        silent = []
        for m in spec.per_layer(bench, cell.name):
            v = spec.reader(m["name"], cell.base)(s)
            if v is None:
                silent.append(m["name"])
            else:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log({"trace": {"jobs": s.jobs, "device_ops": s.device_ops, "family_s": s.family_s,
                       "read_nothing": silent}})
        breakdown = {"device_ops": s.device_top, "idle_gaps": s.gap_top}
        dev_extra = {"busy_s": s.busy_s, "window_s": s.window_s}
    else:
        e2e = end_to_end(w, setup_s)
        for m in spec.end_to_end(bench, cell.name):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    t_check = time.perf_counter()
    res = check(cell, w.outs, pool_vals, pt_vals, seed)
    log({"check": dict(res, check_s=time.perf_counter() - t_check)})
    return {
        "setup_s": setup_s,
        "correct": res["failed"] == 0 and w.compiles == 0,
        "attempted": len(w.outs),
        "failed": res["failed"],
        "metrics": metrics,
        "memory_peak_bytes": peak,
        "device_extra": dev_extra,
        "breakdown": breakdown,
        "checks": {"max_err": {"value": res["max_err"], "limit": res["limit"]},
                   "compiles_in_window": {"value": w.compiles, "limit": 0}},
    }
