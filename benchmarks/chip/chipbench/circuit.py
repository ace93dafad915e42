"""Encrypted-job circuits written as data, and the one interpreter that runs them.

A traffic mix is ``traffic/<name>.json``:

* ``why``: one line, what the circuit is and which layers it exercises;
  ``source``: where its shape comes from (read by people, not by the code).
* ``pool``: how many distinct input sets the clients encrypt in set-up.
  ``clients`` (default 1): how many clients' input sets one job serves; job j
  serves sets j*clients .. j*clients+clients-1, mod pool.  Server data
  (``kind: "pt"`` and ``"diags"``) is drawn once per run, as a server holds
  its table.
* ``inputs``: ``{name, kind, depth_used, values, for?, count?}``.  ``kind`` is
  ``"ct"`` (a client ciphertext, fresh at level L - depth_used), ``"pt"`` (a
  server plaintext at that level) or ``"diags"`` (a server matrix as ``count``
  plain diagonals, encoded by the program when it applies them).  ``values``
  is one of ``{"dist": "uniform", "lo": a, "hi": b}`` (reals),
  ``{"dist": "ints", "lo": a, "hi": b}`` (integers in [a, b), the BGV
  message), ``{"dist": "bits"}`` (0/1 per slot) or ``{"dist": "onehot"}``
  (over the ``for`` group, one member is 1 in each slot and the others 0).
* ``ops``: ``{out, op, args, const?, n1?, for?}`` in program order, with
  ``op`` one of ``OPS``.  ``for`` is a count n (``{i}`` runs over 0..n-1) or a
  list of strings substituted for ``{i}``; it repeats the op, except on
  ``sum``, where it expands the single argument template into the terms
  summed.  ``rotate`` takes its step as ``const``; ``matvec`` (ct, diags)
  takes its baby-step count as ``n1``.  The Galois keys a circuit needs follow
  from its rotate steps and matvec shapes; set-up generates exactly those.
* ``outputs``: the names whose decryptions are compared with the reference.

The scheme is the configuration's: under CKKS a value is one real per slot;
under BGV it is one integer mod t per polynomial coefficient, and a ct x ct
product is the negacyclic convolution mod t.

The same op list runs twice: through ``FheEval`` on ``FheContext`` (the
system under test) and through ``NumpyEval`` on the plain input values
(the plain reference, which imports nothing of the program).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
from pathlib import Path

import numpy as np

# op -> (number of arguments, takes a constant); sum is variadic
OPS = {
    "mul": (2, False),  # ct x ct, relinearise, rescale (BGV: modulus-switch)
    "mul_const": (1, True),  # ct x real constant, rescaled to exactly the scale 2^scale_bits
    "mul_plain": (2, False),  # ct x server plaintext, no rescale
    "rescale": (1, False),  # BGV: modulus-switch
    "add": (2, False),  # ct + ct; the operand at the higher level is first brought down exactly
    "sub": (2, False),
    "add_const": (1, True),
    "rotate": (1, True),  # cyclic left rotation of the slots by ``const`` steps
    "matvec": (2, False),  # (ct, diags): BSGS plaintext matrix-vector product, one rescale
    "sum": (None, False),  # left fold of add over the terms
}
KINDS = ("ct", "pt", "diags")
DISTS = ("uniform", "ints", "bits", "onehot")


@dataclasses.dataclass(frozen=True)
class Input:
    name: str
    kind: str
    depth_used: int
    values: dict
    group: tuple[str, ...]  # the names of this input's ``for`` group (onehot draws across it)
    count: int  # diagonals of a "diags" input; 1 otherwise


@dataclasses.dataclass(frozen=True)
class Op:
    out: str
    op: str
    args: tuple[str, ...]
    const: float | None
    n1: int | None = None


@dataclasses.dataclass(frozen=True)
class Circuit:
    name: str
    why: str
    pool: int
    clients: int
    inputs: tuple[Input, ...]
    ops: tuple[Op, ...]
    outputs: tuple[str, ...]

    @property
    def max_depth_used(self) -> int:
        return max(i.depth_used for i in self.inputs)

    @property
    def rotations(self) -> tuple[int, ...]:
        """The slot rotations whose Galois keys the circuit's jobs use."""
        count = {i.name: i.count for i in self.inputs}
        steps: set[int] = set()
        for op in self.ops:
            if op.op == "rotate":
                steps.add(int(op.const))
            elif op.op == "matvec":
                steps |= set(bsgs_steps(count[op.args[1]], op.n1))
        return tuple(sorted(s for s in steps if s))


def bsgs_steps(count: int, n1: int) -> tuple[int, ...]:
    """Baby and giant rotations of a BSGS product over diagonals 0..count-1."""
    return tuple(sorted(({d % n1 for d in range(count)} | {d // n1 * n1 for d in range(count)}) - {0}))


def _items(spec: dict) -> list[str] | None:
    f = spec.get("for")
    if f is None:
        return None
    if isinstance(f, int) and not isinstance(f, bool) and f > 0:
        return [str(i) for i in range(f)]
    if isinstance(f, list) and f and all(isinstance(x, str) for x in f):
        return list(f)
    raise ValueError(f"'for' must be a positive count or a list of strings: {f!r}")


def _sub(template: str, item: str | None) -> str:
    return template if item is None else template.replace("{i}", item)


def _positive(raw: dict, key: str, default=None) -> int:
    v = raw.get(key, default)
    if not isinstance(v, int) or isinstance(v, bool) or v < 1:
        raise ValueError(f"{key} must be a positive count: {v!r}")
    return v


def load(path: Path) -> Circuit:
    """Read and check one traffic file; every name an op reads must exist,
    with the kind the op takes there."""
    raw = json.loads(Path(path).read_text())
    pool, clients = _positive(raw, "pool"), _positive(raw, "clients", 1)
    inputs: list[Input] = []
    for spec in raw["inputs"]:
        if spec["kind"] not in KINDS:
            raise ValueError(f"{path}: input kind {spec['kind']!r}")
        if spec["values"].get("dist") not in DISTS:
            raise ValueError(f"{path}: values {spec['values']!r}")
        count = _positive(spec, "count") if spec["kind"] == "diags" else 1
        items = _items(spec)
        names = tuple(_sub(spec["name"], it) for it in (items or [None]))
        if spec["values"]["dist"] == "onehot" and len(names) < 2:
            raise ValueError(f"{path}: onehot needs a 'for' group")
        for nm in names:
            inputs.append(Input(nm, spec["kind"], int(spec.get("depth_used", 0)), dict(spec["values"]),
                                names, count))
    ops: list[Op] = []
    for spec in raw["ops"]:
        if spec["op"] not in OPS:
            raise ValueError(f"{path}: unknown op {spec['op']!r}")
        arity, has_const = OPS[spec["op"]]
        if has_const != ("const" in spec):
            raise ValueError(f"{path}: op {spec['op']!r} {'needs' if has_const else 'takes no'} const")
        items = _items(spec)
        const = float(spec["const"]) if has_const else None
        n1 = _positive(spec, "n1") if spec["op"] == "matvec" else None
        if spec["op"] == "sum":
            (template,) = spec["args"]
            ops.append(Op(spec["out"], "sum", tuple(_sub(template, it) for it in items or [None]), None))
            continue
        if len(spec["args"]) != arity:
            raise ValueError(f"{path}: op {spec['op']!r} takes {arity} args")
        for it in items or [None]:
            ops.append(Op(_sub(spec["out"], it), spec["op"], tuple(_sub(a, it) for a in spec["args"]),
                          const, n1))
    kind = {i.name: i.kind for i in inputs}
    for op in ops:
        missing = [a for a in op.args if a not in kind]
        if missing:
            raise ValueError(f"{path}: op {op.out} reads {missing} before they exist")
        want = ("ct", "diags") if op.op == "matvec" else ("ct", "pt") if op.op == "mul_plain" else None
        got = tuple(kind[a] for a in op.args)
        if (want and got != want) or (not want and "diags" in got):
            raise ValueError(f"{path}: op {op.out} ({op.op}) reads kinds {got}")
        kind[op.out] = "ct"
    outputs = tuple(raw["outputs"])
    if not outputs or any(o not in kind for o in outputs):
        raise ValueError(f"{path}: outputs {outputs} not all computed")
    return Circuit(Path(path).stem, raw["why"], pool, clients, tuple(inputs), tuple(ops), outputs)


def _draw(values: dict, shape, rng: np.random.Generator) -> np.ndarray:
    if values["dist"] == "uniform":
        return rng.uniform(float(values["lo"]), float(values["hi"]), size=shape)
    if values["dist"] == "ints":
        return rng.integers(int(values["lo"]), int(values["hi"]), size=shape).astype(np.int64)
    return rng.integers(0, 2, size=shape).astype(np.float64)


def draw_values(circuit: Circuit, width: int, rng: np.random.Generator):
    """Plain values, ``width`` per input (slots under CKKS, coefficients under
    BGV): one {name: value} dict per pool entry for the client ciphertexts,
    and one dict of server data for the run."""
    def fill(kinds) -> dict:
        out: dict[str, np.ndarray] = {}
        for inp in circuit.inputs:
            if inp.kind not in kinds or inp.name in out:
                continue
            if inp.values["dist"] == "onehot":
                hot = rng.integers(0, len(inp.group), size=width)
                for k, nm in enumerate(inp.group):
                    out[nm] = (hot == k).astype(np.float64)
            elif inp.kind == "diags":
                out[inp.name] = _draw(inp.values, (inp.count, width), rng)
            else:
                out[inp.name] = _draw(inp.values, width, rng)
        return out

    pool = [fill(("ct",)) for _ in range(circuit.pool)]
    return pool, fill(("pt", "diags"))


def evaluate(circuit: Circuit, ev, env: dict, annotate=None) -> dict:
    """Run the op list on ``ev`` over ``env`` (input name -> value); returns
    the outputs.  ``annotate(op_name)`` wraps each op call when given."""
    env = dict(env)
    for op in circuit.ops:
        args = [env[a] for a in op.args]
        with annotate(op.op) if annotate else contextlib.nullcontext():
            if op.op == "sum":
                env[op.out] = ev.sum(args)
            elif op.op == "matvec":
                env[op.out] = ev.matvec(*args, op.n1)
            elif op.const is None:
                env[op.out] = getattr(ev, op.op)(*args)
            else:
                env[op.out] = getattr(ev, op.op)(*args, op.const)
    return {o: env[o] for o in circuit.outputs}


class NumpyEval:
    """The plain reference: the circuit's arithmetic on float64 slot values,
    or, given a plaintext modulus ``t``, on integer coefficients mod t in the
    negacyclic ring (BGV)."""

    def __init__(self, t: int | None = None):
        self.t = t

    def _mod(self, x):
        return x if self.t is None else np.mod(x, self.t)

    def mul(self, a, b):
        if self.t is None:
            return a * b
        n = len(a)
        full = np.convolve(np.mod(a, self.t).astype(np.int64), np.mod(b, self.t).astype(np.int64))
        wrapped = full[:n].copy()
        wrapped[: n - 1] -= full[n:]  # x^n = -1
        return np.mod(wrapped, self.t)

    def mul_const(self, a, c):
        return self._mod(a * c)

    def mul_plain(self, a, p):
        return self.mul(a, p)

    def rescale(self, a):
        return a

    def add(self, a, b):
        return self._mod(a + b)

    def sub(self, a, b):
        return self._mod(a - b)

    def add_const(self, a, c):
        return self._mod(a + c)

    def rotate(self, a, r):
        return np.roll(a, -int(r))

    def matvec(self, a, diags, n1):
        return sum(diags[d] * np.roll(a, -d) for d in range(len(diags)))

    def sum(self, terms):
        return self._mod(np.sum(terms, axis=0))


class FheEval:
    """The circuit's ops on ``FheContext``; records the level of each
    key-switch and whether it reads its input afresh (a hoisted rotation
    shares the input of its group)."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.bgv = ctx.scheme == "bgv"
        self.ks: list[tuple[int, bool]] = []

    @property
    def ks_levels(self) -> list[int]:
        return [lv for lv, _ in self.ks]

    def mul(self, a, b):
        self.ks.append((min(a.level, b.level), True))
        return self.ctx.mul(a, b)

    def mul_const(self, a, c):
        return self.ctx.mul_const_exact(a, c, self.ctx.params.scale)

    def mul_plain(self, a, p):
        return self.ctx.mul_plain(a, p, rescale_after=False)

    def rescale(self, a):
        return self.ctx.mod_switch(a) if self.bgv else self.ctx.rescale(a)

    def _aligned(self, a, b):
        if self.bgv:  # the BGV add brings both operands to the lower level itself
            return a, b
        if a.level > b.level:
            a = self.ctx.force_to(a, b.level, b.scale)
        elif b.level > a.level:
            b = self.ctx.force_to(b, a.level, a.scale)
        return a, b

    def add(self, a, b):
        return self.ctx.add(*self._aligned(a, b))

    def sub(self, a, b):
        return self.ctx.sub(*self._aligned(a, b))

    def add_const(self, a, c):
        return self.ctx.add_const(a, c)

    def rotate(self, a, r):
        self.ks.append((a.level, True))
        return self.ctx.rotate(a, int(r))

    def matvec(self, a, diags, n1):
        from repro.fhe import linear

        plan = linear.plan_diags({d: diags[d].astype(np.complex128) for d in range(len(diags))},
                                 self.ctx.params, level=a.level, n1=n1)
        babies, giants = plan.baby_steps(), plan.giant_steps()
        self.ks += [(a.level, k == 0) for k in range(len(babies))] + [(a.level, True)] * len(giants)
        return self.ctx.apply_bsgs(a, plan)

    def sum(self, terms):
        acc = terms[0]
        for t in terms[1:]:
            acc = self.add(acc, t)
        return acc
