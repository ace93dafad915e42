"""Test-environment shims.

Pins JAX to the CPU before anything imports it: every Pallas kernel then runs
in the Pallas interpreter, bit-exact against the uint64 oracle, on any host.
The on-chip check is ``chip_smoke.py``; ``test_tpu_compile.py`` compiles the
kernels for a described TPU without one.

Provides a minimal deterministic fallback for ``hypothesis`` when the real
package is not installed (`pip install -e .[dev]` brings the real one).  The
fallback drives each ``@given`` test with seeded pseudo-random examples —
enough to keep the property tests meaningful and the suite collectable on a
bare runtime, while real hypothesis (shrinking, database, edge-case bias) is
used whenever available.  Only the strategy surface this repo uses is
implemented: integers / sampled_from / lists.
"""

from __future__ import annotations

import functools
import inspect
import os
import random
import sys
import types

os.environ["JAX_PLATFORMS"] = "cpu"

try:
    import hypothesis  # noqa: F401  (real package wins when installed)

    # Fixed CI profile: derandomized example generation so property tests
    # (serving/cluster invariants) can never flake on a lucky-or-unlucky seed.
    hypothesis.settings.register_profile(
        "ci", deadline=None, derandomize=True, max_examples=25)
    if os.environ.get("CI"):
        hypothesis.settings.load_profile("ci")
except ModuleNotFoundError:

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example_from(self, rng: random.Random):
            return self._draw(rng)

    def integers(min_value: int, max_value: int) -> _Strategy:
        return _Strategy(lambda r: r.randint(min_value, max_value))

    def sampled_from(seq) -> _Strategy:
        items = list(seq)
        return _Strategy(lambda r: r.choice(items))

    def lists(elements: _Strategy, min_size: int = 0, max_size: int = 10,
              unique: bool = False) -> _Strategy:
        def draw(r: random.Random):
            out: list = []
            for _ in range(200):  # rejection bound for unique draws
                if len(out) >= r.randint(min_size, max_size) and len(out) >= min_size:
                    break
                v = elements.example_from(r)
                if unique and v in out:
                    continue
                out.append(v)
            return out

        return _Strategy(draw)

    _DEFAULT_EXAMPLES = 20

    def given(*arg_strats, **kw_strats):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_stub_max_examples", _DEFAULT_EXAMPLES)
                rng = random.Random(f"{fn.__module__}.{fn.__name__}")
                for _ in range(n):
                    pos = tuple(s.example_from(rng) for s in arg_strats)
                    kws = {k: s.example_from(rng) for k, s in kw_strats.items()}
                    fn(*args, *pos, **kwargs, **kws)

            wrapper._stub_max_examples = _DEFAULT_EXAMPLES
            # expose only fixture params to pytest: strategy-provided args
            # (positional prefix + keyword names) are filled by the wrapper
            params = list(inspect.signature(fn).parameters.values())
            remaining = [
                q for q in params[len(arg_strats):] if q.name not in kw_strats
            ]
            wrapper.__signature__ = inspect.Signature(remaining)
            wrapper.__dict__.pop("__wrapped__", None)
            return wrapper

        return deco

    def settings(*_args, **kw):
        def deco(fn):
            if "max_examples" in kw:
                fn._stub_max_examples = kw["max_examples"]
            return fn

        return deco

    _strategies = types.ModuleType("hypothesis.strategies")
    _strategies.integers = integers
    _strategies.sampled_from = sampled_from
    _strategies.lists = lists

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = given
    _hyp.settings = settings
    _hyp.strategies = _strategies
    _hyp.__stub__ = True

    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _strategies
