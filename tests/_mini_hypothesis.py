"""Minimal, dependency-free stand-in for the `hypothesis` API surface the
test suite uses (given / settings / strategies.{floats,integers,booleans,
lists,text}).

The container image does not ship hypothesis and the repo policy forbids
installing packages, so ``conftest.py`` installs this module under the
``hypothesis`` name *only when the real library is absent*.  Draws are
deterministic (seeded per test name) and always include the strategy
boundary values first, so the invariant tests keep their edge-case
coverage.  This is intentionally NOT a property-testing engine — no
shrinking, no database — just enough to execute the suite's @given tests.
"""
from __future__ import annotations

import itertools
import random
import string
import zlib

__all__ = ["given", "settings", "strategies", "HealthCheck", "assume",
           "example"]

_DEFAULT_MAX_EXAMPLES = 50


class HealthCheck:  # placeholder namespace, matching hypothesis.HealthCheck
    all = staticmethod(lambda: [])


def assume(condition: bool) -> bool:
    if not condition:
        raise _Unsatisfied()
    return True


class _Unsatisfied(Exception):
    pass


class _Strategy:
    """A draw(rng) callable plus the boundary examples to try first."""

    def __init__(self, draw, boundaries=()):
        self._draw = draw
        self.boundaries = tuple(boundaries)

    def draw(self, rng: random.Random):
        return self._draw(rng)


class _Strategies:
    @staticmethod
    def floats(min_value=0.0, max_value=1.0, **_kw) -> _Strategy:
        lo, hi = float(min_value), float(max_value)
        mid = lo + (hi - lo) * 0.5
        return _Strategy(
            lambda rng: rng.uniform(lo, hi), boundaries=(lo, hi, mid)
        )

    @staticmethod
    def integers(min_value=0, max_value=100, **_kw) -> _Strategy:
        lo, hi = int(min_value), int(max_value)
        return _Strategy(
            lambda rng: rng.randint(lo, hi), boundaries=(lo, hi)
        )

    @staticmethod
    def booleans() -> _Strategy:
        return _Strategy(lambda rng: rng.random() < 0.5, boundaries=(False, True))

    @staticmethod
    def lists(elements: _Strategy, min_size=0, max_size=10, **_kw) -> _Strategy:
        def draw(rng):
            n = rng.randint(min_size, max_size)
            return [elements.draw(rng) for _ in range(n)]

        smallest = [
            (elements.boundaries[0] if elements.boundaries else elements.draw(random.Random(0)))
        ] * max(1, min_size)
        return _Strategy(draw, boundaries=([] if min_size == 0 else smallest,))

    @staticmethod
    def text(min_size=0, max_size=20, alphabet=None, **_kw) -> _Strategy:
        chars = alphabet or (string.ascii_letters + string.digits + " _-.\n")

        def draw(rng):
            n = rng.randint(min_size, max_size)
            return "".join(rng.choice(chars) for _ in range(n))

        return _Strategy(draw, boundaries=("" if min_size == 0 else "a" * min_size,))


strategies = _Strategies()


def settings(max_examples: int = _DEFAULT_MAX_EXAMPLES, deadline=None, **_kw):
    def deco(fn):
        fn._mini_hyp_max_examples = max_examples
        return fn

    return deco


def example(**kw):
    """An explicit example, run before any drawn one (above or below
    ``@given``, as in hypothesis)."""
    def deco(fn):
        fn._mini_hyp_examples = [kw] + getattr(fn, "_mini_hyp_examples", [])
        return fn

    return deco


def _boundary_combos(strats):
    """First examples: cartesian boundary combos (capped), like hypothesis's
    preference for edge values."""
    per = [s.boundaries or (s.draw(random.Random(0)),) for s in strats]
    return list(itertools.islice(itertools.product(*per), 32))


def given(*arg_strats, **kw_strats):
    def deco(fn):
        max_examples = getattr(fn, "_mini_hyp_max_examples", _DEFAULT_MAX_EXAMPLES)
        names = list(kw_strats)
        strats = list(arg_strats) + [kw_strats[k] for k in names]

        def wrapper(*outer_args, **outer_kw):
            for kw in (getattr(wrapper, "_mini_hyp_examples", [])
                       + getattr(fn, "_mini_hyp_examples", [])):
                fn(*outer_args, **outer_kw, **kw)
            seed = zlib.crc32(fn.__qualname__.encode())
            rng = random.Random(seed)
            combos = _boundary_combos(strats)
            ran = 0
            trial = 0
            while ran < max_examples:
                trial += 1
                if trial > max_examples * 10 + len(combos):
                    break  # too many assume() rejections
                if combos:
                    values = list(combos.pop(0))
                else:
                    values = [s.draw(rng) for s in strats]
                pos = values[: len(arg_strats)]
                kw = dict(zip(names, values[len(arg_strats):]))
                try:
                    fn(*outer_args, *pos, **outer_kw, **kw)
                except _Unsatisfied:
                    continue
                # Exception only: KeyboardInterrupt/SystemExit and pytest's
                # Skipped/Failed (BaseException subclasses) must propagate
                except Exception as exc:  # pragma: no cover - failure path
                    raise AssertionError(
                        f"mini-hypothesis falsifying example for "
                        f"{fn.__qualname__}: args={pos} kwargs={kw}"
                    ) from exc
                ran += 1
            if ran == 0:
                # mirror hypothesis's FailedHealthCheck: a test whose every
                # draw was rejected must not silently pass
                raise AssertionError(
                    f"mini-hypothesis: assume() rejected every example for "
                    f"{fn.__qualname__}; the test executed zero examples"
                )

        # keep identity for test reports, but NOT the signature (pytest
        # would otherwise treat the strategy parameters as fixtures)
        wrapper.__name__ = fn.__name__
        wrapper.__qualname__ = fn.__qualname__
        wrapper.__doc__ = fn.__doc__
        # pytest plugins (anyio) introspect fn.hypothesis.inner_test
        wrapper.hypothesis = type("_Hyp", (), {"inner_test": staticmethod(fn)})()
        return wrapper

    return deco
