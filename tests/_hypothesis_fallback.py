"""Deterministic fallback for the ``hypothesis`` test extra.

The property tests (``test_slo_estimators.py``,
``test_comm_model_properties.py``, ``test_message_properties.py``)
use a small, fixed slice of the hypothesis API. In environments
without the ``test`` extra installed (the sandbox CI image bakes no
pip access) those files used to ``importorskip`` and silently drop
their coverage. This module implements exactly that API slice as a
seeded pseudo-random example generator, so the properties still run
everywhere — weaker than hypothesis (no shrinking, no database, no
coverage-guided search), but deterministic per test and far better
than a silent skip.

Scope rules:

* only the strategies the three files draw are implemented — adding a
  new strategy to a test means extending this shim (a loud
  ``AttributeError``, not a silent skip);
* every example stream is seeded from the wrapped test's qualified
  name, so a failure reproduces bit-identically across runs and
  machines;
* ``settings(max_examples=..., deadline=...)`` is honored for
  ``max_examples`` and ignores ``deadline`` (no wall-clock policing).

Usage (the property files):

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        from _hypothesis_fallback import given, settings, strategies as st
"""
from __future__ import annotations

import functools
import random
import zlib


class Strategy:
    """One drawable value source: ``example(rnd)`` returns a value."""

    def __init__(self, fn, name="strategy"):
        self._fn = fn
        self._name = name

    def example(self, rnd):
        return self._fn(rnd)

    def __repr__(self):  # pragma: no cover - debug aid
        return f"<fallback {self._name}>"


class DataObject:
    """The ``st.data()`` handle: interactive draws inside a test."""

    def __init__(self, rnd):
        self._rnd = rnd

    def draw(self, strategy, label=None):
        return strategy.example(self._rnd)


class _DataStrategy(Strategy):
    def __init__(self):
        super().__init__(DataObject, "data")


class _Strategies:
    """The ``strategies as st`` namespace (the used subset only)."""

    @staticmethod
    def data():
        return _DataStrategy()

    @staticmethod
    def integers(min_value, max_value):
        return Strategy(lambda r: r.randint(min_value, max_value),
                        "integers")

    @staticmethod
    def floats(min_value, max_value, allow_nan=False,
               allow_infinity=False):
        lo, hi = float(min_value), float(max_value)
        # bias toward the endpoints (and 0 when in range) the way
        # hypothesis does — the boundary cases are where estimator
        # invariants break
        edges = [lo, hi] + ([0.0] if lo <= 0.0 <= hi else [])

        def draw(r):
            if r.random() < 0.1:
                return r.choice(edges)
            return r.uniform(lo, hi)

        return Strategy(draw, "floats")

    @staticmethod
    def booleans():
        return Strategy(lambda r: r.random() < 0.5, "booleans")

    @staticmethod
    def sampled_from(elements):
        seq = list(elements)
        return Strategy(lambda r: seq[r.randrange(len(seq))],
                        "sampled_from")

    @staticmethod
    def lists(elements, min_size=0, max_size=None, unique=False):
        hi = min_size + 10 if max_size is None else max_size

        def draw(r):
            n = r.randint(min_size, hi)
            if not unique:
                return [elements.example(r) for _ in range(n)]
            out, seen = [], set()
            for _ in range(50 * max(n, 1)):  # collision headroom
                if len(out) >= n:
                    break
                v = elements.example(r)
                if v not in seen:
                    seen.add(v)
                    out.append(v)
            return out

        return Strategy(draw, "lists")

    @staticmethod
    def tuples(*strategies):
        return Strategy(
            lambda r: tuple(s.example(r) for s in strategies),
            "tuples")

    @staticmethod
    def characters(codec=None, min_codepoint=0, max_codepoint=127):
        return Strategy(
            lambda r: chr(r.randint(min_codepoint, max_codepoint)),
            "characters")

    @staticmethod
    def text(alphabet, min_size=0, max_size=None):
        hi = min_size + 8 if max_size is None else max_size
        return Strategy(
            lambda r: "".join(alphabet.example(r)
                              for _ in range(r.randint(min_size, hi))),
            "text")

    @staticmethod
    def dictionaries(keys, values, max_size=None):
        hi = 5 if max_size is None else max_size

        def draw(r):
            out = {}
            for _ in range(r.randint(0, hi)):
                out[keys.example(r)] = values.example(r)
            return out

        return Strategy(draw, "dictionaries")

    @staticmethod
    def composite(fn):
        """``@st.composite`` — the wrapped fn's first arg becomes the
        draw callable; calling the decorated fn returns a Strategy."""

        @functools.wraps(fn)
        def build(*args, **kwargs):
            return Strategy(
                lambda r: fn(lambda s: s.example(r), *args, **kwargs),
                fn.__name__)

        return build


strategies = _Strategies()

_DEFAULT_MAX_EXAMPLES = 20


def settings(max_examples=_DEFAULT_MAX_EXAMPLES, deadline=None,
             **_ignored):
    """Stores the profile on the function; ``given`` reads it at call
    time (the decorators stack ``@settings`` above ``@given``)."""

    def apply(fn):
        fn._fallback_max_examples = int(max_examples)
        return fn

    return apply


def example(**kwargs):
    """An explicit example: ``given`` runs it before the drawn ones (the
    decorators stack ``@given`` above ``@example``)."""

    def apply(fn):
        fn._fallback_examples = getattr(fn, "_fallback_examples", []) \
            + [kwargs]
        return fn

    return apply


def given(**param_strategies):
    """Runs the test body ``max_examples`` times with drawn kwargs,
    seeded from the test's qualified name — deterministic across
    runs, machines, and pytest orderings."""

    def decorate(fn):
        @functools.wraps(fn)
        def wrapper():
            n = getattr(wrapper, "_fallback_max_examples",
                        _DEFAULT_MAX_EXAMPLES)
            seed = zlib.crc32(
                f"{fn.__module__}.{fn.__qualname__}".encode())
            rnd = random.Random(seed)
            explicit = getattr(fn, "_fallback_examples", [])
            for i in range(-len(explicit), n):
                kwargs = explicit[i] if i < 0 else {
                    name: strat.example(rnd)
                    for name, strat in sorted(param_strategies.items())}
                try:
                    fn(**kwargs)
                except Exception as e:
                    raise AssertionError(
                        f"falsifying example (fallback shim, "
                        f"iteration {i}, seed {seed}): "
                        f"{kwargs!r}") from e

        # pytest must not see the drawn params as fixtures:
        # functools.wraps sets __wrapped__, which inspect.signature
        # (and so pytest's fixture resolution) would follow back to
        # the parameterized original
        del wrapper.__wrapped__
        wrapper.hypothesis_fallback = True
        return wrapper

    return decorate
