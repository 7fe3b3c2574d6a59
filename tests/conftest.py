"""Test-suite bootstrap.

1. Puts ``src/`` on ``sys.path`` so plain ``pytest`` works without setting
   ``PYTHONPATH=src`` by hand.
2. Provides the ``profiled`` fixture: run a callable under a
   ``jax.profiler`` trace and read back the host plane's spans.
3. Provides a *functional* ``hypothesis`` stand-in when the real package is
   not installed (the container image has no network; see
   requirements-dev.txt).  Unlike the old shim — which collected property
   tests only to skip them — this mini-engine actually runs each
   ``@given`` test: deterministic seeded sampling per test (stable across
   runs), boundary values first, then randomized draws.  It implements the
   strategy surface this suite uses (integers, floats, lists, tuples,
   booleans, sampled_from, just, one_of) and honors
   ``settings(max_examples=...)`` scaled down by
   ``MINI_HYPOTHESIS_MAX_EXAMPLES`` (default cap 12) to keep the tier-1
   suite fast.  Install real hypothesis and it is used untouched.
"""
from __future__ import annotations

import os
import sys
import types
import warnings
import zlib

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
)

try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    import random

    _MAX_CAP = int(os.environ.get("MINI_HYPOTHESIS_MAX_EXAMPLES", "12"))

    class _Unsatisfied(Exception):
        """Raised by ``assume(False)`` to discard the current example."""

    class _Strategy:
        """A draw function plus a list of boundary examples tried first."""

        def __init__(self, draw, corners=()):
            self._draw = draw
            self.corners = list(corners)

        def draw(self, rng):
            return self._draw(rng)

        def corner(self, i):
            return self.corners[i % len(self.corners)] if self.corners \
                else None

        def map(self, fn):
            return _Strategy(
                lambda rng: fn(self._draw(rng)),
                [fn(c) for c in self.corners],
            )

        def filter(self, pred):
            def draw(rng):
                for _ in range(100):
                    x = self._draw(rng)
                    if pred(x):
                        return x
                raise _Unsatisfied

            return _Strategy(draw, [c for c in self.corners if pred(c)])

    def _integers(min_value=None, max_value=None):
        lo = -(2 ** 31) if min_value is None else int(min_value)
        hi = 2 ** 31 if max_value is None else int(max_value)
        return _Strategy(
            lambda rng: rng.randint(lo, hi),
            [lo, hi, min(max(0, lo), hi), min(max(1, lo), hi)],
        )

    def _floats(min_value=0.0, max_value=1.0, **_kw):
        lo, hi = float(min_value), float(max_value)
        return _Strategy(
            lambda rng: rng.uniform(lo, hi),
            [lo, hi, (lo + hi) / 2.0],
        )

    def _booleans():
        return _Strategy(lambda rng: rng.random() < 0.5, [False, True])

    def _sampled_from(seq):
        seq = list(seq)
        return _Strategy(lambda rng: seq[rng.randrange(len(seq))], seq[:2])

    def _just(value):
        return _Strategy(lambda rng: value, [value])

    def _one_of(*strats):
        return _Strategy(
            lambda rng: strats[rng.randrange(len(strats))].draw(rng),
            [s.corner(0) for s in strats if s.corners],
        )

    def _lists(elements, min_size=0, max_size=None, unique=False,
               unique_by=None, **_kw):
        cap = min_size + 8 if max_size is None else max_size
        keyf = unique_by or (id if not unique else (lambda x: x))

        def draw(rng):
            n = rng.randint(min_size, cap)
            out, seen = [], set()
            attempts = 0
            while len(out) < n and attempts < 50 * (n + 1):
                attempts += 1
                x = elements.draw(rng)
                k = keyf(x)
                if (unique or unique_by) and k in seen:
                    continue
                seen.add(k)
                out.append(x)
            if len(out) < min_size:
                raise _Unsatisfied
            return out

        corner = []
        for i in range(min_size):
            c = elements.corner(i)
            corner.append(elements.draw(random.Random(i)) if c is None else c)
        return _Strategy(draw, [corner] if min_size <= cap else [])

    def _tuples(*strats):
        return _Strategy(
            lambda rng: tuple(s.draw(rng) for s in strats),
            [tuple(s.corner(0) for s in strats)] if all(
                s.corners for s in strats
            ) else [],
        )

    def _settings(*a, **kw):
        if a and callable(a[0]):  # bare @settings
            return a[0]

        def deco(fn):
            fn._mini_settings = dict(kw)
            return fn

        return deco

    _settings.register_profile = lambda *a, **k: None
    _settings.load_profile = lambda *a, **k: None

    def _given(*strats, **kw_strats):
        def deco(fn):
            cfg = getattr(fn, "_mini_settings", {})
            n_examples = min(int(cfg.get("max_examples", _MAX_CAP)),
                             _MAX_CAP)
            seed = zlib.crc32(
                f"{fn.__module__}.{fn.__qualname__}".encode()
            )

            def runner():
                ran = 0
                trial = 0
                while ran < n_examples and trial < 10 * n_examples:
                    rng = random.Random(seed + trial)
                    trial += 1
                    try:
                        args = [
                            s.corner(trial - 1) if trial <= 2 and s.corners
                            else s.draw(rng)
                            for s in strats
                        ]
                        kwargs = {
                            k: s.draw(rng) for k, s in kw_strats.items()
                        }
                        fn(*args, **kwargs)
                    except _Unsatisfied:
                        continue
                    ran += 1

            runner.__name__ = getattr(fn, "__name__", "hypothesis_test")
            runner.__doc__ = getattr(fn, "__doc__", None)
            runner.__module__ = getattr(fn, "__module__", __name__)
            runner.hypothesis_inner = fn  # escape hatch for direct calls
            return runner

        return deco

    def _assume(cond):
        if not cond:
            raise _Unsatisfied
        return True

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.floats = _floats
    _st.booleans = _booleans
    _st.sampled_from = _sampled_from
    _st.lists = _lists
    _st.tuples = _tuples
    _st.just = _just
    _st.one_of = _one_of

    _mod = types.ModuleType("hypothesis")
    _mod.IS_MINI = True  # tests can skip shrinker-dependent assertions
    _mod.given = _given
    _mod.settings = _settings
    _mod.assume = _assume
    _mod.note = lambda *a, **k: None
    _mod.example = lambda *a, **k: (lambda fn: fn)
    _mod.HealthCheck = types.SimpleNamespace(
        too_slow=None, data_too_large=None, filter_too_much=None,
        function_scoped_fixture=None,
    )
    _mod.strategies = _st
    sys.modules["hypothesis"] = _mod
    sys.modules["hypothesis.strategies"] = _st


@pytest.fixture
def profiled(tmp_path):
    """``profiled(fn)`` runs ``fn()`` under a ``jax.profiler`` trace written
    to ``tmp_path`` and returns ``(fn's result, spans)``: each event of the
    ``/host:CPU`` plane as ``(name, start_ns, dur_ns, {stat: value})``."""
    def run(fn):
        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.rglob("*.xplane.pb")
        with warnings.catch_warnings():  # jax's stats type lacks a module
            warnings.simplefilter("ignore", DeprecationWarning)
            spans = [(ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
                     for plane in ProfileData.from_file(str(path)).planes
                     if plane.name == "/host:CPU"
                     for line in plane.lines for ev in line.events]
        return out, spans

    return run
