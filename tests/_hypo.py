"""Hypothesis if installed, else a deterministic integers-only stand-in.

The property tests only ever use ``st.integers`` with ``@given`` /
``@settings(max_examples=..., deadline=None)``.  Where hypothesis is
absent the fallback replays the same decorator API with a fixed-seed RNG,
so the tier-1 suite keeps exercising the properties instead of skipping
the modules wholesale.
"""
try:
    from hypothesis import given, settings, strategies as st  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    import numpy as _np

    class _Ints:
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi

        def sample(self, rng):
            return int(rng.integers(self.lo, self.hi + 1))

    class _Strategies:
        @staticmethod
        def integers(min_value, max_value):
            return _Ints(min_value, max_value)

    st = _Strategies()

    def settings(max_examples=10, deadline=None):
        def deco(fn):
            fn._max_examples = max_examples
            return fn

        return deco

    def given(**strategies):
        def deco(fn):
            def runner(*args, **kwargs):
                # @settings may sit outside (sets runner._max_examples) or
                # inside @given (sets fn._max_examples); honor both orders
                n = getattr(runner, "_max_examples",
                            getattr(fn, "_max_examples", 10))
                rng = _np.random.default_rng(0xC0FFEE)
                for _ in range(n):
                    drawn = {k: s.sample(rng) for k, s in strategies.items()}
                    fn(*args, **kwargs, **drawn)

            runner.__name__ = fn.__name__
            runner.__doc__ = fn.__doc__
            return runner

        return deco
