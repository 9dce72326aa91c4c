"""Shared test fixtures: the hypothesis-or-fallback property sampler.

Clean environments ship no ``hypothesis``; every property-testing module
imports ``given``/``st`` from here (``from conftest import given, st``) so
tier-1 collection and the invariants still run without it. The fallback is
a deterministic sampler seeded per test function (crc32 of the qualname),
covering exactly the strategy surface the suite uses: floats / integers /
booleans / sampled_from / lists-of-floats.

With hypothesis installed you get real shrinking and the registered "ci"
profile (40 examples, no deadline); without it, the same number of
deterministic examples.

JAX's compiled programs are also dropped between tests once the worker
holds many (see ``_release_compiled_programs``).
"""

import gc
import zlib

import jax
import numpy as np
import pytest


# a worker frees its compiled programs past this many memory mappings
_MAPPINGS_BUDGET = 30_000


def _mapping_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:          # no procfs: nothing to watch
        return 0


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """Free JAX's compiled programs after a test once the worker holds many.

    Each program XLA compiles for the CPU keeps its own memory mappings
    until it is freed, and JAX's caches keep every program alive. A worker
    that runs many compile-heavy tests (the interpret-mode Pallas paths
    above all) otherwise reaches the kernel's per-process mapping limit
    (``vm.max_map_count``, 65530 by default) and dies with a segfault
    inside the compiler. Below the budget nothing is dropped, so tests
    that share a compiled step keep sharing it.
    """
    yield
    if _mapping_count() > _MAPPINGS_BUDGET:
        jax.clear_caches()
        gc.collect()

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
    settings.register_profile("ci", max_examples=40, deadline=None)
    settings.load_profile("ci")
except ImportError:
    HAVE_HYPOTHESIS = False
    _MAX_EXAMPLES = 40

    class _Strategy:
        def __init__(self, sample):
            self.sample = sample  # rng -> drawn value

    class _st:
        @staticmethod
        def floats(min_value, max_value, allow_nan=False):
            return _Strategy(
                lambda rng: float(rng.uniform(min_value, max_value)))

        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(
                lambda rng: int(rng.integers(min_value, max_value + 1)))

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: bool(rng.integers(0, 2)))

        @staticmethod
        def sampled_from(options):
            options = list(options)
            return _Strategy(
                lambda rng: options[int(rng.integers(len(options)))])

        @staticmethod
        def lists(elems, min_size=0, max_size=10):
            def sample(rng):
                n = int(rng.integers(min_size, max_size + 1))
                return [elems.sample(rng) for _ in range(n)]
            return _Strategy(sample)

    st = _st

    class settings:  # noqa: N801 - mirrors hypothesis' decorator surface
        """No-op stand-in for ``@settings(...)`` (profiles have no meaning
        for the deterministic fallback sampler)."""

        def __init__(self, *args, **kwargs):
            self.kwargs = kwargs

        def __call__(self, fn):
            n = self.kwargs.get("max_examples")
            if n is not None:
                fn._fallback_max_examples = n
            return fn

    def given(*strategies):
        def deco(fn):
            import inspect
            params = list(inspect.signature(fn).parameters.values())
            outer = params[:len(params) - len(strategies)]
            strat_names = [p.name for p in params[len(outer):]]

            def wrapper(*args, **kwargs):
                seed = zlib.crc32(fn.__qualname__.encode())
                rng = np.random.default_rng(seed)
                # @settings may sit above @given (it then annotates the
                # wrapper) or below it (it annotates fn) — honor both
                examples = getattr(wrapper, "_fallback_max_examples",
                                   getattr(fn, "_fallback_max_examples",
                                           _MAX_EXAMPLES))
                for _ in range(examples):
                    drawn = {nm: s.sample(rng)
                             for nm, s in zip(strat_names, strategies)}
                    fn(*args, **kwargs, **drawn)
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            # hide the strategy-bound trailing parameters from pytest so
            # fixtures/parametrize compose with @given like with hypothesis
            # (e.g. @pytest.mark.parametrize over a leading argument)
            wrapper.__signature__ = inspect.Signature(outer)
            return wrapper
        return deco
