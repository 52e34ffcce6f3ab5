"""Hypothesis import guard for property-test modules.

The seed suite hard-errored at collection when ``hypothesis`` was absent,
taking every non-property test in the module down with it. Importing
``given``/``settings``/``st`` from here instead degrades gracefully: with
hypothesis installed the real decorators pass through untouched; without it
each property test collects and reports as *skipped* (the per-test analogue
of ``pytest.importorskip("hypothesis")``, which would skip whole modules and
hide their plain unit tests).
"""
try:
    from hypothesis import example, given, settings, strategies as st  # noqa: F401

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    import pytest

    HAVE_HYPOTHESIS = False

    def given(*_args, **_kwargs):
        def decorate(fn):
            def _skipped():
                pytest.skip("hypothesis not installed")

            _skipped.__name__ = fn.__name__
            _skipped.__doc__ = fn.__doc__
            return _skipped

        return decorate

    def settings(*_args, **_kwargs):
        return lambda fn: fn

    example = settings

    class _Strategies:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _Strategies()
