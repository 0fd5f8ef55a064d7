"""Optional-hypothesis shim for test modules that mix fuzz and plain tests.

``from hypothesis_compat import example, given, settings, st`` behaves exactly like the
real hypothesis imports when the package is installed.  When it is not, the
``@given`` decorator turns the fuzz test into a skip (with a clear reason)
while the rest of the module keeps collecting and running — the environment
does not ship hypothesis, and tier-1 collection must not depend on it.
"""

from __future__ import annotations

try:
    from hypothesis import example, given, settings, strategies as st  # noqa: F401

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # pragma: no cover - exercised when hyp missing
    import pytest

    HAVE_HYPOTHESIS = False

    def given(*_args, **_kwargs):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)

        return deco

    def settings(*_args, **_kwargs):
        def deco(fn):
            return fn

        return deco

    example = settings

    class _AnyStrategy:
        """Stands in for ``strategies``: strategy constructors are evaluated at
        decoration time, so they must be callable (values are never drawn)."""

        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _AnyStrategy()
