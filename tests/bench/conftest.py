"""The benchmark's tests import the harness as ``bench`` from the root of
the checkout, with JAX's CPU dispatch synchronous as in the repo's own
tests (``tests/conftest.py``), also where they run from a copy of the
benchmark's files alone."""

import os
import sys

import jax

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

jax.config.update("jax_cpu_enable_async_dispatch", False)
