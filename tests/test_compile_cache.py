"""Persistent compilation cache: warm restarts skip XLA compiles.

``IndexSpec(compile_cache_dir=...)`` (and ``KNNIndex.load(...,
compile_cache_dir=...)``) wire jax's persistent compilation cache into the
index lifecycle, with hit/miss accounting surfaced through ``Plan.reasons``
— the same auditability contract as every other planner decision.

The cache is PROCESS-GLOBAL jax state, so the cold-start/warm-restart
lifecycle runs in subprocesses: run 1 populates a shared cache dir (cold
start, warm() reports a miss), run 2 is the simulated restart (warm start,
warm() reports a hit, entry count stable).  In-process tests only cover
the no-cache default and the spec plumbing.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.api import IndexSpec, KNNIndex

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# Build + warm against a shared cache dir, print reason lines + entry count.
_LIFECYCLE = textwrap.dedent("""
    import glob, json, os, sys
    import numpy as np
    import jax
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    from repro.api import IndexSpec, KNNIndex

    cache_dir = sys.argv[1]
    rng = np.random.default_rng(0)
    pts = rng.normal(size=(3000, 8)).astype(np.float32)
    idx = KNNIndex.build(pts, spec=IndexSpec(
        engine="streaming", height=3, k_hint=5,
        compile_cache_dir=cache_dir,
    ))
    idx.warm(64, 5)
    q = rng.normal(size=(64, 8)).astype(np.float32)
    idx.query(q, k=5)
    print(json.dumps({
        "reasons": list(idx.plan.reasons),
        "entries": len(glob.glob(os.path.join(cache_dir, "*-cache"))),
    }))
""")


def _lifecycle_run(cache_dir: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # the spec's dir applies
    out = subprocess.run(
        [sys.executable, "-c", _LIFECYCLE, cache_dir],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cold_then_warm_restart(tmp_path):
    cache_dir = str(tmp_path / "xla-cache")

    cold = _lifecycle_run(cache_dir)
    cache_line = [r for r in cold["reasons"] if "compile cache at" in r]
    assert cache_line and "cold start" in cache_line[0]
    warm_line = [r for r in cold["reasons"] if "for warm(" in r]
    assert warm_line and "miss: compiled" in warm_line[0]
    assert cold["entries"] > 0, "no cache entries persisted to disk"

    # simulated restart: fresh process, same cache dir => compiles are
    # served from disk and the entry count does not grow
    warm = _lifecycle_run(cache_dir)
    cache_line = [r for r in warm["reasons"] if "compile cache at" in r]
    assert cache_line and "warm start" in cache_line[0]
    assert f"{cold['entries']} executable(s) on disk" in cache_line[0]
    warm_line = [r for r in warm["reasons"] if "for warm(" in r]
    assert warm_line and "hit: served from disk" in warm_line[0]
    assert warm["entries"] == cold["entries"]


def test_no_cache_dir_means_no_cache_reasons():
    pts = np.random.default_rng(1).normal(size=(600, 6)).astype(np.float32)
    idx = KNNIndex.build(pts, spec=IndexSpec(engine="chunked", height=2))
    assert not any("compile cache" in r for r in idx.plan.reasons)


def test_spec_field_survives_replace_but_not_manifest():
    spec = IndexSpec(compile_cache_dir="/tmp/x")
    assert spec.replace(k_hint=7).compile_cache_dir == "/tmp/x"
    assert IndexSpec().compile_cache_dir is None
    # host-local path: must NOT leak into the persisted snapshot manifest
    # (cache dirs belong to the saving host, like persist_dir)
    from repro.api.index import _SPEC_MANIFEST_FIELDS
    assert "compile_cache_dir" not in _SPEC_MANIFEST_FIELDS


def test_cache_dir_resolution(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins wherever it is set; otherwise a spec's
    directory, else the one fixed in-checkout path (never a temporary,
    per-process or timestamped one)."""
    from repro.api.index import DEFAULT_COMPILE_CACHE_DIR, compile_cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.abspath(os.path.join(SRC, ".."))
    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(root, ".jax_cache")
    assert compile_cache_dir() == DEFAULT_COMPILE_CACHE_DIR
    assert compile_cache_dir(str(tmp_path)) == str(tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert compile_cache_dir() == str(tmp_path / "env")
    assert compile_cache_dir(str(tmp_path)) == str(tmp_path / "env")


# An entry point's cache under JAX_COMPILATION_CACHE_DIR: nothing overrides it
_ENV_DIR = textwrap.dedent("""
    import glob, os, sys
    import numpy as np
    import jax
    from repro.api import IndexSpec, KNNIndex, enable_compile_cache

    print(enable_compile_cache())
    pts = np.random.default_rng(0).normal(size=(3000, 8)).astype(np.float32)
    idx = KNNIndex.build(pts, spec=IndexSpec(
        engine="chunked", height=3, compile_cache_dir=sys.argv[1]))
    idx.query(pts[:64], k=5)
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    print("ENTRIES", len(glob.glob(os.path.join(
        os.environ["JAX_COMPILATION_CACHE_DIR"], "*-cache"))))
    print("SPEC_DIR", os.path.exists(sys.argv[1]))
""")


def test_env_cache_dir_is_left_in_place(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "env-cache")
    out = subprocess.run(
        [sys.executable, "-c", _ENV_DIR, str(tmp_path / "spec-cache")],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    lines = out.stdout.splitlines()
    assert f"compile cache at {tmp_path / 'env-cache'} " in lines[0]
    assert "(JAX_COMPILATION_CACHE_DIR)" in lines[0]
    assert f"CONFIG {tmp_path / 'env-cache'}" in lines
    assert "SPEC_DIR False" in lines
    assert int(next(x for x in lines if x.startswith("ENTRIES")).split()[1]) > 0
