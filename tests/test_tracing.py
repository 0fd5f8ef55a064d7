"""The program's profiler spans, device scopes and the ``rows_scanned``
counter.

A tiny ``KNNIndex.query`` and ``pair_count`` run under ``jax.profiler``;
the trace, read back with the benchmark's ``load_trace``, holds every host
span, nested as the round loop and the frontier nest them.  The device
scopes are in the lowered programs.  ``rows_scanned`` counts each in-chunk
query once per round and is the same on the batch and streaming paths.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import IndexSpec, KNNIndex
from repro.core.chunked_jit import _chunk_round
from repro.core.dualtree import _pair_hist_kernel

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib.trace import load_trace  # noqa: E402

KNN_SPANS = ("knn.query", "knn.prepare", "knn.round", "knn.schedule",
             "knn.dispatch", "knn.harvest", "knn.compact", "knn.drain",
             "knn.rescore")
KNN_SCOPES = ("knn.plan", "knn.gather", "knn.scan", "knn.merge",
              "knn.advance")
PC_SPANS = ("pc.call", "pc.frontier", "pc.batch", "pc.dispatch",
            "pc.readback")
PC_SCOPES = ("pc.distance", "pc.bin")


def _data(n, m, d, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, d)).astype(np.float32),
            rng.normal(size=(m, d)).astype(np.float32))


def _traced(tmp_path, fn):
    fn()                                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, load_trace(str(tmp_path)).host


def _inside(inner, outer):
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def _by_name(host, prefix):
    out = {}
    for e in host:
        if e.name.startswith(prefix):
            out.setdefault(e.name, []).append(e)
    return out


def _nested(spans, chain):
    """Every span named chain[0] lies inside one named chain[1], and that
    one inside one named chain[2], ..."""
    for inner, outer in zip(chain, chain[1:]):
        for e in spans[inner]:
            assert any(_inside(e, o) for o in spans[outer]), (inner, outer)


def test_knn_query_spans_are_present_and_nested(tmp_path):
    pts, q = _data(4000, 256, 4)
    q[:16] *= 4.0      # a few far queries keep the tail going past a rung
    index = KNNIndex.build(pts, spec=IndexSpec(engine="chunked", height=4))
    res, host = _traced(tmp_path, lambda: index.query(q, k=10))
    assert res.stats.compactions >= 1       # the tail reaches the ladder
    spans = _by_name(host, "knn.")
    assert set(KNN_SPANS) <= set(spans), sorted(spans)
    assert len(spans["knn.query"]) == 1
    assert len(spans["knn.round"]) == res.stats.iterations
    _nested(spans, ("knn.harvest", "knn.round", "knn.query"))
    _nested(spans, ("knn.schedule", "knn.round"))
    _nested(spans, ("knn.dispatch", "knn.round"))
    for name in ("knn.prepare", "knn.compact", "knn.drain", "knn.rescore"):
        _nested(spans, (name, "knn.query"))
        assert not any(_inside(e, r) for e in spans[name]
                       for r in spans["knn.round"]), name


def test_pair_count_spans_are_present_and_nested(tmp_path):
    pts, _ = _data(2000, 1, 3, seed=3)
    index = KNNIndex.build(pts, spec=IndexSpec(op="pair_count", height=4))
    edges = np.array([0.1, 0.5, 1.0, 2.0, 4.0])
    res, host = _traced(tmp_path, lambda: index.pair_count(edges))
    spans = _by_name(host, "pc.")
    assert set(PC_SPANS) <= set(spans), sorted(spans)
    assert len(spans["pc.call"]) == 1
    assert len(spans["pc.batch"]) == res.stats.flushes
    _nested(spans, ("pc.readback", "pc.batch", "pc.call"))
    _nested(spans, ("pc.dispatch", "pc.batch"))
    _nested(spans, ("pc.frontier", "pc.call"))


def test_device_scopes_are_in_the_lowered_programs():
    m, c, nl, k, tq, lp, dp = 64, 4, 8, 4, 32, 16, 8
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    text = _chunk_round.lower(
        sds((m,), i32), sds((m,), i32), sds((m,), i32),
        sds((m + 1, k), f32), sds((m + 1, k), i32), sds((2,), i32),
        sds((m, dp), f32), sds((c, lp, dp), f32), sds((), i32),
        sds((nl,), i32), sds((nl,), i32), sds((nl,), i32), sds((nl,), f32),
        sds((1, 1), f32), sds((1, 1), f32), sds((1, 1), jnp.uint8),
        sds((), f32),
        k=k, tq=tq, first_leaf_heap=nl, ub=2, backend="ref", quant=False,
        affine=False,
    ).as_text(debug_info=True)
    for scope in KNN_SCOPES:
        assert scope in text, scope
    slab = sds((nl, lp, dp), f32)
    ids = sds((8,), i32)
    text = _pair_hist_kernel.lower(
        slab, slab, ids, ids, ids, ids, sds((5,), f32)
    ).as_text(debug_info=True)
    for scope in PC_SCOPES:
        assert scope in text, scope


@pytest.mark.parametrize("m", [1, 37, 300])
def test_rows_scanned_bounds_and_streaming_parity(m):
    pts, q = _data(3000, m, 6, seed=m)
    k = 5
    got = {}
    for engine in ("chunked", "streaming"):
        index = KNNIndex.build(pts, spec=IndexSpec(engine=engine, height=4))
        if engine == "streaming":
            res = index.query_stream(q, k, on_complete=lambda *a: None)
        else:
            res = index.query(q, k=k)
        tq = index._state.engine_tile_q
        s = res.stats
        # every query is scanned at least at its home leaf; a tile holds
        # at most tq rows
        assert m <= s.rows_scanned <= s.units_scanned * tq
        got[engine] = (s.rows_scanned, s.units_scanned, s.iterations)
    assert got["chunked"] == got["streaming"]
