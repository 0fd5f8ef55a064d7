"""repro.api — the unified multi-op front door.

One index API over every execution strategy in the repo::

    from repro.api import KNNIndex

    index = KNNIndex.build(points)             # planner picks the engine
    dists, idx = index.query(queries, k=10)    # exact kNN, any engine

    index = KNNIndex.build(points, op="radius")        # plan for an op
    indptr, ids, dists = index.radius(queries, r=0.1)  # CSR neighborhoods
    densities, err = index.kde(queries, bandwidth=0.05)
    hist, _ = index.pair_count(edges)          # 2-point correlation

Layers (each importable on its own):

  spec     ``IndexSpec`` (what you ask for), ``QueryResult`` /
           ``RadiusResult`` / ``StatResult`` + immutable ``SearchStats``
           (what you get back)
  engine   ``Engine`` protocol, ``EngineCaps`` (including ``caps.ops``,
           the per-engine operation declaration), ``@register_engine``
  planner  ``plan(n, d, m, k, devices, memory_budget, op=...)`` — the
           paper's §3 device-memory constraint and §3.2 topology split as
           a cost model, now op-aware (non-kNN ops restrict the choice to
           declaring engines)
  engines  the registered strategies: brute, kdtree, host, chunked, jit,
           sharded, forest, ring, dynamic (the mutable one:
           ``KNNIndex.insert``/``delete``), streaming (per-row delivery:
           ``KNNIndex.query_stream`` — the online serving engine).  The
           buffer-tree engines (host/chunked/streaming) and brute declare
           the dual-tree ops radius / kde / pair_count
  index    the ``KNNIndex`` facade tying them together

``knn_brute`` is re-exported as the ground-truth oracle (it is also the
``brute`` engine); ``knn_round_cache_size`` and ``dualtree_cache_size``
are diagnostics hooks for recompile accounting in benchmarks
(``chunk_round_cache_size`` is the deprecated former name of the kNN
one — importable for one more release with a ``DeprecationWarning``).
See ``docs/API.md`` for the mapping from paper concepts to ops/engines.
"""

import warnings as _warnings

from repro.api.engine import (
    KNOWN_OPS,
    Engine,
    EngineBase,
    EngineCaps,
    MutabilityError,
    OpUnsupported,
    StreamingUnsupported,
    available_engines,
    get_engine,
    register_engine,
)
from repro.api.planner import (
    CALIBRATION_STALE_S,
    BudgetError,
    Calibration,
    Plan,
    estimate_meta_bytes,
    estimate_slab_bytes,
    plan,
)
from repro.api.spec import (
    IndexSpec,
    QueryResult,
    RadiusResult,
    SearchStats,
    StatResult,
)
from repro.api.index import KNNIndex, enable_compile_cache

# Register the built-in engines (import side effect populates the registry).
from repro.api import engines as _engines  # noqa: F401

# Ground-truth oracle + diagnostics, re-exported so consumers need only
# this facade.  ``chunk_round_cache_size`` was renamed to
# ``knn_round_cache_size`` when the dual-tree ops (and their own
# ``dualtree_cache_size``) arrived; the old name stays importable for one
# release via the module ``__getattr__`` shim below.
from repro.core.brute import knn_brute
from repro.core.chunked_jit import chunk_round_cache_size as knn_round_cache_size
from repro.core.dualtree import dualtree_cache_size

__all__ = [
    "KNNIndex",
    "enable_compile_cache",
    "IndexSpec",
    "QueryResult",
    "RadiusResult",
    "StatResult",
    "SearchStats",
    "Plan",
    "plan",
    "estimate_slab_bytes",
    "estimate_meta_bytes",
    "BudgetError",
    "Calibration",
    "CALIBRATION_STALE_S",
    "Engine",
    "EngineBase",
    "EngineCaps",
    "KNOWN_OPS",
    "MutabilityError",
    "OpUnsupported",
    "StreamingUnsupported",
    "register_engine",
    "get_engine",
    "available_engines",
    "knn_brute",
    "knn_round_cache_size",
    "dualtree_cache_size",
    "chunk_round_cache_size",  # deprecated alias (one release of compat)
]

_DEPRECATED = {
    "chunk_round_cache_size": (
        "knn_round_cache_size",
        "repro.api.chunk_round_cache_size is deprecated and will be removed "
        "next release; import knn_round_cache_size instead",
    ),
}


def __getattr__(name):
    if name in _DEPRECATED:
        new, msg = _DEPRECATED[name]
        _warnings.warn(msg, DeprecationWarning, stacklevel=2)
        return globals()[new]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
