"""``KNNIndex``: the one front door for every kNN workload in this repo.

    from repro.api import KNNIndex

    index = KNNIndex.build(points)            # planner picks the engine
    dists, idx = index.query(queries, k=10)   # QueryResult, tuple-unpackable

Everything between "fits on one device" and "massive data sets on multiple
devices" (the paper's continuum) is reached through these two calls: the
planner inspects (n, d, device topology, memory budget) and selects a
registered engine + parameters; pinning any ``IndexSpec`` field narrows its
freedom, and ``spec.engine=`` removes it entirely.  Consumers (serving,
launch CLI, examples, benchmarks) depend only on this module, so engines
can evolve — or be added — without another call-site migration.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

import numpy as np

from repro.api.engine import (
    KNOWN_OPS,
    EngineBase,
    MutabilityError,
    OpUnsupported,
    StreamingUnsupported,
    get_engine,
)
from repro.api.planner import Plan, plan as make_plan
from repro.api.spec import (
    IndexSpec,
    QueryResult,
    RadiusResult,
    SearchStats,
    StatResult,
)
from repro.persist import PersistError, VersionStore, WriteAheadLog

__all__ = ["KNNIndex", "compile_cache_dir", "enable_compile_cache"]

# IndexSpec fields recorded in a snapshot manifest (JSON-able, topology-
# free): device handles and measured calibrations belong to the HOST that
# saved, not the snapshot; persist_dir is where the snapshot LIVES (and
# compile_cache_dir is a host-local path, like persist_dir).
_SPEC_MANIFEST_FIELDS = (
    "engine", "op", "height", "n_chunks", "n_shards", "buffer_size",
    "tile_q", "backend", "k_hint", "m_hint", "memory_budget", "precision",
    "strict_budget", "mutable", "merge_async", "snapshot_keep", "wal_fsync",
)


# The fixed in-checkout cache directory the entry points use when
# JAX_COMPILATION_CACHE_DIR is not set: a cache is keyed by its path, so a
# temporary or per-process directory would never be hit again.
DEFAULT_COMPILE_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def _compile_cache_entries(path: str) -> int:
    """Serialized executables currently in a persistent compile cache dir."""
    try:
        return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
    except OSError:
        return 0


def compile_cache_dir(path: Optional[str] = None) -> str:
    """Where the persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set (jax reads it at
    import, and nothing here overrides it), else ``path``, else
    ``DEFAULT_COMPILE_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return os.path.abspath(env or path or DEFAULT_COMPILE_CACHE_DIR)


def enable_compile_cache(path: Optional[str] = None) -> str:
    """Turn jax's persistent compilation cache on at
    ``compile_cache_dir(path)`` and return the auditable reason string
    (entry count decides warm vs cold).  Entry points (CLI, benchmarks, the
    smoke script) call it with no path before their first compile; an index
    calls it with ``IndexSpec.compile_cache_dir``.

    The threshold knobs are zeroed because this repo's executables are
    many SMALL kernels (fused rounds, ladder gathers, scan tiles) — the
    default min-compile-time / min-entry-size filters would skip exactly
    the population whose compile count we are trying to amortize.  The
    cache dir is process-global in jax; the last index to enable it wins,
    which is fine for the intended one-serving-process-per-dir layout.
    """
    import jax

    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    path = compile_cache_dir(path)
    os.makedirs(path, exist_ok=True)
    n = _compile_cache_entries(path)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    src = " (JAX_COMPILATION_CACHE_DIR)" if from_env else ""
    return (
        f"compile cache at {path}{src}: {n} executable(s) on disk "
        f"({'warm' if n else 'cold'} start)"
    )


class KNNIndex:
    """A built kNN index: points + a planned engine + its opaque state."""

    def __init__(
        self, *, spec: IndexSpec, plan: Plan, engine: EngineBase, state,
        n: int, d: int,
    ):
        self.spec = spec
        self.plan = plan
        self._engine = engine
        self._state = state
        self.n = n
        self.d = d
        self._last_stats: Optional[SearchStats] = None
        # crash-safe lifecycle (spec.persist_dir / KNNIndex.load): the
        # snapshot store, the mutation WAL and the acknowledged-mutation
        # counter.  All None/0 for a plain in-memory index.
        self._store: Optional[VersionStore] = None
        self._wal: Optional[WriteAheadLog] = None
        self._mutation_seq: int = 0
        self._extra_arrays: Dict[str, np.ndarray] = {}
        # engines declaring stateful_query mutate queues/buffers/chunk
        # slots during a query: one batch at a time per index.  Stateless
        # engines (brute/jit/forest/ring/kdtree) run lock-free so
        # concurrent serving callers are not serialized needlessly.
        self._qlock = (
            threading.Lock() if engine.caps.stateful_query else None
        )

    def _serialized(self, fn, *args):
        """Run one engine hook under the stateful-engine lock (no lock for
        stateless engines, so concurrent serving callers stay parallel)."""
        if self._qlock is None:
            return fn(*args)
        with self._qlock:
            return fn(*args)

    # ------------------------------------------------------------------
    @classmethod
    def build(
        cls, points: np.ndarray, spec: Optional[IndexSpec] = None, **overrides
    ) -> "KNNIndex":
        """Plan + build an index over ``points``.

        ``spec`` (or keyword overrides for its fields) constrains the
        planner; with neither, the engine and all parameters are chosen
        from data shape, visible devices and memory budget alone.
        """
        spec = spec or IndexSpec()
        if overrides:
            spec = spec.replace(**overrides)
        points = np.asarray(points, dtype=np.float32)
        if points.ndim != 2:
            raise ValueError(f"points must be [n, d], got {points.shape}")
        n, d = points.shape
        if spec.devices is None:
            import jax

            spec = spec.replace(devices=tuple(jax.devices()))
        pl = make_plan(
            n, d,
            m=spec.m_hint,
            k=spec.k_hint,
            devices=spec.devices,
            memory_budget=spec.memory_budget,
            engine=spec.engine,
            height=spec.height,
            n_chunks=spec.n_chunks,
            n_shards=spec.n_shards,
            buffer_size=spec.buffer_size,
            tile_q=spec.tile_q,
            backend=spec.backend,
            calibration=spec.calibration,
            mutable=spec.mutable,
            merge_async=spec.merge_async,
            precision=spec.precision,
            strict_budget=spec.strict_budget,
            op=spec.op,
        )
        if spec.compile_cache_dir:
            # enable BEFORE the engine builds: build-phase compiles (warm-
            # at-build precompilation, initial scans) populate the cache
            pl = pl.replace(reasons=pl.reasons + (
                enable_compile_cache(spec.compile_cache_dir),
            ))
        engine = get_engine(pl.engine)
        state = engine.build(points, spec, pl)
        idx = cls(spec=spec, plan=pl, engine=engine, state=state, n=n, d=d)
        if spec.persist_dir:
            idx._init_persistence()
        return idx

    # -- crash-safe lifecycle ------------------------------------------
    def _init_persistence(self) -> None:
        """Root a fresh persist dir: baseline snapshot + empty WAL.

        Refuses a directory that already holds versions — silently
        re-baselining over an existing lifecycle would orphan its WAL
        tail; resume one with ``KNNIndex.load`` instead."""
        root = self.spec.persist_dir
        store = VersionStore(os.path.join(root, "versions"))
        if store.versions():
            raise PersistError(
                f"persist_dir {root!r} already holds snapshot versions; "
                "resume it with KNNIndex.load(...) or point build at a "
                "fresh directory"
            )
        self._store = store
        self._wal = WriteAheadLog(
            os.path.join(root, "wal"), fsync=self.spec.wal_fsync
        )
        self.plan = self.plan.replace(reasons=self.plan.reasons + (
            f"persistence: versioned snapshots + mutation WAL at {root}",
        ))
        self.save()

    def save(self, path: Optional[str] = None, *,
             extra_arrays: Optional[Dict[str, np.ndarray]] = None) -> int:
        """Write one complete snapshot version; returns its number.

        With ``path=None`` the version lands in the index's live persist
        dir (``spec.persist_dir``; error if persistence is off), the WAL
        rotates to a fresh segment, and segments no retained snapshot
        needs are dropped.  An explicit ``path`` writes a one-off export
        (no WAL bookkeeping).  ``extra_arrays`` ride along under
        ``extra/`` — e.g. the kNN-LM value store — and come back via
        ``load``.  Crash-atomic: a version is either complete (manifest
        present) or invisible to ``load``.
        """
        if path is None:
            if self._store is None:
                raise PersistError(
                    "index has no live persist dir: build with "
                    "IndexSpec(persist_dir=...) or pass save(path=...)"
                )
            store = self._store
        else:
            store = VersionStore(os.path.join(path, "versions"))
        arrays, meta = self._serialized(
            self._engine.snapshot_state, self._state
        )
        arrays = dict(arrays)
        for key, value in (extra_arrays or self._extra_arrays).items():
            arrays[f"extra/{key}"] = np.asarray(value)
        pl = self.plan
        manifest = {
            "engine": pl.engine,
            "n": int(self.n),
            "d": int(self.d),
            "mutation_seq": int(self._mutation_seq),
            "spec": {
                f: getattr(self.spec, f) for f in _SPEC_MANIFEST_FIELDS
            },
            # pin the built geometry so load re-plans to the SAME layout
            # the persisted state was shaped for
            "plan": {
                "height": pl.height, "n_chunks": pl.n_chunks,
                "n_shards": pl.n_shards, "buffer_size": pl.buffer_size,
            },
            "meta": meta,
            "created": time.time(),
        }
        version = store.commit(
            arrays, manifest, keep=max(1, self.spec.snapshot_keep)
        )
        if store is self._store and self._wal is not None:
            self._wal.rotate(self._mutation_seq)
            kept = store.versions()
            self._wal.gc(min(
                int(store.read_manifest(v)["mutation_seq"]) for v in kept
            ))
        return version

    @classmethod
    def load(
        cls, path: str, *, devices=None,
        compile_cache_dir: Optional[str] = None,
    ) -> "KNNIndex":
        """Restore an index from a persist dir: latest complete snapshot
        + replay of the WAL tail (every mutation acknowledged after that
        snapshot).  The loaded index continues the same lifecycle — later
        mutations append to the same WAL, later ``save()`` calls add
        versions — so crash/restore cycles compose.

        ``devices`` re-targets the restored state at the CURRENT topology
        (default: ``jax.devices()``); the snapshot itself is host-side
        and topology-free.  ``compile_cache_dir`` re-attaches the host-
        local persistent compilation cache (it is deliberately NOT in the
        manifest — cache paths belong to the host, like ``path`` itself),
        so a warm restart skips both the tree build AND the XLA compiles.
        """
        import jax

        store = VersionStore(os.path.join(path, "versions"))
        # copy-on-write mmap: restore cost is page-table setup, not a
        # bulk read — slabs page in lazily (free on a warm page cache)
        arrays, manifest, version = store.read(mmap=True)
        devs = tuple(devices) if devices else tuple(jax.devices())
        pins = manifest["plan"]
        spec = IndexSpec(**manifest["spec"]).replace(
            engine=manifest["engine"],
            devices=devs,
            persist_dir=str(path),
            compile_cache_dir=compile_cache_dir,
            height=pins["height"],
            n_chunks=pins["n_chunks"],
            n_shards=pins["n_shards"],
            buffer_size=pins["buffer_size"],
        )
        n, d = int(manifest["n"]), int(manifest["d"])
        pl = make_plan(
            max(1, n), d,
            m=spec.m_hint,
            k=spec.k_hint,
            devices=devs,
            memory_budget=spec.memory_budget,
            engine=spec.engine,
            height=spec.height,
            n_chunks=spec.n_chunks,
            n_shards=spec.n_shards,
            buffer_size=spec.buffer_size,
            tile_q=spec.tile_q,
            backend=spec.backend,
            mutable=spec.mutable,
            merge_async=spec.merge_async,
            precision=spec.precision,
            strict_budget=spec.strict_budget,
            op=spec.op,
        )
        if spec.compile_cache_dir:
            pl = pl.replace(reasons=pl.reasons + (
                enable_compile_cache(spec.compile_cache_dir),
            ))
        engine = get_engine(pl.engine)
        state = engine.restore_state(
            {k: v for k, v in arrays.items() if not k.startswith("extra/")},
            manifest["meta"], spec, pl,
        )
        idx = cls(spec=spec, plan=pl, engine=engine, state=state, n=n, d=d)
        idx._extra_arrays = {
            k[len("extra/"):]: v
            for k, v in arrays.items() if k.startswith("extra/")
        }
        seq = int(manifest["mutation_seq"])
        wal = WriteAheadLog(os.path.join(path, "wal"), fsync=spec.wal_fsync)
        replayed = 0
        for rseq, op, arr in wal.replay(min_seq=seq):
            if op == "insert":
                idx._serialized(
                    engine.insert, state,
                    np.ascontiguousarray(arr, np.float32),
                )
            else:
                idx._serialized(
                    engine.delete, state, np.asarray(arr, np.int64)
                )
            seq = rseq + 1
            replayed += 1
        idx.n = int(getattr(state, "n_live", idx.n))
        idx._store, idx._wal, idx._mutation_seq = store, wal, seq
        idx.plan = pl.replace(reasons=pl.reasons + (
            f"restored from {path} v{version} (format "
            f"{manifest['format']}, snapshot seq "
            f"{manifest['mutation_seq']}, replayed {replayed} WAL "
            "record(s))",
        ))
        return idx

    # ------------------------------------------------------------------
    def query(self, queries: np.ndarray, k: Optional[int] = None) -> QueryResult:
        """k nearest neighbors of every query row.

        Returns a ``QueryResult`` (unpacks as ``(dists, idx)``); ``k``
        defaults to the spec's ``k_hint``.
        """
        k = int(k) if k is not None else self.spec.k_hint
        queries = np.asarray(queries, dtype=np.float32)
        if queries.ndim != 2 or queries.shape[1] != self.d:
            raise ValueError(
                f"queries must be [m, {self.d}], got {queries.shape}"
            )
        if k > self.n:
            raise ValueError(f"k={k} > n={self.n}")
        dists, idx, stats = self._serialized(
            self._engine.query, self._state, queries, k
        )
        self._last_stats = stats
        if getattr(stats, "events", ()):
            # degradation events (device loss re-placement) are plan-level
            # facts: surface them where describe()/reasons readers look
            self.plan = self.plan.replace(
                reasons=self.plan.reasons + tuple(stats.events)
            )
        return QueryResult(
            dists=dists, idx=idx, stats=stats, engine=self.plan.engine, k=k
        )

    def query_stream(
        self, queries: np.ndarray, k: Optional[int] = None, *, on_complete
    ) -> QueryResult:
        """k nearest neighbors with per-row streaming delivery.

        ``on_complete(rows, dists, idx)`` is called from inside the engine's
        round loop as query rows retire — each original row exactly once,
        with finalized values identical to ``query``'s — and the assembled
        batch ``QueryResult`` is returned after the last delivery.  The
        callback runs on the calling thread; keep it cheap (resolve
        futures, push to queues) or the rounds stall behind it.

        Engines declaring ``caps.batch_stream`` (the dynamic forest)
        deliver the WHOLE batch in one ``on_complete`` call instead of
        per-row retirement — coarser latency, same contract otherwise.
        Engines declaring neither raise the typed ``StreamingUnsupported``
        — pin ``engine="streaming"`` for an index that accepts this call
        (``KNNServer`` does exactly that).
        """
        caps = self._engine.caps
        if not (caps.streaming or caps.batch_stream):
            raise StreamingUnsupported(
                f"engine {self.engine_name!r} cannot stream per-row "
                "completions (caps.streaming=False); build with "
                "IndexSpec(engine='streaming')"
            )
        k = int(k) if k is not None else self.spec.k_hint
        queries = np.asarray(queries, dtype=np.float32)
        if queries.ndim != 2 or queries.shape[1] != self.d:
            raise ValueError(
                f"queries must be [m, {self.d}], got {queries.shape}"
            )
        if k > self.n:
            raise ValueError(f"k={k} > n={self.n}")
        dists, idx, stats = self._serialized(
            self._engine.query_stream, self._state, queries, k, on_complete
        )
        self._last_stats = stats
        if getattr(stats, "events", ()):
            # same contract as query(): degradation events (device-loss
            # re-placement) surface where describe()/reasons readers look
            self.plan = self.plan.replace(
                reasons=self.plan.reasons + tuple(stats.events)
            )
        return QueryResult(
            dists=dists, idx=idx, stats=stats, engine=self.plan.engine, k=k
        )

    # -- dual-tree ops (core/dualtree.py) ------------------------------
    def _record_stats(self, stats: SearchStats) -> None:
        self._last_stats = stats
        if getattr(stats, "events", ()):
            # same contract as query(): degradation events are plan-level
            # facts; surface them where describe()/reasons readers look
            self.plan = self.plan.replace(
                reasons=self.plan.reasons + tuple(stats.events)
            )

    def _require_op(self, op: str) -> None:
        if op not in self._engine.caps.ops:
            from repro.api.engine import available_engines

            raise OpUnsupported(
                f"engine {self.engine_name!r} does not declare op {op!r} "
                f"(caps.ops={sorted(self._engine.caps.ops)}); build with "
                f"IndexSpec(op={op!r}) so the planner picks a declaring "
                f"engine ({sorted(available_engines(op=op))})"
            )

    def _check_queries(self, queries: np.ndarray) -> np.ndarray:
        queries = np.asarray(queries, dtype=np.float32)
        if queries.ndim != 2 or queries.shape[1] != self.d:
            raise ValueError(
                f"queries must be [m, {self.d}], got {queries.shape}"
            )
        return queries

    def radius(self, queries: np.ndarray, r: float) -> RadiusResult:
        """All reference points within Euclidean distance ``r`` of each
        query row (inclusive of ``dist == r``).

        Returns a ``RadiusResult`` — CSR over query rows, unpacking as
        ``(indptr, indices, dists)``; ``indices`` are i64 into the
        caller's original ``points`` ordering, ``dists`` ascending per
        row.  Engines not declaring ``"radius"`` in ``caps.ops`` raise
        the typed ``OpUnsupported`` (the same caps-contract as
        ``insert``/``query_stream``).
        """
        self._require_op("radius")
        r = float(r)
        if not r >= 0.0:
            raise ValueError(f"need r >= 0, got {r}")
        queries = self._check_queries(queries)
        indptr, indices, dists, stats = self._serialized(
            self._engine.radius, self._state, queries, r
        )
        self._record_stats(stats)
        return RadiusResult(
            indptr=indptr, indices=indices, dists=dists, stats=stats,
            engine=self.plan.engine, r=r,
        )

    def kde(
        self, queries: np.ndarray, bandwidth: float, *,
        rtol: float = 1e-2, atol: float = 1e-9, kernel: str = "gaussian",
    ) -> StatResult:
        """Kernel density estimate at each query row over the reference
        points (mean of ``K(||q - x|| / bandwidth)``).

        Returns a ``StatResult`` unpacking as ``(densities, error_bound)``
        — ``densities`` f32[m]; ``error_bound`` is the dual-tree
        traversal's accumulated absolute-error bound under the combined
        tolerance ``rtol * density + atol`` (0.0 = computed exactly).
        ``kernel`` is "gaussian" or "tophat" (tophat is always exact).
        Same ``OpUnsupported`` caps-contract as ``radius``.
        """
        self._require_op("kde")
        bandwidth = float(bandwidth)
        if not bandwidth > 0.0:
            raise ValueError(f"need bandwidth > 0, got {bandwidth}")
        queries = self._check_queries(queries)
        dens, err, stats = self._serialized(
            lambda: self._engine.kde(
                self._state, queries, bandwidth,
                rtol=rtol, atol=atol, kernel=kernel,
            )
        )
        self._record_stats(stats)
        return StatResult(
            values=dens, error_bound=float(err), stats=stats,
            engine=self.plan.engine, op="kde",
        )

    def pair_count(self, edges) -> StatResult:
        """2-point correlation: histogram of all ordered cross-pair
        distances of the reference set over ``edges`` (np.histogram
        semantics; self-pairs excluded).

        Returns a ``StatResult`` unpacking as ``(hist, error_bound)`` —
        ``hist`` i64[len(edges) - 1], ``error_bound`` always 0.0 (the op
        is exact).  Same ``OpUnsupported`` caps-contract as ``radius``.
        """
        self._require_op("pair_count")
        edges = np.asarray(edges, dtype=np.float64).ravel()
        # validate here so every declaring engine behaves uniformly (the
        # brute oracle itself does not argue about edges)
        if edges.size < 2 or not np.all(np.diff(edges) > 0):
            raise ValueError("edges must be >= 2 strictly increasing values")
        if edges[0] < 0:
            raise ValueError("distance edges must be >= 0")
        hist, stats = self._serialized(
            self._engine.pair_count, self._state, edges
        )
        self._record_stats(stats)
        return StatResult(
            values=hist, error_bound=0.0, stats=stats,
            engine=self.plan.engine, op="pair_count",
        )

    # ------------------------------------------------------------------
    def insert(self, points: np.ndarray) -> np.ndarray:
        """Incrementally add ``points``; returns their assigned i64 ids.

        Ids are allocated in insertion order (``build``'s points hold
        ``0..n-1``) and are what ``query`` returns, so value arrays
        appended in lockstep stay aligned.  Engines declaring
        ``caps.mutable=False`` raise the typed ``MutabilityError`` — plan
        with ``mutable=True`` (or pin ``engine="dynamic"``) for an index
        that accepts this call.
        """
        if not self._engine.caps.mutable:
            raise MutabilityError(
                f"engine {self.engine_name!r} is immutable "
                "(caps.mutable=False); build with IndexSpec(mutable=True)"
            )
        points = np.asarray(points, dtype=np.float32)
        if points.ndim != 2 or points.shape[1] != self.d:
            raise ValueError(
                f"points must be [b, {self.d}], got {points.shape}"
            )
        ids = self._serialized(self._engine.insert, self._state, points)
        self.n = getattr(self._state, "n_live", self.n + points.shape[0])
        # WAL ordering: append AFTER the engine applied (a rejected batch
        # never pollutes the log), BEFORE the ack returns (an acknowledged
        # mutation is always replayable)
        if self._wal is not None:
            self._wal.append("insert", points, self._mutation_seq)
            self._mutation_seq += 1
        return ids

    def delete(self, ids) -> int:
        """Incrementally remove the given ids; returns the count removed.

        Exact, never best-effort: unknown / already-deleted / duplicated
        ids raise ``KeyError`` and nothing is removed.  Immutable engines
        raise ``MutabilityError`` (see ``insert``).
        """
        if not self._engine.caps.mutable:
            raise MutabilityError(
                f"engine {self.engine_name!r} is immutable "
                "(caps.mutable=False); build with IndexSpec(mutable=True)"
            )
        removed = self._serialized(self._engine.delete, self._state, ids)
        self.n = getattr(self._state, "n_live", self.n - removed)
        if self._wal is not None:
            self._wal.append(
                "delete",
                np.ascontiguousarray(np.asarray(ids, np.int64).ravel()),
                self._mutation_seq,
            )
            self._mutation_seq += 1
        return removed

    # ------------------------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> None:
        """Wait for background index maintenance to settle.

        The dynamic engine runs carry-chain merges on a background worker
        (``Plan.merge_async``); queries are exact regardless, so this is
        only needed when the caller wants a quiesced forest — benchmarks
        measuring steady-state layout, tests asserting the binary-counter
        invariant, or a drain before checkpointing.  Engines without
        background work return immediately.  Re-raises any background
        failure rather than letting it vanish with the worker thread.
        """
        fn = getattr(self._state, "drain_merges", None)
        if fn is not None:
            fn(timeout)

    # ------------------------------------------------------------------
    def warm(
        self, m: Optional[int] = None, k: Optional[int] = None, *,
        ops: Optional[tuple] = None, n_edges: int = 9,
    ) -> None:
        """Precompile the execution path of the given ``ops`` (default:
        the spec's primary ``op``) for batches of ``m`` queries.

        For ``"knn"``, ``k`` neighbors (defaults to the spec's
        ``k_hint``); engines without a warm hook ignore this.  For the
        dual-tree ops, the per-op kernels compile at their rung shapes
        (``n_edges`` = expected pair_count edge count); a non-declaring
        engine raises ``OpUnsupported``.  Serving paths SHOULD call this
        with their expected batch shape before taking traffic so no
        compile lands on a request; the chunked engine warms its fused
        round at the full batch shape AND every compaction-ladder rung,
        making the recompile-free guarantee independent of any particular
        query set's retirement trajectory."""
        ops = tuple(ops) if ops is not None else (self.spec.op,)
        for op in ops:
            if op not in KNOWN_OPS:
                raise ValueError(
                    f"unknown op {op!r}; known: {sorted(KNOWN_OPS)}"
                )
        k = int(k) if k is not None else self.spec.k_hint
        mm = int(m) if m is not None else (self.spec.m_hint or self.spec.tile_q)
        ccd = (
            compile_cache_dir(self.spec.compile_cache_dir)
            if self.spec.compile_cache_dir else None
        )
        before = _compile_cache_entries(ccd) if ccd else 0
        if "knn" in ops:
            warm = getattr(self._state, "warm", None)
            if warm is not None:
                # warming streams chunk slabs through the same store a
                # query uses: stateful engines must not see both at once
                self._serialized(warm, mm, k)
        dual = tuple(op for op in ops if op != "knn")
        if dual:
            for op in dual:
                self._require_op(op)
            self._serialized(
                self._engine.warm_ops, self._state, dual,
                int(m) if m is not None else self.spec.m_hint, n_edges,
            )
        if ccd:
            # hit/miss accounting: a warm cache deserializes executables
            # (entry count unchanged); a cold one compiles and adds them
            delta = _compile_cache_entries(ccd) - before
            tag = (
                f"miss: compiled {delta} new executable(s)"
                if delta else "hit: served from disk"
            )
            self.plan = self.plan.replace(reasons=self.plan.reasons + (
                f"compile cache {tag} for warm(m={mm}, k={k}, "
                f"ops={list(ops)}) ({before + max(delta, 0)} total)",
            ))

    @property
    def engine_name(self) -> str:
        return self.plan.engine

    @property
    def height(self) -> int:
        return self.plan.height

    @property
    def scan_backend(self) -> Optional[str]:
        """The leaf-scan kernel backend the built engine runs ("pallas",
        "pallas_interpret" or "ref"), resolved from ``spec.backend`` and the
        platform; None for engines without a leaf-scan tier."""
        return getattr(self._state, "scan_backend", None)

    @property
    def stats(self) -> SearchStats:
        """Stats of the most recent ``query`` (immutable; empty before).

        Only the tiny stats snapshot is retained — never the result arrays.
        """
        return self._last_stats if self._last_stats is not None else SearchStats()

    def resident_bytes(self) -> int:
        """Per-device bytes the reference structure occupies — measured
        from the built state where the engine supports it, otherwise the
        plan-time estimate the planner compared against ``memory_budget``
        (one hook either way: ``Engine.resident_bytes``)."""
        return self._engine.resident_bytes(self.plan, self._state)

    def describe(self) -> str:
        """Human-readable plan summary (engine, parameters, reasons)."""
        pl = self.plan
        lines = [
            f"KNNIndex: n={self.n} d={self.d} engine={pl.engine} "
            f"h={pl.height} n_chunks={pl.n_chunks} n_shards={pl.n_shards} "
            f"B={pl.buffer_size} resident~{pl.resident_bytes / 1e6:.1f}MB"
            + (f" scan={self.scan_backend}" if self.scan_backend else ""),
        ]
        lines += [f"  - {r}" for r in pl.reasons]
        return "\n".join(lines)
