"""Registered engines: the repo's five kNN implementations behind one door.

Every engine answers exact kNN; they differ in *where the data lives and
how the work is scheduled* — which is precisely what the planner chooses on:

  brute    tiled brute-force streaming (paper baseline (3); also the oracle)
  kdtree   classic unbuffered k-d traversal on the host (paper baseline (2))
  host     paper-faithful Alg. 1: host queues/buffers + jitted device phases
  chunked  chunk-resident bulk-synchronous LazySearch (§3 out-of-core path)
  jit      fully-jitted device-resident fixed point (lazy_knn_jit)
  sharded  paper §3.2 query chunking: one tree replica per device
  forest   per-shard buffer k-d trees under shard_map + all-gather merge
  ring     reference shards resident, query blocks rotated over the ICI
  dynamic  batch-dynamic logarithmic-method forest of static shards — the
           one MUTABLE engine (insert/delete); see core/dynamic.py
  streaming  the chunked tier with per-row early retirement: query_stream
           emits each query's finalized result the round it retires instead
           of at batch end; the serving tier's engine (core/streaming.py)

Engines translate their implementation's native conventions (squared vs
Euclidean distances, local vs global ids, i32 vs i64) into the one
``QueryResult`` contract: ascending Euclidean f32[m, k] distances and
i64[m, k] ids in the caller's original ordering.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import numpy as np

from repro.api.engine import EngineBase, EngineCaps, register_engine
from repro.api.planner import _round_up
from repro.core.lazysearch import BufferKDTree, SearchStats

__all__ = []  # engines are reached through the registry, not imports


def _as_out(dists_sq_or_e: np.ndarray, idx: np.ndarray, *, squared: bool):
    d = np.asarray(dists_sq_or_e, np.float32)
    if squared:
        d = np.sqrt(np.maximum(d, 0.0))
    i = np.asarray(idx)
    if i.dtype != np.int64:
        i = i.astype(np.int64)
    return d, i


def _resolve_tq(tile_q: int, backend: str) -> int:
    """Query-tile width for the fused jit engines (shared heuristic)."""
    from repro.kernels import ops as kops

    return kops.engine_tile_q(tile_q, backend)


def _chunked_resident(plan) -> int:
    """Device bytes of a chunk-streamed leaf structure: a chunk holds
    ceil(n_leaves/N) leaf slabs (``ChunkedLeafStore``), two chunks stay
    resident.  Quantized stores keep their dequantize metadata (per-leaf
    scale/offset/dead mask) resident for every leaf, not per chunk."""
    from repro.api.planner import estimate_meta_bytes

    meta = estimate_meta_bytes(
        plan.n, plan.d, plan.height, precision=plan.precision
    )
    if plan.n_chunks <= 1:
        return plan.slab_bytes + meta
    n_leaves = 1 << plan.height
    leaf_bytes = plan.slab_bytes // n_leaves
    return 2 * (-(-n_leaves // plan.n_chunks)) * leaf_bytes + meta


# ---------------------------------------------------------------------------
@register_engine
class BruteEngine(EngineBase):
    name = "brute"
    # NOT out_of_core: knn_brute keeps the whole padded reference set
    # device-resident (only the distance tiles stream)
    caps = EngineCaps(
        exact=True, out_of_core=False, multi_device=False, needs_build=False,
        ops=frozenset({"knn", "radius", "kde", "pair_count"}),
        description="tiled brute-force streaming (baseline/oracle)",
    )

    def build(self, points, spec, plan):
        return np.ascontiguousarray(points, np.float32)

    def query(self, state, queries, k):
        from repro.core.brute import knn_brute

        d, i = knn_brute(queries, state, k)
        stats = SearchStats(
            iterations=1,
            points_scanned=queries.shape[0] * state.shape[0],
            queries_advanced=queries.shape[0],
        )
        return d, i, stats

    def radius(self, state, queries, r):
        from repro.core.dualtree import radius_brute

        queries = np.asarray(queries, np.float32)
        ip, ix, dd = radius_brute(queries, state, float(r))
        stats = SearchStats(
            iterations=1,
            points_scanned=queries.shape[0] * state.shape[0],
            queries_advanced=queries.shape[0],
        )
        return ip, ix, dd, stats

    def kde(self, state, queries, bandwidth, *, rtol=1e-2, atol=1e-9,
            kernel="gaussian"):
        from repro.core.dualtree import kde_brute

        queries = np.asarray(queries, np.float32)
        dens = kde_brute(queries, state, float(bandwidth), kernel=kernel)
        stats = SearchStats(
            iterations=1,
            points_scanned=queries.shape[0] * state.shape[0],
            queries_advanced=queries.shape[0],
        )
        return dens, 0.0, stats  # exact all-pairs sum: no traversal error

    def pair_count(self, state, edges):
        from repro.core.dualtree import pair_count_brute

        hist = pair_count_brute(state, edges)
        stats = SearchStats(
            iterations=1, points_scanned=state.shape[0] * state.shape[0]
        )
        return hist, stats

    def snapshot_state(self, state):
        return {"points": np.asarray(state)}, {}

    def restore_state(self, arrays, meta, spec, plan):
        return np.ascontiguousarray(arrays["points"], np.float32)

    def resident_bytes(self, plan, state=None) -> int:
        # the padded reference set (knn_brute's tile_x granularity), not a
        # leaf structure — no tree is ever built
        return _round_up(plan.n, 16384) * _round_up(plan.d, 8) * 4


# ---------------------------------------------------------------------------
@register_engine
class HostKDTreeEngine(EngineBase):
    name = "kdtree"
    caps = EngineCaps(
        exact=True, out_of_core=True, multi_device=False,
        description="classic unbuffered k-d traversal (CPU baseline)",
    )

    def build(self, points, spec, plan):
        from repro.core.toptree import build_top_tree

        return build_top_tree(np.asarray(points, np.float32), plan.height)

    def query(self, state, queries, k):
        from repro.core.hostkdtree import knn_host_kdtree

        d, i = knn_host_kdtree(queries, state, k)
        stats = SearchStats(queries_advanced=queries.shape[0])
        return d, i, stats

    def snapshot_state(self, state):
        from repro.core.toptree import tree_to_arrays

        arrays = dict(tree_to_arrays(state, include_derived=True))
        return arrays, {"height": state.height, "leaf_pad": state.leaf_pad}

    def restore_state(self, arrays, meta, spec, plan):
        from repro.core.toptree import tree_from_arrays

        return tree_from_arrays(
            np.ascontiguousarray(arrays["points"], np.float32),
            arrays,
            height=int(meta["height"]),
            leaf_pad=int(meta["leaf_pad"]),
        )

    def resident_bytes(self, plan, state=None) -> int:
        return 0  # pure host numpy: nothing lives on a device


# ---------------------------------------------------------------------------
class _BufferTreeEngine(EngineBase):
    """Shared build/query for the two ``BufferKDTree`` tiers."""

    _tier = ""  # "host" | "chunked"

    def build(self, points, spec, plan):
        return BufferKDTree(
            points,
            height=plan.height,
            n_chunks=plan.n_chunks,
            buffer_size=plan.buffer_size,
            fetch_m=plan.fetch_m,
            tile_q=plan.tile_q,
            backend=plan.backend,
            engine=self._tier,
            starvation_deadline=plan.starvation_deadline,
            device=spec.devices[0] if spec.devices else None,
            precision=plan.precision,
        )

    def query(self, state: BufferKDTree, queries, k):
        d, i = state.query(queries, k=k)
        return d, i, state.stats  # per-call immutable snapshot

    # -- dual-tree ops: node-pair frontier over the SAME TopTree +
    # ChunkedLeafStore the kNN rounds use (core/dualtree.py) -------------
    def radius(self, state: BufferKDTree, queries, r):
        return state.dualtree().radius(
            np.asarray(queries, np.float32), float(r)
        )

    def kde(self, state: BufferKDTree, queries, bandwidth, *, rtol=1e-2,
            atol=1e-9, kernel="gaussian"):
        return state.dualtree().kde(
            np.asarray(queries, np.float32), float(bandwidth),
            rtol=rtol, atol=atol, kernel=kernel,
        )

    def pair_count(self, state: BufferKDTree, edges):
        return state.dualtree().pair_count(edges)

    def warm_ops(self, state: BufferKDTree, ops, m=None, n_edges=9):
        dual = [op for op in ops if op != "knn"]
        if dual:
            state.dualtree().warm(dual, m=m, n_edges=n_edges)

    def snapshot_state(self, state: BufferKDTree):
        from repro.core.toptree import tree_to_arrays

        tree = state.tree
        arrays = dict(tree_to_arrays(tree, include_derived=True))
        meta = {"height": tree.height, "leaf_pad": tree.leaf_pad,
                "precision": state.precision}
        if state.store.quantized:
            # persist the codes as stored (plus scales/offsets/dead mask):
            # the fp32 ``points`` stay in the snapshot for the exact
            # re-rank, but the slabs round-trip at the quantized dtype
            arrays.update(state.store.quantized_state().to_arrays())
        return arrays, meta

    def restore_state(self, arrays, meta, spec, plan):
        from repro.core.quantize import QuantizedSlabs
        from repro.core.toptree import tree_from_arrays

        tree = tree_from_arrays(
            np.ascontiguousarray(arrays["points"], np.float32),
            arrays,
            height=int(meta["height"]),
            leaf_pad=int(meta["leaf_pad"]),
        )
        # format-1 snapshots predate the precision field: absent => fp32
        precision = str(meta.get("precision", "fp32"))
        store_state = None
        if precision != "fp32":
            store_state = QuantizedSlabs.from_arrays(arrays, precision)
        # tree= skips the O(h*n) median build; only the chunk slabs and
        # the jitted scans are (re)materialized, lazily
        return BufferKDTree(
            tree.points,
            tree=tree,
            n_chunks=plan.n_chunks,
            buffer_size=plan.buffer_size,
            fetch_m=plan.fetch_m,
            tile_q=plan.tile_q,
            backend=plan.backend,
            engine=self._tier,
            starvation_deadline=plan.starvation_deadline,
            device=spec.devices[0] if spec.devices else None,
            precision=precision,
            store_state=store_state,
        )

    def resident_bytes(self, plan, state=None) -> int:
        if state is not None:
            return state.store.resident_bytes()   # measured, not estimated
        return _chunked_resident(plan)


@register_engine
class HostLoopEngine(_BufferTreeEngine):
    name = "host"
    _tier = "host"
    caps = EngineCaps(
        exact=True, out_of_core=True, multi_device=False,
        stateful_query=True,
        ops=frozenset({"knn", "radius", "kde", "pair_count"}),
        description="paper-faithful Alg. 1 host loop (reference tier)",
    )


@register_engine
class ChunkedEngine(_BufferTreeEngine):
    name = "chunked"
    _tier = "chunked"
    caps = EngineCaps(
        exact=True, out_of_core=True, multi_device=False,
        stateful_query=True,
        ops=frozenset({"knn", "radius", "kde", "pair_count"}),
        description="chunk-resident bulk-synchronous LazySearch (§3)",
    )


@register_engine
class StreamingEngine(_BufferTreeEngine):
    """The chunked tier plus per-row streaming delivery.

    Identical build/state/batch-query to ``chunked`` (so it inherits the
    whole parity suite); adds ``query_stream``, which runs the same round
    loop with the early-retirement hook attached and emits each row's
    finalized result the round it retires.  Never auto-picked by the
    planner — pinned by callers that serve online traffic (``KNNServer``).
    """

    name = "streaming"
    _tier = "chunked"
    caps = EngineCaps(
        exact=True, out_of_core=True, multi_device=False,
        stateful_query=True, streaming=True,
        ops=frozenset({"knn", "radius", "kde", "pair_count"}),
        description="chunked tier + per-row early-retirement streaming "
                    "(the online serving engine)",
    )

    def query_stream(self, state: BufferKDTree, queries, k, emit):
        from repro.core.streaming import stream_query

        d, i, stats = stream_query(state, queries, k, emit)
        return d, i, stats


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _JitState:
    tree: Any
    first_leaf_heap: int
    d: int
    tq: int
    backend: str


@register_engine
class JitEngine(EngineBase):
    name = "jit"
    caps = EngineCaps(
        exact=True, out_of_core=False, multi_device=False,
        description="fully-jitted device-resident fixed point",
    )

    def build(self, points, spec, plan):
        import jax

        from repro.core.jitsearch import tree_arrays_from
        from repro.core.toptree import build_top_tree

        top = build_top_tree(np.asarray(points, np.float32), plan.height)
        tree = tree_arrays_from(top)
        if spec.devices:
            # committed inputs pin the jitted fixed point to this device
            tree = jax.tree.map(
                lambda a: jax.device_put(a, spec.devices[0]), tree
            )
        return _JitState(
            tree=tree,
            first_leaf_heap=top.first_leaf_heap,
            d=top.d,
            tq=_resolve_tq(plan.tile_q, plan.backend),
            backend=plan.backend,
        )

    def query(self, state: _JitState, queries, k):
        import jax.numpy as jnp

        from repro.core.jitsearch import lazy_knn_jit
        from repro.kernels import ops as kops

        backend = (
            kops.default_backend() if state.backend == "auto" else state.backend
        )
        m, d = queries.shape
        d_pad = state.tree.slabs.shape[-1]
        qpad = np.zeros((m, d_pad), np.float32)
        qpad[:, :d] = queries
        d2, oi, rounds = lazy_knn_jit(
            jnp.asarray(qpad), state.tree, k=k, tq=state.tq,
            first_leaf_heap=state.first_leaf_heap, backend=backend,
        )
        dists, idx = _as_out(np.asarray(d2), np.asarray(oi), squared=True)
        stats = SearchStats(
            iterations=int(rounds), queries_advanced=int(rounds) * m
        )
        return dists, idx, stats

    def snapshot_state(self, state: _JitState):
        arrays = {
            f"tree/{name}": np.asarray(value)
            for name, value in state.tree._asdict().items()
        }
        meta = {
            "first_leaf_heap": state.first_leaf_heap,
            "d": state.d,
            "tq": state.tq,
            "backend": state.backend,
        }
        return arrays, meta

    def restore_state(self, arrays, meta, spec, plan):
        import jax
        import jax.numpy as jnp

        from repro.core.jitsearch import TreeArrays

        tree = TreeArrays(
            **{
                name: jnp.asarray(arrays[f"tree/{name}"])
                for name in TreeArrays._fields
            }
        )
        if spec.devices:
            tree = jax.tree.map(
                lambda a: jax.device_put(a, spec.devices[0]), tree
            )
        return _JitState(
            tree=tree,
            first_leaf_heap=int(meta["first_leaf_heap"]),
            d=int(meta["d"]),
            tq=int(meta["tq"]),
            backend=str(meta["backend"]),
        )


# ---------------------------------------------------------------------------
@register_engine
class ShardedEngine(EngineBase):
    name = "sharded"
    # stateful, but MultiDeviceTrees carries its own lock — the facade
    # need not serialize on top of it
    caps = EngineCaps(
        exact=True, out_of_core=True, multi_device=True,
        description="paper §3.2 query chunking: one tree engine per device",
    )

    def build(self, points, spec, plan):
        from repro.distributed.sharded import MultiDeviceTrees

        return MultiDeviceTrees(
            points,
            devices=list(spec.devices) if spec.devices else None,
            height=plan.height,
            n_chunks=plan.n_chunks,
            backend=plan.backend,
            tile_q=plan.tile_q,
            buffer_size=plan.buffer_size,
            starvation_deadline=plan.starvation_deadline,
            precision=plan.precision,
        )

    def query(self, state, queries, k):
        # per-engine stats snapshots are captured under the state's lock,
        # so concurrent batches can't clobber this aggregation
        d, i, _, ran = state.query_with_active(queries, k)
        agg = SearchStats(
            iterations=max((s.iterations for s in ran), default=0),
            flushes=sum(s.flushes for s in ran),
            units_scanned=sum(s.units_scanned for s in ran),
            points_scanned=sum(s.points_scanned for s in ran),
            queries_advanced=sum(s.queries_advanced for s in ran),
            chunk_rounds=sum(s.chunk_rounds for s in ran),
        )
        return d, i, agg

    def resident_bytes(self, plan, state=None) -> int:
        if state is not None:
            return state.resident_bytes()         # measured, not estimated
        # per device (the whole structure is replicated, chunk-streamed)
        return _chunked_resident(plan)


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _ForestState:
    stacked: Any
    offsets: Any
    mesh: Any
    first_leaf_heap: int
    d: int
    d_pad: int
    tq: int
    backend: str


def _mesh_over(devices: Optional[Tuple[Any, ...]], p: int, axis: str):
    import jax

    devs = list(devices) if devices else jax.devices()
    if len(devs) < p:
        raise ValueError(f"need {p} devices, have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:p]), (axis,))


@register_engine
class ForestEngine(EngineBase):
    name = "forest"
    caps = EngineCaps(
        exact=True, out_of_core=True, multi_device=True,
        description="per-shard buffer k-d trees + all-gather top-k merge",
    )

    AXIS = "knn"

    def build(self, points, spec, plan):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.distributed.forest import build_forest, stack_forest

        points = np.asarray(points, np.float32)
        n = points.shape[0]
        ns = plan.n_shards
        if n % ns:
            raise ValueError(
                f"forest engine needs n % n_shards == 0 (n={n}, "
                f"n_shards={ns}); the planner falls back to 'sharded' for "
                "uneven sets"
            )
        trees, offsets = build_forest(points, ns, height=plan.height)
        mesh = _mesh_over(spec.devices, ns, self.AXIS)
        # each device holds its own shard's tree from build on, so a query
        # moves only the queries and the [m, k] candidate lists
        shard = NamedSharding(mesh, PartitionSpec(self.AXIS))
        stacked, offsets = jax.device_put(
            (stack_forest(trees), offsets), shard
        )
        return _ForestState(
            stacked=stacked,
            offsets=offsets,
            mesh=mesh,
            first_leaf_heap=1 << plan.height,
            d=points.shape[1],
            d_pad=int(trees[0].slabs.shape[-1]),
            tq=_resolve_tq(plan.tile_q, plan.backend),
            backend=plan.backend,
        )

    def query(self, state: _ForestState, queries, k):
        import jax.numpy as jnp

        from repro.distributed.forest import forest_knn
        from repro.kernels import ops as kops

        backend = (
            kops.default_backend() if state.backend == "auto" else state.backend
        )
        m = queries.shape[0]
        qpad = np.zeros((m, state.d_pad), np.float32)
        qpad[:, : state.d] = queries
        fd, fi = forest_knn(
            jnp.asarray(qpad), state.stacked, state.offsets, k=k,
            tq=state.tq, first_leaf_heap=state.first_leaf_heap,
            mesh=state.mesh, axis=self.AXIS, backend=backend,
        )
        dists, idx = _as_out(np.asarray(fd), np.asarray(fi), squared=True)
        stats = SearchStats(queries_advanced=m)
        return dists, idx, stats

    def resident_bytes(self, plan, state=None) -> int:
        return plan.slab_bytes // max(1, plan.n_shards)


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _RingState:
    refs: Any          # f32[n_padded, d] device array (PAD_COORD rows appended)
    mesh: Any
    n: int
    d: int
    p: int


@register_engine
class RingEngine(EngineBase):
    name = "ring"
    caps = EngineCaps(
        exact=True, out_of_core=True, multi_device=True, needs_build=False,
        description="resident reference shards, query blocks ringed (ICI)",
    )

    AXIS = "knn"

    def build(self, points, spec, plan):
        import jax.numpy as jnp

        from repro.kernels.ref import PAD_COORD

        points = np.asarray(points, np.float32)
        n, d = points.shape
        p = plan.n_shards
        n_pad = _round_up(n, p)
        if n_pad != n:
            pad = np.full((n_pad - n, d), np.float32(PAD_COORD))
            points = np.concatenate([points, pad])
        return _RingState(
            refs=jnp.asarray(points),
            mesh=_mesh_over(spec.devices, p, self.AXIS),
            n=n, d=d, p=p,
        )

    def query(self, state: _RingState, queries, k):
        import jax.numpy as jnp

        from repro.distributed.ring_knn import ring_knn_brute

        m = queries.shape[0]
        m_pad = _round_up(m, state.p)
        q = queries
        if m_pad != m:
            q = np.concatenate(
                [queries, np.zeros((m_pad - m, state.d), np.float32)]
            )
        d2, gi = ring_knn_brute(
            jnp.asarray(q), state.refs, k=k, mesh=state.mesh, axis=self.AXIS
        )
        dists, idx = _as_out(
            np.asarray(d2)[:m], np.asarray(gi)[:m], squared=True
        )
        idx[idx >= state.n] = -1  # PAD_COORD rows can't win while k <= n
        stats = SearchStats(
            iterations=state.p,
            points_scanned=m * state.n,
            queries_advanced=m,
        )
        return dists, idx, stats

    def resident_bytes(self, plan, state=None) -> int:
        # raw reference shard per chip (no leaf-structure padding)
        p = max(1, plan.n_shards)
        return _round_up(plan.n, p) * plan.d * 4 // p


# ---------------------------------------------------------------------------
@register_engine
class DynamicEngine(EngineBase):
    name = "dynamic"
    # stateful_query: shards above the brute cutoff are BufferKDTree
    # instances, whose queries mutate queues/chunk slots — and insert/
    # delete rebuild shards, so the facade's lock serializes all three.
    # device_parallel_mutable: shard rungs are immutable, so the forest
    # places them across devices like the static engines place trees —
    # mutability and multi-device scaling compose (ISSUE 5 tentpole).
    caps = EngineCaps(
        exact=True, out_of_core=True, multi_device=True,
        stateful_query=True, mutable=True, device_parallel_mutable=True,
        batch_stream=True,
        description="batch-dynamic logarithmic-method forest "
                    "(incremental insert/delete, device-placed shards)",
    )

    def build(self, points, spec, plan):
        from repro.api.planner import BRUTE_N_MAX
        from repro.core.dynamic import DEFAULT_BASE_CAPACITY, DynamicIndex

        idx = DynamicIndex(
            points.shape[1] if points.ndim == 2 else 0,
            # shard rungs are B * 2^i with B from the plan's buffer size,
            # capped at the default so footnote-8 buffers on shallow trees
            # don't inflate the smallest rung
            base_capacity=min(plan.buffer_size, DEFAULT_BASE_CAPACITY),
            brute_cutoff=BRUTE_N_MAX,
            rebuild_crossover=plan.crossover_batch,
            tile_q=plan.tile_q,
            backend=plan.backend,
            devices=list(spec.devices) if spec.devices else None,
            merge_async=plan.merge_async,
            precision=plan.precision,
            memory_budget=spec.memory_budget,
        )
        # WARM-AT-BUILD: register the expected batch shape BEFORE the
        # first insert so the initial shard — and every later shard,
        # including background staging shards — precompiles its scan at
        # construction instead of on the first query that touches it
        if spec.m_hint:
            idx.warm(spec.m_hint, spec.k_hint)
        idx.insert(np.asarray(points, np.float32))
        return idx

    def query(self, state, queries, k):
        return state.query(queries, k)

    def query_stream(self, state, queries, k, emit):
        # batch_stream: the forest has no per-row retirement map, so the
        # whole batch is delivered in ONE emit when the fan-out returns —
        # coarser latency than the streaming engine, but it lets KNNServer
        # front a live mutable index (and inherit its device-loss
        # degradation: stats.events ride back to the server).
        d, i, stats = state.query(queries, k)
        emit(np.arange(queries.shape[0], dtype=np.int64), d, i)
        return d, i, stats

    def insert(self, state, points):
        return state.insert(points)

    def delete(self, state, ids):
        return state.delete(ids)

    def snapshot_state(self, state):
        return state.snapshot()

    def restore_state(self, arrays, meta, spec, plan):
        from repro.core.dynamic import DynamicIndex

        idx = DynamicIndex.restore(
            arrays, meta,
            devices=list(spec.devices) if spec.devices else None,
        )
        if spec.m_hint:
            idx.warm(spec.m_hint, spec.k_hint)
        return idx

    def resident_bytes(self, plan, state=None) -> int:
        if state is not None:
            return state.resident_bytes()         # measured, not estimated
        # worst case per DEVICE: the largest rung holds ~all n points in
        # one power-of-two padded slab (~2x the flat slab) and a rung is
        # never split across devices, so placement does NOT shrink the
        # worst-device estimate — it only spreads the smaller rungs.  The
        # measured path (state.resident_bytes) reports the true max.
        return 2 * plan.slab_bytes
