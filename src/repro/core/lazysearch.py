"""LazySearch: the buffer k-d tree query engine (paper Algorithm 1 + §3.2).

Three engine tiers share one traversal state machine (``traversal.py``), one
work-plan shape and one leaf-scan kernel contract:

  * ``engine="host"`` — the paper-faithful HOST LOOP: queues, leaf buffers
    and work plans live on the host (as in the paper), wrapped around three
    jitted device phases
        FindLeafBatch      -> traversal.advance      (vectorized descent)
        ProcessAllBuffers  -> kernels.ops.leaf_scan  (brute leaf scans)
                              + _merge_knn           (running top-k update)
        re-insert          -> traversal.exit_leaf
    Pedagogical/reference tier; every flush costs host round trips.
  * ``engine="chunked"`` (default) — CHUNK-RESIDENT bulk-synchronous engine
    (``chunked_jit.ChunkResidentEngine``): the host only streams leaf-
    structure chunks (double-buffered ``ChunkedLeafStore``) and reads one
    i32[m] pending-leaf map per round; everything else — plan construction,
    leaf scans, top-k merge, leaf exit, re-advance — is ONE fused jitted
    call per chunk visit, with the neighbor state donated (updated in
    place).  The paper's B/2 buffer-fill rule becomes the chunk-visit
    scheduling policy.  This is the out-of-core fast path.
  * ``jitsearch.lazy_knn_jit`` — FULLY-JITTED device-resident fixed point
    (one ``lax.while_loop``, no host involvement), for reference sets that
    fit on the device; the per-device body of ``distributed/forest.py``.

The leaf structure is held by a ``ChunkedLeafStore`` (paper §3: host-resident
slabs, two device chunk buffers, compute/copy overlap).  ``n_chunks=1``
reproduces the original ICML'14 device-resident workflow.

Defaults follow the paper's footnote 8: for tree height h, buffer capacity
B = 2^(24-h) and fetch size M = 10 B (both capped so CPU-scale runs stay
sane; the paper notes values "did not have a significant influence ... as
long as they were set to reasonable values").
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import traversal
from repro.core.buffers import LeafBuffers, QueryQueues, build_work_plan
from repro.core.chunked import ChunkedLeafStore
from repro.core.chunked_jit import (
    DEFAULT_STARVATION_DEADLINE,
    ChunkResidentEngine,
)
from repro.core.quantize import QUANT_OVERFETCH, QuantizedSlabs
from repro.core.toptree import (
    TopTree,
    build_top_tree,
    default_buffer_size,
    suggest_height,
)
from repro.kernels import ops as kops

__all__ = ["BufferKDTree", "SearchStats", "PLAN_LADDER", "finalize_candidates"]


def finalize_candidates(
    tree: TopTree, queries: np.ndarray, gi: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact rescoring of engine candidates for a (sub)set of query rows.

    The MXU decomposition ||q||^2 - 2qx + ||x||^2 carries O(eps * |q||x|)
    absolute error — at near-zero distances the relative error explodes
    (duplicate/self queries).  Recompute the k selected candidates directly
    ((q-x)^2, error O(eps * d^2)) and re-sort; FAISS-style refinement, cost
    O(r k d).  ``queries`` is f32[r, d] (original feature dim), ``gi`` is
    i32[r, k] reordered-global indices; returns (dists f32[r, k] ascending
    Euclidean, idx i64[r, k] in the caller's original point ordering).
    Shared by the batch return path and the streaming engine's per-row
    early-retirement emissions.
    """
    safe = np.clip(gi, 0, None)
    diff = tree.points[safe] - queries[:, None, :]
    d2 = np.einsum("mkd,mkd->mk", diff, diff)
    d2[gi < 0] = np.inf
    order = np.argsort(d2, axis=1, kind="stable")
    d2 = np.take_along_axis(d2, order, axis=1)
    gi = np.take_along_axis(gi, order, axis=1)
    dists = np.sqrt(np.maximum(d2, 0.0))
    idx_out = tree.orig_idx[np.clip(gi, 0, None)].astype(np.int64)
    idx_out[gi < 0] = -1
    return dists, idx_out


@dataclasses.dataclass(frozen=True)
class SearchStats:
    """Immutable per-call search statistics.

    Every ``query`` produces a fresh instance (returned in the api layer's
    ``QueryResult`` and readable via the ``BufferKDTree.stats`` property,
    which reflects the most recent call) — stats are values, not state
    mutated across calls.
    """

    iterations: int = 0
    flushes: int = 0
    units_scanned: int = 0
    points_scanned: int = 0
    queries_advanced: int = 0
    chunk_rounds: int = 0
    plan_shapes: int = 0     # distinct padded plan widths seen (host engine)
    # chunked-engine round-loop phase breakdown (zero elsewhere)
    compactions: int = 0     # ladder rungs entered
    steady_rounds: int = 0   # rounds at the full batch shape
    tail_rounds: int = 0     # rounds at a compacted ladder rung
    rows_scanned: int = 0    # query rows occupying a slot of a scanned
                             # tile, summed over rounds (<= units * tq)
    steady_s: float = 0.0    # wall seconds in steady-state rounds
    tail_s: float = 0.0      # wall seconds in tail (compacted) rounds
    sync_wait_s: float = 0.0  # wall seconds blocked on schedule readbacks
                              # and compaction barriers
    early_retired: int = 0   # rows delivered by the streaming hook BEFORE
                             # the round loop finished (0 on batch queries)
    # operational events absorbed during the call (e.g. a device loss the
    # dynamic engine degraded around); also appended to Plan.reasons by
    # the api facade so post-hoc `describe()` shows them
    events: Tuple[str, ...] = ()


class _StatsBuilder:
    """Mutable per-call accumulator; frozen into ``SearchStats`` at return."""

    def __init__(self):
        self.iterations = 0
        self.flushes = 0
        self.units_scanned = 0
        self.points_scanned = 0
        self.queries_advanced = 0
        self.chunk_rounds = 0
        self.plan_widths = set()
        self.compactions = 0
        self.steady_rounds = 0
        self.tail_rounds = 0
        self.rows_scanned = 0
        self.steady_s = 0.0
        self.tail_s = 0.0
        self.sync_wait_s = 0.0
        self.early_retired = 0

    @classmethod
    def from_engine(cls, info: dict, slab_rows: int) -> "_StatsBuilder":
        """The counters of one ``ChunkResidentEngine.run`` (its ``info``);
        ``slab_rows`` is the padded leaf length a work unit scans."""
        sb = cls()
        sb.iterations = info["rounds"]
        sb.flushes = info["rounds"]
        sb.chunk_rounds = info["chunk_rounds"]
        sb.units_scanned = info["units"]
        sb.points_scanned = info["units"] * slab_rows
        sb.rows_scanned = info["rows"]
        sb.queries_advanced = info["queries_advanced"]
        sb.compactions = info["compactions"]
        sb.steady_rounds = info["steady_rounds"]
        sb.tail_rounds = info["tail_rounds"]
        sb.steady_s = info["steady_s"]
        sb.tail_s = info["tail_s"]
        sb.sync_wait_s = info["sync_wait_s"]
        sb.early_retired = info.get("early_retired", 0)
        return sb

    def freeze(self) -> SearchStats:
        return SearchStats(
            iterations=self.iterations,
            flushes=self.flushes,
            units_scanned=self.units_scanned,
            points_scanned=self.points_scanned,
            queries_advanced=self.queries_advanced,
            chunk_rounds=self.chunk_rounds,
            plan_shapes=len(self.plan_widths),
            compactions=self.compactions,
            steady_rounds=self.steady_rounds,
            tail_rounds=self.tail_rounds,
            rows_scanned=self.rows_scanned,
            steady_s=self.steady_s,
            tail_s=self.tail_s,
            sync_wait_s=self.sync_wait_s,
            early_retired=self.early_retired,
        )


# Fixed ladder of padded work-plan widths, shared across flushes, queries and
# trees: every host-engine flush pads its W work units up to a rung, so the
# number of jitted scan/merge specializations is bounded by len(PLAN_LADDER)
# for the LIFETIME OF THE PROCESS — not by how many distinct W values flushes
# happen to produce (the old power-of-two rounding gave up to 2x as many
# shapes, and any fresh W between flushes meant a fresh XLA compile).
PLAN_LADDER = (16, 64, 256, 1024, 4096, 16384, 65536)


def _plan_pad(w: int) -> int:
    """Smallest ladder rung >= w (quadrupling beyond the table)."""
    for rung in PLAN_LADDER:
        if w <= rung:
            return rung
    rung = PLAN_LADDER[-1]
    while rung < w:
        rung *= 4
    return rung


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_knn(
    knn_d: jnp.ndarray,       # f32[m+1, k] squared dists (row m = dump)
    knn_i: jnp.ndarray,       # i32[m+1, k] reordered-global indices
    unit_q: jnp.ndarray,      # i32[W, TQ]  (-1 padded)
    new_d: jnp.ndarray,       # f32[W, TQ, kl]  (kl = min(k, L_pad))
    new_li: jnp.ndarray,      # i32[W, TQ, kl] local slab indices
    new_dead: jnp.ndarray,    # bool[W, TQ, kl] selected-row-is-dead mask
    unit_start: jnp.ndarray,  # i32[W] leaf_start per unit
    unit_size: jnp.ndarray,   # i32[W] leaf size per unit
    *,
    k: int,
):
    m = knn_d.shape[0] - 1
    w, tq = unit_q.shape
    kl = new_li.shape[-1]
    flat_q = unit_q.reshape(-1)
    safe_q = jnp.where(flat_q < 0, m, flat_q)

    valid = (new_li < unit_size[:, None, None]) & ~new_dead    # padded/dead rows
    gidx = jnp.where(valid, new_li + unit_start[:, None, None], -1)
    nd = jnp.where(valid, new_d, jnp.float32(kops.INVALID_DIST)).reshape(-1, kl)
    ni = gidx.reshape(-1, kl)

    cur_d = knn_d[safe_q]
    cur_i = knn_i[safe_q]
    cd = jnp.concatenate([cur_d, nd], axis=1)                   # [F, 2k]
    ci = jnp.concatenate([cur_i, ni], axis=1)
    neg, sel = jax.lax.top_k(-cd, k)
    d2 = -neg
    i2 = jnp.take_along_axis(ci, sel, axis=1)
    return knn_d.at[safe_q].set(d2), knn_i.at[safe_q].set(i2)


@functools.partial(jax.jit, static_argnames=("first_leaf_heap", "k"))
def _advance_batch(
    node: jnp.ndarray,        # i32[M] gathered traversal nodes (-padded w/ 0)
    fromc: jnp.ndarray,       # i32[M]
    idx: jnp.ndarray,         # i32[M] query ids (-1 padded)
    queries: jnp.ndarray,     # f32[m, d] (un-padded feature dim is fine here)
    knn_d: jnp.ndarray,       # f32[m+1, k]
    split_dim: jnp.ndarray,
    split_val: jnp.ndarray,
    qeps: jnp.ndarray,        # f32[] radius inflation (quantization bound)
    *,
    first_leaf_heap: int,
    k: int,
):
    m = queries.shape[0]
    safe = jnp.where(idx < 0, 0, idx)
    q = queries[safe]
    radius = jnp.sqrt(knn_d[jnp.where(idx < 0, m, idx), k - 1]) + qeps
    st = traversal.TraversalState(node=node, fromc=fromc)
    leaf, st = traversal.advance(
        st, q, radius, split_dim, split_val, first_leaf_heap=first_leaf_heap
    )
    return leaf, st.node, st.fromc


@functools.partial(jax.jit, static_argnames=("first_leaf_heap",))
def _exit_leaf_batch(node: jnp.ndarray, fromc: jnp.ndarray, *, first_leaf_heap: int):
    st = traversal.exit_leaf(
        traversal.TraversalState(node=node, fromc=fromc), first_leaf_heap
    )
    return st.node, st.fromc


class BufferKDTree:
    """Buffer k-d tree implementation (build + LazySearch queries).

    .. deprecated:: as a *public entry point*.  Applications should go
       through ``repro.api.KNNIndex`` (the planner-backed facade wrapping
       this class as the ``host``/``chunked`` engines); this class is kept
       as a stable shim and as the engines' implementation.

    Example:
        index = BufferKDTree(points, height=9, n_chunks=3)
        dists, idx = index.query(queries, k=10)
        index.stats          # immutable stats of the LAST query (property)
    """

    def __init__(
        self,
        points: np.ndarray,
        *,
        height: Optional[int] = None,
        n_chunks: int = 1,
        buffer_size: Optional[int] = None,
        fetch_m: Optional[int] = None,
        backend: str = "auto",
        tile_q: int = 128,
        d_pad_multiple: int = 8,
        device: Optional[jax.Device] = None,
        engine: str = "chunked",
        engine_tile_q: Optional[int] = None,
        unit_block: int = 8,
        starvation_deadline: int = DEFAULT_STARVATION_DEADLINE,
        tree: Optional[TopTree] = None,
        precision: str = "fp32",
        store_state: Optional[QuantizedSlabs] = None,
    ):
        points = np.asarray(points, dtype=np.float32)
        n, d = points.shape
        if tree is not None:
            # share a prebuilt top tree (multi-device replicas build the
            # O(h n) median splits once, not once per device)
            if tree.n != n or tree.d != d:
                raise ValueError(
                    f"prebuilt tree is for [{tree.n}, {tree.d}] points, "
                    f"got [{n}, {d}]"
                )
            self.tree = tree
        else:
            if height is None:
                height = suggest_height(n)
            self.tree = build_top_tree(points, height)
        h = self.tree.height
        self.k_backend = backend
        self.tile_q = int(tile_q)
        if engine not in ("chunked", "host"):
            raise ValueError(f"engine={engine!r} not in ('chunked', 'host')")
        self.engine = engine

        # Feature padding for the kernel (pad dims contribute 0 distance;
        # PAD rows already carry PAD_COORD in the real dims).
        self.d_pad = max(
            d_pad_multiple, ((d + d_pad_multiple - 1) // d_pad_multiple) * d_pad_multiple
        )
        if store_state is not None:
            # snapshot-restore path: adopt the persisted quantized store
            # verbatim (codes, scales, dead mask) — re-quantizing from the
            # restored fp32 points would re-fit scales against tombstone-
            # mutated coordinates and drift from the saved codes
            if store_state.codes.shape[2] != self.d_pad:
                raise ValueError(
                    f"restored store has d_pad={store_state.codes.shape[2]}, "
                    f"tree wants {self.d_pad}"
                )
            self.store = ChunkedLeafStore(
                store_state, n_chunks=n_chunks, device=device, uniform=True
            )
        else:
            slabs = self.tree.points_padded
            if self.d_pad != d:
                pad = np.zeros(
                    (slabs.shape[0], slabs.shape[1], self.d_pad - d), dtype=np.float32
                )
                slabs = np.concatenate([slabs, pad], axis=-1)
            # uniform chunk slabs: one compiled chunk round serves every chunk
            self.store = ChunkedLeafStore(
                slabs, n_chunks=n_chunks, device=device, uniform=True,
                precision=precision, leaf_sizes=self.tree.leaf_sizes(),
            )
        self.precision = self.store.precision

        self.buffer_size = int(
            buffer_size if buffer_size is not None else default_buffer_size(h)
        )
        self.fetch_m = int(fetch_m) if fetch_m is not None else 10 * self.buffer_size

        # Device-side tree metadata (tiny, replicated in multi-device mode).
        self._split_dim = jnp.asarray(self.tree.split_dim)
        self._split_val = jnp.asarray(self.tree.split_val)
        self._leaf_start_np = self.tree.leaf_start
        self._leaf_size_np = self.tree.leaf_sizes().astype(np.int32)
        self._last_stats = SearchStats()

        resolved = kops.default_backend() if backend == "auto" else backend
        self.scan_backend = resolved   # the leaf-scan kernel that runs
        self.engine_tile_q = int(
            engine_tile_q
            if engine_tile_q is not None
            else kops.engine_tile_q(self.tile_q, resolved)
        )
        self._engine = ChunkResidentEngine(
            self.store,
            self._split_dim,
            self._split_val,
            jnp.asarray(self._leaf_start_np),
            jnp.asarray(self._leaf_size_np),
            self.tree.first_leaf_heap,
            backend=resolved,
            unit_block=unit_block,
            starvation_deadline=starvation_deadline,
        )

    # ------------------------------------------------------------------
    @property
    def n(self) -> int:
        return self.tree.n

    @property
    def d(self) -> int:
        return self.tree.d

    @property
    def stats(self) -> SearchStats:
        """Stats of the most recent ``query`` call (immutable snapshot)."""
        return self._last_stats

    def _engine_k(self, k: int) -> int:
        """Effective selection width the engines run at: quantized stores
        overfetch so the exact fp32 re-rank can see past the quantization
        selection band (``quantize.QUANT_OVERFETCH``); fp32 runs at k."""
        if self.store.quantized:
            return min(k + QUANT_OVERFETCH, self.n)
        return k

    def warm(self, m: int, k: int = 10) -> None:
        """Precompile the chunked engine's fused round for query batches of
        ``m``: the full shape plus every compaction-ladder rung, so no
        live-count trajectory can trigger a compile mid-query.  No-op for
        the host tier (its plan ladder compiles are already shape-bounded).
        """
        if self.engine == "chunked":
            self._engine.warm(m, self._engine_k(k), self.engine_tile_q)

    def dualtree(self):
        """The dual-tree traversal view over this index's TopTree + leaf
        store (``core/dualtree.DualTree``: radius / kde / pair_count).
        Cached — node bounding boxes are computed once; quantized stores
        get a private fp32 slab copy so the ops stay exact."""
        if getattr(self, "_dualtree", None) is None:
            from repro.core.dualtree import DualTree

            self._dualtree = DualTree(self.tree, self.store)
        return self._dualtree

    def _scan_units(
        self,
        dev_slab,            # [chunk_leaves, L_pad, d_pad] device buffer
        leaf_lo: int,
        unit_leaf: np.ndarray,
        unit_q: np.ndarray,
        queries_pad: jnp.ndarray,  # f32[m+1, d_pad] (row m = zeros)
        knn_d: jnp.ndarray,
        knn_i: jnp.ndarray,
        k: int,
        sb: _StatsBuilder,
    ):
        """Run the leaf-scan kernel for one chunk's work units + merge."""
        w = unit_leaf.shape[0]
        wp = _plan_pad(w)
        sb.plan_widths.add((wp, unit_q.shape[1]))
        tq = unit_q.shape[1]
        m = queries_pad.shape[0] - 1

        ul = np.zeros((wp,), np.int32)
        uq = np.full((wp, tq), -1, np.int32)
        ul[:w] = unit_leaf
        uq[:w] = unit_q

        ul_j = jnp.asarray(ul)
        uq_j = jnp.asarray(uq)
        # Gather query tiles (dump row m is all-zero => harmless distances).
        q_tiles = queries_pad[jnp.where(uq_j < 0, m, uq_j)]      # [Wp, TQ, d_pad]
        slab_tiles = dev_slab[ul_j - leaf_lo]                    # [Wp, L_pad, d_pad]
        kl = min(k, slab_tiles.shape[1])
        if self.store.quantized:
            sc, of, dd = self.store.device_meta()
            bits = dd[ul_j]                                 # [Wp, L_pad/8] u8
            dead_tile = (
                (bits[:, :, None]
                 >> jnp.arange(7, -1, -1, dtype=jnp.uint8)) & 1
            ).reshape(bits.shape[0], -1)[
                :, : slab_tiles.shape[1]
            ].astype(bool)                                  # [Wp, L_pad]
            slab_tiles = slab_tiles.astype(jnp.float32)
            if self.store.affine:
                slab_tiles = (
                    slab_tiles * sc[ul_j][:, None, :] + of[ul_j][:, None, :]
                )
            slab_tiles = jnp.where(
                dead_tile[:, :, None], jnp.float32(kops.PAD_COORD), slab_tiles
            )

        nd, nli = kops.leaf_scan(
            q_tiles, slab_tiles, k=kl, backend=self.k_backend, tq=tq
        )
        if self.store.quantized:
            new_dead = dead_tile[jnp.arange(wp)[:, None, None], nli]
        else:
            new_dead = jnp.zeros(nli.shape, bool)
        knn_d, knn_i = _merge_knn(
            knn_d,
            knn_i,
            uq_j,
            nd,
            nli,
            new_dead,
            jnp.asarray(self._leaf_start_np[ul]),
            jnp.asarray(self._leaf_size_np[ul]),
            k=k,
        )
        sb.units_scanned += int(w)
        sb.points_scanned += int(w) * dev_slab.shape[1]
        return knn_d, knn_i

    # ------------------------------------------------------------------
    def query(
        self, queries: np.ndarray, k: int = 10, *, return_sorted: bool = True
    ) -> Tuple[np.ndarray, np.ndarray]:
        """k nearest neighbors for every query (paper Alg. 1).

        Returns (dists f32[m, k] ascending Euclidean, idx i64[m, k] into the
        caller's original ``points`` ordering).  Dispatches to the chunk-
        resident bulk-synchronous engine (default) or the paper-faithful
        host loop (``engine="host"``); both are exact.
        """
        queries = np.asarray(queries, dtype=np.float32)
        m, d = queries.shape
        if d != self.d:
            raise ValueError(f"query dim {d} != reference dim {self.d}")
        if k > self.n:
            raise ValueError(f"k={k} > n={self.n}")
        first_leaf = self.tree.first_leaf_heap
        tq = self.tile_q
        k_eff = self._engine_k(k)

        if self.engine == "chunked":
            # profiler spans: the call, the engine's phases inside ``run``,
            # and the host rescoring (docs/OPERATIONS.md)
            with TraceAnnotation("knn.query"):
                _d2, gi, info = self._engine.run(
                    queries, k_eff, self.engine_tile_q, self.buffer_size
                )
                self._last_stats = _StatsBuilder.from_engine(
                    info, self.store.host.shape[1]
                ).freeze()
                with TraceAnnotation("knn.rescore"):
                    return self._finalize(gi, queries, k)

        sb = _StatsBuilder()
        qs = jnp.asarray(queries)
        qpad = jnp.zeros((m + 1, self.d_pad), jnp.float32)
        qpad = qpad.at[:m, :d].set(qs)

        knn_d = jnp.full((m + 1, k_eff), kops.INVALID_DIST, jnp.float32)
        knn_i = jnp.full((m + 1, k_eff), -1, jnp.int32)

        node = np.ones((m,), np.int32)
        fromc = np.zeros((m,), np.int32)

        queues = QueryQueues(m)
        buffers = LeafBuffers(self.tree.n_leaves, self.buffer_size)
        fetch_m = max(tq, min(self.fetch_m, m))

        while True:
            progressed = False
            if not queues.empty:
                idx = queues.fetch(fetch_m)
                mm = idx.shape[0]
                idx_p = np.full((fetch_m,), -1, np.int32)
                idx_p[:mm] = idx
                gn = np.zeros((fetch_m,), np.int32)
                gf = np.zeros((fetch_m,), np.int32)
                gn[:mm] = node[idx]
                gf[:mm] = fromc[idx]
                leaf, nn, nf = _advance_batch(
                    jnp.asarray(gn),
                    jnp.asarray(gf),
                    jnp.asarray(idx_p),
                    qs,
                    knn_d,
                    self._split_dim,
                    self._split_val,
                    np.float32(self.store.quant_eps),
                    first_leaf_heap=first_leaf,
                    k=k_eff,
                )
                leaf = np.asarray(leaf)[:mm]
                node[idx] = np.asarray(nn)[:mm]
                fromc[idx] = np.asarray(nf)[:mm]
                live = leaf >= 0
                buffers.insert(leaf[live], idx[live])
                sb.iterations += 1
                sb.queries_advanced += int(mm)
                progressed = True

            force = queues.empty
            if buffers.should_flush(force=force):
                bl, bq = buffers.drain()
                plan = build_work_plan(bl, bq, tq)
                chunk_of_unit = self.store.chunk_of_leaf(plan.unit_leaf)
                for cid, dev_slab, leaf_lo in self.store.stream(
                    sorted(set(chunk_of_unit.tolist()))
                ):
                    sel = chunk_of_unit == cid
                    knn_d, knn_i = self._scan_units(
                        dev_slab,
                        leaf_lo,
                        plan.unit_leaf[sel],
                        plan.unit_query[sel],
                        qpad,
                        knn_d,
                        knn_i,
                        k_eff,
                        sb,
                    )
                    sb.chunk_rounds += 1
                # Re-insert processed queries (their traversal resumes by
                # exiting the just-scanned leaf).
                uniq_q = np.unique(bq)
                en, ef = _exit_leaf_batch(
                    jnp.asarray(node[uniq_q]),
                    jnp.asarray(fromc[uniq_q]),
                    first_leaf_heap=first_leaf,
                )
                node[uniq_q] = np.asarray(en)
                fromc[uniq_q] = np.asarray(ef)
                queues.push_reinsert(uniq_q)
                sb.flushes += 1
                progressed = True

            if queues.empty and buffers.total == 0:
                break
            if not progressed:  # pragma: no cover - safety valve
                raise RuntimeError("LazySearch made no progress (engine bug)")

        self._last_stats = sb.freeze()
        gi = np.asarray(knn_i[:m])
        return self._finalize(gi, queries, k)

    def _finalize(
        self, gi: np.ndarray, queries: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact rescoring pass over the full batch (``finalize_candidates``
        for the whole m rows).  ``gi`` may carry more than ``k`` columns
        (quantized overfetch); the rescored, re-sorted result is sliced back
        to the caller's k — this is where quantized selection becomes an
        exact fp32 answer."""
        dists, idx = finalize_candidates(self.tree, queries, gi)
        if dists.shape[1] != k:
            dists, idx = dists[:, :k], idx[:, :k]
        return dists, idx
