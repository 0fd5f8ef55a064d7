"""Chunk-resident bulk-synchronous LazySearch (the out-of-core fast path).

The legacy host engine (``lazysearch.BufferKDTree.query`` with
``engine="host"``) orchestrates the paper's Algorithm 1 queue-by-queue: per
iteration it gathers queue slices, calls three small jitted phases, and syncs
``np.asarray`` results back — ~130 host round trips and hundreds of tiny
chunk dispatches for a 2k-query CPU smoke shape.  ``jitsearch.lazy_knn_jit``
proved the cure on the device-resident path: fuse advance -> plan -> scan ->
merge -> exit into one jitted fixed point.  This module applies the same
bulk-synchronous re-derivation to the paper's §3 *out-of-core* setting,
where only two chunk-sized slabs of the leaf structure fit on the device:

  host                             device (one fused jitted call per visit)
  ----                             --------------------------------------
  stream chunk slab j   ------>    restrict to queries paused at a leaf of
  (double-buffered copy,           chunk j -> static-shape work plan
   ChunkedLeafStore)               (jitsearch._build_plan) -> block-looped
                                   leaf scans -> top-k merge -> exit+advance
  read back leaf[m] once per round: schedule next chunk visits

Key properties:

  * ONE device->host sync per bulk round (the i32[m] pending-leaf map); all
    queue/buffer bookkeeping from the paper collapses into the on-device
    sort-by-leaf plan.
  * The work plan has a single static shape per (m, tq, chunk_leaves)
    triple: ``ChunkedLeafStore(uniform=True)`` pads every chunk to the same
    leaf count, so ONE compiled round serves every chunk and every visit —
    zero recompiles across flushes regardless of how many work units a
    flush produces (the occupied-unit count is a dynamic while-loop bound,
    not a shape).
  * ``knn_d``/``knn_i`` (the O(m*k) neighbor state) and the traversal state
    are donated, so each round updates them in place instead of copying.
  * The paper's B/2 buffer-fill heuristic survives as the chunk-visit
    admission policy: a chunk is visited when >= B/2 queries pend on it,
    or unconditionally when no chunk meets the threshold (forced flush).
    Skipping a cold chunk leaves its queries paused (their ``in_chunk`` mask
    is recomputed on device at visit time, so late visits are always
    consistent) and lets its buffer fill for a denser later visit — fewer
    host->device slab transfers, exactly what B/2 bought the paper.
    Eligible chunks are visited in PENDING-COUNT-DESCENDING order, and a
    pending chunk skipped for ``starvation_deadline`` consecutive rounds is
    force-visited so cold chunks cannot be starved indefinitely by hot ones.
  * Round-loop TAIL handling — two mechanisms keep the late rounds (a
    handful of live queries) from paying full-batch cost:

      - COMPACTION LADDER: when the live-query count falls onto a rung of
        the fixed ladder (m/4, then m/16 — ``compaction_ladder``), the live
        queries and their knn/traversal state are gathered into the
        compacted shape and all subsequent rounds run there.  Each rung is
        one extra compile the first time it is touched and recompile-free
        thereafter (rung shapes depend only on m, never on the live count);
        retired rows are scattered back to the full-m output at compaction
        time.
      - DOUBLE-BUFFERED SCHEDULE SYNC: the i32[m] pending-leaf map is NOT
        donated; after dispatching a round the host starts an async
        device->host copy of the new map and schedules the next round from
        the PREVIOUS round's map (a one-round-stale superset of the live
        set — safe, since retirement is monotone and the in-chunk mask is
        recomputed on device).  The blocking wait thus overlaps the next
        round's compute instead of serializing with it; the pipeline drains
        with an up-to-date map before termination or compaction.
"""

from __future__ import annotations

import functools
import time
import warnings
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import traversal
from repro.core.chunked import ChunkedLeafStore
from repro.core.jitsearch import _build_plan
from repro.kernels import ops as kops

__all__ = [
    "ChunkResidentEngine",
    "chunk_round_cache_size",
    "compaction_cache_size",
    "compaction_ladder",
]

DEFAULT_UNIT_BLOCK = 8
DEFAULT_STARVATION_DEADLINE = 4

# Fixed compaction rungs as fractions of the full batch: live < m/4 gathers
# to the m/4 rung, live < m/16 to the m/16 rung.  Rung sizes are padded to a
# multiple of 16 and floored at COMPACTION_MIN so tiny batches never compact
# (the ladder is empty when m is already below the smallest rung).
COMPACTION_DIVISORS = (4, 16)
COMPACTION_MIN = 32
_RUNG_MULTIPLE = 16


def compaction_ladder(m: int) -> Tuple[int, ...]:
    """Descending compacted-shape rungs for a full query batch of ``m``.

    A pure function of m (never of the observed live count), so the set of
    compiled round shapes is fixed per batch shape: at most
    ``1 + len(COMPACTION_DIVISORS)`` specializations.
    """
    rungs: List[int] = []
    for div in COMPACTION_DIVISORS:
        r = max(COMPACTION_MIN, -(-m // div))
        r = -(-r // _RUNG_MULTIPLE) * _RUNG_MULTIPLE
        if r < m and (not rungs or r < rungs[-1]):
            rungs.append(r)
    return tuple(rungs)


@functools.partial(jax.jit, static_argnames=("mc",))
def _compact_state(sel, qpad, leaf, node, fromc, knn_d, knn_i, *, mc: int):
    """Gather live rows ``sel`` (i32[mc], -1 padding) into the compacted
    shape mc.  Padding rows become retired queries (leaf=-1, node=0) whose
    knn rows are never read back (the scatter uses the live prefix only)."""
    pad = sel < 0
    safe = jnp.clip(sel, 0, None)
    return (
        qpad[safe],
        jnp.where(pad, -1, leaf[safe]).astype(jnp.int32),
        jnp.where(pad, 0, node[safe]).astype(jnp.int32),
        jnp.where(pad, 0, fromc[safe]).astype(jnp.int32),
        jnp.concatenate([knn_d[safe], knn_d[-1:]], axis=0),
        jnp.concatenate([knn_i[safe], knn_i[-1:]], axis=0),
    )


@functools.partial(jax.jit, static_argnames=("first_leaf_heap",))
def _initial_advance(qpad, split_dim, split_val, *, first_leaf_heap):
    """Round 0: descend every query to its home leaf (no chunk needed)."""
    m = qpad.shape[0]
    st = traversal.init_state(m)
    radius = jnp.full((m,), jnp.inf, jnp.float32)
    leaf, st = traversal.advance(
        st, qpad, radius, split_dim, split_val, first_leaf_heap=first_leaf_heap
    )
    return leaf, st.node, st.fromc


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "tq", "first_leaf_heap", "ub", "backend", "quant", "affine"
    ),
    # leaf is deliberately NOT donated: the previous round's pending-leaf
    # map stays a live buffer so its async host readback can overlap the
    # round that consumes it (the double-buffered schedule sync).
    donate_argnums=(0, 1, 3, 4, 5),
)
def _chunk_round(
    node,          # i32[m]   traversal heap position      (donated)
    fromc,         # i32[m]   traversal arrival direction  (donated)
    leaf,          # i32[m]   pending leaf per query, -1 done (NOT donated)
    knn_d,         # f32[m+1, k] running top-k sq-dists    (donated)
    knn_i,         # i32[m+1, k] reordered-global indices  (donated)
    counts,        # i32[2]   running (units, rows) scanned (donated)
    qpad,          # f32[m, d_pad] zero-padded queries
    dev_slab,      # [C, L_pad, d_pad] resident chunk slab (f32/f16/u8 codes)
    lo,            # i32[] first leaf id of the chunk
    leaf_start,    # i32[n_leaves]
    leaf_size,     # i32[n_leaves]
    split_dim,     # i32[2**h]
    split_val,     # f32[2**h]
    q_scale,       # f32[n_leaves_tot, d_pad] dequantize scale  (dummy if !affine)
    q_offset,      # f32[n_leaves_tot, d_pad] dequantize offset (dummy if !affine)
    q_dead,        # u8[n_leaves_tot, ceil(L_pad/8)] bit-packed dead-row mask
    qeps,          # f32[] traversal-radius inflation (quantization error bound)
    *,
    k: int,
    tq: int,
    first_leaf_heap: int,
    ub: int,
    backend: str,
    quant: bool,
    affine: bool,
):
    """One fused bulk-synchronous round over the resident chunk.

    Scans every query paused at a leaf of this chunk, merges its candidates,
    exits its leaf and advances it to its next pending leaf (which may be in
    any chunk).  Queries paused elsewhere are untouched.  Returns the
    updated (node, fromc, leaf, knn_d, knn_i, counts): ``counts`` adds this
    round's work units and the query rows that occupied a slot of them
    (each in-chunk live query fills exactly one), so the host reads the
    call's totals once instead of once per round.

    The named scopes (``knn.plan``, ``knn.gather``, ``knn.scan``,
    ``knn.merge``, ``knn.advance``) tag the device operations for the
    profiler; they sit inside the block loop's body and around the advance,
    never around the block loop itself, so that loop's own op carries none
    and the time of its body is not counted twice.

    ``quant=True`` slabs hold storage codes: each gathered leaf tile is
    dequantized elementwise (codes * scale + offset, O(ub*L_pad*d) next to
    the O(ub*tq*L_pad*d) scan matmul) and dead rows — structural padding and
    tombstoned rows — are masked to PAD_COORD so they lose every contest.
    The traversal radius is inflated by ``qeps`` (the global reconstruction
    error bound), which provably keeps every leaf that could hold a true
    neighbor on the schedule; the Pallas/ref scan kernels see plain f32
    tiles either way.
    """
    m = leaf.shape[0]
    c = dev_slab.shape[0]
    # one leaf holds at most L_pad candidates: clamp the per-scan selection
    # width so overfetched k (quantized re-rank headroom) and k > leaf-size
    # batches stay in the kernel's top-k contract; the running merge below
    # still keeps k columns
    kl = min(k, dev_slab.shape[1])

    with jax.named_scope("knn.plan"):
        in_chunk = (leaf >= lo) & (leaf < lo + c)
        local = jnp.where(in_chunk, leaf - lo, -1)
        unit_leaf, unit_query, n_units = _build_plan(local, tq, c)
        rows = jnp.sum(local >= 0, dtype=jnp.int32)
        counts = counts + jnp.stack([n_units, rows])

        # pad the plan to a whole number of unit blocks so dynamic_slice
        # starts stay in bounds; the occupied prefix [0, n_units) is what
        # gets processed
        w_rows = unit_leaf.shape[0]
        w_pad = -(-w_rows // ub) * ub
        unit_leaf = jnp.concatenate(
            [unit_leaf, jnp.zeros((w_pad - w_rows,), jnp.int32)]
        )
        unit_query = jnp.concatenate(
            [unit_query, jnp.full((w_pad - w_rows, tq), -1, jnp.int32)]
        )
        n_blocks = (n_units + ub - 1) // ub

    def body(carry):
        i, knn_d, knn_i = carry
        with jax.named_scope("knn.gather"):
            ul = jax.lax.dynamic_slice_in_dim(unit_leaf, i * ub, ub)
            uq = jax.lax.dynamic_slice_in_dim(unit_query, i * ub, ub)
            q_tiles = jnp.where(
                (uq >= 0)[..., None], qpad[jnp.clip(uq, 0, m - 1)], 0.0
            )                                              # [ub, tq, d_pad]
            gl = ul + lo
            slabs = dev_slab[ul]                           # [ub, L_pad, d_pad]
            if quant:
                bits = q_dead[gl]                          # [ub, L_pad/8] u8
                dead_tile = (
                    (bits[:, :, None]
                     >> jnp.arange(7, -1, -1, dtype=jnp.uint8)) & 1
                ).reshape(bits.shape[0], -1)[
                    :, : dev_slab.shape[1]
                ].astype(bool)                             # [ub, L_pad]
                slabs = slabs.astype(jnp.float32)
                if affine:
                    slabs = (
                        slabs * q_scale[gl][:, None, :]
                        + q_offset[gl][:, None, :]
                    )
                slabs = jnp.where(
                    dead_tile[:, :, None], jnp.float32(kops.PAD_COORD), slabs
                )
        with jax.named_scope("knn.scan"):
            nd, nli = kops.leaf_scan(
                q_tiles, slabs, k=kl, backend=backend, tq=tq
            )

        with jax.named_scope("knn.merge"):
            ustart = leaf_start[gl]
            usize = leaf_size[gl]
            valid = nli < usize[:, None, None]
            if quant:
                # tombstoned rows sit BELOW usize: drop any that the
                # selection still surfaced (their PAD_COORD distance loses
                # contests, but a sparse leaf can leave them in the top-k
                # tail — and the exact re-rank would rescore them at their
                # true coordinates)
                sel_dead = dead_tile[
                    jnp.arange(ul.shape[0])[:, None, None], nli
                ]
                valid = valid & ~sel_dead
            gidx = jnp.where(valid, nli + ustart[:, None, None], -1)
            ndm = jnp.where(
                valid, nd, jnp.float32(kops.INVALID_DIST)
            ).reshape(-1, kl)
            nim = gidx.reshape(-1, kl)
            flat_q = uq.reshape(-1)
            safe_q = jnp.where(flat_q < 0, m, flat_q)
            cd = jnp.concatenate([knn_d[safe_q], ndm], axis=1)
            ci = jnp.concatenate([knn_i[safe_q], nim], axis=1)
            neg, sel = jax.lax.top_k(-cd, k)
            knn_d = knn_d.at[safe_q].set(-neg, mode="drop")
            knn_i = knn_i.at[safe_q].set(
                jnp.take_along_axis(ci, sel, axis=1), mode="drop"
            )
        return i + 1, knn_d, knn_i

    _, knn_d, knn_i = jax.lax.while_loop(
        lambda carry: carry[0] < n_blocks, body, (jnp.int32(0), knn_d, knn_i)
    )

    # exit the just-scanned leaves (only this chunk's queries move) and
    # advance them to their next pending leaf; everyone else is frozen by
    # advance()'s own pause predicate (at-leaf, descending, or done)
    with jax.named_scope("knn.advance"):
        st = traversal.TraversalState(node=node, fromc=fromc)
        ex = traversal.exit_leaf(st, first_leaf_heap)
        st = traversal.TraversalState(
            node=jnp.where(in_chunk, ex.node, node).astype(jnp.int32),
            fromc=jnp.where(in_chunk, ex.fromc, fromc).astype(jnp.int32),
        )
        radius = jnp.sqrt(knn_d[:m, k - 1]) + qeps
        new_leaf, st = traversal.advance(
            st, qpad, radius, split_dim, split_val,
            first_leaf_heap=first_leaf_heap,
        )
    return st.node, st.fromc, new_leaf, knn_d, knn_i, counts


def chunk_round_cache_size() -> int:
    """Number of compiled specializations of the fused round (one per
    (m, tq, chunk-shape, k, backend) combination, where m ranges over the
    full batch shape plus any compaction-ladder rungs actually entered —
    flush sizes, work-unit counts and live-query counts must NOT add
    entries; the engine bench asserts this)."""
    return _chunk_round._cache_size()


def compaction_cache_size() -> int:
    """Compiled specializations of the ladder gather (one per
    (source shape, rung) transition actually taken)."""
    return _compact_state._cache_size()


class ChunkResidentEngine:
    """Bulk-synchronous out-of-core query engine over a ``ChunkedLeafStore``.

    Built once per ``BufferKDTree``; ``run`` executes one full query batch.
    The store must be uniform (equal chunk slab shapes) so one compiled
    round serves every chunk.
    """

    def __init__(
        self,
        store: ChunkedLeafStore,
        split_dim: jnp.ndarray,
        split_val: jnp.ndarray,
        leaf_start: jnp.ndarray,
        leaf_size: jnp.ndarray,
        first_leaf_heap: int,
        *,
        backend: str = "ref",
        unit_block: int = DEFAULT_UNIT_BLOCK,
        starvation_deadline: int = DEFAULT_STARVATION_DEADLINE,
    ):
        if store.n_chunks > 1 and not store.uniform:
            raise ValueError(
                "ChunkResidentEngine needs ChunkedLeafStore(uniform=True)"
            )
        self.store = store
        self._split_dim = split_dim
        self._split_val = split_val
        self._leaf_start = leaf_start
        self._leaf_size = leaf_size
        self.first_leaf_heap = int(first_leaf_heap)
        self.backend = backend
        self.unit_block = int(unit_block)
        self.starvation_deadline = max(1, int(starvation_deadline))
        self._dummy_meta = None   # placeholder dequantize args (fp32 stores)
        # leaf -> owning chunk, precomputed once: the per-round host work is
        # a masked table lookup over the LIVE queries only, not a
        # searchsorted over the full batch
        self._leaf_chunk = store.chunk_of_leaf(
            np.arange(store.n_leaves, dtype=np.int64)
        )

    def _quant_args(self):
        """Dequantize arguments for the fused round: the store's device-
        resident (scale, offset, dead-mask) triple plus the radius-inflation
        eps, or tiny placeholders (dead code under ``quant=False``) so the
        fp32 round keeps a single stable signature."""
        if self.store.quantized:
            sc, of, dd = self.store.device_meta()
            return (
                sc, of, dd, np.float32(self.store.quant_eps), True,
                self.store.affine,
            )
        if self._dummy_meta is None:
            self._dummy_meta = jax.device_put(
                (
                    jnp.ones((1, 1), jnp.float32),
                    jnp.zeros((1, 1), jnp.float32),
                    jnp.zeros((1, 1), jnp.uint8),
                ),
                self.store.device,
            )
        sc, of, dd = self._dummy_meta
        return sc, of, dd, np.float32(0.0), False, False

    def warm(self, m: int, k: int, tq: int) -> int:
        """Eagerly compile every executable a batch shape ``m`` can reach:
        the fused round at the full shape and at every compaction-ladder
        rung, plus every reachable ladder gather transition.  Makes the
        recompile-free guarantee trajectory-independent — without this, a
        rung is compiled the first time some query batch's live count
        happens to enter it.  Returns the number of round shapes warmed."""
        d_pad = self.store.host.shape[2]
        shapes = [int(m), *compaction_ladder(int(m))]
        dev = self.store.device

        def state_at(ms: int):
            arrs = (
                jnp.zeros((ms,), jnp.int32),                       # node
                jnp.zeros((ms,), jnp.int32),                       # fromc
                jnp.full((ms,), -1, jnp.int32),                    # leaf
                jnp.full((ms + 1, k), kops.INVALID_DIST, jnp.float32),
                jnp.full((ms + 1, k), -1, jnp.int32),
                jnp.zeros((2,), jnp.int32),                        # counts
                jnp.zeros((ms, d_pad), jnp.float32),               # qpad
            )
            return jax.device_put(arrs, dev)

        qsc, qof, qdd, qeps, quant, affine = self._quant_args()
        for _cid, dev_slab, lo in self.store.stream([0]):
            for ms in shapes:
                node, fromc, leaf, knn_d, knn_i, counts, qpad = state_at(ms)
                with warnings.catch_warnings():
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable",
                    )
                    _chunk_round(
                        node, fromc, leaf, knn_d, knn_i, counts,
                        qpad, dev_slab, jnp.int32(lo),
                        self._leaf_start, self._leaf_size,
                        self._split_dim, self._split_val,
                        qsc, qof, qdd, qeps,
                        k=k, tq=tq, first_leaf_heap=self.first_leaf_heap,
                        ub=self.unit_block, backend=self.backend, quant=quant,
                        affine=affine,
                    )
        for i, src in enumerate(shapes):
            node, fromc, leaf, knn_d, knn_i, _counts, qpad = state_at(src)
            for dst in shapes[i + 1:]:
                _compact_state(
                    jnp.asarray(np.full((dst,), -1, np.int32)),
                    qpad, leaf, node, fromc, knn_d, knn_i, mc=dst,
                )
        return len(shapes)

    def _visit_order(
        self,
        counts: np.ndarray,       # i64[n_chunks] pending queries per chunk
        threshold: int,
        starve: np.ndarray,       # i32[n_chunks] rounds a pending chunk waited
    ) -> np.ndarray:
        """Measured-cost chunk schedule for one round.

        Admission: the paper's B/2 fill rule, plus any pending chunk starved
        past the deadline; forced flush (all pending chunks) when nothing is
        admitted.  Order: pending-count DESCENDING, so the densest scan
        (most work to hide the next slab copy behind) is dispatched first.
        Updates ``starve`` in place.
        """
        eligible = (counts >= threshold) | ((counts > 0) & (starve >= self.starvation_deadline))
        visit = np.nonzero(eligible)[0]
        if visit.size == 0:
            visit = np.nonzero(counts > 0)[0]   # forced flush
        visit = visit[np.argsort(-counts[visit], kind="stable")]
        starve[counts > 0] += 1
        starve[counts <= 0] = 0
        starve[visit] = 0
        return visit

    def run(
        self,
        queries: np.ndarray,    # f32[m, d] query rows (d <= the slab's d_pad)
        k: int,
        tq: int,
        buffer_size: int,
        on_retire=None,
    ) -> Tuple[np.ndarray, np.ndarray, Dict[str, float]]:
        """Returns (sq-dists f32[m, k], reordered-global idx i32[m, k],
        info counters).  Distances are pre-rescoring (caller refines).

        ``on_retire(rows, d2, gi)`` is the EARLY-RETIREMENT hook (the
        streaming engine's seam): called zero or more times during the
        round loop with original query rows whose traversal just finished,
        their raw squared distances f32[r, k] and reordered-global indices
        i32[r, k] — the same pre-rescoring values the batch return carries.
        Every row is reported exactly once (rows not seen retiring
        mid-loop are reported in one final call before ``run`` returns).
        Detection rides the double-buffered schedule readback, and the
        knn-row materialization is itself double-buffered (async D2H
        started at detection, completed just before the next dispatch), so
        the hook adds no extra device synchronization to the round loop.

        The host phases are profiler spans (``knn.prepare``, ``knn.round``
        with its ``knn.schedule``/``knn.dispatch``/``knn.harvest``,
        ``knn.compact``, ``knn.drain``), on the device trace's clock; they
        cost nothing while no profiler runs.
        """
        m, d = queries.shape
        store = self.store
        first_leaf = self.first_leaf_heap

        # full-m outputs; compaction scatters retired rows back here
        out_d = np.full((m, k), kops.INVALID_DIST, np.float32)
        out_i = np.full((m, k), -1, np.int32)
        orig = np.arange(m)       # compacted row -> original query row
        ladder = list(compaction_ladder(m))
        m_cur = m

        info = {
            "rounds": 0, "chunk_rounds": 0, "units": 0, "rows": 0,
            "queries_advanced": 0, "compactions": 0,
            "steady_rounds": 0, "tail_rounds": 0,
            "steady_s": 0.0, "tail_s": 0.0, "sync_wait_s": 0.0,
        }
        starve = np.zeros(store.n_chunks, np.int32)

        # ---- early-retirement reporting (the streaming engine's seam) ----
        # `reported` tracks original rows already delivered; `pending_emit`
        # holds one detected-but-unmaterialized batch (rows + refs to the knn
        # buffers whose async D2H was started at detection).  The flush MUST
        # happen before those buffers are donated to the next round.
        reported = np.zeros(m, bool) if on_retire is not None else None
        pending_emit = None
        if reported is not None:
            info["early_retired"] = 0

        def flush_emit() -> None:
            nonlocal pending_emit
            if pending_emit is None:
                return
            rows, rc, d_ref, i_ref = pending_emit
            pending_emit = None
            t0 = time.perf_counter()
            d_rows = np.asarray(d_ref)[rc]
            i_rows = np.asarray(i_ref)[rc]
            info["sync_wait_s"] += time.perf_counter() - t0
            on_retire(rows, d_rows, i_rows)

        def note_retired() -> None:
            """Detect rows newly retired in the current ``sched`` view and
            stage them for delivery (delivering any prior batch first, so
            emissions stay ordered and refs stay one-deep)."""
            nonlocal pending_emit
            if reported is None:
                return
            flush_emit()
            rc = np.nonzero(sched[: orig.size] < 0)[0]
            rc = rc[~reported[orig[rc]]]
            if rc.size == 0:
                return
            rows = orig[rc].copy()
            reported[rows] = True
            for ref in (knn_d, knn_i):
                if hasattr(ref, "copy_to_host_async"):
                    ref.copy_to_host_async()
            pending_emit = (rows, rc, knn_d, knn_i)
            info["early_retired"] += int(rc.size)

        qsc, qof, qdd, qeps, quant, affine = self._quant_args()

        def dispatch_round(visit: np.ndarray) -> None:
            nonlocal node, fromc, leaf, knn_d, knn_i, counts
            flush_emit()   # the round donates knn_d/knn_i: deliver first
            for _cid, dev_slab, lo in store.stream(visit.tolist()):
                with warnings.catch_warnings():
                    # donation is a no-op on CPU; the warning fires at the
                    # (one) compile — scoped here so the process-global
                    # filter is untouched
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable",
                    )
                    node, fromc, leaf, knn_d, knn_i, counts = _chunk_round(
                        node, fromc, leaf, knn_d, knn_i, counts,
                        qpad, dev_slab, jnp.int32(lo),
                        self._leaf_start, self._leaf_size,
                        self._split_dim, self._split_val,
                        qsc, qof, qdd, qeps,
                        k=k, tq=tq, first_leaf_heap=first_leaf,
                        ub=self.unit_block, backend=self.backend, quant=quant,
                        affine=affine,
                    )
                info["chunk_rounds"] += 1
            info["rounds"] += 1
            info["queries_advanced"] += m_cur
            if m_cur == m:
                info["steady_rounds"] += 1
            else:
                info["tail_rounds"] += 1

        def harvest(arr) -> np.ndarray:
            """Blocking completion of an async pending-leaf-map readback."""
            t0 = time.perf_counter()
            out = np.asarray(arr)
            info["sync_wait_s"] += time.perf_counter() - t0
            return out

        with TraceAnnotation("knn.prepare"):
            d_pad = store.host.shape[2]
            qpad = jnp.zeros((m, d_pad), jnp.float32).at[:, :d].set(
                jnp.asarray(queries)
            )
            knn_d = jnp.full((m + 1, k), kops.INVALID_DIST, jnp.float32)
            knn_i = jnp.full((m + 1, k), -1, jnp.int32)
            leaf, node, fromc = _initial_advance(
                qpad, self._split_dim, self._split_val,
                first_leaf_heap=first_leaf,
            )
            # commit the round state to the store's device: round outputs
            # are committed (the slab input is), and a committed/uncommitted
            # avals mismatch would cost a second (pointless) round
            # specialization.  `counts` sums (units, rows) over the rounds
            # on the device; the host reads it once, at the drain.
            qpad, leaf, node, fromc, knn_d, knn_i, counts = jax.device_put(
                (qpad, leaf, node, fromc, knn_d, knn_i,
                 np.zeros((2,), np.int32)),
                store.device,
            )
            # The schedule is double-buffered: `sched` is the host's
            # (possibly one-round-stale) view of the pending-leaf map;
            # `inflight` is the device map whose async readback overlaps the
            # round in flight.  Staleness is safe: retirement is monotone, so
            # a stale map's live set is a superset of the true one, and the
            # device recomputes the in-chunk mask at visit time.
            sched = harvest(leaf)       # round 0: nothing to overlap yet
            inflight = None
            note_retired()

        while True:
            live_rows = np.nonzero(sched >= 0)[0]
            if live_rows.size == 0:
                if inflight is not None:
                    # stale map says done — drain the pipeline and re-check
                    # against the freshest map before concluding
                    with TraceAnnotation("knn.drain"):
                        sched, inflight = harvest(inflight), None
                        note_retired()
                    continue
                break

            if ladder and live_rows.size <= ladder[0]:
                with TraceAnnotation("knn.compact"):
                    if inflight is not None:
                        # compaction re-indexes rows: barrier the pipeline
                        # so the gather uses the freshest (smallest) live set
                        sched, inflight = harvest(inflight), None
                        note_retired()
                        continue
                    rung = ladder.pop(0)
                    while ladder and live_rows.size <= ladder[0]:
                        rung = ladder.pop(0)
                    # retire everything the current shape holds (live rows
                    # are re-scattered at the next compaction or at exit);
                    # this blocks on all in-flight rounds, so it is
                    # accounted as sync wait like the schedule readbacks
                    t0 = time.perf_counter()
                    out_d[orig] = np.asarray(knn_d)[: orig.size]
                    out_i[orig] = np.asarray(knn_i)[: orig.size]
                    info["sync_wait_s"] += time.perf_counter() - t0
                    sel = np.full((rung,), -1, np.int32)
                    sel[: live_rows.size] = live_rows
                    qpad, leaf, node, fromc, knn_d, knn_i = _compact_state(
                        jnp.asarray(sel), qpad, leaf, node, fromc, knn_d,
                        knn_i, mc=rung,
                    )
                    orig = orig[live_rows]
                    new_sched = np.full((rung,), -1, sched.dtype)
                    new_sched[: live_rows.size] = sched[live_rows]
                    sched = new_sched
                    m_cur = rung
                    info["compactions"] += 1
                continue

            with TraceAnnotation("knn.round", round=info["rounds"],
                                 rung=m_cur):
                # per-round host work is over the LIVE queries only: a
                # precomputed leaf->chunk table lookup (no full-m
                # searchsorted)
                with TraceAnnotation("knn.schedule"):
                    threshold = max(1, min(int(buffer_size), m_cur) // 2)
                    chunk_counts = np.bincount(
                        self._leaf_chunk[sched[live_rows]],
                        minlength=store.n_chunks,
                    )
                    visit = self._visit_order(chunk_counts, threshold, starve)
                t0 = time.perf_counter()
                wait0 = info["sync_wait_s"]
                with TraceAnnotation("knn.dispatch"):
                    dispatch_round(visit)
                # overlap: complete the PREVIOUS round's readback while this
                # round computes, then start this round's readback
                if inflight is not None:
                    with TraceAnnotation("knn.harvest"):
                        sched = harvest(inflight)
                        note_retired()
                inflight = leaf
                if hasattr(inflight, "copy_to_host_async"):
                    inflight.copy_to_host_async()
                # blocked readback time is accounted in sync_wait_s only, so
                # the phase buckets sum to the loop wall time (and the
                # calibrator's round_s = steady_s / rounds stays copy-free)
                dt = time.perf_counter() - t0 - (info["sync_wait_s"] - wait0)
                info["steady_s" if m_cur == m else "tail_s"] += dt

        with TraceAnnotation("knn.drain"):
            out_d[orig] = np.asarray(knn_d)[: orig.size]
            out_i[orig] = np.asarray(knn_i)[: orig.size]
            info["units"], info["rows"] = (int(x) for x in np.asarray(counts))
            if reported is not None:
                flush_emit()
        if reported is not None:
            rest = np.nonzero(~reported)[0]
            if rest.size:
                on_retire(rest, out_d[rest], out_i[rest])
                reported[rest] = True
        return out_d, out_i, info
