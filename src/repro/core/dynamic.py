"""Batch-dynamic mutable index: a device-aware logarithmic-method forest.

The paper's buffer k-d tree is STATIC: any change to the reference catalog
means a full rebuild.  This module adds incremental ``insert``/``delete``
without touching the static engines, using the classic logarithmic method
(Bentley–Saxe; Parallel Batch-Dynamic kd-trees, PAPERS.md): the live point
multiset is partitioned across a small forest of *immutable* shards whose
capacities are ``B * 2^i`` (at most one shard per size rung once merges
settle, like the bits of a binary counter), and every shard is served by
one of the repo's existing static engines:

    rung capacity <= brute_cutoff   ->  tiled brute scan over the padded slab
    rung capacity  > brute_cutoff   ->  ``BufferKDTree`` (chunked engine)

  insert(points)   the batch becomes a new shard at the smallest fitting
                   rung; a rung collision triggers a MERGE of the two
                   shards (live points collected, shard rebuilt one rung
                   up if needed) — the binary-counter CARRY CHAIN.  Each
                   point participates in O(log(n/B)) rebuilds over the
                   index lifetime.  Batches at or beyond the rebuild/merge
                   crossover (``rebuild_crossover``) skip the chain and
                   trigger one flattening rebuild.
  delete(ids)      TOMBSTONES: the row's ``live`` bit is cleared and the
                   row is reclaimed in the backing structure — coordinate
                   overwrite on brute shards, leaf-store row rewrite on
                   tree shards (see FETCH WIDTHS below).  A shard
                   whose tombstone count exceeds ``tomb_limit`` is
                   compacted; a shard with no live rows is dropped.
  query(q, k)      fans out over live shards — grouped per DEVICE, one
                   thread per device so every dispatch queue stays busy —
                   and folds the per-shard lists with the Pallas kernel's
                   two-phase ``_rank_merge``.

MULTI-DEVICE PLACEMENT (distributed/dynamic_shards.py): shards are
immutable, so each rung can live on its own device the way the static
``forest``/``sharded`` engines place whole trees.  Tree rungs go to the
least-loaded device (greedy, by capacity); brute rungs are pinned to the
lead device so the churning low rungs never bounce slabs between devices.

BACKGROUND CARRY MERGES: with ``merge_async=True`` a rung collision does
NOT block the insert (or any query).  The colliding shards are snapshotted
under the mutation lock, a single background worker builds the merged
shard into a staging slab, and the result is atomically swapped in — the
sources stay queryable until that instant, so the live multiset (and thus
every query answer) is identical throughout.  Deletes that land on a
source mid-merge are re-applied to the staging shard at swap time from the
snapshot delta; a source that disappears entirely (compaction, flattening
rebuild) aborts the merge and reschedules.  ``merge_async=False`` keeps
the original inline carry chain (the default for direct construction; the
planner decides for ``repro.api`` indexes and records why).

FETCH WIDTHS — EXACTNESS UNDER TOMBSTONES (the invariant the parity
harness checks): a shard must contribute its nearest ``min(k, n_live)``
live points to the fold.  EVERY shard fetches bare ``min(k, capacity)``
candidates, because deletes reclaim the row in the backing structure at
tombstone time (the ROADMAP's "tombstone coordinate overwrite", now
covering both shard kinds):

  * BRUTE shards overwrite the slab row's coordinates with ``PAD_COORD``,
    so dead rows rank strictly after ALL live rows.
  * TREE shards rewrite the corresponding leaf-store row
    (``ChunkedLeafStore.kill_rows`` via ``_reclaim_tree_rows``): fp32
    stores overwrite the slab row in place, quantized stores flip the
    row's dead-mask bit (the scan-time dequantize masks dead rows back to
    ``PAD_COORD``), re-uploading only the tiny mask — never the slabs.
    The leaf-ordered fp32 rescore copies are overwritten too.

Either way the nearest ``k`` physical rows ARE the nearest ``k`` live
rows, so compaction pressure no longer inflates query shapes.

Tombstoned/padding candidates are additionally masked via the ``live``
bits, and the per-shard sorted lists are folded at the uniform merge width
``w = k + tomb_limit`` (pad-extended where a shard fetched less), one
jitted pairwise merge per shard.

RECOMPILE DISCIPLINE (same contract as the compaction ladder): per-shard
query shapes depend only on the rung, never on live or tombstone counts —

  * shard slabs are padded to their rung capacity with ``PAD_COORD`` rows,
    so a rung has ONE reference shape for the lifetime of the process;
  * query batches are padded up to a power-of-two rung (``_pad_batch``),
    so at most one compile per (batch rung, shard rung, k) triple — and
    per DEVICE, since each device compiles its own executable;
  * fetch widths use the ``tomb_limit`` BOUND (tree) or bare ``k``
    (brute), never the instantaneous tombstone count;
  * the merge chain is a Python fold over ONE jitted pairwise function, so
    its compile count is independent of how many shards are live.

WARM-AT-BUILD: ``warm(m, k)`` registers the (batch, k) shape and every
shard created afterwards — including staging shards built by the
background merge worker — precompiles its scan for the registered shapes
AT CONSTRUCTION, so no query ever pays a rung's first compile.

``tests/test_dynamic.py`` holds the generative parity harness (random
insert/delete/query interleavings vs ``knn_brute`` over the live multiset)
and the carry-chain compile-count regression;
``tests/test_dynamic_multidevice.py`` replays it on 4 virtual devices with
merges completing mid-stream.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults
from repro.core.lazysearch import BufferKDTree, SearchStats
from repro.core.quantize import BYTES_PER_ELEM, PRECISIONS
from repro.core.toptree import (
    PAD_COORD,
    _round_up,
    slab_len,
    suggest_height,
    tree_from_arrays,
    tree_to_arrays,
)
from repro.distributed.dynamic_shards import (
    DeviceFanout,
    MergeRetryExhausted,
    MergeWorker,
    ShardPlacer,
)
from repro.kernels.knn_scan import _rank_merge

faults.load_env()

__all__ = [
    "DynamicIndex",
    "DEFAULT_BASE_CAPACITY",
    "DEFAULT_TOMB_LIMIT",
    "DEFAULT_BRUTE_CUTOFF",
    "MERGE_MAX_RETRIES",
    "merge_cache_size",
    "shard_scan_cache_size",
]

DEFAULT_BASE_CAPACITY = 1024   # B: smallest shard rung (paper footnote-8 scale)
DEFAULT_TOMB_LIMIT = 32        # per-shard tombstones before compaction
DEFAULT_BRUTE_CUTOFF = 2048    # rungs above this get a BufferKDTree engine

# Bounded retry of failed background merges: a transient failure (OOM
# blip, compile hiccup, a staging device that just died) is retried with
# capped exponential backoff; a persistent one surfaces as
# ``MergeRetryExhausted`` on ``drain()`` instead of a silent retry storm.
MERGE_MAX_RETRIES = 4
_MERGE_RETRY_BASE_S = 0.05
_MERGE_RETRY_CAP_S = 1.0

_MIN_BATCH_PAD = 16            # smallest padded query-batch rung
_BRUTE_TILE_X = 2048           # reference tile for brute shards (cap-aligned)
_BRUTE_TILE_Q = 1024           # query tile for brute shards (ladder-aligned)


def _pad_batch(m: int) -> int:
    """Next power-of-two batch rung >= m (floored at ``_MIN_BATCH_PAD``)."""
    p = _MIN_BATCH_PAD
    while p < m:
        p <<= 1
    return p


# ---------------------------------------------------------------------------
# jitted merge chain: filter/sort one shard's candidate list, then fold with
# the kernel's two-phase rank merge.  Candidates travel as i32 CODES
# ``shard_slot * w + column`` (decoded to global i64 ids on the host) so the
# merge reuses ``_rank_merge`` verbatim, i32 indices and all.
# ---------------------------------------------------------------------------
@jax.jit
def _filter_sort(d: jnp.ndarray, keep: jnp.ndarray, code_base: jnp.ndarray):
    """Mask dead candidates to +inf and sort ascending.

    d f32[mp, w], keep bool[mp, w] -> (sorted dists f32[mp, w],
    codes i32[mp, w] = code_base + original column).  jax sorts are stable,
    so equal distances keep their engine-produced order.
    """
    d = jnp.where(keep, d, jnp.inf)
    order = jnp.argsort(d, axis=1)
    return (
        jnp.take_along_axis(d, order, axis=1),
        order.astype(jnp.int32) + code_base,
    )


@functools.partial(jax.jit, static_argnames=("w",))
def _merge_pair(a_d, a_c, b_d, b_c, *, w: int):
    """Fold two sorted w-lists into their w smallest (kernel rank merge)."""
    return _rank_merge(a_d, a_c, b_d, b_c, w)


def merge_cache_size() -> int:
    """Jit-cache entries of the fan-out merge (filter/sort + pairwise fold).

    Grows once per (padded batch, candidate width) pair and NEVER with the
    shard count — the compile-count regression test's second counter."""
    return _filter_sort._cache_size() + _merge_pair._cache_size()


def shard_scan_cache_size() -> int:
    """Jit-cache entries of the brute shard scan (``knn_brute``'s tile step).

    Grows once per (batch rung, shard rung, d, fetch width) per device —
    the carry-chain compile-count regression's primary counter."""
    from repro.core.brute import _tile_step

    return _tile_step._cache_size()


# ---------------------------------------------------------------------------
@dataclasses.dataclass(eq=False)
class _Shard:
    """One immutable slab of the forest (mutated only via tombstone bits and
    the matching PAD_COORD coordinate overwrite on brute shards).  Identity
    semantics (``eq=False``): the merge swap tracks shards by object, never
    by content."""

    rung: int                      # capacity = base << rung
    capacity: int
    points: np.ndarray             # f32[capacity, d]; PAD_COORD beyond n_rows
    ids: np.ndarray                # i64[capacity]; sorted ascending, -1 pads
    live: np.ndarray               # bool[capacity]; False for pads/tombstones
    n_rows: int                    # occupied rows (live + tombstoned)
    n_tomb: int = 0
    engine: Optional[BufferKDTree] = None   # None => brute scan
    device: Any = None             # placement (None = process default)
    seq: int = 0                   # creation order: stable fan-out slots
    merging: bool = False          # reserved by an in-flight background merge
    tomb_limit: int = DEFAULT_TOMB_LIMIT    # owning forest's bound
    _dev_slab: Any = None          # brute: cached device copy (tile-padded)

    @property
    def n_live(self) -> int:
        return self.n_rows - self.n_tomb

    @property
    def kind(self) -> str:
        return "brute" if self.engine is None else "tree"

    def fetch_width(self, k: int) -> int:
        """Per-shard candidate fetch width for a k-NN query (see module
        doc, FETCH WIDTHS): bare ``k`` suffices for BOTH kinds now that
        tombstoned rows are reclaimed in the backing structure at delete
        time — brute shards by PAD_COORD coordinate overwrite, tree
        shards by leaf-store row rewrite (``_reclaim_tree_rows``) — so a
        dead row can never outrank a live one."""
        return min(k, self.capacity)

    def dev_slab(self):
        """Brute slab on this shard's device, tile-padded, built once and
        invalidated by tombstone coordinate overwrites."""
        if self._dev_slab is None:
            tx = min(self.capacity, _BRUTE_TILE_X)
            nx = _round_up(self.capacity, tx)
            slab = self.points
            if nx != self.capacity:
                pad = np.full(
                    (nx - self.capacity, slab.shape[1]), np.float32(PAD_COORD)
                )
                slab = np.concatenate([slab, pad])
            arr = jnp.asarray(slab)
            if self.device is not None:
                arr = jax.device_put(arr, self.device)
            self._dev_slab = arr
        return self._dev_slab


class DynamicIndex:
    """Mutable exact-kNN index over a logarithmic-method shard forest.

    Global ids are assigned in insertion order (the initial
    ``from_points(points)`` batch gets ``0..n-1``), are never reused, and
    are what ``query`` returns — so they index any value array the caller
    appends to in lockstep (the kNN-LM datastore does exactly this).

    ``devices`` places shards across multiple accelerators (see module
    doc); ``merge_async=True`` moves carry-chain merges to a background
    worker so neither inserts nor queries wait on them.  Both default to
    the old single-device / inline behavior for direct construction; the
    ``repro.api`` planner turns them on and records why in
    ``Plan.reasons``.
    """

    def __init__(
        self,
        d: int,
        *,
        base_capacity: int = DEFAULT_BASE_CAPACITY,
        tomb_limit: int = DEFAULT_TOMB_LIMIT,
        brute_cutoff: int = DEFAULT_BRUTE_CUTOFF,
        rebuild_crossover: Optional[int] = None,
        tile_q: int = 128,
        backend: str = "auto",
        devices: Optional[Sequence[Any]] = None,
        merge_async: bool = False,
        precision: str = "fp32",
        memory_budget: Optional[int] = None,
    ):
        if d < 1:
            raise ValueError(f"need d >= 1, got {d}")
        if base_capacity < 2:
            raise ValueError(f"base_capacity must be >= 2, got {base_capacity}")
        if tomb_limit < 1:
            raise ValueError(f"tomb_limit must be >= 1, got {tomb_limit}")
        if brute_cutoff < 4:
            raise ValueError(f"brute_cutoff must be >= 4, got {brute_cutoff}")
        if precision not in PRECISIONS:
            raise ValueError(f"precision={precision!r} not in {PRECISIONS}")
        if memory_budget is not None and memory_budget < 1:
            raise ValueError(f"memory_budget must be >= 1, got {memory_budget}")
        self.d = int(d)
        self.base_capacity = int(base_capacity)
        self.tomb_limit = int(tomb_limit)
        self.brute_cutoff = int(brute_cutoff)
        self.rebuild_crossover = (
            int(rebuild_crossover) if rebuild_crossover is not None else None
        )
        self.tile_q = int(tile_q)
        self.backend = backend
        self.merge_async = bool(merge_async)
        # tree-shard leaf slabs are stored at ``precision`` (brute shards
        # stay fp32: they sit below the cutoff, a rounding error next to
        # the tree rungs) and chunk-stream when ``memory_budget`` can't
        # hold a rung's slab resident — see _tree_shard_chunks
        self.precision = precision
        self.memory_budget = (
            int(memory_budget) if memory_budget is not None else None
        )
        self._placer = ShardPlacer(devices)
        # stable device ordinals for fault injection / event strings:
        # placement drops lost devices, this list never mutates
        self._all_devices = list(self._placer.devices)
        self._fanout = DeviceFanout()
        self._merger: Optional[MergeWorker] = None
        self._shards: List[_Shard] = []
        self._seq = itertools.count()
        self._next_id = 0
        self._n_live = 0
        self._last_stats = SearchStats()
        self._warm_shapes: set = set()
        # _mu guards forest topology + live bits against the merge worker;
        # user-facing calls are already serialized by the KNNIndex facade
        self._mu = threading.RLock()
        self._merge_stats = {
            "scheduled": 0, "completed": 0, "aborted": 0, "failed": 0,
            "inline": 0, "retried": 0, "device_loss": 0,
        }
        self._retry_streak = 0         # consecutive merge failures
        self._events: List[str] = []   # operational events -> SearchStats
        self._merge_test_hook = None   # tests: callable(phase, a, b)

    # ------------------------------------------------------------------
    @classmethod
    def from_points(cls, points: np.ndarray, **kw) -> "DynamicIndex":
        points = np.asarray(points, np.float32)
        if points.ndim != 2:
            raise ValueError(f"points must be [n, d], got {points.shape}")
        idx = cls(points.shape[1], **kw)
        idx.insert(points)
        return idx

    # ------------------------------------------------------------------
    @property
    def n_live(self) -> int:
        return self._n_live

    @property
    def n_tomb(self) -> int:
        with self._mu:
            return sum(s.n_tomb for s in self._shards)

    @property
    def stats(self) -> SearchStats:
        return self._last_stats

    @property
    def devices(self) -> List[Any]:
        return list(self._placer.devices)

    @property
    def pending_merges(self) -> int:
        """Background carry merges still in flight (0 when inline)."""
        return self._merger.pending if self._merger is not None else 0

    def merge_stats(self) -> Dict[str, int]:
        with self._mu:
            return dict(self._merge_stats)

    def drain_merges(self, timeout: Optional[float] = None) -> None:
        """Block until every background merge (and its carry chain,
        including backoff retries) has landed.  No-op when inline.

        Raises ``MergeRetryExhausted`` (with ``.rung``) when a merge kept
        failing through its bounded retries, and ``DrainTimeout`` (with
        the stuck ``.rungs``) when ``timeout`` expires first — a wedged
        worker can bound shutdown, never hang it."""
        if self._merger is not None:
            self._merger.drain(timeout)

    def _sorted_shards(self) -> List[_Shard]:
        return sorted(self._shards, key=lambda s: (s.rung, s.seq))

    def shard_layout(self) -> List[Tuple[int, int, int, str]]:
        """(capacity, live, tombstones, kind) per shard, smallest rung first
        — the forest's 'binary counter' state, for tests and describe().
        Transient duplicates at a rung mean a background merge is pending;
        ``drain_merges()`` settles the counter."""
        with self._mu:
            return [
                (s.capacity, s.n_live, s.n_tomb, s.kind)
                for s in self._sorted_shards()
            ]

    def placement(self) -> List[Tuple[int, str, Any]]:
        """(capacity, kind, device) per shard — the live placement map."""
        with self._mu:
            return [
                (s.capacity, s.kind, s.device) for s in self._sorted_shards()
            ]

    def _device_ordinal(self, device: Any) -> int:
        for i, d in enumerate(self._all_devices):
            if d is device:
                return i
        return -1

    def handle_device_loss(self, device: Any) -> str:
        """Degrade gracefully after ``device`` stops answering: drop it
        from placement and rebuild its shards onto the survivors from the
        host slabs (shards are immutable host-resident arrays plus a
        persisted top tree, so migration is a device transfer, never a
        median-split rebuild).  Returns the event string, which is also
        queued for the next ``SearchStats.events`` (and from there lands
        in ``Plan.reasons`` via the api facade).  Raises when the lost
        device is the LAST one — there is nothing left to degrade to.

        The migrated shards warm lazily: their first scan on the new
        device pays that device's compile, the price of degraded mode.
        In-flight merges targeting the dead device fail and re-route via
        the bounded-backoff retry (the placer no longer offers it).
        """
        with self._mu:
            if not any(d is device for d in self._placer.devices):
                return ""   # concurrent loss already handled
            self._placer.drop_device(device)   # raises on the last device
            moved = 0
            for s in self._shards:
                if s.device is device:
                    new_dev = self._placer.place(s.capacity, s.kind)
                    s.device = new_dev
                    s._dev_slab = None
                    if s.engine is not None:
                        # adopt the old store's state (codes + dead mask):
                        # re-quantizing would refit scales against PAD-
                        # overwritten reclaim rows and waste O(n d) work
                        s.engine = BufferKDTree(
                            s.points,
                            tree=s.engine.tree,
                            n_chunks=s.engine.store.n_chunks,
                            tile_q=self.tile_q,
                            backend=self.backend,
                            device=new_dev,
                            precision=s.engine.precision,
                            store_state=s.engine.store.quantized_state(),
                        )
                    moved += 1
            self._merge_stats["device_loss"] += 1
            event = (
                f"device loss: device {self._device_ordinal(device)} "
                f"({device}) dropped; re-placed {moved} shard(s) across "
                f"{self._placer.n_devices} surviving device(s); queries "
                f"degrade to survivors, exactness preserved"
            )
            self._events.append(event)
        return event

    def live_ids(self) -> np.ndarray:
        """Sorted i64 ids of the live multiset (test oracle support)."""
        with self._mu:
            parts = [s.ids[s.live] for s in self._shards]
        if not parts:
            return np.empty((0,), np.int64)
        return np.sort(np.concatenate(parts))

    def resident_bytes(self) -> int:
        """Largest per-device byte footprint of the shard slabs (the
        planner's §3 memory term is per device)."""
        with self._mu:
            per_dev: Dict[int, int] = {}
            for s in self._shards:
                b = (
                    s.engine.store.resident_bytes()
                    if s.engine is not None
                    else s.capacity * self.d * 4
                )
                key = id(s.device)
                per_dev[key] = per_dev.get(key, 0) + b
        return max(per_dev.values(), default=0)

    # ------------------------------------------------------------------
    # persistence: array-map snapshot of the live forest + lossless restore
    # (serialized by repro.persist; see docs/OPERATIONS.md for the format)
    # ------------------------------------------------------------------
    def snapshot(self) -> Tuple[Dict[str, np.ndarray], dict]:
        """Consistent array-map snapshot of the forest: per-shard slabs,
        ids, live bits, and (for tree shards) the top-tree arrays — plus a
        JSON-able meta dict (ctor params, id counter, warm-shape set).

        Taken under the mutation lock, so it is consistent at a mutation
        boundary even with background merges in flight: a pending merge's
        SOURCES are captured (same live multiset as the merged result),
        and ``restore`` re-schedules the collision.  No drain required.
        """
        with self._mu:
            shards = self._sorted_shards()
            arrays: Dict[str, np.ndarray] = {}
            shard_meta: List[dict] = []
            for i, s in enumerate(shards):
                arrays[f"shard{i}/points"] = s.points.copy()
                arrays[f"shard{i}/ids"] = s.ids.copy()
                arrays[f"shard{i}/live"] = s.live.copy()
                sm = dict(
                    rung=s.rung, capacity=s.capacity, n_rows=s.n_rows,
                    n_tomb=s.n_tomb, kind=s.kind,
                )
                if s.engine is not None:
                    # include_derived: the leaf-ordered slab + padded slab
                    # are immutable after build (tombstones only flip
                    # ``live``), so no copy is needed, and persisting them
                    # keeps restore free of the [n] gather and the padded
                    # fill — pure mmap-able I/O (space-for-time; see
                    # docs/OPERATIONS.md)
                    t = s.engine.tree
                    for key, arr in tree_to_arrays(
                        t, include_derived=True
                    ).items():
                        arrays[f"shard{i}/tree/{key}"] = arr
                    sm["tree"] = dict(height=t.height, leaf_pad=t.leaf_pad)
                    if s.engine.store.quantized:
                        # quantized stores round-trip their codes + dead
                        # mask verbatim (re-quantizing on restore would
                        # refit scales against reclaim-overwritten rows)
                        for key, arr in (
                            s.engine.store.quantized_state()
                            .to_arrays().items()
                        ):
                            arrays[f"shard{i}/{key}"] = arr
                shard_meta.append(sm)
            meta = dict(
                d=self.d,
                base_capacity=self.base_capacity,
                tomb_limit=self.tomb_limit,
                brute_cutoff=self.brute_cutoff,
                rebuild_crossover=self.rebuild_crossover,
                tile_q=self.tile_q,
                backend=self.backend,
                merge_async=self.merge_async,
                precision=self.precision,
                memory_budget=self.memory_budget,
                next_id=int(self._next_id),
                n_live=int(self._n_live),
                warm_shapes=sorted(list(t) for t in self._warm_shapes),
                shards=shard_meta,
            )
        return arrays, meta

    @classmethod
    def restore(
        cls,
        arrays: Dict[str, np.ndarray],
        meta: dict,
        *,
        devices: Optional[Sequence[Any]] = None,
    ) -> "DynamicIndex":
        """Rebuild a forest from ``snapshot()`` output WITHOUT re-running
        any O(h*n) median-split build: tree shards reconstruct their
        ``TopTree`` from the persisted split arrays (``tree_from_arrays``)
        and hand it to ``BufferKDTree`` prebuilt — the warm-restart path.

        ``devices`` is the CURRENT device list (snapshots are placement-
        free: shards are re-placed biggest-first on whatever is visible
        now, so a snapshot from a 4-device host restores on 1 and vice
        versa).  The warm-shape set is restored for FUTURE shards; the
        restored shards themselves compile lazily on first touch (both
        boot paths pay the same compiles, so this keeps restore I/O-bound
        — call ``warm`` after restore to front-load them).
        """
        idx = cls(
            int(meta["d"]),
            base_capacity=int(meta["base_capacity"]),
            tomb_limit=int(meta["tomb_limit"]),
            brute_cutoff=int(meta["brute_cutoff"]),
            rebuild_crossover=meta.get("rebuild_crossover"),
            tile_q=int(meta["tile_q"]),
            backend=meta["backend"],
            devices=devices,
            merge_async=bool(meta["merge_async"]),
            # snapshots written before the precision field default to fp32
            precision=str(meta.get("precision", "fp32")),
            memory_budget=meta.get("memory_budget"),
        )
        idx._warm_shapes = {tuple(t) for t in meta.get("warm_shapes", [])}
        # biggest-first placement, like any bin-packing heuristic
        order = sorted(
            range(len(meta["shards"])),
            key=lambda i: -int(meta["shards"][i]["capacity"]),
        )
        with idx._mu:
            for i in order:
                sm = meta["shards"][i]
                pts = np.ascontiguousarray(
                    arrays[f"shard{i}/points"], np.float32
                )
                ids = np.ascontiguousarray(arrays[f"shard{i}/ids"], np.int64)
                live = np.ascontiguousarray(arrays[f"shard{i}/live"], bool)
                cap = int(sm["capacity"])
                device = idx._placer.place(cap, sm["kind"])
                engine = None
                if sm["kind"] == "tree":
                    from repro.core.quantize import QuantizedSlabs

                    tm = sm["tree"]
                    prefix = f"shard{i}/tree/"
                    t_arr = {
                        key[len(prefix):]: arr
                        for key, arr in arrays.items()
                        if key.startswith(prefix)
                    }
                    # snapshots with derived slabs restore without the
                    # [n] gather; older ones fall back to it
                    reordered = t_arr.get("points")
                    if reordered is None:
                        reordered = pts[t_arr["orig_idx"]]
                    tree = tree_from_arrays(
                        reordered,
                        t_arr,
                        height=int(tm["height"]),
                        leaf_pad=int(tm["leaf_pad"]),
                    )
                    store_state = None
                    if f"shard{i}/quant/codes" in arrays:
                        store_state = QuantizedSlabs.from_arrays(
                            arrays, idx.precision, prefix=f"shard{i}/quant"
                        )
                    engine = BufferKDTree(
                        pts, tree=tree,
                        n_chunks=idx._tree_shard_chunks(
                            cap, int(tm["height"])
                        ),
                        tile_q=idx.tile_q,
                        backend=idx.backend, device=device,
                        precision=idx.precision, store_state=store_state,
                    )
                shard = _Shard(
                    rung=int(sm["rung"]), capacity=cap, points=pts,
                    ids=ids, live=live, n_rows=int(sm["n_rows"]),
                    n_tomb=int(sm["n_tomb"]), engine=engine, device=device,
                    seq=next(idx._seq), tomb_limit=idx.tomb_limit,
                )
                if engine is not None and shard.n_tomb:
                    # re-apply the leaf-store reclaim (idempotent): format-1
                    # snapshots predate the tree-shard row rewrite, and the
                    # tightened bare-k fetch width depends on it
                    tomb_rows = np.nonzero(~live[: shard.n_rows])[0]
                    idx._reclaim_tree_rows(shard, tomb_rows)
                idx._shards.append(shard)
            idx._next_id = int(meta["next_id"])
            idx._n_live = int(meta["n_live"])
            # a snapshot taken mid-merge holds the pre-swap sources: the
            # rung collision is still pending — resolve it now
            idx._schedule_carries()
        return idx

    # ------------------------------------------------------------------
    def _fit_rung(self, count: int) -> int:
        r = 0
        while (self.base_capacity << r) < count:
            r += 1
        return r

    def _tree_geom(self, cap: int, height: int) -> Tuple[int, int, int]:
        """(n_leaves, per-leaf slab bytes, dequantize meta bytes) of a
        rung-``cap`` tree shard at ``height`` — the planner's residency
        model (same padding rules as ``build_top_tree``)."""
        n_leaves = 1 << height
        leaf_pad = slab_len(-(-cap // n_leaves))
        d_pad = max(_round_up(self.d, 8), 8)
        leaf_bytes = leaf_pad * d_pad * BYTES_PER_ELEM[self.precision]
        if self.precision == "fp32":
            meta = 0
        elif self.precision == "fp16":
            meta = n_leaves * (-(-leaf_pad // 8))
        else:
            meta = n_leaves * (2 * d_pad * 4 + -(-leaf_pad // 8))
        return n_leaves, leaf_bytes, meta

    def _tree_shard_height(self, cap: int) -> int:
        """Tree height for a rung-``cap`` shard: the usual heuristic,
        DEEPENED under a ``memory_budget`` until two leaves (the streaming
        floor) fit — big leaves are fine when the whole slab is resident,
        but they are the streaming granularity, so an honest budget needs
        leaves small enough to stream within it.  Bounded by the 8-row
        leaf-pad floor; a budget below even that is handled (and reported)
        by ``_tree_shard_chunks``."""
        height = suggest_height(cap)
        if self.memory_budget is None:
            return height
        max_h = max(height, (max(2, cap // 8)).bit_length() - 1)
        best_h, best_floor = height, None
        for h in range(height, max_h + 1):
            n_leaves, leaf_bytes, meta = self._tree_geom(cap, h)
            if (
                n_leaves * leaf_bytes + meta <= self.memory_budget
                or 2 * leaf_bytes + meta <= self.memory_budget
            ):
                return h
            floor = 2 * leaf_bytes + meta
            if best_floor is None or floor < best_floor:
                best_h, best_floor = h, floor
        # nothing fits (quantize metadata alone can exceed a tiny budget):
        # take the height whose streaming floor comes closest — the
        # over-budget event is recorded by _tree_shard_chunks
        return best_h

    def _tree_shard_chunks(self, cap: int, height: int) -> int:
        """Budget-aware chunk count for one tree shard's leaf store: keep
        the rung resident when its slab + any dequantize metadata fit
        ``memory_budget``, otherwise chunk-stream with two buffers
        resident.  The budget bounds each shard individually — the
        dominant rung holds ~all points, so it is the forest's residency
        high-water mark; lower rungs are geometrically smaller.  A budget
        below even the 2-leaf streaming floor is recorded as an
        over-budget event (surfaced via ``SearchStats.events``), and the
        shard streams one leaf per chunk — best effort, honestly
        reported.
        """
        if self.memory_budget is None:
            return 1
        n_leaves, leaf_bytes, meta = self._tree_geom(cap, height)
        if n_leaves * leaf_bytes + meta <= self.memory_budget:
            return 1
        chunk_leaves = (self.memory_budget - meta) // (2 * leaf_bytes)
        if chunk_leaves >= 1:
            return min(-(-n_leaves // int(chunk_leaves)), n_leaves)
        with self._mu:
            self._events.append(
                f"over budget: memory_budget={self.memory_budget}B is "
                f"below the rung-{cap} tree shard's 2-leaf streaming "
                f"floor {2 * leaf_bytes + meta}B at precision "
                f"{self.precision}; streaming one leaf per chunk"
            )
        return n_leaves

    def _make_shard(self, pts: np.ndarray, ids: np.ndarray) -> _Shard:
        """Build one immutable shard from live rows (sorted by id), place
        it, and precompile its scan for every registered warm shape.  Runs
        WITHOUT the mutation lock when called from the merge worker — all
        inputs are snapshots, the placer carries its own lock."""
        order = np.argsort(ids, kind="stable")
        pts, ids = pts[order], ids[order]
        n = pts.shape[0]
        rung = self._fit_rung(n)
        cap = self.base_capacity << rung
        slab = np.full((cap, self.d), np.float32(PAD_COORD))
        slab[:n] = pts
        id_arr = np.full((cap,), -1, np.int64)
        id_arr[:n] = ids
        live = np.zeros((cap,), bool)
        live[:n] = True
        kind = "brute" if cap <= self.brute_cutoff else "tree"
        device = self._placer.place(cap, kind)
        engine = None
        if kind == "tree":
            # static chunked-engine shard over the FULL padded slab: the
            # rung, not the live count, determines every compiled shape
            height = self._tree_shard_height(cap)
            engine = BufferKDTree(
                slab,
                height=height,
                n_chunks=self._tree_shard_chunks(cap, height),
                tile_q=self.tile_q,
                backend=self.backend,
                device=device,
                precision=self.precision,
            )
        shard = _Shard(
            rung=rung, capacity=cap, points=slab, ids=id_arr, live=live,
            n_rows=n, engine=engine, device=device, seq=next(self._seq),
            tomb_limit=self.tomb_limit,
        )
        self._warm_shard(shard)
        return shard

    def _warm_shard(self, shard: _Shard) -> None:
        """Precompile the shard's scan for every registered (batch, k)
        shape — at construction, i.e. in the background worker for staging
        shards, never on the query path."""
        with self._mu:
            # snapshot: warm() mutates the set under _mu while the merge
            # worker runs this lock-free (the compiles below must NOT hold
            # the lock — they can take seconds)
            shapes = sorted(self._warm_shapes)
        for mp, k in shapes:
            kq = shard.fetch_width(k)
            if shard.engine is not None:
                shard.engine.warm(mp, kq)
            else:
                qz = np.zeros((mp, self.d), np.float32)
                self._brute_scan(shard, self._put_queries(qz, shard.device), kq)

    def _drop_shard(self, shard: _Shard) -> None:
        """Remove from the forest and return its capacity to the placer
        (caller holds ``_mu``)."""
        self._shards.remove(shard)
        self._placer.release(shard.capacity, shard.device)

    # ------------------------------------------------------------------
    # carry chain: inline (merge_async=False) or background staging swap
    # ------------------------------------------------------------------
    def _collisions(self) -> Dict[int, List[_Shard]]:
        by: Dict[int, List[_Shard]] = {}
        for s in self._sorted_shards():
            if not s.merging:
                by.setdefault(s.rung, []).append(s)
        return {r: ss for r, ss in by.items() if len(ss) >= 2}

    def _schedule_carries(self) -> None:
        """Resolve rung collisions (caller holds ``_mu``): inline fuse, or
        snapshot + hand off to the background worker."""
        if not self.merge_async:
            while True:
                coll = self._collisions()
                if not coll:
                    return
                rung = min(coll)
                a, b = coll[rung][0], coll[rung][1]
                pts = np.concatenate([a.points[a.live], b.points[b.live]])
                ids = np.concatenate([a.ids[a.live], b.ids[b.live]])
                self._drop_shard(a)
                self._drop_shard(b)
                self._shards.append(self._make_shard(pts, ids))
                self._merge_stats["inline"] += 1
        if self._merger is None:
            self._merger = MergeWorker()
        while True:   # a rung may hold >2 free shards after an abort
            coll = self._collisions()
            if not coll:
                return
            for _, ss in sorted(coll.items()):
                a, b = ss[0], ss[1]
                a.merging = b.merging = True
                # snapshot the live rows NOW, under the lock: the worker
                # must never read arrays a concurrent delete overwrites
                snaps = [
                    (s, s.points[s.live].copy(), s.ids[s.live].copy())
                    for s in (a, b)
                ]
                self._merge_stats["scheduled"] += 1
                self._merger.submit(
                    functools.partial(self._merge_task, snaps), meta=a.rung
                )

    def _merge_task(self, snaps) -> None:
        """Background carry merge: build the staging shard lock-free from
        the snapshots, then swap it in atomically (re-applying any deletes
        that landed on the sources mid-merge).  If the re-applied deltas
        leave the staging shard over-tombstoned, it is compacted OUTSIDE
        the lock and the swap retried — the forest is only ever mutated
        once the shard that will replace the sources exists, and every
        expensive build runs lock-free so queries never wait on a merge.

        FAILURE CONTRACT: an exception anywhere (the realistic case is
        ``_make_shard`` failing to build/compile a staging shard) must not
        wedge the rung — the except path un-reserves the surviving
        sources and returns any un-swapped staging placement.  The merge
        is then RETRIED with capped exponential backoff (fresh snapshots
        each attempt, so a retry also re-routes around a dropped device);
        after ``MERGE_MAX_RETRIES`` consecutive failures the typed
        ``MergeRetryExhausted`` surfaces on the next ``drain()`` instead
        of a silent retry storm.  The sources are untouched until the
        single atomic swap, so no data is ever lost to a failed merge."""
        staged: List[_Shard] = []   # placed but not yet swapped/released
        hook = self._merge_test_hook

        def _discard(shard: _Shard) -> None:
            self._placer.release(shard.capacity, shard.device)
            staged.remove(shard)

        try:
            pts = np.concatenate([p for _, p, _ in snaps])
            ids = np.concatenate([i for _, _, i in snaps])
            while True:
                if hook is not None:
                    hook("build", snaps)
                faults.fire("merge.build", rung=snaps[0][0].rung)
                merged = self._make_shard(pts, ids)   # lock-free build
                staged.append(merged)
                if hook is not None:
                    hook("swap", snaps)
                faults.fire("merge.swap", rung=snaps[0][0].rung)
                with self._mu:
                    sources = [s for s, _, _ in snaps]
                    if not all(
                        any(s is t for t in self._shards) for s in sources
                    ):
                        # a source was compacted or flattened away mid-
                        # merge: its points live elsewhere now — discard
                        # the staging shard
                        for s in sources:
                            if any(s is t for t in self._shards):
                                s.merging = False
                        _discard(merged)
                        self._merge_stats["aborted"] += 1
                        self._schedule_carries()
                        return
                    for src, _, snap_ids in snaps:
                        # delta: snapshot rows whose live bit was cleared
                        # since (idempotent across retries — only rows
                        # still present and live in `merged` are touched)
                        pos = np.searchsorted(src.ids[: src.n_rows], snap_ids)
                        dead = snap_ids[~src.live[: src.n_rows][pos]]
                        if dead.size:
                            self._tombstone_rows(merged, dead)
                    if merged.n_tomb <= self.tomb_limit or merged.n_live == 0:
                        # THE swap: the only point where the forest mutates
                        for src in sources:
                            self._drop_shard(src)
                        if merged.n_live == 0:
                            _discard(merged)
                        else:
                            self._shards.append(merged)
                            staged.remove(merged)
                        self._merge_stats["completed"] += 1
                        self._retry_streak = 0
                        self._schedule_carries()
                        return
                    # over-tombstoned (deletes landed mid-merge): compact
                    # OUTSIDE the lock and retry — `merged` is invisible
                    # to every other thread, so its arrays are stable
                    pts = merged.points[merged.live]
                    ids = merged.ids[merged.live]
                    _discard(merged)
        except BaseException as err:
            # clean up first (un-reserve sources, return staging
            # placement), then decide: bounded backoff retry, or surface.
            # Queries stay exact off the untouched sources either way.
            with self._mu:
                for s, _, _ in snaps:
                    if any(s is t for t in self._shards):
                        s.merging = False
                for sh in staged:
                    if not any(sh is t for t in self._shards):
                        self._placer.release(sh.capacity, sh.device)
                self._merge_stats["failed"] += 1
                self._retry_streak += 1
                streak = self._retry_streak
            rung = snaps[0][0].rung
            if isinstance(err, Exception) and streak <= MERGE_MAX_RETRIES:
                # NOT a tight worker loop: the retry re-enters via
                # _schedule_carries after a capped exponential delay,
                # taking FRESH snapshots (sources may have gained deltas,
                # a dead staging device is no longer in the placer).  The
                # timer raises the worker's pending count immediately, so
                # drain() waits through the backoff window.
                delay = min(
                    _MERGE_RETRY_BASE_S * (2 ** (streak - 1)),
                    _MERGE_RETRY_CAP_S,
                )
                with self._mu:
                    self._merge_stats["retried"] += 1
                self._merger.submit_after(delay, self._retry_carries, meta=rung)
                return
            raise MergeRetryExhausted(
                f"carry merge at rung {rung} failed {streak} consecutive "
                f"time(s); bounded backoff exhausted "
                f"(MERGE_MAX_RETRIES={MERGE_MAX_RETRIES})",
                rung=rung,
            ) from err

    def _retry_carries(self) -> None:
        """Backoff retry body: the cleaned-up collision is still visible
        to ``_collisions()``, so re-running the scheduler re-snapshots the
        sources and resubmits the merge."""
        with self._mu:
            self._schedule_carries()

    # ------------------------------------------------------------------
    def insert(self, points: np.ndarray) -> np.ndarray:
        """Insert a batch; returns the assigned global ids (i64[b])."""
        pts = np.asarray(points, np.float32)
        if pts.ndim != 2 or pts.shape[1] != self.d:
            raise ValueError(f"points must be [b, {self.d}], got {pts.shape}")
        b = pts.shape[0]
        with self._mu:
            ids = np.arange(self._next_id, self._next_id + b, dtype=np.int64)
            self._next_id += b
            if b == 0:
                return ids
            # rebuild-vs-merge: a batch at/above the crossover makes one
            # flattening rebuild cheaper than pushing a carry chain through
            # every rung.  The planner-costed value was taken at BUILD-time
            # n; the true crossover scales ~n/levels, so as the index grows
            # the pinned number acts as a floor and the model takes over.
            if self.rebuild_crossover is not None:
                levels = max(1, math.ceil(math.log2(
                    max(2.0, max(1, self._n_live) / self.base_capacity)
                )))
                crossover = max(self.rebuild_crossover, self._n_live // levels)
            else:
                crossover = max(1, self._n_live)
            if self._shards and b >= crossover:
                all_pts = [s.points[s.live] for s in self._shards]
                all_ids = [s.ids[s.live] for s in self._shards]
                for s in list(self._shards):
                    self._drop_shard(s)   # in-flight merges abort at swap
                self._shards.append(
                    self._make_shard(
                        np.concatenate(all_pts + [pts]),
                        np.concatenate(all_ids + [ids]),
                    )
                )
            else:
                self._shards.append(self._make_shard(pts, ids))
            self._n_live += b
            self._schedule_carries()
            return ids

    # ------------------------------------------------------------------
    def _tombstone_rows(self, shard: _Shard, dead_ids: np.ndarray) -> None:
        """Clear live bits for the ``dead_ids`` present AND live in the
        shard (idempotent: ids already tombstoned or compacted away are
        skipped — merge-retry deltas are cumulative) and reclaim the rows
        in the backing structure so the bare-``k`` fetch width stays exact
        (caller holds ``_mu``): brute shards overwrite the slab
        coordinates with PAD_COORD; tree shards rewrite the corresponding
        leaf-store rows (``ChunkedLeafStore.kill_rows``) plus the
        leaf-ordered rescore copies."""
        sid = shard.ids[: shard.n_rows]
        pos = np.searchsorted(sid, dead_ids)
        safe = np.clip(pos, 0, max(0, shard.n_rows - 1))
        hit = (pos < shard.n_rows) & (sid[safe] == dead_ids) & shard.live[safe]
        rows = safe[hit]
        if rows.size == 0:
            return
        shard.live[rows] = False
        shard.n_tomb += int(rows.size)
        if shard.engine is None:
            shard.points[rows] = np.float32(PAD_COORD)
            shard._dev_slab = None   # re-put on next query
        else:
            self._reclaim_tree_rows(shard, rows)

    @staticmethod
    def _reclaim_tree_rows(shard: _Shard, rows: np.ndarray) -> None:
        """Rewrite tombstoned rows inside a tree shard's leaf structure
        (the ROADMAP's tombstone coordinate overwrite, tree-shard case):
        map slab rows -> leaf-ordered positions -> (leaf, row) and kill
        them in the ``ChunkedLeafStore`` (fp32: PAD_COORD overwrite in
        place; quantized: dead-mask flip, re-uploading only the tiny
        mask).  The leaf-ordered fp32 copies (``tree.points`` /
        ``points_padded``) are overwritten too, so the exact re-rank can
        never resurrect a deleted point and persisted derived slabs carry
        the reclaim.  Idempotent — restore re-applies it for snapshots
        written before this reclaim existed."""
        tree = shard.engine.tree
        n = tree.points.shape[0]
        inv = np.empty((n,), np.int64)
        inv[tree.orig_idx] = np.arange(n)
        p = inv[rows]                                 # leaf-ordered positions
        leaf = np.searchsorted(
            tree.leaf_start, p, side="right"
        ).astype(np.int64) - 1
        lrow = p - tree.leaf_start[leaf]
        shard.engine.store.kill_rows(leaf, lrow)
        tree.points[p] = np.float32(PAD_COORD)
        tree.points_padded[leaf, lrow, :] = np.float32(PAD_COORD)

    def delete(self, ids) -> int:
        """Tombstone the given live ids; returns the count removed.

        Raises ``KeyError`` if any id is unknown, already deleted, or
        repeated within the request — deletes are exact, never best-effort.
        """
        req = np.asarray(ids, np.int64).ravel()
        if req.size == 0:
            return 0
        if np.unique(req).size != req.size:
            raise KeyError("delete request contains duplicate ids")
        with self._mu:
            # resolve EVERY id before touching any live bit: a bad request
            # (unknown / already-deleted id) must leave the index unchanged
            found = np.zeros(req.shape, bool)
            hits: List[Tuple[_Shard, np.ndarray]] = []
            for shard in self._shards:
                sid = shard.ids[: shard.n_rows]
                pos = np.searchsorted(sid, req)
                safe = np.clip(pos, 0, max(0, shard.n_rows - 1))
                hit = (
                    (pos < shard.n_rows) & (sid[safe] == req)
                    & shard.live[safe]
                )
                if hit.any():
                    hits.append((shard, req[hit]))
                    found |= hit
            if not found.all():
                missing = req[~found].tolist()
                raise KeyError(f"ids not live in index: {missing}")
            for shard, dead in hits:
                self._tombstone_rows(shard, dead)
            self._n_live -= int(req.size)

            # threshold-triggered compaction: rebuild over-tombstoned
            # shards from their live rows (restores the n_tomb <=
            # tomb_limit invariant the tree-shard exactness bound relies
            # on); drop empty shards.  A shard reserved by an in-flight
            # merge is handled the same way — the merge aborts at swap.
            for shard in list(self._sorted_shards()):
                if shard.n_live == 0:
                    self._drop_shard(shard)
                elif shard.n_tomb > self.tomb_limit:
                    pts = shard.points[shard.live]
                    sids = shard.ids[shard.live]
                    self._drop_shard(shard)
                    self._shards.append(self._make_shard(pts, sids))
            self._schedule_carries()
        return int(req.size)

    # ------------------------------------------------------------------
    @staticmethod
    def _put_queries(qp: np.ndarray, device) -> jnp.ndarray:
        arr = jnp.asarray(qp)
        return arr if device is None else jax.device_put(arr, device)

    def _brute_scan(
        self, shard: _Shard, qp_dev: jnp.ndarray, kq: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Tiled brute scan of one shard's device-resident slab: the same
        jitted tile step as ``knn_brute``, but the slab stays committed to
        the shard's device across queries."""
        from repro.core.brute import _tile_step

        slab = shard.dev_slab()
        nx = slab.shape[0]
        tx = min(shard.capacity, _BRUTE_TILE_X)
        mp = qp_dev.shape[0]
        tq = min(mp, _BRUTE_TILE_Q)   # both powers of two: tq divides mp
        out_d = np.empty((mp, kq), np.float32)
        out_i = np.empty((mp, kq), np.int64)
        for qs in range(0, mp, tq):
            q = jax.lax.dynamic_slice_in_dim(qp_dev, qs, tq, 0)
            best_d = jnp.full((tq, kq), jnp.inf, jnp.float32)
            best_i = jnp.full((tq, kq), -1, jnp.int32)
            for xs in range(0, nx, tx):
                best_d, best_i = _tile_step(
                    q, jax.lax.dynamic_slice_in_dim(slab, xs, tx, 0),
                    jnp.int32(xs), best_d, best_i, k=kq,
                )
            out_d[qs:qs + tq] = np.sqrt(np.maximum(np.asarray(best_d), 0.0))
            out_i[qs:qs + tq] = np.asarray(best_i)
        return out_d, out_i

    def _shard_candidates(
        self, shard: _Shard, qp: np.ndarray, qp_dev, k: int, w: int, sb: dict
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One shard's nearest candidates (dists, global ids, keep).

        Fetches ``kq = shard.fetch_width(k)`` neighbors through the
        shard's engine, maps rows to global ids, masks tombstones/padding,
        and pads the list out to the uniform merge width ``w``.
        """
        mp = qp.shape[0]
        kq = shard.fetch_width(k)
        if shard.engine is not None:
            dd, rows = shard.engine.query(qp, k=kq)
            st = shard.engine.stats
            sb["points_scanned"] += st.points_scanned
            sb["units_scanned"] += st.units_scanned
            sb["flushes"] += st.flushes
            sb["iterations"] = max(sb["iterations"], st.iterations)
        else:
            dd, rows = self._brute_scan(shard, qp_dev, kq)
            sb["points_scanned"] += mp * shard.capacity
            sb["iterations"] = max(sb["iterations"], 1)
        rows = np.asarray(rows)
        valid = (rows >= 0) & (rows < shard.capacity)
        safe = np.clip(rows, 0, shard.capacity - 1)
        gids = shard.ids[safe]
        keep = valid & shard.live[safe] & (gids >= 0)
        if kq < w:
            pad = ((0, 0), (0, w - kq))
            dd = np.pad(np.asarray(dd, np.float32), pad,
                        constant_values=np.inf)
            gids = np.pad(gids, pad, constant_values=-1)
            keep = np.pad(keep, pad, constant_values=False)
        return np.asarray(dd, np.float32), gids, keep

    def query(
        self, queries: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
        """Exact kNN of the live multiset: (dists f32[m, k] ascending
        Euclidean, ids i64[m, k] global insertion ids, SearchStats).

        Fan-out runs one thread per DEVICE GROUP (each device's shards
        scanned in slot order on its own thread, so every dispatch queue
        stays busy); the fold is the usual jitted rank-merge chain.
        Background merges never block here — the snapshot taken under the
        lock answers from whichever side of a pending swap is current, and
        both sides hold the identical live multiset.
        """
        q = np.asarray(queries, np.float32)
        if q.ndim != 2 or q.shape[1] != self.d:
            raise ValueError(f"queries must be [m, {self.d}], got {q.shape}")
        if not 1 <= k <= self._n_live:
            raise ValueError(f"k={k} not in [1, n_live={self._n_live}]")
        m = q.shape[0]
        mp = _pad_batch(m)
        qp = np.zeros((mp, self.d), np.float32)
        qp[:m] = q
        w = k + self.tomb_limit

        # Fan-out with device-loss degradation: a DeviceLost from any
        # group re-places that device's shards onto the survivors (from
        # the host slabs — shards are immutable host arrays, nothing is
        # lost) and the fan-out restarts over the new placement.  Bounded
        # by the device count: each loss removes a device for good, and
        # losing the last one raises.
        for _attempt in range(len(self._placer.devices) + 1):
            with self._mu:
                shards = self._sorted_shards()
            results: List = [None] * len(shards)
            by_dev: Dict[Any, List[int]] = {}
            for slot, s in enumerate(shards):
                by_dev.setdefault(s.device, []).append(slot)
            boards: List[dict] = []

            def group_thunk(device, slots, shards=shards, results=results,
                            boards=boards):
                def run():
                    faults.fire(
                        "device.scan", device=device,
                        device_index=self._device_ordinal(device),
                    )
                    sb = dict(points_scanned=0, units_scanned=0, flushes=0,
                              iterations=0)
                    qp_dev = self._put_queries(qp, device)
                    for slot in slots:
                        results[slot] = self._shard_candidates(
                            shards[slot], qp, qp_dev, k, w, sb
                        )
                    boards.append(sb)
                return run

            try:
                self._fanout.run(
                    {dev: group_thunk(dev, slots)
                     for dev, slots in by_dev.items()}
                )
                break
            except faults.DeviceLost as e:
                self.handle_device_loss(e.device)
        else:  # pragma: no cover - handle_device_loss raises first
            raise RuntimeError("query fan-out kept losing devices")

        acc_d = acc_c = None
        gid_lists: List[np.ndarray] = []
        for slot, (dd, gids, keep) in enumerate(results):
            gid_lists.append(gids)
            sd, sc = _filter_sort(
                jnp.asarray(dd), jnp.asarray(keep), jnp.int32(slot * w)
            )
            if acc_d is None:
                acc_d, acc_c = sd, sc
            else:
                acc_d, acc_c = _merge_pair(acc_d, acc_c, sd, sc, w=w)

        out_d = np.asarray(acc_d)[:m, :k]
        codes = np.asarray(acc_c)[:m, :k]
        gids_all = np.stack(gid_lists)                      # [S, mp, w]
        rows = np.arange(m)[:, None]
        out_i = gids_all[codes // w, rows, codes % w].astype(np.int64)
        # k <= n_live guarantees k finite candidates per row; belt+braces
        # for the impossible tail (keeps the -1 contract if it ever trips)
        out_i[~np.isfinite(out_d)] = -1
        with self._mu:
            events = tuple(self._events)
            self._events.clear()
        self._last_stats = SearchStats(
            iterations=max((sb["iterations"] for sb in boards), default=0),
            flushes=sum(sb["flushes"] for sb in boards),
            units_scanned=sum(sb["units_scanned"] for sb in boards),
            points_scanned=sum(sb["points_scanned"] for sb in boards),
            queries_advanced=m,
            events=events,
        )
        return out_d, out_i, self._last_stats

    # ------------------------------------------------------------------
    def warm(self, m: int, k: int) -> None:
        """Register the (batch, k) shape so every FUTURE shard — including
        background-merge staging shards — precompiles its scan at
        construction, and precompile the current fan-out + merge chain
        with one throwaway query (no-op while the index holds < k
        points)."""
        with self._mu:
            self._warm_shapes.add((_pad_batch(int(m)), int(k)))
        if 1 <= k <= self._n_live:
            self.query(np.zeros((m, self.d), np.float32), k)
