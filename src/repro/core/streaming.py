"""Streaming queries: per-row completions out of the chunked round loop.

The chunk-resident engine (``chunked_jit.ChunkResidentEngine``) retires
queries monotonically — once a row's pending-leaf entry goes to -1 its knn
row is final, even though the bulk-synchronous loop keeps running for the
rest of the batch.  ``stream_query`` exploits that: it runs the normal round
loop with the engine's ``on_retire`` hook attached, finalizes each retired
row subset immediately (the same exact-rescoring pass the batch path uses,
``lazysearch.finalize_candidates``) and delivers it to the caller's ``emit``
callback while later rounds are still scanning.  The hook detection rides
the double-buffered schedule readback, so streaming adds no extra device
syncs — round i+1's host-side scheduling still overlaps round i's scans.

This is what makes an online serving tier latency-honest: a request whose
query retires in round 3 of a 12-round batch is answered after round 3, not
after round 12.  ``serving/knn_server.py`` builds the admission-queue /
micro-batching front door on top of this primitive.
"""

from __future__ import annotations

from typing import Callable, Tuple

import numpy as np

from repro.core.lazysearch import (
    BufferKDTree,
    SearchStats,
    _StatsBuilder,
    finalize_candidates,
)

__all__ = ["stream_query"]

# emit(rows i64[r], dists f32[r, k], idx i64[r, k]) — rows are original
# query-row positions; each row is delivered exactly once, in retirement
# order, with finalized (rescored, sorted, original-ordering) results.
EmitFn = Callable[[np.ndarray, np.ndarray, np.ndarray], None]


def stream_query(
    bkd: BufferKDTree,
    queries: np.ndarray,
    k: int,
    emit: EmitFn,
) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
    """Exact kNN over ``queries`` with per-row streaming delivery.

    Runs the chunk-resident round loop once for the whole batch; every time
    a subset of rows retires, finalizes those rows and calls ``emit(rows,
    dists, idx)``.  Returns the fully assembled batch result ``(dists, idx,
    stats)`` — identical values to ``bkd.query`` — after the last emission,
    so callers may use either the callback stream or the return value.

    ``emit`` runs on the calling thread, interleaved with the round loop:
    keep it cheap (hand off to queues/events) or the rounds stall behind it.
    Requires the chunked engine tier (the host loop has no retirement map).

    ABORT CONTRACT: an exception raised by ``emit`` propagates out of this
    call, abandoning the remaining rounds — rows already emitted stay
    delivered, rows not yet retired are simply never emitted.  The abort
    leaves NO residual state: the tree, the engine and its jit caches are
    untouched, so the next ``stream_query``/``query`` on the same index is
    exact (``tests/test_serving_faults.py`` proves it, and ``KNNServer``'s
    transient-fault retry depends on it: the retry re-enters the engine
    with only the still-unresolved rows).
    """
    if bkd.engine != "chunked":
        raise ValueError(
            f"stream_query needs the chunked engine tier, got {bkd.engine!r}"
        )
    queries = np.asarray(queries, dtype=np.float32)
    m, d = queries.shape
    if d != bkd.d:
        raise ValueError(f"query dim {d} != reference dim {bkd.d}")
    if k > bkd.n:
        raise ValueError(f"k={k} > n={bkd.n}")

    out_d = np.empty((m, k), np.float32)
    out_i = np.full((m, k), -1, np.int64)
    # quantized stores overfetch candidates; the per-row exact re-rank below
    # slices each emission back to the caller's k (same seam as the batch path)
    k_eff = bkd._engine_k(k)

    def on_retire(rows: np.ndarray, d2: np.ndarray, gi: np.ndarray) -> None:
        dists, idx = finalize_candidates(bkd.tree, queries[rows], gi)
        dists, idx = dists[:, :k], idx[:, :k]
        out_d[rows] = dists
        out_i[rows] = idx
        emit(rows, dists, idx)

    _d2, _gi, info = bkd._engine.run(
        queries, k_eff, bkd.engine_tile_q, bkd.buffer_size, on_retire=on_retire
    )

    sb = _StatsBuilder.from_engine(info, bkd.store.host.shape[1])
    stats = sb.freeze()
    bkd._last_stats = stats
    return out_d, out_i, stats
