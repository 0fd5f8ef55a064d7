"""kNN service launcher — the paper's own workload as a server.

Builds a ``repro.api.KNNIndex`` over a reference catalog and answers batched
kNN queries.  With no flags the planner picks the engine from data shape,
visible devices and (optionally simulated) memory budget; every plan
decision is printed with its reason.

``--append P`` exercises the batch-dynamic path: the index is planned
mutable, so the planner selects the ``dynamic`` engine (pinning an
immutable ``--engine`` together with ``--append`` fails fast at plan time
with a ValueError — no engine can honor both).  P extra points are
inserted incrementally in ``--append-batches`` batches after the initial
build, per-batch ingest timing is printed, and verification runs against
brute force over the GROWN reference set.

Example:
  PYTHONPATH=src python -m repro.launch.knn --n 100000 --m 10000 --d 10 \\
      --k 10 --chunks 3
  PYTHONPATH=src python -m repro.launch.knn --n 100000 --engine forest
  PYTHONPATH=src python -m repro.launch.knn --n 100000 --memory-budget 4000000
  PYTHONPATH=src python -m repro.launch.knn --n 100000 --append 20000
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import IndexSpec, KNNIndex, enable_compile_cache, knn_brute
from repro.data.pipeline import PointCloud


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--m", type=int, default=10_000)
    ap.add_argument("--d", type=int, default=10)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--height", type=int, default=0, help="0 = auto")
    ap.add_argument("--chunks", type=int, default=0, help="0 = auto")
    ap.add_argument("--engine", type=str, default=None,
                    help="registry engine name; default = planner's choice")
    ap.add_argument("--memory-budget", type=int, default=0,
                    help="device bytes for the leaf structure (0 = unlimited)")
    ap.add_argument("--append", type=int, default=0,
                    help="insert this many extra points incrementally after "
                         "the build (plans a mutable index)")
    ap.add_argument("--append-batches", type=int, default=4,
                    help="number of insert batches --append is split into")
    ap.add_argument("--sync-merges", action="store_true",
                    help="pin the dynamic engine's carry merges to the "
                         "insert path (default: background staging worker)")
    ap.add_argument("--verify", type=int, default=256,
                    help="verify this many queries against brute force")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    print(f"[knn] {enable_compile_cache()}")
    pc = PointCloud(args.n, args.d, seed=args.seed)
    pts = pc.points()
    q = pc.queries(args.m)

    spec = IndexSpec(
        engine=args.engine,
        height=args.height or None,
        n_chunks=args.chunks or None,
        memory_budget=args.memory_budget or None,
        k_hint=args.k,
        m_hint=args.m,
        mutable=True if args.append else None,
        merge_async=False if args.sync_merges else None,
    )
    t0 = time.time()
    idx = KNNIndex.build(pts, spec=spec)
    t_build = time.time() - t0
    print(idx.describe())
    t0 = time.time()
    res = idx.query(q, k=args.k)
    t_query = time.time() - t0
    print(f"[knn] n={args.n} m={args.m} d={args.d} k={args.k} "
          f"engine={idx.engine_name} chunks={idx.plan.n_chunks} "
          f"h={idx.height}")
    line = (f"[knn] train {t_build:.2f}s  test {t_query:.2f}s  "
            f"({args.m / t_query:.0f} q/s)")
    if res.stats.points_scanned:   # not every engine reports scan volume
        scanned = res.stats.points_scanned / max(1, args.m * args.n)
        line += f"  scanned {scanned:.3%} of brute"
    print(line)

    if args.append:
        extra = PointCloud(args.append, args.d, seed=args.seed + 1).points()
        batches = np.array_split(extra, max(1, args.append_batches))
        t_ingest = 0.0
        for i, batch in enumerate(batches):
            t0 = time.time()
            idx.insert(batch)
            dt = time.time() - t0
            t_ingest += dt
            print(f"[knn] append batch {i}: +{batch.shape[0]} pts in "
                  f"{dt:.3f}s ({batch.shape[0] / max(dt, 1e-9):.0f} pts/s)")
        print(f"[knn] append total: +{args.append} pts in {t_ingest:.2f}s "
              f"(full rebuild took {t_build:.2f}s for {args.n})")
        t0 = time.time()
        idx.drain()
        state = idx._state  # dynamic engine: report the forest's placement
        print(f"[knn] background merges drained in {time.time() - t0:.3f}s "
              f"({state.merge_stats()})")
        placed = {}
        for cap, kind, dev in state.placement():
            placed.setdefault(str(dev), []).append(f"{kind}:{cap}")
        for dev, shards in placed.items():
            print(f"[knn]   {dev}: {' '.join(shards)}")
        pts = np.concatenate([pts, extra])
        t0 = time.time()
        res = idx.query(q, k=args.k)
        print(f"[knn] post-append test {time.time() - t0:.2f}s over "
              f"n={idx.n}")

    if args.verify:
        v = min(args.verify, args.m)
        bd, bi = knn_brute(q[:v], pts, args.k)
        ok = np.allclose(res.dists[:v], bd, rtol=1e-4, atol=1e-4)
        recall = float((res.idx[:v] == bi).mean())
        print(f"[knn] verify: dists_ok={ok} recall@{args.k}={recall:.4f}")


if __name__ == "__main__":
    main()
