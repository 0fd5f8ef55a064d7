#!/usr/bin/env python3
"""One served sweep over a kNN configuration: not a cell, a measurement
that fixes the rate and the deadline of a later served cell.

    python bench/sweeps/served.py --config photo_d10_10m --seed 1 \\
        --rates 50,100,150,200,250 --seconds 20 --deadline-ms 3000

``KNNServer`` runs on ``IndexSpec(engine="streaming")`` with
``max_batch=256`` and ``purge_expired=False``.  The sweep first times whole
256-query batches (the warm service time), then offers open-loop Poisson
arrivals of single queries at each rate for ``--seconds``, drawn from the
seed.  Latency is timed from each request's scheduled send time, so a
stalled sender counts against the server; how late the sender ran is
reported beside it.  A TPU is required unless ``--rehearse``.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import harness, oracles  # noqa: E402


def _pct(values, q):
    return float(np.percentile(np.asarray(values), q)) if values else None


def sweep(cfg, seed, rates, seconds, *, deadline_ms, max_batch=256,
          log=harness.log):
    from repro.api import IndexSpec, KNNIndex
    from repro.serving.knn_server import KNNServer

    centers = oracles.cluster_centers(cfg["n_clusters"], cfg["dim"],
                                      cfg["centers_seed"])
    points = oracles.gaussian_mixture(centers, cfg["spread"],
                                      cfg["n_points"], seed, 0)
    spec = dict(cfg["index_spec"], engine="streaming")
    t = time.perf_counter()
    index = KNNIndex.build(points, spec=IndexSpec(**spec))
    log(f"[sweep] build_s={time.perf_counter() - t:.3f} "
        f"engine={index.engine_name} h={index.height}")
    k = int(cfg["k"])
    n_req = sum(int(r * seconds) for r in rates) + max_batch * 4
    queries = oracles.gaussian_mixture(centers, cfg["spread"], n_req, seed, 2)
    out = {"max_batch": max_batch, "deadline_ms": deadline_ms,
           "full_batch_s": [], "rates": []}
    with KNNServer(index, k=k, max_batch=max_batch,
                   default_deadline_ms=deadline_ms,
                   purge_expired=False) as server:
        for rep in range(4):     # the first includes warm-up
            t = time.perf_counter()
            tickets = server.submit_many(
                queries[rep * max_batch:(rep + 1) * max_batch])
            for tk in tickets:
                tk.result(timeout=600.0)
            out["full_batch_s"].append(time.perf_counter() - t)
        log(f"[sweep] full {max_batch}-query batch s: {out['full_batch_s']}")
        rng = np.random.default_rng(oracles.seed_sequence(seed, 8))
        base = 4 * max_batch
        for rate in rates:
            gaps = rng.exponential(1.0 / rate, size=int(rate * seconds))
            due = np.cumsum(gaps)
            qs = queries[base:base + due.size]
            start = time.monotonic()
            sent = []
            late = []
            for i, d in enumerate(due):
                now = time.monotonic() - start
                if d > now:
                    time.sleep(d - now)
                t_sub = time.monotonic()
                late.append(t_sub - start - d)
                sent.append((d, t_sub, server.submit(qs[i])))
            lat = []
            for d, t_sub, tk in sent:
                tk.result(timeout=600.0)
                lat.append(t_sub - start - d + tk.info["latency_s"])
            wall = max(t_sub - start + tk.info["latency_s"]
                       for d, t_sub, tk in sent)
            row = {
                "rate": rate, "requests": len(sent),
                "completed_per_s": len(sent) / wall,
                "p50_ms": 1e3 * _pct(lat, 50), "p95_ms": 1e3 * _pct(lat, 95),
                "max_ms": 1e3 * float(max(lat)),
                "sender_late_p95_ms": 1e3 * _pct(late, 95),
                "queue_wait_p95_ms": 1e3 * _pct(
                    [tk.info["wait_s"] for _, _, tk in sent], 95),
                "mean_batch": statistics.mean(
                    [tk.info["shape"] for _, _, tk in sent]),
            }
            out["rates"].append(row)
            log(f"[sweep] {row}")
            base += due.size
        out["server_stats"] = {k2: v for k2, v in server.stats().items()
                               if isinstance(v, (int, float, dict))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--deadline-ms", type=float, default=3000.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        harness.log("sweep: needs a TPU.  No CPU fallback.")
        return 1
    if not args.rehearse:
        harness.log(harness.use_compile_cache())
    with open(os.path.join(harness.BENCH, "configs",
                           args.config + ".json")) as f:
        raw = json.load(f)
    cfg = harness._overlay(raw, args.rehearse)
    rates = [float(r) for r in args.rates.split(",")]
    print(json.dumps(sweep(cfg, args.seed, rates, args.seconds,
                           deadline_ms=args.deadline_ms)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
