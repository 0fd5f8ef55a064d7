#!/usr/bin/env python3
"""Smoke run of the exact kNN service on one TPU chip (or four: ``--chips 4``).

Drives the main path through the entry points a user calls, ``KNNIndex`` and
``KNNServer``, over a 10,000,000-point, 10-D catalog (the psd_model_mag / crts
shape of ``PointCloud``) and checks every phase against a float64 NumPy brute
force on the host.  No phase checks against a device path.

  1. resident kNN     default ``IndexSpec``: the chunked engine with the
                      compiled Pallas leaf scan, 1,048,576 queries, k = 10;
                      the first call (compile included) and a warm call timed
  2. out-of-core kNN  the same catalog streamed in 4 chunks, answering the
                      first 131,072 of those queries; its neighbours must be
                      phase 1's
  3. served           ``KNNServer`` on the streaming engine answers 256 single
                      submits; no error, shed or deadline miss, and every
                      answer matches phase 1
  4. dual-tree        ``pair_count`` over 50,000 clustered 3-D lattice
                      positions equals the host histogram bin for bin

``--chips 4`` runs only the multi-device phase over the same catalog and
32,768 queries (256 checked against the oracle): the planner's own choice
(``forest``), a pinned ``sharded`` run and, as the comparison, ``chunked`` on
one device, with every device's ``bytes_in_use`` printed for each.

The last line of stdout is ``{"ok": true, "device": {...}}``.  A failed
check, or a platform other than TPU, exits non-zero and prints no such line;
nothing falls back to the CPU.  Run from the root of a checkout:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, multi-device phase only
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
if os.path.isdir(os.path.join(SRC, "repro")) and SRC not in sys.path:
    sys.path.insert(0, SRC)

SEED = 0
N_POINTS = 10_000_000
DIM = 10
N_QUERIES = 1_048_576
N_SUB = 131_072        # the query prefix phase 2 answers
N_FOUR = 32_768        # queries of the four-chip phase, where compiles dominate
N_FOUR_CHECK = 256
K = 10
N_CHECK = 512          # queries checked against the float64 oracle, drawn
                       # from the first N_SUB so every phase checks the same
RTOL = 1e-4            # relative tolerance on Euclidean distances
OOC_CHUNKS = 4
N_SERVED = 256
SERVE_DEADLINE_MS = 600_000.0
PC_POINTS = 50_000
PC_SPAN = 2048         # lattice coordinates in [0, PC_SPAN)
PC_HEIGHT = 8          # the dual-tree benchmark's tree height (~200-point leaves)
# pair_count edges sqrt(N) with N = 7 mod 8: no sum of three squares is 7 mod
# 8, so no lattice distance falls on an edge and f32 bins match float64 ones
PC_EDGE_SQ = (7, 31, 103, 407, 1607, 6407, 25607, 102407, 168103)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def log_host_memory(tag: str) -> None:
    """Resident and peak host memory of this process (the host oracles and
    the index builds share the machine's RAM with the runtime)."""
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    log(f"[host memory] {tag}: rss_gb={rss / 2**30:.2f} "
        f"peak_gb={peak / 2**30:.2f}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Host oracles (float64 / integer NumPy; independent of the code under test)
# ---------------------------------------------------------------------------
def _host_map(fn, items):
    """Map ``fn`` over ``items`` on a thread pool: NumPy's matmuls and ufuncs
    release the GIL, so blocks of an oracle run on the host's cores."""
    workers = max(1, min(8, os.cpu_count() or 1))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        return list(ex.map(fn, items))


def knn_oracle(points: np.ndarray, queries: np.ndarray, k: int,
               block: int = 32768, sample: int = 16384):
    """Exact kNN in float64 on the host, blocked over the reference set.

    Returns (Euclidean dists f64[m, k], ids i64[m, k]) ascending.  Every
    block scores |x|^2 - 2 q.x with one float64 matmul and keeps the points
    at or below the k-th best score among the first ``sample`` points (an
    upper bound on the final k-th); the survivors are rescored directly as
    sum((x - q)^2).
    """
    q = np.asarray(queries, np.float64)
    m = q.shape[0]
    qt = -2.0 * q.T

    def scores(lo, size=block):
        x = np.asarray(points[lo:lo + size], np.float64)
        v = x @ qt
        v += np.einsum("nd,nd->n", x, x)[:, None]          # [b, m]
        return v

    v0 = scores(0, max(sample, k))
    thr = np.partition(v0.T, k - 1, axis=1)[:, k - 1]
    del v0

    def candidates(lo):
        v = scores(lo)
        rows, cols = np.nonzero(v <= thr[None, :])
        return cols, v[rows, cols], rows + lo

    parts = _host_map(candidates, range(0, points.shape[0], block))
    cq, cv, ci = (np.concatenate(p) for p in zip(*parts))
    order = np.lexsort((ci, cv, cq))
    cq, ci = cq[order], ci[order]
    rank = np.arange(cq.size) - np.searchsorted(cq, np.arange(m))[cq]
    best_i = ci[rank < k].reshape(m, k)
    diff = np.asarray(points[best_i], np.float64) - q[:, None, :]
    d = np.sqrt(np.einsum("mkd,mkd->mk", diff, diff))
    order = np.argsort(d, axis=1, kind="stable")
    return np.take_along_axis(d, order, 1), np.take_along_axis(best_i, order, 1)


def pair_count_oracle(pos: np.ndarray, edge_sq, block: int = 256):
    """Histogram of all ordered pairs (i != j) of integer positions over the
    edges sqrt(edge_sq), in exact integer arithmetic (squared distances of
    lattice points below 2^31)."""
    p = np.asarray(pos, np.int32)
    e2 = np.asarray(edge_sq, np.int64)
    cap = int(e2[-1]) + 1

    def counts(lo):
        a = p[lo:lo + block]
        d2 = np.zeros((a.shape[0], p.shape[0]), np.int32)
        for c in range(p.shape[1]):
            diff = np.subtract(a[:, c:c + 1], p[None, :, c])
            np.multiply(diff, diff, out=diff)
            d2 += diff
        np.minimum(d2, cap, out=d2)
        return np.bincount(d2.ravel(), minlength=cap + 1)

    total = np.sum(_host_map(counts, range(0, p.shape[0], block)), axis=0)
    # d2 never equals an edge (edge_sq = 7 mod 8); the self pairs sit at 0
    cum = np.concatenate([[0], np.cumsum(total)])
    return cum[e2[1:]] - cum[e2[:-1]]


def lattice_catalog(n: int, span: int = PC_SPAN, seed: int = SEED) -> np.ndarray:
    """Clustered 3-D positions on the integer lattice [0, span)^3: 64 blobs
    of radius span/50, like the dual-tree benchmark's catalog."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 5]))
    r = span / 50.0
    centers = rng.uniform(r, span - 1 - r, size=(64, 3))
    u = rng.normal(size=(n, 3))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    radial = r * rng.random(n) ** (1.0 / 3.0)
    pos = centers[rng.integers(0, 64, n)] + u * radial[:, None]
    return np.clip(np.rint(pos), 0, span - 1).astype(np.float32)


# ---------------------------------------------------------------------------
# Agreement checks
# ---------------------------------------------------------------------------
def compare_knn(dists, idx, ref_d, ref_i, points, queries, rtol=RTOL) -> dict:
    """Agreement of one answer with the oracle over the checked queries.

    ``ref_d``/``ref_i`` hold k + 1 oracle neighbours so a tie across rank k
    is visible.  Distances must agree to ``rtol`` relative; ids must be equal
    except at ranks whose oracle distance ties a neighbouring rank within
    ``rtol``; every returned id's own float64 distance must be the distance
    returned for it.
    """
    k = dists.shape[1]
    d = np.asarray(dists, np.float64)
    rd = ref_d[:, :k]
    rel = np.abs(d - rd) / np.maximum(rd, 1e-30)
    tie_next = np.diff(ref_d, axis=1) <= rtol * ref_d[:, 1:]   # r ~ r+1
    tied = tie_next[:, :k].copy()
    tied[:, 1:] |= tie_next[:, :k - 1]
    mism = (idx != ref_i[:, :k]) & ~tied
    safe = np.clip(idx, 0, None)
    diff = np.asarray(points[safe], np.float64) - np.asarray(
        queries, np.float64)[:, None, :]
    own = np.sqrt(np.einsum("mkd,mkd->mk", diff, diff))
    own_rel = np.abs(own - d) / np.maximum(own, 1e-30)
    srt = np.sort(idx, axis=1)
    out = {
        "checked": int(d.shape[0]),
        "max_rel_dist_err": float(rel.max()),
        "index_mismatches": int(mism.sum()),
        "tied_ranks": int(tied.sum()),
        "max_rel_own_err": float(own_rel.max()),
    }
    out["ok"] = bool(
        out["max_rel_dist_err"] <= rtol
        and out["index_mismatches"] == 0
        and out["max_rel_own_err"] <= rtol
        and (idx >= 0).all()
        and (srt[:, 1:] != srt[:, :-1]).all()
    )
    return out


def same_neighbours(d_a, i_a, d_b, i_b) -> dict:
    """Rows where two answers differ; a difference is allowed only as a swap
    of ids at exactly equal distances."""
    rows = np.nonzero((i_a != i_b).any(axis=1))[0]
    swaps = int(sum(
        np.array_equal(d_a[r], d_b[r])
        and set(i_a[r].tolist()) == set(i_b[r].tolist())
        for r in rows
    ))
    return {"rows_differ": int(rows.size), "tie_swaps": swaps,
            "ok": bool(rows.size == swaps)}


def agree_within(a, b, points, queries, rtol=RTOL) -> dict:
    """Agreement of answer ``b`` with answer ``a`` from an engine whose
    float32 arithmetic differs (forest against chunked): distances match
    within ``rtol`` at every rank, ids are distinct, and an id that differs
    is, by its own float64 distance, within ``rtol`` of ``a``'s distance at
    that rank — a swap between neighbours the tolerance cannot order."""
    (d_a, i_a), (d_b, i_b) = a, b
    d_a = np.asarray(d_a, np.float64)
    rel = np.abs(np.asarray(d_b, np.float64) - d_a) / np.maximum(d_a, 1e-30)
    r, c = np.nonzero(i_a != i_b)
    diff = np.asarray(points[i_b[r, c]], np.float64) - np.asarray(
        queries[r], np.float64)
    own = np.sqrt(np.einsum("nd,nd->n", diff, diff))
    swap_rel = np.abs(own - d_a[r, c]) / np.maximum(d_a[r, c], 1e-30)
    srt = np.sort(i_b, axis=1)
    out = {
        "rows_differ": int(np.unique(r).size),
        "max_rel_dist_diff": float(rel.max()),
        "max_rel_swap_err": float(swap_rel.max()) if r.size else 0.0,
    }
    out["ok"] = bool(
        out["max_rel_dist_diff"] <= rtol and out["max_rel_swap_err"] <= rtol
        and (i_b >= 0).all() and (srt[:, 1:] != srt[:, :-1]).all()
    )
    return out


def fmt(info: dict) -> str:
    return " ".join(
        f"{k}={v:.3e}" if isinstance(v, float) and (v and abs(v) < 1e-3)
        else f"{k}={v}" for k, v in info.items()
    )


# ---------------------------------------------------------------------------
# Phases (sizes and backend are parameters so they rehearse at tiny size)
# ---------------------------------------------------------------------------
def phase_resident(points, queries, rows, oracle, *, k=K, backend="auto",
                   expect_backend="pallas"):
    from repro.api import IndexSpec, KNNIndex

    t0 = time.perf_counter()
    idx = KNNIndex.build(points, spec=IndexSpec(backend=backend))
    build_s = time.perf_counter() - t0
    log(idx.describe())
    check(idx.engine_name == "chunked",
          f"phase 1: planner chose {idx.engine_name}, expected chunked")
    check(idx.scan_backend == expect_backend,
          f"phase 1: leaf scan resolved to {idx.scan_backend}, "
          f"expected {expect_backend}")
    t0 = time.perf_counter()
    first = idx.query(queries, k=k)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = idx.query(queries, k=k)
    warm_s = time.perf_counter() - t0
    repeat = same_neighbours(first.dists, first.idx, res.dists, res.idx)
    agree = compare_knn(res.dists[rows], res.idx[rows], *oracle, points,
                        queries[rows])
    st = res.stats
    log(f"[phase1 resident] n={points.shape[0]} m={queries.shape[0]} k={k} "
        f"engine={idx.engine_name} backend={idx.scan_backend} "
        f"h={idx.height} n_chunks={idx.plan.n_chunks} "
        f"build_s={build_s:.3f} first_call_s={first_s:.3f} "
        f"warm_call_s={warm_s:.3f} rounds={st.iterations} "
        f"tail_rounds={st.tail_rounds} sync_wait_s={st.sync_wait_s:.3f}")
    log(f"[phase1 resident] oracle: {fmt(agree)}; warm vs first call: "
        f"{fmt(repeat)}")
    check(agree["ok"], f"phase 1 disagrees with the oracle: {agree}")
    check(repeat["ok"], f"phase 1 warm call differs from the first: {repeat}")
    return res.dists, res.idx


def phase_out_of_core(points, queries, rows, oracle, resident, *, k=K,
                      n_chunks=OOC_CHUNKS, backend="auto",
                      expect_backend="pallas"):
    """``queries`` is a prefix of phase 1's that holds ``rows``."""
    from repro.api import IndexSpec, KNNIndex

    resident = tuple(a[:queries.shape[0]] for a in resident)
    t0 = time.perf_counter()
    idx = KNNIndex.build(
        points, spec=IndexSpec(n_chunks=n_chunks, backend=backend)
    )
    build_s = time.perf_counter() - t0
    log(idx.describe())
    check(idx.engine_name == "chunked" and idx.plan.n_chunks == n_chunks,
          f"phase 2: got {idx.engine_name} with N={idx.plan.n_chunks}")
    check(idx.scan_backend == expect_backend,
          f"phase 2: leaf scan resolved to {idx.scan_backend}")
    t0 = time.perf_counter()
    res = idx.query(queries, k=k)
    call_s = time.perf_counter() - t0
    same = same_neighbours(resident[0], resident[1], res.dists, res.idx)
    agree = compare_knn(res.dists[rows], res.idx[rows], *oracle, points,
                        queries[rows])
    log(f"[phase2 out-of-core] m={queries.shape[0]} n_chunks={n_chunks} "
        f"build_s={build_s:.3f} first_call_s={call_s:.3f} "
        f"rounds={res.stats.iterations} tail_rounds={res.stats.tail_rounds} "
        f"chunk_rounds={res.stats.chunk_rounds} "
        f"sync_wait_s={res.stats.sync_wait_s:.3f} "
        f"resident_bytes={idx.resident_bytes()}")
    log(f"[phase2 out-of-core] oracle: {fmt(agree)}; vs phase 1: {fmt(same)}")
    check(agree["ok"], f"phase 2 disagrees with the oracle: {agree}")
    check(same["ok"], f"phase 2 neighbours differ from phase 1: {same}")


def phase_served(points, queries, rows, oracle, resident, *, k=K,
                 n_served=N_SERVED, backend="auto", expect_backend="pallas"):
    from repro.api import IndexSpec, KNNIndex
    from repro.serving.knn_server import KNNServer

    t0 = time.perf_counter()
    idx = KNNIndex.build(
        points, spec=IndexSpec(engine="streaming", backend=backend)
    )
    build_s = time.perf_counter() - t0
    check(idx.scan_backend == expect_backend,
          f"phase 3: leaf scan resolved to {idx.scan_backend}")
    served = rows[:n_served]
    t0 = time.perf_counter()
    server = KNNServer(idx, k=k, max_batch=n_served,
                       default_deadline_ms=SERVE_DEADLINE_MS)
    warm_s = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        tickets = [server.submit(queries[r]) for r in served]
        errors = [t.exception(timeout=SERVE_DEADLINE_MS / 1e3)
                  for t in tickets]
        serve_s = time.perf_counter() - t0
    finally:
        server.close(timeout=60.0)
    stats = server.stats()
    n_err = sum(e is not None for e in errors)
    log(f"[phase3 served] requests={len(tickets)} build_s={build_s:.3f} "
        f"server_warm_s={warm_s:.3f} serve_s={serve_s:.3f} "
        f"completed={stats['completed']} errors={n_err} "
        f"failed={stats['failed']} shed={stats['shed']} "
        f"purged={stats['purged']} batches={stats['batches_by_close']}")
    check(n_err == 0, f"phase 3: {n_err} request(s) failed: "
          f"{[repr(e) for e in errors if e is not None][:3]}")
    check(stats["failed"] == 0 and stats["shed"] == 0
          and stats["purged"] == 0 and stats["cancelled"] == 0
          and stats["completed"] == len(tickets),
          f"phase 3: server stats {stats}")
    d = np.stack([t.result()[0] for t in tickets])
    i = np.stack([t.result()[1] for t in tickets])
    same = same_neighbours(resident[0][served], resident[1][served], d, i)
    n = len(served)
    agree = compare_knn(d, i, oracle[0][:n], oracle[1][:n], points,
                        queries[served])
    log(f"[phase3 served] oracle: {fmt(agree)}; vs phase 1: {fmt(same)}")
    check(agree["ok"], f"phase 3 disagrees with the oracle: {agree}")
    check(same["ok"], f"phase 3 answers differ from phase 1: {same}")


def phase_pair_count(pos, ref, edge_sq=PC_EDGE_SQ, *, height=PC_HEIGHT,
                     backend="auto"):
    """``ref`` is ``pair_count_oracle(pos, edge_sq)``."""
    from repro.api import IndexSpec, KNNIndex

    edges = np.sqrt(np.asarray(edge_sq, np.float64))
    t0 = time.perf_counter()
    idx = KNNIndex.build(pos, spec=IndexSpec(
        op="pair_count", height=height, backend=backend))
    build_s = time.perf_counter() - t0
    check(idx.engine_name == "chunked",
          f"phase 4: planner chose {idx.engine_name}, expected chunked")
    t0 = time.perf_counter()
    first = idx.pair_count(edges)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = idx.pair_count(edges)
    warm_s = time.perf_counter() - t0
    diff = np.asarray(res.values, np.int64) - ref
    log(f"[phase4 pair_count] n={pos.shape[0]} h={idx.height} "
        f"bins={len(edges) - 1} build_s={build_s:.3f} "
        f"first_call_s={first_s:.3f} warm_call_s={warm_s:.3f} "
        f"pairs={int(ref.sum())}")
    log(f"[phase4 pair_count] hist={res.values.tolist()} "
        f"oracle={ref.tolist()} bins_differ={int((diff != 0).sum())}")
    check(np.array_equal(first.values, res.values),
          "phase 4: warm call differs from the first")
    check(not diff.any(), "phase 4: histogram differs from the oracle")


def _catalog(n, m, n_sub, n_check):
    """The seeded catalog, its queries and the checked rows (drawn from the
    first ``n_sub`` queries)."""
    from repro.data.pipeline import PointCloud

    pc = PointCloud(n, DIM, seed=SEED)
    points = pc.points()
    queries = pc.queries(m)
    rng = np.random.default_rng(np.random.SeedSequence([SEED, 6]))
    rows = rng.choice(min(n_sub, m), size=min(n_check, n_sub, m),
                      replace=False)
    return points, queries, rows


def run_one(*, n=N_POINTS, m=N_QUERIES, n_sub=N_SUB, n_check=N_CHECK,
            n_served=N_SERVED, n_chunks=OOC_CHUNKS, pc_points=PC_POINTS,
            pc_height=PC_HEIGHT, backend="auto",
            expect_backend="pallas") -> None:
    """The four one-chip phases, in order; the first failure raises."""
    t0 = time.perf_counter()
    points, queries, rows = _catalog(n, m, n_sub, n_check)
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    oracle = knn_oracle(points, queries[rows], K + 1)
    log(f"[setup] catalog n={n} d={DIM} queries={m} data_s={data_s:.3f} "
        f"host_oracle_s={time.perf_counter() - t0:.3f} "
        f"(float64, {rows.size} queries)")
    # the pair-count oracle runs here, while the host holds the least
    t0 = time.perf_counter()
    pos = lattice_catalog(pc_points)
    pc_ref = pair_count_oracle(pos, PC_EDGE_SQ)
    log(f"[setup] lattice n={pc_points} pair_count host_oracle_s="
        f"{time.perf_counter() - t0:.3f}")
    common = dict(backend=backend, expect_backend=expect_backend)
    log_host_memory("after setup")
    resident = phase_resident(points, queries, rows, oracle, **common)
    gc.collect()
    log_host_memory("after phase 1")
    phase_out_of_core(points, queries[:n_sub], rows, oracle, resident,
                      n_chunks=n_chunks, **common)
    gc.collect()
    log_host_memory("after phase 2")
    phase_served(points, queries, rows, oracle, resident,
                 n_served=n_served, **common)
    gc.collect()
    log_host_memory("after phase 3")
    phase_pair_count(pos, pc_ref, height=pc_height, backend=backend)
    log_host_memory("after phase 4")


def _bytes_in_use(devices) -> list:
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devices]


def run_four(devices, *, n=N_POINTS, m=N_FOUR, n_check=N_FOUR_CHECK,
             backend="auto") -> None:
    """Multi-device phase: forest (the planner's choice), pinned sharded, and
    one-device chunked as the comparison, all on one catalog; one call each
    (compile included)."""
    from repro.api import IndexSpec, KNNIndex

    t0 = time.perf_counter()
    points, queries, rows = _catalog(n, m, m, n_check)
    oracle = knn_oracle(points, queries[rows], K + 1)
    log(f"[setup] catalog n={n} d={DIM} queries={m} devices={len(devices)} "
        f"data_and_host_oracle_s={time.perf_counter() - t0:.3f}")
    runs = (
        ("one-device", IndexSpec(devices=tuple(devices[:1]),
                                 backend=backend), "chunked", devices[:1]),
        ("planner", IndexSpec(backend=backend), "forest", devices),
        ("sharded", IndexSpec(engine="sharded", backend=backend), "sharded",
         devices),
    )
    answers = {}
    for label, spec, engine, holders in runs:
        base = _bytes_in_use(devices)
        t0 = time.perf_counter()
        idx = KNNIndex.build(points, spec=spec)
        build_s = time.perf_counter() - t0
        log(idx.describe())
        check(idx.engine_name == engine,
              f"{label}: engine {idx.engine_name}, expected {engine}")
        held = [a - b for a, b in zip(_bytes_in_use(devices), base)]
        t0 = time.perf_counter()
        res = idx.query(queries, k=K)
        call_s = time.perf_counter() - t0
        agree = compare_knn(res.dists[rows], res.idx[rows], *oracle, points,
                            queries[rows])
        share = idx.plan.resident_bytes
        log(f"[four-chip {label}] engine={idx.engine_name} "
            f"n_shards={idx.plan.n_shards} build_s={build_s:.3f} "
            f"first_call_s={call_s:.3f} planned_bytes_per_device={share}")
        for dev, h in zip(devices, held):
            log(f"[four-chip {label}]   {dev}: bytes_in_use after build "
                f"+{h}")
        log(f"[four-chip {label}] oracle: {fmt(agree)}")
        check(agree["ok"], f"{label} disagrees with the oracle: {agree}")
        held_by = {d.id for d in holders}
        for dev, h in zip(devices, held):
            if dev.id in held_by:
                check(h >= share // 2,
                      f"{label}: {dev} holds {h} bytes, expected its share "
                      f"~{share}")
        answers[label] = (res.dists, res.idx)
        del idx, res
        gc.collect()
    ref = answers["one-device"]
    for label in ("planner", "sharded"):
        agree = agree_within(ref, answers[label], points, queries)
        log(f"[four-chip {label}] vs one-device chunked: {fmt(agree)}")
        check(agree["ok"], f"{label} differs from one-device chunked: "
              f"{agree}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device phase on four chips")
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError:
        print("chip_smoke: the repro package is not importable; run this "
              "script from the root of a checkout (src/repro)",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind}).  No CPU fallback.", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) visible", file=sys.stderr)
        return 1
    from repro.api import enable_compile_cache

    log(f"[setup] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four(devices[:4])
        else:
            run_one()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"[done] total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
