"""Topology/memory-aware query planner (the paper's §3 constraints as code).

The paper's narrative — "use the device-resident workflow while the leaf
structure fits, two streamed chunk buffers when it does not (§3), and split
work across devices when there are several (§3.2)" — lives here as an
explicit cost model instead of being implied by which entry point a caller
happens to import:

  * ``estimate_slab_bytes``   the device-memory term: the padded leaf
    structure is ``2**h * leaf_pad * d_pad * 4`` bytes (what §3 says must
    fit, or be chunked);
  * ``plan``                  picks (engine, height, n_chunks, n_shards,
    buffer_size) from (n, d, m, k, devices, memory_budget) and records WHY
    in ``Plan.reasons`` — every decision is a testable string, not a code
    path.

Planning rules (in order):
  1. an explicit ``engine=`` request is honored (parameters still filled);
  2. tiny reference sets take ``brute`` — below ~2k points tree build +
     traversal overhead exceeds one fused scan, and so does k ~ O(n);
  3. >1 visible device => ``forest`` (per-shard buffer k-d trees, §3.2's
     scale-out) when n splits evenly, else ``sharded`` (paper-faithful
     query chunking, which tolerates any n);
  4. a memory budget below the resident slab bytes => ``chunked`` with the
     smallest N such that TWO chunk buffers fit (§3's double-buffered
     streaming: resident = 2 * slab/N);
  5. otherwise ``chunked`` with N=1 — the device-resident ICML'14 workflow.

Height defaults to ``suggest_height`` but is clamped so the mean leaf still
holds >= k points (the leaf-scan kernel selects k of leaf_pad candidates),
and buffer capacity follows the paper's footnote 8: B = 2^(24-h) capped,
fetch M = 10 B — the B/2 flush rule's inputs, now planned explicitly.

MEASURED-COST CALIBRATION: pass a ``Calibration`` (H2D bandwidth + fused
round cost from ``benchmarks/copy_cost.py``, per-engine q/s from
``BENCH_engine.json``; ``Calibration.load()`` reads both) and decisions
become calibrated instead of rule-based: the single-device engine choice
compares measured q/s, and the chunk-visit starvation deadline is derived
from the copy-cost/round-cost ratio (expensive copies => let cold chunks
starve longer so visits batch denser).  Every calibrated decision still
lands in ``Plan.reasons`` with the numbers it used.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
import warnings
from typing import Any, Mapping, Optional, Sequence, Tuple

from repro.core.chunked_jit import DEFAULT_STARVATION_DEADLINE
from repro.core.quantize import BYTES_PER_ELEM, PRECISIONS
from repro.core.toptree import default_buffer_size, slab_len, suggest_height

__all__ = [
    "Plan",
    "plan",
    "BudgetError",
    "estimate_slab_bytes",
    "estimate_meta_bytes",
    "Calibration",
    "BRUTE_N_MAX",
    "BRUTE_WORK_MAX",
    "CALIBRATION_STALE_S",
    "PRECISION_ENGINES",
]


class BudgetError(ValueError):
    """Raised under ``IndexSpec(strict_budget=True)`` when no plan fits the
    ``memory_budget`` — the structured form of the ``Plan.over_budget`` flag
    (a budget below even two streamed chunk buffers cannot be honored)."""

# Below this reference-set size the tree cannot pay for itself on any
# backend we target (one brute tile covers the whole set).
BRUTE_N_MAX = 2048

# Below this total distance-pair count (m * n) the whole job fits in a
# couple of brute tiles — tree construction would dominate end-to-end time.
BRUTE_WORK_MAX = 1 << 21

# Calibration measurements older than this are STALE: the planner still
# uses them (measured-but-old usually beats rule-based) but warns and
# records the staleness in Plan.reasons so decisions stay auditable.
CALIBRATION_STALE_S = 7 * 24 * 3600.0

_F32 = 4

# Engines whose leaf slabs live in a ChunkedLeafStore (directly or through
# the dynamic forest's tree shards) and therefore honor a precision choice;
# everything else (brute/jit/forest/ring) keeps fp32 reference arrays.
PRECISION_ENGINES = ("chunked", "host", "streaming", "sharded", "dynamic")


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pad_dims(
    n: int, d: int, height: int, leaf_pad_multiple: int, d_pad_multiple: int
) -> Tuple[int, int, int]:
    n_leaves = 1 << height
    leaf_pad = slab_len(-(-n // n_leaves), leaf_pad_multiple)
    d_pad = max(_round_up(d, d_pad_multiple), d_pad_multiple)
    return n_leaves, leaf_pad, d_pad


def estimate_slab_bytes(
    n: int, d: int, height: int, *, leaf_pad_multiple: int = 8,
    d_pad_multiple: int = 8, precision: str = "fp32",
) -> int:
    """Device bytes of the padded leaf structure at tree height ``height``.

    Mirrors ``build_top_tree``'s padding: 2**h equal (±1) leaves of
    ceil(n / 2**h) points, slab length from ``toptree.slab_len``, feature
    dim rounded up to ``d_pad_multiple``.  ``precision`` scales the
    per-element cost (fp32 4B, fp16 2B, int8 1B — ``core.quantize``).
    """
    n_leaves, leaf_pad, d_pad = _pad_dims(
        n, d, height, leaf_pad_multiple, d_pad_multiple
    )
    return n_leaves * leaf_pad * d_pad * BYTES_PER_ELEM[precision]


def estimate_meta_bytes(
    n: int, d: int, height: int, *, leaf_pad_multiple: int = 8,
    d_pad_multiple: int = 8, precision: str = "fp32",
) -> int:
    """Device bytes of the dequantize metadata a quantized store keeps
    resident next to its slabs: the bit-packed dead-row mask
    (u8[n_leaves, ceil(leaf_pad/8)]) plus, for int8 only, the per-leaf
    affine scale + offset (f32[n_leaves, d_pad] each — fp16 is a plain
    cast and carries none).  0 for fp32 (mirrors
    ``ChunkedLeafStore.meta_bytes``)."""
    if precision == "fp32":
        return 0
    n_leaves, leaf_pad, d_pad = _pad_dims(
        n, d, height, leaf_pad_multiple, d_pad_multiple
    )
    dead = -(-leaf_pad // 8)
    if precision == "fp16":
        return n_leaves * dead
    return n_leaves * (2 * d_pad * _F32 + dead)


def _probe_h2d(
    sizes_mb: Tuple[float, float] = (1.0, 8.0), repeats: int = 3
) -> Tuple[float, float]:
    """Two-point host->device copy fit: (bandwidth GB/s, fixed latency s).

    The inline miniature of ``benchmarks/copy_cost.py``'s H2D sweep —
    median of ``repeats`` timed ``device_put``s at two sizes, solved for
    slope (bandwidth) and intercept (per-transfer latency)."""
    import jax
    import numpy as np

    dev = jax.devices()[0]
    points = []
    for mb in sizes_mb:
        nbytes = int(mb * (1 << 20))
        host = np.zeros(nbytes // 4, np.float32)
        jax.block_until_ready(jax.device_put(host, dev))  # warm the path
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(host, dev))
            ts.append(time.perf_counter() - t0)
        points.append((float(nbytes), sorted(ts)[len(ts) // 2]))
    (b0, t0), (b1, t1) = points
    slope = max((t1 - t0) / max(b1 - b0, 1.0), 1e-15)
    intercept = max(t0 - slope * b0, 0.0)
    return 1.0 / (slope * 1e9), intercept


def _clamp_height(n: int, k: int, height: Optional[int]) -> Tuple[int, Tuple[str, ...]]:
    reasons = ()
    if height is not None:
        return int(height), reasons
    h = suggest_height(n)
    # keep mean leaf >= k so one leaf scan can yield k candidates
    while h > 1 and (n >> h) < max(2, k):
        h -= 1
        reasons = (f"height lowered to {h}: leaves must hold >= k={k} points",)
    return h, reasons


@dataclasses.dataclass(frozen=True)
class Calibration:
    """Measured machine numbers the planner may substitute for its rules.

    Produced by ``benchmarks/copy_cost.py`` (H2D bandwidth + fused round
    cost, written to ``BENCH_copy_cost.json``) and ``benchmarks/
    engine_bench.py`` (per-engine q/s in ``BENCH_engine.json``);
    ``Calibration.load()`` assembles one from whichever files exist.
    All fields optional — a partial calibration informs only the decisions
    it has numbers for.
    """

    h2d_gbps: Optional[float] = None       # host->device copy bandwidth
    h2d_latency_s: float = 0.0             # fixed per-transfer cost
    round_s: Optional[float] = None        # one fused round, reference shape
    engine_qps: Mapping[str, float] = dataclasses.field(default_factory=dict)
    build_pps: Optional[float] = None      # static index build, points/sec
    dynamic_crossover: Optional[int] = None  # measured batch size beyond
                                           # which rebuild-from-scratch beats
                                           # batch-dynamic merge (dynamic_bench)
    dynamic_measured: bool = False         # True when dynamic_bench ran —
                                           # distinguishes "measured: no
                                           # crossover in range" (crossover
                                           # None, batch-dynamic always won)
                                           # from "never measured"
    age_s: Optional[float] = None          # seconds since the OLDEST source
                                           # file was measured; None = unknown
    slow_age_s: Optional[float] = None     # seconds since the oldest SLOW
                                           # field (round cost, engine q/s)
                                           # was measured — the inline H2D
                                           # probe cannot refresh these, so
                                           # their staleness survives a
                                           # Calibration.refresh
    source: str = ""

    @property
    def stale(self) -> bool:
        """True when the oldest source measurement has outlived
        ``CALIBRATION_STALE_S`` — plan() warns and records it in reasons
        instead of silently trusting old numbers."""
        return self.age_s is not None and self.age_s > CALIBRATION_STALE_S

    @property
    def slow_stale(self) -> bool:
        """True when the slow fields (round cost, engine q/s — the ones only
        their real benches can re-measure) have outlived the staleness
        window.  ``refresh()`` zeroes ``age_s`` but deliberately carries
        this, so a refreshed calibration still discloses that the
        starvation-deadline / engine-choice inputs are old."""
        return (
            self.slow_age_s is not None
            and self.slow_age_s > CALIBRATION_STALE_S
        )

    def chunk_copy_s(self, chunk_bytes: int) -> Optional[float]:
        """Predicted seconds to stream one chunk slab host->device."""
        if self.h2d_gbps is None or self.h2d_gbps <= 0:
            return None
        return self.h2d_latency_s + chunk_bytes / (self.h2d_gbps * 1e9)

    @classmethod
    def refresh(cls, base: Optional["Calibration"] = None) -> "Calibration":
        """Re-run the cheap copy-cost probe INLINE and fold the fresh H2D
        numbers over ``base`` (keeping its engine q/s etc.).

        This is the ``calibration="refresh"`` escape from the staleness
        warning: instead of trusting week-old BENCH files forever, plan()
        re-measures the two-point H2D fit (~tens of milliseconds) and
        plans from that.  Slower fields (round cost, engine q/s) still
        need their real benches; they are carried over unmodified — and so
        is ``slow_age_s``, so consumers (and ``Plan.reasons``) keep seeing
        how old those numbers really are instead of a refreshed-looking
        calibration built on dead measurements."""
        gbps, latency_s = _probe_h2d()
        base = base if base is not None else cls()
        src = "inline-refresh" if not base.source else (
            base.source + "+inline-refresh"
        )
        return dataclasses.replace(
            base, h2d_gbps=gbps, h2d_latency_s=latency_s, age_s=0.0,
            slow_age_s=base.slow_age_s, source=src,
        )

    @classmethod
    def load(cls, root: Optional[str] = None) -> Optional["Calibration"]:
        """Assemble from BENCH_copy_cost.json / BENCH_engine.json under
        ``root`` (default: the repo checkout this package sits in).
        Returns None when neither file exists — callers then plan by rule.

        PROVENANCE CAVEAT: the repo commits its bench JSONs as the perf
        trajectory, so on a machine that has never run the benches the
        default root yields the *committed* (foreign) measurements.  The
        file names travel in ``source`` and are echoed in every calibrated
        ``Plan.reasons`` entry; re-run ``benchmarks/copy_cost.py`` and
        ``benchmarks/engine_bench.py`` locally before trusting the numbers
        on new hardware (docs/PERF.md, "Re-running calibration").
        """
        if root is None:
            root = os.path.abspath(
                os.path.join(os.path.dirname(__file__), "..", "..", "..")
            )
        h2d_gbps, h2d_latency_s, round_s = None, 0.0, None
        build_pps, dynamic_crossover = None, None
        engine_qps: dict = {}
        sources = []
        mtimes = []
        slow_mtimes = []   # files feeding the SLOW fields (round_s, qps)
        cc = os.path.join(root, "BENCH_copy_cost.json")
        if os.path.exists(cc):
            with open(cc) as f:
                data = json.load(f)
            h2d_gbps = data.get("h2d_gbps")
            h2d_latency_s = data.get("h2d_latency_s", 0.0)
            round_s = data.get("round_s")
            sources.append("BENCH_copy_cost.json")
            mtimes.append(os.path.getmtime(cc))
            if round_s is not None:
                slow_mtimes.append(os.path.getmtime(cc))
        eb = os.path.join(root, "BENCH_engine.json")
        if os.path.exists(eb):
            with open(eb) as f:
                data = json.load(f)
            m = data.get("shape", {}).get("m")
            for eng, key in (("chunked", "chunked_s"), ("host", "host_s")):
                qps = data.get(f"{eng}_qps")
                if qps is None and m and data.get(key):
                    qps = m / data[key]
                if qps:
                    engine_qps[eng] = float(qps)
            sources.append("BENCH_engine.json")
            mtimes.append(os.path.getmtime(eb))
            if engine_qps:
                slow_mtimes.append(os.path.getmtime(eb))
        db = os.path.join(root, "BENCH_dynamic.json")
        dynamic_measured = False
        if os.path.exists(db):
            with open(db) as f:
                data = json.load(f)
            build_pps = data.get("build_pps")
            dynamic_crossover = data.get("crossover_batch")
            dynamic_measured = True
            sources.append("BENCH_dynamic.json")
            mtimes.append(os.path.getmtime(db))
        if not sources:
            return None
        # age from file mtimes, not an embedded field: it tracks when the
        # numbers landed on THIS machine (a fresh checkout of committed
        # bench JSONs is "new but foreign" — the provenance caveat above —
        # while a file untouched for weeks is genuinely stale either way)
        return cls(
            h2d_gbps=h2d_gbps, h2d_latency_s=h2d_latency_s, round_s=round_s,
            engine_qps=engine_qps, build_pps=build_pps,
            dynamic_crossover=dynamic_crossover,
            dynamic_measured=dynamic_measured,
            age_s=max(0.0, time.time() - min(mtimes)),
            slow_age_s=(
                max(0.0, time.time() - min(slow_mtimes))
                if slow_mtimes else None
            ),
            source="+".join(sources),
        )


@dataclasses.dataclass(frozen=True)
class Plan:
    """A fully-resolved execution plan (every engine parameter pinned)."""

    engine: str
    height: int
    n: int = 0
    d: int = 0
    n_chunks: int = 1
    n_shards: int = 1
    n_devices: int = 1
    buffer_size: int = 4096
    fetch_m: int = 40960
    tile_q: int = 128
    backend: str = "auto"
    slab_bytes: int = 0         # full leaf structure, one device, at the
                                # planned precision (dequantize metadata is
                                # counted in resident_bytes, not here)
    resident_bytes: int = 0     # per-device bytes actually held under plan
    memory_budget: Optional[int] = None
    precision: str = "fp32"     # leaf-slab storage precision ("fp32" |
                                # "fp16" | "int8"); quantized slabs stay
                                # exact via the fp32 candidate re-rank
    over_budget: bool = False   # True when even the best plan (maximum
                                # chunking at the chosen precision) exceeds
                                # memory_budget — the structured form of the
                                # old "best effort" prose note; strict_budget
                                # turns this into a BudgetError at plan time
    visit_policy: str = "pending_desc"   # chunk-visit ordering policy
    starvation_deadline: int = DEFAULT_STARVATION_DEADLINE
    calibrated: bool = False    # True when a Calibration informed decisions
    crossover_batch: Optional[int] = None  # dynamic engine: insert batches
                                           # >= this trigger a flattening
                                           # rebuild instead of a carry chain
    merge_async: bool = False   # dynamic engine: carry merges run on a
                                # background worker, off the query path
    reasons: Tuple[str, ...] = ()

    def replace(self, **kw) -> "Plan":
        return dataclasses.replace(self, **kw)


def plan(
    n: int,
    d: int,
    m: Optional[int] = None,
    k: int = 10,
    devices: Optional[Sequence[Any]] = None,
    memory_budget: Optional[int] = None,
    *,
    engine: Optional[str] = None,
    height: Optional[int] = None,
    n_chunks: Optional[int] = None,
    n_shards: Optional[int] = None,
    buffer_size: Optional[int] = None,
    tile_q: int = 128,
    backend: str = "auto",
    calibration: Optional[Calibration] = None,
    mutable: Optional[bool] = None,
    merge_async: Optional[bool] = None,
    precision: Optional[str] = None,
    strict_budget: bool = False,
    op: str = "knn",
) -> Plan:
    """Pick an engine + parameters for (n, d) references and (m, k) queries.

    ``op`` is the primary operation the index is planned for ("knn" —
    the default — or a dual-tree op: "radius" / "kde" / "pair_count").
    Non-kNN ops restrict the engine choice to engines declaring the op in
    ``EngineCaps.ops``; the decision lands in ``Plan.reasons`` either way
    (a pinned engine lacking the op raises, an auto choice reroutes).

    ``devices`` is a sequence of devices (only its length and identity are
    consulted, so tests may pass simulated device lists); ``None`` means the
    process's visible ``jax.devices()``.  ``memory_budget`` is per-device
    bytes available for the leaf structure; ``None`` means unconstrained.
    ``calibration`` substitutes measured numbers (H2D bandwidth, round cost,
    per-engine q/s) for the static rules where it has them — see
    ``Calibration``; the string ``"refresh"`` loads the bench files and,
    when they are missing or stale, re-runs the cheap inline H2D probe
    (``Calibration.refresh``) instead of warning about staleness.  ``mutable=True`` requires an engine with incremental
    ``insert``/``delete`` (the ``dynamic`` logarithmic-method forest); the
    rebuild-vs-merge crossover is costed here and pinned into the plan,
    and with >1 device the forest's shard rungs are PLACED across devices
    (tree rungs least-loaded, brute rungs pinned — the assignment preview
    lands in ``Plan.reasons``).  ``merge_async`` pins the dynamic engine's
    carry-merge offload; ``None`` lets the planner decide (background).
    """
    if n < 1 or d < 1:
        raise ValueError(f"need n >= 1, d >= 1; got n={n} d={d}")
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    from repro.api.engine import KNOWN_OPS

    if op not in KNOWN_OPS:
        raise ValueError(f"unknown op {op!r}; known: {sorted(KNOWN_OPS)}")
    if devices is None:
        import jax

        devices = jax.devices()
    p = max(1, len(devices))
    reasons: list = []

    if isinstance(calibration, str):
        if calibration != "refresh":
            raise ValueError(
                f"calibration={calibration!r}: pass a Calibration, None, "
                "or the string 'refresh'"
            )
        loaded = Calibration.load()
        if loaded is None or loaded.stale:
            calibration = Calibration.refresh(loaded)
            reasons.append(
                "calibration auto-refresh: "
                + ("no bench files found"
                   if loaded is None
                   else f"sources {loaded.age_s / 86400.0:.1f}d old")
                + f"; inline H2D probe measured {calibration.h2d_gbps:.2f}"
                f"GB/s + {calibration.h2d_latency_s * 1e6:.0f}us/transfer "
                f"({calibration.source})"
            )
        else:
            calibration = loaded

    if calibration is not None and calibration.stale:
        age_d = calibration.age_s / 86400.0
        warnings.warn(
            f"planner calibration is {age_d:.1f} days old "
            f"(source: {calibration.source}); re-run benchmarks/"
            "copy_cost.py and benchmarks/engine_bench.py to refresh",
            stacklevel=2,
        )
        reasons.append(
            f"calibration stale: oldest source measured {age_d:.1f}d ago "
            f"({calibration.source}); using it, but numbers may have drifted"
        )

    h, h_reasons = _clamp_height(n, k, height)
    reasons.extend(h_reasons)
    # paper footnote 8: B = 2^(24-h) (capped for CPU-scale sanity), M = 10B
    b = (
        int(buffer_size) if buffer_size is not None else default_buffer_size(h)
    )
    slab32 = estimate_slab_bytes(n, d, h)

    def footprint(p: str) -> int:
        """Per-device resident bytes at precision ``p`` when fully resident:
        slabs plus the dequantize metadata quantized stores keep."""
        return estimate_slab_bytes(n, d, h, precision=p) + estimate_meta_bytes(
            n, d, h, precision=p
        )

    # -- precision: cost capacity-per-byte against the budget -------------
    if precision is not None:
        if precision not in PRECISIONS:
            raise ValueError(
                f"precision={precision!r} not in {PRECISIONS}"
            )
        prec = precision
        reasons.append(
            f"precision {prec} pinned by caller: leaf slabs "
            f"{footprint(prec)}B ({slab32}B at fp32)"
        )
    elif memory_budget is None:
        prec = "fp32"
        reasons.append(
            "precision fp32: no memory_budget given, nothing to trade "
            "capacity against"
        )
    else:
        for cand in PRECISIONS:
            if footprint(cand) <= memory_budget:
                prec = cand
                if cand == "fp32":
                    reasons.append(
                        f"precision fp32: slab {slab32}B fits budget "
                        f"{memory_budget}B at full precision"
                    )
                else:
                    reasons.append(
                        f"precision {cand}: fp32 slab {slab32}B exceeds "
                        f"budget {memory_budget}B but {cand} "
                        f"({footprint(cand)}B incl. dequantize meta) fits "
                        "device-resident; candidates re-ranked exactly in "
                        "fp32"
                    )
                break
        else:
            prec = "int8"
            reasons.append(
                f"precision int8: no precision fits budget {memory_budget}B "
                f"resident (int8 needs {footprint('int8')}B); int8 "
                "maximizes points per streamed byte, chunk-streaming covers "
                "the rest"
            )

    slab = estimate_slab_bytes(n, d, h, precision=prec)
    meta = estimate_meta_bytes(n, d, h, precision=prec)
    base = dict(
        height=h, n=n, d=d, n_devices=p, buffer_size=b, fetch_m=10 * b,
        tile_q=tile_q, backend=backend, slab_bytes=slab,
        memory_budget=memory_budget,
    )
    over_budget = False
    over_detail = ""

    def chunks_for_budget() -> Tuple[int, str, bool]:
        if memory_budget is None or slab + meta <= memory_budget:
            return (
                1, "leaf structure fits device memory: device-resident (N=1)",
                False,
            )
        n_leaves = 1 << h
        # two streamed chunk buffers (plus any dequantize metadata) must
        # fit, at LEAF granularity: a chunk holds ceil(n_leaves/N) leaf
        # slabs (ChunkedLeafStore), so floor-dividing bytes here would
        # understate real residency
        leaf_bytes = slab // n_leaves
        budget_slab = memory_budget - meta   # what is left for the buffers
        c_max = budget_slab // max(1, 2 * leaf_bytes)  # leaves per chunk
        if c_max >= 1:
            nc = min(max(2, -(-n_leaves // c_max)), n_leaves)
        else:
            nc = n_leaves
        resident = 2 * (-(-n_leaves // nc)) * leaf_bytes + meta
        note = (
            f"slab {slab}B > budget {memory_budget}B at precision {prec}: "
            f"stream in N={nc} chunks (2 buffers resident = {resident}B)"
        )
        over = resident > memory_budget
        if over:
            note += (
                f" [over budget: even N={nc} (one leaf per chunk) holds "
                f"{resident}B resident — budget is below the 2-chunk floor]"
            )
        if calibration is not None:
            copy_s = calibration.chunk_copy_s((resident - meta) // 2)
            if copy_s is not None:
                note += (
                    f"; calibrated chunk copy ~{copy_s * 1e3:.2f}ms at "
                    f"{calibration.h2d_gbps:.1f}GB/s"
                )
                if calibration.round_s:
                    note += f" vs fused round ~{calibration.round_s * 1e3:.2f}ms"
        return nc, note, over

    def calibrated_deadline() -> Tuple[int, Optional[str]]:
        """Starvation deadline (rounds a pending chunk may be skipped) from
        the measured copy-cost / round-cost ratio: when slab copies dominate
        a round, let cold chunks wait longer so each visit is denser; when
        rounds dominate, visit promptly."""
        if calibration is None:
            return DEFAULT_STARVATION_DEADLINE, None
        n_leaves = 1 << h
        nc_cand = n_chunks if n_chunks else 2
        chunk_bytes = (-(-n_leaves // max(1, nc_cand))) * (slab // n_leaves)
        copy_s = calibration.chunk_copy_s(chunk_bytes)
        if copy_s is None or not calibration.round_s:
            return DEFAULT_STARVATION_DEADLINE, None
        ratio = copy_s / max(calibration.round_s, 1e-9)
        dl = int(min(16, max(1, round(ratio))))
        src = f"; {calibration.source}" if calibration.source else ""
        return dl, (
            f"calibrated starvation deadline {dl} rounds: chunk copy "
            f"~{copy_s * 1e3:.2f}ms / round ~{calibration.round_s * 1e3:.2f}ms "
            f"(ratio {ratio:.2f}{src})"
        )

    # pinning a tree parameter (height / n_chunks / buffer_size) is an
    # implicit request for a tree engine; only unconstrained specs may
    # short-circuit to brute
    tree_requested = (
        height is not None or n_chunks is not None or buffer_size is not None
    )
    small_job = (
        n <= BRUTE_N_MAX
        or k * 4 > n
        or (m is not None and m * n <= BRUTE_WORK_MAX)
    )
    def resident_for(name: str, nc: int = 1, ns: int = 1) -> int:
        """Per-device residency under a candidate engine — one source of
        truth: the engine's own ``resident_bytes`` hook (slab fallback
        only if the registry is unavailable, e.g. direct module import)."""
        probe = Plan(
            engine=name, n_chunks=nc, n_shards=ns, resident_bytes=slab,
            reasons=(), **base
        )
        try:
            from repro.api.engine import get_engine

            return get_engine(name).resident_bytes(probe)
        except KeyError:
            return slab

    # knn_brute keeps the whole padded reference set device-resident, so
    # the shortcut is off the table when that alone would bust the budget
    brute_fits = (
        memory_budget is None or resident_for("brute") <= memory_budget
    )

    def mutable_costing() -> Tuple[Optional[int], str]:
        """Rebuild-vs-merge crossover for the dynamic engine.

        A batch of b points absorbed by the carry chain costs ~b*levels
        amortized point-rebuilds (each point re-participates once per rung
        it climbs); absorbing it by rebuilding from scratch costs ~n+b.
        They cross at b* ~ n/levels — batches beyond that should flatten.
        A measurement (benchmarks/dynamic_bench.py -> BENCH_dynamic.json)
        overrides the model — including a measured NULL crossover, which
        means batch-dynamic won at every measured size and nothing may be
        forced through a flattening rebuild; measured build throughput
        turns the reason's ratios into seconds."""
        from repro.core.dynamic import DEFAULT_BASE_CAPACITY

        levels = max(
            1, math.ceil(math.log2(max(2.0, n / DEFAULT_BASE_CAPACITY)))
        )
        if calibration is not None and calibration.dynamic_measured:
            if calibration.dynamic_crossover:
                cx = int(calibration.dynamic_crossover)
                return cx, (
                    f"mutable: dynamic engine; measured rebuild-vs-merge "
                    f"crossover at batches >= {cx} points "
                    f"({calibration.source})"
                )
            return None, (
                "mutable: dynamic engine; measured: batch-dynamic ingest "
                "won at every measured batch size, no flattening "
                f"threshold pinned ({calibration.source})"
            )
        cx = max(DEFAULT_BASE_CAPACITY, n // levels)
        note = (
            f"mutable: dynamic engine; carry-chain merge touches a point "
            f"<= {levels}x vs full rebuild of {n}, modeled crossover at "
            f"batches >= {cx}"
        )
        if calibration is not None and calibration.build_pps:
            note += (
                f" (~{cx * levels / calibration.build_pps:.2f}s merge "
                f"~= {(n + cx) / calibration.build_pps:.2f}s rebuild at "
                f"{calibration.build_pps:.0f} pts/s)"
            )
        return cx, note

    if mutable and engine is not None:
        try:
            from repro.api.engine import get_engine

            caps = get_engine(engine).caps
        except KeyError:
            caps = None
        if caps is not None and not caps.mutable:
            raise ValueError(
                f"mutable=True but pinned engine {engine!r} declares "
                "caps.mutable=False; unpin the engine or pick a mutable "
                "one (e.g. 'dynamic')"
            )
    if engine is not None and op != "knn":
        # op-capability mirror of the mutable pin check above: a pinned
        # engine that does not declare the op is a contradiction, not a
        # reroute opportunity
        from repro.api.engine import available_engines, get_engine

        try:
            caps = get_engine(engine).caps
        except KeyError:
            caps = None
        if caps is not None and op not in caps.ops:
            raise ValueError(
                f"op={op!r} but pinned engine {engine!r} does not declare "
                f"it (caps.ops={sorted(caps.ops)}); unpin the engine or "
                f"pick one of {sorted(available_engines(op=op))}"
            )
    if engine is None:
        if mutable:
            engine = "dynamic"
        elif not tree_requested and small_job and brute_fits:
            engine = "brute"
            reasons.append(
                f"n={n} <= {BRUTE_N_MAX}, k~O(n), or m*n <= "
                f"{BRUTE_WORK_MAX}: one fused brute scan beats tree build "
                "+ traversal"
            )
        elif p > 1:
            # a caller-pinned shard count must itself divide n; otherwise
            # the shard count IS the device count
            shards = int(n_shards) if n_shards is not None else p
            per_shard = slab // max(1, shards)
            fits = memory_budget is None or per_shard <= memory_budget
            # a pinned n_chunks > 1 is an out-of-core constraint forest's
            # device-resident shards cannot honor — route to sharded
            wants_chunks = n_chunks is not None and n_chunks > 1
            if (
                n % shards == 0 and (n // shards) >= max(2 * k, 2)
                and fits and not wants_chunks
            ):
                engine = "forest"
                reasons.append(
                    f"{p} devices visible and n % {shards} == 0: per-shard "
                    "buffer k-d trees + all-gather merge (paper §3.2 scale-out)"
                )
            else:
                engine = "sharded"
                if not fits:
                    why = (
                        f"per-shard slab {per_shard}B exceeds budget "
                        f"{memory_budget}B (forest shards are device-resident)"
                    )
                elif wants_chunks:
                    why = (
                        f"pinned n_chunks={n_chunks} requires chunk "
                        "streaming, which forest shards cannot do"
                    )
                else:
                    why = f"n={n} does not split into {shards} equal shards"
                reasons.append(
                    f"{p} devices visible but {why}: paper-faithful query "
                    "chunking over replicated trees"
                )
        elif calibration is not None and calibration.engine_qps:
            # calibrated single-device choice: measured q/s beats the rule,
            # filtered to engines that can honor an out-of-core constraint
            candidates = {}
            for name, qps in calibration.engine_qps.items():
                try:
                    from repro.api.engine import get_engine

                    caps = get_engine(name).caps
                except KeyError:
                    continue
                if memory_budget is not None and not caps.out_of_core:
                    continue
                candidates[name] = qps
            if candidates:
                engine = max(candidates, key=candidates.get)
                measured = ", ".join(
                    f"{e}={q:.0f} q/s" for e, q in sorted(candidates.items())
                )
                reasons.append(
                    f"1 device, calibrated engine choice ({measured}; "
                    f"{calibration.source}): {engine}"
                )
            else:
                engine = "chunked"
                reasons.append("1 device: chunk-streamed buffer k-d tree")
        else:
            engine = "chunked"
            reasons.append("1 device: chunk-streamed buffer k-d tree")

    # non-kNN primary op: the chosen engine must declare it in caps.ops.
    # A pinned engine was already validated above (ValueError); an auto
    # choice that landed on a non-declaring engine reroutes to 'chunked'
    # (dual-tree over the same chunk-streamed leaf store) — unless the
    # choice was forced by mutable=True, which is a contradiction.
    if op != "knn":
        from repro.api.engine import available_engines, get_engine

        declaring = sorted(available_engines(op=op))
        if op in get_engine(engine).caps.ops:
            reasons.append(f"op={op!r} declared by engine {engine!r} (caps.ops)")
        elif mutable:
            raise ValueError(
                f"op={op!r} with mutable=True: the mutable engine "
                f"{engine!r} does not declare it (caps.ops); declaring "
                f"engines: {declaring}"
            )
        else:
            reasons.append(
                f"op={op!r} not declared by auto choice {engine!r}; "
                f"rerouted to 'chunked' (declaring engines: {declaring})"
            )
            engine = "chunked"

    # engines without a ChunkedLeafStore keep fp32 reference arrays — a
    # quantized precision choice cannot apply there; say so and fall back
    if engine not in PRECISION_ENGINES and prec != "fp32":
        reasons.append(
            f"precision request {prec} not applicable: engine {engine} "
            "stores fp32 reference arrays (no leaf slabs to quantize)"
        )
        prec = "fp32"
        slab = estimate_slab_bytes(n, d, h)
        meta = 0
        base["slab_bytes"] = slab

    # the BufferKDTree tiers (host/chunked/streaming) and sharded hold the
    # (full, replicated) leaf structure per device, so all honor the budget
    # through chunk streaming — ONE place decides the chunk count
    if engine in ("chunked", "host", "sharded", "streaming"):
        if n_chunks is None:
            n_chunks, note, over_budget = chunks_for_budget()
            reasons.append(note)
            if over_budget:
                over_detail = note
        else:
            reasons.append(f"N={n_chunks} chunks pinned by caller")

    if engine == "streaming":
        # never auto-picked: streaming is the chunked tier plus per-row
        # delivery, pinned by online-serving callers (KNNServer)
        reasons.append(
            "streaming engine pinned: chunked round loop with per-row "
            "early retirement; compaction-ladder rungs double as serving "
            "micro-batch buckets (docs/SERVING.md)"
        )

    crossover = None
    do_merge_async = False
    if engine == "dynamic":
        crossover, cx_note = mutable_costing()
        reasons.append(cx_note)
        # carry-merge offload: background staging by default (queries keep
        # answering from the pre-merge shards — exactness is unaffected,
        # only the insert/query tail latency is), inline only when pinned
        do_merge_async = True if merge_async is None else bool(merge_async)
        if do_merge_async:
            reasons.append(
                "carry merges offloaded to a background staging worker; "
                "queries answer from the pre-merge shards until the "
                "atomic swap (merge_async=True)"
            )
        else:
            reasons.append(
                "carry merges run inline on the insert path "
                "(merge_async=False pinned by caller)"
            )
        # device placement: shard rungs are immutable, so they place
        # across devices like the static forest's trees — tree rungs
        # least-loaded, churning brute rungs pinned to the lead device
        if p > 1:
            from repro.distributed.dynamic_shards import (
                preview_rung_placement,
            )

            from repro.core.dynamic import DEFAULT_BASE_CAPACITY

            preview = preview_rung_placement(
                n,
                base_capacity=min(b, DEFAULT_BASE_CAPACITY),
                brute_cutoff=BRUTE_N_MAX,
                n_devices=p,
            )
            pv = ", ".join(
                f"rung {cap}->dev{dev}" for cap, dev in preview[:6]
            )
            reasons.append(
                f"mutable multi-device: {p} devices; tree rungs placed "
                f"least-loaded (steady-state preview: {pv}), brute rungs "
                "pinned to dev0; per-device fan-out folds with the "
                "two-phase rank merge"
            )
        else:
            reasons.append(
                "1 device: dynamic forest runs single-device (placement "
                "and fan-out degenerate to the lead device)"
            )
        if memory_budget is not None:
            est = resident_for("dynamic", ns=p)
            if est > memory_budget:
                # the forest honors the budget by chunk-streaming tree-shard
                # leaf slabs (core.dynamic passes the remaining envelope into
                # each shard's ChunkedLeafStore); the only unhonorable case
                # is a budget below even two leaf slabs of the largest shard
                n_leaves = 1 << h
                floor = 2 * max(1, slab // n_leaves) + meta
                if floor > memory_budget:
                    over_budget = True
                    over_detail = (
                        f"memory_budget {memory_budget}B is below the "
                        f"dynamic forest's 2-leaf streaming floor {floor}B "
                        f"at precision {prec}"
                    )
                    reasons.append(over_detail + " [over budget]")
                else:
                    reasons.append(
                        f"memory_budget {memory_budget}B below the dynamic "
                        f"forest's resident estimate {est}B: tree shards "
                        "chunk-stream their leaf slabs to stay inside the "
                        f"envelope (precision {prec})"
                    )

    if (
        calibration is not None
        and calibration.slow_stale
        and engine in ("chunked", "host", "sharded", "streaming", "dynamic")
    ):
        # the inline H2D refresh cannot re-measure these; disclose that the
        # deadline / engine-choice inputs are seeded from dead numbers
        reasons.append(
            "calibration stale: slow fields (round cost, engine q/s) "
            f"measured {calibration.slow_age_s / 86400.0:.1f}d ago and the "
            "inline H2D probe cannot refresh them; re-run benchmarks/"
            "copy_cost.py and benchmarks/engine_bench.py"
        )

    if over_budget and strict_budget:
        raise BudgetError(
            f"strict_budget: no {engine} plan fits memory_budget="
            f"{memory_budget}B — {over_detail or 'residency exceeds budget'}"
        )

    nc = int(n_chunks) if n_chunks is not None else 1
    ns = int(n_shards) if n_shards is not None else (
        p if engine in ("forest", "sharded", "ring", "dynamic") else 1
    )
    deadline, dl_note = calibrated_deadline()
    if dl_note is not None and engine in ("chunked", "host", "sharded", "streaming"):
        reasons.append(dl_note)
    return Plan(
        engine=engine, n_chunks=nc, n_shards=ns,
        resident_bytes=resident_for(engine, nc, ns),
        starvation_deadline=deadline,
        calibrated=calibration is not None,
        crossover_batch=crossover,
        merge_async=do_merge_async,
        precision=prec,
        over_budget=over_budget,
        reasons=tuple(reasons), **base
    )
