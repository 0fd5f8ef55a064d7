"""Device time by the program's own spans and scopes.

The program marks its host phases with ``jax.profiler.TraceAnnotation``
spans (``knn.round``, ``knn.harvest``, ``pc.readback``, ...) and tags its
device operations with ``jax.named_scope`` scopes (``knn.merge``,
``pc.bin``, ...).  Both land in the profiler's trace beside the device
operations, on the same clock, so

- ``idle_by_span`` puts each part of a device idle gap down to the
  innermost program span open on the host at that time;
- ``scope_seconds`` gives the device seconds of the operations a scope
  tags.

A trace of a program without spans or scopes gives nothing to read: the
readers built on these return None there.

Shared arithmetic: later benchmarks add functions and never edit these.
"""

from __future__ import annotations

import bisect
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import trace as tr
from bench.lib.harness import CALL_SPAN
from bench.lib.readers import share

__all__ = ["NO_SPAN", "program_spans", "idle_by_span", "idle_share_in",
           "scope_seconds"]

NO_SPAN = "(no span)"     # idle time that no program span covers


def program_spans(host: Sequence[tr.Event],
                  prefixes: Sequence[str]) -> List[tr.Event]:
    """The host events whose name starts with one of ``prefixes``; Python
    tracer frames (``$...``) never count."""
    pre = tuple(prefixes)
    return [e for e in host
            if e.name.startswith(pre) and not e.name.startswith(tr.PY_FRAME)]


def _innermost_segments(spans: Sequence[tr.Event], lo: float,
                        hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut at every span boundary, each piece named by the
    innermost span open over it: of the spans that started at or before
    the piece and end after its start, the one that started last (the
    shorter one where two start together)."""
    live = [e for e in spans if e.end_ns > lo and e.start_ns < hi]
    cuts = sorted({lo, hi} | {x for e in live
                              for x in (e.start_ns, e.end_ns) if lo < x < hi})
    by_start = sorted(live, key=lambda e: (e.start_ns, -e.end_ns))
    heap: list = []
    out = []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(by_start) and by_start[j].start_ns <= a:
            e = by_start[j]
            heapq.heappush(heap, (-e.start_ns, e.end_ns, j, e.name))
            j += 1
        while heap and heap[0][1] <= a:     # ended: closed spans drop out
            heapq.heappop(heap)
        out.append((a, b, heap[0][3] if heap else NO_SPAN))
    return out


def idle_by_span(trace: tr.TraceData, window_span: str,
                 prefixes: Sequence[str]) -> Dict[str, float]:
    """Idle device seconds of the traced window by program span.

    The window and each device's idle gaps are those of
    ``trace.reduce_trace``: from the first ``window_span`` to the end of
    the last, minus the union of the device's ``XLA Ops`` intervals.  Each
    part of a gap goes to the innermost program span (``program_spans``)
    covering it; time no span covers goes to ``NO_SPAN``.  Seconds are
    averaged over the devices.
    """
    win = tr._window(trace.host, window_span)
    if win is None:
        raise ValueError(f"no host span named {window_span!r} in the trace")
    lo, hi = win
    spans = [e for e in program_spans(trace.host, prefixes)
             if e.name != window_span]
    segs = _innermost_segments(spans, lo, hi)
    seg_starts = [s for s, _, _ in segs]
    idle: Dict[str, float] = {}
    n_dev = 0
    for _plane, events in sorted(trace.device.items()):
        ops = [(e.start_ns, e.end_ns) for e in events if e.line == tr.OPS_LINE]
        if not ops:
            continue
        n_dev += 1
        busy = tr.merge_intervals(tr._clip(ops, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            i = max(0, bisect.bisect_right(seg_starts, g0) - 1)
            while i < len(segs) and segs[i][0] < g1:
                s, e, name = segs[i]
                part = min(e, g1) - max(s, g0)
                if part > 0:
                    idle[name] = idle.get(name, 0.0) + part * 1e-9
                i += 1
    if n_dev == 0:
        raise ValueError("the trace holds no device operations")
    return {k: v / n_dev for k, v in idle.items()}


def idle_share_in(run, spans: Sequence[str],
                  prefixes: Sequence[str]) -> Optional[float]:
    """Device idle time inside ``spans`` over the traced window, in %.
    None without a trace, or where the trace holds no program span with
    one of ``prefixes`` (a program that has no spans)."""
    if run.trace is None or run.trace_data is None:
        return None
    if not program_spans(run.trace_data.host, prefixes):
        return None
    idle = idle_by_span(run.trace_data, CALL_SPAN, prefixes)
    return share(sum(idle.get(s, 0.0) for s in spans), run.trace["window_s"])


def scope_seconds(run, scope: str) -> Optional[float]:
    """Device seconds of the traced window's ``XLA Ops`` events whose name,
    detail or ``scope`` path (``trace.load_trace``) carries ``scope``,
    averaged over the devices.

    An operation nested in another of the same scope (the body of a loop
    that the scope covers) is counted once: the seconds are the union of
    the matching intervals.  None without a trace or where nothing matches.
    """
    if run.trace is None or run.trace_data is None:
        return None
    lo, hi = run.trace["window_ns"]
    per_dev = []
    for events in run.trace_data.device.values():
        hits = [(e.start_ns, e.end_ns) for e in events
                if e.line == tr.OPS_LINE
                and (scope in e.name or scope in e.detail
                     or scope in e.scope)]
        busy = tr.merge_intervals(tr._clip(hits, lo, hi))
        per_dev.append(sum(e - s for s, e in busy) * 1e-9)
    total = sum(per_dev) / max(1, len(per_dev))
    return total if total > 0 else None
