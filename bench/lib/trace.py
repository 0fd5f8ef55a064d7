"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load_trace`` reads it with JAX's own ``ProfileData`` into plain events;
``reduce_trace`` turns those into

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
- ``window_s``: the length of the traced window, from the first span named
  ``window_span`` on the host to the end of the last;
- ``op_s``: device seconds by operation name (``short_name``);
- ``idle_by_host``: idle device seconds by what the host's Python was
  doing (the innermost Python frame, as the profiler's Python tracer names
  it, that covers the middle of each gap).

The reduction is kept as code so that every benchmark computes these
numbers the same way; it is checked on events with known answers.

Shared arithmetic: later benchmarks add functions and never edit these.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Event",
    "TraceData",
    "load_trace",
    "merge_intervals",
    "reduce_trace",
    "device_time",
    "short_name",
    "OPS_LINE",
    "MODULES_LINE",
]

OPS_LINE = "XLA Ops"          # one event per HLO operation run on the device
MODULES_LINE = "XLA Modules"  # one event per compiled program run
DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
PY_FRAME = "$"                # the Python tracer's prefix of a frame's name


@dataclasses.dataclass(frozen=True)
class Event:
    line: str
    name: str
    start_ns: float
    dur_ns: float
    detail: str = ""          # long name / op path where the trace has one

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceData:
    device: Dict[str, List[Event]]    # device plane name -> its events
    host: List[Event]                 # host-thread events


_DETAIL_STATS = ("long_name", "tf_op", "hlo_op", "name")


def _detail(ev) -> str:
    try:
        stats = dict(ev.stats)
    except Exception:  # noqa: BLE001 - a stat the reader cannot decode
        return ""
    for key in _DETAIL_STATS:
        val = stats.get(key)
        if isinstance(val, str) and val:
            return val
    return ""


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` file under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load_trace(log_dir: str) -> TraceData:
    """Device and host events of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(log_dir))
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = [Event(line.name, ev.name, float(ev.start_ns),
                         float(ev.duration_ns), _detail(ev))
                   for line in plane.lines
                   if line.name in (OPS_LINE, MODULES_LINE)
                   for ev in line.events]
            if evs:     # planes with no operations are not chips in use
                device[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host.append(Event(line.name, ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)))
    return TraceData(device=device, host=host)


def merge_intervals(spans: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(spans):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(spans, lo, hi):
    for s, e in spans:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def short_name(name: str) -> str:
    """A device operation's name without its HLO text: ``%fusion.2 =
    f32[65536]{...} fusion(...)`` becomes ``%fusion.2 f32[65536]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return head if shape.startswith("(") else f"{head} {shape}"


def _window(host: Sequence[Event], window_span: str) -> Optional[Tuple[float, float]]:
    marks = [e for e in host if e.name == window_span]
    if not marks:
        return None
    return min(e.start_ns for e in marks), max(e.end_ns for e in marks)


def _host_activity(host_sorted: Sequence[Event], starts: Sequence[float],
                   t: float, max_scan: int = 4000) -> str:
    """Innermost host frame covering time ``t``: of the frames that started
    at or before ``t`` and end after it, the one that started last."""
    i = bisect.bisect_right(starts, t) - 1
    scanned = 0
    while i >= 0 and scanned < max_scan:
        ev = host_sorted[i]
        if ev.end_ns > t:
            return ev.name
        i -= 1
        scanned += 1
    return "host: no Python frame"


def device_time(events: Sequence[Event], *, line: str, patterns: Sequence[str],
                lo: float = float("-inf"), hi: float = float("inf")) -> float:
    """Seconds of the events on ``line`` whose name or detail contains any
    of ``patterns``, clipped to [lo, hi)."""
    total = 0.0
    for ev in events:
        if ev.line != line:
            continue
        if not any(p in ev.name or p in ev.detail for p in patterns):
            continue
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if e > s:
            total += e - s
    return total * 1e-9


def reduce_trace(trace: TraceData, *, window_span: str,
                 top: int = 10) -> dict:
    """Busy and idle device time of the traced window (see module doc).

    Returns ``{"busy_s", "window_s", "devices", "op_s", "idle_by_host",
    "window_ns"}``; ``op_s`` and ``idle_by_host`` are lists of
    ``[name, seconds]``, largest first, at most ``top`` long.
    """
    win = _window(trace.host, window_span)
    if win is None:
        raise ValueError(f"no host span named {window_span!r} in the trace")
    lo, hi = win
    window_s = (hi - lo) * 1e-9
    host_sorted = sorted((e for e in trace.host
                          if e.name.startswith(PY_FRAME)),
                         key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host_sorted]
    busy_total = 0.0
    op_s: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    n_dev = 0
    for _plane, events in sorted(trace.device.items()):
        ops = [e for e in events if e.line == OPS_LINE]
        if not ops:
            continue
        n_dev += 1
        busy = merge_intervals(_clip(((e.start_ns, e.end_ns) for e in ops),
                                     lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for e in ops:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                key = short_name(e.name)
                op_s[key] = op_s.get(key, 0.0) + (t - s) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            who = _host_activity(host_sorted, starts, 0.5 * (g0 + g1))
            idle[who] = idle.get(who, 0.0) + (g1 - g0) * 1e-9
    if n_dev == 0:
        raise ValueError("the trace holds no device operations")

    def ranked(d: Dict[str, float]) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_total / n_dev,
        "window_s": window_s,
        "devices": n_dev,
        "op_s": ranked(op_s),
        "idle_by_host": ranked({k: v / n_dev for k, v in idle.items()}),
        "window_ns": (lo, hi),
    }
