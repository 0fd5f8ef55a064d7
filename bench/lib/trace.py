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

``load_trace`` also gives each device event its ``scope``: the ``tf_op``
path (``jit(_chunk_round)/while/body/knn.merge/...``) that the compiler
keeps in the event's metadata, where ``jax.named_scope`` scopes land.
``ProfileData`` hands out only an event's own stats, so ``xplane_scopes``
reads the metadata from the file's protobuf wire format itself, and
``load_trace`` checks each path against its event: a file the reader cannot
parse, or a path whose metadata is not its event's, raises ``ValueError``
with the plane and line.  No reduction here reads ``scope``;
``spans.scope_seconds`` does.

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
    "xplane_scopes",
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
    scope: str = ""           # the metadata's tf_op path (device events)

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

    path = find_xplane(log_dir)
    data = ProfileData.from_file(path)
    scopes = xplane_scopes(path)
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = list(line.events)
                tags = _line_scopes(scopes, plane.name, line.name, events)
                evs.extend(Event(line.name, ev.name, float(ev.start_ns),
                                 float(ev.duration_ns), _detail(ev), tag)
                           for ev, tag in zip(events, tags))
            if evs:     # planes with no operations are not chips in use
                device[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host.append(Event(line.name, ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)))
    return TraceData(device=device, host=host)


def _line_scopes(scopes, plane: str, line: str, events) -> List[str]:
    """The ``tf_op`` path of each of ``events``, the events of one line as
    ``ProfileData`` gives them, checked one by one against what
    ``xplane_scopes`` read: as many events, each of the same metadata name."""
    read = scopes.get(plane, {}).get(line)
    where = f"xplane: plane {plane!r}, line {line!r}"
    if read is None or len(read) != len(events):
        raise ValueError(f"{where}: {len(events)} events, the scope reader "
                         f"found {'none' if read is None else len(read)}")
    for i, (ev, (name, _)) in enumerate(zip(events, read)):
        if name != ev.name:
            raise ValueError(f"{where}, event {i}: named {ev.name!r}, the "
                             f"scope reader's metadata {name!r}")
    return [tag for _, tag in read]


# ---------------------------------------------------------------------------
# The tf_op path of each device event, from the xplane's wire format
# ---------------------------------------------------------------------------
SCOPE_STAT = "tf_op"    # the stat of an event's metadata that holds its path

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_MD_ID = 1
_MAP_KEY, _MAP_VALUE = 1, 2
_EMD_NAME, _EMD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_SMD_NAME = 2


def _varint(buf, i: int) -> Tuple[int, int]:
    """The varint at buf[i] and the index after it."""
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of each field of the message in buf[lo:hi]: an
    int for a varint, a (start, end) slice for a length-delimited field;
    fixed-width fields are skipped."""
    i = lo
    hi = len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, val
        elif wire == 2:
            n, i = _varint(buf, i)
            if i + n > hi:
                raise ValueError(f"field {field} at byte {i} runs past its "
                                 f"message's end at byte {hi}")
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, spans):
    """(key, value slice) of each entry of a map<int64, message> field."""
    for span in spans:
        key, value = 0, None
        for f, v in _fields(buf, *span):
            if f == _MAP_KEY:
                key = v
            elif f == _MAP_VALUE:
                value = v
        if value is not None:
            yield key, value


def _plane_scopes(buf, plane) -> Dict[str, List[Tuple[str, str]]]:
    parts: Dict[int, list] = {}
    for f, v in _fields(buf, *plane):
        if f in (_PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD):
            parts.setdefault(f, []).append(v)
    wanted = (OPS_LINE, MODULES_LINE)
    ids: Dict[str, List[int]] = {}
    for line in parts.get(_PLANE_LINES, []):
        name, got = "", []
        for f, v in _fields(buf, *line):
            if f == _LINE_NAME:
                name = _text(buf, v)
                if name not in wanted:      # the name comes first: skip
                    break
            elif f == _LINE_EVENTS:
                got.append(_event_metadata_id(buf, v))
        if name in wanted:
            ids[name] = got
    needed = {i for got in ids.values() for i in got}
    stat_names = {}
    for key, value in _map_entries(buf, parts.get(_PLANE_STAT_MD, [])):
        for f, v in _fields(buf, *value):
            if f == _SMD_NAME:
                stat_names[key] = _text(buf, v)
    scope_ids = {k for k, name in stat_names.items() if name == SCOPE_STAT}
    tag: Dict[int, str] = {}
    md_name: Dict[int, str] = {}
    for key, value in _map_entries(buf, parts.get(_PLANE_EVENT_MD, [])):
        if key not in needed:
            continue
        for f, v in _fields(buf, *value):
            if f == _EMD_NAME:
                md_name[key] = _text(buf, v)
            if f != _EMD_STATS:
                continue
            stat = dict(_fields(buf, *v))
            if stat.get(_STAT_MD_ID) not in scope_ids:
                continue
            if _STAT_STR in stat:
                tag[key] = _text(buf, stat[_STAT_STR])
            elif _STAT_REF in stat:     # an interned string
                tag[key] = stat_names.get(stat[_STAT_REF], "")
    return {name: [(md_name.get(i, ""), tag.get(i, "")) for i in got]
            for name, got in ids.items()}


def _event_metadata_id(buf, span) -> int:
    """An event's ``metadata_id``: its first field as the profiler writes
    it, else wherever it stands."""
    if span[1] > span[0] and buf[span[0]] == (_EVENT_MD_ID << 3):
        return _varint(buf, span[0] + 1)[0]
    return next((x for g, x in _fields(buf, *span) if g == _EVENT_MD_ID), 0)


def xplane_scopes(path: str) -> Dict[str, Dict[str, List[Tuple[str, str]]]]:
    """For each device plane of the xplane file at ``path``, and each of its
    ``XLA Ops`` and ``XLA Modules`` lines, the metadata name and the
    ``tf_op`` path of every event in the file's order ("" where its metadata
    has none).  Other planes, the host's among them, are skipped unread.
    Raises ``ValueError``, naming the plane, where the file cannot be read."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    try:
        planes = [v for f, v in _fields(buf) if f == _SPACE_PLANES]
    except (ValueError, IndexError) as e:
        raise ValueError(f"xplane {path}: {e}") from e
    out: Dict[str, Dict[str, List[Tuple[str, str]]]] = {}
    for plane in planes:
        name = "?"
        try:
            name = next((_text(buf, v) for g, v in _fields(buf, *plane)
                         if g == _PLANE_NAME), "")
            if name.startswith(DEVICE_PREFIX):
                out[name] = _plane_scopes(buf, plane)
        except (ValueError, IndexError) as e:
            raise ValueError(f"xplane {path}: plane {name!r}: {e}") from e
    return out


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
