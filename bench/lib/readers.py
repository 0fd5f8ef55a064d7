"""Arithmetic shared by the metric readers in ``bench/metrics``.

Each reader is ``read(run) -> float | None`` over a ``harness.RunRecord``;
it returns None where the run holds nothing to read (no trace, no traced
call, no counter), and the harness then leaves the metric out of the line.

Shared arithmetic: later benchmarks add functions and never edit these.
"""

from __future__ import annotations

from bench.lib import trace as tr

__all__ = ["idle_share", "traced_sum", "device_seconds", "share"]


def share(part, whole):
    """100 * part / whole, or None where either is missing or whole is 0."""
    if part is None or not whole:
        return None
    return 100.0 * part / whole


def idle_share(run):
    """Device idle share of the traced window, in %."""
    if run.trace is None:
        return None
    return share(run.trace["window_s"] - run.trace["busy_s"],
                 run.trace["window_s"])


def traced_sum(run, field: str):
    """Sum of a SearchStats counter over the traced calls (None untraced)."""
    calls = run.traced_calls
    if not calls:
        return None
    return sum(getattr(c.stats, field) for c in calls)


def device_seconds(run, *, line: str, patterns):
    """Device seconds of the traced window's events on ``line`` matching
    ``patterns``, averaged over the devices; None without a trace or where
    no event matches."""
    if run.trace is None or run.trace_data is None:
        return None
    lo, hi = run.trace["window_ns"]
    per_dev = [tr.device_time(evs, line=line, patterns=patterns, lo=lo, hi=hi)
               for evs in run.trace_data.device.values()]
    total = sum(per_dev) / max(1, len(per_dev))
    return total if total > 0 else None
