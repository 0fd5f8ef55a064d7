"""Device time by program span and scope (``bench/lib/spans.py``) on events
with known answers, and the readers built on it."""

import os
import types

import pytest

from bench.lib import harness, spans, trace as tr

MS = 1_000_000.0
METRICS = os.path.join(harness.BENCH, "metrics")


def _ev(line, name, start_ms, dur_ms, detail=""):
    return tr.Event(line, name, start_ms * MS, dur_ms * MS, detail)


def _known_trace(second_device=False):
    """Window [10, 110) ms.  Device busy [10, 15), [25, 35), [45, 60),
    [70, 92): idle gaps [15, 25), [35, 45), [60, 70), [92, 110), 48 ms.

    Program spans: knn.query [10, 48) holding knn.round [12, 30), which
    holds knn.schedule [12, 16) (it starts with its parent) and
    knn.harvest [20, 28), and knn.drain [40, 47); knn.query [52, 108)
    holding knn.rescore [90, 108).  So the gaps go: [15, 16) schedule,
    [16, 20) round, [20, 25) harvest, [35, 40) query (it starts under the
    query and ends under the drain), [40, 45) drain, [60, 70) query,
    [92, 108) rescore, [108, 110) no span."""
    dev = [_ev(tr.OPS_LINE, "fusion.1", 10, 5),
           _ev(tr.OPS_LINE, "fusion.2", 25, 10),
           _ev(tr.OPS_LINE, "fusion.3", 45, 15),
           _ev(tr.OPS_LINE, "fusion.4", 70, 22),
           _ev(tr.MODULES_LINE, "jit__chunk_round(1)", 0, 200)]
    host = [
        _ev("main", "bench.call", 10, 40),
        _ev("main", "bench.call", 50, 60),
        _ev("py", "$run.py:1 call", 0, 200),          # a Python frame
        _ev("main", "knn.query", 10, 38),
        _ev("main", "knn.round", 12, 18),
        _ev("main", "knn.schedule", 12, 4),
        _ev("main", "knn.harvest", 20, 8),
        _ev("main", "knn.drain", 40, 7),
        _ev("main", "knn.query", 52, 56),
        _ev("main", "knn.rescore", 90, 18),
        _ev("main", "pc.call", 0, 200),               # another prefix
    ]
    device = {"/device:TPU:0": dev}
    if second_device:
        device["/device:TPU:1"] = [_ev(tr.OPS_LINE, "x", 0, 200)]
    return tr.TraceData(device=device, host=host)


EXPECTED = {"knn.schedule": 0.001, "knn.round": 0.004, "knn.harvest": 0.005,
            "knn.query": 0.015, "knn.drain": 0.005, "knn.rescore": 0.016,
            spans.NO_SPAN: 0.002}


def test_idle_by_span_gives_each_part_of_a_gap_to_the_innermost_span():
    got = spans.idle_by_span(_known_trace(), "bench.call", ("knn.",))
    assert got == pytest.approx(EXPECTED)
    assert sum(got.values()) == pytest.approx(0.048)
    red = tr.reduce_trace(_known_trace(), window_span="bench.call")
    assert sum(got.values()) == pytest.approx(red["window_s"] - red["busy_s"])


def test_idle_by_span_averages_devices():
    got = spans.idle_by_span(_known_trace(second_device=True), "bench.call",
                             ("knn.",))
    assert got == pytest.approx({k: v / 2 for k, v in EXPECTED.items()})


def test_python_frames_and_the_window_span_are_not_program_spans():
    t = _known_trace()
    # even where a prefix would take them, neither a Python frame nor the
    # window's own span is a program span
    got = spans.idle_by_span(t, "bench.call", ("knn.", "bench.", "$"))
    assert got == pytest.approx(EXPECTED)
    assert [e.name for e in spans.program_spans(t.host, ("$", "pc."))] \
        == ["pc.call"]
    # with another prefix the pc.call span covers every gap
    assert spans.idle_by_span(t, "bench.call", ("pc.",)) \
        == pytest.approx({"pc.call": 0.048})


def test_idle_by_span_refuses_a_trace_without_window_or_device():
    t = _known_trace()
    with pytest.raises(ValueError):
        spans.idle_by_span(t, "no.such.span", ("knn.",))
    with pytest.raises(ValueError):
        spans.idle_by_span(tr.TraceData(device={}, host=t.host),
                           "bench.call", ("knn.",))


def _run(trace_data, *, stats=(), tq=128):
    red = None
    if trace_data is not None:
        red = tr.reduce_trace(trace_data, window_span="bench.call")
    calls = [types.SimpleNamespace(stats=s, wall_s=0.05, traced=True)
             for s in stats]
    return types.SimpleNamespace(trace=red, trace_data=trace_data,
                                 traced_calls=calls, calls=calls,
                                 driver=types.SimpleNamespace(
                                     shapes={"tq": tq}))


def _scoped_trace(second_device=False):
    """Window [10, 110) ms.  knn.merge tags [20, 40) and a loop body op
    nested in it, [25, 30) (counted once); knn.advance tags [100, 120),
    clipped to [100, 110).  The module event carries no scope."""
    path = "jit(_chunk_round)/jit(main)/while/body/{}/op"
    dev = [_ev(tr.OPS_LINE, "%while.1", 20, 20, path.format("knn.merge")),
           _ev(tr.OPS_LINE, "%fusion.2", 25, 5, path.format("knn.merge")),
           _ev(tr.OPS_LINE, "%fusion.3", 50, 10, "jit(_chunk_round)/x"),
           _ev(tr.OPS_LINE, "%while.4", 100, 20,
               "jit(_chunk_round)/knn.advance/while"),
           _ev(tr.MODULES_LINE, "jit__chunk_round(1)", 20, 90)]
    host = [_ev("main", "bench.call", 10, 100)]
    device = {"/device:TPU:0": dev}
    if second_device:
        device["/device:TPU:1"] = [_ev(tr.OPS_LINE, "%f", 20, 10,
                                       path.format("knn.merge"))]
    return tr.TraceData(device=device, host=host)


def test_scope_seconds_counts_nested_operations_once():
    run = _run(_scoped_trace())
    assert spans.scope_seconds(run, "knn.merge") == pytest.approx(0.020)
    assert spans.scope_seconds(run, "knn.advance") == pytest.approx(0.010)
    assert spans.scope_seconds(run, "knn.plan") is None
    run2 = _run(_scoped_trace(second_device=True))
    assert spans.scope_seconds(run2, "knn.merge") == pytest.approx(0.015)
    assert spans.scope_seconds(_run(None), "knn.merge") is None


def _read(name, run):
    return harness.load_module(os.path.join(METRICS, name + ".py")).read(run)


def test_readers_read_spans_and_stay_silent_without_them():
    run = _run(_known_trace())
    # harvest 5 + drain 5 ms of idle in a 100-ms window; no compaction
    assert _read("readback_idle_share.batch", run) == pytest.approx(10.0)
    assert _read("readback_idle_share.pc", run) == pytest.approx(0.0)
    # a program without spans (the trace of the reduction's own tests) and
    # a run without a trace give nothing to read
    bare = tr.TraceData(device=_known_trace().device,
                        host=[e for e in _known_trace().host
                              if not e.name.startswith(("knn.", "pc."))])
    for name in ("readback_idle_share.batch", "readback_idle_share.pc"):
        assert _read(name, _run(bare)) is None, name
        assert _read(name, _run(None)) is None, name


def test_tile_fill_reads_the_counter_and_stays_silent_without_it():
    s = types.SimpleNamespace(units_scanned=10, rows_scanned=320)
    assert _read("tile_fill.batch", _run(None, stats=[s, s], tq=128)) \
        == pytest.approx(25.0)
    old = types.SimpleNamespace(units_scanned=10)    # no such counter
    assert _read("tile_fill.batch", _run(None, stats=[old])) is None
    assert _read("tile_fill.batch", _run(None)) is None
