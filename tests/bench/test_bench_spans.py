"""Device time by program span and scope (``bench/lib/spans.py``) on events
with known answers, and the readers built on it."""

import dataclasses
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


# ---------------------------------------------------------------------------
# Scope paths on the events change no reader that does not read them
# ---------------------------------------------------------------------------
SCOPE_READERS = {"merge_share.batch": "knn.merge",
                 "advance_share.batch": "knn.advance"}


def _every_reader_trace(scoped):
    """The known trace with a module event of each program, the leaf-scan
    kernel among its operations, and each operation tagged with a scope
    path where ``scoped``: fusion.2 and fusion.3 under ``knn.merge``
    ([25, 35) and [45, 60), 25 ms), fusion.4 under ``knn.advance`` ([70,
    92), 22 ms)."""
    base = _known_trace()
    dev = base.device["/device:TPU:0"]
    dev = [dataclasses.replace(e, name="%leaf_scan_pallas.1 = f32[8]")
           if e.name == "fusion.1" else e for e in dev]
    dev.append(_ev(tr.MODULES_LINE, "jit__pair_hist_kernel(2)", 45, 15))
    if scoped:
        path = {"fusion.2": "knn.merge", "fusion.3": "knn.merge",
                "fusion.4": "knn.advance", "%leaf_scan_pallas.1 = f32[8]":
                "knn.scan"}
        dev = [dataclasses.replace(
            e, scope=f"jit(_chunk_round)/while/body/{path[e.name]}/op")
            if e.name in path else e for e in dev]
    host = base.host + [_ev("main", "pc.readback", 60, 8)]
    return tr.TraceData(device={"/device:TPU:0": dev}, host=host)


def _every_reader_run(trace_data):
    stats = types.SimpleNamespace(
        units_scanned=12, rows_scanned=400, sync_wait_s=0.01,
        tail_rounds=3, iterations=9, flushes=7)
    calls = [types.SimpleNamespace(index=i, start_s=0.05 * i, wall_s=0.05,
                                   work=256, stats=stats, traced=True)
             for i in range(2)]
    return types.SimpleNamespace(
        workload="w", setup_s=12.5, setup_phases={"build": 3.25},
        calls=calls, traced_calls=calls, window_s=0.1,
        device_kind="TPU v5 lite", trace_data=trace_data,
        trace=tr.reduce_trace(trace_data, window_span="bench.call"),
        driver=types.SimpleNamespace(shapes={
            "tq": 128, "l_pad": 5120, "d_pad": 16, "k": 10,
            "slab_itemsize": 4, "backend": "pallas"}))


def test_scope_paths_leave_every_other_reader_unchanged():
    plain, scoped = _every_reader_trace(False), _every_reader_trace(True)
    assert tr.reduce_trace(plain, window_span="bench.call") \
        == tr.reduce_trace(scoped, window_span="bench.call")
    names = sorted(f[:-3] for f in os.listdir(METRICS) if f.endswith(".py"))
    assert set(SCOPE_READERS) <= set(names)
    read_something = 0
    for name in names:
        a = _read(name, _every_reader_run(plain))
        b = _read(name, _every_reader_run(scoped))
        if name in SCOPE_READERS:
            assert a is None and b is not None, name
            continue
        assert a == b, name         # the same value, to the last bit
        read_something += a is not None
    # the synthetic run gives every one of them something to read
    assert read_something == len(names) - len(SCOPE_READERS)
    run = _every_reader_run(scoped)
    # knn.merge 25 ms, knn.advance 22 ms over 2 traced calls of 50 ms
    assert _read("merge_share.batch", run) == pytest.approx(25.0)
    assert _read("advance_share.batch", run) == pytest.approx(22.0)


_XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.call" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 7 offset_ps: 10000000000 duration_ps: 20000000000 }
    events { metadata_id: 8 offset_ps: 15000000000 duration_ps: 5000000000
             stats { metadata_id: 3 int64_value: 4 } }
    events { metadata_id: 9 offset_ps: 50000000000 duration_ps: 10000000000 }
    events { metadata_id: 10 offset_ps: 70000000000 duration_ps: 5000000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 11 offset_ps: 10000000000 duration_ps: 70000000000 } }
  event_metadata { key: 7 value { id: 7 name: "while.1"
    stats { metadata_id: 1 str_value: "jit(_chunk_round)/while/body/knn.merge/while" } } }
  event_metadata { key: 8 value { id: 8 name: "fusion.2"
    stats { metadata_id: 1 str_value: "jit(_chunk_round)/while/body/knn.merge/top_k" } } }
  event_metadata { key: 9 value { id: 9 name: "fusion.3"
    stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 10 value { id: 10 name: "copy.4" } }
  event_metadata { key: 11 value { id: 11 name: "jit__chunk_round(1)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(_chunk_round)/knn.advance/add" } }
  stat_metadata { key: 3 value { id: 3 name: "group_id" } }
}
"""


def test_load_trace_gives_each_device_event_its_scope_path(tmp_path):
    """Window [0, 100) ms; knn.merge tags [10, 30) and, nested in it, [15,
    20) (counted once); knn.advance, an interned string, [50, 60); the copy
    and the module have no path."""
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    data = tr.load_trace(str(tmp_path))
    ops = {e.name: e for e in data.device["/device:TPU:0"]}
    assert ops["fusion.2"].scope.endswith("/knn.merge/top_k")
    assert ops["fusion.3"].scope == "jit(_chunk_round)/knn.advance/add"
    assert ops["copy.4"].scope == ops["jit__chunk_round(1)"].scope == ""
    assert all(e.scope == "" for e in data.host)
    assert ops["fusion.2"].start_ns == pytest.approx(16_000_000)
    run = _run(data)
    assert run.trace["window_s"] == pytest.approx(0.100)
    assert spans.scope_seconds(run, "knn.merge") == pytest.approx(0.020)
    assert spans.scope_seconds(run, "knn.advance") == pytest.approx(0.010)
    assert spans.scope_seconds(run, "knn.plan") is None
    # the reduction reads the same events as before, scope or none
    bare = tr.TraceData(
        device={k: [dataclasses.replace(e, scope="") for e in v]
                for k, v in data.device.items()}, host=data.host)
    assert tr.reduce_trace(bare, window_span="bench.call") == run.trace


def _write_xplane(tmp_path, blob):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(blob)
    return str(d / "host.xplane.pb")


def _ld(field, payload):
    """A length-delimited protobuf field (lengths under 128 bytes)."""
    assert len(payload) < 128
    return bytes([field << 3 | 2, len(payload)]) + payload


@pytest.mark.parametrize("line", [
    _ld(2, b"XLA Ops") + bytes([4 << 3 | 3]),     # a wire type xplane never has
    _ld(2, b"XLA Ops") + bytes([4 << 3 | 2, 9]),  # an event past the line's end
])
def test_xplane_scopes_names_the_plane_it_cannot_read(tmp_path, line):
    plane = _ld(2, b"/device:TPU:0") + _ld(3, line)
    path = _write_xplane(tmp_path, _ld(1, _ld(2, b"/host:CPU")) + _ld(1, plane))
    with pytest.raises(ValueError, match="plane '/device:TPU:0'"):
        tr.xplane_scopes(path)


def _shifted(read):
    return {p: {ln: pairs[1:] + pairs[:1] for ln, pairs in lines.items()}
            for p, lines in read.items()}


def _one_short(read):
    return {p: {ln: pairs[:-1] for ln, pairs in lines.items()}
            for p, lines in read.items()}


def _no_ops_line(read):
    return {p: {ln: pairs for ln, pairs in lines.items() if ln != tr.OPS_LINE}
            for p, lines in read.items()}


@pytest.mark.parametrize("misread, says", [
    (_shifted, r"line 'XLA Ops', event 0: named 'while.1'"),
    (_one_short, r"line 'XLA Ops': 4 events, the scope reader found 3"),
    (_no_ops_line, r"line 'XLA Ops': 4 events, the scope reader found none"),
])
def test_load_trace_refuses_scope_paths_that_are_not_their_events(
        tmp_path, monkeypatch, misread, says):
    """Paths paired with events by position alone would tag the wrong
    operations: every event's path is checked against its metadata."""
    from jax.profiler import ProfileData

    _write_xplane(tmp_path, ProfileData.text_proto_to_serialized_xspace(
        _XSPACE))
    read = tr.xplane_scopes
    monkeypatch.setattr(tr, "xplane_scopes", lambda p: misread(read(p)))
    with pytest.raises(ValueError, match="plane '/device:TPU:0', " + says):
        tr.load_trace(str(tmp_path))


def test_scope_sweep_reads_each_scope_against_its_program():
    sweep = harness.load_module(os.path.join(harness.BENCH, "sweeps",
                                             "scopes.py"))
    data = tr.TraceData(
        device={k: [dataclasses.replace(e, scope=e.detail, detail="")
                    for e in v] for k, v in _scoped_trace().device.items()},
        host=_scoped_trace().host)
    assert sweep.scope_names(data, "knn.") == ["knn.advance", "knn.merge"]
    got = sweep.read_scopes(data, 0.1, "knn.", "_chunk_round")
    assert got["scopes_s"] == pytest.approx({"knn.merge": 0.020,
                                             "knn.advance": 0.010})
    # the module runs [20, 110) of the window: the scopes cover a third
    assert got["module_s"] == pytest.approx(0.090)
    assert got["scopes_union_s"] == pytest.approx(0.030)
    assert got["covered"] == pytest.approx(1 / 3)
    assert got["share_of_wall"]["knn.merge"] == pytest.approx(20.0)
