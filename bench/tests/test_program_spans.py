"""The readers of the program's own host spans (``bpt.*``, written by
``repro.sanitize.span``), on hand-made events and on the recorded slice of
a case7 SGWU round, which holds no program span."""
import json
import os

import pytest

import devtrace
import harness

E = devtrace.Event
HOST, DEV, DEV1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
READERS = ("host_syncs_per_merge", "sync_ms_per_merge",
           "input_ms_per_merge", "idle_unattributed_pct")


def _span(name, start, end):
    return E(HOST, "python", name, float(start), float(end - start))


def _op(chip, start, end):
    return E(chip, "XLA Ops", "%fusion.1 = f32[8]{0} fusion()", float(start),
             float(end - start))


def _events(rounds_only=False):
    """Window [100, 1100), two merges.  Round 0 holds a draw and a
    placement that overlap, a dispatch, a loss sync with a sync nested in
    it, and a merge; the trainer's eval sync follows it; round 1 holds a
    draw, a placement and a loss sync; a draw that began before the window
    and an eval sync that runs past its end cross the two edges."""
    spans = [
        _span("bench.window", 100, 1100),
        _span("bpt.round", 100, 600),
        _span("bpt.round", 700, 1050),
    ]
    if not rounds_only:
        spans += [
            _span("bpt.draw", 50, 130),               # starts before: out
            _span("bpt.draw", 110, 150),
            _span("bpt.place", 140, 170),
            _span("bpt.dispatch", 170, 180),
            _span("bpt.sync.round.losses", 180, 400),
            _span("bpt.sync.eval-nodes", 300, 350),   # nested in the above
            _span("bpt.merge", 400, 450),
            _span("bpt.sync.eval", 600, 700),
            _span("bpt.draw", 700, 720),
            _span("bpt.place", 720, 740),
            _span("bpt.sync.round.losses", 760, 900),
            _span("bpt.sync.eval", 1050, 1200),       # runs past the end
        ]
    return spans + [_op(DEV, 150, 400), _op(DEV, 800, 900),
                    _op(DEV1, 100, 600)]


def _ctx(events, merges=2):
    return {"trace": devtrace.Trace(events), "window_events": merges}


def _read(name, ctx):
    return harness.load_metric(name).read(ctx)


def test_host_syncs_count_the_sync_spans_that_start_in_the_window():
    # round.losses, eval-nodes, eval, round.losses, eval (past the end)
    assert _read("host_syncs_per_merge", _ctx(_events())) == 5 / 2


def test_sync_ms_is_the_union_of_the_sync_spans():
    # [180, 400) holds the nested sync; [600, 700), [760, 900) and the
    # whole of [1050, 1200), which starts in the window
    union_ns = 220 + 100 + 140 + 150
    assert _read("sync_ms_per_merge", _ctx(_events())) == \
        pytest.approx(union_ns / 1e6 / 2)


def test_input_ms_is_the_union_of_draws_and_placements_in_the_window():
    # [110, 170) and [700, 740); the draw that began at 50 does not count
    assert _read("input_ms_per_merge", _ctx(_events())) == \
        pytest.approx(100 / 1e6 / 2)


def test_idle_unattributed_averages_the_chips():
    # chip 0 idles [100, 150), [400, 800), [900, 1100): 650, of which the
    # spans other than bpt.round cover [110, 150), [400, 450),
    # [600, 740), [760, 800) and [1050, 1100): 320.  Chip 1 idles
    # [600, 1100): 500, covered [600, 740), [760, 900), [1050, 1100): 330.
    want = 100 * ((1 - 320 / 650) + (1 - 330 / 500)) / 2
    assert _read("idle_unattributed_pct", _ctx(_events())) == \
        pytest.approx(want)


def test_round_self_time_is_unattributed():
    assert _read("idle_unattributed_pct",
                 _ctx(_events(rounds_only=True))) == 100.0


def test_no_program_span_reads_none():
    bare = [e for e in _events() if not e.name.startswith("bpt.")]
    for name in READERS:
        assert _read(name, _ctx(bare)) is None, name


def test_the_recorded_slice_has_no_program_spans():
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "case7_sgwu_slice.json")
    with open(path) as f:
        events = [E(*e) for e in json.load(f)["events"]]
    for name in READERS:
        assert _read(name, _ctx(events, merges=1)) is None, name


def test_the_harness_reports_the_span_metrics_once_listed(monkeypatch):
    """The harness's reduction of a traced window gives each reader's
    value, and names idle gaps by program spans, once ``BENCHMARK.json``
    lists the readers (PERF.md, section 7: not before ``devtrace.load``
    keeps the spans)."""
    monkeypatch.setattr(devtrace, "load", lambda _: _events())
    cell = harness.load_cell("case7.sgwu")
    cell.per_layer = dict(cell.per_layer, host_syncs_per_merge="syncs",
                          sync_ms_per_merge="ms", input_ms_per_merge="ms",
                          idle_unattributed_pct="%")
    metrics, breakdown, _ = harness._trace_metrics(
        cell, "unused.xplane.pb", 1024, 2, 1e-6, "TPU v5 lite")
    for name in READERS:
        assert metrics[name]["unit"] == cell.per_layer[name]
    assert metrics["host_syncs_per_merge"]["value"] == 2.5
    # the longest idle gap, [400, 800), is named by the innermost host
    # span over its middle (600): the trainer's eval sync
    assert breakdown["idle_gaps"][0] == ["bpt.sync.eval", 400e-9]
