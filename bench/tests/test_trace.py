"""The reduction from trace events to metrics, on hand-made events and on
a slice of a trace recorded on a TPU v5 lite (case7, SGWU round)."""
import json
import os

import pytest

import devtrace
import harness
import kernel_share

E = devtrace.Event
DEV, DEV1 = "/device:TPU:0", "/device:TPU:1"
CC = ('custom-call({args}), custom_call_target="tpu_custom_call", '
      'operand_layout_constraints={{}}')


def _pallas(name, out, *args):
    body = ", ".join(f"{a}{{1,0}} %p{i}" for i, a in enumerate(args))
    return f"%{name} = {out}{{1,0}} " + CC.format(args=body)


def _events():
    dense = _pallas("jvp.1", "f32[64,2048]", "f32[64,2048]",
                    "f32[2048,2048]")
    conv = _pallas("jvp.2", "f32[64,32,32,12]", "f32[64,34,34,3]",
                   "f32[3,3,3,12]", "f32[1,12]")
    return [
        E("/host:CPU", "python", "bench.window", 100.0, 1000.0),
        E("/host:CPU", "python", "bench.event", 100.0, 500.0),
        E("/host:CPU", "python", "bench.draw", 600.0, 300.0),
        E(DEV, "XLA Modules", "jit_step(1)", 50.0, 250.0),
        E(DEV, "XLA Modules", "jit_bench_eval_q(2)", 900.0, 100.0),
        E(DEV, "XLA Ops", "%while.3", 50.0, 250.0),      # spans its body
        E(DEV, "XLA Ops", dense, 60.0, 100.0),           # clipped to 100
        E(DEV, "XLA Ops", conv, 200.0, 80.0),
        E(DEV, "XLA Ops", dense, 920.0, 50.0),           # in the eval
        E(DEV, "XLA Ops", "%fusion.7", 1050.0, 200.0),   # clipped to 1100
        E(DEV1, "XLA Ops", "%all-reduce.1", 300.0, 100.0),
    ]


def test_window_busy_and_idle():
    tr = devtrace.Trace(_events())
    assert (tr.start, tr.end, tr.window_ns) == (100.0, 1100.0, 1000.0)
    assert tr.chips() == [DEV, DEV1]
    # while [100, 300) covers its body; eval dense [920, 970); fusion
    # [1050, 1100)
    assert tr.busy_ns(DEV) == 200.0 + 50.0 + 50.0
    assert tr.busy_ns(DEV1) == 100.0


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = devtrace.Trace(_events())
    gaps = tr.idle_gaps(DEV)
    assert gaps[0] == ["bench.draw", 620e-9]              # [300, 920)
    assert ["none", 80e-9] in gaps                        # [970, 1050)
    assert sum(g[1] for g in gaps) == pytest.approx(700e-9)


def test_leaf_ops_and_top_ops_leave_control_flow_out():
    tr = devtrace.Trace(_events())
    names = [devtrace.op_name(e.name) for e in tr.leaf_ops(DEV)]
    assert "while" not in names
    top = dict(tr.top_ops(DEV))
    assert top["pallas f32[64,2048]"] == pytest.approx(110e-9)
    assert top["fusion"] == pytest.approx(50e-9)


def test_kernel_time_excludes_the_eval_program():
    cfg = json.load(open(os.path.join(harness.BENCH, "configs",
                                      "case7.json")))
    fam = harness.load_module(os.path.join(harness.BENCH, "families",
                                           "cnn.py"), "cnn_family")
    ctx = {"trace": devtrace.Trace(_events()), "family": fam, "cfg": cfg,
           "eval_program": harness.EVAL_PROGRAM}
    assert kernel_share.kernel_ns(ctx, "dense") == 60.0
    assert kernel_share.kernel_ns(ctx, "conv") == 80.0
    assert ctx["trace"].module_ns(DEV, harness.EVAL_PROGRAM) == 100.0


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "case7_sgwu_slice.json")
    with open(path) as f:
        events = [E(*e) for e in json.load(f)["events"]]
    cfg = json.load(open(os.path.join(harness.BENCH, "configs",
                                      "case7.json")))
    fam = harness.load_module(os.path.join(harness.BENCH, "families",
                                           "cnn.py"), "cnn_family")
    return {"trace": devtrace.Trace(events), "family": fam, "cfg": cfg,
            "eval_program": harness.EVAL_PROGRAM}


def test_recorded_slice_reduces_to_its_pinned_readings(recorded):
    tr = recorded["trace"]
    assert tr.chips() == [DEV]
    assert tr.window_ns == 14e6
    assert tr.busy_ns(DEV) == 11791218.0
    assert tr.module_ns(DEV, harness.EVAL_PROGRAM) == 4088299.0
    assert kernel_share.kernel_ns(recorded, "dense") == 525613.0
    assert kernel_share.kernel_ns(recorded, "conv") == 1226652.0
    assert kernel_share.kernel_ns(recorded, "pool") == 844028.0


def test_recorded_slice_sorts_every_pallas_call(recorded):
    tr, fam, cfg = recorded["trace"], recorded["family"], recorded["cfg"]
    kinds = []
    for e in tr.leaf_ops(DEV):
        shapes = devtrace.custom_call_shapes(e.name)
        if shapes:
            kinds.append(fam.kernel_kind(shapes[0], shapes[1], cfg))
    assert len(kinds) == 35
    assert kinds.count("dense") == 10 and kinds.count("conv") == 21


def test_roofline_share_of_the_slice_stays_under_the_peak(recorded):
    ctx = dict(recorded, traffic={"batch": 64},
               peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               window_images=0)
    assert kernel_share.roofline_share(ctx, "dense",
                                       ctx["family"].dense_work) is None
    # one node-step of 64 images per node, 4 nodes: the dense time of a
    # slice cut mid-round is less than the work it would need
    ctx["window_images"] = 1
    share = kernel_share.roofline_share(ctx, "dense",
                                        ctx["family"].dense_work)
    assert 0 < share < 100


def test_a_traced_window_reduces_to_every_metric_of_its_cell(monkeypatch):
    """The harness's own reduction of one traced window: the recorded
    slice (14 ms cut from the end of a round, so credited with one
    node-step of 64 images) gives each per-layer metric of the cell, the
    device seconds and a breakdown within the contract's limits."""
    import devtrace as dt
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "case7_sgwu_slice.json")
    with open(path) as f:
        events = [E(*e) for e in json.load(f)["events"]]
    monkeypatch.setattr(dt, "load", lambda _: events)
    cell = harness.load_cell("case7.sgwu")
    metrics, breakdown, dev = harness._trace_metrics(
        cell, "unused.xplane.pb", 64, 1, 0.014, "TPU v5 lite")
    assert set(metrics) == set(cell.per_layer)
    for name, m in metrics.items():
        assert m["unit"] == cell.per_layer[name] and m["value"] > 0
    assert all(metrics[k]["value"] <= 100 for k in metrics)
    assert dev == {"busy_s": 11791218.0 / 1e9, "window_s": 14e6 / 1e9}
    assert 0 < len(breakdown["device_ops"]) <= 10
    assert 0 < len(breakdown["idle_gaps"]) <= 10


def test_an_unknown_device_kind_is_refused(monkeypatch):
    import devtrace as dt
    monkeypatch.setattr(dt, "load", lambda _: _events())
    with pytest.raises(harness.Refused, match="no peaks"):
        harness._trace_metrics(harness.load_cell("case7.sgwu"), "x", 1, 1,
                               1.0, "TPU v9 imaginary")
