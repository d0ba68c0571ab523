"""The ConvNeXt family's work counts against hand counts of ConvNeXt-T
(arXiv:2201.03545, Table 9: 28.59 M parameters, 4.5 GFLOPs, which are
multiply-accumulates, per 224 px image)."""
import json
import os

import jax
import pytest

import harness

with open(os.path.join(harness.BENCH, "configs", "convnext_t.json")) as _f:
    CFG = json.load(_f)


@pytest.fixture(scope="module")
def fam():
    return harness.load_module(os.path.join(harness.BENCH, "families",
                                            "convnext.py"), "convnext_family")


def test_parameters_match_the_hand_count(fam):
    # stem 4*4*3*96 + 96 + LN 192; a block of C channels 8C^2 + 58C
    # (7x7 depthwise 49C + C, LN 2C, C->4C->C 8C^2 + 5C, layer scale C);
    # downsampling LN 2C_in + 4*C_in*C_out + C_out; head LN 1536 +
    # 768*1000 + 1000
    blocks = sum(d * (8 * c * c + 58 * c)
                 for d, c in [(3, 96), (3, 192), (9, 384), (3, 768)])
    down = sum(2 * a + 4 * a * b + b
               for a, b in [(96, 192), (192, 384), (384, 768)])
    hand = 4 * 4 * 3 * 96 + 96 + 192 + blocks + down + 1536 + 768_000 + 1000
    assert hand == 28_589_128 == CFG["parameters"]
    shapes = jax.eval_shape(lambda: fam.reference().init_params(0, CFG))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == hand


def test_forward_macs_match_the_hand_count(fam):
    # per image: stem 56^2*48*96; per block 49*S^2*C depthwise +
    # 8*S^2*C^2 pointwise; downsampling S^2*4C_in*C into a stage of S
    # px; head 768*1000
    stages = [(3, 56, 96), (3, 28, 192), (9, 14, 384), (3, 7, 768)]
    dw = sum(d * 49 * s * s * c for d, s, c in stages)
    pw = sum(d * 8 * s * s * c * c for d, s, c in stages)
    down = sum(s * s * 4 * c_in * c for c_in, (_, s, c)
               in zip([96, 192, 384], stages[1:], strict=True))
    stem = 56 * 56 * 48 * 96
    f = fam.forward_flops(CFG)
    assert f == {"conv": 2 * dw, "dense": 2 * (stem + pw + down + 768_000)}
    macs = (f["conv"] + f["dense"]) // 2
    assert macs == 4_455_531_264 == CFG["forward_macs"]
    assert macs == pytest.approx(4.455e9, rel=1e-3)


def test_training_flops_are_three_passes_less_the_stem_dx(fam):
    f = fam.forward_flops(CFG)
    stem_dx = 2 * 56 * 56 * 48 * 96
    assert fam.train_flops(CFG) == 3 * (f["conv"] + f["dense"]) - stem_dx


def test_depthwise_bytes_are_two_planes_per_pass(fam):
    _, b1 = fam.conv_work(CFG, 1)
    planes = sum(d * s * s * c for d, s, c in
                 [(3, 56, 96), (3, 28, 192), (9, 14, 384), (3, 7, 768)])
    filters = 49 * (3 * 96 + 3 * 192 + 9 * 384 + 3 * 768)
    assert b1 == 3 * 4 * (2 * planes + filters)


@pytest.mark.parametrize("out,operands,kind", [
    ("f32[4,16,56,56,96]", ["f32[4,16,56,56,96]", "f32[4,7,7,96]",
                            "f32[4,1,96]"], "conv"),
    ("f32[4,7,7,768]", ["f32[4,16,7,7,768]", "f32[4,16,7,7,768]"], "conv"),
    ("f32[16,14,14,384]", ["f32[16,14,14,384]", "f32[7,7,384]"], "conv"),
    ("f32[4,50176,384]", ["f32[4,50176,96]", "f32[4,96,384]",
                          "f32[4,1,384]"], "dense"),
    ("f32[4,96,384]", ["f32[4,96,50176]", "f32[4,50176,384]"], "dense"),
    ("f32[4,16,56,56,96]", ["f32[4,16,56,56,96]"], "other"),
])
def test_kernel_kind_by_operand_shapes(fam, out, operands, kind):
    assert fam.kernel_kind(out, operands, CFG) == kind


def test_dwconv_device_pct_reads_depthwise_calls_over_busy_time(fam):
    """On hand-made trace events: the depthwise calls outside the eval
    program over the device's busy time outside it; nothing for the cnn
    family."""
    import devtrace
    dev = "/device:TPU:0"
    cc = ('custom-call({args}), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={{}}')

    def pallas(out, *args):
        body = ", ".join(f"{a}{{1,0}} %p{i}" for i, a in enumerate(args))
        return f"%c = {out}{{1,0}} " + cc.format(args=body)

    dw = pallas("f32[4,16,56,56,96]", "f32[4,16,56,56,96]",
                "f32[4,7,7,96]", "f32[4,1,96]")
    dense = pallas("f32[4,50176,384]", "f32[4,50176,96]", "f32[4,96,384]")
    E = devtrace.Event
    trace = devtrace.Trace([
        E("/host:CPU", "python", "bench.window", 0.0, 1000.0),
        E(dev, "XLA Modules", "jit_bench_eval_q(1)", 800.0, 100.0),
        E(dev, "XLA Ops", dw, 100.0, 150.0),
        E(dev, "XLA Ops", dense, 300.0, 250.0),
        E(dev, "XLA Ops", dw, 820.0, 60.0),             # in the eval
        E(dev, "XLA Ops", dense, 880.0, 15.0),          # in the eval
    ])
    metric = harness.load_metric("dwconv_device_pct")
    ctx = {"trace": trace, "family": fam, "cfg": CFG,
           "eval_program": harness.EVAL_PROGRAM}
    # busy outside the eval: 150 + 250 ns; depthwise outside it: 150 ns
    assert metric.read(ctx) == pytest.approx(100.0 * 150 / 400)
    cnn = harness.load_module(os.path.join(harness.BENCH, "families",
                                           "cnn.py"), "cnn_family")
    assert metric.read(dict(ctx, family=cnn)) is None
