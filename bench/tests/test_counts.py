"""The work counts against hand counts of the Table-2 shapes."""
import json
import os

import pytest

import harness

CONFIGS = os.path.join(harness.BENCH, "configs")
# Table 2 case 4, which has no cell yet: case 7's file with its own sizes
CASE4 = {"conv_layers": 8, "filters": 8, "fc_layers": 5, "fc_neurons": 1500}


def _cfg(name):
    with open(os.path.join(CONFIGS, "case7.json")) as f:
        cfg = json.load(f)
    return dict(cfg, **CASE4) if name == "case4" else cfg


@pytest.fixture(scope="module")
def cnn():
    return harness.load_module(os.path.join(harness.BENCH, "families",
                                            "cnn.py"), "cnn_family")


# forward FLOPs per image: case7 conv 1.78 M (32, 16, 8 px pooled, then
# seven layers at 4 px) + dense 40.8 M (192->2000, 5 x 2000->2000,
# 2000->10); case4 conv 0.90 M + dense 13.9 M (128->1500, 3 x 1500->1500,
# 1500->10)
@pytest.mark.parametrize("name,conv,dense,total", [
    ("case7", 1_783_296, 40_808_000, 42.6e6),
    ("case4", 903_168, 13_914_000, 14.8e6),
])
def test_forward_flops_match_hand_counts(cnn, name, conv, dense, total):
    f = cnn.forward_flops(_cfg(name))
    assert f == {"conv": conv, "dense": dense}
    assert abs(f["conv"] + f["dense"] - total) / total < 2e-3


@pytest.mark.parametrize("name", ["case7", "case4"])
def test_training_flops_are_three_passes_less_first_conv_dx(cnn, name):
    cfg = _cfg(name)
    f = cnn.forward_flops(cfg)
    first = 2 * cfg["image_size"] ** 2 * cfg["filter_size"] ** 2 \
        * cfg["in_channels"] * cfg["filters"]
    assert cnn.train_flops(cfg) == 3 * (f["conv"] + f["dense"]) - first


def test_dense_bytes_count_weights_once_per_call(cnn):
    cfg = _cfg("case7")
    _, b1 = cnn.dense_work(cfg, 1)
    _, b64 = cnn.dense_work(cfg, 64)
    weights = 4 * (192 * 2000 + 5 * 2000 * 2000 + 2000 * 10)
    assert b1 - b64 == pytest.approx(3 * weights * (1 - 1 / 64))


@pytest.mark.parametrize("out,operands,kind", [
    ("f32[4,64,2048]", ["f32[4,64,2048]", "f32[4,2048,2048]"], "dense"),
    ("f32[2000,2048]", ["f32[2000,64]", "f32[64,2048]"], "dense"),
    ("f32[4,64,32,32,12]", ["f32[4,64,34,34,3]", "f32[4,3,3,3,12]",
                            "f32[4,1,12]"], "conv"),
    ("f32[3,3,12,12]", ["f32[64,6,6,12]", "f32[64,4,4,12]"], "conv"),
    ("f32[4,64,16,16,12]", ["f32[4,64,32,32,12]"], "pool"),
    ("f32[64,32,32,12]", ["f32[64,32,32,12]", "f32[64,16,16,12]",
                          "f32[64,16,16,12]"], "pool"),
])
def test_kernel_kind_by_operand_shapes(cnn, out, operands, kind):
    assert cnn.kernel_kind(out, operands, _cfg("case7")) == kind
