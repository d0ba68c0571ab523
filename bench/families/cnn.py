"""The Table-2 CNN family: how the benchmark builds it in the program, and
the work its kernels do, counted from the configuration's own shapes.

``program(cfg)`` turns a configuration file into the program's
``CNNConfig``, loss and accuracy.  The counting functions take the
unpadded layer shapes of the configuration (never the padded shapes a
kernel runs at), so a count stays the same work whatever implements it:

- ``dense_work`` / ``conv_work``: FLOPs and HBM bytes one training image
  needs in the fully connected / convolution kernels, forward plus the
  backward that training requires (the input gradient of the first conv
  layer is not required, and the program does not compute it);
- ``forward_flops``: the forward pass of one image (the hand count the
  tests pin);
- ``train_flops``: ``dense_work + conv_work`` FLOPs, the numerator of
  ``mfu``.

A byte count is the operands read and the result written by each kernel
call, in float32, once each: activations per image, weights per call.
``kernel_kind`` sorts a Pallas call seen in a device trace into
``dense``, ``conv`` or ``pool`` by its operand shapes, since the
program's kernels carry no name of their own there.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import re

F32 = 4
_HERE = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=None)
def reference():
    """The family's plain reference module (``configs/cnn_reference.py``)."""
    path = os.path.join(os.path.dirname(_HERE), "configs", "cnn_reference.py")
    spec = importlib.util.spec_from_file_location("bench_cnn_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program(cfg: dict):
    """-> (loss_fn(params, batch) -> (loss, aux), accuracy(params, batch))
    of the program under test, for this configuration."""
    from repro.models.cnn import CNNConfig, cnn_accuracy, cnn_loss
    if cfg["pool_min_size"] != 8:
        raise ValueError("the program pools while the map is >= 8 px; "
                         f"config asks for {cfg['pool_min_size']}")
    mc = CNNConfig(name=cfg["name"], image_size=cfg["image_size"],
                   in_channels=cfg["in_channels"],
                   conv_layers=cfg["conv_layers"], filters=cfg["filters"],
                   filter_size=cfg["filter_size"],
                   fc_layers=cfg["fc_layers"], fc_neurons=cfg["fc_neurons"],
                   num_classes=cfg["num_classes"],
                   pool_every=cfg["pool_every"])

    def loss_fn(params, batch):
        return cnn_loss(params, batch, mc), {}

    def accuracy(params, batch):
        return cnn_accuracy(params, batch, mc)

    return loss_fn, accuracy


def _layers(cfg: dict):
    ref = reference()
    shapes, _ = ref.conv_shapes(cfg)
    return shapes, ref.fc_dims(cfg)


def forward_flops(cfg: dict) -> dict:
    """Forward FLOPs of one image: {"conv": ..., "dense": ...}."""
    shapes, fcs = _layers(cfg)
    k2 = cfg["filter_size"] ** 2
    conv = sum(2 * s * s * k2 * cin * cout for cin, cout, s, _ in shapes)
    dense = sum(2 * din * dout for din, dout in fcs)
    return {"conv": conv, "dense": dense}


def dense_work(cfg: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) per training image in the dense kernels: forward,
    input gradient and weight gradient of every FC layer, the weights
    read or written once per call of a ``batch``-row step."""
    _, fcs = _layers(cfg)
    flops = byt = 0.0
    for din, dout in fcs:
        flops += 3 * 2 * din * dout
        acts = F32 * (din + dout)             # x and y (or dy and dx)
        byt += 3 * acts + 3 * F32 * din * dout / batch
    return flops, byt


def conv_work(cfg: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) per training image in the conv kernels: forward and
    weight gradient of every layer, input gradient of all but the first."""
    shapes, _ = _layers(cfg)
    k2 = cfg["filter_size"] ** 2
    flops = byt = 0.0
    for i, (cin, cout, s, _) in enumerate(shapes):
        calls = 3 if i > 0 else 2
        flops += calls * 2 * s * s * k2 * cin * cout
        acts = F32 * s * s * (cin + cout)
        wbytes = F32 * k2 * cin * cout / batch
        byt += calls * (acts + wbytes)
    return flops, byt


def train_flops(cfg: dict) -> float:
    """Training FLOPs of one image (forward and the required backward)."""
    return dense_work(cfg, 1)[0] + conv_work(cfg, 1)[0]


_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _dims(text: str):
    m = _SHAPE.match(text)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else ()


def kernel_kind(out_shape: str, operand_shapes: list, cfg: dict) -> str:
    """``dense``, ``conv`` or ``pool`` for one Pallas call of this family.

    Dense calls work on matrices (rank <= 3 with the node axis).  A conv
    call has a filter-shaped operand or result (..., k, k, c_in, c_out);
    a pool call has neither."""
    k = cfg["filter_size"]
    shapes = [_dims(out_shape)] + [_dims(s) for s in operand_shapes]
    if len(shapes[0]) <= 3:
        return "dense"
    for d in shapes:
        if len(d) >= 4 and d[-4] == k and d[-3] == k \
                and d[-1] == cfg["filters"] \
                and d[-2] in (cfg["in_channels"], cfg["filters"]):
            return "conv"
    return "pool"
