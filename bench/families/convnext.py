"""The ConvNeXt family: how the benchmark builds it in the program, and the
work its kernels do, counted from the configuration's own shapes.

``program(cfg)`` turns a configuration file into the program's
``ConvNeXtConfig``, loss and accuracy.  The counts take the unpadded
shapes of the configuration, per training image:

- ``dense_work``: the matmuls that run as ``dense_matmul`` — the stem and
  downsampling convs (a patchify reshape, then a matmul of k*k*C_in
  columns, one row per output pixel), every block's two pointwise
  Linears, and the head.  Forward, input gradient and weight gradient of
  each; the stem's input gradient is not required (the program does not
  compute it);
- ``conv_work``: the depthwise 7x7 convs (``dwconv_fwd``, ``dwconv_dx``,
  ``dwconv_dw``), all three passes in every block.  Their FLOPs are
  small, so the HBM bytes bound their roofline;
- ``forward_flops``: the forward pass of one image (the hand count the
  tests pin: 4.455 GMACs);
- ``train_flops``: ``dense_work + conv_work`` FLOPs, the numerator of
  ``mfu``.

A byte count is the operands read and the result written by each kernel
call, in float32, once each: activations per image, weights per call of
a ``batch``-image step.  ``kernel_kind`` sorts a Pallas call seen in a
device trace by its operand shapes: a (k, k, C) filter operand or
result is a depthwise call (``conv``), a matrix is ``dense``.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import re

import jax

# imported here, not in ``program``: a checkout without the model fails
# as soon as the benchmark loads the family
from repro.models.convnext import (ConvNeXtConfig, convnext_accuracy,
                                   convnext_loss)

# This family's runs keep no persistent compile cache.  Their programs
# (37 compiles, the reference's included) take more than the 192 MiB to
# which JAX_COMPILATION_CACHE_MAX_SIZE caps the cache on the TPU v5e
# hosts, so a run could not load them back anyway, and writing them would
# evict the other cells' programs from the checkout's one cache and
# change those cells' set-up.  The benchmark loads the family before its
# first compile, where JAX decides whether the process uses the cache.
jax.config.update("jax_enable_compilation_cache", False)

F32 = 4
# ``kernel_kind``'s name for the depthwise calls, which
# ``metrics/dwconv_device_pct.py`` reads
DEPTHWISE_KIND = "conv"
_HERE = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=None)
def reference():
    """The family's plain reference (``configs/convnext_reference.py``)."""
    path = os.path.join(os.path.dirname(_HERE), "configs",
                        "convnext_reference.py")
    spec = importlib.util.spec_from_file_location(
        "bench_convnext_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stages(cfg: dict):
    return reference().stages(cfg)


def program(cfg: dict):
    """-> (loss_fn(params, batch) -> (loss, aux), accuracy(params, batch))
    of the program under test, for this configuration."""
    if cfg["drop_path"]:
        raise ValueError("the program trains without drop path; config "
                         f"asks for {cfg['drop_path']}")
    st = _stages(cfg)
    mc = ConvNeXtConfig(
        name=cfg["name"], image_size=cfg["image_size"],
        in_channels=cfg["in_channels"], num_classes=cfg["num_classes"],
        depths=tuple(d for d, _ in st), dims=tuple(c for _, c in st),
        kernel_size=cfg["kernel_size"], patch_size=cfg["patch_size"],
        layer_scale_init=cfg["layer_scale_init"], ln_eps=cfg["ln_eps"],
        label_smoothing=cfg["label_smoothing"])

    def loss_fn(params, batch):
        return convnext_loss(params, batch, mc), {}

    def accuracy(params, batch):
        return convnext_accuracy(params, batch, mc)

    return loss_fn, accuracy


def matmuls(cfg: dict):
    """[(rows per image, d_in, d_out, input gradient required)] of every
    ``dense_matmul`` layer."""
    st = _stages(cfg)
    p = cfg["patch_size"]
    size = cfg["image_size"] // p
    out = [(size * size, p * p * cfg["in_channels"], st[0][1], False)]
    for s, (depth, c) in enumerate(st):
        if s:
            size //= 2
            out.append((size * size, 4 * st[s - 1][1], c, True))
        out += [(size * size, c, 4 * c, True),
                (size * size, 4 * c, c, True)] * depth
    out.append((1, st[-1][1], cfg["num_classes"], True))
    return out


def depthwise(cfg: dict):
    """[(size, channels)] of every depthwise conv."""
    st = _stages(cfg)
    size = cfg["image_size"] // cfg["patch_size"]
    out = []
    for s, (depth, c) in enumerate(st):
        if s:
            size //= 2
        out += [(size, c)] * depth
    return out


def forward_flops(cfg: dict) -> dict:
    """Forward FLOPs of one image: {"conv": ..., "dense": ...}."""
    k2 = cfg["kernel_size"] ** 2
    conv = sum(2 * s * s * k2 * c for s, c in depthwise(cfg))
    dense = sum(2 * r * din * dout for r, din, dout, _ in matmuls(cfg))
    return {"conv": conv, "dense": dense}


def dense_work(cfg: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) per training image in the dense kernels: forward,
    input gradient (where required) and weight gradient of every matmul,
    the weights read or written once per call of a ``batch``-image step."""
    flops = byt = 0.0
    for rows, din, dout, dx in matmuls(cfg):
        calls = 3 if dx else 2
        flops += calls * 2 * rows * din * dout
        byt += calls * (F32 * rows * (din + dout)
                        + F32 * din * dout / batch)
    return flops, byt


def conv_work(cfg: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) per training image in the depthwise kernels: forward,
    input gradient and weight gradient of every block's 7x7 conv."""
    k2 = cfg["kernel_size"] ** 2
    flops = byt = 0.0
    for s, c in depthwise(cfg):
        flops += 3 * 2 * s * s * k2 * c
        byt += 3 * (F32 * 2 * s * s * c + F32 * k2 * c / batch)
    return flops, byt


def train_flops(cfg: dict) -> float:
    """Training FLOPs of one image (forward and the required backward)."""
    return dense_work(cfg, 1)[0] + conv_work(cfg, 1)[0]


_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _dims(text: str):
    m = _SHAPE.match(text)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else ()


def kernel_kind(out_shape: str, operand_shapes: list, cfg: dict) -> str:
    """``conv`` (depthwise), ``dense`` or ``other`` for one Pallas call.

    A depthwise call has a (..., k, k, C) filter operand or result with C
    one of the stage widths; a dense call works on matrices (rank <= 3
    with the node axis)."""
    k = cfg["kernel_size"]
    widths = {c for _, c in _stages(cfg)}
    shapes = [_dims(out_shape)] + [_dims(s) for s in operand_shapes]
    for d in shapes:
        if len(d) >= 3 and d[-3] == k and d[-2] == k and d[-1] in widths:
            return DEPTHWISE_KIND
    if len(shapes[0]) <= 3:
        return "dense"
    return "other"
