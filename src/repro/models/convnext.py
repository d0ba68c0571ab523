"""ConvNeXt (Liu et al., "A ConvNet for the 2020s", arXiv:2201.03545).

The published network, block for block (Table 9 and Appendix A; the
reference code is ``models/convnext.py`` of facebookresearch/ConvNeXt):

- stem: a 4x4 stride-4 conv, then LayerNorm;
- four stages of ``depths`` blocks at ``dims`` channels, each stage after
  the first entered through LayerNorm and a 2x2 stride-2 conv;
- block: 7x7 depthwise conv with bias, LayerNorm over channels (eps
  1e-6), Linear C->4C, exact GELU, Linear 4C->C, layer scale gamma,
  residual add;
- head: global average pool, LayerNorm, Linear to the classes.

Every conv site runs through ``kernels.ops``.  The depthwise conv is
``ops.depthwise_conv2d`` (the ``dwconv_*`` Pallas kernels).  The stem
and downsampling convs have stride equal to their kernel size, so their
windows tile the image without overlap: such a conv is exactly a
space-to-depth reshape (each k x k patch flattened in (row, column,
channel) order, the HWIO filter's own order) followed by ``ops.dense``
on the (k*k*C_in, C_out) filter matrix.  That is the mapping, not an
approximation.  The pointwise Linears and the head are ``ops.dense`` too;
LayerNorm, GELU and the layer scale are XLA elementwise passes.

Departures from the published training recipe (not the model): no drop
path (``loss_fn(params, batch)`` carries no per-step key), and no EMA,
mixup, cutmix or RandAugment.  The loss is softmax cross-entropy with
the recipe's label smoothing.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers

__all__ = ["ConvNeXtConfig", "CONVNEXT_T", "init_convnext",
           "convnext_forward", "convnext_loss", "convnext_accuracy",
           "patchify"]


@dataclasses.dataclass(frozen=True)
class ConvNeXtConfig:
    name: str = "convnext_t"
    image_size: int = 224
    in_channels: int = 3
    num_classes: int = 1000
    depths: tuple = (3, 3, 9, 3)
    dims: tuple = (96, 192, 384, 768)
    kernel_size: int = 7            # the depthwise filter
    patch_size: int = 4             # the stem's stride and kernel
    layer_scale_init: float = 1e-6
    ln_eps: float = 1e-6
    label_smoothing: float = 0.1

    def __post_init__(self):
        if len(self.depths) != len(self.dims):
            raise ValueError(f"depths {self.depths} and dims {self.dims} "
                             "name different numbers of stages")
        scale = self.patch_size * 2 ** (len(self.dims) - 1)
        if self.image_size % scale:
            raise ValueError(f"image_size {self.image_size} is no multiple "
                             f"of the total stride {scale}")


CONVNEXT_T = ConvNeXtConfig()


def _weight(key, shape, dtype):
    """timm's trunc_normal_(std=.02): a normal of std 0.02 cut at +-2."""
    return jnp.clip(0.02 * jax.random.normal(key, shape, jnp.float32),
                    -2.0, 2.0).astype(dtype)


def _conv(key, k, c_in, c_out, dtype):
    return {"w": _weight(key, (k, k, c_in, c_out), dtype),
            "b": jnp.zeros((c_out,), dtype)}


def _linear(key, d_in, d_out, dtype):
    return {"w": _weight(key, (d_in, d_out), dtype),
            "b": jnp.zeros((d_out,), dtype)}


def init_convnext(key, cfg: ConvNeXtConfig = CONVNEXT_T, dtype=jnp.float32):
    """Weights as the reference code initialises them: truncated-normal
    (std 0.02) conv and Linear weights, zero biases, LayerNorm at (1, 0),
    layer scale at ``layer_scale_init``."""
    # stem, downsampling convs, three per block, head
    keys = iter(jax.random.split(key, len(cfg.dims) + 1
                                 + 3 * sum(cfg.depths)))
    d0 = cfg.dims[0]
    params = {"stem": {**_conv(next(keys), cfg.patch_size, cfg.in_channels,
                               d0, dtype),
                       "norm": layers.init_layer_norm(d0, dtype)},
              "down": [], "stages": []}
    for c_in, c_out in zip(cfg.dims[:-1], cfg.dims[1:]):
        params["down"].append({"norm": layers.init_layer_norm(c_in, dtype),
                               **_conv(next(keys), 2, c_in, c_out, dtype)})
    k = cfg.kernel_size
    for depth, c in zip(cfg.depths, cfg.dims):
        params["stages"].append([{
            "dw": {"w": _weight(next(keys), (k, k, c), dtype),
                   "b": jnp.zeros((c,), dtype)},
            "norm": layers.init_layer_norm(c, dtype),
            "pw1": _linear(next(keys), c, 4 * c, dtype),
            "pw2": _linear(next(keys), 4 * c, c, dtype),
            "gamma": jnp.full((c,), cfg.layer_scale_init, dtype),
        } for _ in range(depth)])
    params["head"] = {"norm": layers.init_layer_norm(cfg.dims[-1], dtype),
                      **_linear(next(keys), cfg.dims[-1], cfg.num_classes,
                                dtype)}
    return params


def patchify(x, k: int):
    """Space-to-depth: (B, H, W, C) -> (B, H/k, W/k, k*k*C), each k x k
    patch flattened in (row, column, channel) order."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // k, k, W // k, k, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, H // k, W // k, k * k * C)


def _strided_conv(p, x):
    """A conv whose stride equals its kernel size: ``patchify`` then
    ``ops.dense`` on the HWIO filter as a (k*k*C_in, C_out) matrix."""
    from repro.kernels import ops
    k, _, c_in, c_out = p["w"].shape
    return ops.dense(patchify(x, k), p["w"].reshape(k * k * c_in, c_out),
                     p["b"])


def _block(p, x, eps: float):
    from repro.kernels import ops
    y = ops.depthwise_conv2d(x, p["dw"]["w"], p["dw"]["b"])
    y = layers.layer_norm(p["norm"], y, eps)
    y = ops.dense(y, p["pw1"]["w"], p["pw1"]["b"])
    y = jax.nn.gelu(y, approximate=False)
    y = ops.dense(y, p["pw2"]["w"], p["pw2"]["b"])
    return x + p["gamma"].astype(y.dtype) * y


def convnext_forward(params, images, cfg: ConvNeXtConfig = CONVNEXT_T):
    """images (B, H, W, C) -> logits (B, classes)."""
    eps = cfg.ln_eps
    x = _strided_conv(params["stem"], images)
    x = layers.layer_norm(params["stem"]["norm"], x, eps)
    for s, blocks in enumerate(params["stages"]):
        if s:
            down = params["down"][s - 1]
            x = _strided_conv(down, layers.layer_norm(down["norm"], x, eps))
        for p in blocks:
            x = _block(p, x, eps)
    head = params["head"]
    x = layers.layer_norm(head["norm"], jnp.mean(x, axis=(1, 2)), eps)
    return layers.fc(head, x)


def convnext_loss(params, batch, cfg: ConvNeXtConfig = CONVNEXT_T):
    """Softmax cross-entropy against labels smoothed by
    ``cfg.label_smoothing``, averaged over the batch."""
    logits = convnext_forward(params, batch["images"], cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    eps = cfg.label_smoothing
    target = (1.0 - eps) * jax.nn.one_hot(batch["labels"], cfg.num_classes) \
        + eps / cfg.num_classes
    return jnp.mean(-jnp.sum(target * logp, axis=-1))


def convnext_accuracy(params, batch, cfg: ConvNeXtConfig = CONVNEXT_T):
    logits = convnext_forward(params, batch["images"], cfg)
    return jnp.mean((jnp.argmax(logits, -1) == batch["labels"])
                    .astype(jnp.float32))
