"""Plain reference of ConvNeXt: weights, forward, loss, accuracy.

Straight ``jax.numpy`` / ``lax`` from the paper (Liu et al., "A ConvNet
for the 2020s", arXiv:2201.03545, Table 9 and Appendix A) and its
reference code (``models/convnext.py`` of facebookresearch/ConvNeXt),
independent of the program:

- stem: a 4x4 stride-4 convolution, then LayerNorm;
- stages of ``depth_<s>`` blocks at ``dim_<s>`` channels; every stage
  after the first is entered through LayerNorm and a 2x2 stride-2
  convolution;
- block: 7x7 depthwise convolution with bias (``feature_group_count``
  equal to the channels), LayerNorm over channels, Linear C->4C, exact
  GELU, Linear 4C->C, layer scale, residual add;
- head: global average pool, LayerNorm, Linear to the classes;
- loss: softmax cross-entropy against labels smoothed by
  ``label_smoothing``, averaged over the batch.

The configuration is read from scalar keys only (``depth_1`` ...,
``dim_1`` ...), as the outer reference passes them.  ``dtype`` and
``precision`` select how it computes: float32 at ``HIGHEST`` is the
reference; ``bfloat16`` at ``DEFAULT`` is the control.  The weights are
made here, on the device in one jitted call from the seed, and handed to
the program and to the reference alike: truncated-normal conv and Linear
weights of std 0.02 (cut at +-2, as timm's ``trunc_normal_``), zero
biases, LayerNorm scale 1 and bias 0, layer scale ``layer_scale_init``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["stages", "init_params", "forward", "loss", "accuracy"]


def stages(cfg: dict):
    """[(depth, dim)] of each stage, from ``depth_<s>`` / ``dim_<s>``."""
    out, s = [], 1
    while f"dim_{s}" in cfg:
        out.append((cfg[f"depth_{s}"], cfg[f"dim_{s}"]))
        s += 1
    return out


def _frozen(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def _tn(key, shape):
    return jnp.clip(0.02 * jax.random.normal(key, shape, jnp.float32),
                    -2.0, 2.0)


def _norm(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, frozen):
    cfg = dict(frozen)
    st = stages(cfg)
    dims = [d for _, d in st]
    k, p = cfg["kernel_size"], cfg["patch_size"]
    keys = iter(jax.random.split(key, 2 + len(st) + 3 * sum(
        d for d, _ in st)))
    params = {"stem": {"w": _tn(next(keys), (p, p, cfg["in_channels"],
                                             dims[0])),
                       "b": jnp.zeros((dims[0],), jnp.float32),
                       "norm": _norm(dims[0])},
              "down": [], "stages": []}
    for c_in, c_out in zip(dims[:-1], dims[1:], strict=True):
        params["down"].append({"norm": _norm(c_in),
                               "w": _tn(next(keys), (2, 2, c_in, c_out)),
                               "b": jnp.zeros((c_out,), jnp.float32)})
    for depth, c in st:
        blocks = []
        for _ in range(depth):
            blocks.append({
                "dw": {"w": _tn(next(keys), (k, k, c)),
                       "b": jnp.zeros((c,), jnp.float32)},
                "norm": _norm(c),
                "pw1": {"w": _tn(next(keys), (c, 4 * c)),
                        "b": jnp.zeros((4 * c,), jnp.float32)},
                "pw2": {"w": _tn(next(keys), (4 * c, c)),
                        "b": jnp.zeros((c,), jnp.float32)},
                "gamma": jnp.full((c,), cfg["layer_scale_init"],
                                  jnp.float32)})
        params["stages"].append(blocks)
    params["head"] = {"norm": _norm(dims[-1]),
                      "w": _tn(next(keys), (dims[-1], cfg["num_classes"])),
                      "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return params


def init_params(seed: int, cfg: dict):
    """Float32 weights from ``seed``, made on the default device in one
    jitted call."""
    key = jax.random.PRNGKey(np.uint32(seed % 2**32))
    return _init(key, _frozen(cfg))


def _layer_norm(p, x, eps, dtype):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(dtype) \
        + p["bias"].astype(dtype)


def _conv(x, w, stride, groups, precision):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME" if stride == 1 else "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=precision)


def forward(params, images, cfg: dict, *, dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST):
    """images (B, H, W, C) -> logits (B, classes), computed in ``dtype``."""
    eps = cfg["ln_eps"]

    def cast(t):
        return jax.tree_util.tree_map(lambda a: a.astype(dtype), t)

    def linear(p, x):
        return jnp.dot(x, p["w"], precision=precision) + p["b"]

    params = cast(params)
    stem = params["stem"]
    x = _conv(images.astype(dtype), stem["w"], cfg["patch_size"], 1,
              precision) + stem["b"]
    x = _layer_norm(stem["norm"], x, eps, dtype)
    for s, blocks in enumerate(params["stages"]):
        if s:
            down = params["down"][s - 1]
            x = _layer_norm(down["norm"], x, eps, dtype)
            x = _conv(x, down["w"], 2, 1, precision) + down["b"]
        for p in blocks:
            c = x.shape[-1]
            y = _conv(x, p["dw"]["w"][:, :, None, :], 1, c, precision) \
                + p["dw"]["b"]
            y = _layer_norm(p["norm"], y, eps, dtype)
            y = jax.nn.gelu(linear(p["pw1"], y), approximate=False)
            x = x + p["gamma"] * linear(p["pw2"], y)
    head = params["head"]
    x = _layer_norm(head["norm"], jnp.mean(x, axis=(1, 2)), eps, dtype)
    return linear(head, x)


def loss(params, images, labels, cfg: dict, **kw):
    """Label-smoothed softmax cross-entropy, averaged over the batch."""
    logits = forward(params, images, cfg, **kw)
    k, eps = cfg["num_classes"], cfg["label_smoothing"]
    target = (1.0 - eps) * jax.nn.one_hot(labels, k, dtype=logits.dtype) \
        + eps / k
    per_example = -jnp.sum(target * jax.nn.log_softmax(logits, -1), -1)
    return jnp.mean(per_example).astype(jnp.float32)


def accuracy(params, images, labels, cfg: dict, **kw):
    logits = forward(params, images, cfg, **kw)
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
