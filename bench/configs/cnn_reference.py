"""Plain reference of the Table-2 CNN: weights, forward, loss, accuracy.

Straight ``jax.numpy`` / ``lax`` from the paper's description (arXiv
1810.07742, section 3.1 and Table 2), independent of the program: 3x3
SAME convolutions with bias and ReLU, a 2x2 max-pool after every
``pool_every``-th conv layer while the map is still at least
``pool_min_size`` pixels wide, the map flattened in (H, W, C) order, fully
connected layers with ReLU on all but the last, and the squared error
between the softmax and the one-hot label (Eq. 16), averaged over the
batch.

``dtype`` and ``precision`` select how it computes: float32 at
``HIGHEST`` matmul precision is the reference; ``bfloat16`` at
``DEFAULT`` is the control, one step below the float32 that the
configurations state.  The weights are made here too, on the device in
one jitted call from the seed, and handed to the program and to the
reference alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["conv_shapes", "fc_dims", "init_params", "forward", "loss",
           "accuracy"]


def conv_shapes(cfg: dict):
    """[(c_in, c_out, size, pooled)] per conv layer, and the final size."""
    shapes, size, cin = [], cfg["image_size"], cfg["in_channels"]
    for i in range(cfg["conv_layers"]):
        pooled = (i + 1) % cfg["pool_every"] == 0 \
            and size >= cfg["pool_min_size"]
        shapes.append((cin, cfg["filters"], size, pooled))
        if pooled:
            size //= 2
        cin = cfg["filters"]
    return shapes, size


def fc_dims(cfg: dict):
    """[(d_in, d_out)] per fully connected layer."""
    _, final = conv_shapes(cfg)
    d_in = final * final * cfg["filters"]
    dims = [d_in] + [cfg["fc_neurons"]] * (cfg["fc_layers"] - 1) \
        + [cfg["num_classes"]]
    return list(zip(dims[:-1], dims[1:], strict=True))


def _frozen(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, frozen):
    cfg = dict(frozen)
    shapes, _ = conv_shapes(cfg)
    fcs = fc_dims(cfg)
    keys = jax.random.split(key, len(shapes) + len(fcs))
    k = cfg["filter_size"]
    params = {"conv": [], "fc": []}
    for i, (cin, cout, _, _) in enumerate(shapes):
        w = jax.random.normal(keys[i], (k, k, cin, cout), jnp.float32)
        params["conv"].append({"w": w * jnp.sqrt(2.0 / (cin * k * k)),
                               "b": jnp.zeros((cout,), jnp.float32)})
    for j, (din, dout) in enumerate(fcs):
        w = jax.random.normal(keys[len(shapes) + j], (din, dout),
                              jnp.float32)
        params["fc"].append({"w": w * jnp.sqrt(2.0 / din),
                             "b": jnp.zeros((dout,), jnp.float32)})
    return params


def init_params(seed: int, cfg: dict):
    """He-initialised float32 weights from ``seed``, made on the default
    device in one jitted call."""
    key = jax.random.PRNGKey(np.uint32(seed % 2**32))
    return _init(key, _frozen(cfg))


def forward(params, images, cfg: dict, *, dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST):
    """images (B, H, W, C) -> logits (B, classes), computed in ``dtype``."""
    shapes, _ = conv_shapes(cfg)
    x = images.astype(dtype)
    for p, (_, _, _, pooled) in zip(params["conv"], shapes, strict=True):
        x = jax.lax.conv_general_dilated(
            x, p["w"].astype(dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        x = jax.nn.relu(x + p["b"].astype(dtype))
        if pooled:
            x = jax.lax.reduce_window(x, -jnp.inf,
                                      jax.lax.max, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    last = len(params["fc"]) - 1
    for j, p in enumerate(params["fc"]):
        x = jnp.dot(x, p["w"].astype(dtype), precision=precision) \
            + p["b"].astype(dtype)
        if j < last:
            x = jax.nn.relu(x)
    return x


def loss(params, images, labels, cfg: dict, **kw):
    """Eq. 16: squared error of the softmax against the one-hot label."""
    logits = forward(params, images, cfg, **kw)
    y = jax.nn.one_hot(labels, cfg["num_classes"], dtype=logits.dtype)
    err = jnp.sum((y - jax.nn.softmax(logits, -1)) ** 2, -1)
    return jnp.mean(err).astype(jnp.float32)


def accuracy(params, images, labels, cfg: dict, **kw):
    logits = forward(params, images, cfg, **kw)
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
