"""ConvNeXt against its plain float32 reference (the benchmark's
``bench/configs/convnext_reference.py``, loaded by path), on seeded
random weights at a small size: the loss and every leaf of the gradient,
through the jnp kernels and through the Pallas kernels (interpret mode).
Also the published ConvNeXt-T's parameter count, and the mapping of a
conv whose stride equals its kernel onto a patchify reshape and a dense
matmul."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.models.convnext import (CONVNEXT_T, ConvNeXtConfig,
                                   convnext_accuracy, convnext_forward,
                                   convnext_loss, init_convnext, patchify)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "bench", "configs")
HI = jax.lax.Precision.HIGHEST

# ConvNeXt-T's file with the widths and depths the CPU can hold
SMALL = {"image_size": 32, "num_classes": 10, "dim_1": 8, "dim_2": 16,
         "dim_3": 32, "dim_4": 64, "depth_1": 1, "depth_2": 1,
         "depth_3": 2, "depth_4": 1}


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "convnext_reference", os.path.join(CONFIGS, "convnext_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(**changes):
    with open(os.path.join(CONFIGS, "convnext_t.json")) as f:
        cfg = json.load(f)
    return dict(cfg, **changes)


def _model(cfg: dict) -> ConvNeXtConfig:
    n = len([k for k in cfg if k.startswith("dim_")])
    return ConvNeXtConfig(
        name=cfg["name"], image_size=cfg["image_size"],
        in_channels=cfg["in_channels"], num_classes=cfg["num_classes"],
        depths=tuple(cfg[f"depth_{s}"] for s in range(1, n + 1)),
        dims=tuple(cfg[f"dim_{s}"] for s in range(1, n + 1)),
        kernel_size=cfg["kernel_size"], patch_size=cfg["patch_size"],
        layer_scale_init=cfg["layer_scale_init"], ln_eps=cfg["ln_eps"],
        label_smoothing=cfg["label_smoothing"])


def _random_weights(params, seed: int):
    """Every leaf drawn from the seed, so no branch hides behind the
    published init (a layer scale of 1e-6, zero biases, unit norms)."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = [p + (0.5 if p.ndim == 1 else 0.1)
           * jax.random.normal(k, p.shape, p.dtype)
           for p, k in zip(leaves, keys, strict=True)]
    return jax.tree_util.tree_unflatten(tree, out)


def test_published_convnext_t_has_its_parameter_count(reference):
    shapes = jax.eval_shape(lambda: init_convnext(jax.random.PRNGKey(0)))
    ref = jax.eval_shape(lambda: reference.init_params(0, _config()))
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(shapes),
                    jax.tree_util.tree_leaves(ref), strict=True):
        assert a.shape == b.shape
    # arXiv:2201.03545 Table 9 / timm convnext_tiny: 28.59 M
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 28_589_128


def _init(seed: int, mc: ConvNeXtConfig):
    return jax.jit(init_convnext, static_argnums=1)(
        jax.random.PRNGKey(seed), mc)


def test_init_follows_the_reference_code():
    p = _init(1, _model(_config(**SMALL)))
    block = p["stages"][2][1]
    assert float(jnp.max(jnp.abs(block["gamma"] - 1e-6))) == 0.0
    assert float(jnp.max(jnp.abs(block["pw1"]["b"]))) == 0.0
    assert float(jnp.min(block["norm"]["scale"])) == 1.0
    std = float(jnp.std(p["stages"][3][0]["pw1"]["w"]))
    assert 0.018 < std < 0.022


@pytest.mark.parametrize("k,c_in,c_out", [(4, 3, 8), (2, 8, 16)])
def test_strided_conv_is_patchify_then_dense(k, c_in, c_out):
    """A conv of stride equal to its kernel size (the stem, a
    downsampling layer) is exactly space-to-depth and a matmul on the
    HWIO filter flattened in the same (row, column, channel) order."""
    kx, kw = jax.random.split(jax.random.PRNGKey(k))
    x = jax.random.normal(kx, (2, 4 * k, 2 * k, c_in))
    w = jax.random.normal(kw, (k, k, c_in, c_out))
    conv = jax.lax.conv_general_dilated(
        x, w, (k, k), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HI)
    mapped = jnp.dot(patchify(x, k), w.reshape(k * k * c_in, c_out),
                     precision=HI)
    np.testing.assert_allclose(mapped, conv, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_loss_and_gradients_match_the_reference(reference, monkeypatch,
                                                impl):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    ops.clear_fallback_log()
    cfg = _config(**SMALL)
    mc = _model(cfg)
    params = _random_weights(reference.init_params(7, cfg), 8)
    kx, ky = jax.random.split(jax.random.PRNGKey(9))
    batch = {"images": jax.random.normal(kx, (4, 32, 32, 3)),
             "labels": jax.random.randint(ky, (4,), 0, 10)}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: convnext_loss(p, batch, mc)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, batch["images"], batch["labels"],
                                 cfg)))(params)
    assert ops.fallback_events() == {}
    # float32 on the CPU, where every matmul is exact float32: the two
    # differ only in the order of their sums (XLA's grouped conv against
    # the kernels' shifted taps, a reshape-and-matmul against a strided
    # conv), a few ulps, far below what any wrong term would move
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    flat = jax.tree_util.tree_leaves_with_path
    expected = {jax.tree_util.keystr(k): v for k, v in flat(want_grads)}
    got = {jax.tree_util.keystr(k): v for k, v in flat(grads)}
    assert got.keys() == expected.keys()
    for name, g in got.items():
        r = expected[name]
        # the same ulps, compounded through a few layers of backward: a
        # relative 1e-4 of the leaf's norm; a mistaken gradient term is
        # of the order of the leaf itself
        assert float(jnp.linalg.norm(g - r)) \
            <= 1e-4 * float(jnp.linalg.norm(r)), name


def test_accuracy_is_the_argmax_rate(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    mc = _model(_config(**SMALL))
    params = _init(2, mc)
    images = jax.random.normal(jax.random.PRNGKey(3), (6, 32, 32, 3))
    logits = jax.jit(convnext_forward, static_argnums=2)(params, images, mc)
    assert logits.shape == (6, 10)
    labels = jnp.argmax(logits, -1).at[:2].add(1) % 10
    acc = jax.jit(convnext_accuracy, static_argnums=2)(
        params, {"images": images, "labels": labels}, mc)
    assert float(acc) == pytest.approx(4 / 6)


def test_label_smoothing_enters_the_loss(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    mc = _model(_config(**SMALL))
    params = _init(4, mc)
    batch = {"images": jax.random.normal(jax.random.PRNGKey(5),
                                         (2, 32, 32, 3)),
             "labels": jnp.array([1, 2])}

    @jax.jit
    def both(params, batch):
        logp = jax.nn.log_softmax(
            convnext_forward(params, batch["images"], mc))
        hard = -jnp.mean(logp[jnp.arange(2), batch["labels"]])
        smooth = 0.9 * hard - 0.1 * jnp.mean(jnp.mean(logp, -1))
        return convnext_loss(params, batch, mc), smooth

    loss, smooth = both(params, batch)
    assert float(loss) == pytest.approx(float(smooth), rel=1e-5)


def test_the_published_config_is_the_default():
    assert _model(_config()) == ConvNeXtConfig(name="convnext_t")
    assert CONVNEXT_T.depths == (3, 3, 9, 3)
    assert CONVNEXT_T.dims == (96, 192, 384, 768)
