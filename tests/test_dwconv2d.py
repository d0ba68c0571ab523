"""The depthwise conv Pallas kernels (interpret mode) against XLA's grouped
convolution: forward, input gradient and weight gradient, at ConvNeXt's
channel counts and under ``vmap`` over nodes as the fused engine runs
them; and ``ops.depthwise_conv2d``'s explicit-fallback contract."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import dwconv2d
from repro.kernels.conv2d import _vmem_bytes
from repro.kernels.dwconv2d import channel_block, dwconv2d_pallas

HI = jax.lax.Precision.HIGHEST


def lax_depthwise(x, w, b):
    """XLA's grouped conv, one group per channel, exact in float32."""
    out = jax.lax.conv_general_dilated(
        x, w[:, :, None, :], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1], precision=HI)
    return out + b


def _inputs(shape, k, seed=0):
    kx, kw, kb, kg = jax.random.split(jax.random.PRNGKey(seed), 4)
    C = shape[-1]
    x = jax.random.normal(kx, shape, jnp.float32)
    w = jax.random.normal(kw, (k, k, C), jnp.float32)
    b = jax.random.normal(kb, (C,), jnp.float32)
    g = jax.random.normal(kg, shape, jnp.float32)
    return x, w, b, g


def _passes(f, x, w, b, g):
    """forward, and (dx, dw, db) of <f(x, w, b), g>."""
    out, vjp = jax.vjp(f, x, w, b)
    return (out,) + vjp(g)


def _close(got, want, what):
    # float32 sums of k*k products in another order: a few ulps of the
    # largest term, relative to the result's scale
    for name, a, e in zip(("out", "dx", "dw", "db"), got, want, strict=True):
        scale = float(jnp.max(jnp.abs(e)))
        np.testing.assert_allclose(a, e, atol=2e-5 * scale, rtol=0,
                                   err_msg=f"{what}: {name}")


# C=8 below a lane; 96 (ConvNeXt stage 1) a partial lane block taken
# whole; 128 one full lane; odd H/W and an even filter for the pads
@pytest.mark.parametrize("B,H,W,C,k", [
    (2, 8, 8, 8, 7),
    (2, 9, 7, 96, 7),
    (1, 6, 10, 128, 7),
    (2, 5, 5, 8, 3),
    (1, 6, 6, 8, 4),
])
def test_passes_match_grouped_conv(B, H, W, C, k):
    x, w, b, g = _inputs((B, H, W, C), k, seed=B * H * W + C + k)
    got = _passes(lambda *a: dwconv2d_pallas(*a, interpret=True), x, w, b, g)
    _close(got, _passes(lax_depthwise, x, w, b, g), f"{(B, H, W, C, k)}")


@pytest.mark.parametrize("C", [8, 96, 128])
def test_vmapped_over_nodes_as_the_engine_runs_it(C):
    """The fused SGWU round vmaps each node's step: a leading node axis on
    the images and on the filter (a grid axis the kernels gain)."""
    nodes, k = 3, 7
    x, _, _, g = _inputs((nodes, 2, 7, 9, C), k, seed=C)
    kw, kb = jax.random.split(jax.random.PRNGKey(C + 1))
    w = jax.random.normal(kw, (nodes, k, k, C), jnp.float32)
    b = jax.random.normal(kb, (nodes, C), jnp.float32)
    got = jax.vmap(lambda *a: _passes(
        lambda *p: dwconv2d_pallas(*p, interpret=True), *a))(x, w, b, g)
    want = jax.vmap(lambda *a: _passes(lax_depthwise, *a))(x, w, b, g)
    _close(got, want, f"vmap C={C}")


def test_channel_blocks_tile_a_wide_lane_axis(monkeypatch):
    """Where a cell of all of a lane-multiple C does not fit the VMEM
    budget, the channels split into lane blocks; the result is the
    one-block result."""
    x, w, b, g = _inputs((2, 5, 6, 256), 7, seed=3)
    whole = _passes(lambda *a: dwconv2d_pallas(*a, interpret=True),
                    x, w, b, g)
    # a budget that holds a 128-channel cell and not a 256-channel one
    cell_128 = (4 * _vmem_bytes((5, 6, 128), 4)
                + _vmem_bytes((11, 12, 128), 4))
    monkeypatch.setattr(dwconv2d, "_VMEM_BUDGET", cell_128)
    assert channel_block(5, 6, 256, 7, 7) == 128
    tiled = _passes(lambda *a: dwconv2d_pallas(*a, interpret=True),
                    x, w, b, g)
    _close(tiled, whole, "block 128")


@pytest.mark.parametrize("H,C", [(56, 96), (28, 192), (14, 384), (7, 768),
                                 (224, 256)])
def test_channel_block_is_whole_where_it_fits(H, C):
    ct = channel_block(H, H, C, 7, 7)
    assert C % ct == 0 and (ct == C or ct % 128 == 0)
    # every ConvNeXt-T stage takes all its channels in one cell
    assert (ct == C) == (H <= 56)


def test_dispatch_runs_the_kernel_under_pallas(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    ops.clear_fallback_log()
    x, w, b, _ = _inputs((1, 6, 6, 8), 7)
    text = str(jax.make_jaxpr(ops.depthwise_conv2d)(x, w, b))
    assert "dwconv_fwd" in text
    assert ops.fallback_events() == {}


@pytest.mark.parametrize("padding,stride", [("VALID", 1), ("SAME", 2)])
def test_unsupported_calls_take_the_fallback_contract(monkeypatch, padding,
                                                      stride):
    x, w, b, _ = _inputs((1, 8, 8, 8), 3)
    with pytest.raises(NotImplementedError):
        ops.depthwise_conv2d(x, w, b, padding=padding, stride=stride,
                             impl="pallas")
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    ops.clear_fallback_log()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = ops.depthwise_conv2d(x, w, b, padding=padding, stride=stride)
    assert any(issubclass(c.category, ops.KernelFallbackWarning)
               for c in caught)
    assert [op for op, _ in ops.fallback_events()] == ["depthwise_conv2d"]
    want = jax.lax.conv_general_dilated(
        x, w[:, :, None, :], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=8,
        precision=HI) + b
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    ops.clear_fallback_log()
