"""Pallas TPU max-pool2d — the paper's Eq. 15 pooling tasks, differentiable.

TPU adaptation of the paper's per-output-element pooling decomposition:
the ``pallas_call`` grid cell is one image's pooling task list PT_Pool —
Eq. (15) computes every output element as the window max, and the Eq. (18)
backward routes each cotangent element to the argmax position(s) of its
window.

Two kernels cover the layer's training step:

* ``_pool_fwd_kernel`` — Eq. (15): the window max over non-overlapping
  ``window x window`` tiles, computed as ONE reshape + max per image.
* ``_pool_bwd_kernel`` — Eq. (18) error routing: the cotangent flows to the
  positions that achieved the max.  Ties split evenly (mask / tie-count),
  matching ``jax.grad`` of the jnp reference exactly — relu feature maps
  tie often (many exact zeros), so the tie rule is load-bearing for the
  pallas ≡ ref trajectory equivalence, not a corner case.

``max_pool2d_pallas`` ties them together with ``jax.custom_vjp`` so
``jax.grad`` through the Pallas path never falls back to the jnp reference.

Layout: x NHWC.  Non-overlapping pooling only (``stride == window``, the
paper's 2x2 configuration); the ``ops.max_pool2d`` dispatcher applies the
explicit-fallback contract for anything else.  Trailing rows/cols that do
not fill a window are dropped (and receive zero gradient), exactly like
``ref.max_pool2d_ref``.  ``interpret=None`` resolves via
``kernels.ops._interpret()`` — interpret mode off TPU, compiled on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

__all__ = ["max_pool2d_pallas"]


# ----------------------------------------------------------------------
# Kernels
# ----------------------------------------------------------------------
def _pool_fwd_kernel(x_ref, o_ref, *, window: int, Ho: int, Wo: int):
    """One PT_Pool task: all Eq. (15) window maxima for one image.

    x (1, H, W, C); o (1, Ho, Wo, C) with Ho = H // window (trailing
    remainder rows/cols dropped, like the jnp reference).
    """
    k = window
    C = x_ref.shape[-1]
    x = x_ref[0, :Ho * k, :Wo * k, :].reshape(Ho, k, Wo, k, C)
    o_ref[0, :, :, :] = x.max(axis=(1, 3)).astype(o_ref.dtype)


def _pool_bwd_kernel(x_ref, o_ref, g_ref, dx_ref, *, window: int,
                     Ho: int, Wo: int):
    """Eq. (18) error routing for one image: cotangent -> argmax positions.

    x (1, H, W, C); o/g (1, Ho, Wo, C); dx (1, H, W, C).  The saved
    forward output is the argmax oracle: positions equal to the window max
    share the cotangent evenly (ties split 1/count — the jnp/jax rule).
    """
    k = window
    C = x_ref.shape[-1]
    x = x_ref[0, :Ho * k, :Wo * k, :].reshape(Ho, k, Wo, k, C)
    out = o_ref[0, :, :, :][:, None, :, None, :]
    g = g_ref[0, :, :, :][:, None, :, None, :]
    mask = (x == out).astype(jnp.float32)
    counts = mask.sum(axis=(1, 3), keepdims=True)
    routed = g.astype(jnp.float32) * mask / counts
    # dropped remainder rows/cols get zero gradient
    dx_ref[...] = jnp.zeros_like(dx_ref)
    dx_ref[0, :Ho * k, :Wo * k, :] = \
        routed.reshape(Ho * k, Wo * k, C).astype(dx_ref.dtype)


# ----------------------------------------------------------------------
# pallas_call wrappers
# ----------------------------------------------------------------------
def _forward(x, *, window: int, interpret: bool):
    B, H, W, C = x.shape
    Ho, Wo = H // window, W // window
    return pl.pallas_call(
        functools.partial(_pool_fwd_kernel, window=window, Ho=Ho, Wo=Wo),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W, C), lambda bi: (bi, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, Ho, Wo, C), lambda bi: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, C), x.dtype),
        interpret=interpret,
        name="pool_fwd",
    )(x)


def _backward(x, out, g, *, window: int, interpret: bool):
    B, H, W, C = x.shape
    Ho, Wo = out.shape[1], out.shape[2]
    return pl.pallas_call(
        functools.partial(_pool_bwd_kernel, window=window, Ho=Ho, Wo=Wo),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((1, Ho, Wo, C), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((1, Ho, Wo, C), lambda bi: (bi, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, W, C), lambda bi: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), x.dtype),
        interpret=interpret,
        name="pool_bwd",
    )(x, out, g)


# ----------------------------------------------------------------------
# custom_vjp wiring
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pool(cfg, x):
    window, interpret = cfg
    return _forward(x, window=window, interpret=interpret)


def _pool_fwd(cfg, x):
    out = _pool(cfg, x)
    # the forward output IS the argmax oracle — no index residual needed
    return out, (x, out)


def _pool_bwd(cfg, residuals, g):
    window, interpret = cfg
    x, out = residuals
    return (_backward(x, out, g, window=window, interpret=interpret),)


_pool.defvjp(_pool_fwd, _pool_bwd)


# ----------------------------------------------------------------------
# Public entry point
# ----------------------------------------------------------------------
def max_pool2d_pallas(x, window: int = 2, stride: int = 2, *,
                      interpret: bool | None = None):
    """Differentiable max pooling: (B, H, W, C) -> (B, H//w, W//w, C).

    Non-overlapping windows only (``stride == window``) — the paper's
    pooling configuration; the dispatcher falls back explicitly otherwise.
    ``jax.grad`` runs the Eq. (18) argmax-routing backward kernel via
    ``custom_vjp`` (ties split evenly, matching the jnp oracle).
    ``interpret=None`` resolves via ``kernels.ops._interpret()``.
    """
    if window != stride:
        raise ValueError(
            f"max_pool2d_pallas supports non-overlapping pooling only "
            f"(stride == window), got window={window} stride={stride}")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    B, H, W, C = x.shape
    if H // window < 1 or W // window < 1:
        raise ValueError(
            f"input {H}x{W} smaller than the {window}x{window} window")
    interpret = resolve_interpret(interpret)
    return _pool((int(window), bool(interpret)), x)
