"""Pallas TPU conv2d — the paper's inner-layer hot spot, now differentiable.

TPU adaptation of the paper's per-output-element task decomposition
(Eq. 13-14): the ``pallas_call`` grid cell *is* the paper's "task" — one
(batch, output-channel-tile) block — and the BlockSpec is the task
granularity.  Instead of scalar element tasks (GPU/CPU-friendly) each task
computes kh*kw shifted (H*W, Cin) x (Cin, Cout_tile) matmuls, the MXU-native
im2col form of Eq. (1).

Three kernels cover one training step of the layer (§4.1):

* ``_conv_fwd_kernel`` — Eq. (1) convolution with the Eq. (2) bias +
  activation epilogue fused in, so the layer forward is ONE ``pallas_call``
  (the paper's PT_Conv task list).
* ``_conv_dx_kernel`` — input gradient: the transposed convolution expressed
  as a VALID correlation of the padded cotangent with the spatially flipped,
  channel-transposed filters, over the same (batch, channel-tile) grid.
* ``_conv_dw_kernel`` — weight gradient: grid cells are the paper's
  per-filter gradient tasks G_Conv (§4.1.2); each cell contracts the padded
  input against the cotangent over (batch, H, W) for one filter tile.

``conv2d_pallas`` ties them together with ``jax.custom_vjp`` so
``jax.grad`` through the Pallas path trains the CNN end-to-end (Eq. 17-23)
without ever falling back to the jnp reference.

Layout: x NHWC, w HWIO, out NHWC.  Stride 1 (the paper's CNNs pool instead
of striding).  ``interpret=None`` resolves via ``kernels.ops._interpret()``
— interpret mode off TPU, compiled on TPU — so callers cannot accidentally
ship interpret-mode kernels to real hardware.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import LANE, resolve_interpret

__all__ = ["conv2d_pallas"]

_ACTIVATIONS = ("none", "relu")


def _same_pads(kh: int, kw: int) -> tuple[int, int]:
    return (kh - 1) // 2, (kw - 1) // 2


# ----------------------------------------------------------------------
# Kernels
# ----------------------------------------------------------------------
def _im2col_accum(in_ref, w_ref, *, kh: int, kw: int, H: int, W: int):
    """The shared task body: kh*kw shifted (H*W, Cin) x (Cin, Ct) matmuls.

    in (1, H+kh-1, W+kw-1, Cin); w (kh, kw, Cin, Ct) -> f32 (H*W, Ct).
    Forward and input-gradient kernels are both this loop — the dx pass
    just feeds the padded cotangent and flipped/transposed filters.
    """
    cin = in_ref.shape[-1]
    ct = w_ref.shape[-1]
    acc = jnp.zeros((H * W, ct), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            patch = in_ref[0, i:i + H, j:j + W, :].reshape(H * W, cin)
            wmat = w_ref[i, j, :, :]
            acc += jnp.dot(patch, wmat, preferred_element_type=jnp.float32)
    return acc


def _conv_fwd_kernel(x_ref, w_ref, b_ref, o_ref, *, kh: int, kw: int,
                     H: int, W: int, activation: str):
    """One PT_Conv task: conv + fused bias/activation epilogue (Eq. 1+2).

    x (1, H+kh-1, W+kw-1, Cin); w (kh,kw,Cin,Ct); b (1,Ct); o (1,H,W,Ct).
    """
    ct = o_ref.shape[-1]
    acc = _im2col_accum(x_ref, w_ref, kh=kh, kw=kw, H=H, W=W)
    acc += b_ref[0, :].astype(jnp.float32)
    if activation == "relu":
        acc = jnp.maximum(acc, 0.0)
    o_ref[0, :, :, :] = acc.reshape(H, W, ct).astype(o_ref.dtype)


def _conv_dx_kernel(g_ref, w_ref, o_ref, *, kh: int, kw: int,
                    H: int, W: int):
    """Input-gradient task: the same im2col body, no epilogue.

    g (1, H+kh-1, W+kw-1, Cout) — pre-padded cotangent; w here is the
    flipped filter (kh,kw,Cout,Ct_in); o (1,H,W,Ct_in).
    """
    ct = o_ref.shape[-1]
    acc = _im2col_accum(g_ref, w_ref, kh=kh, kw=kw, H=H, W=W)
    o_ref[0, :, :, :] = acc.reshape(H, W, ct).astype(o_ref.dtype)


def _conv_dw_kernel(x_ref, g_ref, o_ref, *, kh: int, kw: int,
                    H: int, W: int):
    """One G_Conv task (§4.1.2): the weight gradient for one filter tile.

    x (Bt, H+kh-1, W+kw-1, Cin); g (Bt, H, W, Ct); o (kh, kw, Cin, Ct).
    The batch is tiled along the *sequential* innermost grid axis so one
    cell only holds a Bt-slice in VMEM; the output block is revisited
    across that axis and accumulated (zeroed at the first batch tile).
    Each visit contracts over (Bt, H, W) with kh*kw (Cin, BtHW) x
    (BtHW, Ct) matmuls.
    """
    bi = pl.program_id(1)
    Bt = x_ref.shape[0]
    cin = x_ref.shape[-1]
    ct = g_ref.shape[-1]

    @pl.when(bi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[...].reshape(Bt * H * W, ct)
    for i in range(kh):
        for j in range(kw):
            patch = x_ref[:, i:i + H, j:j + W, :].reshape(Bt * H * W, cin)
            o_ref[i, j, :, :] += jax.lax.dot_general(
                patch, g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(o_ref.dtype)


# ----------------------------------------------------------------------
# pallas_call wrappers
# ----------------------------------------------------------------------
def _channel_tile(channels: int, oc_tile: int) -> int:
    """Derive a tile over a *different* channel axis than the one the
    caller sized ``oc_tile`` for (the dx grid tiles Cin with a knob chosen
    for Cout): reuse it when it divides, otherwise fall back to one task
    per image.  The primary axis validates strictly in ``conv2d_pallas``.
    """
    if oc_tile and channels % oc_tile == 0:
        return oc_tile
    return channels


def _forward(x, w, b, *, padding: str, activation: str, oc_tile: int,
             interpret: bool):
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    if padding == "SAME":
        ph, pw = _same_pads(kh, kw)
        xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw),
                         (0, 0)))
    elif padding == "VALID":
        xp = x
        H, W = H - kh + 1, W - kw + 1
    else:
        raise ValueError(padding)
    ct = oc_tile or Cout
    grid = (B, Cout // ct)

    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, kh=kh, kw=kw, H=H, W=W,
                          activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H + kh - 1, W + kw - 1, Cin),
                         lambda bi, c: (bi, 0, 0, 0)),
            pl.BlockSpec((kh, kw, Cin, ct), lambda bi, c: (0, 0, 0, c)),
            pl.BlockSpec((1, ct), lambda bi, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((1, H, W, ct), lambda bi, c: (bi, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, Cout), x.dtype),
        interpret=interpret,
    )(xp, w, b.reshape(1, Cout))


def _backward_dx(g, w, x_shape, out_dtype, *, padding: str, oc_tile: int,
                 interpret: bool):
    """dL/dx: VALID correlation of the padded cotangent with flip(w)^T.

    For SAME the cotangent padding mirrors the forward pads
    ((kh-1-ph, ph) vs the forward's (ph, kh-1-ph)); for VALID it is the
    full (kh-1)-halo — both make the output exactly ``x_shape``.
    """
    B, H, W, Cin = x_shape
    kh, kw, _, Cout = w.shape
    wf = w[::-1, ::-1].transpose(0, 1, 3, 2)       # (kh, kw, Cout, Cin)
    if padding == "SAME":
        ph, pw = _same_pads(kh, kw)
        gp = jnp.pad(g, ((0, 0), (kh - 1 - ph, ph), (kw - 1 - pw, pw),
                         (0, 0)))
    else:                                          # VALID
        gp = jnp.pad(g, ((0, 0), (kh - 1, kh - 1), (kw - 1, kw - 1),
                         (0, 0)))
    ct = _channel_tile(Cin, oc_tile)
    grid = (B, Cin // ct)

    return pl.pallas_call(
        functools.partial(_conv_dx_kernel, kh=kh, kw=kw, H=H, W=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H + kh - 1, W + kw - 1, Cout),
                         lambda bi, c: (bi, 0, 0, 0)),
            pl.BlockSpec((kh, kw, Cout, ct), lambda bi, c: (0, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, H, W, ct), lambda bi, c: (bi, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, Cin), out_dtype),
        interpret=interpret,
    )(gp, wf)


# VMEM the dw kernel's batch slice may take per grid cell: the
# double-buffered input and cotangent blocks plus the kernel's 2-D
# (Bt*H*W, C) operands.
_DW_VMEM_BUDGET = 8 * 2**20


def _vmem_bytes(shape, itemsize: int) -> int:
    """Bytes of one VMEM buffer: its minor two axes pad to (8, LANE)."""
    *lead, sub, lane = shape
    return (math.prod(lead) * (-(-sub // 8) * 8)
            * (-(-lane // LANE) * LANE) * itemsize)


def _dw_batch_tile(B: int, in_block, g_block, itemsize: int) -> int:
    """The largest divisor of B whose dw cell fits ``_DW_VMEM_BUDGET``.

    ``in_block``/``g_block`` are one image's input and cotangent block
    shapes.  A minor axis of 3 or 12 channels pads to a full LANE, so
    early layers take few images per cell and deep, small maps many.
    Never below one image.
    """
    cin = in_block[-1]
    H, W, ct = g_block
    per_image = (2 * (_vmem_bytes(in_block, itemsize)
                      + _vmem_bytes(g_block, itemsize))
                 + _vmem_bytes((H * W, cin), itemsize)
                 + _vmem_bytes((H * W, ct), itemsize))
    fit = max(1, _DW_VMEM_BUDGET // per_image)
    return max(d for d in range(1, min(B, fit) + 1) if B % d == 0)


def _backward_dw(x, g, w_shape, *, padding: str, oc_tile: int,
                 interpret: bool):
    """dL/dw over the per-filter G_Conv grid.

    Grid (Cout/oc_tile, B/Bt): one output block per filter tile, revisited
    along the sequential batch axis so VMEM holds a Bt-image slice
    (``_dw_batch_tile``) instead of the whole batch.
    """
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w_shape
    if padding == "SAME":
        ph, pw = _same_pads(kh, kw)
        xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw),
                         (0, 0)))
        Ho, Wo = H, W
    else:                                          # VALID
        xp = x
        Ho, Wo = H - kh + 1, W - kw + 1
    ct = oc_tile or Cout
    bt = _dw_batch_tile(B, (Ho + kh - 1, Wo + kw - 1, Cin), (Ho, Wo, ct),
                        x.dtype.itemsize)

    return pl.pallas_call(
        functools.partial(_conv_dw_kernel, kh=kh, kw=kw, H=Ho, W=Wo),
        grid=(Cout // ct, B // bt),
        in_specs=[
            pl.BlockSpec((bt, Ho + kh - 1, Wo + kw - 1, Cin),
                         lambda c, bi: (bi, 0, 0, 0)),
            pl.BlockSpec((bt, Ho, Wo, ct), lambda c, bi: (bi, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((kh, kw, Cin, ct),
                               lambda c, bi: (0, 0, 0, c)),
        # f32 output: the cross-batch-tile accumulation lives in this
        # buffer, so it must not round through the input dtype
        out_shape=jax.ShapeDtypeStruct((kh, kw, Cin, Cout), jnp.float32),
        interpret=interpret,
    )(xp, g)


# ----------------------------------------------------------------------
# custom_vjp wiring
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv2d(cfg, x, w, b):
    padding, activation, oc_tile, interpret = cfg
    return _forward(x, w, b, padding=padding, activation=activation,
                    oc_tile=oc_tile, interpret=interpret)


def _conv2d_fwd(cfg, x, w, b):
    out = _conv2d(cfg, x, w, b)
    # The post-activation output doubles as the relu mask (out > 0 iff the
    # pre-activation was > 0), so no pre-activation residual is needed.
    return out, (x, w, b, out)


def _conv2d_bwd(cfg, residuals, g):
    padding, activation, oc_tile, interpret = cfg
    x, w, b, out = residuals
    if activation == "relu":
        g = g * (out > 0).astype(g.dtype)
    # No f32 input casts: the kernels accumulate in f32 internally
    # (preferred_element_type / f32 dw output), so bf16 models keep bf16
    # memory traffic through the backward pass.
    dx = _backward_dx(g, w, x.shape, x.dtype, padding=padding,
                      oc_tile=oc_tile, interpret=interpret)
    dw = _backward_dw(x, g, w.shape, padding=padding,
                      oc_tile=oc_tile, interpret=interpret).astype(w.dtype)
    db = jnp.sum(g, axis=(0, 1, 2), dtype=jnp.float32).astype(b.dtype)
    return dx, dw, db


_conv2d.defvjp(_conv2d_fwd, _conv2d_bwd)


# ----------------------------------------------------------------------
# Public entry point
# ----------------------------------------------------------------------
def conv2d_pallas(x, w, b=None, *, padding: str = "SAME",
                  activation: str = "none", oc_tile: int = 0,
                  interpret: bool | None = None):
    """Differentiable fused conv2d: (B,H,W,Cin) x (kh,kw,Cin,Cout) -> NHWC.

    ``b`` (Cout,) and ``activation`` fuse the Eq. (2) epilogue into the
    forward kernel; ``jax.grad`` runs the two backward Pallas kernels via
    ``custom_vjp``.  ``oc_tile`` is the output-channel tile (0 = all
    channels in one task); the grid (B, Cout/oc_tile) is the paper's
    parallel task list PT_Conv.  ``interpret=None`` resolves via
    ``kernels.ops._interpret()`` (compiled only on TPU).
    """
    if activation not in _ACTIVATIONS:
        raise ValueError(f"activation must be one of {_ACTIVATIONS}")
    if padding not in ("SAME", "VALID"):
        raise ValueError(padding)
    if oc_tile and w.shape[-1] % oc_tile:
        raise ValueError(
            f"oc_tile {oc_tile} must divide Cout {w.shape[-1]} "
            "(0 = one task per image)")
    interpret = resolve_interpret(interpret)
    if b is None:
        b = jnp.zeros((w.shape[-1],), x.dtype)
    cfg = (padding, activation, int(oc_tile), bool(interpret))
    return _conv2d(cfg, x, w, b)
