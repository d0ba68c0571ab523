"""Pallas TPU depthwise conv2d — ConvNeXt's 7x7 spatial mixing.

A depthwise convolution filters each channel with its own kh x kw filter:
``out[b, h, w, c] = bias[c] + sum_ij xpad[b, h+i, w+j, c] * w[i, j, c]``.
There is no contraction over channels, so the MXU has nothing to do: the
work is kh*kw shifted multiply-adds per output element on the vector
unit, and the bytes are whole activation planes read and written once.

The grid has one cell per (image, channel block).  The channel axis rides
the TPU's lanes; the block is all of C where the cell's planes fit
``_VMEM_BUDGET`` (every ConvNeXt-T stage, 96 to 768 channels), else the
widest lane multiple that divides C and fits.  Each cell copies its
unpadded image into a zero-bordered VMEM plane (SAME padding happens in
VMEM, never as an XLA pad in HBM) and sweeps the output rows, each row a
register accumulator of kh*kw shifted plane rows times the filter taps.

Three kernels cover one training step of the layer:

* ``dwconv_fwd`` — the convolution with the bias fused in.
* ``dwconv_dx`` — input gradient.  A depthwise filter maps channel c to
  channel c alone, so the transposed convolution is the same kernel on
  the cotangent with the spatially flipped filter (and the mirrored SAME
  pads, which differ only for an even filter size).
* ``dwconv_dw`` — weight gradient: ``dw[i, j, c] = sum_{b,h,w}
  xpad[b, h+i, w+j, c] * g[b, h, w, c]``, a per-channel reduction.  The
  grid's image axis is sequential: the (kh, kw, block) f32 output stays
  in VMEM and accumulates across images.

``dwconv2d_pallas`` ties them together with ``jax.custom_vjp``.

Layout: x NHWC, w (kh, kw, C), b (C,), SAME padding, stride 1 — other
strides and paddings go through ``ops.depthwise_conv2d``'s fallback
contract.  ``interpret=None`` resolves via ``kernels.ops._interpret()``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANE, resolve_interpret
from repro.kernels.conv2d import _same_pads, _vmem_bytes

__all__ = ["dwconv2d_pallas", "channel_block"]

# VMEM one grid cell's buffers may take: the double-buffered image and
# output (or cotangent) blocks plus the padded f32 plane.  ConvNeXt-T's
# widest cell (56x56x96 at stage 1) takes 8.4 MB, inside v5e's 16 MiB
# default scoped limit.
_VMEM_BUDGET = 12 * 2**20


def channel_block(H: int, W: int, C: int, kh: int, kw: int,
                  itemsize: int = 4) -> int:
    """The channel block of a cell: all of C where the cell fits
    ``_VMEM_BUDGET``, else the widest multiple of ``LANE`` dividing C that
    does (a block must be a lane multiple or the whole axis)."""
    def cell(ct):
        return (4 * _vmem_bytes((H, W, ct), itemsize)
                + _vmem_bytes((H + kh - 1, W + kw - 1, ct), 4))
    if cell(C) <= _VMEM_BUDGET or C % LANE:
        return C
    fits = [ct for ct in range(LANE, C, LANE)
            if C % ct == 0 and cell(ct) <= _VMEM_BUDGET]
    return max(fits) if fits else LANE


# ----------------------------------------------------------------------
# Kernels
# ----------------------------------------------------------------------
def _fill_plane(src_ref, plane_ref, top: int, left: int):
    """plane <- the image block src (1, H, W, ct) at (top, left), in f32,
    with a zero border (every cell writes its own border: cells may run
    in any order, or on another core)."""
    _, H, W, _ = src_ref.shape
    Hp, Wp, ct = plane_ref.shape
    bottom, right = Hp - top - H, Wp - left - W

    def zeros(h, w):
        return jnp.zeros((h, w, ct), jnp.float32)
    if top:
        plane_ref[:top] = zeros(top, Wp)
    if bottom:
        plane_ref[top + H:] = zeros(bottom, Wp)
    if left:
        plane_ref[top:top + H, :left] = zeros(H, left)
    if right:
        plane_ref[top:top + H, left + W:] = zeros(H, right)
    plane_ref[top:top + H, left:left + W] = src_ref[0].astype(jnp.float32)


def _conv_kernel(*refs, kh: int, kw: int, top: int, left: int,
                 has_bias: bool):
    """One (image, channel block) cell of the forward or of dx.

    src (1, H, W, ct); w (kh, kw, ct); [b (1, ct)]; o (1, H, W, ct);
    plane (H+kh-1, W+kw-1, ct) f32 scratch.  Each output row is a (W, ct)
    register accumulator of kh*kw shifted plane rows times their taps.
    """
    if has_bias:
        src_ref, w_ref, b_ref, o_ref, plane_ref = refs
    else:
        src_ref, w_ref, o_ref, plane_ref = refs
    _, H, W, ct = o_ref.shape
    _fill_plane(src_ref, plane_ref, top, left)
    init = (jnp.broadcast_to(b_ref[...].astype(jnp.float32), (W, ct))
            if has_bias else jnp.zeros((W, ct), jnp.float32))

    def row(h, carry):
        def filter_row(i, acc):
            for j in range(kw):
                tap = w_ref[i, j:j + 1, :].astype(jnp.float32)
                acc = acc + plane_ref[h + i, j:j + W, :] * tap
            return acc
        acc = jax.lax.fori_loop(0, kh, filter_row, init)
        o_ref[0, h] = acc.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, H, row, 0)


def _dw_kernel(x_ref, g_ref, o_ref, plane_ref, *, kh: int, kw: int,
               top: int, left: int):
    """One (channel block, image) cell of the weight gradient.

    x (1, H, W, ct); g (1, H, W, ct); o (kh, kw, ct) f32, revisited along
    the sequential image axis and accumulated (zeroed at the first
    image); plane as in ``_conv_kernel``.  Each tap sums
    ``plane[h+i, j:j+W] * g[h]`` over the rows in a (W, ct) register
    accumulator, then over W.
    """
    _, H, W, ct = g_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _fill_plane(x_ref, plane_ref, top, left)

    def filter_row(i, carry):
        for j in range(kw):
            def row(h, acc, j=j):
                return acc + (plane_ref[h + i, j:j + W, :]
                              * g_ref[0, h].astype(jnp.float32))
            acc = jax.lax.fori_loop(0, H, row,
                                    jnp.zeros((W, ct), jnp.float32))
            o_ref[i, j:j + 1, :] += jnp.sum(acc, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, kh, filter_row, 0)


# ----------------------------------------------------------------------
# pallas_call wrappers
# ----------------------------------------------------------------------
def _conv(src, w, b, *, pads, ct: int, name: str, interpret: bool):
    """The forward (with ``b``) or dx (``b`` None) pass over the
    (image, channel block) grid; ``pads`` is the (top, left) SAME pad."""
    B, H, W, C = src.shape
    kh, kw, _ = w.shape
    img = pl.BlockSpec((1, H, W, ct), lambda bi, c: (bi, 0, 0, c))
    in_specs = [img, pl.BlockSpec((kh, kw, ct), lambda bi, c: (0, 0, c))]
    args = [src, w]
    if b is not None:
        in_specs.append(pl.BlockSpec((1, ct), lambda bi, c: (0, c)))
        args.append(b.reshape(1, C))
    return pl.pallas_call(
        functools.partial(_conv_kernel, kh=kh, kw=kw, top=pads[0],
                          left=pads[1], has_bias=b is not None),
        grid=(B, C // ct),
        in_specs=in_specs,
        out_specs=img,
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), src.dtype),
        scratch_shapes=[pltpu.VMEM((H + kh - 1, W + kw - 1, ct),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(*args)


def _weight_grad(x, g, w_shape, *, ct: int, interpret: bool):
    """dL/dw over the (channel block, image) grid, in f32."""
    B, H, W, C = x.shape
    kh, kw, _ = w_shape
    top, left = _same_pads(kh, kw)
    img = pl.BlockSpec((1, H, W, ct), lambda c, bi: (bi, 0, 0, c))
    return pl.pallas_call(
        functools.partial(_dw_kernel, kh=kh, kw=kw, top=top, left=left),
        grid=(C // ct, B),
        in_specs=[img, img],
        out_specs=pl.BlockSpec((kh, kw, ct), lambda c, bi: (0, 0, c)),
        # f32 output: the cross-image accumulation lives in this buffer
        out_shape=jax.ShapeDtypeStruct((kh, kw, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((H + kh - 1, W + kw - 1, ct),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dwconv_dw",
    )(x, g)


# ----------------------------------------------------------------------
# custom_vjp wiring
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dwconv2d(cfg, x, w, b):
    ct, interpret = cfg
    return _conv(x, w, b, pads=_same_pads(*w.shape[:2]), ct=ct,
                 name="dwconv_fwd", interpret=interpret)


def _dwconv2d_fwd(cfg, x, w, b):
    return _dwconv2d(cfg, x, w, b), (x, w, b)


def _dwconv2d_bwd(cfg, residuals, g):
    ct, interpret = cfg
    x, w, b = residuals
    kh, kw, _ = w.shape
    ph, pw = _same_pads(kh, kw)
    # the transposed depthwise conv: flipped taps, mirrored pads
    dx = _conv(g, w[::-1, ::-1], None, pads=(kh - 1 - ph, kw - 1 - pw),
               ct=ct, name="dwconv_dx", interpret=interpret)
    dw = _weight_grad(x, g, w.shape, ct=ct, interpret=interpret)
    db = jnp.sum(g, axis=(0, 1, 2), dtype=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


_dwconv2d.defvjp(_dwconv2d_fwd, _dwconv2d_bwd)


# ----------------------------------------------------------------------
# Public entry point
# ----------------------------------------------------------------------
def dwconv2d_pallas(x, w, b=None, *, interpret: bool | None = None):
    """Differentiable depthwise conv, SAME, stride 1:
    (B, H, W, C) x (kh, kw, C) [+ b (C,)] -> (B, H, W, C), over the
    channel blocks ``channel_block`` picks.  ``jax.grad`` runs the dx and
    dw kernels via ``custom_vjp``.  ``interpret=None`` resolves via
    ``kernels.ops._interpret()`` (compiled only on TPU).
    """
    if x.ndim != 4 or w.ndim != 3 or w.shape[-1] != x.shape[-1]:
        raise ValueError(f"dwconv2d_pallas takes (B,H,W,C) x (kh,kw,C), "
                         f"got {x.shape} x {w.shape}")
    B, H, W, C = x.shape
    kh, kw, _ = w.shape
    ct = channel_block(H, W, C, kh, kw, x.dtype.itemsize)
    if b is None:
        b = jnp.zeros((C,), w.dtype)
    cfg = (int(ct), bool(resolve_interpret(interpret)))
    return _dwconv2d(cfg, x, w, b)
