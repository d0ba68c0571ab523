"""Pallas TPU fused dense layer — the paper's §4.1.2 FC task lists.

The full-connection layer's training step decomposes into per-neuron-block
tasks: the forward (Eq. 19 local response + Eq. 2 bias/activation epilogue
fused into ONE ``pallas_call``) and the backward per-block weight-gradient
tasks G_FC (Eq. 20-21).  The block size is the task granularity, chosen
by the same Alg. 4.2 cost model as the conv tile
(``core.dag.choose_fc_block``), and it is the output-column tile of every
grid below.

All three passes run one tiled matmul kernel (``_matmul_kernel``) over a
``(rows/tm, cols/tn, K/tk)`` grid, reading every operand where it lies
in HBM — no padded, transposed or sliced copy of a weight is staged
around the call:

* forward — ``x @ w`` with the bias + activation epilogue fused into the
  last K step; ``tn`` is the neuron block.
* input gradient — ``g @ w^T``: ``w`` is read in its own (Din, Dout)
  layout and contracted on its Dout axis in the kernel, ``tn`` tiling
  Din (``_block_of``).
* G_FC weight gradient — ``x^T @ g`` per neuron block (Eq. 21's sum over
  samples is the contraction); the Eq. 20 bias gradient is the
  cotangent's batch sum.  ``x^T`` is the one operand XLA transposes: an
  activation (B, Din), a B/Dout share of a weight's bytes, where the
  MXU would pay a transpose of every (tk, tm) block in the kernel.

The row and K axes keep every cell inside VMEM whatever the layer width:
an axis up to its cap is one whole-axis block, a wider one is tiled.  A
row or column axis that its tile does not divide ends in a ragged edge
block: ``pl.cdiv`` blocks cover it, and the edge block's rows/columns
past the array are never stored.  The contraction axis must not carry
such garbage into the sums: a wide K that is a lane multiple takes a
dividing lane tile; otherwise the whole K is one block when its panels
fit ``_K_PANEL_BUDGET``, and only past that is the last K block ragged,
its out-of-bounds part selected to zero in the kernel.  Every block is
TPU-legal: sublane tiles are multiples of 8 (16 below 32 bits), lane
tiles multiples of ``LANE`` — or the whole axis.

``dense_pallas`` ties the passes together with ``jax.custom_vjp`` so
``jax.grad`` through the Pallas path trains the FC stack end-to-end
(Eq. 19-21) without falling back to the jnp reference.

Layout: x (B, Din), w (Din, Dout), b (Dout,) — callers with leading batch
dims flatten through ``ops.dense``.  ``interpret=None`` resolves via
``kernels.ops._interpret()`` — interpret mode off TPU, compiled on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANE, MAX_TILE, resolve_interpret

__all__ = ["dense_pallas"]

_ACTIVATIONS = ("none", "relu")

# Row-tile cap.  Every row tile re-reads the whole weight panel from HBM,
# so the cap sets how often a (Din, Dout) weight is read per call.  The
# largest tiled cell — a (512, MAX_TILE) row tile, a (MAX_TILE, MAX_TILE)
# weight tile, both double-buffered, plus the output tile and its f32
# accumulator — is 7 MiB in f32, inside the TPU's default scoped VMEM
# limit (16 MiB on v5e).
_MAX_ROWS = 512
# VMEM the two operand panels of a whole-K cell may take, double-buffered:
# case 7's K=2000 with a 64-row tile and a 256-neuron block takes 5.1 MB.
_K_PANEL_BUDGET = 8 * 2**20


# ----------------------------------------------------------------------
# Kernel
# ----------------------------------------------------------------------
def _matmul_kernel(*refs, activation: str, has_bias: bool, trans_b: bool,
                   k_size: int):
    """One (tm, tn) output tile, accumulated over the sequential K axis.

    a (tm, tk); b (tk, tn), or (tn, tk) with ``trans_b`` — contracted on
    its minor axis, which the MXU takes as it is; [bias (1, tn)];
    o (tm, tn); acc f32 (tm, tn).
    ``k_size`` is K when the last K block is ragged (0 otherwise): that
    block's out-of-bounds part is unspecified, maybe NaN, so it is
    selected away, not multiplied by zero.  The bias + activation
    epilogue runs once, at the last K step.
    """
    if has_bias:
        a_ref, b_ref, bias_ref, o_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kb = 1 if trans_b else 0
    a, b = a_ref[...], b_ref[...]
    if k_size:
        left = k_size - k * a.shape[1]
        a = jnp.where(jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) < left,
                      a, jnp.zeros_like(a))
        b = jnp.where(jax.lax.broadcasted_iota(jnp.int32, b.shape, kb) < left,
                      b, jnp.zeros_like(b))
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (kb,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_bias:
            acc += bias_ref[...].astype(jnp.float32)
        if activation == "relu":
            acc = jnp.maximum(acc, 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


# ----------------------------------------------------------------------
# tiling
# ----------------------------------------------------------------------
def _tile(dim: int, cap: int, unit: int) -> int:
    """The block of one matmul axis.

    The whole axis when it fits ``cap`` (a full-axis block is always
    legal); otherwise the largest multiple of ``unit`` up to ``cap`` that
    divides the axis rounded up to ``unit`` — ``pl.cdiv(dim, tile)``
    blocks then cover it, the last one ragged when ``unit`` does not
    divide the axis.
    """
    if dim <= cap:
        return dim
    padded = -(-dim // unit) * unit
    return max(t for t in range(unit, cap + 1, unit) if padded % t == 0)


def _k_tile(K: int, panel_bytes: int) -> int:
    """The contraction block: ``_tile``'s lane tile where it divides K;
    else the whole K when its panels (``panel_bytes`` per unit of K)
    fit ``_K_PANEL_BUDGET``; else ``MAX_TILE``, its ragged tail masked
    in the kernel."""
    if K <= MAX_TILE or K % LANE == 0:
        return _tile(K, MAX_TILE, LANE)
    if K * panel_bytes <= _K_PANEL_BUDGET:
        return K
    return MAX_TILE


def _block_of(features: int, block: int) -> int:
    """Derive a lane tile over a *different* feature axis than the one
    the caller sized ``block`` for (the dx grid tiles Din with a knob
    chosen for Dout): reuse it when it is a lane multiple that fits the
    axis, else tile the axis on its own (``_tile``).  Either way the
    result is TPU-legal: a multiple of ``LANE`` or the whole axis.
    """
    if block and block % LANE == 0 and block <= features:
        return block
    return _tile(features, MAX_TILE, LANE)


def _matmul(a, b, bias=None, *, tn: int, out_dtype, activation="none",
            trans_b: bool = False, interpret: bool):
    """(M, K) x (K, N) [+ bias, activation] through ``_matmul_kernel``.

    ``b`` is (K, N), or (N, K) with ``trans_b``: read in its own layout
    and contracted on its minor axis in the kernel.  ``tn`` is the
    output-column (task) tile: a multiple of ``LANE`` (the last block may
    be ragged) or a divisor of N.
    """
    M, K = a.shape
    N = b.shape[0] if trans_b else b.shape[1]
    tm = _tile(M, _MAX_ROWS, 8 * max(1, 4 // a.dtype.itemsize))
    tk = _k_tile(K, 2 * (tm * a.dtype.itemsize + tn * b.dtype.itemsize))
    in_specs = [
        pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
        pl.BlockSpec((tn, tk), lambda i, j, k: (j, k)) if trans_b
        else pl.BlockSpec((tk, tn), lambda i, j, k: (k, j))]
    args = [a, b]
    if bias is not None:
        args.append(bias.reshape(1, N))
        in_specs.append(pl.BlockSpec((1, tn), lambda i, j, k: (0, j)))
    return pl.pallas_call(
        functools.partial(_matmul_kernel, activation=activation,
                          has_bias=bias is not None, trans_b=trans_b,
                          k_size=K if K % tk else 0),
        grid=(pl.cdiv(M, tm), pl.cdiv(N, tn), pl.cdiv(K, tk)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dense_matmul",
    )(*args)


# ----------------------------------------------------------------------
# custom_vjp wiring
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dense(cfg, x, w, b):
    activation, block, interpret = cfg
    return _matmul(x, w, b, tn=block or w.shape[-1], out_dtype=x.dtype,
                   activation=activation, interpret=interpret)


def _dense_fwd(cfg, x, w, b):
    out = _dense(cfg, x, w, b)
    # The post-activation output doubles as the relu mask (out > 0 iff the
    # pre-activation was > 0), so no pre-activation residual is needed.
    return out, (x, w, b, out)


def _dense_bwd(cfg, residuals, g):
    activation, block, interpret = cfg
    x, w, b, out = residuals
    if activation == "relu":
        g = g * (out > 0).astype(g.dtype)
    dx = _matmul(g, w, tn=_block_of(w.shape[0], block), out_dtype=x.dtype,
                 trans_b=True, interpret=interpret)
    # f32 gradients: rounded to the param dtypes below
    dw = _matmul(x.T, g, tn=block or w.shape[-1], out_dtype=jnp.float32,
                 interpret=interpret)
    db = jnp.sum(g, axis=0, dtype=jnp.float32)
    return dx, dw.astype(w.dtype), db.astype(b.dtype)


_dense.defvjp(_dense_fwd, _dense_bwd)


# ----------------------------------------------------------------------
# Public entry point
# ----------------------------------------------------------------------
def dense_pallas(x, w, b=None, *, activation: str = "none", block: int = 0,
                 interpret: bool | None = None):
    """Differentiable fused dense: (B, Din) x (Din, Dout) -> (B, Dout).

    ``b`` (Dout,) and ``activation`` fuse the Eq. (2) epilogue into the
    forward kernel; ``jax.grad`` runs the two backward matmuls via
    ``custom_vjp`` (the §4.1.2 per-block G_FC gradient tasks).  ``block``
    is the output-neuron block (0 = all neurons in one task): a divisor
    of Dout, or a multiple of ``LANE`` (the last block may be ragged:
    its columns past Dout are never stored).
    Compiled kernels need a TPU-legal block — a multiple of ``LANE`` or
    all of Dout — which is all ``core.dag.choose_fc_block`` offers.
    ``interpret=None`` resolves via ``kernels.ops._interpret()``
    (compiled only on TPU).
    """
    if activation not in _ACTIVATIONS:
        raise ValueError(f"activation must be one of {_ACTIVATIONS}")
    if x.ndim != 2 or w.ndim != 2:
        raise ValueError(
            f"dense_pallas takes 2-D x and w, got {x.shape} x {w.shape} "
            "(flatten leading dims through ops.dense)")
    if block and w.shape[-1] % block and block % LANE:
        raise ValueError(
            f"block {block} must divide Dout {w.shape[-1]} or be a "
            f"multiple of {LANE} (0 = one task for the whole layer)")
    interpret = resolve_interpret(interpret)
    if b is None:
        b = jnp.zeros((w.shape[-1],), x.dtype)
    cfg = (activation, int(block), bool(interpret))
    return _dense(cfg, x, w, b)
