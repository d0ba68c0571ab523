"""Pallas TPU fused dense layer — the paper's §4.1.2 FC task lists.

The full-connection layer's training step decomposes into per-neuron-block
tasks: the forward (Eq. 19 local response + Eq. 2 bias/activation epilogue
fused into ONE ``pallas_call``) and the backward per-block weight-gradient
tasks G_FC (Eq. 20-21).  The block size is the task granularity, chosen
by the same Alg. 4.2 cost model as the conv tile
(``core.dag.choose_fc_block``), and it is the output-column tile of every
grid below.

All three passes of the layer run one tiled matmul kernel
(``_matmul_kernel``) over a ``(rows/tm, cols/tn, K/tk)`` grid:

* forward — ``x @ w`` with the bias + activation epilogue fused into the
  last K step; ``tn`` is the neuron block.
* input gradient — ``g @ w^T``, its ``tn`` tiling Din (``_block_of``).
* G_FC weight gradient — ``x^T @ g`` per neuron block (Eq. 21's sum over
  samples is the contraction); the Eq. 20 bias gradient is the
  cotangent's batch sum.

The row and K axes keep every cell inside VMEM whatever the layer width:
an axis up to its cap is one whole-axis block, a wider one is tiled and
zero-padded up to a whole number of tiles (zero rows/columns contribute
nothing to a matmul, and the padding is sliced off the result).  Every
block is TPU-legal: sublane tiles are multiples of 8 (16 below 32 bits),
lane tiles multiples of ``LANE`` — or the whole axis.

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

# Row-tile cap.  The largest cell — a (256, MAX_TILE) row tile, a
# (MAX_TILE, MAX_TILE) weight tile, double-buffered, plus the output tile
# and its f32 accumulator — is ~5 MiB in f32, well inside the TPU's
# default scoped VMEM limit (16 MiB on v5e).
_MAX_ROWS = 256


# ----------------------------------------------------------------------
# Kernel
# ----------------------------------------------------------------------
def _matmul_kernel(*refs, activation: str, has_bias: bool):
    """One (tm, tn) output tile, accumulated over the sequential K axis.

    a (tm, tk); b (tk, tn); [bias (1, tn)]; o (tm, tn); acc f32 (tm, tn).
    The bias + activation epilogue runs once, at the last K step.
    """
    if has_bias:
        a_ref, b_ref, bias_ref, o_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

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
def _round_up(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def _tile(dim: int, cap: int, unit: int) -> tuple[int, int]:
    """(tile, padded extent) for one matmul axis.

    The whole axis when it fits ``cap`` (a full-axis block is always
    legal); otherwise the largest multiple of ``unit`` up to ``cap`` that
    divides the axis once padded up to ``unit``.
    """
    if dim <= cap:
        return dim, dim
    padded = _round_up(dim, unit)
    tile = max(t for t in range(unit, cap + 1, unit) if padded % t == 0)
    return tile, padded


def _block_of(features: int, block: int) -> int:
    """Derive a lane tile over a *different* feature axis than the one
    the caller sized ``block`` for (the dx grid tiles Din with a knob
    chosen for Dout): reuse it when it is a lane multiple that fits the
    axis, else tile the axis on its own (``_tile``).  Either way the
    result is TPU-legal: a multiple of ``LANE`` or the whole axis.
    """
    if block and block % LANE == 0 and block <= features:
        return block
    return _tile(features, MAX_TILE, LANE)[0]


def _pad2(a, rows: int, cols: int):
    if a.shape == (rows, cols):
        return a
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


def _matmul(a, b, bias=None, *, tn: int, out_dtype, activation="none",
            interpret: bool):
    """(M, K) x (K, N) [+ bias, activation] through ``_matmul_kernel``.

    ``tn`` is the output-column (task) tile: a multiple of ``LANE``
    (N pads up to it) or a divisor of N.
    """
    M, K = a.shape
    N = b.shape[1]
    sublane = 8 * max(1, 4 // a.dtype.itemsize)
    tm, Mp = _tile(M, _MAX_ROWS, sublane)
    tk, Kp = _tile(K, MAX_TILE, LANE)
    Np = _round_up(N, tn)
    args = [_pad2(a, Mp, Kp), _pad2(b, Kp, Np)]
    in_specs = [pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
                pl.BlockSpec((tk, tn), lambda i, j, k: (k, j))]
    if bias is not None:
        args.append(_pad2(bias.reshape(1, N), 1, Np))
        in_specs.append(pl.BlockSpec((1, tn), lambda i, j, k: (0, j)))
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, activation=activation,
                          has_bias=bias is not None),
        grid=(Mp // tm, Np // tn, Kp // tk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dense_matmul",
    )(*args)
    return out[:M, :N] if (Mp, Np) != (M, N) else out


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
    dx = _matmul(g, w.T, tn=_block_of(w.shape[0], block),
                 out_dtype=x.dtype, interpret=interpret)
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
    of Dout, or a multiple of ``LANE`` (Dout pads up to whole blocks).
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
