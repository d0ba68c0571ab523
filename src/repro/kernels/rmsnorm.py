"""Pallas TPU fused RMSNorm kernel.

Grid over row tiles; each task normalises a (rows_tile, d) block in VMEM —
a fused read-once/write-once pass instead of XLA's separate
square/mean/rsqrt/mul ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

__all__ = ["rmsnorm_pallas"]


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


# forward-only for now: the fused backward is the ROADMAP "LM-family
# kernels" item — training falls back to the ref path via ops.rmsnorm
def rmsnorm_pallas(x, scale, eps: float = 1e-6, row_tile: int = 256,  # reprolint: disable=RPL301
                   interpret: bool | None = None):
    """x: (..., d); scale: (d,).  ``interpret=None`` -> ops._interpret()."""
    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    d = x.shape[-1]
    rows = int(jnp.prod(jnp.array(orig_shape[:-1]))) if len(orig_shape) > 1 else 1
    x2 = x.reshape(rows, d)
    rt = min(row_tile, rows)
    n = -(-rows // rt)
    if n * rt != rows:
        x2 = jnp.pad(x2, ((0, n * rt - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((rt, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n * rt, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, scale)
    return out[:rows].reshape(orig_shape)
