"""Pallas TPU flash-attention kernel (blockwise online softmax).

Inner-layer task parallelism for transformer blocks: the grid cell is one
(batch, head, q-tile) task; the sequential innermost kv axis performs the
online-softmax accumulation in VMEM scratch.  Supports GQA (kv-head
index_map h -> h // G), causal masking, sliding windows and gemma-2 attn
logit soft-capping — the same semantics as ``models.attention``'s jnp path
and ``ref.attention_ref``.

Layouts: q (B, H, Sq, D);  k, v (B, KH, Sk, D);  out (B, H, Sq, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  tq: int, tk: int, nk: int, causal: bool, window: int,
                  softcap: float, scale: float, sq: int, sk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (tq, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (tk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    mask = k_pos < sk                                  # kv padding
    mask &= q_pos < sq
    if causal:
        mask &= k_pos <= q_pos + (sk - sq)
    if window:
        mask &= (q_pos + (sk - sq)) - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


# forward-only for now: the fused backward is the ROADMAP "LM-family
# kernels" item — training falls back to the ref path via ops.attention
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,  # reprolint: disable=RPL301
                           softcap: float = 0.0, q_tile: int = 128,
                           k_tile: int = 128, interpret: bool | None = None):
    """q: (B,H,Sq,D); k,v: (B,KH,Sk,D) -> (B,H,Sq,D).

    ``interpret=None`` resolves via ``ops._interpret()`` (compiled on TPU).
    """
    interpret = resolve_interpret(interpret)
    B, H, Sq, D = q.shape
    KH, Sk = k.shape[1], k.shape[2]
    G = H // KH
    tq, tk = min(q_tile, Sq), min(k_tile, Sk)
    nq, nk = -(-Sq // tq), -(-Sk // tk)
    # pad sequences to tile multiples
    if nq * tq != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * tq - Sq), (0, 0)))
    if nk * tk != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * tk - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * tk - Sk), (0, 0)))

    kern = functools.partial(
        _flash_kernel, tq=tq, tk=tk, nk=nk, causal=causal, window=window,
        softcap=softcap, scale=1.0 / float(D) ** 0.5, sq=Sq, sk=Sk)
    out = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, tq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, tk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, tk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * tq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, D), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :, :Sq]
