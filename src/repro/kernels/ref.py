"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the ground truth for the per-kernel allclose sweeps in
``tests/test_kernels.py`` and the differentiable fallback implementation on
CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["conv2d_ref", "depthwise_conv2d_ref", "max_pool2d_ref",
           "dense_ref", "attention_ref", "rmsnorm_ref"]


def conv2d_ref(x, w, padding: str = "SAME", stride: int = 1):
    """im2col convolution, NHWC x HWIO -> NHWC.  Pure jnp, differentiable."""
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    if padding == "SAME":
        ph, pw = (kh - 1) // 2, (kw - 1) // 2
        ph2, pw2 = kh - 1 - ph, kw - 1 - pw
        x = jnp.pad(x, ((0, 0), (ph, ph2), (pw, pw2), (0, 0)))
    elif padding != "VALID":
        raise ValueError(padding)
    Hp, Wp = x.shape[1], x.shape[2]
    Ho = (Hp - kh) // stride + 1
    Wo = (Wp - kw) // stride + 1
    # gather all kh*kw shifted views: (B, Ho, Wo, kh*kw*Cin)
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(jax.lax.slice(
                x, (0, i, j, 0), (B, i + (Ho - 1) * stride + 1,
                                  j + (Wo - 1) * stride + 1, Cin),
                (1, stride, stride, 1)))
    cols = jnp.concatenate(cols, axis=-1)
    wmat = w.transpose(0, 1, 2, 3).reshape(kh * kw * Cin, Cout)
    out = cols.reshape(B, Ho, Wo, kh * kw * Cin) @ wmat.astype(x.dtype)
    return out


def depthwise_conv2d_ref(x, w, padding: str = "SAME", stride: int = 1):
    """Depthwise convolution, NHWC x (kh, kw, C) -> NHWC: each channel
    filtered by its own taps (a grouped conv, one group per channel).
    Differentiable."""
    return jax.lax.conv_general_dilated(
        x, w[:, :, None, :].astype(x.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1])


def max_pool2d_ref(x, window: int = 2, stride: int = 2):
    """Non-overlapping window max (window == stride), NHWC.  Differentiable;
    jax.grad splits tied maxima evenly — the contract the Pallas backward
    kernel reproduces."""
    if window != stride:
        raise ValueError(
            f"max_pool2d_ref is non-overlapping only (stride == window), "
            f"got window={window} stride={stride}")
    B, H, W, C = x.shape
    Ho, Wo = H // stride, W // stride
    x = x[:, :Ho * stride, :Wo * stride, :]
    x = x.reshape(B, Ho, stride, Wo, stride, C)
    return x.max(axis=(2, 4))


def dense_ref(x, w, b=None, activation: str = "none"):
    """Fused dense oracle: x @ w (+ b) (+ activation).  Pure jnp,
    differentiable; x may carry leading batch dims."""
    out = x @ w.astype(x.dtype)
    if b is not None:
        out = out + b.astype(out.dtype)
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation != "none":
        raise ValueError(activation)
    return out


def attention_ref(q, k, v, *, causal=True, window=None, softcap=0.0,
                  scale=None):
    """Naive O(S^2) GQA attention oracle.  q: (B,Sq,H,D); k,v: (B,Sk,KH,D)."""
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale or (1.0 / jnp.sqrt(D))
    qg = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qi = jnp.arange(Sq)[:, None]
    kj = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kj <= qi + (Sk - Sq)      # align ends when Sq != Sk
    if window:
        mask &= (qi + (Sk - Sq)) - kj < window
    s = jnp.where(mask[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def rmsnorm_ref(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)
