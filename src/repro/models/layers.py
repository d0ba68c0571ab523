"""Common neural-net layers (pure-functional JAX, params as pytrees)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.shardlib import constrain

__all__ = [
    "rms_norm", "init_layer_norm", "layer_norm", "init_dense", "dense",
    "init_mlp", "mlp", "rope_frequencies", "apply_rope", "init_embedding",
    "embed", "softcap", "init_rms_norm", "init_conv2d", "conv2d", "init_fc",
    "fc",
]


def init_rms_norm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def init_layer_norm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(params, x, eps: float = 1e-6):
    """LayerNorm over the last axis (biased variance), in float32."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def softcap(x, cap: float):
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    return jnp.tanh(x / cap) * cap


def init_conv2d(key, kh: int, kw: int, c_in: int, c_out: int,
                dtype=jnp.float32):
    """He-initialised HWIO conv filter + zero bias."""
    fan = c_in * kh * kw
    return {
        "w": jax.random.normal(key, (kh, kw, c_in, c_out), dtype)
        * jnp.sqrt(2.0 / fan),
        "b": jnp.zeros((c_out,), dtype),
    }


def conv2d(params, x, padding: str = "SAME", stride: int = 1,
           activation: str = "none"):
    """Conv + fused bias/activation via the kernels.ops dispatch.

    All model conv sites go through here so ``REPRO_KERNEL_IMPL=pallas``
    trains through the differentiable Pallas kernel (custom_vjp backward),
    and ``ref`` lowers the jnp oracle — one switch, one call site.
    """
    from repro.kernels import ops
    return ops.conv2d(x, params["w"], params["b"], padding=padding,
                      stride=stride, activation=activation)


def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32):
    scale = 1.0 / jnp.sqrt(d_in)
    return {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}


def dense(params, x):
    """Bias-free projection via the kernels.ops dispatch.

    Every model matmul site goes through here, so
    ``REPRO_KERNEL_IMPL=pallas`` runs the differentiable Pallas dense
    kernel (custom_vjp backward) and ``ref`` lowers ``x @ w`` — one
    switch, one call site, same as ``conv2d``.
    """
    from repro.kernels import ops
    return ops.dense(x, params["w"])


def init_fc(key, d_in: int, d_out: int, dtype=jnp.float32):
    """He-initialised full-connection layer (weight + zero bias, §4.1.2)."""
    return {
        "w": jax.random.normal(key, (d_in, d_out), dtype)
        * jnp.sqrt(2.0 / d_in),
        "b": jnp.zeros((d_out,), dtype),
    }


def fc(params, x, activation: str = "none"):
    """Full-connection layer + fused bias/activation via kernels.ops.

    The CNN's classifier stack (paper §4.1.2, Eq. 19-21) routes through
    here so the pallas impl runs the whole-layer training step — forward
    matmul+epilogue and per-block G_FC gradient tasks — in Pallas.
    Inside a ``core.planner`` plan scope (2-D hybrid-mesh rounds) the
    dispatch also takes the layer's planned tile / channel-parallel
    dataflow from the active ``LayerPlan`` — see ``kernels.ops.dense``.
    """
    from repro.kernels import ops
    return ops.dense(x, params["w"], params["b"], activation=activation)


def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": init_dense(k1, d_model, d_ff, dtype),
        "wg": init_dense(k2, d_model, d_ff, dtype),
        "wo": init_dense(k3, d_ff, d_model, dtype),
    }


def mlp(params, x, activation: str = "silu", megatron: bool = False):
    """Gated MLP (SwiGLU / GeGLU).

    megatron=True: classic TP dataflow — all-gather x over seq once, keep
    the hidden ff-sharded on `model`, reduce-scatter the output back to
    seq-sharded (cheaper than GSPMD's default per-matmul weight gathers
    when d_ff >> d_model; §Perf hillclimb 1).
    """
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    if megatron:
        x = constrain(x, "batch", None, None)          # gather seq
        h = act(dense(params["wg"], x)) * dense(params["wi"], x)
        h = constrain(h, "batch", None, "mlp_ff")      # ff stays sharded
        y = dense(params["wo"], h)
        return constrain(y, "batch", "seq", None)      # reduce-scatter
    h = act(dense(params["wg"], x)) * dense(params["wi"], x)
    return dense(params["wo"], h)


# ----------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float = 10_000.0):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    return inv                                         # (head_dim/2,)


def apply_rope(x, positions, theta: float = 10_000.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    inv = rope_frequencies(head_dim, theta)
    ang = positions[..., :, None].astype(jnp.float32) * inv   # (..., S, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Embeddings
# ----------------------------------------------------------------------
def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)
