"""Jit'd public wrappers over the Pallas kernels with jnp-ref fallbacks.

Implementation selection:
  * ``REPRO_KERNEL_IMPL=ref``    — pure-jnp oracles (default on CPU; what
    the 512-device dry-run lowers).
  * ``REPRO_KERNEL_IMPL=pallas`` — Pallas kernels (interpret mode off TPU,
    compiled on TPU).  ``conv2d``, ``max_pool2d`` and ``dense`` are fully
    differentiable through their ``custom_vjp`` backward kernels, so the
    whole CNN forward+backward (conv Eq. 13, pooling Eq. 15/18, FC
    Eq. 19-21) is a real Pallas training path.

Kernel entry points take ``interpret=None`` and resolve it through
``_interpret()`` here — the single switch that decides interpret-vs-compiled
— so no call site can silently ship interpret-mode kernels to a TPU.

**Fallback contract**: when the pallas impl cannot serve a call (a
strided conv, overlapping pooling) the fallback to the jnp ref is never
silent.  An explicit ``impl="pallas"``
argument raises ``NotImplementedError``; an environment/default-selected
pallas impl warns once per (op, reason) with ``KernelFallbackWarning`` and
records the event in ``fallback_events()`` — tests assert the log stays
empty on the paths that must be all-Pallas.  Dispatch happens in Python,
so events are recorded at *trace* time: one entry per traced call site,
not per compiled execution (re-running an already-jitted function records
nothing new — assert on the log in eager code or around fresh traces).

Default task granularities come from ``core.dag``'s Alg. 4.2 cost model —
``conv2d``'s ``oc_tile`` from ``choose_oc_tile`` and ``dense``'s ``block``
from ``choose_fc_block`` — so the paper's task decomposition and the
executed Pallas grids stay one concept.

**Planner hook**: inside an active ``core.planner.plan_scope`` (the 2-D
``(nodes, model)`` rounds of ``ShardMapEngine``) the tile knobs come from
the per-layer ``LayerPlan`` instead — the plan's tiles were chosen by the
same Alg. 4.2 model on the post-sharding LOCAL shapes, so scheduled and
executed grids stay one concept under the hybrid mesh too.  A ``channel``
fc plan additionally reroutes ``dense`` through the Megatron column-
parallel dataflow (``rep_in``/``shard_dim``/``gather_cols``).  With no
scope active (every 1-D / fused / eval path) behavior is unchanged.
"""
from __future__ import annotations

import os
import warnings

import jax

from . import ref
from .conv2d import conv2d_pallas
from .dense import dense_pallas
from .flash_attention import flash_attention_pallas
from .pool2d import max_pool2d_pallas
from .rmsnorm import rmsnorm_pallas

__all__ = ["conv2d", "max_pool2d", "dense", "flash_attention", "rmsnorm",
           "default_impl", "KernelFallbackWarning", "fallback_events",
           "clear_fallback_log"]


def default_impl() -> str:
    impl = os.environ.get("REPRO_KERNEL_IMPL", "")
    if impl:
        return impl
    return "ref" if jax.default_backend() == "cpu" else "pallas"


def _interpret() -> bool:
    """Interpret-mode switch: compiled kernels only on real TPU silicon."""
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------------
# the explicit-fallback contract
# ----------------------------------------------------------------------
class KernelFallbackWarning(UserWarning):
    """A pallas-selected dispatch served a call from the jnp ref."""


_FALLBACKS: dict[tuple[str, str], int] = {}


def _fallback(op: str, reason: str, explicit: bool) -> None:
    """Record a pallas -> ref fallback; never silent.

    ``explicit`` (the caller passed ``impl="pallas"``) raises — the caller
    asked for a kernel that cannot serve the call.  An env/default-selected
    pallas impl warns once per (op, reason) and logs the event.
    """
    if explicit:
        raise NotImplementedError(
            f"{op}: impl='pallas' was requested explicitly but {reason}; "
            "pass impl='ref' (or fix the call) to opt in to the jnp "
            "reference instead")
    key = (op, reason)
    first = key not in _FALLBACKS
    _FALLBACKS[key] = _FALLBACKS.get(key, 0) + 1
    if first:
        warnings.warn(f"{op}: falling back to the jnp ref — {reason}",
                      KernelFallbackWarning, stacklevel=3)


def fallback_events() -> dict[tuple[str, str], int]:
    """(op, reason) -> count of pallas dispatches served by the ref."""
    return dict(_FALLBACKS)


def clear_fallback_log() -> None:
    _FALLBACKS.clear()


def _plan_take(kind: str):
    """The active LayerPlan for the next ``kind`` call, or None."""
    from repro.core import planner
    return planner.take(kind)


# ----------------------------------------------------------------------
# dispatch wrappers
# ----------------------------------------------------------------------
def conv2d(x, w, b=None, padding: str = "SAME", stride: int = 1,
           activation: str = "none", impl: str = "",
           oc_tile: int | None = None):
    """Conv + optional fused bias/activation epilogue (paper Eq. 1+2).

    The Pallas path (stride 1) is differentiable end-to-end via
    ``custom_vjp``; ``oc_tile=None`` asks the §4 cost model for the task
    granularity, ``oc_tile=0`` forces one task per batch image.  A strided
    call under pallas takes the explicit-fallback contract (the paper's
    CNNs pool instead of striding, so the kernel is stride-1 only).
    """
    if oc_tile is None:
        lp = _plan_take("conv")
        if lp is not None:
            oc_tile = lp.tile
    explicit = impl == "pallas"
    impl = impl or default_impl()
    if impl == "pallas":
        if stride == 1:
            if oc_tile is None:
                from repro.core.dag import choose_oc_tile
                oc_tile = choose_oc_tile(int(x.shape[0]), int(w.shape[-1]))
            return conv2d_pallas(x, w, b, padding=padding,
                                 activation=activation, oc_tile=oc_tile,
                                 interpret=_interpret())
        _fallback("conv2d",
                  f"stride={stride} is unsupported (stride-1 kernel only)",
                  explicit)
    out = ref.conv2d_ref(x, w, padding=padding, stride=stride)
    if b is not None:
        out = out + b.astype(out.dtype)    # match the kernel's output dtype
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation != "none":
        raise ValueError(activation)
    return out


def max_pool2d(x, window: int = 2, stride: int = 2, impl: str = ""):
    """Max pooling (paper Eq. 15; backward Eq. 18 via ``custom_vjp``).

    The Pallas path covers non-overlapping pooling (``window == stride``,
    the paper's configuration); anything else takes the explicit-fallback
    contract.  Note the jnp ref is also non-overlapping-only today, so an
    overlapping env-selected fallback will raise there — loudly, after the
    recorded warning — rather than silently pool the wrong windows.
    """
    explicit = impl == "pallas"
    impl = impl or default_impl()
    if impl == "pallas":
        if window == stride:
            return max_pool2d_pallas(x, window=window, stride=stride,
                                     interpret=_interpret())
        _fallback("max_pool2d",
                  f"window={window} stride={stride} is unsupported "
                  "(non-overlapping pooling only)", explicit)
    return ref.max_pool2d_ref(x, window=window, stride=stride)


def dense(x, w, b=None, activation: str = "none", impl: str = "",
          block: int | None = None):
    """Fused dense layer: x @ w (+ b) (+ activation), paper §4.1.2.

    ``x`` may carry leading batch dims — they flatten into the kernel's
    row axis and reshape back.  The Pallas path is differentiable via
    ``custom_vjp`` (per-block G_FC gradient tasks); ``block=None`` asks
    the Alg. 4.2 cost model (``core.dag.choose_fc_block``) for the task
    granularity, ``block=0`` forces one task for the whole layer.  The
    kernel tiles rows and the contraction itself, so every width is
    served — there is no dense fallback.

    Under an active plan scope a ``channel``-parallel LayerPlan reroutes
    the call through the Megatron column dataflow: the weight/bias shard
    for this device's ``model`` index, the kernel on the local block
    (with the plan's LOCAL-shape tile), and a replication-aware
    all-gather back to the full activation — gradients stay exactly
    replicated across ``model`` via the collectives' custom VJPs.
    """
    if block is None:
        lp = _plan_take("fc")
        if lp is not None:
            block = lp.tile
            if lp.parallel_dim == "channel":
                from repro.core import planner
                full = int(w.shape[-1])
                xr = planner.rep_in(x, lp.axis)
                ws = planner.shard_dim(w, lp.shards, full, lp.axis)
                bs = planner.shard_dim(b, lp.shards, full, lp.axis) \
                    if b is not None else None
                out = dense(xr, ws, bs, activation=activation, impl=impl,
                            block=block)
                return planner.gather_cols(out, lp.shards, lp.axis)
    impl = impl or default_impl()
    if impl == "pallas":
        if block is None:
            from repro.core.dag import choose_fc_block
            block = choose_fc_block(int(w.shape[-1]))
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        # match the ref's compute dtype (w cast to the activations')
        out = dense_pallas(x2, w.astype(x.dtype), b, activation=activation,
                           block=block, interpret=_interpret())
        return out.reshape(*lead, w.shape[-1])
    return ref.dense_ref(x, w, b, activation=activation)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    impl: str = ""):
    """q: (B,Sq,H,D); k,v: (B,Sk,KH,D) — BSHD layout like the models."""
    impl = impl or default_impl()
    if impl == "pallas":
        out = flash_attention_pallas(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window,
            softcap=softcap, interpret=_interpret())
        return out.transpose(0, 2, 1, 3)
    return ref.attention_ref(q, k, v, causal=causal,
                             window=window or None, softcap=softcap)


def rmsnorm(x, scale, eps: float = 1e-6, impl: str = ""):
    impl = impl or default_impl()
    if impl == "pallas":
        return rmsnorm_pallas(x, scale, eps=eps, interpret=_interpret())
    return ref.rmsnorm_ref(x, scale, eps=eps)
