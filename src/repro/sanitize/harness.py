"""Runtime sanitizer — shardcheck's dynamic half (see docs/LINTING.md).

The static rules (RPL2xx/RPL6xx) prove what the AST can prove; this
module catches the two failure classes that only exist at run time, and
names the host path for the profiler:

* **Hidden transfers.**  ``sanitized()`` arms ``jax.transfer_guard`` so
  that any *implicit* host<->device transfer inside an engine round —
  a numpy batch silently uploaded at jit dispatch, a device value
  silently pulled by host arithmetic — raises instead of serializing
  the pipeline.  Host syncs that are *supposed* to happen (the Eq. 8
  measured-wall boundary, accuracy evals feeding Eq. 7/10) route
  through ``sanctioned_sync()`` / ``sanctioned_scope()``: the one
  audited escape hatch, mirrored on the static side by RPL201's
  allowlist.
* **Silent recompiles.**  A compile-event counter built on
  ``jax.monitoring`` duration events (which fire only on real
  compilations, never on cached dispatches) backs ``compile_budget(n)``
  assertions — steady-state code paths pin a budget of 0 new compiles,
  the same contract ``ServeEngine.prefill_traces`` enforces per
  function (PR 8 pattern).
* **Host spans.**  ``span(name)`` is a ``jax.profiler.TraceAnnotation``
  named ``bpt.<name>``: it lands in the profiler's own trace, on the
  device planes' clock, and costs one inactive ``TraceMe`` check when no
  profiler is attached.  Every sanctioned sync is the span
  ``bpt.sync.<label>``, so the trace and the audit log count the same
  pulls.  Open spans in host code only: inside a jitted, shard_mapped or
  Pallas body a span would time the trace, not the run.

The guards and the audit log are gated on ``REPRO_SANITIZE`` (off by
default; the CI tier-1 matrix runs a ``REPRO_SANITIZE=1`` leg).  With the
gate off, ``sanitized`` is a no-op, ``sync_log`` stays empty and
``sanctioned_sync`` still blocks + materializes — callers never branch on
the env var themselves.

Backend honesty note: on the CPU backend device arrays are host-resident,
so the device-to-host half of the guard never fires there — CPU CI
enforces the implicit host-to-device class (dispatch hygiene) and the
d2h half arms automatically on real accelerators.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax
import numpy as np

__all__ = [
    "sanitize_enabled", "sanitized", "sanctioned_scope", "sanctioned_sync",
    "span", "sync_log", "clear_sync_log",
    "install_compile_listener", "compile_counts", "compile_budget",
    "CompileBudgetExceeded",
]


def sanitize_enabled() -> bool:
    """True when the REPRO_SANITIZE env gate is on ("", "0", "off" = off)."""
    return os.environ.get("REPRO_SANITIZE", "").lower() not in ("", "0", "off")


@contextlib.contextmanager
def sanitized(label: str = ""):
    """Arm the transfer guards around an engine round body.

    Inside the scope every implicit host-to-device transfer (numpy
    leaves reaching a jit dispatch, weak python scalars promoted at call
    time) and every implicit device-to-host transfer raises
    ``jax.errors.JaxRuntimeError``.  Explicit placements
    (``jax.device_put``, ``jnp.asarray``) stay legal — the point is that
    every transfer is *visible in the code*, not that no data moves.
    No-op when ``REPRO_SANITIZE`` is off.
    """
    if not sanitize_enabled():
        yield
        return
    with jax.transfer_guard_host_to_device("disallow"), \
            jax.transfer_guard_device_to_host("disallow"):
        yield


def span(name: str, **args):
    """The host span ``bpt.<name>`` in the profiler's trace, with ``args``
    as its event stats (ints, floats or strings)."""
    return jax.profiler.TraceAnnotation(f"bpt.{name}", **args)


# audit trail of sanctioned sync points while the gate is on, most recent
# last: labels are enough for tests to assert "the only syncs were the
# measured ones"
_sync_log: list = []
_sync_lock = threading.Lock()


def sync_log() -> list:
    """Labels of every sanctioned sync made with the gate on since the
    last clear (copy)."""
    with _sync_lock:
        return list(_sync_log)


def clear_sync_log() -> None:
    with _sync_lock:
        _sync_log.clear()


@contextlib.contextmanager
def sanctioned_scope(label: str):
    """The audited escape hatch: transfers are allowed inside, the scope
    is the span ``bpt.sync.<label>``, and with the gate on it is recorded
    in ``sync_log()``.  Use it where a host sync IS the semantics —
    measured-wall boundaries (``MeasuredTimer``), accuracy evals whose
    scalar feeds Eq. 7/10 weighting."""
    with span(f"sync.{label}"), jax.transfer_guard("allow"):
        yield
    if sanitize_enabled():
        with _sync_lock:
            _sync_log.append(label)


def sanctioned_sync(x, label: str = "sync"):
    """Block on ``x`` and materialize it on host, as a sanctioned sync.

    The runtime twin of RPL201's allowlist: engine code that must pull a
    device value (per-node losses for ``RoundEvent``, eval scalars)
    calls this instead of raw ``np.asarray(jax.block_until_ready(...))``
    so the pull stays legal under ``sanitized()`` and lands in the audit
    log.  Returns the pytree with every leaf as ``np.ndarray``.
    """
    with sanctioned_scope(label):
        out = jax.block_until_ready(x)
        return jax.tree_util.tree_map(np.asarray, out)


# ----------------------------------------------------------------------
# compile budgets
# ----------------------------------------------------------------------
class CompileBudgetExceeded(AssertionError):
    """A ``compile_budget`` scope compiled more than it promised."""


# jax.monitoring duration events that fire ONLY on real compilations
# (cached dispatches emit nothing).  One XLA compilation emits >= 1
# backend_compile event and >= 1 trace event — treat the counts as
# "compile activity", not an exact compilation count: budgets are upper
# bounds, and the load-bearing assertion is the steady-state budget of 0.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

_counts = {"traces": 0, "compiles": 0}
_listening = False


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == _TRACE_EVENT:
        _counts["traces"] += 1
    elif event == _BACKEND_EVENT:
        _counts["compiles"] += 1


def install_compile_listener() -> None:
    """Register the compile-event listener (idempotent, process-wide).

    ``jax.monitoring`` has no unregister, so the listener stays for the
    life of the process — it only bumps two ints per compilation.
    """
    global _listening
    if _listening:
        return
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    _listening = True


def compile_counts() -> dict:
    """Cumulative compile-activity counters since the listener install:
    ``traces`` (jaxpr traces) and ``compiles`` (XLA backend compiles)."""
    return dict(_counts)


@contextlib.contextmanager
def compile_budget(n: int, what: str = "compiles", label: str = ""):
    """Assert the scope triggers at most ``n`` compile events.

    ``what`` selects the counter ("compiles" = XLA backend compilations,
    "traces" = jaxpr traces).  ``compile_budget(0)`` is the steady-state
    contract: a warmed code path must dispatch from cache.  Raises
    ``CompileBudgetExceeded`` (an AssertionError) on overrun.
    """
    install_compile_listener()
    before = _counts[what]
    yield
    spent = _counts[what] - before
    if spent > n:
        where = f" [{label}]" if label else ""
        raise CompileBudgetExceeded(
            f"compile budget exceeded{where}: {spent} {what} > "
            f"budget {n} — a warmed path recompiled (shape/dtype drift or "
            "a python-object hash miss in jit static args)")
