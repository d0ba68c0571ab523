"""Trace-hygiene rules (RPL201/RPL202).

The paper's Eq. 8 feedback loop (IDPA reads measured round durations)
only works if the measured walls are *compute-only*: host syncs inside
traced code silently serialize the device pipeline, and host clocks /
RNGs inside traced code bake a trace-time constant into the compiled
program (the wall-clock-placement bug class fixed in PR 5).

These rules build a per-module trace reachability set — every function
that is jitted / shard_mapped / pallas_called / custom_vjp-registered,
via decorator or call-site wrapping, plus everything those functions
reference transitively inside the module — and flag:

* RPL201 ``host-sync-in-trace``: ``jax.block_until_ready``,
  ``jax.device_get``, ``.item()``, ``np.asarray``/``np.array`` calls.
* RPL202 ``nondet-in-trace``: ``time.*`` calls, stdlib ``random.*`` and
  ``np.random.*`` calls (``jax.random`` is keyed and deterministic and
  does NOT flag), argless ``datetime.now()``, and host spans
  (``repro.sanitize.span``, ``TraceAnnotation``), which would time the
  trace, not the run.

``TIMER_ALLOWLIST`` names the engine timer scopes that are *supposed*
to measure walls (the serving ``MeasuredTimer`` — the serving twin of
the PR 7 measured-duration clocks); findings inside those qualnames are
dropped.

Honesty notes: reachability is per-module (a cross-module call into a
host sync is not followed) and name-based (all same-named defs are
treated as one), so the rules are deliberately conservative about what
counts as reachable — suppress with ``# reprolint: disable=RPL201``
where a flagged call is really trace-time-only host bookkeeping.
"""
from __future__ import annotations

import ast
from typing import Iterator, Optional

from ..engine import Rule, terminal_name

# call/decorator names whose function arguments get traced by JAX
TRACE_WRAPPERS = frozenset({
    "jit", "vmap", "pmap", "grad", "value_and_grad", "jacfwd", "jacrev",
    "custom_vjp", "custom_jvp", "defvjp", "defjvp", "checkpoint", "remat",
    "shard_map", "pallas_call", "scan", "while_loop", "fori_loop", "cond",
    "switch", "associative_scan",
})

# innermost enclosing qualnames where wall measurement is the point,
# plus the runtime sanitizer's sanctioned escape hatches (repro.sanitize):
# their bodies ARE the host-sync boundary every other site routes through
TIMER_ALLOWLIST = frozenset({
    "MeasuredTimer.call", "sanctioned_sync", "sanctioned_scope",
})


def _wrapped_fn_names(node: ast.AST) -> Iterator[str]:
    """Function names referenced by an argument passed to a trace
    wrapper: bare ``f``, ``partial(f, ...)``, or nested wrapper calls
    like ``jax.jit(jax.vmap(f))``."""
    if isinstance(node, ast.Name):
        yield node.id
    elif isinstance(node, ast.Call):
        tn = terminal_name(node.func)
        if tn == "partial" and node.args:
            yield from _wrapped_fn_names(node.args[0])
        elif tn in TRACE_WRAPPERS:
            for a in node.args:
                yield from _wrapped_fn_names(a)


class _ModuleTraceIndex:
    """Per-module function defs, aliases, and the traced-reachable set."""

    def __init__(self, tree: ast.AST):
        self.defs: dict[str, list[ast.AST]] = {}
        self.qualname: dict[ast.AST, str] = {}
        self.aliases: dict[str, set[str]] = {}   # var -> referenced fn names
        self._collect(tree, ())
        self.traced: set[ast.AST] = set()
        self._seed_roots(tree)
        self._close_over_references()

    # -- collection ----------------------------------------------------
    def _collect(self, node: ast.AST, stack: tuple):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = ".".join(stack + (child.name,))
                self.defs.setdefault(child.name, []).append(child)
                self.qualname[child] = q
                self._collect(child, stack + (child.name,))
            elif isinstance(child, ast.ClassDef):
                self._collect(child, stack + (child.name,))
            else:
                if isinstance(child, ast.Assign) and len(child.targets) == 1:
                    tgt = child.targets[0]
                    names = set(_wrapped_fn_names(child.value))
                    if isinstance(tgt, ast.Name) and names:
                        self.aliases.setdefault(tgt.id, set()).update(names)
                self._collect(child, stack)

    def _resolve(self, name: str) -> list[ast.AST]:
        out = list(self.defs.get(name, ()))
        for ref in self.aliases.get(name, ()):
            out.extend(self.defs.get(ref, ()))
        return out

    # -- roots: decorators + call-site wrapping ------------------------
    def _seed_roots(self, tree: ast.AST):
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if self._is_trace_wrapper(dec):
                        self.traced.add(node)
            elif isinstance(node, ast.Call):
                if terminal_name(node.func) in TRACE_WRAPPERS:
                    for a in node.args:
                        for fn in _wrapped_fn_names(a):
                            self.traced.update(self._resolve(fn))

    @staticmethod
    def _is_trace_wrapper(dec: ast.AST) -> bool:
        if terminal_name(dec) in TRACE_WRAPPERS:
            return True
        if isinstance(dec, ast.Call):            # @partial(jax.jit, ...)
            tn = terminal_name(dec.func)
            if tn in TRACE_WRAPPERS:
                return True
            if tn == "partial" and dec.args:
                return terminal_name(dec.args[0]) in TRACE_WRAPPERS
        return False

    # -- transitive closure over intra-module references ---------------
    def _close_over_references(self):
        work = list(self.traced)
        while work:
            fn = work.pop()
            for node in ast.walk(fn):
                if isinstance(node, ast.Name):
                    for d in self._resolve(node.id):
                        if d not in self.traced:
                            self.traced.add(d)
                            work.append(d)
                # defs nested in a traced def run at trace time too (e.g.
                # the @pl.when-decorated bodies inside Pallas kernels)
                elif (node is not fn and node in self.qualname
                        and node not in self.traced):
                    self.traced.add(node)
                    work.append(node)


def _np_receiver(node: ast.AST) -> bool:
    return isinstance(node, ast.Name) and node.id in ("np", "numpy", "onp")


def _host_sync_reason(call: ast.Call) -> Optional[str]:
    fn = call.func
    tn = terminal_name(fn)
    if tn in ("block_until_ready", "device_get"):
        return f"`{tn}` forces a host sync"
    if (isinstance(fn, ast.Attribute) and fn.attr == "item"
            and not call.args and not call.keywords):
        return "`.item()` pulls a traced value to host"
    if (isinstance(fn, ast.Attribute) and fn.attr in ("asarray", "array")
            and _np_receiver(fn.value)):
        return (f"`np.{fn.attr}` materializes a traced value on host "
                "(use jnp)")
    return None


# host spans: inside traced code they record trace time, not run time
HOST_SPANS = frozenset({"span", "TraceAnnotation", "StepTraceAnnotation"})


def _nondet_reason(call: ast.Call) -> Optional[str]:
    fn = call.func
    if terminal_name(fn) in HOST_SPANS:
        return (f"`{terminal_name(fn)}` opens a host span, which would "
                "time the trace, not the run")
    if not isinstance(fn, ast.Attribute):
        return None
    base = fn.value
    if isinstance(base, ast.Name) and base.id == "time":
        return (f"`time.{fn.attr}` reads the host clock — a trace-time "
                "constant inside compiled code")
    if isinstance(base, ast.Name) and base.id == "random":
        return (f"stdlib `random.{fn.attr}` is untraced host RNG "
                "(use jax.random with an explicit key)")
    if (isinstance(base, ast.Attribute) and base.attr == "random"
            and _np_receiver(base.value)):
        return (f"`np.random.{fn.attr}` is untraced host RNG "
                "(use jax.random with an explicit key)")
    if (fn.attr == "now" and not call.args and not call.keywords
            and "datetime" in ast.dump(base)):
        return "argless `datetime.now()` is a trace-time constant"
    return None


def _own_body(fn: ast.AST):
    """Descendants of ``fn`` excluding nested function-def subtrees —
    each traced def is scanned exactly once, under its own qualname."""
    todo = list(ast.iter_child_nodes(fn))
    while todo:
        n = todo.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield n
        todo.extend(ast.iter_child_nodes(n))


def _allowlisted(qualname: str) -> bool:
    return any(qualname == a or qualname.startswith(a + ".")
               for a in TIMER_ALLOWLIST)


class _TraceHygieneRule(Rule):
    """Shared machinery: walk traced-reachable functions, flag calls."""

    def _reason(self, call: ast.Call) -> Optional[str]:
        raise NotImplementedError

    def check(self, ctx, project):
        idx = _ModuleTraceIndex(ctx.tree)
        for fn in sorted(idx.traced, key=lambda f: f.lineno):
            q = idx.qualname[fn]
            if _allowlisted(q):
                continue
            for node in _own_body(fn):
                if isinstance(node, ast.Call):
                    reason = self._reason(node)
                    if reason:
                        yield self.finding(
                            ctx, node,
                            f"{reason} inside `{q}`, which is reachable "
                            "from a jit/shard_map/pallas_call/custom_vjp "
                            "scope")


class HostSyncInTraceRule(_TraceHygieneRule):
    """No host syncs inside traced code: they stall the device pipeline
    and make Eq. 8 walls measure host work."""
    id = "RPL201"
    name = "host-sync-in-trace"
    description = ("block_until_ready / device_get / .item() / np.asarray "
                   "must not run inside trace-reachable functions")

    def _reason(self, call):
        return _host_sync_reason(call)


class NondetInTraceRule(_TraceHygieneRule):
    """No host clocks or untraced RNG inside traced code: the value is
    frozen at trace time, so the compiled program silently replays it."""
    id = "RPL202"
    name = "nondet-in-trace"
    description = ("time.* / random.* / np.random.* / argless datetime.now "
                   "/ host spans must not run inside trace-reachable "
                   "functions (timer scopes in TIMER_ALLOWLIST exempt)")

    def _reason(self, call):
        return _nondet_reason(call)
