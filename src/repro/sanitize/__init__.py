"""repro.sanitize — runtime sanitizer (transfer guards + compile budgets)
and the program's host spans.

The dynamic half of shardcheck; the static half is the RPL6xx rule
family in ``tools/reprolint``.  See ``harness`` for the full contract.
"""
from .harness import (CompileBudgetExceeded, clear_sync_log, compile_budget,
                      compile_counts, install_compile_listener,
                      sanctioned_scope, sanctioned_sync, sanitize_enabled,
                      sanitized, span, sync_log)

__all__ = [
    "sanitize_enabled", "sanitized", "sanctioned_scope", "sanctioned_sync",
    "span", "sync_log", "clear_sync_log",
    "install_compile_listener", "compile_counts", "compile_budget",
    "CompileBudgetExceeded",
]
