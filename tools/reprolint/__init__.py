"""reprolint — repo-specific static analysis for the BPT-CNN codebase.

Machine-checks the invariants the architecture depends on (single
dispatch decision points, trace hygiene for Eq. 8 timing, the kernel
custom_vjp/fallback contracts, deprecation bans, donation safety) with
a stdlib-``ast`` rule engine.  Run it as::

    python -m tools.reprolint src tests examples

See docs/LINTING.md for the rule catalogue.
"""
from __future__ import annotations

from .engine import (FileContext, Finding, Project, Rule, lint_paths,
                     lint_source, lint_sources, render_json, render_text,
                     run_rules)
from .rules import ALL_RULES

__all__ = [
    "ALL_RULES", "FileContext", "Finding", "Project", "Rule",
    "lint_paths", "lint_source", "lint_sources",
    "render_json", "render_text", "run_rules",
]
