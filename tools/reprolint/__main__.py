"""CLI: ``python -m tools.reprolint [paths...]``.

Exit status 1 when findings exist (CI gates on it), 2 on usage errors.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

from .engine import FileContext, Project, _select, iter_py_files, \
    render_json, render_text, run_rules
from .rules import ALL_RULES

DEFAULT_PATHS = ("src", "tests", "examples")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.reprolint",
        description="repo-specific static analysis (see docs/LINTING.md)")
    ap.add_argument("paths", nargs="*", default=list(DEFAULT_PATHS),
                    help="files or directories to lint "
                         f"(default: {' '.join(DEFAULT_PATHS)})")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    help="stdout format (default: text)")
    ap.add_argument("--json-report", metavar="FILE",
                    help="also write a JSON report to FILE")
    ap.add_argument("--rules", "--only", dest="rules", metavar="ID[,ID...]",
                    help="run only these rules (ids or names, "
                         "comma-separated)")
    ap.add_argument("--disable", metavar="ID[,ID...]",
                    help="skip these rules (ids or names, comma-separated; "
                         "applied after --only)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in ALL_RULES:
            print(f"{r.id}  {r.name:<24} {r.description}")
        return 0

    def _split(raw):
        return [t.strip() for t in raw.split(",") if t.strip()] \
            if raw else None

    only, disable = _split(args.rules), _split(args.disable)
    try:
        files = list(iter_py_files(args.paths))
        ctxs = [FileContext(str(f), f.read_text()) for f in files]
        picked = _select(ALL_RULES, only, disable)
        findings = run_rules(Project(ctxs), picked)
    except (FileNotFoundError, ValueError) as e:
        print(f"reprolint: error: {e}", file=sys.stderr)
        return 2

    if args.format == "json":
        print(render_json(findings, len(ctxs), picked))
    else:
        print(render_text(findings, len(ctxs)))
    if args.json_report:
        Path(args.json_report).write_text(
            render_json(findings, len(ctxs), picked) + "\n")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
