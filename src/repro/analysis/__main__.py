"""CLI: ``python -m repro.analysis [--ci] [paths...]``.

Zero runtime deps (stdlib + the repo's own AST passes — jax is never
imported), so the CI job needs no ``pip install`` beyond a checkout.

Exit status: 0 = clean, 1 = findings, 2 = bad invocation.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

from repro.analysis.core import RULES, analyze_paths, summarize

_CI_PATHS = ("src", "tests", "tools")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repo-aware invariant linter for the serving stack "
                    "(recompile hazards, lock discipline, donation)")
    parser.add_argument(
        "paths", nargs="*", type=Path,
        help="files or directories to lint (default: %s)"
             % " ".join(_CI_PATHS))
    parser.add_argument(
        "--ci", action="store_true",
        help="CI mode: default paths to src/ tests/ tools/ and "
             "keep output terse")
    parser.add_argument(
        "--rules", action="store_true",
        help="list the known rule names and exit")
    args = parser.parse_args(argv)

    if args.rules:
        print("\n".join(RULES))
        return 0

    paths = args.paths or [Path(p) for p in _CI_PATHS]
    missing = [p for p in paths if not p.exists()]
    if missing:
        print("no such path: %s" % ", ".join(map(str, missing)),
              file=sys.stderr)
        return 2

    findings = analyze_paths(paths, root=Path.cwd())
    for f in findings:
        print(f)
    if findings:
        print(summarize(findings), file=sys.stderr)
        return 1
    if not args.ci:
        n = len(list(paths))
        print(f"repro.analysis: clean ({n} root(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
