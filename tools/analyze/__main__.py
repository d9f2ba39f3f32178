"""CLI driver: ``python -m tools.analyze [--check NAME] [--baseline]
[--changed-only]``.

Exit codes (pinned by tests/test_analyze.py):

- 0  no findings beyond the committed baseline
- 1  new findings (printed as ``file:line CODE message``)
- 2  usage error (unknown --check name)

``--changed-only`` restricts the PER-FILE checkers to the .py files in
the current git working diff (staged + unstaged + untracked) — the
pre-commit fast path.  Cross-file checkers (metrics/chaos/pallas/error
reconciliation) always run over the full tree: restricting their view
would misreport every unchanged site as missing.  On a tree with no
changes (or no git) it falls back to the full run — never silently
lints nothing.
"""
from __future__ import annotations

import argparse
import subprocess

from .core import (CHECKS, default_root, load_baseline, new_findings,
                   run_checks, save_baseline)


def changed_files(root: str):
    """Repo-relative .py paths in the working diff, or None when git is
    unavailable / the tree is clean (callers fall back to a full run)."""
    try:
        res = subprocess.run(
            ["git", "status", "--porcelain", "-uall"], cwd=root,
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if res.returncode != 0:
        return None
    out = set()
    for line in res.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:].strip()
        if " -> " in path:                 # rename: lint the new side
            path = path.split(" -> ", 1)[1]
        path = path.strip('"')
        if path.endswith(".py"):
            out.add(path.replace("\\", "/"))
    return sorted(out) or None


def main(argv=None) -> int:
    from . import checkers  # noqa: F401,PLC0415 — registers CHECKS

    ap = argparse.ArgumentParser(
        prog="python -m tools.analyze",
        description="Concurrency & hazard lint suite "
                    "(docs/ANALYSIS.md)")
    ap.add_argument("--check", action="append", metavar="NAME",
                    help="run only this checker (repeatable); default "
                         "all")
    ap.add_argument("--baseline", action="store_true",
                    help="rewrite tools/analyze/baseline.txt with the "
                         "current findings and exit 0")
    ap.add_argument("--root", default=None,
                    help="repo root to analyze (default: this checkout)")
    ap.add_argument("--list", action="store_true",
                    help="list available checkers and exit")
    ap.add_argument("--changed-only", action="store_true",
                    help="per-file checkers lint only files in the git "
                         "working diff (cross-file checkers still see "
                         "the full tree); clean tree => full run")
    args = ap.parse_args(argv)
    if args.list:
        for name in sorted(CHECKS):
            print(name)
        return 0
    only = None
    if args.changed_only and args.baseline:
        # a baseline written from a restricted run would silently drop
        # every grandfathered finding in unchanged files — force the
        # full run for --baseline
        print("--changed-only is ignored with --baseline "
              "(the baseline must come from a full run)")
    elif args.changed_only:
        only = changed_files(args.root or default_root())
    try:
        findings = run_checks(root=args.root, checks=args.check,
                              only=only)
    except KeyError as e:
        print(e.args[0])
        return 2
    if args.baseline:
        path = save_baseline(findings)
        print(f"wrote {len(findings)} finding(s) to {path}")
        return 0
    fresh = new_findings(findings, load_baseline())
    for f in fresh:
        print(f.render())
    base_n = len(findings) - len(fresh)
    checks = ", ".join(sorted(args.check)) if args.check \
        else "all checks"
    print(f"{len(fresh)} new finding(s), {base_n} baselined "
          f"({checks})")
    return 1 if fresh else 0


if __name__ == "__main__":
    raise SystemExit(main())
