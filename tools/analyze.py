#!/usr/bin/env python
"""Project-invariant analyzer driver (distar_tpu/analysis framework).

Usage:
    python tools/analyze.py [paths...]           # analyze (default tree)
    python tools/analyze.py --changed            # only `git diff` files
    python tools/analyze.py report [paths...]    # ranked-markdown summary
    python tools/analyze.py --json out.json      # machine-readable report
    python tools/analyze.py --write-baseline     # regenerate the baseline

Default paths: ``distar_tpu tools``. Exit codes: 0 = clean,
1 = baselined-only (grandfathered debt, nothing new), 2 = new findings or
stale baseline entries (the baseline may only shrink). Tier-1 runs this via
tests/test_analysis.py::test_analysis_repo_clean; ``--changed`` is the fast
pre-commit mode. Rule catalog: docs/analysis.md.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distar_tpu.analysis import (  # noqa: E402
    Analyzer, collect_files, load_baseline, render_markdown, save_baseline,
)

DEFAULT_PATHS = ("distar_tpu", "tools")
DEFAULT_BASELINE = os.path.join(_REPO, "tools", "analysis_baseline.json")


def _changed_files() -> list:
    """Python files touched per git (staged + unstaged + untracked)."""
    out = subprocess.run(
        ["git", "diff", "--name-only", "HEAD"],
        cwd=_REPO, capture_output=True, text=True, check=False,
    ).stdout
    untracked = subprocess.run(
        ["git", "ls-files", "--others", "--exclude-standard"],
        cwd=_REPO, capture_output=True, text=True, check=False,
    ).stdout
    files = []
    for line in (out + untracked).splitlines():
        line = line.strip()
        # scope --changed to the same tree the full run analyzes: tests and
        # docs change constantly and are not the analyzer's subject
        if not line.endswith(".py") or not os.path.exists(os.path.join(_REPO, line)):
            continue
        if not line.startswith(("distar_tpu/", "tools/")):
            continue
        files.append(os.path.join(_REPO, line))
    return sorted(set(files))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("cmd_or_paths", nargs="*",
                        help="'report' or files/dirs to analyze "
                             f"(default: {' '.join(DEFAULT_PATHS)})")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE,
                        help="baseline JSON (default tools/analysis_baseline.json)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline (every finding is new)")
    parser.add_argument("--changed", action="store_true",
                        help="analyze only files git reports changed (pre-commit mode)")
    parser.add_argument("--rules", default="",
                        help="comma-separated rule ids to restrict to")
    parser.add_argument("--json", dest="json_out", default="",
                        help="write the JSON report here ('-' = stdout)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="regenerate the baseline from current NEW findings "
                             "(then exits 0; review the diff before committing)")
    args = parser.parse_args(argv)

    paths = list(args.cmd_or_paths)
    report_mode = bool(paths) and paths[0] == "report"
    if report_mode:
        paths = paths[1:]
    if not paths:
        paths = list(DEFAULT_PATHS)

    analyzer = Analyzer(
        repo_root=_REPO,
        rules=[r.strip() for r in args.rules.split(",") if r.strip()] or None,
    )
    if args.changed:
        files = _changed_files()
        if not files:
            sys.stdout.write("analyze --changed: no changed python files\n")
            return 0
    else:
        files = collect_files(paths, repo_root=_REPO)
    baseline = [] if args.no_baseline else load_baseline(args.baseline)
    # --changed analyzes a subset, so baseline entries for files outside the
    # subset would all look stale; restrict the stale check to scanned files
    if args.changed and baseline:
        scanned = {os.path.relpath(f, _REPO).replace(os.sep, "/") for f in files}
        baseline = [e for e in baseline if e.get("path") in scanned]
    result = analyzer.run(files, baseline=baseline)

    if args.write_baseline:
        save_baseline(args.baseline, result.findings + result.baselined)
        sys.stdout.write(
            f"wrote {len(result.findings) + len(result.baselined)} entries to "
            f"{args.baseline}\n")
        return 0

    if args.json_out:
        payload = json.dumps(result.to_dict(), indent=1, sort_keys=True)
        if args.json_out == "-":
            sys.stdout.write(payload + "\n")
        else:
            with open(args.json_out, "w") as f:
                f.write(payload + "\n")
    if report_mode:
        sys.stdout.write(render_markdown(result))
    else:
        for f in result.findings:
            sys.stderr.write(str(f) + "\n")
        for e in result.stale_baseline:
            sys.stderr.write(
                f"STALE baseline entry (remove it — shrink-only): "
                f"{e['path']}: {e['rule']}: {e['ident']}\n")
        sys.stderr.write(
            f"analyze: {result.files} files · {len(result.findings)} new · "
            f"{len(result.baselined)} baselined · "
            f"{len(result.suppressed)} pragma-suppressed · "
            f"{len(result.stale_baseline)} stale baseline entries "
            f"(exit {result.exit_code})\n")
    return result.exit_code


if __name__ == "__main__":
    raise SystemExit(main())
