"""pbox-lint CLI: ``python tools/pbox_analyze.py --all --json ...``.

Exit codes: 0 clean, 1 findings (incl. stale-baseline errors), 2 the
analyzer itself is misconfigured (bad baseline schema, unknown rule,
bad git ref).

Modes:

  --all                analyze the default roots (package, tools)
  PATH [PATH ...]      analyze specific files/directories instead
  --changed [REF]      findings only on lines touched vs the git ref
                       (default HEAD) — the fast pre-commit entry point
  --rules a,b          run only the named rules
  --list-rules         print the rule catalog and exit
  --json               machine-readable output (list of finding dicts)
  --update-baseline    accept every current finding into the baseline
  --publish-root PATH  additionally audit a publish root (repeatable;
                       runtime data check, imports the package)
  --store-root PATH    additionally audit a durable-log store root
                       (repeatable; runtime data check)
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import re
import subprocess
import sys
import time

from . import all_rules, run_passes
from . import baseline as baseline_mod
from .core import REPO, Context, Finding, discover_files


def parse_changed_diff(text: str) -> dict:
    """{post-image repo-relative path: set of touched 1-based lines} from
    unified-diff text.

    Robust to the shapes a working tree actually produces: deleted files
    (``+++ /dev/null`` — their hunks belong to no current file and must
    not bleed onto the previous file), renames (``+++ b/<new path>`` is
    the analyzable file; a pure rename with no hunks touches nothing),
    and mode-only entries (no ``+++`` line at all)."""
    touched: dict = {}
    current = None
    for line in text.splitlines():
        if line.startswith("+++ b/"):
            current = line[6:]
            touched.setdefault(current, set())
        elif line.startswith("+++ "):
            current = None  # '+++ /dev/null': the file is gone
        elif line.startswith("diff --git"):
            current = None  # a headerless entry must not inherit state
        elif line.startswith("@@") and current is not None:
            m = re.search(r"\+(\d+)(?:,(\d+))?", line)
            if m:
                start = int(m.group(1))
                count = int(m.group(2)) if m.group(2) is not None else 1
                touched[current].update(range(start, start + max(count, 1)))
    return touched


def _changed_lines(ref: str) -> dict:
    """{repo-relative path: set of touched 1-based lines} vs the ref."""
    try:
        out = subprocess.run(
            ["git", "diff", "--unified=0", "--find-renames", ref,
             "--", "*.py"],
            cwd=REPO, capture_output=True, text=True, timeout=60,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise SystemExit(f"ERROR: git diff {ref} failed: {e}")
    if out.returncode != 0:
        raise SystemExit(
            f"ERROR: git diff {ref} failed: {out.stderr.strip()}")
    return parse_changed_diff(out.stdout)


def _resolve_paths(paths: list) -> list:
    out: list = []
    for p in paths:
        full = p if os.path.isabs(p) else os.path.join(REPO, p)
        if not os.path.exists(full):
            raise SystemExit(f"ERROR: no such path: {p}")
        out.extend(discover_files(REPO, [full]) if os.path.isdir(full)
                   else [full])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/pbox_analyze.py",
        description=__doc__.splitlines()[0],
    )
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to analyze (default: --all roots)")
    ap.add_argument("--all", action="store_true",
                    help="analyze the default roots (paddlebox_tpu/, "
                         "tools/)")
    ap.add_argument("--json", action="store_true",
                    help="emit findings as JSON")
    ap.add_argument("--rules", metavar="A,B",
                    help="comma-separated rule ids to run (default all)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--changed", nargs="?", const="HEAD", metavar="REF",
                    help="report only findings on lines touched vs REF "
                         "(default HEAD) — the pre-commit fast path")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write every current finding into the baseline "
                         "(new entries get a placeholder reason to edit)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report raw findings, ignoring the baseline")
    ap.add_argument("--publish-root", action="append", default=[],
                    metavar="PATH",
                    help="also audit a publish root (runtime data check)")
    ap.add_argument("--store-root", action="append", default=[],
                    metavar="PATH",
                    help="also audit a durable-log store root "
                         "(runtime data check)")
    args = ap.parse_args(argv)

    rules_catalog = all_rules()
    if args.list_rules:
        width = max(len(r) for r in rules_catalog)
        for rule in sorted(rules_catalog):
            print(f"{rule:<{width}}  {rules_catalog[rule]}")
        return 0

    rules = None
    if args.rules:
        # globs select rule families: --rules 'spmd-*' runs the four
        # SPMD passes, --rules 'protocol-*' the typestate specs
        requested = {r.strip() for r in args.rules.split(",") if r.strip()}
        rules = set()
        unknown = set()
        for pat in requested:
            if any(ch in pat for ch in "*?["):
                hits = {r for r in rules_catalog
                        if fnmatch.fnmatchcase(r, pat)}
                if hits:
                    rules |= hits
                else:
                    unknown.add(pat)
            elif pat in rules_catalog:
                rules.add(pat)
            else:
                unknown.add(pat)
        if unknown:
            print(f"ERROR: unknown rule(s): {', '.join(sorted(unknown))} "
                  f"(see --list-rules)", file=sys.stderr)
            return 2

    t0 = time.monotonic()
    ctx = Context(_resolve_paths(args.paths) if args.paths else None)
    findings = ctx.parse_errors() + run_passes(ctx, rules)

    # inline suppressions
    kept: list = []
    suppressed = 0
    for f in findings:
        sf = ctx.by_rel.get(f.file)
        if sf is not None and sf.suppressed(f):
            suppressed += 1
        else:
            kept.append(f)

    # publish roots (opt-in runtime audit)
    for root in args.publish_root:
        from .publish import check_publish_root
        errors, warnings = check_publish_root(root)
        for w in warnings:
            print(f"WARNING: {root}: {w}", file=sys.stderr)
        kept += [
            Finding(file=root, line=1, rule="publish-dir", message=e)
            for e in errors
        ]

    # store roots (opt-in runtime audit of the durable cold tier)
    for root in args.store_root:
        from .publish import check_store_root
        errors, warnings = check_store_root(root)
        for w in warnings:
            print(f"WARNING: {root}: {w}", file=sys.stderr)
        kept += [
            Finding(file=root, line=1, rule="store-dir", message=e)
            for e in errors
        ]

    # baseline
    baselined: list = []
    if args.update_baseline:
        entries = baseline_mod.update(kept)
        print(f"baseline updated: {len(entries)} entr(y/ies) written to "
              f"{os.path.relpath(baseline_mod.BASELINE_PATH, REPO)}")
        return 0
    if not args.no_baseline:
        try:
            entries = baseline_mod.load()
        except baseline_mod.BaselineError as e:
            print(f"ERROR: {e}", file=sys.stderr)
            return 2
        kept, baselined, stale = baseline_mod.apply(kept, entries)
        kept += stale

    # incremental mode: only touched lines.  stale-baseline findings
    # survive the filter (a stale entry is a whole-repo invariant), and
    # so does a parse error in any touched file — a mid-edit syntax
    # error reported at line 1 would otherwise vanish whenever line 1
    # itself wasn't part of the diff.
    if args.changed is not None:
        touched = _changed_lines(args.changed)
        kept = [
            f for f in kept
            if f.rule == "stale-baseline"
            or (f.rule == "parse-error" and f.file in touched)
            or f.line in touched.get(f.file, ())
        ]

    kept.sort()
    elapsed = time.monotonic() - t0
    if args.json:
        print(json.dumps([f.to_dict() for f in kept], indent=2))
    else:
        for f in kept:
            print(f)
        scope = f"{len(ctx.files)} file(s)"
        if args.changed is not None:
            scope += f", changed vs {args.changed}"
        print(
            f"pbox-lint: {len(kept)} finding(s) ({suppressed} suppressed "
            f"inline, {len(baselined)} baselined) over {scope} "
            f"in {elapsed:.2f}s",
            file=sys.stderr,
        )
    return 1 if kept else 0
