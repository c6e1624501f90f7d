#!/usr/bin/env python
"""Env-flag drift check: every PBOX_* var the package reads must be
documented, and every documented PBOX_* var must still exist.

Thin wrapper: the implementation moved into the pbox-lint framework
(tools/pbox_analyze/rules_drift.py, rule ``env-flag-drift``).  This CLI
and its module-level functions are preserved for tier-1 tests and docs.

referenced − documented = undocumented flags (fail); documented −
referenced = stale docs (fail).

Usage:
    python tools/check_env_flags.py            # check, exit 1 on drift
    python tools/check_env_flags.py --list     # dump what was found
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from pbox_analyze import rules_drift  # noqa: E402


def flag_vars() -> dict:
    """{PBOX_<NAME>: 'config.py:_Flags._DEFAULTS'} parsed statically out
    of the flag shim (no package import: must run on a bare checkout)."""
    return rules_drift.env_flag_vars()


def referenced_vars() -> dict:
    """{var: first 'file:line' seen}: flag-shim entries + every literal
    PBOX_* token in the package source."""
    return rules_drift.env_referenced_vars()


def documented_vars() -> dict:
    """{var: first 'doc:line' seen} across ARCHITECTURE.md + README.md."""
    return rules_drift.env_documented_vars()


def check() -> tuple:
    """(undocumented, stale) drift lists: [(var, where), ...]."""
    # late-bound module globals: tests monkeypatch referenced_vars /
    # documented_vars on THIS module and expect check() to honor it
    return rules_drift.env_check(
        referenced_fn=lambda: referenced_vars(),
        documented_fn=lambda: documented_vars(),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--list", action="store_true",
                    help="print every discovered env var and exit 0")
    args = ap.parse_args(argv)
    if args.list:
        documented = documented_vars()
        for var, where in sorted(referenced_vars().items()):
            mark = " " if var in documented else "!"
            print(f"{mark} {var:36s} {where}")
        return 0
    undocumented, stale = check()
    rc = 0
    if undocumented:
        print("PBOX_* env vars the package reads but no doc names "
              "(add a row to ARCHITECTURE.md '## Environment flags'):",
              file=sys.stderr)
        for var, where in undocumented:
            print(f"  {var}  ({where})", file=sys.stderr)
        rc = 1
    if stale:
        print("PBOX_* env vars documented but referenced nowhere "
              "(stale docs — operators would chase dead knobs):",
              file=sys.stderr)
        for var, where in stale:
            print(f"  {var}  ({where})", file=sys.stderr)
        rc = 1
    if rc:
        print(f"{len(undocumented)} undocumented + {len(stale)} stale; "
              "fix the catalog or the code.", file=sys.stderr)
    else:
        print(f"env-flag catalog OK: {len(referenced_vars())} referenced "
              f"var(s), all documented, no stale doc entries")
    return rc


if __name__ == "__main__":
    sys.exit(main())
