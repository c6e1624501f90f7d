"""Shared ARCHITECTURE.md catalog scraping and source discovery.

The five original ``tools/check_*.py`` guards each re-implemented the
same three pieces: walking ``paddlebox_tpu/`` for source files, scraping backticked first-column names out of an ARCHITECTURE.md
section's table, and turning a regex match offset into a ``file:line``
string.  This module is the single home for all three; the drift passes
(rules_drift.py) and the thin legacy wrappers both build on it.
"""

from __future__ import annotations

import os
import re

from .core import REPO

ARCH = os.path.join(REPO, "ARCHITECTURE.md")
README = os.path.join(REPO, "README.md")

#: the roots the legacy guards scan — the shipped package, deliberately
#: NOT tools/ (the guards' own regex fixture strings would self-trigger).
GUARD_ROOTS = ("paddlebox_tpu",)

# backticked names in a catalog table's first column
_TABLE_ROW_RE = re.compile(r"^\|\s*`([^`]+)`")


def source_files(roots=GUARD_ROOTS, repo: str = REPO, extra=()) -> list:
    """Every .py file under the given roots (roots may be files), sorted,
    plus any ``extra`` paths verbatim (the synthetic-fixture hook the
    fault-site self-test uses)."""
    files: list = []
    for root in roots:
        path = os.path.join(repo, root)
        if path.endswith(".py"):
            files.append(path)
            continue
        for d, dirs, fs in os.walk(path):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            files += [os.path.join(d, f) for f in fs if f.endswith(".py")]
    return sorted(files) + [os.path.abspath(p) for p in extra]


def line_of(text: str, pos: int) -> int:
    """1-based line number of a character offset (regex match start)."""
    return text.count("\n", 0, pos) + 1


def normalize_name(name: str, is_fstring: bool = False) -> str:
    """Collapse dynamic segments to ``*``: f-string ``{expr}`` holes in
    code names, ``<x>`` placeholders in catalog rows — so a dynamic
    family ("retry.<site>.calls") stays one catalog row."""
    if is_fstring:
        name = re.sub(r"\{[^}]*\}", "*", name)
    return re.sub(r"<[^>]*>", "*", name)


def table_patterns(section: str, path: str = ARCH) -> dict:
    """{glob pattern: '<doc>:line'} for every backticked first-column
    table name under the ``## <section>`` heading (prefix-matched,
    case-insensitive).  ``<x>`` placeholders normalize to ``*``."""
    pats: dict = {}
    in_sec = False
    rel = os.path.basename(path)
    with open(path, encoding="utf-8") as fh:
        for i, line in enumerate(fh, 1):
            if line.startswith("## "):
                in_sec = line.strip().lower().startswith(
                    "## " + section.lower())
                continue
            if not in_sec:
                continue
            m = _TABLE_ROW_RE.match(line.strip())
            if m:
                pats.setdefault(normalize_name(m.group(1)), f"{rel}:{i}")
    return pats


def scan_literal_calls(call_re: re.Pattern, roots=GUARD_ROOTS,
                       repo: str = REPO, name_filter=None) -> dict:
    """{normalized literal first-arg: first 'file:line' seen} over every
    source file, for call-site regexes shaped like the metric/span ones:
    group 1 = optional ``f`` prefix, group 3 = the literal text."""
    found: dict = {}
    for path in source_files(roots, repo):
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        rel = os.path.relpath(path, repo)
        for m in call_re.finditer(text):
            name = normalize_name(m.group(3), is_fstring=bool(m.group(1)))
            if name_filter is not None and not name_filter(name):
                continue
            found.setdefault(name, f"{rel}:{line_of(text, m.start())}")
    return found
