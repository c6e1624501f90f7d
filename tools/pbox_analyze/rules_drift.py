"""Catalog-drift passes: metric names, fault sites, env flags, span names.

The four cross-checks that used to live as four standalone
``tools/check_*.py`` scripts, each with its own copy of the source
walker, the table scraper, and the offset→line math — now one module on
top of catalog.py.  The original scripts remain as thin wrappers (their
CLIs and test-visible functions are load-bearing), delegating here.

These passes scan text with regexes rather than the AST: metric/span
names live inside f-strings and comments as much as calls, and the env
check deliberately reads *prose* (a comment citing a stale flag name
should fail too).  They share the Context only for suppression and
reporting; their file set is the guard roots (the package), not the
analyzer roots.
"""

from __future__ import annotations

import ast
import fnmatch
import os
import re

from . import catalog
from .core import REPO, Context, Finding, cached_walk

RULES = {
    "metric-name-drift": (
        "metric created in code but missing from the ARCHITECTURE.md "
        "Observability catalog"
    ),
    "fault-site-drift": (
        "fault site used but not in KNOWN_SITES, or cataloged but never "
        "used"
    ),
    "env-flag-drift": (
        "PBOX_* env var read but undocumented, or documented but gone"
    ),
    "span-name-drift": (
        "span recorded but missing from the tracing catalog, or "
        "cataloged but never recorded"
    ),
    "health-rule-drift": (
        "health rule in telemetry/health.py but missing from the "
        "ARCHITECTURE.md Run health table, or documented but gone"
    ),
}

FAULTS_PY = os.path.join(REPO, "paddlebox_tpu", "utils", "faults.py")
CONFIG_PY = os.path.join(REPO, "paddlebox_tpu", "config.py")
HEALTH_PY = os.path.join(REPO, "paddlebox_tpu", "telemetry", "health.py")

# -- metric names ----------------------------------------------------------- #
_METRIC_CALL_RE = re.compile(
    r"""\b(?:stats\.(?:add|set)|counter|gauge|histogram)\(\s*
        (f?)(["'])([^"']+)\2""",
    re.VERBOSE | re.DOTALL,
)


def metric_scan_sources() -> dict:
    """{normalized metric name pattern: first 'file:line' seen}."""
    return catalog.scan_literal_calls(
        _METRIC_CALL_RE,
        name_filter=lambda name: bool(re.search(r"[a-zA-Z]", name)),
    )


def metric_catalog_patterns() -> list:
    """Glob patterns from the ARCHITECTURE.md metric catalog."""
    return list(catalog.table_patterns("observability"))


def metric_missing() -> list:
    """[(name, where)] for call-site names no catalog row covers."""
    pats = metric_catalog_patterns()
    missing = []
    for name, where in sorted(metric_scan_sources().items()):
        # placeholders in the code name become a concrete dummy segment
        # so glob matching runs pattern-vs-string, not pattern-vs-pattern
        concrete = name.replace("*", "ANY")
        if not any(fnmatch.fnmatchcase(concrete, p) for p in pats):
            missing.append((name, where))
    return missing


# -- fault sites ------------------------------------------------------------ #
# literal site uses: inject("x") / fire("x") / site="x".  The name must
# be the WHOLE first argument — a literal that continues with '+' is a
# dynamic-prefix construction, collected separately.
_SITE_USE_RE = re.compile(
    r"""\b(?:faults\.)?(?:inject|fire)\(\s*(["'])([^"']+)\1\s*[,)]
      | \bsite\s*=\s*(["'])([^"']+)\3\s*[,)\n]""",
    re.VERBOSE,
)
_SITE_DYN_RE = re.compile(
    r"""\b(?:faults\.)?(?:inject|fire)\(\s*(["'])([^"']+)\1\s*\+""",
    re.VERBOSE,
)
_SITE_REGISTER_RE = re.compile(
    r"""\bregister_site\(\s*(["'])([^"']+)\1\s*\)""",
    re.VERBOSE,
)


def fault_known_sites() -> set:
    """KNOWN_SITES parsed statically out of utils/faults.py (no package
    import: the tool must run on a bare checkout)."""
    tree = ast.parse(open(FAULTS_PY).read())
    for node in cached_walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "KNOWN_SITES":
                    return set(ast.literal_eval(node.value))
    raise SystemExit(f"ERROR: no KNOWN_SITES literal found in {FAULTS_PY}")


def fault_scan_sources(extra=()):
    """(used, dynamic_prefixes, registered), each {name: 'file:line'}."""
    used: dict = {}
    prefixes: dict = {}
    registered: dict = {}
    for path in catalog.source_files(extra=extra):
        text = open(path).read()
        rel = os.path.relpath(path, REPO)

        def note(out, name, start):
            out.setdefault(name, f"{rel}:{catalog.line_of(text, start)}")

        for m in _SITE_USE_RE.finditer(text):
            note(used, m.group(2) or m.group(4), m.start())
        for m in _SITE_DYN_RE.finditer(text):
            note(prefixes, m.group(2), m.start())
        for m in _SITE_REGISTER_RE.finditer(text):
            note(registered, m.group(2), m.start())
    return used, prefixes, registered


def fault_check(extra=(), known_sites_fn=fault_known_sites) -> tuple:
    """(unknown, orphaned) drift lists: [(site, where), ...]."""
    known = known_sites_fn()
    used, prefixes, registered = fault_scan_sources(extra)
    unknown = sorted(
        (site, where) for site, where in used.items()
        if site not in known and site not in registered
    )
    reachable = set(used) | set(registered)
    orphaned = sorted(
        (site, "utils/faults.py KNOWN_SITES") for site in known
        if site not in reachable
        and not any(site.startswith(p) for p in prefixes)
    )
    return unknown, orphaned


# -- env flags -------------------------------------------------------------- #
# a real var name: PBOX_ + at least one more segment ("PBOX_<NAME>"-style
# placeholder prose matches nothing)
_VAR_RE = re.compile(r"PBOX_[A-Z][A-Z0-9_]*")


def env_flag_vars() -> dict:
    """{PBOX_<NAME>: 'config.py:_Flags._DEFAULTS'} parsed statically out
    of the flag shim."""
    tree = ast.parse(open(CONFIG_PY).read())
    for node in cached_walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "_DEFAULTS":
                    return {
                        "PBOX_" + ast.literal_eval(k).upper():
                            "paddlebox_tpu/config.py:_Flags._DEFAULTS"
                        for k in node.value.keys
                    }
    raise SystemExit(f"ERROR: no _DEFAULTS literal found in {CONFIG_PY}")


def env_referenced_vars() -> dict:
    """Flag-shim entries + every literal PBOX_* token in the sources."""
    found = dict(env_flag_vars())
    for path in catalog.source_files():
        text = open(path).read()
        rel = os.path.relpath(path, REPO)
        for m in _VAR_RE.finditer(text):
            found.setdefault(
                m.group(0), f"{rel}:{catalog.line_of(text, m.start())}")
    return found


def env_documented_vars() -> dict:
    """{var: first 'doc:line' seen} across ARCHITECTURE.md + README.md."""
    found: dict = {}
    for path in (catalog.ARCH, catalog.README):
        if not os.path.exists(path):
            continue
        text = open(path).read()
        rel = os.path.relpath(path, REPO)
        for m in _VAR_RE.finditer(text):
            found.setdefault(
                m.group(0), f"{rel}:{catalog.line_of(text, m.start())}")
    return found


def env_check(referenced_fn=env_referenced_vars,
              documented_fn=env_documented_vars) -> tuple:
    """(undocumented, stale) drift lists: [(var, where), ...].  The two
    scanners are injectable so the legacy wrapper's tests can
    monkeypatch them at its module level."""
    referenced = referenced_fn()
    documented = documented_fn()
    undocumented = sorted(
        (var, where) for var, where in referenced.items()
        if var not in documented
    )
    stale = sorted(
        (var, where) for var, where in documented.items()
        if var not in referenced
    )
    return undocumented, stale


# -- span names ------------------------------------------------------------- #
_SPAN_CALL_RE = re.compile(
    r"""\b(?:span|add_span|instant)\(\s*
        (f?)(["'])([^"']+)\2""",
    re.VERBOSE | re.DOTALL,
)


def _span_name_filter(name: str) -> bool:
    # skip docstring/prose fragments; a real span name is dotted-or-bare
    # lowercase identifier text, and "name" is the docs' placeholder
    return bool(re.fullmatch(r"[a-z0-9_.*]+", name)) and name != "name"


def span_scan_sources() -> dict:
    """{normalized span name: first 'file:line' seen}."""
    return catalog.scan_literal_calls(
        _SPAN_CALL_RE, name_filter=_span_name_filter)


def span_catalog_patterns() -> dict:
    """{glob pattern: 'ARCHITECTURE.md:line'} from the span catalog."""
    return catalog.table_patterns("distributed tracing")


def span_check() -> tuple:
    """(missing, stale, found, pats) exactly as the legacy tool shaped
    it (both directions checked)."""
    found = span_scan_sources()
    pats = span_catalog_patterns()
    missing = []
    for name, where in sorted(found.items()):
        concrete = name.replace("*", "ANY")
        if not any(fnmatch.fnmatchcase(concrete, p) for p in pats):
            missing.append((name, where))
    stale = []
    for pat, where in sorted(pats.items()):
        if not any(
            fnmatch.fnmatchcase(name.replace("*", "ANY"), pat)
            for name in found
        ):
            stale.append((pat, where))
    return missing, stale, found, pats


# -- health rules ----------------------------------------------------------- #
def health_rule_names() -> dict:
    """{rule name: 'telemetry/health.py:line'} parsed statically out of
    the _RULE_SPECS literal (no package import — same discipline as
    KNOWN_SITES / _DEFAULTS)."""
    text = open(HEALTH_PY).read()
    tree = ast.parse(text)
    for node in cached_walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "_RULE_SPECS":
                    specs = ast.literal_eval(node.value)
                    return {
                        spec["name"]:
                            f"paddlebox_tpu/telemetry/health.py:"
                            f"{node.lineno}"
                        for spec in specs
                    }
    raise SystemExit(f"ERROR: no _RULE_SPECS literal found in {HEALTH_PY}")


def health_catalog_patterns() -> dict:
    """{glob pattern: 'ARCHITECTURE.md:line'} from the Run health rule
    table."""
    return catalog.table_patterns("run health")


def health_check() -> tuple:
    """(missing, stale) drift lists, both directions: every _RULE_SPECS
    rule needs a Run-health table row, every row must name a live rule."""
    names = health_rule_names()
    pats = health_catalog_patterns()
    missing = []
    for name, where in sorted(names.items()):
        concrete = name.replace("*", "ANY")
        if not any(fnmatch.fnmatchcase(concrete, p) for p in pats):
            missing.append((name, where))
    stale = []
    for pat, where in sorted(pats.items()):
        if not any(
            fnmatch.fnmatchcase(name.replace("*", "ANY"), pat)
            for name in names
        ):
            stale.append((pat, where))
    return missing, stale


# -- the pass --------------------------------------------------------------- #
def _finding(ctx: Context, rule: str, where: str, message: str) -> Finding:
    file, _, line = where.partition(":")
    lineno = int(line) if line.isdigit() else 1
    sf = ctx.by_rel.get(file)
    snippet = sf.line_text(lineno) if sf else ""
    return Finding(file=file, line=lineno, rule=rule,
                   message=message, snippet=snippet)


def run(ctx: Context) -> list:
    findings: list = []
    for name, where in metric_missing():
        findings.append(_finding(
            ctx, "metric-name-drift", where,
            f"metric {name!r} has no row in the ARCHITECTURE.md "
            "Observability catalog",
        ))
    unknown, orphaned = fault_check()
    for site, where in unknown:
        findings.append(_finding(
            ctx, "fault-site-drift", where,
            f"fault site {site!r} used here but missing from "
            "utils.faults.KNOWN_SITES",
        ))
    for site, where in orphaned:
        findings.append(_finding(
            ctx, "fault-site-drift", "paddlebox_tpu/utils/faults.py:1",
            f"KNOWN_SITES entry {site!r} is referenced by no call site "
            "(plans naming it can never fire)",
        ))
    undocumented, stale = env_check()
    for var, where in undocumented:
        findings.append(_finding(
            ctx, "env-flag-drift", where,
            f"{var} is read by the package but documented nowhere",
        ))
    for var, where in stale:
        findings.append(_finding(
            ctx, "env-flag-drift", where,
            f"{var} is documented but referenced nowhere (dead knob)",
        ))
    missing, stale_spans, _, _ = span_check()
    for name, where in missing:
        findings.append(_finding(
            ctx, "span-name-drift", where,
            f"span {name!r} has no row in the ARCHITECTURE.md tracing "
            "catalog",
        ))
    for pat, where in stale_spans:
        findings.append(_finding(
            ctx, "span-name-drift", where,
            f"span catalog row {pat!r} matches no recorded span",
        ))
    h_missing, h_stale = health_check()
    for name, where in h_missing:
        findings.append(_finding(
            ctx, "health-rule-drift", where,
            f"health rule {name!r} has no row in the ARCHITECTURE.md "
            "Run health table",
        ))
    for pat, where in h_stale:
        findings.append(_finding(
            ctx, "health-rule-drift", where,
            f"Run health table row {pat!r} names no rule in "
            "telemetry/health.py _RULE_SPECS",
        ))
    return findings
