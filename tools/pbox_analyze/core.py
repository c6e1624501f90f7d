"""Framework core: Finding schema, parsed-file cache, suppressions, and
the per-class concurrency model the lock/thread passes share.

Everything here is stdlib-only and import-free of the package under
analysis: the tool must run on a bare checkout (no jax, no numpy) and
finish in seconds, so each file is read and parsed exactly once and
every pass walks the same cached tree.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# memoized subtree walks: ten-odd passes each re-walk the same module and
# function subtrees; one materialization per root serves them all (the
# single biggest term in the --all wall-time budget).  Entries pin a
# strong reference to their root node, so an id() can never be reused
# while its entry lives; the cache is bounded by a coarse clear so a
# long-lived test session over many small fixture Contexts cannot grow
# it without bound.
_WALK_CACHE: dict = {}
_WALK_CACHE_MAX = 1 << 20


def cached_walk(node: "ast.AST"):
    """ast.walk(node) as a memoized tuple (identical node order)."""
    key = id(node)
    hit = _WALK_CACHE.get(key)
    if hit is not None and hit[0] is node:
        return hit[1]
    if len(_WALK_CACHE) > _WALK_CACHE_MAX:
        _WALK_CACHE.clear()
    nodes = tuple(ast.walk(node))
    _WALK_CACHE[key] = (node, nodes)
    return nodes

#: what ``--all`` analyzes: the package and the tools themselves.
#: tests/ is deliberately out — test code wedges threads and swallows
#: exceptions on purpose.
DEFAULT_ROOTS = ("paddlebox_tpu", "tools")

_SUPPRESS_RE = re.compile(
    r"#\s*pbox-lint:\s*ignore\[([a-z0-9_\-, ]+)\]\s*(.*)"
)


@dataclass(frozen=True, order=True)
class Finding:
    """One defect at one source location.  ``snippet`` (the stripped
    source line) is the stable identity baseline matching keys on —
    line numbers drift, code text doesn't."""

    file: str  # repo-relative path
    line: int  # 1-based
    rule: str
    message: str = field(compare=False)
    snippet: str = ""

    @property
    def key(self):
        return (self.rule, self.file, self.snippet)

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "file": self.file,
            "line": self.line,
            "message": self.message,
            "snippet": self.snippet,
        }

    def __str__(self) -> str:
        return f"{self.file}:{self.line}: {self.rule}: {self.message}"


class SourceFile:
    """One parsed source file: text, lines, AST with parent links, and
    the inline suppression table."""

    def __init__(self, path: str, repo: str = REPO):
        self.path = path
        self.rel = os.path.relpath(path, repo)
        with open(path, encoding="utf-8") as fh:
            self.text = fh.read()
        self.lines = self.text.splitlines()
        self.parse_error: str | None = None
        try:
            self.tree = ast.parse(self.text)
        except SyntaxError as e:  # surfaced as a finding by the runner
            self.tree = ast.Module(body=[], type_ignores=[])
            self.parse_error = f"syntax error: {e.msg} (line {e.lineno})"
        self._parents: dict | None = None
        # {lineno: set(rule ids)} — a marker on a code line covers that
        # line; on a comment-only line it covers the next code line
        # (skipping the rest of the comment block, so a multi-line
        # reason still lands on the code it justifies).
        self.suppressions: dict = {}
        for i, line in enumerate(self.lines, 1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            target = i
            if line[: m.start()].strip() == "":
                target = i + 1
                while target <= len(self.lines):
                    t = self.lines[target - 1].strip()
                    if t and not t.startswith("#"):
                        break
                    target += 1
            self.suppressions.setdefault(target, set()).update(rules)

    # -- helpers ----------------------------------------------------------- #
    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, rule: str, node_or_line, message: str) -> Finding:
        line = getattr(node_or_line, "lineno", node_or_line)
        return Finding(
            file=self.rel, line=line, rule=rule, message=message,
            snippet=self.line_text(line),
        )

    def suppressed(self, finding: Finding) -> bool:
        return finding.rule in self.suppressions.get(finding.line, ())

    def parent(self, node: ast.AST):
        if self._parents is None:
            self._parents = {}
            for parent in cached_walk(self.tree):
                for child in ast.iter_child_nodes(parent):
                    self._parents[child] = parent
        return self._parents.get(node)


class Context:
    """The shared walker state one analysis run operates on: every file
    parsed once, addressable by repo-relative path."""

    def __init__(self, paths=None, repo: str = REPO):
        self.repo = repo
        if paths is None:
            paths = discover_files(repo, DEFAULT_ROOTS)
        self.files = [SourceFile(p, repo) for p in sorted(paths)]
        self.by_rel = {sf.rel: sf for sf in self.files}
        # scratch space for pass-private memos (rank-taint tables,
        # collective-sequence summaries, ...) so interprocedural passes
        # stay inside the wall-time budget without new attributes per
        # pass.  Passes key by their own module name.
        self.caches: dict = {}

    def parse_errors(self) -> list:
        return [
            sf.finding("parse-error", 1, sf.parse_error)
            for sf in self.files
            if sf.parse_error
        ]


def discover_files(repo: str = REPO, roots=DEFAULT_ROOTS) -> list:
    """Every .py file under the given roots (roots may be files)."""
    out: list = []
    for root in roots:
        path = os.path.join(repo, root)
        if os.path.isfile(path):
            out.append(path)
            continue
        for d, dirs, fs in os.walk(path):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            out.extend(os.path.join(d, f) for f in fs if f.endswith(".py"))
    return sorted(out)


# --------------------------------------------------------------------------- #
# name resolution helpers shared by several passes
# --------------------------------------------------------------------------- #
def dotted(node) -> str:
    """'jax.lax.scan' for nested Attribute/Name chains, '' otherwise."""
    parts: list = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


#: constructors whose instances are themselves synchronization points or
#: thread-safe containers — attributes bound to these are exempt from
#: the thread-shared-state rule.
SYNC_CTORS = {
    "Lock", "RLock", "Condition", "Event", "Semaphore",
    "BoundedSemaphore", "Barrier", "Queue", "LifoQueue", "PriorityQueue",
    "SimpleQueue", "deque", "local", "Thread", "ThreadPoolExecutor",
}
LOCK_CTORS = {"Lock": "lock", "RLock": "rlock", "Condition": "cond"}


def _ctor_name(value) -> str:
    """Constructor base name for ``x = threading.Lock()`` shapes."""
    if isinstance(value, ast.Call):
        name = dotted(value.func)
        return name.rsplit(".", 1)[-1] if name else ""
    return ""


@dataclass
class ClassModel:
    """The concurrency-relevant surface of one class (or of the module
    itself, modeled as a pseudo-class for module-level locks/functions)."""

    name: str
    node: ast.AST
    is_module: bool = False
    lock_attrs: dict = field(default_factory=dict)   # attr -> lock|rlock|cond
    sync_attrs: set = field(default_factory=set)     # incl. events/queues
    thread_attrs: set = field(default_factory=set)   # bound to Thread(...)
    methods: dict = field(default_factory=dict)      # name -> FunctionDef
    thread_targets: set = field(default_factory=set)  # method names

    def is_lock_name(self, expr) -> str | None:
        """The canonical lock id this expression names, if any: a
        ``self.X`` attribute or (module model) a bare name."""
        if (
            not self.is_module
            and isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"
            and expr.attr in self.lock_attrs
        ):
            return expr.attr
        if self.is_module and isinstance(expr, ast.Name) \
                and expr.id in self.lock_attrs:
            return expr.id
        return None

    def lock_kind(self, lock_id: str) -> str:
        return self.lock_attrs.get(lock_id, "lock")

    def reachable_from(self, entry_points) -> set:
        """Method names transitively reachable from the given methods
        via self.<m>() calls — the 'runs on the thread path' closure."""
        seen: set = set()
        stack = [m for m in entry_points if m in self.methods]
        while stack:
            name = stack.pop()
            if name in seen:
                continue
            seen.add(name)
            for node in cached_walk(self.methods[name]):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"
                    and node.func.attr in self.methods
                ):
                    stack.append(node.func.attr)
        return seen


def _scan_attr_bindings(model: ClassModel, tree) -> None:
    """Collect self.X = <ctor>() bindings and Thread(target=self.m)."""
    for node in cached_walk(tree):
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            value = node.value
            ctor = _ctor_name(value)
            for t in targets:
                attr = None
                if (
                    not model.is_module
                    and isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ):
                    attr = t.attr
                elif model.is_module and isinstance(t, ast.Name):
                    attr = t.id
                if attr is None:
                    continue
                if ctor in LOCK_CTORS:
                    model.lock_attrs[attr] = LOCK_CTORS[ctor]
                    model.sync_attrs.add(attr)
                elif ctor in SYNC_CTORS:
                    model.sync_attrs.add(attr)
                    if ctor == "Thread":
                        model.thread_attrs.add(attr)
        if isinstance(node, ast.Call) and \
                _ctor_name(node) == "Thread":
            for kw in node.keywords:
                if kw.arg != "target":
                    continue
                tgt = kw.value
                if (
                    isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"
                ):
                    model.thread_targets.add(tgt.attr)
                elif isinstance(tgt, ast.Name):
                    model.thread_targets.add(tgt.id)


def class_models(sf: SourceFile) -> list:
    """ClassModels for every class in the file, plus one module-level
    pseudo-model (bare functions + module locks) as the last element.
    Cached per SourceFile — three passes share one scan."""
    cached = getattr(sf, "_class_models", None)
    if cached is not None:
        return cached
    models: list = []
    for node in sf.tree.body:
        if isinstance(node, ast.ClassDef):
            cm = ClassModel(name=node.name, node=node)
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    cm.methods[item.name] = item
            _scan_attr_bindings(cm, node)
            models.append(cm)
    mod = ClassModel(name="<module>", node=sf.tree, is_module=True)
    for node in sf.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            mod.methods[node.name] = node
    _scan_attr_bindings(mod, sf.tree)
    # module functions can also spawn threads targeting module functions
    models.append(mod)
    sf._class_models = models
    return models
