"""pbox-lint (tools/pbox_analyze/): the concurrency- and JAX-aware
static-analysis framework.

Per rule: a good fixture (no finding), a bad fixture (finding at the
expected line), a suppressed fixture (inline ``# pbox-lint: ignore``),
and — once — a baselined fixture.  Plus the framework plumbing
(suppression placement, baseline schema/order/staleness hygiene,
--changed line filtering) and the tier-1 gate: zero non-baselined
findings over the repo's default roots.
"""

import json
import os
import resource
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
CLI = os.path.join(TOOLS, "pbox_analyze.py")

sys.path.insert(0, TOOLS)

from pbox_analyze import baseline as baseline_mod  # noqa: E402
from pbox_analyze import (  # noqa: E402
    rules_clock,
    rules_except,
    rules_locks,
    rules_threads,
    rules_tracer,
)
from pbox_analyze.core import Context, SourceFile  # noqa: E402


def _ctx(tmp_path, source: str) -> Context:
    path = tmp_path / "fixture.py"
    path.write_text(textwrap.dedent(source))
    return Context(paths=[str(path)], repo=str(tmp_path))


def _run(mod, tmp_path, source: str):
    ctx = _ctx(tmp_path, source)
    findings = mod.run(ctx)
    # apply inline suppressions the way the CLI does
    return [
        f for f in findings
        if not ctx.by_rel[f.file].suppressed(f)
    ]


# --------------------------------------------------------------------------- #
# swallowed-exception
# --------------------------------------------------------------------------- #
BAD_EXCEPT = """\
    def f():
        try:
            risky()
        except Exception:
            pass
"""


def test_swallowed_exception_bad(tmp_path):
    (finding,) = _run(rules_except, tmp_path, BAD_EXCEPT)
    assert finding.rule == "swallowed-exception"
    assert finding.line == 4


@pytest.mark.parametrize("body", [
    "raise",                                # re-raise
    "logger.warning('x', exc_info=True)",   # log
    "stats.add('x.errors')",                # counter
    "flight.dump_now('boom')",              # flight dump
    "print('x')",                           # stderr surfacing
])
def test_swallowed_exception_good(tmp_path, body):
    src = BAD_EXCEPT.replace("pass", body)
    assert _run(rules_except, tmp_path, src) == []


def test_swallowed_exception_stored_latch_good(tmp_path):
    src = """\
        def f(self):
            try:
                risky()
            except BaseException as e:
                self._err = e
    """
    assert _run(rules_except, tmp_path, src) == []


def test_narrow_except_is_not_flagged(tmp_path):
    src = BAD_EXCEPT.replace("Exception", "ValueError")
    assert _run(rules_except, tmp_path, src) == []


def test_swallowed_exception_suppressed(tmp_path):
    src = BAD_EXCEPT.replace(
        "except Exception:",
        "# pbox-lint: ignore[swallowed-exception] fixture reason\n"
        "    except Exception:",
    )
    assert _run(rules_except, tmp_path, src) == []


def test_multiline_reason_comment_still_covers_the_site(tmp_path):
    src = BAD_EXCEPT.replace(
        "except Exception:",
        "# pbox-lint: ignore[swallowed-exception] a reason so long it\n"
        "    # wraps onto a second comment line before the code\n"
        "    except Exception:",
    )
    assert _run(rules_except, tmp_path, src) == []


# --------------------------------------------------------------------------- #
# clock-misuse
# --------------------------------------------------------------------------- #
def test_clock_misuse_literal_deadline(tmp_path):
    src = """\
        import time
        deadline = time.time() + 10.0
    """
    (finding,) = _run(rules_clock, tmp_path, src)
    assert finding.rule == "clock-misuse"
    assert finding.line == 2


def test_clock_misuse_timeout_name_and_compare(tmp_path):
    src = """\
        import time
        state = {"deadline": time.time() + hang_timeout}
        if time.time() > state["deadline"]:
            boom()
    """
    lines = {f.line for f in _run(rules_clock, tmp_path, src)}
    assert lines == {2, 3}


def test_clock_wallclock_timestamps_are_legal(tmp_path):
    src = """\
        import time
        published_at = time.time()
        lag = time.time() - rec.event_ts
        fresh = time.time() - oldest
    """
    assert _run(rules_clock, tmp_path, src) == []


def test_clock_misuse_suppressed(tmp_path):
    src = """\
        import time
        # pbox-lint: ignore[clock-misuse] fixture reason
        deadline = time.time() + 10.0
    """
    assert _run(rules_clock, tmp_path, src) == []


# --------------------------------------------------------------------------- #
# lock-order / lock-held-blocking
# --------------------------------------------------------------------------- #
LOCK_CYCLE = """\
    import threading

    class Gate:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._b:
                with self._a:
                    pass
"""


def test_lock_order_cycle(tmp_path):
    findings = _run(rules_locks, tmp_path, LOCK_CYCLE)
    assert {f.rule for f in findings} == {"lock-order"}
    assert {f.line for f in findings} == {10, 15}


def test_lock_order_consistent_is_legal(tmp_path):
    src = LOCK_CYCLE.replace(
        "with self._b:\n                with self._a:",
        "with self._a:\n                with self._b:",
    )
    assert "def two" in src and src.count("with self._a:") == 2
    assert _run(rules_locks, tmp_path, src) == []


def test_lock_order_interprocedural(tmp_path):
    src = """\
        import threading

        class Gate:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def outer(self):
                with self._a:
                    self.inner()

            def inner(self):
                with self._b:
                    pass

            def reversed(self):
                with self._b:
                    with self._a:
                        pass
    """
    assert any(
        f.rule == "lock-order"
        for f in _run(rules_locks, tmp_path, src)
    )


BLOCKING = """\
    import threading
    import time

    class Gate:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition()
            self.sock = None

        def bad(self):
            with self._lock:
                time.sleep(1.0)
                self.sock.recv(4096)
                self._cond.wait()

        def good(self):
            with self._cond:
                self._cond.wait()
            time.sleep(1.0)
"""


def test_lock_held_blocking(tmp_path):
    findings = _run(rules_locks, tmp_path, BLOCKING)
    assert {f.rule for f in findings} == {"lock-held-blocking"}
    assert {f.line for f in findings} == {12, 13, 14}


def test_lock_held_blocking_suppressed(tmp_path):
    src = BLOCKING.replace(
        "time.sleep(1.0)\n                self.sock.recv",
        "time.sleep(1.0)  # pbox-lint: ignore[lock-held-blocking] reason\n"
        "                self.sock.recv",
    )
    assert "ignore[lock-held-blocking]" in src
    lines = {f.line for f in _run(rules_locks, tmp_path, src)}
    assert lines == {13, 14}  # only the sleep was waved through


# --------------------------------------------------------------------------- #
# thread-shared-state
# --------------------------------------------------------------------------- #
SHARED = """\
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            self.count += 1

        def read(self):
            return self.count
"""


def test_thread_shared_state_bad(tmp_path):
    (finding,) = _run(rules_threads, tmp_path, SHARED)
    assert finding.rule == "thread-shared-state"
    assert finding.line == 10
    assert "count" in finding.message


def test_thread_shared_state_locked_is_legal(tmp_path):
    src = SHARED.replace(
        "def _loop(self):\n        self.count += 1",
        "def _loop(self):\n        with self._lock:\n"
        "            self.count += 1",
    ).replace(
        "return self.count",
        "with self._lock:\n            return self.count",
    )
    assert _run(rules_threads, tmp_path, src) == []


def test_thread_shared_state_sync_attrs_exempt(tmp_path):
    src = """\
        import threading

        class Worker:
            def __init__(self):
                self._stop = threading.Event()
                self._thread = threading.Thread(target=self._loop)

            def _loop(self):
                self._stop.wait(1.0)

            def close(self):
                self._stop.set()
                self._thread = None
    """
    assert _run(rules_threads, tmp_path, src) == []


def test_thread_shared_state_suppressed(tmp_path):
    src = SHARED.replace(
        "self.count += 1",
        "# pbox-lint: ignore[thread-shared-state] fixture reason\n"
        "        self.count += 1",
    )
    assert _run(rules_threads, tmp_path, src) == []


# --------------------------------------------------------------------------- #
# jax-tracer-safety
# --------------------------------------------------------------------------- #
def test_tracer_host_effect_and_branch(tmp_path):
    src = """\
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            print("trace-time only")
            y = np.asarray(x)
            if x > 0:
                return y
            return -y
    """
    findings = _run(rules_tracer, tmp_path, src)
    assert {f.rule for f in findings} == {"jax-tracer-safety"}
    assert {f.line for f in findings} == {6, 7, 8}


def test_tracer_static_idioms_are_legal(tmp_path):
    src = """\
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def step(x, mask=None):
            k = x.shape[0]
            pad = np.zeros((4,), np.float32)
            if mask is None:
                mask = jnp.ones((k,))
            if k > 128:
                x = x[:128]
            jax.debug.print("ok {}", x)
            return x * mask + pad
    """
    assert _run(rules_tracer, tmp_path, src) == []


def test_tracer_scan_body_by_callsite(tmp_path):
    src = """\
        import jax

        def body(carry, x):
            print("host effect in scan body")
            return carry, x

        def outer(xs):
            return jax.lax.scan(body, 0, xs)
    """
    (finding,) = _run(rules_tracer, tmp_path, src)
    assert finding.line == 4


def test_tracer_untraced_function_is_free(tmp_path):
    src = """\
        def host_loop(x):
            print("fine: nobody traces this")
            if x > 0:
                return 1
    """
    assert _run(rules_tracer, tmp_path, src) == []


def test_tracer_suppressed(tmp_path):
    src = """\
        import jax

        @jax.jit
        def step(x):
            # pbox-lint: ignore[jax-tracer-safety] fixture reason
            print("deliberate trace-time banner")
            return x
    """
    assert _run(rules_tracer, tmp_path, src) == []


# --------------------------------------------------------------------------- #
# suppression plumbing
# --------------------------------------------------------------------------- #
def test_suppression_only_masks_the_named_rule(tmp_path):
    path = tmp_path / "s.py"
    path.write_text(
        "import time\n"
        "# pbox-lint: ignore[swallowed-exception] wrong rule named\n"
        "deadline = time.time() + 10.0\n"
    )
    ctx = Context(paths=[str(path)], repo=str(tmp_path))
    findings = rules_clock.run(ctx)
    assert findings and not ctx.by_rel[findings[0].file].suppressed(
        findings[0])


def test_suppression_multiple_rules_one_marker(tmp_path):
    sf = SourceFile.__new__(SourceFile)  # placement parsing only
    path = tmp_path / "m.py"
    path.write_text(
        "x = 1  # pbox-lint: ignore[rule-a, rule-b] both at once\n")
    sf = SourceFile(str(path), repo=str(tmp_path))
    assert sf.suppressions[1] == {"rule-a", "rule-b"}


# --------------------------------------------------------------------------- #
# baseline hygiene
# --------------------------------------------------------------------------- #
def _entry(rule="clock-misuse", file="a.py", snippet="x = 1", reason="r"):
    return {"rule": rule, "file": file, "snippet": snippet, "reason": reason}


def test_baseline_matches_by_snippet_not_line(tmp_path):
    src = """\
        import time


        deadline = time.time() + 10.0
    """
    ctx = _ctx(tmp_path, src)
    (finding,) = rules_clock.run(ctx)
    entries = [_entry(file="fixture.py",
                      snippet="deadline = time.time() + 10.0")]
    kept, baselined, stale = baseline_mod.apply([finding], entries)
    assert kept == [] and stale == [] and len(baselined) == 1


def test_stale_baseline_entry_is_an_error(tmp_path):
    entries = [_entry(snippet="code that no longer exists")]
    kept, baselined, stale = baseline_mod.apply([], entries)
    assert baselined == [] and len(stale) == 1
    assert stale[0].rule == "stale-baseline"


def test_baseline_schema_rejects_bad_entries(tmp_path):
    for bad in (
        {"rule": "r", "file": "f"},                      # missing keys
        {**_entry(), "extra": 1},                        # unknown key
        {**_entry(), "reason": "   "},                   # empty reason
    ):
        p = tmp_path / "b.json"
        p.write_text(json.dumps([bad]))
        with pytest.raises(baseline_mod.BaselineError):
            baseline_mod.load(str(p))


def test_baseline_must_be_sorted(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps([
        _entry(rule="z-rule"), _entry(rule="a-rule"),
    ]))
    with pytest.raises(baseline_mod.BaselineError):
        baseline_mod.load(str(p))


def test_checked_in_baseline_is_valid():
    # the repo's own baseline must always load (sorted, schema-clean)
    baseline_mod.load()


# --------------------------------------------------------------------------- #
# CLI + the tier-1 gate
# --------------------------------------------------------------------------- #
def test_tier1_gate_repo_is_clean():
    """THE gate: zero non-baselined findings over paddlebox_tpu/ and
    tools/.  A new finding means fix it, suppress it with a
    reason, or (legacy only) baseline it — not ignore it."""
    r = subprocess.run(
        [sys.executable, CLI, "--all"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, f"pbox-lint found:\n{r.stdout}\n{r.stderr}"


def test_every_analysis_root_exists():
    """A root that is gone is walked as nothing: ``--all`` would report 0
    findings for a file it never read."""
    from pbox_analyze import catalog, core

    for root in core.DEFAULT_ROOTS + catalog.GUARD_ROOTS:
        assert os.path.exists(os.path.join(REPO, root)), root


def test_documents_name_files_that_exist():
    """Every ``*.py`` file and ``tools/`` path that README.md,
    ARCHITECTURE.md and the verify skill write as code is in the tree
    (by its path from the root, or from a directory under it, as
    ``sparse/table.py`` is written; ``*`` as in ``tools/check_*.py``).
    Where this fails the document is what is corrected."""
    import fnmatch
    import re

    tree = []
    for d, dirs, files in os.walk(REPO):
        dirs[:] = [x for x in dirs
                   if not x.startswith(".") and x != "__pycache__"]
        rel = os.path.relpath(d, REPO)
        tree += [os.path.normpath(os.path.join(rel, n)) for n in files + dirs]
    path_re = re.compile(
        r"(?<![\w/.*-])((?:[\w.*-]+/)*[\w.*-]+\.py|tools/[\w./*-]+)")
    dangling = []
    for doc in ("README.md", "ARCHITECTURE.md",
                ".claude/skills/verify/SKILL.md"):
        with open(os.path.join(REPO, doc)) as f:
            text = f.read()
        named = {
            m.group(1).rstrip(".,/")
            for code in re.findall(r"`([^`\n]+)`", text)
            for m in path_re.finditer(code)
        }
        assert named, f"{doc} names no file: the pattern has rotted"
        dangling += [
            (doc, t) for t in sorted(named)
            if not any(fnmatch.fnmatch(f, t) or fnmatch.fnmatch(f, "*/" + t)
                       for f in tree)
        ]
    assert not dangling, dangling


def test_cli_json_shape():
    r = subprocess.run(
        [sys.executable, CLI, "--all", "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout) == []


def test_cli_names_rule_file_line_on_regression(tmp_path):
    """The acceptance scenario: a seeded clock regression exits non-zero
    and the output names the rule, file and line."""
    bad = tmp_path / "regress.py"
    bad.write_text("import time\ndeadline = time.time() + 10.0\n")
    r = subprocess.run(
        [sys.executable, CLI, str(bad)],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "clock-misuse" in r.stdout
    assert "regress.py:2" in r.stdout


def test_cli_rules_filter_and_unknown_rule(tmp_path):
    bad = tmp_path / "regress.py"
    bad.write_text(
        "import time\n"
        "deadline = time.time() + 10.0\n"
        "try:\n"
        "    pass\n"
        "except Exception:\n"
        "    pass\n"
    )
    r = subprocess.run(
        [sys.executable, CLI, str(bad), "--rules", "swallowed-exception"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "swallowed-exception" in r.stdout
    assert "clock-misuse" not in r.stdout
    r = subprocess.run(
        [sys.executable, CLI, "--rules", "no-such-rule"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 2


def test_cli_list_rules():
    r = subprocess.run(
        [sys.executable, CLI, "--list-rules"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0
    for rule in ("lock-order", "lock-held-blocking", "thread-shared-state",
                 "swallowed-exception", "clock-misuse", "jax-tracer-safety",
                 "metric-name-drift", "fault-site-drift", "env-flag-drift",
                 "span-name-drift"):
        assert rule in r.stdout


def test_cli_changed_mode_clean():
    """--changed vs HEAD on a clean-or-dirty tree must not crash and must
    honor the touched-lines filter (findings subset of a full run)."""
    r = subprocess.run(
        [sys.executable, CLI, "--changed", "HEAD"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode in (0, 1), r.stderr
    assert "changed vs HEAD" in r.stderr


# --------------------------------------------------------------------------- #
# call graph (callgraph.py)
# --------------------------------------------------------------------------- #
from pbox_analyze import rules_protocol, rules_resources  # noqa: E402
from pbox_analyze.callgraph import CallGraph  # noqa: E402
from pbox_analyze.cli import parse_changed_diff  # noqa: E402


def _graph(tmp_path, files: dict) -> CallGraph:
    for name, src in files.items():
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    ctx = Context(
        paths=[str(tmp_path / n) for n in files], repo=str(tmp_path))
    return CallGraph.of(ctx)


def test_callgraph_resolves_cross_module_calls(tmp_path):
    cg = _graph(tmp_path, {
        "util.py": "def helper():\n    pass\n",
        "main.py": (
            "from util import helper\n"
            "def drive():\n"
            "    helper()\n"
        ),
    })
    assert "util:helper" in cg.callees("main:drive")


def test_callgraph_self_and_attr_dispatch(tmp_path):
    cg = _graph(tmp_path, {"m.py": """\
        class Store:
            def merge(self):
                pass

        class Table:
            def __init__(self):
                self._store = Store()

            def flush(self):
                self._store.merge()

            def state_dict(self):
                self.flush()
    """})
    assert "m:Table.flush" in cg.callees("m:Table.state_dict")
    assert "m:Store.merge" in cg.callees("m:Table.flush")
    assert "m:Store.merge" in cg.transitive_callees("m:Table.state_dict")


def test_callgraph_property_read_is_a_call(tmp_path):
    cg = _graph(tmp_path, {"m.py": """\
        class T:
            @property
            def n(self):
                self.flush()
                return 0

            def flush(self):
                pass

            def shrink(self):
                if self.n == 0:
                    return 0
    """})
    assert "m:T.n" in cg.callees("m:T.shrink")
    assert "m:T.flush" in cg.transitive_callees("m:T.shrink")


def test_callgraph_thread_edges_are_kinded(tmp_path):
    cg = _graph(tmp_path, {"m.py": """\
        import threading

        class W:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self):
                pass
    """})
    kinds = {(e.callee, e.kind) for e in cg.edges["m:W.start"]}
    assert ("m:W._run", "thread") in kinds
    # thread edges are excluded from the synchronous-call closure
    assert "m:W._run" not in cg.transitive_callees("m:W.start")


# --------------------------------------------------------------------------- #
# typestate protocols (rules_protocol.py + protocols.py)
# --------------------------------------------------------------------------- #
def test_protocol_sparse_pass_double_begin(tmp_path):
    src = """\
        def drive(conf, k):
            table = SparseTable(conf)
            table.begin_pass(k)
            table.begin_pass(k)
            table.end_pass()
    """
    findings = _run(rules_protocol, tmp_path, src)
    assert [f.rule for f in findings] == ["protocol-sparse-pass"]
    assert findings[0].line == 4


def test_protocol_sparse_pass_loop_without_end(tmp_path):
    # second loop iteration re-begins an unclosed pass
    src = """\
        def drive(conf, passes):
            table = SparseTable(conf)
            for k in passes:
                table.begin_pass(k)
                train(table)
    """
    findings = _run(rules_protocol, tmp_path, src)
    assert any(f.rule == "protocol-sparse-pass" and f.line == 4
               for f in findings)


def test_protocol_sparse_pass_good_loop(tmp_path):
    src = """\
        def drive(conf, passes):
            table = SparseTable(conf)
            for k in passes:
                table.begin_pass(k)
                table.end_pass()
            state = table.state_dict()
            return state
    """
    assert _run(rules_protocol, tmp_path, src) == []


def test_protocol_sparse_pass_checkpoint_inside_pass(tmp_path):
    src = """\
        def drive(conf, k):
            table = SparseTable(conf)
            table.begin_pass(k)
            snap = table.state_dict()
            table.end_pass()
            return snap
    """
    findings = _run(rules_protocol, tmp_path, src)
    assert any("state_dict" in f.message for f in findings)


def test_protocol_sparse_pass_interprocedural_summary(tmp_path):
    # the helper ends the pass — the call graph summary must see it
    good = """\
        def finish(t):
            t.end_pass()

        def drive(conf, k):
            table = SparseTable(conf)
            table.begin_pass(k)
            finish(table)
    """
    assert _run(rules_protocol, tmp_path, good) == []
    bad = good.replace("t.end_pass()", "pass")
    findings = _run(rules_protocol, tmp_path, bad)
    assert any(f.rule == "protocol-sparse-pass" for f in findings)


def test_protocol_stream_close_on_running(tmp_path):
    src = """\
        def drive(lines):
            source = IterableSource(lines)
            source.start()
            source.close()
    """
    findings = _run(rules_protocol, tmp_path, src)
    assert [f.rule for f in findings] == ["protocol-stream-lifecycle"]
    assert findings[0].line == 4


def test_protocol_stream_two_phase_good(tmp_path):
    src = """\
        def drive(lines):
            source = IterableSource(lines)
            source.start()
            source.stop()
            source.close()
    """
    assert _run(rules_protocol, tmp_path, src) == []


def test_protocol_admission_release_every_path(tmp_path):
    src = """\
        def score(server, body):
            server.gate.admit(1.0)
            return run(body)
    """
    findings = _run(rules_protocol, tmp_path, src)
    assert [f.rule for f in findings] == ["protocol-admission-ticket"]
    assert "held" in findings[0].message


def test_protocol_admission_release_not_finally_guarded(tmp_path):
    src = """\
        def score(server, body):
            server.gate.admit(1.0)
            out = run(body)
            server.gate.release(0.1)
            return out
    """
    findings = _run(rules_protocol, tmp_path, src)
    assert any("finally" in f.message for f in findings)


def test_protocol_admission_try_finally_good(tmp_path):
    src = """\
        def score(server, body):
            server.gate.admit(1.0)
            try:
                return run(body)
            finally:
                server.gate.release(0.1)
    """
    assert _run(rules_protocol, tmp_path, src) == []


def test_protocol_admission_shed_handler_is_not_a_leak(tmp_path):
    # admit() raising means NO slot was taken: the except path must not
    # be reported as holding a ticket
    src = """\
        def score(server, body):
            try:
                server.gate.admit(1.0)
            except ShedRequest:
                return None
            try:
                return run(body)
            finally:
                server.gate.release(0.1)
    """
    assert _run(rules_protocol, tmp_path, src) == []


def test_protocol_publish_order_donefile_last(tmp_path):
    bad = """\
        class P:
            def publish(self, table, local):
                self._append_donefile(entry)
                write_manifest(local, "manifest.json")
                self._upload(local, "x", site="s")
                table.clear_delta()
    """
    findings = _run(rules_protocol, tmp_path, bad)
    assert any(f.rule == "protocol-publish-order" and f.line == 3
               for f in findings)

    good = """\
        class P:
            def publish(self, table, local):
                write_manifest(local, "manifest.json")
                self._upload(local, "x", site="s")
                self._append_donefile(entry)
                table.clear_delta()
    """
    assert _run(rules_protocol, tmp_path, good) == []


def test_protocol_publish_order_clear_before_visible(tmp_path):
    src = """\
        class P:
            def publish(self, table, local):
                write_manifest(local, "manifest.json")
                self._upload(local, "x", site="s")
                table.clear_delta()
                self._append_donefile(entry)
    """
    findings = _run(rules_protocol, tmp_path, src)
    assert any("clear_delta" in f.message for f in findings)


def test_protocol_span_pairing(tmp_path):
    bad = """\
        def trace(x):
            s = span("step")
            s.__enter__()
            return x
    """
    findings = _run(rules_protocol, tmp_path, bad)
    assert [f.rule for f in findings] == ["protocol-span-pairing"]

    good = bad.replace("return x",
                       "s.__exit__(None, None, None)\n    return x")
    assert _run(rules_protocol, tmp_path, good) == []

    with_form = """\
        def trace(x):
            with span("step"):
                return x
    """
    assert _run(rules_protocol, tmp_path, with_form) == []


def test_protocol_impl_obligation_fixture(tmp_path):
    # a class NAMED SparseTable whose state_dict forgets the flush
    # barrier trips the obligation; adding it back clears it
    bad = """\
        class SparseTable:
            def flush(self):
                pass

            def state_dict(self):
                return {}
    """
    findings = _run(rules_protocol, tmp_path, bad)
    assert any(f.rule == "protocol-impl-requires"
               and "state_dict" in f.message for f in findings)
    good = bad.replace("return {}", "self.flush()\n        return {}")
    assert not [f for f in _run(rules_protocol, tmp_path, good)
                if "state_dict() must" in f.message]


def test_protocol_segment_writer_read_before_seal(tmp_path):
    bad = """\
        def write(root, keys, vals):
            writer = SegmentWriter(root, 0, 1)
            writer.append(keys, vals)
            blocks = writer.info()
            writer.seal()
            return blocks
    """
    findings = _run(rules_protocol, tmp_path, bad)
    assert any(f.rule == "protocol-segment-lifecycle" and f.line == 4
               for f in findings)

    good = """\
        def write(root, keys, vals):
            writer = SegmentWriter(root, 0, 1)
            writer.append(keys, vals)
            writer.seal()
            return writer.info()
    """
    assert _run(rules_protocol, tmp_path, good) == []


def test_protocol_segment_writer_leaked_open(tmp_path):
    # a scope that neither seals nor aborts leaks an unsynced segment
    bad = """\
        def write(root, keys, vals):
            writer = SegmentWriter(root, 0, 1)
            writer.append(keys, vals)
    """
    findings = _run(rules_protocol, tmp_path, bad)
    assert any(f.rule == "protocol-segment-lifecycle" for f in findings)

    aborted = bad.replace("writer.append(keys, vals)",
                          "writer.append(keys, vals)\n    writer.abort()")
    assert _run(rules_protocol, tmp_path, aborted) == []


def test_protocol_segment_compact_swap_before_commit(tmp_path):
    bad = """\
        class S:
            def compact(self, b):
                staged = self._compact_write(b)
                self._swap_segments(b, [staged], [])
                self._commit_manifest([[staged]])
    """
    findings = _run(rules_protocol, tmp_path, bad)
    assert any(f.rule == "protocol-segment-lifecycle" and f.line == 4
               for f in findings)

    good = """\
        class S:
            def compact(self, b):
                staged = self._compact_write(b)
                self._commit_manifest([[staged]])
                self._swap_segments(b, [staged], [])
    """
    assert _run(rules_protocol, tmp_path, good) == []


def test_protocol_suppressed(tmp_path):
    src = """\
        def drive(conf, k):
            table = SparseTable(conf)
            table.begin_pass(k)
            # pbox-lint: ignore[protocol-sparse-pass] fixture reason
            table.begin_pass(k)
            table.end_pass()
    """
    assert _run(rules_protocol, tmp_path, src) == []


# --------------------------------------------------------------------------- #
# resource lifecycle (rules_resources.py)
# --------------------------------------------------------------------------- #
def test_thread_unjoined_bad(tmp_path):
    src = """\
        import threading

        class W:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                pass
    """
    findings = _run(rules_resources, tmp_path, src)
    assert [f.rule for f in findings] == ["thread-unjoined"]


@pytest.mark.parametrize("fix", [
    # daemonized
    "self._t = threading.Thread(target=self._run, daemon=True)",
    # joined elsewhere in the class (added below)
    None,
])
def test_thread_unjoined_good(tmp_path, fix):
    src = """\
        import threading

        class W:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def close(self):
                self._t.join(timeout=5.0)

            def _run(self):
                pass
    """
    if fix:
        src = src.replace(
            "self._t = threading.Thread(target=self._run)", fix)
    assert _run(rules_resources, tmp_path, src) == []


def test_thread_join_through_loop_alias(tmp_path):
    src = """\
        import threading

        class W:
            def start(self):
                self._a = threading.Thread(target=self._run)
                self._b = threading.Thread(target=self._run)

            def close(self):
                for t in (self._a, self._b):
                    t.join(timeout=2.0)

            def _run(self):
                pass
    """
    assert _run(rules_resources, tmp_path, src) == []


def test_executor_shutdown_bad_and_good(tmp_path):
    bad = """\
        from concurrent.futures import ThreadPoolExecutor

        class S:
            def warm(self):
                self._pool = ThreadPoolExecutor(max_workers=2)
    """
    findings = _run(rules_resources, tmp_path, bad)
    assert [f.rule for f in findings] == ["executor-shutdown"]

    good = bad + """\

            def close(self):
                pool, self._pool = self._pool, None
                if pool is not None:
                    pool.shutdown(wait=False)
    """
    assert _run(rules_resources, tmp_path, good) == []


def test_executor_shutdown_lazy_channel_pool_shape(tmp_path):
    """The KvChannel lifecycle shape (ISSUE 15 satellite): a LAZILY built
    peer-read pool (created under an is-None guard inside the hot method)
    must still be flagged when nothing retires it, and the real pattern —
    ``close()`` shutting the pool down and dropping the attribute, wired
    into trainer teardown — must pass clean."""
    bad = """\
        from concurrent.futures import ThreadPoolExecutor

        class Channel:
            def __init__(self, name):
                self.name = name
                self._pool = None

            def allgather(self, peers):
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(max_workers=4)
                return [self._pool.submit(lambda p: p, r) for r in peers]
    """
    findings = _run(rules_resources, tmp_path, bad)
    assert "executor-shutdown" in [f.rule for f in findings]

    good = bad + """\

            def close(self):
                if self._pool is not None:
                    self._pool.shutdown(wait=False)
                    self._pool = None
    """
    assert _run(rules_resources, tmp_path, good) == []


def test_resource_passes_clean_on_host_plane_and_census(tmp_path):
    """Pin the REAL host-plane modules clean under the resource passes:
    KvChannel's lazy pool + close() and the census plane must never
    regress into a leak (the trainer closes the plan channel, the sharded
    table closes its census channel)."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    ctx = Context(
        paths=[str(root / "paddlebox_tpu" / "parallel" / "host_plane.py"),
               str(root / "paddlebox_tpu" / "parallel" / "census.py")],
        repo=str(root),
    )
    findings = [
        f for f in rules_resources.run(ctx)
        if not ctx.by_rel[f.file].suppressed(f)
    ]
    assert findings == [], [str(f) for f in findings]


def test_resource_leak_on_early_return(tmp_path):
    src = """\
        def read(path, skip):
            fh = open(path)
            if skip:
                return None
            data = fh.read()
            fh.close()
            return data
    """
    findings = _run(rules_resources, tmp_path, src)
    assert [f.rule for f in findings] == ["resource-leak"]
    assert findings[0].line == 4

    fixed = src.replace("return None", "fh.close()\n        return None")
    assert _run(rules_resources, tmp_path, fixed) == []

    with_form = """\
        def read(path, skip):
            with open(path) as fh:
                if skip:
                    return None
                return fh.read()
    """
    assert _run(rules_resources, tmp_path, with_form) == []


def test_lock_manual_release_shapes(tmp_path):
    bad = """\
        import threading

        class G:
            def __init__(self):
                self._lock = threading.Lock()

            def work(self):
                self._lock.acquire()
                compute()
                self._lock.release()
    """
    findings = _run(rules_resources, tmp_path, bad)
    assert [f.rule for f in findings] == ["lock-manual-release"]

    good = """\
        import threading

        class G:
            def __init__(self):
                self._lock = threading.Lock()

            def work(self):
                self._lock.acquire()
                try:
                    compute()
                finally:
                    self._lock.release()
    """
    assert _run(rules_resources, tmp_path, good) == []

    trylock = """\
        import threading

        class G:
            def __init__(self):
                self._lock = threading.Lock()

            def work(self):
                if self._lock.acquire(blocking=False):
                    try:
                        compute()
                    finally:
                        self._lock.release()
    """
    assert _run(rules_resources, tmp_path, trylock) == []


# --------------------------------------------------------------------------- #
# interprocedural / cross-class lock analysis
# --------------------------------------------------------------------------- #
def test_lock_order_cross_class_cycle(tmp_path):
    src = """\
        import threading

        class Router:
            def __init__(self, sup: Supervisor):
                self._la = threading.Lock()
                self.sup = sup

            def route(self):
                with self._la:
                    self.sup.poke()

        class Supervisor:
            def __init__(self, router: Router):
                self._lb = threading.Lock()
                self.router = router

            def poke(self):
                with self._lb:
                    pass

            def back(self):
                with self._lb:
                    self.router.route()
    """
    findings = _run(rules_locks, tmp_path, src)
    assert any(f.rule == "lock-order" for f in findings)


def test_lock_held_blocking_through_callee(tmp_path):
    src = """\
        import threading
        import time

        class G:
            def __init__(self):
                self._lock = threading.Lock()

            def slow(self):
                time.sleep(1.0)

            def work(self):
                with self._lock:
                    self.slow()
    """
    findings = _run(rules_locks, tmp_path, src)
    assert any(
        f.rule == "lock-held-blocking" and "slow()" in f.message
        and f.line == 13
        for f in findings
    )


def test_lock_split_helper_wait_on_own_cond_is_legal(tmp_path):
    src = """\
        import threading

        class G:
            def __init__(self):
                self._cv = threading.Condition()

            def _wait_locked(self):
                self._cv.wait(timeout=1.0)

            def take(self):
                with self._cv:
                    self._wait_locked()
    """
    assert _run(rules_locks, tmp_path, src) == []


# --------------------------------------------------------------------------- #
# --changed diff parsing robustness
# --------------------------------------------------------------------------- #
FABRICATED_DIFF = """\
diff --git a/kept.py b/kept.py
index 111..222 100644
--- a/kept.py
+++ b/kept.py
@@ -10,0 +11,2 @@ def f():
+new line
+another
diff --git a/gone.py b/gone.py
deleted file mode 100644
index 333..000
--- a/gone.py
+++ /dev/null
@@ -1,5 +0,0 @@
-removed
diff --git a/old_name.py b/new_name.py
similarity index 90%
rename from old_name.py
rename to new_name.py
--- a/old_name.py
+++ b/new_name.py
@@ -3,0 +4 @@ def g():
+renamed-file line
diff --git a/pure_rename.py b/also_pure.py
similarity index 100%
rename from pure_rename.py
rename to also_pure.py
"""


def test_parse_changed_diff_handles_rename_and_delete():
    touched = parse_changed_diff(FABRICATED_DIFF)
    assert touched["kept.py"] == {11, 12}
    # the deleted file's hunks must not bleed onto the previous file,
    # nor appear under /dev/null
    assert "gone.py" not in touched
    assert not any("dev/null" in k for k in touched)
    # renamed file is tracked under its NEW path
    assert touched["new_name.py"] == {4}
    assert "old_name.py" not in touched
    # a 100%-similarity rename has no hunks and touches nothing
    assert "pure_rename.py" not in touched and "also_pure.py" not in touched


def test_changed_mode_survives_unparsable_file(tmp_path):
    """A mid-edit syntax error must surface as parse-error, not crash."""
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    r = subprocess.run(
        [sys.executable, CLI, str(bad)],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "parse-error" in r.stdout


def test_cli_names_protocol_rule_on_regression(tmp_path):
    bad = tmp_path / "regress.py"
    bad.write_text(
        "def drive(conf, k):\n"
        "    table = SparseTable(conf)\n"
        "    table.begin_pass(k)\n"
        "    table.begin_pass(k)\n"
        "    table.end_pass()\n"
    )
    r = subprocess.run(
        [sys.executable, CLI, str(bad)],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "protocol-sparse-pass" in r.stdout
    assert "regress.py:4" in r.stdout


def test_new_rules_listed():
    r = subprocess.run(
        [sys.executable, CLI, "--list-rules"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0
    for rule in ("protocol-sparse-pass", "protocol-stream-lifecycle",
                 "protocol-admission-ticket", "protocol-publish-order",
                 "protocol-span-pairing", "protocol-impl-requires",
                 "thread-unjoined", "executor-shutdown", "resource-leak",
                 "lock-manual-release"):
        assert rule in r.stdout


def _children_cpu_seconds() -> float:
    ru = resource.getrusage(resource.RUSAGE_CHILDREN)
    return ru.ru_utime + ru.ru_stime


def test_full_run_wall_time_budget():
    """The interprocedural passes must not regress lint latency: a full
    --all run stays under the 5s budget (pre-commit viability).  Judged by
    the child's CPU seconds (it is single-threaded, so on a free machine
    they are its wall time): the budget pins the ANALYZER, and the wall
    clock of a subprocess run beside five other xdist workers pins the
    neighbours.  Best of two runs — a genuinely slow lint fails both."""
    best = None
    for _ in range(2):
        c0 = _children_cpu_seconds()
        r = subprocess.run(
            [sys.executable, CLI, "--all"],
            capture_output=True, text=True, timeout=60,
        )
        spent = _children_cpu_seconds() - c0
        assert r.returncode == 0, f"repo not clean:\n{r.stdout}"
        best = spent if best is None else min(best, spent)
        if best <= 5.0:
            break
    assert best <= 5.0, f"pbox-lint --all took {best:.2f} CPU-s (> 5s)"


# --------------------------------------------------------------------------- #
# SPMD safety (rules_spmd.py + spmd_catalog.py)
# --------------------------------------------------------------------------- #
from pbox_analyze import rules_spmd  # noqa: E402

#: mirrors sharded_table.begin_pass with the census gather moved inside a
#: rank guard — the seeded-bug shape from the acceptance criteria
SPMD_SEEDED_BUG = """\
    import jax


    class ShardedTable:
        def begin_pass(self, pass_keys):
            if jax.process_index() == 0:
                self.chan.allgather(pass_keys)
            self.live = True
"""


def test_spmd_rank_divergence_bad(tmp_path):
    findings = _run(rules_spmd, tmp_path, SPMD_SEEDED_BUG)
    rules = {f.rule for f in findings}
    assert "spmd-rank-divergence" in rules
    assert "spmd-collective-sequence" in rules
    div = [f for f in findings if f.rule == "spmd-rank-divergence"]
    assert div[0].line == 7  # the allgather call
    seq = [f for f in findings if f.rule == "spmd-collective-sequence"]
    assert seq[0].line == 6  # the rank-conditional branch


def test_spmd_rank_divergence_early_return(tmp_path):
    src = """\
        import jax

        def export(table, x):
            if jax.process_index() != 0:
                return None
            return host_allgather(x)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert any(f.rule == "spmd-rank-divergence" and f.line == 6
               for f in findings)


def test_spmd_rank_divergence_through_callee(tmp_path):
    src = """\
        import jax

        def helper(chan, x):
            chan.allgather(x)

        def drive(chan, x):
            if jax.process_index() == 0:
                helper(chan, x)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert any(f.rule == "spmd-rank-divergence" and "helper" in f.message
               for f in findings)


def test_spmd_rank_divergence_env_seed(tmp_path):
    src = """\
        import os

        def drive(chan, x):
            if os.environ.get("PBOX_PROCESS_ID", "0") == "0":
                chan.allgather(x)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert any(f.rule == "spmd-rank-divergence" for f in findings)


def test_spmd_rank_guarded_side_effects_are_legal(tmp_path):
    # the donefile-write / rank-0 log-line / rank-label family: rank used
    # for non-collective work produces ZERO findings, no suppressions
    src = """\
        import jax

        def publish(entry, path):
            if jax.process_index() == 0:
                with open(path, "w") as fh:
                    fh.write(entry)

        def banner(merged):
            if jax.process_index() == 0:
                print(merged, flush=True)

        def dump_suffix(multiproc):
            return f"-r{jax.process_index()}" if multiproc else ""
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_all_paths_raise_branch_is_legal(tmp_path):
    src = """\
        import jax

        def validate(mesh, x):
            pid = jax.process_index()
            if pid >= mesh:
                raise RuntimeError("bad layout")
            return host_allgather(x)
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_uniform_world_gate_is_legal(tmp_path):
    # process_count is the same value on every rank — the standard
    # `if is_multiprocess():` gate must never fire the rule
    src = """\
        import jax

        def gather(x):
            if jax.process_count() > 1:
                return host_allgather(x)
            return x
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_watchdog_peer_loop_shape_is_legal(tmp_path):
    # watchdog._check_peers: `if rank == self.rank: continue` guards only
    # non-collective abort bookkeeping (watchdog.py:488 acceptance shape)
    src = """\
        class W:
            def check_peers(self, now):
                for rank in range(self.world):
                    if rank == self.rank:
                        continue
                    self.observe(rank, now)

            def observe(self, rank, now):
                self.seen[rank] = now
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_rank_divergence_suppressed(tmp_path):
    src = SPMD_SEEDED_BUG.replace(
        "self.chan.allgather(pass_keys)",
        "# pbox-lint: ignore[spmd-rank-divergence, spmd-collective-sequence]"
        " fixture reason\n"
        "            self.chan.allgather(pass_keys)",
    )
    # the sequence finding lands on the `if` line; suppress it there too
    src = src.replace(
        "if jax.process_index() == 0:",
        "if jax.process_index() == 0:"
        "  # pbox-lint: ignore[spmd-collective-sequence] fixture reason",
    )
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_sequence_order_swap(tmp_path):
    # both arms gather on both channels but in opposite order: sequence
    # divergence WITHOUT rank-divergence (nothing is skipped)
    src = """\
        import jax

        def plan(a, b, x):
            rank = jax.process_index()
            if rank % 2 == 0:
                a.allgather(x)
                b.allgather(x)
            else:
                b.allgather(x)
                a.allgather(x)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert [f.rule for f in findings] == ["spmd-collective-sequence"]
    assert findings[0].line == 5


def test_spmd_sequence_loop_continue_skip(tmp_path):
    src = """\
        def drain(chan, items, rank):
            for it in items:
                if it.owner == rank:
                    continue
                chan.allgather(it)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert any(f.rule == "spmd-collective-sequence" for f in findings)
    assert any(f.rule == "spmd-rank-divergence" and f.line == 5
               for f in findings)


def test_spmd_sequence_same_both_arms_is_legal(tmp_path):
    src = """\
        import jax

        def plan(chan, x, rank):
            if rank == 0:
                y = chan.allgather(x)
            else:
                y = chan.allgather(x)
            return y
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_collective_on_thread_bad(tmp_path):
    src = """\
        import threading

        class Stager:
            def start(self):
                self._t = threading.Thread(target=self._stage, daemon=True)
                self._t.start()

            def _stage(self):
                host_allgather_varlen(self.keys)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert [f.rule for f in findings] == ["spmd-collective-on-thread"]
    assert findings[0].line == 5  # the Thread(...) edge
    assert "host_allgather_varlen" in findings[0].message


def test_spmd_collective_on_executor_submit(tmp_path):
    src = """\
        class Stager:
            def kick(self):
                self._pool.submit(self._job)

            def _job(self):
                host_allgather(self.keys)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert [f.rule for f in findings] == ["spmd-collective-on-thread"]


def test_spmd_kvchannel_on_thread_is_legal(tmp_path):
    # KvChannel.allgather exists precisely to run off-thread (the
    # feed-producer plans concurrently with the device step)
    src = """\
        import threading

        class Producer:
            def start(self):
                self._t = threading.Thread(target=self._plan, daemon=True)
                self._t.start()

            def _plan(self):
                self.chan.allgather(self.keys)
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_collective_on_thread_suppressed(tmp_path):
    src = """\
        import threading

        class Stager:
            def start(self):
                # pbox-lint: ignore[spmd-collective-on-thread] fixture
                self._t = threading.Thread(target=self._stage, daemon=True)
                self._t.start()

            def _stage(self):
                host_allgather_varlen(self.keys)
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_mesh_axis_unbound(tmp_path):
    src = """\
        import jax
        from jax.experimental.shard_map import shard_map

        def body(x):
            return jax.lax.psum(x, "seq")

        def outer(x):
            sm = shard_map(body, in_specs=("s",), out_specs=None,
                           axis_names={"expert"})
            return sm(x)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert [f.rule for f in findings] == ["spmd-mesh-axis"]
    assert findings[0].line == 5
    assert "'seq'" in findings[0].message


def test_spmd_mesh_axis_bound_through_constant_and_default(tmp_path):
    # EXPERT_AXIS-style module constant flows through the param default
    # and the axis_names set literal — the composed-mesh idiom
    src = """\
        import jax
        from jax.experimental.shard_map import shard_map

        EXPERT_AXIS = "expert"

        def mix(h, axis_name=EXPERT_AXIS):
            return jax.lax.psum(h, axis_name)

        def body(h):
            return mix(h)

        def outer(h):
            sm = shard_map(body, in_specs=("s",), out_specs=None,
                           axis_names={EXPERT_AXIS})
            return sm(h)
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_mesh_axis_unknown_mesh_is_conservative(tmp_path):
    src = """\
        import jax
        from jax.experimental.shard_map import shard_map

        def body(x):
            return jax.lax.psum(x, "anything")

        def outer(self, x):
            sm = shard_map(body, mesh=self.mesh, in_specs=("s",),
                           out_specs=None)
            return sm(x)
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_spmd_mesh_axis_in_specs_arity(tmp_path):
    src = """\
        from jax.experimental.shard_map import shard_map

        def body(a, b):
            return a + b

        def outer(mesh, a, b):
            sm = shard_map(body, mesh=mesh, in_specs=("x", "y", "z"),
                           out_specs=None)
            return sm(a, b)
    """
    findings = _run(rules_spmd, tmp_path, src)
    assert [f.rule for f in findings] == ["spmd-mesh-axis"]
    assert "3 entr" in findings[0].message

    good = src.replace('("x", "y", "z")', '("x", "y")')
    assert _run(rules_spmd, tmp_path, good) == []


def test_spmd_mesh_axis_suppressed(tmp_path):
    src = """\
        import jax
        from jax.experimental.shard_map import shard_map

        def body(x):
            # pbox-lint: ignore[spmd-mesh-axis] fixture reason
            return jax.lax.psum(x, "seq")

        def outer(x):
            sm = shard_map(body, in_specs=("s",), out_specs=None,
                           axis_names={"expert"})
            return sm(x)
    """
    assert _run(rules_spmd, tmp_path, src) == []


def test_cli_names_spmd_rules_on_seeded_regression(tmp_path):
    """Acceptance scenario: the seeded begin_pass bug is flagged by BOTH
    spmd-rank-divergence and spmd-collective-sequence, naming file+line."""
    bad = tmp_path / "regress.py"
    bad.write_text(textwrap.dedent(SPMD_SEEDED_BUG))
    r = subprocess.run(
        [sys.executable, CLI, str(bad), "--rules", "spmd-*"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "spmd-rank-divergence" in r.stdout
    assert "spmd-collective-sequence" in r.stdout
    assert "regress.py:7" in r.stdout  # the moved allgather
    assert "regress.py:6" in r.stdout  # the rank-conditional branch


def test_cli_rules_glob_selects_spmd_family(tmp_path):
    bad = tmp_path / "regress.py"
    bad.write_text(
        "import time\n"
        "deadline = time.time() + 10.0\n"
    )
    # the glob selects only the spmd family: the clock regression is NOT
    # reported under --rules spmd-*
    r = subprocess.run(
        [sys.executable, CLI, str(bad), "--rules", "spmd-*"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stdout
    r = subprocess.run(
        [sys.executable, CLI, "--rules", "nope-*"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 2


def test_spmd_rules_listed():
    r = subprocess.run(
        [sys.executable, CLI, "--list-rules"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0
    for rule in ("spmd-rank-divergence", "spmd-collective-sequence",
                 "spmd-collective-on-thread", "spmd-mesh-axis"):
        assert rule in r.stdout


def test_spmd_repo_is_clean_without_suppressions():
    """The acceptance bar: the four SPMD rules over the default roots
    produce zero findings AND zero spmd suppressions were needed at the
    existing rank-guarded non-collective sites (donefile writes, rank-0
    log lines, watchdog.py peer loop)."""
    r = subprocess.run(
        [sys.executable, CLI, "--all", "--rules", "spmd-*", "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout
    assert json.loads(r.stdout) == []
    # no inline spmd ignores anywhere in the analyzed roots
    for root in ("paddlebox_tpu", "tools"):
        for d, _, fs in os.walk(os.path.join(REPO, root)):
            for f in fs:
                if not f.endswith(".py"):
                    continue
                with open(os.path.join(d, f), encoding="utf-8") as fh:
                    assert "ignore[spmd" not in fh.read(), (
                        f"unexpected spmd suppression in {d}/{f}"
                    )


def test_wrapper_cli_contract_survives_context_fields():
    """The five thin tools/check_*.py wrappers monkeypatch-import the
    framework: their module APIs and the Context surface they ride on
    must survive new fields (here: Context.caches for the SPMD memos)."""
    from pbox_analyze.core import Context as _Ctx

    ctx = _Ctx(paths=[CLI])
    assert hasattr(ctx, "caches") and isinstance(ctx.caches, dict)
    assert hasattr(ctx, "files") and hasattr(ctx, "by_rel")

    import check_env_flags
    import check_fault_sites
    import check_metric_names
    import check_publish_dir
    import check_span_names

    assert callable(check_metric_names.scan_sources)
    assert callable(check_metric_names.catalog_patterns)
    assert isinstance(check_metric_names.scan_sources(), dict)
    assert callable(check_span_names.scan_sources)
    assert callable(check_env_flags.main)
    assert callable(check_fault_sites.main)
    assert callable(check_publish_dir.main)


# --------------------------------------------------------------------------- #
# numerics & recompilation safety (rules_numerics.py + num_catalog.py)
# --------------------------------------------------------------------------- #
from pbox_analyze import rules_numerics  # noqa: E402


# -- num-dtype-flow ---------------------------------------------------------- #
BAD_DEQUANT = """\
    import numpy as np
    from paddlebox_tpu.inference.quant import quantize_rows

    def publish(values):
        head, codes, scales = quantize_rows(values, 2, "int8")
        rows = codes.astype(np.float32) * scales[:, None]
        return rows
"""


def test_dtype_flow_bad_dequant_outside_fused_gather(tmp_path):
    (finding,) = _run(rules_numerics, tmp_path, BAD_DEQUANT)
    assert finding.rule == "num-dtype-flow"
    assert finding.line == 6
    assert "fused gather" in finding.message


def test_dtype_flow_good_codes_stay_quantized(tmp_path):
    src = """\
        import numpy as np
        from paddlebox_tpu.inference.quant import quantize_rows

        def publish(values):
            head, codes, scales = quantize_rows(values, 2, "int8")
            np.save("head.npy", head)
            np.save("codes.npy", codes)
            np.save("scales.npy", scales)
    """
    assert _run(rules_numerics, tmp_path, src) == []


def test_dtype_flow_bad_merge_mixing(tmp_path):
    src = """\
        import numpy as np

        def merge(values, embedx_q):
            head = values.astype(np.float32)
            return np.concatenate([head, embedx_q], axis=1)
    """
    (finding,) = _run(rules_numerics, tmp_path, src)
    assert finding.rule == "num-dtype-flow"
    assert "EmbeddingDtypeMismatch" in finding.message


def test_dtype_flow_good_merge_same_dtype(tmp_path):
    src = """\
        import numpy as np

        def merge(a, b):
            x = a.astype(np.float32)
            y = b.astype(np.float32)
            return np.concatenate([x, y], axis=1)
    """
    assert _run(rules_numerics, tmp_path, src) == []


def test_dtype_flow_suppressed(tmp_path):
    src = BAD_DEQUANT.replace(
        "        rows = codes.astype(np.float32) * scales[:, None]",
        "        # pbox-lint: ignore[num-dtype-flow] fixture reason\n"
        "        rows = codes.astype(np.float32) * scales[:, None]",
    )
    assert _run(rules_numerics, tmp_path, src) == []


# -- num-key-width ----------------------------------------------------------- #
BAD_KEY_CAST = """\
    import numpy as np

    def bucketize(keys):
        return keys.astype(np.float32) / 7.0
"""


def test_key_width_bad_float_cast(tmp_path):
    findings = _run(rules_numerics, tmp_path, BAD_KEY_CAST)
    assert findings and all(f.rule == "num-key-width" for f in findings)
    assert findings[0].line == 4
    assert "2^53" in findings[0].message


@pytest.mark.parametrize("expr,needle", [
    ("np.int64(batch.keys)", "sign"),
    ("keys * 0.5", "float arithmetic"),
    ("jnp.asarray(keys)", "uint32"),
    ("float(keys[0])", "2^53"),
])
def test_key_width_bad_sink_family(tmp_path, expr, needle):
    src = f"""\
        import numpy as np
        import jax.numpy as jnp

        def f(keys, batch):
            return {expr}
    """
    findings = _run(rules_numerics, tmp_path, src)
    assert findings, expr
    assert findings[0].rule == "num-key-width"
    assert needle in findings[0].message


def test_key_width_good_split_convention(tmp_path):
    """The split itself — shift/mask with np.uint64 then narrow — is the
    sanctioned uint64->uint32 path (utils/keycodec.py split_u64)."""
    src = """\
        import numpy as np

        def split_u64(keys):
            keys = np.asarray(keys, dtype=np.uint64)
            out = np.empty((keys.shape[0], 2), np.uint32)
            out[:, 0] = (keys >> np.uint64(32)).astype(np.uint32)
            out[:, 1] = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            return out
    """
    assert _run(rules_numerics, tmp_path, src) == []


def test_key_width_good_comparisons_and_searchsorted(tmp_path):
    src = """\
        import numpy as np

        def resolve(keys, batch_keys):
            pos = np.searchsorted(keys, batch_keys)
            found = keys[np.minimum(pos, keys.shape[0] - 1)] == batch_keys
            return pos, found
    """
    assert _run(rules_numerics, tmp_path, src) == []


def test_key_width_bad_32bit_recombine(tmp_path):
    src = """\
        from paddlebox_tpu.utils.keycodec import split_u64

        def roundtrip(keys):
            pairs = split_u64(keys)
            hi = pairs[:, 0]
            lo = pairs[:, 1]
            return (hi << 32) | lo
    """
    (finding,) = _run(rules_numerics, tmp_path, src)
    assert finding.rule == "num-key-width"
    assert "np.uint64(hi)" in finding.message


def test_key_width_suppressed(tmp_path):
    src = BAD_KEY_CAST.replace(
        "    return keys.astype(np.float32) / 7.0",
        "    # pbox-lint: ignore[num-key-width] fixture reason\n"
        "    return keys.astype(np.float32) / 7.0",
    )
    assert _run(rules_numerics, tmp_path, src) == []


# -- jit-retrace-hazard ------------------------------------------------------ #
BAD_FRESH_WRAPPER = """\
    import jax

    def merge(tree):
        return jax.jit(lambda t: t)(tree)
"""


def test_retrace_bad_fresh_wrapper_per_call(tmp_path):
    """The merge_device_axis bug this PR fixed: jit built and invoked in
    one expression retraces on every call."""
    (finding,) = _run(rules_numerics, tmp_path, BAD_FRESH_WRAPPER)
    assert finding.rule == "jit-retrace-hazard"
    assert finding.line == 4


def test_retrace_bad_wrap_in_loop(tmp_path):
    src = """\
        import jax

        def f(fns, x):
            for fn in fns:
                g = jax.jit(fn)
                x = g(x)
            return x
    """
    (finding,) = _run(rules_numerics, tmp_path, src)
    assert finding.rule == "jit-retrace-hazard"
    assert "loop" in finding.message


def test_retrace_bad_shape_varying_arg(tmp_path):
    src = """\
        import jax
        import numpy as np

        step = jax.jit(lambda x: x)

        def f(batch):
            return step(np.unique(batch))
    """
    (finding,) = _run(rules_numerics, tmp_path, src)
    assert finding.rule == "jit-retrace-hazard"
    assert "padded-bucket" in finding.message


def test_retrace_bad_python_scalar_arg(tmp_path):
    src = """\
        import jax

        step = jax.jit(lambda x, n: x)

        def f(x, ys):
            return step(x, len(ys))
    """
    (finding,) = _run(rules_numerics, tmp_path, src)
    assert finding.rule == "jit-retrace-hazard"
    assert "scalar" in finding.message


def test_retrace_bad_closure_captured_device_array(tmp_path):
    src = """\
        import jax
        import jax.numpy as jnp

        def build(w):
            scale = jnp.asarray(w)

            def body(x):
                return x * scale

            return jax.jit(body)
    """
    (finding,) = _run(rules_numerics, tmp_path, src)
    assert finding.rule == "jit-retrace-hazard"
    assert "scale" in finding.message and "constant" in finding.message


def test_retrace_good_cached_factory_and_padded_args(tmp_path):
    """The repo's own discipline: build the wrapper once through a
    factory, pad feeds to a fixed buffer before dispatch."""
    src = """\
        import jax
        import numpy as np

        class T:
            def _build(self):
                return jax.jit(lambda x: x)

            def go(self, feeds):
                self._fn = self._build()
                buf = np.zeros(1024)
                for f in feeds:
                    buf[: f.size] = f
                    self._fn(buf)
    """
    assert _run(rules_numerics, tmp_path, src) == []


def test_retrace_suppressed(tmp_path):
    src = BAD_FRESH_WRAPPER.replace(
        "    return jax.jit(lambda t: t)(tree)",
        "    # pbox-lint: ignore[jit-retrace-hazard] fixture reason\n"
        "    return jax.jit(lambda t: t)(tree)",
    )
    assert _run(rules_numerics, tmp_path, src) == []


# -- host-sync-in-hot-loop --------------------------------------------------- #
BAD_HOT_SYNC = """\
    import jax

    step = jax.jit(lambda x: x)

    def train(feeds):
        for dev in feeds:
            loss = step(dev)
            x = jax.device_get(loss)
        return x
"""


def test_host_sync_bad_device_get_in_hot_loop(tmp_path):
    (finding,) = _run(rules_numerics, tmp_path, BAD_HOT_SYNC)
    assert finding.rule == "host-sync-in-hot-loop"
    assert finding.line == 8


def test_host_sync_bad_float_in_batches_loop(tmp_path):
    src = """\
        import jax

        step = jax.jit(lambda x: x)

        def train(ds):
            out = []
            for batch in ds.batches():
                loss = step(batch)
                out.append(float(loss))
            return out
    """
    (finding,) = _run(rules_numerics, tmp_path, src)
    assert finding.rule == "host-sync-in-hot-loop"


def test_host_sync_bad_through_callee_summary(tmp_path):
    """The 133-candidate-site reality: the sync hides one call down.
    The callee summary carries it back to the hot-loop call site."""
    src = """\
        import jax
        import numpy as np

        step = jax.jit(lambda x: x)

        def readback(v):
            return np.asarray(v)

        def train(ds):
            for batch in ds.batches():
                loss = step(batch)
                r = readback(loss)
            return r
    """
    (finding,) = _run(rules_numerics, tmp_path, src)
    assert finding.rule == "host-sync-in-hot-loop"
    assert "readback" in finding.message


def test_host_sync_good_pass_boundary_and_prof_guard(tmp_path):
    """The two designed idioms: D2H after the loop (pass boundary), and
    a profiling-gated readback inside it — neither needs an annotation."""
    src = """\
        import jax
        import numpy as np

        step = jax.jit(lambda x: x)

        def train(ds, prof):
            for batch in ds.batches():
                loss = step(batch)
                if prof.enabled:
                    loss.block_until_ready()
            return float(loss)
    """
    assert _run(rules_numerics, tmp_path, src) == []


def test_host_sync_good_shape_read_is_not_a_sync(tmp_path):
    src = """\
        import jax

        step = jax.jit(lambda x: x)

        def train(feeds):
            n = 0
            for dev in feeds:
                loss = step(dev)
                n += int(loss.shape[0])
            return n
    """
    assert _run(rules_numerics, tmp_path, src) == []


def test_host_sync_suppressed(tmp_path):
    src = BAD_HOT_SYNC.replace(
        "        x = jax.device_get(loss)",
        "        # pbox-lint: ignore[host-sync-in-hot-loop] fixture reason\n"
        "        x = jax.device_get(loss)",
    )
    assert _run(rules_numerics, tmp_path, src) == []


# -- CLI / tooling ----------------------------------------------------------- #
def test_cli_names_num_key_width_on_seeded_regression(tmp_path):
    """Acceptance scenario: a seeded uint64->float regression exits
    non-zero and the output names rule, file and line via the CLI."""
    bad = tmp_path / "regress.py"
    bad.write_text(
        "import numpy as np\n"
        "def shard_of(keys, n):\n"
        "    return keys.astype(np.float64) % n\n"
    )
    r = subprocess.run(
        [sys.executable, CLI, str(bad)],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "num-key-width" in r.stdout
    assert "regress.py:3" in r.stdout


def test_cli_rules_glob_selects_num_and_jit_families(tmp_path):
    bad = tmp_path / "regress.py"
    bad.write_text(
        "import numpy as np\n"
        "import jax\n"
        "def f(keys, tree):\n"
        "    jax.jit(lambda t: t)(tree)\n"
        "    return keys * 0.5\n"
    )
    r = subprocess.run(
        [sys.executable, CLI, str(bad), "--rules", "num-*"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "num-key-width" in r.stdout
    assert "jit-retrace-hazard" not in r.stdout
    r = subprocess.run(
        [sys.executable, CLI, str(bad), "--rules", "jit-*"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "jit-retrace-hazard" in r.stdout
    assert "num-key-width" not in r.stdout


def test_changed_mode_picks_up_numerics_rules(tmp_path, monkeypatch, capsys):
    """--changed REF reports a new-rule finding when its line is in the
    diff, and filters it out when only other lines were touched."""
    from pbox_analyze import cli as cli_mod

    bad = tmp_path / "touched.py"
    bad.write_text(
        "import numpy as np\n"
        "def f(keys):\n"
        "    return keys.astype(np.float32)\n"
    )
    rel = os.path.relpath(str(bad), cli_mod.REPO)

    monkeypatch.setattr(cli_mod, "_changed_lines", lambda ref: {rel: {3}})
    rc = cli_mod.main(["--changed", "HEAD", str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "num-key-width" in out

    monkeypatch.setattr(cli_mod, "_changed_lines", lambda ref: {rel: {1}})
    rc = cli_mod.main(["--changed", "HEAD", str(bad)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "num-key-width" not in out


def test_numerics_rules_listed():
    r = subprocess.run(
        [sys.executable, CLI, "--list-rules"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0
    for rule in ("num-dtype-flow", "num-key-width", "jit-retrace-hazard",
                 "host-sync-in-hot-loop"):
        assert rule in r.stdout


def test_numerics_repo_is_clean(tmp_path):
    """The acceptance bar: the four numerics rules over the default roots
    produce zero findings (intentional sites carry inline reasons; the
    baseline stays empty)."""
    r = subprocess.run(
        [sys.executable, CLI, "--all", "--json",
         "--rules", "num-*,jit-*,host-sync-in-hot-loop"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout
    assert json.loads(r.stdout) == []


def test_numerics_memos_live_in_context_caches(tmp_path):
    """Per-function dtype envs and sync summaries are memoized under
    Context.caches so repeated pass runs (and the wall-time budget) don't
    re-derive them."""
    ctx = _ctx(tmp_path, BAD_KEY_CAST)
    rules_numerics.run(ctx)
    cache = ctx.caches.get("numerics")
    assert cache is not None
    assert cache["dtype_env"], "dtype envs must be memoized per function"
    # second run hits the memo table (same object, no rebuild)
    envs = cache["dtype_env"]
    rules_numerics.run(ctx)
    assert ctx.caches["numerics"]["dtype_env"] is envs
