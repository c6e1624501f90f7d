"""Env-flag drift check (tools/check_env_flags.py): every PBOX_* var the
package reads must be documented in ARCHITECTURE.md/README.md and vice
versa — the tier-1 guard that keeps the ops contract honest, exactly
like the metric-name and fault-site guards."""

import os
import subprocess
import sys

import pytest

TOOL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools", "check_env_flags.py",
)


def _tool():
    sys.path.insert(0, os.path.dirname(TOOL))
    try:
        import importlib

        return importlib.import_module("check_env_flags")
    finally:
        sys.path.pop(0)


def test_tree_has_no_drift():
    mod = _tool()
    undocumented, stale = mod.check()
    assert undocumented == [] and stale == []
    assert mod.main([]) == 0


def test_flag_shim_entries_are_derived():
    """Every _Flags._DEFAULTS key becomes a PBOX_<NAME> var even when the
    literal string never appears anywhere (the dynamic-read hazard this
    tool exists for)."""
    mod = _tool()
    fv = mod.flag_vars()
    assert "PBOX_RETRY_MAX_ATTEMPTS" in fv
    assert "PBOX_HBM_CACHE" in fv
    # the streaming flags this PR adds are caught from day one
    assert "PBOX_STREAM_ROOT" in fv
    assert "PBOX_MAX_STALENESS_S" in fv
    assert "PBOX_STREAM_WINDOW_RECORDS" in fv


def test_scanner_finds_literal_reads():
    """Direct os.environ reads (no flag-shim entry) are collected from
    source literals."""
    mod = _tool()
    refs = mod.referenced_vars()
    assert "PBOX_COORDINATOR_ADDRESS" in refs  # launch.py env injection
    assert "PBOX_HADOOP_BIN" in refs  # utils/fs.py direct read


def test_docs_cover_referenced_vars():
    mod = _tool()
    documented = mod.documented_vars()
    for var in ("PBOX_STREAM_ROOT", "PBOX_MAX_STALENESS_S",
                "PBOX_STREAM_WINDOW_RECORDS", "PBOX_FAULT_PLAN"):
        assert var in documented, f"{var} missing from the docs catalog"


def test_undocumented_var_fails(monkeypatch):
    mod = _tool()
    real = mod.referenced_vars()

    def fake():
        return {**real, "PBOX_TOTALLY_NEW_KNOB": "nowhere.py:1"}

    monkeypatch.setattr(mod, "referenced_vars", fake)
    undocumented, stale = mod.check()
    assert any(v == "PBOX_TOTALLY_NEW_KNOB" for v, _ in undocumented)
    assert stale == []


def test_stale_doc_fails(monkeypatch):
    mod = _tool()
    real = mod.documented_vars()

    def fake():
        return {**real, "PBOX_REMOVED_KNOB": "ARCHITECTURE.md:1"}

    monkeypatch.setattr(mod, "documented_vars", fake)
    undocumented, stale = mod.check()
    assert undocumented == []
    assert any(v == "PBOX_REMOVED_KNOB" for v, _ in stale)


@pytest.mark.parametrize("args,rc", [([], 0), (["--list"], 0)])
def test_cli_exit_codes(args, rc):
    r = subprocess.run(
        [sys.executable, TOOL] + args,
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == rc, r.stderr
