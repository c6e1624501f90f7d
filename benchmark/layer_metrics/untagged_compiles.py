"""Compile requests of the whole process, up to the window's end, that no
stage claimed: ``jit.compiles{stage=untagged}``.  Eager programs outside
any ``stage_scope``: part of what a first run adds to ``setup_s``."""


def read(run):
    counters = run.after["counters"]
    if not any(k.startswith("jit.compiles") for k in counters):
        return None
    return counters.get("jit.compiles{stage=untagged}", 0.0)
