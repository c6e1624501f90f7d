"""What the readers of a traced run's device seconds by named scope share
(``run.trace["scope_s"]``, rows ``[scope, seconds]``; trace_reduce.scope_of: the innermost
``jax.named_scope`` of each device operation).  A run without a trace, or a
program whose step has none of the scopes (an older checkout), reads None,
and so does the metric built on it."""

from __future__ import annotations

import importlib

from benchmark import costs


def scope_seconds(run, scopes):
    """Device seconds of ``scopes`` together over the traced steps, or
    None."""
    if run.trace is None or not run.traced_steps:
        return None
    got = [t for name, t in run.trace.get("scope_s", []) if name in scopes]
    return sum(got) if got else None


def scope_ms_per_step(run, scopes):
    s = scope_seconds(run, scopes)
    return None if s is None else 1e3 * s / run.traced_steps


def roofline_share(run, scopes, part: str, *args):
    """The least seconds the part's operations and bytes allow (the
    model's ``<part>_cost``, benchmark/models/<model>.py) on this device,
    over the scopes' device seconds a step, in %."""
    s = scope_seconds(run, scopes)
    model = importlib.import_module(
        "benchmark.models." + run.cell.cfg["model"])
    cost_of = getattr(model, part + "_cost", None)
    if not s or cost_of is None:
        return None
    least, _ = costs.roofline_seconds(
        cost_of(run.cell.cfg, *args), costs.load_peaks(run.device_kind))
    return 100.0 * least * run.traced_steps / s
