"""The row cache's directory work per boundary, on the host:
``pass.stage_seconds`` of lookup, touch, plan_update, commit (and the
sharded table's hot_sync)."""
from benchmark.layer_metrics._window import stage_seconds


def read(run):
    s = stage_seconds(run, "pass", ["lookup", "touch", "plan_update",
                                    "commit", "hot_sync"])
    return None if s is None else 1e3 * s / len(run.passes)
