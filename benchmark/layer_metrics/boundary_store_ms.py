"""The host store's share of a boundary: ``pass.stage_seconds`` of fetch
(cache misses resolved or initialised), write_back and take_stage.  Near
zero while every census is a full hit; a guard like ``host_row_bytes``."""
from benchmark.layer_metrics._window import stage_seconds


def read(run):
    s = stage_seconds(run, "pass", ["fetch", "write_back", "take_stage"])
    return None if s is None else 1e3 * s / len(run.passes)
