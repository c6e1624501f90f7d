"""Set-up's seconds restoring the table from a checkpoint's rows:
``start.stage_seconds{stage=table_load}`` (``load_state_dict``: the store's
sort and split, the caches' invalidation) up to the window's start."""
from benchmark.layer_metrics._setup import seconds_before


def read(run):
    return seconds_before(run, "start.stage_seconds", "table_load")
