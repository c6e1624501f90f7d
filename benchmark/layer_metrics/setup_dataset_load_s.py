"""Set-up's seconds loading datasets: ``start.stage_seconds{stage=
dataset_load}``, one observation a ``load_into_memory`` (files to the
usable block, the key census with it) up to the window's start.  Loads
that ran side by side on several threads each count their own wall."""
from benchmark.layer_metrics._setup import seconds_before


def read(run):
    return seconds_before(run, "start.stage_seconds", "dataset_load")
