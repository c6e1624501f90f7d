"""Device time a step in the pull (the gather of the occurrences' rows
from the table): the named scope ``pull`` of the traced steps, in ms a
step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("pull",)


def read(run):
    return scope_ms_per_step(run, SCOPES)
