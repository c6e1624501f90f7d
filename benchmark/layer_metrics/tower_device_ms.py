"""Device time a step in the model's dense half: the named scopes
``tower`` (forward and backward, what no inner scope claims) and
``dense_opt`` (the dense optimizer) of the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("tower", "dense_opt")


def read(run):
    return scope_ms_per_step(run, SCOPES)
