"""Device time a step in the routed expert layers: the named scopes
``router`` + ``experts`` (routing, the experts' products, the weighted
sum) of the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("router", "experts")


def read(run):
    return scope_ms_per_step(run, SCOPES)
