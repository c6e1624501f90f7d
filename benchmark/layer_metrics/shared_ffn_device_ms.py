"""Device time a step in the feed-forward every token goes through
whatever the routing: the named scopes ``shared_experts`` (the sparse
layers' shared experts) + ``dense_mlp`` (the leading dense layers), of the
traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("shared_experts", "dense_mlp")


def read(run):
    return scope_ms_per_step(run, SCOPES)
