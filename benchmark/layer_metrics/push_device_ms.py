"""Device time a step in the push (the merge of the occurrences, the
scatter-add and adagrad over the unique rows): the named scope ``push`` of
the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("push",)


def read(run):
    return scope_ms_per_step(run, SCOPES)
