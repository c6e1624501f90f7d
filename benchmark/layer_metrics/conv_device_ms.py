"""Device time a step in the gated short convolutions (norm, the
projection to the two gates and the convolved third, the taps' shifted
multiply-adds, the gates, the output projection): the named scope
``conv_mixer`` of the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("conv_mixer",)


def read(run):
    return scope_ms_per_step(run, SCOPES)
