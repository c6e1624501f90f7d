"""Device time a step in the KDA operators (norm, the projections, the
short convolutions, decay and step size, the chunked scan, the output norm,
gate and projection): the named scopes ``kda_mixer`` and, nested in it,
``kda_scan`` of the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("kda_mixer", "kda_scan")


def read(run):
    return scope_ms_per_step(run, SCOPES)
