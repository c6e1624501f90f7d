"""Device time a step in the KDA operators' recurrence alone, everything
from q, k, v, g and beta to o (the pairs' decays, the triangular solve, the
scan over chunks that carries the state): the named scope ``kda_scan`` of
the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("kda_scan",)


def read(run):
    return scope_ms_per_step(run, SCOPES)
