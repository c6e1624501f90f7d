"""Device time a step in the fused sequence pool and CVM transform: the
named scope ``seqpool_cvm`` of the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("seqpool_cvm",)


def read(run):
    return scope_ms_per_step(run, SCOPES)
