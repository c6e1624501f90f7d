"""Device time a step in the attention layers under the block mask over
two streams (norms, projections, rotary codes, the strips of scores of the
noised and the clean queries): the named scope ``attn_block_diffusion`` of
the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("attn_block_diffusion",)


def read(run):
    return scope_ms_per_step(run, SCOPES)
