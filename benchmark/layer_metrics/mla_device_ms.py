"""Device time a step in the latent-attention layers (norm, the two
down-projections and the latent's norm, the up-projection, rotary codes on
the slice, strips of scores, output projection): the named scope
``attn_latent`` of the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("attn_latent",)


def read(run):
    return scope_ms_per_step(run, SCOPES)
