"""Device time a step in the attention layers (projections, rotary codes,
scores): the named scopes ``attn_window`` + ``attn_full`` of the traced
steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("attn_window", "attn_full")


def read(run):
    return scope_ms_per_step(run, SCOPES)
