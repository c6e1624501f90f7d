"""Device time a step in the looped stack: every use of every layer (the
attention layers' norms, projections, rotary codes and score blocks, the
dense feed-forwards with their norms), all rounds, forward and backward --
the named scopes ``attn_full`` and ``dense_mlp`` of the traced steps, in ms
a step.  The scan's ``while`` carries neither name and is not summed."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("attn_full", "dense_mlp")


def read(run):
    return scope_ms_per_step(run, SCOPES)
