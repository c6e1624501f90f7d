"""Device time a step in the output head and the loss (final norm, logits
in token chunks, softmax cross-entropy): the named scope ``lm_head`` of
the traced steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step


def read(run):
    return scope_ms_per_step(run, ("lm_head",))
