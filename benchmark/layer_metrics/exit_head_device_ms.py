"""Device time a step in what a looped stack does once a round beside its
layers: the norm between the rounds, the head's pass over the round's
state with its cross-entropies, and the exit gate with the exit
distribution, its entropy and the weighting of the rounds' losses -- the
named scopes ``round_norm``, ``lm_head`` and ``exit_gate`` of the traced
steps, in ms a step."""
from benchmark.layer_metrics._scopes import scope_ms_per_step

SCOPES = ("lm_head", "exit_gate", "round_norm")


def read(run):
    return scope_ms_per_step(run, SCOPES)
