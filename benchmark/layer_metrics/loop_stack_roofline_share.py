"""The least time the looped stack's products need on this device
(``stack_cost`` of the cell's model: every layer product once a use, R
uses a step, the scores on the causal pairs only, whatever implements
them) over the device time of the scopes ``attn_full`` and ``dense_mlp``,
in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.loop_stack_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "stack")
