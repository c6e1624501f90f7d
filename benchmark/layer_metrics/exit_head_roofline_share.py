"""The least time the R head passes and gate products need on this device
(``exit_head_cost`` of the cell's model: the head on the scored positions
and the gate on every position, once a round) over the device time of the
scopes ``lm_head``, ``exit_gate`` and ``round_norm``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.exit_head_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "exit_head")
