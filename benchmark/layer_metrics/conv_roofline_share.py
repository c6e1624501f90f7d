"""The least time the gated short convolutions' work needs on this device
(``conv_cost`` of the cell's model: the two projections' products, the
taps' multiply-adds, the gate-conv-gate chain read and written once) over
the device time of the scope ``conv_mixer``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.conv_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "conv")
