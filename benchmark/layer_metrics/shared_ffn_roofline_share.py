"""The least time the shared experts' and the dense layers' products need
on this device (``ffn_cost`` of the cell's model) over the device time of
the scopes ``shared_experts`` + ``dense_mlp``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.shared_ffn_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "ffn")
