"""The least time the attention layers' products need on this device
(``attn_cost`` of the cell's model: scores on unmasked pairs only) over the
device time of the scopes ``attn_window`` + ``attn_full``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.attn_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "attn")
