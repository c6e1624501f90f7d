"""The least time the block-diffusion attention layers' products need on
this device (``bd_attn_cost`` of the cell's model: the projections, and
the scores on the block mask's pairs only, whatever implements them) over
the device time of the scope ``attn_block_diffusion``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.bd_attn_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "bd_attn")
