"""The least time the latent-attention layers' products need on this device
(``attn_cost`` of the cell's model: the projections, and the scores on the
causal triangle's pairs only) over the device time of the scope
``attn_latent``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.mla_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "attn")
