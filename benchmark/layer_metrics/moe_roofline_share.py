"""The least time the routed layers need on this device (``moe_cost`` of
the cell's model: the router, and the experts' products for the
token-expert pairs really routed to an expert held here -- the window's
``moe.pairs_local`` a step) over the device time of the scopes ``router`` +
``experts``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics._window import counter_change
from benchmark.layer_metrics.moe_device_ms import SCOPES


def read(run):
    pairs = counter_change(run, "moe.pairs_local")
    if not pairs or not run.steps:
        return None
    return roofline_share(run, SCOPES, "moe", pairs / run.steps)
