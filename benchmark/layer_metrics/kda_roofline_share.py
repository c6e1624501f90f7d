"""The least time the KDA operators' work needs on this device
(``kda_cost`` of the cell's model: the projections' products, the
convolutions' multiply-adds and the recurrence token by token, whatever
implements it) over the device time of the scopes ``kda_mixer`` and
``kda_scan``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.kda_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "kda")
