"""The least time the KDA recurrence needs on this device
(``kda_scan_cost`` of the cell's model: 6 x 128 x 128 operations a head and
token, three times; q, k, v, g, o and their cotangents written once and
read once; no chunk length, no solve, no rematerialised forward) over the
device time of the scope ``kda_scan``, in %."""
from benchmark.layer_metrics._scopes import roofline_share
from benchmark.layer_metrics.kda_scan_device_ms import SCOPES


def read(run):
    return roofline_share(run, SCOPES, "kda_scan")
