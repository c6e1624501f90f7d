"""The largest held expert's tokens over the held experts' mean, in %
(100 = even): the window's ``moe.expert_load_max`` over
``moe.expert_load_mean``, both summed over steps and layers."""
from benchmark.layer_metrics._window import counter_change


def read(run):
    top = counter_change(run, "moe.expert_load_max")
    mean = counter_change(run, "moe.expert_load_mean")
    return 100.0 * top / mean if top is not None and mean else None
