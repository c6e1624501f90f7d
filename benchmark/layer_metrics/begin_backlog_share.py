"""Share of ``begin_pass`` calls that returned with the boundary's device
work still running (``is_ready`` false on the uploaded and filled pass
buffer or on the cache's rows): ``pass.device_pending{at=begin_exit}`` over ``pass.begins``.  The
first steps of the pass then queue behind it."""
from benchmark.layer_metrics._window import counter_change


def read(run):
    n = counter_change(run, "pass.begins")
    left = counter_change(run, "pass.device_pending{at=begin_exit}",
                          base="pass.begins")
    return None if not n else 100.0 * left / n
