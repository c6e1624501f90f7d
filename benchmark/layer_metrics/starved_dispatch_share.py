"""Share of dispatches that found no earlier one in flight, so the device
had run dry and waited for the host: ``trainer.dispatches_starved`` over
``trainer.dispatches``.  A pass's first dispatch always counts."""
from benchmark.layer_metrics._window import counter_change


def read(run):
    n = counter_change(run, "trainer.dispatches")
    starved = counter_change(run, "trainer.dispatches_starved",
                             base="trainer.dispatches")
    return None if not n else 100.0 * starved / n
