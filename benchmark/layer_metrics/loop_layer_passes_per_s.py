"""Layer applications a position, a second of the window: the program's
``loop.layer_passes`` counter (positions x rounds x layers of every step,
which only a stack that runs more than once counts) over the window's wall
seconds."""
from benchmark.layer_metrics._window import counter_change


def read(run):
    n = counter_change(run, "loop.layer_passes")
    return n / run.window_s if n else None
