"""Positions trained (those with a next token to predict) a second of the
window: the program's ``trainer.tokens`` counter over the window's wall
seconds."""
from benchmark.layer_metrics._window import counter_change


def read(run):
    n = counter_change(run, "trainer.tokens")
    return n / run.window_s if n else None
