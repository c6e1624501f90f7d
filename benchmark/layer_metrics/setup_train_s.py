"""Set-up's seconds training: the sum of ``trainer.pass_seconds`` (one
observation a ``train_from_dataset``) up to the window's start -- the
check steps and the warm-up cycle, a first ``train.step``'s compile
inside them."""
from benchmark.layer_metrics._setup import seconds_before


def read(run):
    return seconds_before(run, "trainer.pass_seconds")
