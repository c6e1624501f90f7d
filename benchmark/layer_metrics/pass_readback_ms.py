"""The pass's tail after the device has drained: the metric read-back of
``train_from_dataset`` (AUC, mean loss, gradient and weight norms, eager
programs), ``trainer.stage_seconds{stage=readback}`` per pass."""
from benchmark.layer_metrics._window import stage_seconds


def read(run):
    s = stage_seconds(run, "trainer", ["readback"])
    return None if s is None else 1e3 * s / len(run.passes)
