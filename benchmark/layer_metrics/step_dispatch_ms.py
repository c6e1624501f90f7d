"""Host time to enqueue a step: ``trainer.stage_seconds{stage=step}`` over
the window, per step.  The call returns before the device has done the
work; what the device took is ``step_p95_ms`` / ``device_step_ms``."""
from benchmark.layer_metrics._window import stage_seconds


def read(run):
    s = stage_seconds(run, "trainer", ["step"])
    return None if s is None else 1e3 * s / run.steps
