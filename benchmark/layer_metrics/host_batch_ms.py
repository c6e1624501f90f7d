"""Time a step the feed producer spent assembling the batch, inside
``dataset.batches()`` (``BatchBuilder.build``), before planning it:
``trainer.stage_seconds{stage=batch}`` over the window, per step.  With
``host_plan_feed_ms`` it is the producer's whole period; where that is over
the device's step, the feed bounds the pass (``starved_dispatch_share``)."""
from benchmark.layer_metrics._window import stage_seconds


def read(run):
    s = stage_seconds(run, "trainer", ["batch"])
    return None if s is None else 1e3 * s / run.steps
