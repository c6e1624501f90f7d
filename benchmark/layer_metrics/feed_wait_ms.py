"""Time a step the dispatching thread spent blocked on an empty feed queue:
``trainer.stage_seconds{stage=feed_wait}`` (the consumer's waits in
``_FeedPrefetcher.__next__``) over the window, per step.  Near zero while
the producer keeps ahead of the device."""
from benchmark.layer_metrics._window import stage_seconds


def read(run):
    s = stage_seconds(run, "trainer", ["feed_wait"])
    return None if s is None else 1e3 * s / run.steps
