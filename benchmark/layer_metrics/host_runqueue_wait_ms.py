"""Time a pass the pass loop's threads (dispatcher, feed producer,
completion watcher) were runnable and not running: the window's change of
``host.runqueue_wait_seconds`` over all three (each thread's own
``/proc/thread-self/schedstat``, read once a pass), per pass, in ms."""
from benchmark.layer_metrics._setup import family


def read(run):
    after = family(run.after, "counters", "host.runqueue_wait_seconds")
    if not after:
        return None
    before = family(run.before, "counters", "host.runqueue_wait_seconds")
    return 1e3 * (sum(after) - sum(before)) / len(run.passes)
