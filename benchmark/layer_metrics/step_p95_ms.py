"""95th percentile of a step's completion interval on the device, from
the window's growth of ``trainer.step_complete_seconds`` (completion(i) -
max(completion(i-1), dispatch(i)), one sample a dispatch, taken by the
trainer's completion watcher thread), per step of a dispatch.  Buckets
are 5% apart: the value is a bucket's interpolated inside."""
from benchmark.layer_metrics._window import bucket_growth, steps_per_dispatch


def read(run):
    got = bucket_growth(run, "trainer.step_complete_seconds")
    if got is None:
        return None
    bounds, counts, largest = got
    rank, seen = 0.95 * sum(counts), 0.0
    for i, c in enumerate(counts):
        if c and seen + c >= rank:
            lo = bounds[i - 1] if i else 0.0
            hi = bounds[i] if i < len(bounds) else max(largest, lo)
            at = lo + (hi - lo) * (rank - seen) / c
            return 1e3 * at / steps_per_dispatch(run)
        seen += c
    return None
