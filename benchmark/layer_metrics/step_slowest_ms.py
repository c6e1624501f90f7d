"""The window's slowest step on the device: the upper edge of the highest
bucket of ``trainer.step_complete_seconds`` that grew in the window, per
step of a dispatch.  A pass that stalls (PERF.md section 5) shows here in
every run, traced or not."""
from benchmark.layer_metrics._window import bucket_growth, steps_per_dispatch


def read(run):
    got = bucket_growth(run, "trainer.step_complete_seconds")
    if got is None:
        return None
    bounds, counts, largest = got
    top = max(i for i, c in enumerate(counts) if c)
    edge = bounds[top] if top < len(bounds) else largest
    return 1e3 * edge / steps_per_dispatch(run)
