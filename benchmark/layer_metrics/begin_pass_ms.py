"""Median wall time of ``table.begin_pass(ds.unique_keys())`` over the
window's passes (the benchmark's own span), in ms."""

import statistics


def read(run):
    return 1e3 * statistics.median(run.span_seconds("begin_pass"))
