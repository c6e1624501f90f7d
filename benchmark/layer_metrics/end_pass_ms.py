"""Median wall time of ``table.end_pass()`` over the window's passes (the
benchmark's own span), in ms."""

import statistics


def read(run):
    return 1e3 * statistics.median(run.span_seconds("end_pass"))
