"""Median over the window's passes of ``train_from_dataset``'s wall time
per step (the benchmark's own span; the call ends in the pass's metric
read-back, which waits for the device), in ms."""

import statistics


def read(run):
    return 1e3 * statistics.median(
        p["train"] / p["steps"] for p in run.passes)
