"""What the readers of the program's own spans and counters share: the
window's change of a registry series, between ``run.before`` and
``run.after``.  A series the program does not have (an older checkout) reads
as ``None``, and so does the metric built on it."""

from __future__ import annotations

STAGES = "{family}.stage_seconds{{stage={stage}}}"


def histogram_sum(run, series: str):
    """Seconds a histogram series grew by in the window, or None."""
    after = run.after["histograms"].get(series)
    if after is None:
        return None
    before = run.before["histograms"].get(series, {"sum": 0.0})
    return after["sum"] - before["sum"]


def stage_seconds(run, family: str, stages) -> float | None:
    """The window's seconds in the named stages of ``<family>.stage_seconds``
    together; None when the program has none of them."""
    got = [histogram_sum(run, STAGES.format(family=family, stage=s))
           for s in stages]
    got = [g for g in got if g is not None]
    return sum(got) if got else None


def counter_change(run, series: str, base: str | None = None):
    """Change of one counter series in the window.  A series that never
    counted is absent from a snapshot: it reads 0 where ``base`` (the
    counter it is a share of) is there, and None where that is absent too."""
    if series not in run.after["counters"] and (
            base or series) not in run.after["counters"]:
        return None
    return (run.after["counters"].get(series, 0.0)
            - run.before["counters"].get(series, 0.0))


def bucket_growth(run, series: str):
    """(boundaries, per-bucket growth in the window, the series' largest
    sample), or None where the series is absent or did not grow."""
    after = run.after["histograms"].get(series)
    if after is None:
        return None
    before = run.before["histograms"].get(series)
    counts = list(after["counts"])
    if before is not None:
        counts = [a - b for a, b in zip(counts, before["counts"])]
    if sum(counts) <= 0:
        return None
    return after["boundaries"], counts, after["max"]


def steps_per_dispatch(run) -> float:
    n = counter_change(run, "trainer.dispatches")
    return run.steps / n if n else 1.0
