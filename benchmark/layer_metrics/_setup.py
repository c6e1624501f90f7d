"""What the readers of set-up share: ``run.before``, the registry's
snapshot at the window's start, is cumulative from the process's first
import, so the seconds a series holds there are set-up's.  A series the
program does not have (an older checkout) reads as ``None``, and so does
the metric built on it."""

from __future__ import annotations


def family(snapshot: dict, kind: str, name: str) -> list:
    """The values of ``name``'s series in a snapshot, every label set of
    it (``name`` itself and ``name{...}``)."""
    return [v for k, v in snapshot[kind].items()
            if k == name or k.startswith(name + "{")]


def seconds_before(run, name: str, stage: str | None = None):
    """Seconds histogram ``name`` held when the window opened: one stage's
    series, or all of its series together; None where it has none."""
    if stage is not None:
        name = f"{name}{{stage={stage}}}"
    got = family(run.before, "histograms", name)
    return sum(h["sum"] for h in got) if got else None


def count_before(run, name: str):
    """Counter ``name`` when the window opened, over all its series; None
    where it never counted."""
    got = family(run.before, "counters", name)
    return sum(got) if got else None
