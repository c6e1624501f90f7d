"""Host seconds per step in key planning and feed assembly: the growth of
``trainer.stage_seconds{stage=plan}`` and ``{stage=feed}`` (StatsProfiler,
always on) over the window, per step, in ms."""


def read(run):
    total = sum(run.histogram_delta(
        f"trainer.stage_seconds{{stage={s}}}")[0] for s in ("plan", "feed"))
    return 1e3 * total / run.steps if run.steps else None
