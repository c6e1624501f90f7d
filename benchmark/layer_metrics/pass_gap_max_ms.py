"""The longest pass boundary of the window (``train_from_dataset``
returning for one pass to its call for the next), same clock as the
end-to-end ``pass_gap_ms``: the stall a job's operator notices.  A
maximum of 6-20 samples; its spread admits no bound (PERF.md section 2),
so it stands here and not among the end-to-end metrics."""


def read(run):
    return 1e3 * max(run.gaps_s) if run.gaps_s else None
