"""Set-up's seconds in pass boundaries: every stage of
``pass.stage_seconds`` up to the window's start -- admission's chunks
(``begin_pass`` / ``end_pass`` over the table's keys), the check steps'
and the warm-up cycle's boundaries."""
from benchmark.layer_metrics._setup import seconds_before


def read(run):
    return seconds_before(run, "pass.stage_seconds")
