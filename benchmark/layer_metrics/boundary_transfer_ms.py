"""Moving rows per boundary, as the host sees it: ``pass.stage_seconds``
of alloc, upload, fill (begin_pass) and pack, d2h, set_rows (end_pass).
Host time of the calls; whether the device is still at it when
``begin_pass`` returns is ``begin_backlog_share``."""
from benchmark.layer_metrics._window import stage_seconds


def read(run):
    s = stage_seconds(run, "pass", ["alloc", "upload", "fill", "pack", "d2h",
                                    "set_rows"])
    return None if s is None else 1e3 * s / len(run.passes)
