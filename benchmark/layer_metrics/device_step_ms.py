"""Device time per step: chip 0's busy time (union of its op intervals)
inside the traced passes' ``train_from_dataset`` spans, per step, in ms."""


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    return 1e3 * run.trace["step_busy_s"] / run.traced_steps
