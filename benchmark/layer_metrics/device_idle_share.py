"""Share of the traced window (two whole passes with their boundaries) in
which no operation ran on the device, mean over the cell's chips, in %."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
