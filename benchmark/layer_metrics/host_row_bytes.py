"""Embedding-row bytes that crossed between host and device at the pass
boundaries: the growth of ``pass.host_row_bytes_in`` + ``_out`` over the
window per pass, in MB (a count)."""


def read(run):
    moved = run.counter_delta("pass.host_row_bytes_in") + run.counter_delta(
        "pass.host_row_bytes_out")
    return moved / len(run.passes) / 1e6
