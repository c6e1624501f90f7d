"""Programs the backend really compiled before the window: compile
requests (``jit.compiles``) less those the persistent cache served
(``jit.cache_hits``), over every stage.  0 on a warm cache."""
from benchmark.layer_metrics._setup import count_before


def read(run):
    n = count_before(run, "jit.compiles")
    return None if n is None else n - (count_before(run, "jit.cache_hits")
                                       or 0.0)
