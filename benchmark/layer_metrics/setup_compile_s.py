"""Set-up's seconds making programs, all three phases: tracing functions
to jaxprs (``jit.trace_seconds``), lowering them to StableHLO
(``jit.lower_seconds``) and the backend's compile or, on a persistent-cache
hit, the retrieval (``jit.compile_seconds``), over every stage, up to the
window's start.  It overlaps the phase inside which a program was made (a
first ``train.step`` compiles inside a pass), so set-up's phases are
summed without it."""
from benchmark.layer_metrics._setup import seconds_before

PHASES = ("jit.trace_seconds", "jit.lower_seconds", "jit.compile_seconds")


def read(run):
    got = [seconds_before(run, name) for name in PHASES]
    # a program that hears the backend alone has no series of the other two
    return None if None in got[:2] else sum(g or 0.0 for g in got)
