"""Time a pass the host as a whole kept from its runnable tasks: the
window's change of ``host.steal_seconds`` (the hypervisor ran others; all
CPUs, ``/proc/stat``) and ``host.cpu_pressure_seconds`` (some task waited
for a CPU; ``/proc/pressure/cpu``), per pass, in ms.  A host without one
of the two files reports the other alone."""
from benchmark.layer_metrics._window import counter_change

SERIES = ("host.steal_seconds", "host.cpu_pressure_seconds")


def read(run):
    got = [counter_change(run, s) for s in SERIES]
    got = [g for g in got if g is not None]
    return 1e3 * sum(got) / len(run.passes) if got else None
