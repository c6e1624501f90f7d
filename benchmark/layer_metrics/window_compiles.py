"""Compile requests inside the window: the growth of ``jit.compiles``
over all stages.  A request that the persistent cache serves counts too;
the steady state is 0."""


def read(run):
    return run.counter_delta("jit.compiles")
