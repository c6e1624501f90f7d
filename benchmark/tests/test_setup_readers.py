"""The readers PR 37 adds, on hand-made ``run.before`` / ``run.after`` /
``run.trace``: set-up's seconds are what ``run.before`` holds (the
registry is cumulative from the process's first import), the host's stall
counters are the window's change a pass, a scope's device time comes from
the traced steps -- and each finds nothing in a program without its
series."""

import importlib

import pytest

STAGE = "{}.stage_seconds{{stage={}}}".format
SETUP = ("setup_compile_s", "setup_cold_compiles", "setup_table_load_s",
         "setup_dataset_load_s", "setup_boundary_s", "setup_train_s")
HOST = ("host_runqueue_wait_ms", "host_stolen_ms")
SCOPES = ("pull_device_ms", "seqpool_device_ms", "tower_device_ms",
          "push_device_ms")


class _Run:
    """What benchmark.run.Run gives a reader, by hand."""

    def __init__(self, before, after, passes=4, trace=None, traced_steps=0):
        self.before, self.after = before, after
        self.passes = [{}] * passes
        self.trace, self.traced_steps = trace, traced_steps


def _snap(counters=None, histograms=None) -> dict:
    return {"counters": counters or {}, "gauges": {},
            "histograms": {k: {"sum": v, "count": 1}
                           for k, v in (histograms or {}).items()}}


def _read(name: str, run):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).read(run)


def _change():
    """A program with every series, at the window's start and end."""
    before = _snap(
        counters={"jit.compiles{stage=train.step}": 2.0,
                  "jit.compiles{stage=untagged}": 40.0,
                  "jit.cache_hits{stage=untagged}": 30.0,
                  "host.runqueue_wait_seconds{thread=dispatch}": 1.0,
                  "host.runqueue_wait_seconds{thread=feed}": 2.0,
                  "host.steal_seconds": 0.5,
                  "host.cpu_pressure_seconds": 0.25},
        histograms={"jit.trace_seconds{stage=train.step}": 3.0,
                    "jit.trace_seconds{stage=untagged}": 0.5,
                    "jit.lower_seconds{stage=train.step}": 2.0,
                    "jit.compile_seconds{stage=train.step}": 20.0,
                    "jit.compile_seconds{stage=untagged}": 1.5,
                    STAGE("start", "table_load"): 25.0,
                    STAGE("start", "store_sort"): 9.0,
                    STAGE("start", "dataset_load"): 11.0,
                    STAGE("pass", "lookup"): 6.0,
                    STAGE("pass", "upload"): 20.0,
                    STAGE("pass", "set_rows"): 2.0,
                    "trainer.pass_seconds": 22.5})
    after = _snap(
        counters={"jit.compiles{stage=train.step}": 2.0,
                  "jit.compiles{stage=untagged}": 40.0,
                  "jit.cache_hits{stage=untagged}": 30.0,
                  "host.runqueue_wait_seconds{thread=dispatch}": 1.5,
                  "host.runqueue_wait_seconds{thread=feed}": 2.25,
                  "host.runqueue_wait_seconds{thread=watch}": 0.25,
                  "host.steal_seconds": 0.75,
                  "host.cpu_pressure_seconds": 1.0},
        histograms={"jit.trace_seconds{stage=train.step}": 3.0,
                    "jit.lower_seconds{stage=train.step}": 2.0,
                    "jit.compile_seconds{stage=train.step}": 20.0,
                    STAGE("start", "table_load"): 25.0,
                    STAGE("start", "dataset_load"): 11.0,
                    STAGE("pass", "lookup"): 9.0,
                    "trainer.pass_seconds": 70.0})
    return before, after


def test_setup_readers_read_what_the_window_start_holds():
    run = _Run(*_change())
    assert _read("setup_compile_s", run) == 27.0
    assert _read("setup_cold_compiles", run) == 12.0
    assert _read("setup_table_load_s", run) == 25.0  # not its children
    assert _read("setup_dataset_load_s", run) == 11.0
    assert _read("setup_boundary_s", run) == 28.0
    assert _read("setup_train_s", run) == 22.5


def test_host_readers_take_the_windows_change_a_pass():
    run = _Run(*_change(), passes=4)
    # (0.5 + 0.25 + 0.25 of a thread first heard in the window) / 4 passes
    assert _read("host_runqueue_wait_ms", run) == 250.0
    assert _read("host_stolen_ms", run) == 250.0
    # a host without PSI reports the steal alone
    for snap in (run.before, run.after):
        del snap["counters"]["host.cpu_pressure_seconds"]
    assert _read("host_stolen_ms", run) == 62.5


def test_scope_readers_divide_the_traced_steps_device_seconds():
    trace = {"scope_s": [["push", 2.0], ["pull", 1.0], ["tower", 0.5],
                         ["seqpool_cvm", 0.25], ["dense_opt", 0.125],
                         ["metrics", 0.0625], ["unscoped", 0.03125]]}
    run = _Run(_snap(), _snap(), trace=trace, traced_steps=200)
    assert _read("pull_device_ms", run) == 5.0
    assert _read("seqpool_device_ms", run) == 1.25
    assert _read("tower_device_ms", run) == 3.125
    assert _read("push_device_ms", run) == 10.0


@pytest.mark.parametrize("name", SETUP + HOST + SCOPES)
def test_a_reader_finds_nothing_in_a_program_without_its_series(name):
    # the parent of PR 37: the backend's compiles and the boundary's
    # stages are there, nothing of the start family, the three phases or
    # the host's counters; an untraced run, or a step without the scopes
    old = _snap(
        counters={"jit.compiles{stage=train.step}": 2.0,
                  "jit.cache_hits{stage=train.step}": 2.0},
        histograms={"jit.compile_seconds{stage=train.step}": 20.0,
                    STAGE("pass", "lookup"): 6.0,
                    STAGE("trainer", "step"): 1.0})
    run = _Run(old, old, trace={"scope_s": [["fusion", 1.0]]},
               traced_steps=100)
    found_on_the_parent = {"setup_cold_compiles": 0.0,
                           "setup_boundary_s": 6.0}
    assert _read(name, run) == found_on_the_parent.get(name)
    assert _read(name, _Run(_snap(), _snap())) is None
