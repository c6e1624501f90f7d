"""The job-start path under the program's own stages (PR 37): the ``start``
family on the registry's and the device trace's clock, the three phases of
making a program, the host's stall counters, the pass's head and tail, and
one series for a whole pass."""

import glob
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu import telemetry
from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.telemetry import compiles
from paddlebox_tpu.train.trainer import Trainer
from paddlebox_tpu.utils.profiler import HOST, CompletionWatcher, HostStall

S, DENSE, B = 3, 2, 16
N_FILES = 3


def _snap() -> dict:
    return telemetry.registry.snapshot()


def _grew(before: dict, after: dict, series: str) -> tuple:
    """(observations, seconds) a histogram series grew by."""
    a = after["histograms"].get(series, {"count": 0, "sum": 0.0})
    b = before["histograms"].get(series, {"count": 0, "sum": 0.0})
    return a["count"] - b["count"], a["sum"] - b["sum"]


def _start(before, after, stage: str) -> tuple:
    return _grew(before, after, f"start.stage_seconds{{stage={stage}}}")


def _counted(before: dict, after: dict, series: str) -> float:
    return (after["counters"].get(series, 0.0)
            - before["counters"].get(series, 0.0))


def _dataset(tmp_path, n_files=N_FILES, load=True):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=8)
    files = write_synth_files(
        str(tmp_path / "data"), n_files=n_files, ins_per_file=32,
        n_sparse_slots=S, vocab_per_slot=40, dense_dim=DENSE, seed=3)
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    if load:
        ds.load_into_memory()
    return ds


def _model_and_conf(**table_kw):
    tconf = SparseTableConfig(embedding_dim=4, **table_kw)
    return CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,)), tconf


def _rows(tconf, n=200):
    rng = np.random.default_rng(0)
    keys = rng.permutation(np.arange(1, n + 1, dtype=np.uint64))
    vals = rng.normal(size=(n, tconf.row_width + 1)).astype(np.float32)
    return {"keys": keys, "values": vals}


# --------------------------------------------------------------------------- #
# (a) every stage of the family observes once a call, children inside
# --------------------------------------------------------------------------- #
def _load_into_memory(tmp_path):
    ds = _dataset(tmp_path, load=False)
    ds.load_into_memory()
    ds.close()


def _preload_into_memory(tmp_path):
    ds = _dataset(tmp_path, load=False)
    ds.preload_into_memory()
    ds.wait_preload_done()
    assert ds.unique_keys().shape[0] > 0  # the block is usable
    ds.close()


def _preload_into_disk(tmp_path):
    ds = _dataset(tmp_path, load=False)
    ds.preload_into_disk(str(tmp_path / "spill"))
    ds.wait_preload_done()
    ds.release_memory()
    ds.close()


def _table_load(tmp_path):
    _, tconf = _model_and_conf()
    table = SparseTable(tconf, seed=0)
    table.load_state_dict(_rows(tconf))
    assert table.n_features == 200
    table.close()


def _table_load_with_a_log(tmp_path):
    _, tconf = _model_and_conf(store_log_dir=str(tmp_path / "log"))
    table = SparseTable(tconf, seed=0)
    table.load_state_dict(_rows(tconf))
    table.close()


def _sharded_table_load(tmp_path):
    from paddlebox_tpu.parallel import ShardedSparseTable, make_mesh

    _, tconf = _model_and_conf()
    table = ShardedSparseTable(tconf, make_mesh(2), seed=0)
    table.load_state_dict(_rows(tconf))
    table.close()


def _trainer(tmp_path):
    model, tconf = _model_and_conf()
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10))
    trainer.load_dense_state(trainer.params, trainer.opt_state)
    trainer.close()


def _sharded_trainer(tmp_path):
    from paddlebox_tpu.parallel import MultiChipTrainer, make_mesh

    model, tconf = _model_and_conf()
    trainer = MultiChipTrainer(
        model, tconf, make_mesh(2), TrainerConfig(auc_buckets=1 << 10))
    trainer.load_dense_state(*trainer.dense_state())
    trainer.close()


TABLE_CHILDREN = {"store_sort": 1, "store_split": 1, "invalidate": 1}
FAMILY = {
    # call: ({parent: observations}, {child: observations})
    "load_into_memory": (_load_into_memory, {"dataset_load": 1},
                         {"read_parse": N_FILES, "merge": 1}),
    "preload_into_memory": (_preload_into_memory, {"dataset_load": 1},
                            {"read_parse": N_FILES, "merge": 1}),
    "preload_into_disk": (_preload_into_disk, {"dataset_load": 1},
                          {"read_parse": N_FILES}),
    "table_load": (_table_load, {"table_load": 1}, TABLE_CHILDREN),
    "table_load_with_a_log": (_table_load_with_a_log, {"table_load": 1},
                              {**TABLE_CHILDREN, "log_rewrite": 1}),
    "sharded_table_load": (_sharded_table_load, {"table_load": 1},
                           TABLE_CHILDREN),
    "trainer": (_trainer, {"trainer_init": 1, "dense_load": 1}, {}),
    "sharded_trainer": (_sharded_trainer,
                        {"trainer_init": 1, "dense_load": 1}, {}),
}
ALL_STAGES = ("dataset_load", "read_parse", "merge", "table_load",
              "store_sort", "store_split", "invalidate", "log_rewrite",
              "dense_load", "trainer_init")


@pytest.mark.parametrize("call", sorted(FAMILY))
def test_a_start_stage_observes_once_a_call_with_its_children_inside(
        tmp_path, call):
    fn, parents, children = FAMILY[call]
    before = _snap()
    t0 = time.perf_counter()
    fn(tmp_path)
    wall = time.perf_counter() - t0
    after = _snap()
    want = {**parents, **children}
    for stage in ALL_STAGES:
        assert _start(before, after, stage)[0] == want.get(stage, 0), stage
    parent_s = sum(_start(before, after, s)[1] for s in parents)
    child_s = sum(_start(before, after, s)[1] for s in children)
    # one reader thread: read_parse does not overlap itself either
    assert 0.0 <= child_s <= parent_s <= wall


def test_the_dataset_keeps_no_timer_of_its_own(tmp_path):
    ds = _dataset(tmp_path, n_files=1)
    assert not hasattr(ds, "read_timer")
    with pytest.raises(ImportError):
        import paddlebox_tpu.utils.timer  # noqa: F401
    ds.close()


# --------------------------------------------------------------------------- #
# (b) one clock: the start stages on a live jax.profiler trace
# --------------------------------------------------------------------------- #
def test_table_load_lands_on_a_live_trace_with_the_sort_inside(tmp_path):
    _, tconf = _model_and_conf()
    table = SparseTable(tconf, seed=0)
    trace_dir = str(tmp_path / "xtrace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("outer.restore"):
            table.load_state_dict(_rows(tconf))
    finally:
        jax.profiler.stop_trace()
    table.close()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
    if not any(n == "outer.restore" for n, _, _ in events):
        pytest.skip("this host's profiler writes no host plane")

    def span(name):
        (got,) = [(s, e) for n, s, e in events if n == name]
        return got

    outer, load = span("outer.restore"), span("pbox.start.table_load")
    assert outer[0] <= load[0] and load[1] <= outer[1]
    for child in ("store_sort", "store_split", "invalidate"):
        s, e = span("pbox.start." + child)
        assert load[0] <= s and e <= load[1], child


# --------------------------------------------------------------------------- #
# (c) the compile witness hears all three phases
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("series", ["jit.trace_seconds", "jit.lower_seconds",
                                    "jit.compile_seconds"])
def test_a_phase_grows_on_a_first_call_and_not_on_the_second(series):
    stage = "test.phases." + series.split(".")[1]
    name = f"{series}{{stage={stage}}}"

    def slow_to_trace(x):
        time.sleep(0.05)  # Python's time: the trace's, not the program's
        return jnp.tanh(x) * 2.0

    fn = compiles.counted_jit(slow_to_trace, stage=stage)
    x = jnp.ones((7, 3))
    before = _snap()
    fn(x)
    first = _snap()
    fn(x)
    second = _snap()
    n, s = _grew(before, first, name)
    assert n >= 1 and s > 0.0
    if series == "jit.trace_seconds":
        # tanh and multiply are traced inside it: counted once, not twice
        assert 0.05 <= s < 0.05 + 1.0
    assert _grew(first, second, name) == (0, 0.0)
    row = compiles.compile_summary()[stage]
    assert set(row) == {"compiles", "cache_hits", "trace_seconds",
                        "lower_seconds", "seconds"}
    assert row["compiles"] == 1 and row["trace_seconds"] >= 0.05


def test_a_trace_inside_a_trace_is_counted_once():
    inner = jax.jit(lambda x: (time.sleep(0.1), x + 1.0)[1])

    def outer(x):
        time.sleep(0.05)
        return inner(x) * 3.0

    stage = "test.phases.nested"
    before = _snap()
    t0 = time.perf_counter()
    compiles.counted_jit(outer, stage=stage)(jnp.ones(5))
    wall = time.perf_counter() - t0
    n, s = _grew(before, _snap(), f"jit.trace_seconds{{stage={stage}}}")
    assert n >= 2  # outer, inner, and each jnp function on the way
    assert 0.15 <= s <= wall  # not 0.25: inner's 0.1 is in outer's too


# --------------------------------------------------------------------------- #
# (d) the host's stall counters, on a fake /proc and on the real one
# --------------------------------------------------------------------------- #
def _fake_proc(root, schedstat=None, pressure=None, steal=None):
    os.makedirs(os.path.join(root, "thread-self"), exist_ok=True)
    os.makedirs(os.path.join(root, "pressure"), exist_ok=True)

    def put(rel, text):
        path = os.path.join(root, rel)
        if text is None:
            if os.path.exists(path):
                os.remove(path)
            return
        with open(path, "w") as f:
            f.write(text)

    put("thread-self/schedstat",
        None if schedstat is None else f"123456789 {schedstat} 42\n")
    put("pressure/cpu",
        None if pressure is None else
        f"some avg10=0.00 avg60=0.00 avg300=0.00 total={pressure}\n"
        f"full avg10=0.00 avg60=0.00 avg300=0.00 total=0\n")
    put("stat",
        None if steal is None else
        f"cpu  100 0 50 1000 5 0 1 {steal} 0 0\ncpu0 100 0 50 1000 5 0 1 "
        f"{steal} 0 0\nintr 1\n")


HOST_SERIES = ("host.runqueue_wait_seconds{thread=probe}",
               "host.cpu_pressure_seconds", "host.steal_seconds",
               "host.involuntary_switches")


@pytest.mark.parametrize("missing", ["none", "pressure", "schedstat", "all"])
def test_host_stall_reads_what_the_kernel_has_and_skips_the_rest(
        tmp_path, missing):
    root = str(tmp_path / "proc")
    tick = os.sysconf("SC_CLK_TCK")
    have = {"schedstat": missing not in ("schedstat", "all"),
            "pressure": missing not in ("pressure", "all"),
            "steal": missing != "all"}

    def write(schedstat, pressure, steal):
        _fake_proc(root,
                   schedstat=schedstat if have["schedstat"] else None,
                   pressure=pressure if have["pressure"] else None,
                   steal=steal if have["steal"] else None)

    host = HostStall(root)
    write(2_000_000_000, 5_000_000, 7 * tick)
    before = _snap()
    host.thread("probe")
    host.process()
    first = _snap()
    write(2_500_000_000, 5_250_000, 10 * tick)
    host.thread("probe")
    host.process()
    second = _snap()
    wait, pressure, steal, _ = HOST_SERIES
    # the first reading: a thread's wait from its start, the host's two
    # from now
    assert _counted(before, first, wait) == (
        pytest.approx(2.0) if have["schedstat"] else 0.0)
    assert _counted(before, first, pressure) == 0.0
    assert _counted(before, first, steal) == 0.0
    # the second: the growth
    assert _counted(first, second, wait) == (
        pytest.approx(0.5) if have["schedstat"] else 0.0)
    assert _counted(first, second, pressure) == (
        pytest.approx(0.25) if have["pressure"] else 0.0)
    assert _counted(first, second, steal) == (
        pytest.approx(3.0) if have["steal"] else 0.0)
    # ru_nivcsw is the process's own, whatever /proc has
    assert _counted(before, second, HOST_SERIES[3]) >= 0.0
    assert HOST_SERIES[3] in second["counters"]


def test_a_missing_file_leaves_its_series_absent(tmp_path):
    host = HostStall(str(tmp_path / "nothing_here"))
    host.thread("absent_probe")
    host.process()
    got = _snap()["counters"]
    assert "host.runqueue_wait_seconds{thread=absent_probe}" not in got


def test_each_thread_accounts_for_itself_on_the_real_proc():
    if not os.path.exists("/proc/thread-self/schedstat"):
        pytest.skip("no /proc/thread-self/schedstat on this host")
    names = ["real_a", "real_b"]
    threads = [threading.Thread(target=HOST.thread, args=(n,))
               for n in names]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    HOST.after_drain(CompletionWatcher())
    got = _snap()["counters"]
    for n in names + ["dispatch"]:
        assert got[f"host.runqueue_wait_seconds{{thread={n}}}"] >= 0.0
    assert got["host.involuntary_switches"] >= 0.0
    t0 = time.perf_counter()
    idle = CompletionWatcher()  # no thread: nothing queued
    for _ in range(50):
        HOST.after_drain(idle)
    assert (time.perf_counter() - t0) / 50 < 0.005  # a few file reads


def test_the_watcher_accounts_on_its_own_thread():
    if not os.path.exists("/proc/thread-self/schedstat"):
        pytest.skip("no /proc/thread-self/schedstat on this host")
    series = "host.runqueue_wait_seconds{thread=watch}"
    watch = CompletionWatcher()
    watch.account()  # no thread yet: nothing to account for, no error
    watch.dispatched(jnp.ones(3) + 1.0, time.perf_counter())
    watch.settle()
    before = _snap()
    watch.account()
    watch.close()  # the thread finishes what it holds first
    after = _snap()
    assert series in after["counters"]
    assert _counted(before, after, series) >= 0.0


# --------------------------------------------------------------------------- #
# (e) the pass: its head and tail have names, the whole has a series
# --------------------------------------------------------------------------- #
DISPATCHER_STAGES = ("open", "feed_wait", "step", "drain", "readback",
                     "observe")
PRODUCER_STAGES = ("batch", "plan", "feed")


@pytest.mark.parametrize("prefetch", [2, 0], ids=["prefetch", "inline"])
def test_the_stages_cover_a_pass_on_the_dispatching_thread(
        tmp_path, prefetch):
    ds = _dataset(tmp_path)
    model, tconf = _model_and_conf(hbm_cache_rows=1 << 10)
    trainer = Trainer(
        model, tconf,
        TrainerConfig(auc_buckets=1 << 10, prefetch_batches=prefetch),
        seed=0)
    table = SparseTable(tconf, seed=0)

    def one_pass():
        table.begin_pass(ds.unique_keys())
        t0 = time.perf_counter()
        m = trainer.train_from_dataset(ds, table)
        wall = time.perf_counter() - t0
        table.end_pass()
        return m, wall

    one_pass()  # compiles
    stages = DISPATCHER_STAGES + (() if prefetch else PRODUCER_STAGES)
    best = 0.0
    for _ in range(3):  # a neighbour's burst may stretch one pass
        before = _snap()
        m, wall = one_pass()
        after = _snap()
        for stage in ("open", "observe", "drain", "readback"):
            assert _grew(before, after,
                         f"trainer.stage_seconds{{stage={stage}}}")[0] == 1
        covered = sum(
            _grew(before, after,
                  f"trainer.stage_seconds{{stage={s}}}")[1] for s in stages)
        assert covered <= wall
        # one series for the whole pass: its duration_s
        n, s = _grew(before, after, "trainer.pass_seconds")
        assert n == 1 and s == pytest.approx(m["duration_s"])
        assert s <= wall
        best = max(best, covered / wall)
    assert best >= 0.95
    trainer.close()
    ds.close()


def test_a_pass_tells_the_three_threads_wait(tmp_path):
    if not os.path.exists("/proc/thread-self/schedstat"):
        pytest.skip("no /proc/thread-self/schedstat on this host")
    ds = _dataset(tmp_path)
    model, tconf = _model_and_conf()
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10))
    table = SparseTable(tconf, seed=0)
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()
    trainer.close()  # the watcher's thread has accounted by now
    got = _snap()["counters"]
    for thread in ("dispatch", "feed", "watch"):
        assert f"host.runqueue_wait_seconds{{thread={thread}}}" in got
    ds.close()


def test_the_sharded_pass_has_the_same_head_tail_and_series(tmp_path):
    from paddlebox_tpu.parallel import (
        MultiChipTrainer,
        ShardedSparseTable,
        make_mesh,
    )

    ds = _dataset(tmp_path)
    model, tconf = _model_and_conf()
    mesh = make_mesh(2)
    trainer = MultiChipTrainer(
        model, tconf, mesh, TrainerConfig(auc_buckets=1 << 10), seed=0)
    table = ShardedSparseTable(tconf, mesh, seed=0)
    before = _snap()
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    trainer.close()
    after = _snap()
    for stage in ("open", "observe"):
        assert _grew(before, after,
                     f"trainer.stage_seconds{{stage={stage}}}")[0] == 1
    n, s = _grew(before, after, "trainer.pass_seconds")
    assert n == 1 and s == pytest.approx(m["duration_s"])
    if os.path.exists("/proc/thread-self/schedstat"):
        assert _counted(
            before, after,
            "host.runqueue_wait_seconds{thread=dispatch}") >= 0.0
        assert ("host.runqueue_wait_seconds{thread=feed}"
                in after["counters"])
    table.close()
    ds.close()
