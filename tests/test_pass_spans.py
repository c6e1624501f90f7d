"""The pass loop's own spans and counters (PR 24): one span primitive on the
device trace's clock, stage timing inside the pass boundary, feed wait and
starvation, the completion watcher, named step scopes, tagged eager
programs — and a profiled mode that is the same loop."""

import glob
import importlib
import os
import re
import threading

import numpy as np
import pytest

import jax

from paddlebox_tpu import telemetry
from paddlebox_tpu.config import (
    SparseTableConfig,
    TelemetryConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.telemetry import compiles
from paddlebox_tpu.train.trainer import Trainer

S, DENSE, B = 3, 2, 16


def _world(tmp_path, hbm_cache_rows=1 << 10, embedding_dim=4, **trainer_kw):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=8,
    )
    files = write_synth_files(
        str(tmp_path / "data"), n_files=1, ins_per_file=96,
        n_sparse_slots=S, vocab_per_slot=40, dense_dim=DENSE, seed=3,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(
        embedding_dim=embedding_dim, hbm_cache_rows=hbm_cache_rows)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
    trainer = Trainer(
        model, tconf, TrainerConfig(auc_buckets=1 << 10, **trainer_kw),
        seed=0)
    return ds, trainer, SparseTable(tconf, seed=0)


def _one_pass(ds, trainer, table) -> dict:
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    return m


def _hist(name: str) -> dict:
    return telemetry.registry.snapshot()["histograms"].get(
        name, {"count": 0, "sum": 0.0})


def _stage(family: str, stage: str) -> dict:
    return _hist(f"{family}.stage_seconds{{stage={stage}}}")


def _counter(name: str) -> float:
    return telemetry.registry.snapshot()["counters"].get(name, 0.0)


# --------------------------------------------------------------------------- #
# (a) one clock: the program's stages on a live jax.profiler trace
# --------------------------------------------------------------------------- #
def _host_events(trace_dir: str) -> list:
    """(name, start_ns, end_ns) of every host-plane event of the trace."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    profile = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return out


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
def test_stages_land_on_a_live_device_trace_inside_the_callers_span(
        tmp_path, warm):
    """cold: the pass's census misses the row cache, so its rows are
    fetched from the host store and uploaded; warm: every census key is
    resident, the pass buffer starts on the device and nothing crosses."""
    ds, trainer, table = _world(tmp_path)
    if warm:
        _one_pass(ds, trainer, table)  # misses and compiles outside the trace
    trace_dir = str(tmp_path / "xtrace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("outer.begin_pass"):
            table.begin_pass(ds.unique_keys())
        with jax.profiler.TraceAnnotation("outer.train"):
            trainer.train_from_dataset(ds, table)
        with jax.profiler.TraceAnnotation("outer.end_pass"):
            table.end_pass()
    finally:
        jax.profiler.stop_trace()
    trainer.close()
    ds.close()
    events = _host_events(trace_dir)
    names = {n for n, _, _ in events}
    if not any(n.startswith("outer.") for n in names):
        pytest.skip("this host's profiler writes no host plane")

    def inside(inner: str, outer: str) -> bool:
        (o0, o1), = [(s, e) for n, s, e in events if n == outer]
        got = [(s, e) for n, s, e in events if n == inner]
        return bool(got) and all(o0 <= s and e <= o1 for s, e in got)

    assert inside("pbox.data.census", "outer.begin_pass")
    for stage in ("census", "lookup", "alloc", "touch"):
        assert inside(f"pbox.pass.{stage}", "outer.begin_pass"), stage
    # (an empty row cache has nothing to fill a cold pass's buffer with)
    here, absent = ((("fill",), ("fetch", "upload")) if warm
                    else (("fetch", "upload"), ("fill",)))
    for stage in here:
        assert inside(f"pbox.pass.{stage}", "outer.begin_pass"), stage
    for stage in absent:
        assert f"pbox.pass.{stage}" not in names, stage
    for stage in ("batch", "plan", "feed", "feed_wait", "step", "drain",
                  "readback"):
        assert inside(f"pbox.trainer.{stage}", "outer.train"), stage
    assert inside("pbox.pass", "outer.train")  # telemetry.span("pass")
    for stage in ("plan_update", "set_rows", "commit", "write_back"):
        assert inside(f"pbox.pass.{stage}", "outer.end_pass"), stage


def test_span_enters_a_pbox_annotation(monkeypatch):
    """Without depending on the host's profiler: telemetry.span and a
    profiler stage enter TraceAnnotation("pbox.<name>")."""
    from paddlebox_tpu.telemetry import trace
    from paddlebox_tpu.utils.profiler import StatsProfiler, timed

    seen = []

    class Stub:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_trace_annotation", Stub)
    with telemetry.span("ckpt.save.base", kind="base"):
        with StatsProfiler("pass.stage_seconds").stage("upload"):
            pass
    with timed("data.census_seconds", "data.census"):
        pass
    assert seen == ["pbox.ckpt.save.base", "pbox.pass.upload",
                    "pbox.data.census"]


# --------------------------------------------------------------------------- #
# (b) every series counts, and the sums stay inside the wall time
# --------------------------------------------------------------------------- #
def test_one_pass_counts_every_series_and_sums_within_wall(tmp_path):
    import time

    ds, trainer, table = _world(tmp_path)

    def grew(series: str) -> tuple:
        a = after["histograms"].get(series, {"count": 0, "sum": 0.0})
        b = before["histograms"].get(series, {"count": 0, "sum": 0.0})
        return a["count"] - b["count"], a["sum"] - b["sum"]

    def counted(series: str) -> float:
        return (after["counters"].get(series, 0.0)
                - before["counters"].get(series, 0.0))

    def stage_s(stages) -> float:
        return sum(grew(f"pass.stage_seconds{{stage={s}}}")[1]
                   for s in stages)

    # the cold pass: its census misses the (empty) row cache, so the host
    # buffer is allocated, filled from the host store and uploaded
    before = telemetry.registry.snapshot()
    keys = ds.unique_keys()
    t1 = time.perf_counter()
    table.begin_pass(keys)
    t2 = time.perf_counter()
    after = telemetry.registry.snapshot()
    begin = ["census", "take_stage", "alloc", "lookup", "fetch", "upload",
             "touch"]
    for stage in begin:
        assert grew(f"pass.stage_seconds{{stage={stage}}}")[0] >= 1, stage
    assert grew("pass.stage_seconds{stage=lookup}")[0] == 1
    assert stage_s(begin) <= t2 - t1
    trainer.train_from_dataset(ds, table)  # compiles
    table.end_pass()
    trainer._watch.settle()

    # the warm pass: every census key is resident, so the pass buffer is
    # allocated on the device and neither fetched nor uploaded
    before = telemetry.registry.snapshot()
    t0 = time.perf_counter()
    keys = ds.unique_keys()
    t1 = time.perf_counter()
    table.begin_pass(keys)
    t2 = time.perf_counter()
    m = trainer.train_from_dataset(ds, table)
    t3 = time.perf_counter()
    table.end_pass()
    t4 = time.perf_counter()
    after = telemetry.registry.snapshot()

    steps = m["steps"]
    assert steps == 6
    assert grew("data.census_seconds")[0] == 1
    assert grew("data.census_seconds")[1] <= t1 - t0
    begin = ["census", "take_stage", "alloc", "lookup", "fill", "touch"]
    end = ["pack", "plan_update", "d2h", "set_rows", "commit", "write_back"]
    for stage in begin + end:
        assert grew(f"pass.stage_seconds{{stage={stage}}}")[0] >= 1, stage
    for stage in ("fetch", "upload"):
        assert grew(f"pass.stage_seconds{{stage={stage}}}")[0] == 0, stage
    assert grew("pass.stage_seconds{stage=lookup}")[0] == 1
    assert stage_s(begin) <= t2 - t1
    assert stage_s(end) <= t4 - t3
    for stage, n in (("batch", steps + 1), ("plan", steps), ("step", steps),
                     ("drain", 1),
                     ("readback", 1), ("feed_wait", steps + 1),
                     ("feed_put_wait", steps + 1)):
        assert grew(f"trainer.stage_seconds{{stage={stage}}}")[0] == n, stage
    assert sum(grew(f"trainer.stage_seconds{{stage={s}}}")[1]
               for s in ("feed_wait", "step", "drain", "readback")) <= t3 - t2
    assert counted("trainer.dispatches") == steps
    assert 1 <= counted("trainer.dispatches_starved") <= steps
    assert grew("trainer.step_complete_seconds")[0] == steps
    assert counted("pass.begins") == 1
    # is_ready is asked, never waited for: the counter may or may not move
    assert counted("pass.device_pending{at=begin_exit}") in (0.0, 1.0)

    # a census of resident keys and new ones takes the host's path again:
    # the misses fetched and uploaded, the hits filled from the cache
    before = telemetry.registry.snapshot()
    new = np.arange(1, 9, dtype=np.uint64) + keys.max()
    table.begin_pass(np.concatenate([keys, new]))
    after = telemetry.registry.snapshot()
    for stage in ("alloc", "fetch", "upload", "fill"):
        assert grew(f"pass.stage_seconds{{stage={stage}}}")[0] == 1, stage
    table.end_pass()
    trainer.close()
    ds.close()


def test_completion_watcher_samples_in_order_without_blocking_the_caller():
    import time

    from paddlebox_tpu.utils.profiler import CompletionWatcher

    class Slow:
        def __init__(self, gate):
            self.gate = gate

        def block_until_ready(self):
            self.gate.wait(5.0)

    w = CompletionWatcher()
    n0 = _hist("trainer.step_complete_seconds")["count"]
    d0 = _counter("trainer.dispatches")
    s0 = _counter("trainer.dispatches_starved")
    gates = [threading.Event() for _ in range(3)]
    t = time.perf_counter()
    for g in gates:
        w.dispatched(Slow(g), time.perf_counter())
    assert time.perf_counter() - t < 1.0  # three puts, no wait
    assert _counter("trainer.dispatches") - d0 == 3
    assert _counter("trainer.dispatches_starved") - s0 == 1  # only the first
    assert _hist("trainer.step_complete_seconds")["count"] == n0
    for g in gates:
        g.set()
    w.settle(5.0)
    assert _hist("trainer.step_complete_seconds")["count"] == n0 + 3
    w.dispatched(Slow(gates[0]), time.perf_counter())  # device had run dry
    assert _counter("trainer.dispatches_starved") - s0 == 2
    w.close()


# --------------------------------------------------------------------------- #
# (c) the profiled mode is the same loop
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["profile", "telemetry_trace_dir"])
def test_profiling_runs_the_same_loop(tmp_path, mode, monkeypatch):
    from paddlebox_tpu.train import pass_loop as trainer_mod

    threads = []
    real = trainer_mod._FeedPrefetcher

    class Spy(real):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            threads.append(self._thread)

    monkeypatch.setattr(trainer_mod, "_FeedPrefetcher", Spy)

    def run(sub, **kw):
        threads.clear()
        n_sync = _hist("trainer.step_complete_seconds")["count"]
        ds, trainer, table = _world(tmp_path / sub, **kw)
        m = _one_pass(ds, trainer, table)
        trainer.close()
        ds.close()
        return m, len(threads), (
            _hist("trainer.step_complete_seconds")["count"] - n_sync)

    plain, plain_threads, plain_dispatches = run("plain")
    kw = ({"profile": True} if mode == "profile" else
          {"telemetry": TelemetryConfig(trace_dir=str(tmp_path / "tr"))})
    try:
        prof, prof_threads, prof_dispatches = run(mode, **kw)
    finally:
        telemetry.disable_tracing()
    assert "profile" not in plain
    assert prof["loss"] == plain["loss"]
    assert prof["steps"] == plain["steps"] == 6
    assert prof_threads == plain_threads == 1  # a live prefetch thread
    assert prof_dispatches == plain_dispatches == 6  # one step a dispatch
    report = prof["profile"]
    assert report["steps"] == prof["steps"]
    for stage in ("plan", "feed", "step"):
        assert report[f"{stage}_sec"] >= 0.0
        assert report[f"{stage}_count"] >= 1
        assert f"{stage}_ms_per_step" in report
    assert report["complete_count"] == 6
    assert report["stage_quantiles"]["complete"]["count"] == 6
    assert set(report["stage_quantiles"]["step"]) == {
        "p50_ms", "p99_ms", "count"}


# --------------------------------------------------------------------------- #
# (d) named scopes: metadata on the same operations
# --------------------------------------------------------------------------- #
SCOPES = ("pull", "seqpool_cvm", "tower", "dense_opt", "push", "metrics")


def _without_scopes(monkeypatch):
    import contextlib

    class NoScope(contextlib.ContextDecorator):
        def __init__(self, name):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax, "named_scope", NoScope)


def _op_count(hlo_text: str) -> int:
    return len(re.findall(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = ", hlo_text, re.M))


def _scopes_in(hlo_text: str) -> set:
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        found.update(re.split(r"[/()]", name))
    return found


def test_single_chip_step_carries_the_scopes_on_the_same_ops(
        tmp_path, monkeypatch):
    from paddlebox_tpu.train.trainer import _device_batch

    ds, trainer, table = _world(tmp_path)
    table.begin_pass(ds.unique_keys())
    batch = next(iter(ds.batches()))
    dev = _device_batch(batch, table.plan_batch(batch), batch.n_sparse_slots)
    args = (trainer.params, trainer.opt_state, table.values, table.g2sum,
            trainer._init_mstate(), dev)
    scoped = trainer._build_step().lower(*args).compile().as_text()
    assert set(SCOPES) <= _scopes_in(scoped)
    _without_scopes(monkeypatch)
    bare = trainer._build_step().lower(*args).compile().as_text()
    assert not set(SCOPES) & _scopes_in(bare)
    assert _op_count(scoped) == _op_count(bare) > 50
    table.abort_pass()
    ds.close()


def test_sharded_step_carries_the_scopes_and_exchange(tmp_path, monkeypatch):
    from paddlebox_tpu.parallel import (
        MultiChipTrainer,
        ShardedSparseTable,
        make_mesh,
    )
    from paddlebox_tpu.parallel.multiprocess import global_from_local
    from paddlebox_tpu.parallel.trainer import _group_batches, _stack_group

    ds, _, _ = _world(tmp_path)
    mesh = make_mesh(2)
    tconf = SparseTableConfig(embedding_dim=4)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
    trainer = MultiChipTrainer(
        model, tconf, mesh, TrainerConfig(auc_buckets=1 << 10), seed=0)
    table = ShardedSparseTable(tconf, mesh, seed=0)
    table.begin_pass(ds.unique_keys())
    group = next(_group_batches(ds.batches(), 2))
    plan = table.plan_group(group, n_slots=S)
    feed = global_from_local(
        trainer._sharding, _stack_group(group, plan, S, None))
    args = (trainer.params, trainer.opt_state, table.values, table.g2sum,
            trainer._init_mstate(), feed)
    scoped = trainer._build_step().lower(*args).compile().as_text()
    assert set(SCOPES) | {"exchange"} <= _scopes_in(scoped)
    _without_scopes(monkeypatch)
    bare = trainer._build_step().lower(*args).compile().as_text()
    assert _op_count(scoped) == _op_count(bare) > 50
    # the same spans and counters on the sharded path
    d0 = _counter("trainer.dispatches")
    b0 = _counter("pass.begins")
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    assert _counter("trainer.dispatches") - d0 == m["steps"] == 3
    table.begin_pass(ds.unique_keys())
    table.end_pass()
    assert _counter("pass.begins") - b0 == 1
    trainer.close()
    ds.close()


# --------------------------------------------------------------------------- #
# (e) the benchmark's readers, on hand-made snapshots
# --------------------------------------------------------------------------- #
class _Run:
    """What benchmark.run.Run gives a reader, by hand."""

    def __init__(self, before, after, steps=200, passes=2):
        self.before, self.after = before, after
        self.steps = steps
        self.passes = [{}] * passes


def _snap(counters=None, histograms=None) -> dict:
    return {"counters": counters or {}, "gauges": {},
            "histograms": histograms or {}}


def _h(total, count=1, bounds=None, counts=None, largest=None) -> dict:
    return {"sum": total, "count": count, "boundaries": bounds or [],
            "counts": counts or [], "max": largest, "min": None}


def _reader(name: str):
    return importlib.import_module("benchmark.layer_metrics." + name).read


STAGE = "{}.stage_seconds{{stage={}}}".format
NEW_READERS = (
    "feed_wait_ms", "starved_dispatch_share", "step_dispatch_ms",
    "step_p95_ms", "step_slowest_ms", "pass_readback_ms",
    "boundary_census_ms", "boundary_directory_ms", "boundary_transfer_ms",
    "boundary_store_ms", "begin_backlog_share", "untagged_compiles",
    "host_batch_ms")


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_in_a_program_without_its_series(name):
    # the parent of PR 24 has trainer.stage_seconds{plan,feed,step} and
    # jit.compiles, and nothing else of what these read
    old = _snap(
        counters={"jit.compiles{stage=untagged}": 90.0,
                  "jit.compiles{stage=train.step}": 1.0},
        histograms={STAGE("trainer", "plan"): _h(1.0),
                    STAGE("trainer", "feed"): _h(1.0),
                    STAGE("trainer", "step"): _h(0.5)})
    got = _reader(name)(_Run(_snap(), old))
    expected = {"step_dispatch_ms": 2.5, "untagged_compiles": 90.0}
    assert got == expected.get(name)
    assert _reader(name)(_Run(_snap(), _snap())) is None


def test_stage_readers_divide_the_windows_growth():
    before = _snap(histograms={
        STAGE("trainer", "feed_wait"): _h(1.0),
        STAGE("pass", "census"): _h(0.5), "data.census_seconds": _h(0.25)})
    after = _snap(histograms={
        STAGE("trainer", "feed_wait"): _h(1.5),
        STAGE("trainer", "step"): _h(0.25),
        STAGE("trainer", "batch"): _h(9.0),
        STAGE("trainer", "readback"): _h(0.03),
        STAGE("pass", "census"): _h(0.75), "data.census_seconds": _h(0.75),
        STAGE("pass", "lookup"): _h(0.125), STAGE("pass", "touch"): _h(0.5),
        STAGE("pass", "commit"): _h(0.125),
        STAGE("pass", "upload"): _h(0.25), STAGE("pass", "set_rows"): _h(0.25),
        STAGE("pass", "write_back"): _h(0.002)})
    run = _Run(before, after)
    assert _reader("feed_wait_ms")(run) == 2.5
    assert _reader("step_dispatch_ms")(run) == 1.25
    assert _reader("host_batch_ms")(run) == 45.0
    assert _reader("pass_readback_ms")(run) == 15.0
    assert _reader("boundary_census_ms")(run) == 375.0
    assert _reader("boundary_directory_ms")(run) == 375.0
    assert _reader("boundary_transfer_ms")(run) == 250.0
    assert _reader("boundary_store_ms")(run) == 1.0


def test_share_readers_and_the_counter_that_never_counted():
    before = _snap(counters={"trainer.dispatches": 100.0,
                             "trainer.dispatches_starved": 10.0,
                             "pass.begins": 3.0})
    after = _snap(counters={"trainer.dispatches": 300.0,
                            "trainer.dispatches_starved": 14.0,
                            "pass.begins": 5.0,
                            "jit.compiles{stage=train.step}": 1.0})
    run = _Run(before, after)
    assert _reader("starved_dispatch_share")(run) == 2.0
    assert _reader("begin_backlog_share")(run) == 0.0  # absent: never left
    assert _reader("untagged_compiles")(run) == 0.0
    after["counters"]["pass.device_pending{at=begin_exit}"] = 1.0
    assert _reader("begin_backlog_share")(run) == 50.0


def test_step_tail_readers_take_the_windows_bucket_growth():
    bounds = [0.01, 0.02, 0.04, 0.08]
    series = "trainer.step_complete_seconds"
    before = _snap(
        counters={"trainer.dispatches": 0.0},
        histograms={series: _h(9.0, 10, bounds, [0, 0, 0, 0, 10], 30.0)})
    # the window: 90 samples in (0.02, 0.04], 10 in (0.04, 0.08]; warm-up's
    # ten compiles sit in the +Inf bucket and must not show
    after = _snap(
        counters={"trainer.dispatches": 100.0},
        histograms={series: _h(12.0, 110, bounds, [0, 0, 90, 10, 10], 30.0)})
    run = _Run(before, after, steps=100)
    assert _reader("step_p95_ms")(run) == pytest.approx(60.0)
    assert _reader("step_slowest_ms")(run) == 80.0
    run = _Run(before, after, steps=200)  # scan: two steps a dispatch
    assert _reader("step_p95_ms")(run) == pytest.approx(30.0)
    assert _reader("step_slowest_ms")(run) == 40.0
    # a stall lands in the +Inf bucket: the series' largest sample
    after["histograms"][series] = _h(
        15.0, 111, bounds, [0, 0, 90, 10, 11], 30.0)
    after["counters"]["trainer.dispatches"] = 101.0
    assert _reader("step_slowest_ms")(_Run(before, after, steps=101)) == 3e4
    assert _reader("step_p95_ms")(_Run(after, after)) is None  # no growth


# --------------------------------------------------------------------------- #
# (f) the boundary's and the read-back's eager programs are tagged
# --------------------------------------------------------------------------- #
def test_no_untagged_compile_in_boundary_or_readback(tmp_path):
    compiles.install_compile_listener()
    # a row width no other test of the process has compiled for
    ds, trainer, table = _world(tmp_path, embedding_dim=6)
    keys = ds.unique_keys()
    before = compiles.compiles_by_stage()
    table.load_state_dict({
        "keys": keys,
        "values": np.zeros(
            (keys.shape[0], table.conf.row_width + 1), np.float32)})
    for _ in range(2):  # cold (misses, admits) and warm (hits) boundaries
        table.begin_pass(keys)
        trainer.train_from_dataset(ds, table)
        table.end_pass()
    after = compiles.compiles_by_stage()
    assert after.get("untagged", 0) == before.get("untagged", 0)
    for stage in ("pass.begin", "pass.end", "train.readback", "train.step"):
        assert after.get(stage, 0) > before.get(stage, 0), stage
    trainer.close()
    ds.close()
