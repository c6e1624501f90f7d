"""Telemetry layer (paddlebox_tpu/telemetry/): typed metrics + quantile
math, Prometheus exposition, span tracing, JSONL events, /metrics on the
scoring server, and cross-rank snapshot aggregation."""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddlebox_tpu import telemetry
from paddlebox_tpu.telemetry.metrics import (
    MetricRegistry,
    quantile_from_buckets,
)
from paddlebox_tpu.utils.monitor import stats


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.registry.reset()
    yield
    telemetry.registry.reset()
    telemetry.disable_tracing()


# --------------------------------------------------------------------------- #
# histogram bucket / quantile math
# --------------------------------------------------------------------------- #
def test_histogram_empty_quantiles_are_none():
    reg = MetricRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0))
    assert h.quantile(0.5) is None
    assert h.summary()["count"] == 0
    assert h.summary()["p99"] is None
    assert h.summary()["mean"] is None


def test_histogram_single_sample_reports_that_sample():
    reg = MetricRegistry()
    h = reg.histogram("h", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(0.05)
    s = h.summary()
    assert s["count"] == 1 and s["min"] == s["max"] == 0.05


def test_histogram_quantiles_bracket_the_distribution():
    reg = MetricRegistry()
    h = reg.histogram("h", buckets=(0.01, 0.1, 1.0, 10.0))
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.02, 0.09, size=1000)  # all inside one bucket
    for v in vals:
        h.observe(float(v))
    p50 = h.quantile(0.5)
    # interpolation inside the (0.01, 0.1] bucket: must stay in range
    assert 0.02 <= p50 <= 0.09
    # p99 >= p50 and both clamp to observed extremes
    assert p50 <= h.quantile(0.99) <= vals.max()
    assert h.quantile(0.0) >= vals.min()


def test_histogram_overflow_bucket_uses_observed_max():
    reg = MetricRegistry()
    h = reg.histogram("h", buckets=(1.0,))
    h.observe(5.0)
    h.observe(7.0)
    assert h.quantile(0.99) <= 7.0
    assert h.quantile(0.99) > 1.0


def test_quantile_from_buckets_validates_q():
    with pytest.raises(ValueError):
        quantile_from_buckets((1.0,), [1, 0], 1, 0.5, 0.5, 1.5)


def test_histogram_labels_split_and_merge():
    reg = MetricRegistry()
    h = reg.histogram("h", buckets=(0.01, 1.0))
    h.observe(0.005, stage="a")
    h.observe(0.5, stage="b")
    assert h.summary(stage="a")["count"] == 1
    assert h.summary(stage="b")["count"] == 1
    assert h.summary()["count"] == 2  # no labels = merged across series


def test_counter_gauge_basics_and_kind_conflict():
    reg = MetricRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2, rank="1")
    assert c.value() == 1 and c.value(rank="1") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(3.5)
    g.set(1.0, rank="0")
    g.remove(rank="0")
    assert g.value(rank="0") == 0.0 and g.value() == 3.5
    with pytest.raises(TypeError):
        reg.gauge("c")  # same name, different kind


def test_delta_snapshot_reports_per_interval_values():
    reg = MetricRegistry()
    reg.counter("c").inc(5)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    first = reg.delta_snapshot()
    assert first["counters"]["c"] == 5
    assert first["histograms"]["h"]["count"] == 1
    reg.counter("c").inc(2)
    second = reg.delta_snapshot()
    assert second["counters"]["c"] == 2  # only the new increments
    assert second["histograms"]["h"]["count"] == 0


# --------------------------------------------------------------------------- #
# legacy stats facade
# --------------------------------------------------------------------------- #
def test_stats_facade_forwards_to_typed_registry():
    stats.add("x.count", 2)
    stats.add("x.count")
    stats.set("x.gauge", 7.5)
    assert stats.get("x.count") == 3
    assert stats.get("x.gauge") == 7.5
    snap = stats.snapshot()
    assert snap["x.count"] == 3 and snap["x.gauge"] == 7.5
    # the satellite: snapshot carries a monotonic timestamp taken under
    # the registry lock
    assert snap.monotonic_ts > 0
    snap2 = stats.snapshot()
    assert snap2.monotonic_ts >= snap.monotonic_ts
    # legacy counters land in the shared typed registry
    assert telemetry.registry.get("x.count") is not None


def test_stats_reset_keeps_cached_metric_handles_registered():
    c = telemetry.counter("cached.handle")
    c.inc(3)
    stats.reset()
    assert stats.get("cached.handle") == 0
    c.inc()  # the old handle still feeds the registry after reset
    assert stats.get("cached.handle") == 1
    assert "cached_handle_total" in telemetry.render_prometheus()


# --------------------------------------------------------------------------- #
# profiler: auto-created stages + counts (satellites 1-2)
# --------------------------------------------------------------------------- #
def test_step_profiler_auto_creates_stages():
    from paddlebox_tpu.utils.profiler import StatsProfiler

    p = StatsProfiler()
    mark = p.mark()
    with p.stage("brand_new_stage"):  # KeyError before PR 3
        pass
    with p.stage("plan"):
        pass
    with p.stage("plan"):
        pass
    r = p.report(mark, n_steps=1)
    assert r["steps"] == 1
    assert r["brand_new_stage_count"] == 1
    assert r["plan_count"] == 2  # the pass's delta, not the process's total
    assert "brand_new_stage_sec" in r and "plan_ms_per_step" in r
    assert "plan" in p.log_line(r)
    q = r["stage_quantiles"]
    assert q["plan"]["count"] == 2 and q["plan"]["p99_ms"] >= 0
    # a second window sees only its own stages
    mark = p.mark()
    with p.stage("plan"):
        pass
    r = p.report(mark, n_steps=1)
    assert r["plan_count"] == 1 and "brand_new_stage_count" not in r


def test_stats_profiler_records_histograms_without_enabling():
    from paddlebox_tpu.utils import profiler

    # one profiler, always on: there is no second, "enabled" mode to enter
    assert not hasattr(profiler, "StepProfiler")
    assert not hasattr(profiler, "NullProfiler")
    p = profiler.StatsProfiler()
    assert not hasattr(p, "enabled")
    with p.stage("plan"):
        pass
    h = telemetry.registry.get("trainer.stage_seconds")
    assert h.summary(stage="plan")["count"] == 1


# --------------------------------------------------------------------------- #
# Prometheus exposition
# --------------------------------------------------------------------------- #
def test_prometheus_golden_output():
    reg = MetricRegistry()
    reg.counter("train.nan_rollback", help="rollbacks").inc(2)
    reg.gauge("watchdog.staleness_s").set(1.5, rank="0")
    h = reg.histogram("req.seconds", buckets=(0.1, 1.0))
    h.observe(0.05, model="m")
    h.observe(0.5, model="m")
    h.observe(5.0, model="m")
    golden = "\n".join([
        "# TYPE req_seconds histogram",
        'req_seconds_bucket{model="m",le="0.1"} 1',
        'req_seconds_bucket{model="m",le="1"} 2',
        'req_seconds_bucket{model="m",le="+Inf"} 3',
        'req_seconds_sum{model="m"} 5.55',
        'req_seconds_count{model="m"} 3',
        "# HELP train_nan_rollback_total rollbacks",
        "# TYPE train_nan_rollback_total counter",
        "train_nan_rollback_total 2",
        "# TYPE watchdog_staleness_s gauge",
        'watchdog_staleness_s{rank="0"} 1.5',
        "",
    ])
    assert telemetry.render_prometheus(reg) == golden


# --------------------------------------------------------------------------- #
# span tracing: Chrome-trace JSON nesting
# --------------------------------------------------------------------------- #
def test_span_trace_nesting_and_json_validity(tmp_path):
    tr = telemetry.enable_tracing(pid=3)
    with telemetry.span("outer", pass_idx=1):
        with telemetry.span("inner"):
            pass
        with telemetry.span("inner2"):
            pass
    telemetry.instant("marker", note="x")
    path = telemetry.flush_trace(str(tmp_path / "t.json"))
    doc = json.load(open(path))  # valid JSON by construction
    evs = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert set(evs) == {"outer", "inner", "inner2"}
    assert evs["inner"]["args"]["parent"] == "outer"
    assert evs["inner2"]["args"]["parent"] == "outer"
    assert "parent" not in evs["outer"].get("args", {})
    # time containment: children inside the parent window (Perfetto nests
    # same-tid X events by exactly this)
    out = evs["outer"]
    for child in ("inner", "inner2"):
        c = evs[child]
        assert c["ts"] >= out["ts"]
        assert c["ts"] + c["dur"] <= out["ts"] + out["dur"] + 1e-3
    assert any(e.get("ph") == "i" for e in doc["traceEvents"])
    assert tr.pid == 3
    # flush drained the buffer: a second flush writes no X events
    doc2 = json.loads(json.dumps(tr.to_dict()))
    assert not [e for e in doc2["traceEvents"] if e.get("ph") == "X"]


def test_span_is_noop_when_disabled():
    telemetry.disable_tracing()
    with telemetry.span("nothing"):
        pass
    assert telemetry.flush_trace("/nonexistent/never-written.json") is None


# --------------------------------------------------------------------------- #
# JSONL events
# --------------------------------------------------------------------------- #
def test_event_log_rank_tagged_jsonl(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    el = telemetry.EventLog(path, rank=2)
    telemetry.counter("ev.c").inc(4)
    el.log("custom", foo=1)
    el.log_pass({"auc": 0.5, "steps": 3}, pass_idx=0)
    el.close()
    recs = [json.loads(ln) for ln in open(path)]
    assert [r["event"] for r in recs] == ["custom", "pass_end"]
    assert all(r["rank"] == 2 and r["t"] > 0 for r in recs)
    assert recs[1]["metrics"]["auc"] == 0.5
    assert recs[1]["telemetry"]["counters"]["ev.c"] == 4


# --------------------------------------------------------------------------- #
# /metrics on ScoringServer (round-trip, no artifact needed)
# --------------------------------------------------------------------------- #
class _StubPredictor:
    """Predictor stand-in: the HTTP/parse/batch path is real, only the
    device program is faked (export is unavailable on legacy-jax images)."""

    meta = {"n_tasks": 1}
    n_features = 3

    def __init__(self, conf):
        b = conf.batch_size
        kcap = conf.batch_key_capacity or b * conf.max_feasigns_per_ins
        self.bucket_shapes = [(b, kcap)]

    def predict(self, batch):
        return np.zeros(int(batch.ins_mask.sum()), np.float32)


@pytest.fixture
def stub_server(tmp_path):
    from paddlebox_tpu.data.slot_parser import SlotParser
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference.server import ModelEntry, ScoringServer

    conf = make_synth_config(
        n_sparse_slots=3, dense_dim=2, batch_size=8, max_feasigns_per_ins=8
    )
    files = write_synth_files(
        str(tmp_path / "d"), n_files=1, ins_per_file=4, n_sparse_slots=3,
        vocab_per_slot=10, dense_dim=2, seed=1,
    )
    srv = ScoringServer()
    entry = ModelEntry.__new__(ModelEntry)
    entry.name, entry.predictor, entry.feed_conf = (
        "m", _StubPredictor(conf), conf
    )
    entry.parser = SlotParser(conf)
    entry.requests = entry.instances = 0
    srv._models["m"] = entry
    srv._default = "m"
    port = srv.start()
    body = open(files[0], "rb").read()
    try:
        yield srv, port, body
    finally:
        srv.stop()


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.status, r.headers, r.read().decode()


def _wait_for(cond, timeout=5.0):
    """The handler thread records telemetry AFTER writing the response, so
    a client-side assertion must allow that handoff to land."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def test_metrics_endpoint_round_trip(stub_server):
    srv, port, body = stub_server
    # one 2xx, one 4xx (unknown model), one 4xx (garbage body)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/score", data=body, method="POST"
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    for path, data in (("/score/ghost", b"x"), ("/score", b"garbage")):
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{port}{path}", data=data,
                    method="POST",
                ),
                timeout=10,
            )
    c = telemetry.registry.get("server.requests")
    assert _wait_for(
        lambda: sum(cell[0] for cell in c.series().values()) >= 3
    )
    st, headers, text = _get(port, "/metrics")
    assert st == 200
    # the satellite: exposition content type, version pinned
    assert headers["Content-Type"] == "text/plain; version=0.0.4"
    # request-latency histogram buckets, split by model and status class
    assert 'server_request_seconds_bucket{model="m",status="2xx"' in text
    assert 'server_request_seconds_bucket{model="m",status="4xx"' in text
    assert 'server_request_seconds_bucket{model="ghost",status="4xx"' in text
    assert 'server_requests_total{model="m",status="2xx"} 1' in text
    # valid exposition shape: every _bucket line ends with an int, and the
    # +Inf bucket equals _count for each series
    inf = {
        ln.split("le=\"+Inf\"}")[0] for ln in text.splitlines()
        if 'le="+Inf"' in ln
    }
    assert inf  # at least one histogram rendered


def test_metrics_endpoint_counts_error_latency(stub_server):
    srv, port, body = stub_server
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{port}/score/ghost", data=b"x",
                method="POST",
            ),
            timeout=10,
        )
    h = telemetry.registry.get("server.request_seconds")
    assert _wait_for(
        lambda: h.summary(model="ghost", status="4xx")["count"] == 1
    )


# --------------------------------------------------------------------------- #
# cross-rank aggregation (2-rank simulated fleet on the in-memory KV)
# --------------------------------------------------------------------------- #
def test_gather_fleet_snapshot_two_ranks_merge():
    from paddlebox_tpu.parallel.watchdog import InMemoryKv
    from paddlebox_tpu.utils.profiler import STAGE_BUCKETS

    kv = InMemoryKv()
    regs = [MetricRegistry() for _ in range(2)]
    # per-rank stage timings: rank 1 is the slow one
    for rank, reg in enumerate(regs):
        h = reg.histogram("trainer.stage_seconds", buckets=STAGE_BUCKETS)
        for _ in range(10):
            h.observe(0.001 if rank == 0 else 0.2, stage="step")
        reg.counter("train.steps").inc(10)
        reg.gauge("watchdog.staleness_s").set(0.5 * (rank + 1), rank=str(rank))
    merged = [None, None]
    import threading

    def run(rank):
        merged[rank] = telemetry.gather_fleet_snapshot(
            kv, rank=rank, world=2, seq=7, registry=regs[rank],
            timeout_s=10.0,
        )

    ts = [threading.Thread(target=run, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # both ranks converge on the SAME merged view
    assert merged[0] == merged[1]
    m = merged[0]
    assert m["world"] == 2
    assert m["counters"]["train.steps"]["sum"] == 20
    assert m["counters"]["train.steps"]["per_rank"] == [10.0, 10.0]
    h = m["histograms"]["trainer.stage_seconds{stage=step}"]
    assert h["count"] == 20
    # fleet p99 reflects the slow rank; per-rank p99 names it
    assert h["p99"] > 0.05
    assert h["per_rank_p99"][1] > h["per_rank_p99"][0]
    # the rank-0 pass log line carries merged per-rank stage timings
    line = telemetry.format_fleet_view(m)
    assert "world=2" in line
    assert "trainer.stage_seconds{stage=step}" in line
    assert "per_rank_p99_ms=" in line
    assert "train.steps=20" in line


def test_gather_fleet_snapshot_timeout_names_missing_rank():
    from paddlebox_tpu.parallel.watchdog import InMemoryKv

    kv = InMemoryKv()
    with pytest.raises(telemetry.FleetGatherTimeout) as ei:
        telemetry.gather_fleet_snapshot(
            kv, rank=0, world=2, seq=0, registry=MetricRegistry(),
            timeout_s=0.2, poll_s=0.01,
        )
    assert ei.value.missing == [1]
    assert "rank(s) [1]" in str(ei.value)


# --------------------------------------------------------------------------- #
# standalone exporter
# --------------------------------------------------------------------------- #
def test_metrics_exporter_serves_registry(tmp_path):
    telemetry.counter("exp.hits").inc(3)
    exp = telemetry.MetricsExporter()
    port = exp.start(port=0)
    try:
        st, headers, text = _get(port, "/metrics")
        assert st == 200
        assert headers["Content-Type"] == "text/plain; version=0.0.4"
        assert "exp_hits_total 3" in text
        st, _, _ = _get(port, "/healthz")
        assert st == 200
    finally:
        exp.stop()


# --------------------------------------------------------------------------- #
# acceptance: a traced single-pass training run
# --------------------------------------------------------------------------- #
def test_traced_training_pass_writes_nested_chrome_trace(tmp_path):
    from paddlebox_tpu.config import (
        SparseTableConfig,
        TelemetryConfig,
        TrainerConfig,
    )
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    conf = make_synth_config(
        n_sparse_slots=3, dense_dim=2, batch_size=16, max_feasigns_per_ins=8
    )
    files = write_synth_files(
        str(tmp_path / "d"), n_files=1, ins_per_file=64, n_sparse_slots=3,
        vocab_per_slot=40, dense_dim=2, seed=3,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=4)
    trace_dir = str(tmp_path / "traces")
    events = str(tmp_path / "events.jsonl")
    trconf = TrainerConfig(
        auc_buckets=1 << 10,
        telemetry=TelemetryConfig(trace_dir=trace_dir, events_path=events),
        need_dump_field=True,
        dump_fields_path=str(tmp_path / "dump"),
    )
    model = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(8,))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, trconf, seed=0)
    table.begin_pass(ds.unique_keys())
    metrics = trainer.train_from_dataset(ds, table)
    table.end_pass()
    ds.close()
    telemetry.close_event_log()

    # Chrome-trace JSON with nested plan/feed/step/dump spans
    tf = [f for f in os.listdir(trace_dir) if f.endswith(".json")]
    assert tf == ["host-trace-r0-pass0.json"]
    doc = json.load(open(os.path.join(trace_dir, tf[0])))
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"pass", "plan", "feed", "feed_wait", "step", "dump"} <= names
    for e in spans:
        if e["name"] in ("plan", "feed", "feed_wait", "step", "dump"):
            assert e["args"]["parent"] == "pass"
    # tracing does not change the loop: plan and feed come from the
    # prefetch producer's thread (the pass inherited as their parent), the
    # step and the dump from the thread that dispatches
    tid = {n: {e["tid"] for e in spans if e["name"] == n} for n in names}
    assert len(tid["plan"]) == 1 and tid["plan"] != tid["step"]
    assert tid["step"] == tid["dump"] == tid["pass"]
    assert metrics["profile"]["complete_count"] == metrics["steps"]
    # existing stats.add call-sites unmodified + per-stage distributions
    assert metrics["profile"]["stage_quantiles"]["step"]["count"] > 0
    # JSONL pass record, rank-tagged
    recs = [json.loads(ln) for ln in open(events)]
    assert recs and recs[-1]["event"] == "pass_end"
    assert "trainer.stage_seconds{stage=step}" in (
        recs[-1]["telemetry"]["histograms"]
    )
