"""Continuous micro-batching at the admission gate
(inference/admission.BatchCoalescer + the ScoringServer HTTP wiring):
batched-vs-sequential bit-exactness over mixed-shape requests under
concurrency, deadline shedding mid-linger (429, never scored), hot-swap
atomicity (one predictor per batch), per-request clipped-instance
attribution through a coalesced batch, and overload behavior of the
widened admission gate."""

import http.client
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from paddlebox_tpu import telemetry
from paddlebox_tpu.config import (
    DataFeedConfig,
    SlotConfig,
    SparseTableConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.inference import ScoringServer, export_model
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

S, DENSE, B = 3, 2, 16


def _train_and_export(tmp_path, tag="m", seed=1):
    conf = make_synth_config(n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
                             max_feasigns_per_ins=8)
    files = write_synth_files(str(tmp_path / f"d{tag}"), n_files=1,
                              ins_per_file=64, n_sparse_slots=S,
                              vocab_per_slot=40, dense_dim=DENSE, seed=seed)
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=4)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
    table = SparseTable(tconf, seed=seed)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                      seed=seed)
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()
    ds.close()
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    art = str(tmp_path / f"art{tag}")
    export_model(model, trainer.params, table, art,
                 batch_size=B, key_capacity=kcap, dense_dim=DENSE)
    return conf, art


def _lines(n, seed=5, max_keys=5):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        parts = ["1 0"]
        for _s in range(S):
            ks = rng.integers(0, 40, int(rng.integers(1, max_keys)))
            parts.append(f"{len(ks)} " + " ".join(map(str, ks)))
        parts.append(f"{DENSE} " + " ".join(
            f"{v:.3f}" for v in rng.random(DENSE)))
        out.append(" ".join(parts))
    return ("\n".join(out) + "\n").encode()


def _post(port, body, path="/score", headers=None, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=body, headers=headers or {})
        r = conn.getresponse()
        data = r.read()
        return r.status, (json.loads(data) if data else {}), dict(
            (k.lower(), v) for k, v in r.getheaders())
    finally:
        conn.close()


class _StubPredictor:
    meta = {"n_tasks": 1, "row_width": 4}
    bucket_shapes = [(8, 64)]
    n_features = 1


def _stub_conf():
    return DataFeedConfig(
        slots=(SlotConfig("click", type="float", is_dense=True),
               SlotConfig("s0")),
        batch_size=8,
    )


# --------------------------------------------------------------------------- #
# the tentpole pin: batched scores are BIT-EXACT vs sequential
# --------------------------------------------------------------------------- #
def test_batched_bitexact_vs_sequential_mixed_shapes(tmp_path):
    """The acceptance pin: mixed-shape concurrent requests coalesced into
    shared padded-bucket device calls demultiplex to EXACTLY the scores
    each request gets when scored alone, FIFO attribution intact —
    scoring is per-instance row-independent by the padding/segment rules,
    so the combined batch changes dispatch count, never a single bit of
    any score."""
    conf, art = _train_and_export(tmp_path)
    srv = ScoringServer(max_batch=8, batch_linger_ms=20)
    srv.register("m", art, conf)
    sizes = [1, 3, 7, 2, 5, 4, 1, 6, 3, 2, 8, 5, 2, 1, 4, 6]
    bodies = [_lines(n, seed=100 + i) for i, n in enumerate(sizes)]
    # sequential oracle through the DIRECT path (never coalesced)
    want = [srv.score_lines(b, "m") for b in bodies]

    port = srv.start(port=0)
    try:
        _post(port, bodies[0])  # compile warmup outside the hammer
        got = [None] * len(bodies)
        errors = []

        def post(i):
            try:
                st, out, _ = _post(port, bodies[i])
                assert st == 200, (st, out)
                got[i] = out["scores"]
            except Exception as e:  # surfaced below, not swallowed
                errors.append((i, repr(e)))

        for _round in range(3):  # several rounds -> varied batch mixes
            threads = [threading.Thread(target=post, args=(i,))
                       for i in range(len(bodies))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors, errors
            for i in range(len(bodies)):
                assert got[i] == want[i], f"request {i} diverged"
        # and batching actually happened: at least one multi-request batch
        hist = telemetry.histogram("serve.batch_size")
        assert (hist.summary() or {}).get("max", 0) > 1
    finally:
        srv.stop()


# --------------------------------------------------------------------------- #
# deadline mid-linger: shed with 429, never scored
# --------------------------------------------------------------------------- #
def test_deadline_expires_while_queued_behind_batch_never_scored():
    """A queued request whose deadline dies while the previous batch
    occupies the scorer (the mid-linger/mid-queue window) is shed with
    429 at batch cut — its payload NEVER reaches the scoring path."""
    srv = ScoringServer(max_batch=4, batch_linger_ms=50, max_queue=16)
    srv.register_predictor("stub", _StubPredictor(), _stub_conf())
    release = threading.Event()
    entered = threading.Event()
    scored = []

    def score_lines(text, name=None):
        scored.append(bytes(text))
        entered.set()
        assert release.wait(20), "test never released the scorer"
        return [0.5 for ln in text.decode().splitlines() if ln.strip()]

    srv.score_lines = score_lines
    port = srv.start(port=0)
    try:
        res_a = {}

        def post_a():
            res_a["r"] = _post(port, b"request-A\n")

        ta = threading.Thread(target=post_a)
        ta.start()
        assert entered.wait(10)  # A's batch is on the (blocked) scorer
        t0 = time.monotonic()
        # B carries a 200ms deadline; the scorer stays blocked past it
        res_b = {}

        def post_b():
            res_b["r"] = _post(
                port, b"request-B\n",
                headers={"X-Request-Deadline-Ms": "200"})

        tb = threading.Thread(target=post_b)
        tb.start()
        while time.monotonic() - t0 < 0.35:
            time.sleep(0.01)
        release.set()
        ta.join(timeout=20)
        tb.join(timeout=20)
        st_a, out_a, _ = res_a["r"]
        st_b, out_b, hdrs_b = res_b["r"]
        assert st_a == 200 and out_a["scores"] == [0.5]
        assert st_b == 429 and "deadline" in out_b["error"]
        assert "retry-after" in hdrs_b
        # the shed request's payload never reached the scorer
        assert all(b"request-B" not in s for s in scored)
    finally:
        release.set()
        srv.stop()


# --------------------------------------------------------------------------- #
# hot swap mid-coalesce: one predictor per batch
# --------------------------------------------------------------------------- #
def test_hot_swap_mid_coalesce_never_mixes_predictors(tmp_path):
    """swap_model racing batch formation: every HTTP response must be
    EXACTLY the old model's scores or the new one's — a batch split
    across two predictors (or one request's chunks scored on both) would
    produce a third sequence."""
    conf_a, art_a = _train_and_export(tmp_path, "a", seed=1)
    conf_b, art_b = _train_and_export(tmp_path, "b", seed=2)
    from paddlebox_tpu.inference import Predictor

    pred_a, pred_b = Predictor.load(art_a), Predictor.load(art_b)
    srv = ScoringServer(max_batch=8, batch_linger_ms=5)
    srv.register("m", art_a, conf_a)
    body = _lines(23)  # several chunks per request
    want_a = srv.score_lines(body, "m")
    srv.swap_model("m", pred_b)
    want_b = srv.score_lines(body, "m")
    assert want_a != want_b
    srv.swap_model("m", pred_a)

    port = srv.start(port=0)
    bad, stop = [], threading.Event()

    def hammer():
        while not stop.is_set():
            st, out, _ = _post(port, body)
            if st != 200:
                bad.append(("status", st, out))
            elif out["scores"] != want_a and out["scores"] != want_b:
                bad.append(("mixed", out["scores"][:3]))

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for i in range(30):
            srv.swap_model("m", pred_b if i % 2 == 0 else pred_a)
            time.sleep(0.005)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        srv.stop()
    assert not bad, bad[:3]


# --------------------------------------------------------------------------- #
# per-request clipped attribution through one coalesced batch
# --------------------------------------------------------------------------- #
def test_clipped_attribution_per_request_in_shared_batch(tmp_path):
    """A key-dense request and a normal one coalesced into ONE batch:
    clipped_instances lands on the fat request's response only (the
    combined call's clipped instance ids demultiplex by request range)."""
    conf, art = _train_and_export(tmp_path, "clip", seed=9)
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    srv = ScoringServer(max_batch=8, batch_linger_ms=50)
    srv.register("clip", art, conf)
    calls = []
    orig = srv.score_lines

    def recording(text, name=None):
        out = orig(text, name)
        calls.append(len(out))
        return out

    srv.score_lines = recording

    rng = np.random.default_rng(3)
    parts = ["1 0"]
    per_slot = kcap // S + 8  # one instance over the whole batch capacity
    for _s in range(S):
        ks = rng.integers(0, 40, per_slot)
        parts.append(f"{len(ks)} " + " ".join(map(str, ks)))
    parts.append(f"{DENSE} " + " ".join(
        f"{v:.3f}" for v in rng.random(DENSE)))
    fat = (" ".join(parts) + "\n").encode()
    normal = _lines(3, seed=4)

    port = srv.start(port=0)
    try:
        # sacrificial request occupies the scorer so fat+normal pend
        # together and cut as ONE batch when it finishes
        with srv._lock:
            ts = threading.Thread(target=_post, args=(port, _lines(1)))
            ts.start()
            time.sleep(0.15)  # its batch is parsed and blocked at _lock
            res = {}

            def post(name, body):
                res[name] = _post(port, body)

            tf = threading.Thread(target=post, args=("fat", fat))
            tn = threading.Thread(target=post, args=("normal", normal))
            tf.start()
            tn.start()
            time.sleep(0.15)  # both pending in the forming batch
        ts.join(timeout=30)
        tf.join(timeout=30)
        tn.join(timeout=30)
        st_f, out_f, _ = res["fat"]
        st_n, out_n, _ = res["normal"]
        assert st_f == 200 and len(out_f["scores"]) == 1
        assert out_f["clipped_instances"] == 1
        assert st_n == 200 and len(out_n["scores"]) == 3
        assert "clipped_instances" not in out_n
        # fat + normal really shared one combined scoring call (4 scores)
        assert 4 in calls, calls
    finally:
        srv.stop()


# --------------------------------------------------------------------------- #
# overload under batching: shed loudly, never 5xx, queue drains
# --------------------------------------------------------------------------- #
def test_batched_overload_sheds_cleanly():
    srv = ScoringServer(max_batch=4, batch_linger_ms=2, max_queue=2)
    srv.register_predictor("stub", _StubPredictor(), _stub_conf())

    def score_lines(text, name=None):
        with srv._lock:
            time.sleep(0.03)  # one simulated device call per BATCH
        return [0.5 for ln in text.decode().splitlines() if ln.strip()]

    srv.score_lines = score_lines
    port = srv.start(port=0)
    statuses = []
    lock = threading.Lock()

    def client():
        for _ in range(5):
            st, out, hdrs = _post(port, b"a\nb\n")
            with lock:
                statuses.append(st)
            if st == 429:
                assert int(hdrs["retry-after"]) >= 1

    threads = [threading.Thread(target=client) for _ in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    srv.stop()
    assert set(statuses) <= {200, 429}
    assert statuses.count(200) > 0
    assert srv.gate.queue_depth() == 0  # no ghost tickets after the storm


def test_error_isolation_in_shared_batch(tmp_path):
    """One request's malformed payload 400s THAT request only: its batch
    mates score normally through the individual-fallback path."""
    conf, art = _train_and_export(tmp_path, "err", seed=7)
    srv = ScoringServer(max_batch=8, batch_linger_ms=50)
    srv.register("m", art, conf)
    good = _lines(2, seed=8)
    want = srv.score_lines(good, "m")
    port = srv.start(port=0)
    try:
        with srv._lock:
            ts = threading.Thread(target=_post, args=(port, _lines(1)))
            ts.start()
            time.sleep(0.15)
            res = {}

            def post(name, body):
                res[name] = _post(port, body)

            tg = threading.Thread(target=post, args=("good", good))
            tb = threading.Thread(
                target=post, args=("bad", b"not a slot line\n"))
            tg.start()
            tb.start()
            time.sleep(0.15)
        for t in (ts, tg, tb):
            t.join(timeout=30)
        st_g, out_g, _ = res["good"]
        st_b, out_b, _ = res["bad"]
        assert st_g == 200 and out_g["scores"] == want
        assert st_b == 400
    finally:
        srv.stop()
