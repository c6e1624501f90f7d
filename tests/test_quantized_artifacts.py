"""Quantized embedding artifacts (inference/quant.py + embedding_dtype
through export/predictor/publisher/syncer): per-row-scale int8/fp8
codecs, dequant-on-gather scoring quality (AUC delta vs fp32), the
quantized delta-publish round trip, and the chain-mixing guard (fp32
delta onto an int8 base is a structured refusal -> full-reload
fallback, never a corrupt merge)."""

import os

import numpy as np
import pytest

from paddlebox_tpu import telemetry
from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.inference import Predictor, ScoringServer, export_model
from paddlebox_tpu.inference import quant
from paddlebox_tpu.inference.predictor import EmbeddingDtypeMismatch
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.serving_sync import Publisher, Syncer
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

S, DENSE, B = 3, 2, 8
KCAP = B * 8


# --------------------------------------------------------------------------- #
# codec units: determinism, zero rows, disk round trip
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_quantize_rows_roundtrip_and_determinism(dtype):
    rng = np.random.default_rng(3)
    vals = rng.normal(scale=0.2, size=(50, 2 + 1 + 8)).astype(np.float32)
    vals[7] = 0.0  # an all-zero row must quantize/dequantize cleanly
    head, q, scales = quant.quantize_rows(vals, 2, dtype)
    assert head.shape == (50, 3) and q.shape == (50, 8)
    assert scales.shape == (50,)
    np.testing.assert_array_equal(head, vals[:, :3])
    # zero row: scale 1.0, zero codes, zero dequant
    assert scales[7] == 1.0 and not q[7].any()
    # row-wise deterministic: the same row quantizes to the same bytes
    # whatever export it rides in (the delta round-trip foundation)
    h2, q2, s2 = quant.quantize_rows(vals.copy(), 2, dtype)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(scales, s2)
    # disk form round-trips bit-exactly
    restored = quant.load_q(quant.store_q(q).copy(), dtype)
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(q))
    # dequant error bounded by one quantization step per element
    deq = quant.dequantize_rows(head, q, scales)
    step = scales[:, None] * (1.0 if dtype == "int8" else 32.0)
    assert np.all(np.abs(deq[:, 3:] - vals[:, 3:]) <= step + 1e-7)


def test_quantize_rows_refuses_headonly_rows():
    with pytest.raises(ValueError, match="nothing to quantize"):
        quant.quantize_rows(np.zeros((4, 3), np.float32), 2, "int8")
    with pytest.raises(ValueError, match="embedding_dtype"):
        quant.validate_dtype("int4")


# --------------------------------------------------------------------------- #
# export/predict: dequant-on-gather quality + payload bytes + reporting
# --------------------------------------------------------------------------- #
def _train_small(td, embedding_dim=16, create_threshold=0.0):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=8,
    )
    files = write_synth_files(
        str(td), n_files=1, ins_per_file=128, n_sparse_slots=S,
        vocab_per_slot=60, dense_dim=DENSE, seed=11,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=embedding_dim,
                              create_threshold=create_threshold)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                      seed=0)
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()
    return conf, ds, model, table, trainer


def _sparse_payload_bytes(art):
    sp = os.path.join(art, "sparse")
    return sum(os.path.getsize(os.path.join(sp, f))
               for f in os.listdir(sp) if not f.startswith("keys"))


def test_quantized_auc_delta_and_bytes(tmp_path):
    """int8 AND fp8 artifacts score the synthetic CTR eval within
    0.005 AUC of the fp32 artifact, at a fraction of its payload bytes
    (the acceptance criterion's quality gate).  AUC is the package's own
    streaming one (metrics/auc.py), 2^20 score buckets."""
    from paddlebox_tpu.metrics.auc import (
        compute_metrics, init_auc_state, update_auc_state)

    conf, ds, model, table, trainer = _train_small(tmp_path / "d")
    kcap = conf.batch_key_capacity or KCAP
    labels = []
    for batch in ds.batches(drop_last=False):
        labels.extend(batch.labels[: batch.n_real_ins].tolist())
    labels = np.asarray(labels, np.float32)
    auc, payload = {}, {}
    for dt in ("fp32", "int8", "fp8"):
        art = str(tmp_path / f"art-{dt}")
        export_model(model, trainer.params, table, art, batch_size=B,
                     key_capacity=kcap, dense_dim=DENSE, embedding_dtype=dt)
        pred = Predictor.load(art)
        assert pred.embedding_dtype == dt
        scores = np.concatenate(list(pred.predict_dataset(ds)))
        auc[dt] = compute_metrics(update_auc_state(
            init_auc_state(), scores.astype(np.float32), labels,
            np.ones_like(labels)))["auc"]
        payload[dt] = _sparse_payload_bytes(art)
        if dt != "fp32":
            assert pred._quantized and pred.artifact_bytes > 0
    ds.close()
    assert abs(auc["int8"] - auc["fp32"]) < 0.005
    assert abs(auc["fp8"] - auc["fp32"]) < 0.005
    # emb 16: head 3*4 + q 16 + scale 4 = 32 B/row vs 76 B/row fp32
    assert payload["int8"] < 0.55 * payload["fp32"]
    assert payload["fp8"] < 0.55 * payload["fp32"]


def test_quantized_respects_create_threshold(tmp_path):
    """Feature admission is fused INTO the quantized program: with an
    impossible create_threshold every score must equal the zero-embedding
    forward, exactly as the fp32 host resolve produces it."""
    conf, ds, model, table, trainer = _train_small(
        tmp_path / "d", create_threshold=1e9)
    kcap = conf.batch_key_capacity or KCAP
    outs = {}
    for dt in ("fp32", "int8"):
        art = str(tmp_path / f"art-{dt}")
        export_model(model, trainer.params, table, art, batch_size=B,
                     key_capacity=kcap, dense_dim=DENSE, embedding_dtype=dt)
        pred = Predictor.load(art)
        outs[dt] = pred.predict(next(ds.batches(drop_last=False)))
    ds.close()
    # all embeddings hidden on both paths -> identical forward
    np.testing.assert_allclose(outs["int8"], outs["fp32"], rtol=1e-6,
                               atol=1e-7)


def test_models_endpoint_reports_bytes_and_dtype(tmp_path):
    import json
    import urllib.request

    conf, ds, model, table, trainer = _train_small(tmp_path / "d")
    ds.close()
    kcap = conf.batch_key_capacity or KCAP
    art = str(tmp_path / "art")
    export_model(model, trainer.params, table, art, batch_size=B,
                 key_capacity=kcap, dense_dim=DENSE, embedding_dtype="int8",
                 feed_conf=conf)
    srv = ScoringServer()
    srv.register("q", art)
    port = srv.start(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/models", timeout=30) as r:
            m = json.loads(r.read())["models"]["q"]
        assert m["embedding_dtype"] == "int8"
        assert m["artifact_bytes"] > 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            h = json.loads(r.read())["models"]["q"]
        assert h["embedding_dtype"] == "int8"
        assert h["artifact_bytes"] == m["artifact_bytes"]
    finally:
        srv.stop()


# --------------------------------------------------------------------------- #
# delta plane: quantized round trip + chain-mixing guard
# --------------------------------------------------------------------------- #
class _Job:
    """Trainable CTR job mirroring test_serving_sync's, publishing at a
    configurable embedding dtype."""

    def __init__(self, workdir, seed=0):
        self.workdir = str(workdir)
        self.conf = make_synth_config(
            n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
            max_feasigns_per_ins=8,
        )
        self.tconf = SparseTableConfig(embedding_dim=4)
        self.model = CtrDnn(S, self.tconf.row_width, dense_dim=DENSE,
                            hidden=(8,))
        self.table = SparseTable(self.tconf, seed=seed)
        self.trainer = Trainer(self.model, self.tconf,
                               TrainerConfig(auc_buckets=1 << 10), seed=seed)

    def train_pass(self, i):
        files = write_synth_files(
            os.path.join(self.workdir, f"d{i}"), n_files=1, ins_per_file=32,
            n_sparse_slots=S, vocab_per_slot=60, dense_dim=DENSE,
            seed=100 + i,
        )
        ds = PadBoxSlotDataset(self.conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        self.table.begin_pass(ds.unique_keys())
        self.trainer.train_from_dataset(ds, self.table)
        self.table.end_pass()
        ds.close()

    def publisher(self, root):
        return Publisher(
            root, staging_dir=os.path.join(self.workdir, "stage"))

    def publish_base(self, pub, tag, dtype):
        return pub.publish_base(
            tag, self.model, self.trainer.params, self.table,
            batch_size=B, key_capacity=KCAP, dense_dim=DENSE,
            feed_conf=self.conf, embedding_dtype=dtype,
        )

    def fresh_artifact(self, out, dtype):
        export_model(
            self.model, self.trainer.params, self.table, out,
            batch_size=B, key_capacity=KCAP, dense_dim=DENSE,
            feed_conf=self.conf, embedding_dtype=dtype,
        )
        return out


def _lines(n, seed=5, vocab=60):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        parts = ["1 0"]
        for _s in range(S):
            ks = rng.integers(0, vocab, 2)
            parts.append(f"{len(ks)} " + " ".join(map(str, ks)))
        parts.append(f"{DENSE} " + " ".join(
            f"{v:.3f}" for v in rng.random(DENSE)))
        out.append(" ".join(parts))
    return ("\n".join(out) + "\n").encode()


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_quantized_delta_chain_roundtrip(tmp_path, dtype):
    """Quantized base + 3 quantized deltas == a quantized fresh full
    export at the same pass: bit-equal keys, head, embedx codes, scales
    AND scores — the delta-publish path ships ~4x fewer bytes with zero
    drift (row-wise deterministic quantization, inference/quant.py)."""
    job = _Job(tmp_path)
    root = str(tmp_path / "pub")
    pub = job.publisher(root)
    job.train_pass(0)
    entry = job.publish_base(pub, "p0", dtype)
    assert entry.embedding_dtype == dtype and entry.n_bytes > 0
    for i in range(1, 4):
        job.train_pass(i)
        d = pub.publish_delta(f"p{i}", job.table, job.model,
                              job.trainer.params)
        assert d.embedding_dtype == dtype

    srv = ScoringServer()
    sync = Syncer(root, srv, "live", cache_dir=str(tmp_path / "cache"),
                  poll_interval_s=0.05)
    assert sync.poll_once() == 4
    version = sync.registry.current_version("live")
    assert version.embedding_dtype == dtype

    fresh = Predictor.load(
        job.fresh_artifact(str(tmp_path / "full"), dtype))
    live = srv._models["live"].predictor
    np.testing.assert_array_equal(live._keys, fresh._keys)
    np.testing.assert_array_equal(live._head, fresh._head)
    np.testing.assert_array_equal(np.asarray(live._q),
                                  np.asarray(fresh._q))
    np.testing.assert_array_equal(live._scales, fresh._scales)

    body = _lines(23)
    srv2 = ScoringServer()
    srv2.register("fresh", str(tmp_path / "full"))
    assert srv.score_lines(body, "live") == srv2.score_lines(body, "fresh")


def test_fp32_delta_onto_quantized_base_full_reloads(tmp_path):
    """The chain-mixing guard: an fp32 delta arriving on an int8 chain is
    a STRUCTURED refusal (EmbeddingDtypeMismatch) that triggers the
    Syncer's full-reload fallback — the live table is never corrupted by
    a dtype-mixed merge, and serving continues."""
    job = _Job(tmp_path)
    root = str(tmp_path / "pub")
    pub = job.publisher(root)
    job.train_pass(0)
    job.publish_base(pub, "p0", "int8")
    srv = ScoringServer()
    sync = Syncer(root, srv, "live", cache_dir=str(tmp_path / "cache"),
                  poll_interval_s=0.05)
    assert sync.poll_once() == 1
    body = _lines(9)
    assert srv.score_lines(body, "live")

    # unit guard first: the predictor itself refuses the mixed merge
    live = srv._models["live"].predictor
    with pytest.raises(EmbeddingDtypeMismatch):
        live.with_delta(np.array([1], np.uint64),
                        np.zeros((1, job.tconf.row_width), np.float32),
                        embedding_dtype="fp32")

    # now ship a mismatched delta for real (a misconfigured trainer
    # overriding the chain dtype) and let the fallback ladder handle it
    job.train_pass(1)
    d = pub.publish_delta("p1", job.table, job.model, job.trainer.params,
                          embedding_dtype="fp32")
    assert d.embedding_dtype == "fp32"
    fails = telemetry.counter("sync.apply_failures")
    reloads = telemetry.counter("sync.full_reload_fallback")
    f0, r0 = fails.value(kind="delta"), reloads.value()
    sync.poll_once()
    assert fails.value(kind="delta") == f0 + 1
    assert reloads.value() == r0 + 1
    # the full reload re-applied the base; the server keeps serving and
    # the live artifact is still the quantized base, not a corrupt mix
    live = srv._models["live"].predictor
    assert live.embedding_dtype == "int8" and live._quantized
    assert srv.score_lines(body, "live")


def test_resumed_publisher_keeps_chain_dtype(tmp_path):
    """A publisher restarted against an existing root publishes deltas in
    the CHAIN's dtype (read off the donefile base entry), not the flag
    default — restart must not silently flip a chain to fp32."""
    job = _Job(tmp_path)
    root = str(tmp_path / "pub")
    pub = job.publisher(root)
    job.train_pass(0)
    job.publish_base(pub, "p0", "int8")
    job.train_pass(1)
    pub2 = Publisher(root, staging_dir=os.path.join(job.workdir, "stage2"))
    d = pub2.publish_delta("p1", job.table)  # sparse-only, resumed
    assert d.embedding_dtype == "int8"
    srv = ScoringServer()
    sync = Syncer(root, srv, "live", cache_dir=str(tmp_path / "cache"),
                  poll_interval_s=0.05)
    assert sync.poll_once() == 2  # base + delta, no fallback needed
    assert srv._models["live"].predictor.embedding_dtype == "int8"


def test_legacy_quantize_flag_still_loads(tmp_path):
    """The pre-existing quantize=True format (global per-shard scale,
    dequant at load) keeps working unchanged next to the new path."""
    conf, ds, model, table, trainer = _train_small(tmp_path / "d",
                                                   embedding_dim=8)
    kcap = conf.batch_key_capacity or KCAP
    art = str(tmp_path / "legacy")
    export_model(model, trainer.params, table, art, batch_size=B,
                 key_capacity=kcap, dense_dim=DENSE, quantize=True)
    pred = Predictor.load(art)
    assert pred.embedding_dtype == "fp32"  # in-memory form IS f32
    assert not pred._quantized
    out = pred.predict(next(ds.batches(drop_last=False)))
    assert np.all(np.isfinite(out))
    ds.close()
