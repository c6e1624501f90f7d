"""Export/serving tests: train -> export_model -> Predictor parity."""

import os

import numpy as np
import pytest

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.inference import Predictor, export_model
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

S, DENSE, B = 3, 2, 8


def _train_small(td, create_threshold=0.0):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B, max_feasigns_per_ins=16
    )
    files = write_synth_files(
        td, n_files=1, ins_per_file=64, n_sparse_slots=S, vocab_per_slot=50,
        dense_dim=DENSE, seed=11,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(
        embedding_dim=8, create_threshold=create_threshold
    )
    trconf = TrainerConfig(auc_buckets=1 << 10)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, trconf, seed=0)
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()
    return conf, ds, model, table, trainer


def test_export_predict_parity(tmp_path):
    """Predictor output == trainer-side forward on the same batch."""
    import jax
    import jax.numpy as jnp

    conf, ds, model, table, trainer = _train_small(str(tmp_path / "data"))
    art = str(tmp_path / "artifact")
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    export_model(
        model, trainer.params, table, art,
        batch_size=B, key_capacity=kcap, dense_dim=DENSE,
    )
    assert os.path.exists(os.path.join(art, "serving.stablehlo"))
    assert os.path.exists(os.path.join(art, "meta.json"))

    pred = Predictor.load(art)
    batch = next(ds.batches(drop_last=False))
    got = pred.predict(batch)
    assert got.shape[0] == int(batch.ins_mask.sum())

    # trainer-side reference forward: resolve rows through the live table
    table.begin_pass(table.state_dict()["keys"])
    plan = table.plan_batch(batch)
    from paddlebox_tpu.sparse.table import pull_rows

    rows = pull_rows(table.values, jnp.asarray(plan.idx))
    logits = model.apply(
        trainer.params, rows, jnp.asarray(batch.key_segments),
        jnp.asarray(batch.dense), B,
    )
    want = np.asarray(jax.nn.sigmoid(logits))[: got.shape[0]]
    table.end_pass()
    ds.close()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_predict_unseen_keys_and_batch_size_guard(tmp_path):
    conf, ds, model, table, trainer = _train_small(str(tmp_path / "data"))
    art = str(tmp_path / "artifact")
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    export_model(
        model, trainer.params, table, art,
        batch_size=B, key_capacity=kcap, dense_dim=DENSE,
    )
    pred = Predictor.load(art)
    batch = next(ds.batches(drop_last=False))
    # poison the keys: unseen features must resolve to zero rows, not crash
    batch.keys = batch.keys + np.uint64(10_000_000)
    out = pred.predict(batch)
    assert np.all(np.isfinite(out)) and out.shape[0] > 0

    # a request whose REAL instance/key counts exceed every exported
    # bucket must be rejected with actionable guidance (shape flexibility
    # covers anything smaller via padding, not anything larger)
    with pytest.raises(ValueError):
        pred._pick_bucket(B + 1, 0)
    kcap = pred.meta["key_capacity"]
    with pytest.raises(ValueError):
        pred._pick_bucket(1, kcap + 1)
    ds.close()


def test_predict_rejects_schema_mismatch(tmp_path):
    """A batch built under a different feed schema must be rejected up
    front (ADVICE r4: wrong slot count silently scored garbage — segment
    ids ins*S+slot computed under the wrong S; wider seq feeds silently
    dropped behavior history)."""
    conf, ds, model, table, trainer = _train_small(str(tmp_path / "data"))
    art = str(tmp_path / "artifact")
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    export_model(
        model, trainer.params, table, art,
        batch_size=B, key_capacity=kcap, dense_dim=DENSE,
    )
    pred = Predictor.load(art)
    ds.close()

    def batch_from(n_slots, dense_dim):
        c = make_synth_config(
            n_sparse_slots=n_slots, dense_dim=dense_dim, batch_size=B,
            max_feasigns_per_ins=16,
        )
        files = write_synth_files(
            str(tmp_path / f"d{n_slots}x{dense_dim}"), n_files=1,
            ins_per_file=B, n_sparse_slots=n_slots, vocab_per_slot=50,
            dense_dim=dense_dim, seed=3,
        )
        d = PadBoxSlotDataset(c, read_threads=1)
        d.set_filelist(files)
        d.load_into_memory()
        b = next(d.batches(drop_last=False))
        d.close()
        return b

    with pytest.raises(ValueError, match="sparse slots"):
        pred.predict(batch_from(S + 1, DENSE))
    with pytest.raises(ValueError, match="dense"):
        pred.predict(batch_from(S, DENSE + 2))


def test_predict_rejects_seq_len_mismatch(tmp_path):
    """Serving raises on a seq-width mismatch exactly like training does,
    instead of silently truncating behavior history (ADVICE r4)."""
    from paddlebox_tpu.models import LongSeqCtrDnn

    T = 8

    def data(seq_len, tag):
        c = make_synth_config(
            n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
            max_feasigns_per_ins=16, sequence_slot="slot0",
            max_seq_len=seq_len,
        )
        files = write_synth_files(
            str(tmp_path / tag), n_files=1, ins_per_file=32,
            n_sparse_slots=S, vocab_per_slot=50, dense_dim=DENSE, seed=11,
            max_keys_per_slot=6,
        )
        d = PadBoxSlotDataset(c, read_threads=1)
        d.set_filelist(files)
        d.load_into_memory()
        return c, d

    conf, ds = data(T, "train")
    tconf = SparseTableConfig(embedding_dim=8)
    model = LongSeqCtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,),
                          max_seq_len=T, n_heads=2, head_dim=4)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10), seed=0)
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()
    art = str(tmp_path / "artifact")
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    export_model(model, trainer.params, table, art,
                 batch_size=B, key_capacity=kcap, dense_dim=DENSE)
    pred = Predictor.load(art)
    # matching width serves fine
    out = pred.predict(next(ds.batches(drop_last=False)))
    assert np.all(np.isfinite(out))
    ds.close()
    # a WIDER feed (more history than the artifact was exported for) must
    # raise, not silently slice
    _, ds_wide = data(2 * T, "wide")
    with pytest.raises(ValueError, match="seq_len"):
        pred.predict(next(ds_wide.batches(drop_last=False)))
    ds_wide.close()


def test_predict_dataset_streams_all(tmp_path):
    conf, ds, model, table, trainer = _train_small(str(tmp_path / "data"))
    art = str(tmp_path / "artifact")
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    export_model(
        model, trainer.params, table, art,
        batch_size=B, key_capacity=kcap, dense_dim=DENSE,
    )
    pred = Predictor.load(art)
    total = sum(p.shape[0] for p in pred.predict_dataset(ds))
    assert total == 64
    ds.close()


def test_quantized_export_close_and_smaller(tmp_path):
    """int8 embedx snapshot: predictions close to the f32 artifact, sparse
    payload ~4x smaller."""
    conf, ds, model, table, trainer = _train_small(str(tmp_path / "data"))
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    art_f, art_q = str(tmp_path / "f32"), str(tmp_path / "q8")
    for art, quant in ((art_f, False), (art_q, True)):
        export_model(
            model, trainer.params, table, art,
            batch_size=B, key_capacity=kcap, dense_dim=DENSE, quantize=quant,
        )
    pf, pq = Predictor.load(art_f), Predictor.load(art_q)
    batch = next(ds.batches(drop_last=False))
    a, b2 = pf.predict(batch), pq.predict(batch)
    np.testing.assert_allclose(a, b2, atol=2e-2)  # int8 quant noise only
    ds.close()

    def sparse_bytes(art):
        d = os.path.join(art, "sparse")
        return sum(
            os.path.getsize(os.path.join(d, f))
            for f in os.listdir(d)
            if not f.startswith("keys")
        )

    # row: 3 f32 head cols + 5 int8 embedx vs 8 f32 cols -> ~0.53x here;
    # production rows (embedx >> head) approach 0.25x
    assert sparse_bytes(art_q) < 0.6 * sparse_bytes(art_f)


def test_rank_model_export_roundtrip(tmp_path):
    """RankCtrDnn (rank_offset-consuming) exports with the rank matrix as a
    fourth program input and predicts on PV-merged batches."""
    from paddlebox_tpu.models import RankCtrDnn

    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=16, parse_logkey=True, enable_pv_merge=True,
        pv_batch_size=4, rank_cmatch_filter=(222, 223),
    )
    files = write_synth_files(
        str(tmp_path / "pv"), n_files=1, ins_per_file=48, n_sparse_slots=S,
        vocab_per_slot=50, dense_dim=DENSE, seed=4, with_logkey=True,
        max_ads_per_pv=3,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    ds.preprocess_instance()
    tconf = SparseTableConfig(embedding_dim=8)
    model = RankCtrDnn(
        S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8),
        max_rank=conf.max_rank,
    )
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10))
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()

    art = str(tmp_path / "artifact")
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    export_model(
        model, trainer.params, table, art,
        batch_size=next(ds.batches()).batch_size,
        key_capacity=kcap, dense_dim=DENSE,
        rank_offset_cols=conf.rank_offset_cols,
    )
    pred = Predictor.load(art)
    batch = next(ds.batches(drop_last=False))
    out = pred.predict(batch)
    assert out.shape[0] == int(batch.ins_mask.sum())
    assert np.all(np.isfinite(out))
    # without the rank matrix the artifact must refuse
    batch.rank_offset = None
    with pytest.raises(ValueError, match="rank_offset"):
        pred.predict(batch)
    ds.close()


def test_export_respects_create_threshold(tmp_path):
    """Feature admission carries into serving: under-shown features read
    zero embeddings through the predictor's host resolve."""
    conf, ds, model, table, trainer = _train_small(
        str(tmp_path / "data"), create_threshold=1e9  # nothing admitted
    )
    art = str(tmp_path / "artifact")
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    export_model(
        model, trainer.params, table, art,
        batch_size=B, key_capacity=kcap, dense_dim=DENSE,
    )
    pred = Predictor.load(art)
    batch = next(ds.batches(drop_last=False))
    rows = pred._resolve_rows(
        batch.keys, batch.n_keys, pred.meta["key_capacity"]
    )
    co = pred.meta["cvm_offset"]
    assert np.all(rows[:, co:] == 0.0)  # embeddings hidden
    assert rows[:, :co].any()  # counters still visible
    ds.close()


def test_shape_buckets_serve_any_smaller_batch(tmp_path):
    """The artifact serves batches of ANY real size
    that fits a bucket — scores are bucket-invariant (padding rows are zero
    and padding segments drop out of the pooling segment_sum)."""
    conf, ds, model, table, trainer = _train_small(str(tmp_path / "data"))
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    art = str(tmp_path / "artifact")
    export_model(
        model, trainer.params, table, art,
        batch_size=B, key_capacity=kcap, dense_dim=DENSE,
        batch_buckets=[(B // 2, kcap // 2), (2 * B, 2 * kcap)],
    )
    pred = Predictor.load(art)
    assert sorted(pred.bucket_shapes) == [
        (B // 2, kcap // 2), (B, kcap), (2 * B, 2 * kcap)
    ]

    batch = next(ds.batches(drop_last=False))
    b_real = int(batch.ins_mask.sum())
    out_primary = pred.predict(batch)
    assert out_primary.shape[0] == b_real

    # shrink to a half batch: the small bucket must produce IDENTICAL
    # scores for the surviving instances
    import dataclasses

    half = B // 2
    nk_half = int((batch.key_segments[: batch.n_keys] < half * S).sum())
    small = dataclasses.replace(
        batch,
        batch_size=half,
        keys=batch.keys[: kcap // 2],
        key_segments=batch.key_segments[: kcap // 2],
        n_keys=nk_half,
        dense=batch.dense[:half],
        labels=batch.labels[:half],
        ins_mask=batch.ins_mask[:half],
    )
    out_small = pred.predict(small)
    np.testing.assert_allclose(out_small, out_primary[:half], rtol=1e-5,
                               atol=1e-6)
    ds.close()
