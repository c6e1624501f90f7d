"""Long-sequence CTR: ordered behavior feed + attention tower + seq mesh.

Sequence parallelism as a model consumes it, not as pure functions alone.
These tests pin the full consumable path: the feed's
seq_pos construction, masked attention (key_valid) parity, LongSeqCtrDnn
training end-to-end through the unmodified Trainer, and single-device vs
sequence-parallel (ring AND ulysses) output parity on the virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import LongSeqCtrDnn
from paddlebox_tpu.parallel.sequence import SEQ_AXIS, full_attention
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

S, DENSE, B, T = 3, 2, 32, 16


def _config(**kw):
    return make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=24, sequence_slot="slot0", max_seq_len=T, **kw
    )


def _dataset(tmp_path, n_ins=256):
    files = write_synth_files(
        str(tmp_path), n_files=1, ins_per_file=n_ins, n_sparse_slots=S,
        vocab_per_slot=50, dense_dim=DENSE, seed=11, max_keys_per_slot=9,
    )
    conf = _config()
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    return conf, ds


def test_feed_seq_pos_points_at_slot_keys_in_order(tmp_path):
    conf, ds = _dataset(tmp_path)
    batch = next(ds.batches(drop_last=False))
    assert batch.seq_pos is not None and batch.seq_pos.shape == (B, T)
    K = batch.keys.shape[0]
    for i in range(min(8, int(batch.ins_mask.sum()))):
        pos = batch.seq_pos[i]
        real = pos[pos < K]
        # every position belongs to instance i's slot0 segment, in order
        assert (batch.key_segments[real] == i * S).all()
        assert (np.diff(real) == 1).all()  # contiguous run, file order
        # count matches the instance's slot0 key count (<= T)
        n_slot0 = int((batch.key_segments[: batch.n_keys] == i * S).sum())
        assert real.shape[0] == min(n_slot0, T)
    ds.close()


def test_masked_full_attention_matches_dense_reference():
    rng = np.random.default_rng(0)
    b, t, h, d = 2, 8, 2, 4
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
        for _ in range(3)
    )
    valid = jnp.asarray(
        np.array([[1, 1, 1, 0, 0, 0, 0, 0], [1] * 8], dtype=bool)
    )
    got = np.asarray(full_attention(q, k, v, key_valid=valid))
    # dense reference: softmax over valid keys only
    qn, kn, vn = (np.asarray(x).transpose(0, 2, 1, 3) for x in (q, k, v))
    s = qn @ kn.transpose(0, 1, 3, 2) / np.sqrt(d)
    s = np.where(np.asarray(valid)[:, None, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = (p @ vn).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_seq_parallel_matches_single_device(tmp_path, impl):
    """The SAME model, single-device vs sharded over a 4-way seq mesh, must
    produce identical logits (ring/ulysses reduce to full attention)."""
    conf, ds = _dataset(tmp_path)
    tconf = SparseTableConfig(embedding_dim=8)
    mesh = Mesh(np.array(jax.devices()[:4]), (SEQ_AXIS,))
    kw = dict(dense_dim=DENSE, hidden=(16,), max_seq_len=T, n_heads=4,
              head_dim=8)
    single = LongSeqCtrDnn(S, tconf.row_width, **kw)
    sharded = LongSeqCtrDnn(S, tconf.row_width, seq_mesh=mesh,
                            seq_impl=impl, **kw)
    params = single.init(jax.random.PRNGKey(3))

    table = SparseTable(tconf, seed=0)
    table.begin_pass(ds.unique_keys())
    batch = next(ds.batches(drop_last=True))
    plan = table.plan_batch(batch)
    from paddlebox_tpu.train.trainer import _device_batch

    dev = _device_batch(batch, plan, S)
    from paddlebox_tpu.sparse.table import pull_rows

    rows = pull_rows(table.values, dev["idx"])
    args = (rows, dev["key_segments"], dev["dense"], B, dev["seq_pos"])
    l1 = np.asarray(single.apply(params, *args))
    l2 = np.asarray(sharded.apply(params, *args))
    table.end_pass()
    ds.close()
    np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)


def test_longseq_trains_e2e_and_attention_gets_gradients(tmp_path):
    """Full Trainer pass: finite loss, qkv projection receives gradients
    (the attention tower is live, not dead weight), and a second pass
    improves the loss."""
    conf, ds = _dataset(tmp_path, n_ins=512)
    tconf = SparseTableConfig(embedding_dim=8, learning_rate=0.5,
                              initial_range=0.05)
    model = LongSeqCtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(32,),
                          max_seq_len=T)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf,
                      TrainerConfig(dense_lr=3e-3, auc_buckets=1 << 12),
                      seed=0)
    qkv0 = np.asarray(trainer.params["qkv"]).copy()
    losses = []
    for p in range(3):
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(ds, table)
        table.end_pass()
        losses.append(m["loss"])
    ds.close()
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert np.abs(np.asarray(trainer.params["qkv"]) - qkv0).max() > 1e-6


def test_seq_model_without_seq_feed_raises(tmp_path):
    files = write_synth_files(
        str(tmp_path), n_files=1, ins_per_file=64, n_sparse_slots=S,
        vocab_per_slot=50, dense_dim=DENSE, seed=1,
    )
    conf = make_synth_config(  # NO sequence_slot configured
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=24,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=8)
    model = LongSeqCtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,),
                          max_seq_len=T)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10))
    table.begin_pass(ds.unique_keys())
    with pytest.raises(RuntimeError, match="sequence_slot"):
        trainer.train_from_dataset(ds, table)
    table.end_pass()
    ds.close()


def test_longseq_export_and_predict(tmp_path):
    """The sequence model exports and serves: Predictor scores equal the
    in-process forward, including through a smaller shape bucket."""
    from paddlebox_tpu.inference import Predictor, export_model

    conf, ds = _dataset(tmp_path / "data")
    tconf = SparseTableConfig(embedding_dim=8)
    model = LongSeqCtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16,),
                          max_seq_len=T)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10), seed=0)
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()

    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    art = str(tmp_path / "artifact")
    export_model(
        model, trainer.params, table, art,
        batch_size=B, key_capacity=kcap, dense_dim=DENSE,
    )
    pred = Predictor.load(art)
    assert pred.meta["seq_len"] == T
    batch = next(ds.batches(drop_last=True))
    out = pred.predict(batch)

    # in-process reference forward on the same batch
    table.begin_pass(ds.unique_keys())
    plan = table.plan_batch(batch)
    from paddlebox_tpu.sparse.table import pull_rows
    from paddlebox_tpu.train.trainer import _device_batch

    dev = _device_batch(batch, plan, S)
    rows = pull_rows(table.values, dev["idx"])
    logits = model.apply(trainer.params, rows, dev["key_segments"],
                         dev["dense"], B, seq_pos=dev["seq_pos"])
    table.end_pass()
    ds.close()
    want = np.asarray(jax.nn.sigmoid(logits))[: out.shape[0]]
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


def test_longseq_multichip_trains(tmp_path):
    """LongSeqCtrDnn under MultiChipTrainer on the 8-device mesh: the seq
    feed stacks per device and the step runs (the plumbing finding)."""
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.parallel.sharded_table import ShardedSparseTable
    from paddlebox_tpu.parallel.trainer import MultiChipTrainer

    conf, ds = _dataset(tmp_path, n_ins=512)
    tconf = SparseTableConfig(embedding_dim=8)
    mesh = make_mesh(8)
    model = LongSeqCtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16,),
                          max_seq_len=T)
    st = ShardedSparseTable(tconf, mesh)
    mt = MultiChipTrainer(model, tconf, mesh,
                          TrainerConfig(auc_buckets=1 << 10))
    st.begin_pass(ds.unique_keys())
    m = mt.train_from_dataset(ds, st)
    st.end_pass()
    ds.close()
    assert np.isfinite(m["loss"]) and m["steps"] > 0
