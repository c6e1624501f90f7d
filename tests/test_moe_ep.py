"""Expert parallelism consumed by a real model (MMoE expert_mesh).

parallel/expert.py as a model consumes it; these tests pin that path: MMoE with its expert bank sharded over a
4-way ``expert`` mesh produces the SAME logits and trains end-to-end
through the unmodified multi-task Trainer."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import MMoE
from paddlebox_tpu.parallel.expert import EXPERT_AXIS
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

S, DENSE, B, E = 3, 2, 32, 4


def _mesh():
    return Mesh(np.array(jax.devices()[:4]), (EXPERT_AXIS,))


def _data(tmp_path, n_ins=256):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=8, n_task_labels=1,
    )
    files = write_synth_files(
        str(tmp_path), n_files=1, ins_per_file=n_ins, n_sparse_slots=S,
        vocab_per_slot=50, dense_dim=DENSE, seed=4, n_task_labels=1,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    return conf, ds


def test_ep_matches_serial(tmp_path):
    conf, ds = _data(tmp_path)
    tconf = SparseTableConfig(embedding_dim=4)
    kw = dict(dense_dim=DENSE, n_tasks=2, n_experts=E,
              expert_hidden=(16,), expert_dim=8, tower_hidden=(8,))
    serial = MMoE(S, tconf.row_width, **kw)
    sharded = MMoE(S, tconf.row_width, expert_mesh=_mesh(), **kw)
    params = serial.init(jax.random.PRNGKey(1))

    table = SparseTable(tconf, seed=0)
    table.begin_pass(ds.unique_keys())
    batch = next(ds.batches(drop_last=True))
    plan = table.plan_batch(batch)
    from paddlebox_tpu.sparse.table import pull_rows
    from paddlebox_tpu.train.trainer import _device_batch

    dev = _device_batch(batch, plan, S)
    rows = pull_rows(table.values, dev["idx"])
    args = (rows, dev["key_segments"], dev["dense"], B)
    l1 = np.asarray(serial.apply(params, *args))
    l2 = np.asarray(sharded.apply(params, *args))
    table.end_pass()
    ds.close()
    assert l1.shape == (B, 2)
    np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)


def test_ep_trains_e2e(tmp_path):
    conf, ds = _data(tmp_path, n_ins=512)
    tconf = SparseTableConfig(embedding_dim=4, learning_rate=0.5,
                              initial_range=0.05)
    model = MMoE(S, tconf.row_width, dense_dim=DENSE, n_tasks=2,
                 n_experts=E, expert_hidden=(16,), expert_dim=8,
                 tower_hidden=(8,), expert_mesh=_mesh())
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf,
                      TrainerConfig(dense_lr=3e-3, auc_buckets=1 << 10),
                      seed=0)
    losses = []
    for p in range(3):
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(ds, table)
        table.end_pass()
        losses.append(m["loss"])
    ds.close()
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert "task1/auc" in m  # multi-task metric streams intact


def test_ep_validates_divisibility():
    with pytest.raises(ValueError, match="divisible"):
        MMoE(S, 6, n_experts=6, expert_mesh=_mesh())
    with pytest.raises(ValueError, match="axis"):
        MMoE(S, 6, n_experts=4,
             expert_mesh=Mesh(np.array(jax.devices()[:4]), ("data",)))


def test_ep_matches_serial_bf16(tmp_path):
    """Cast-policy parity: the EP path upcasts expert outputs to f32 before
    the gate mixing exactly like the serial mlp() does, so sharded ==
    serial under a bf16 bank too (the review's measured failure case)."""
    conf, ds = _data(tmp_path)
    tconf = SparseTableConfig(embedding_dim=4)
    kw = dict(dense_dim=DENSE, n_tasks=2, n_experts=E, expert_hidden=(16,),
              expert_dim=8, tower_hidden=(8,), compute_dtype="bfloat16")
    serial = MMoE(S, tconf.row_width, **kw)
    sharded = MMoE(S, tconf.row_width, expert_mesh=_mesh(), **kw)
    params = serial.init(jax.random.PRNGKey(2))

    table = SparseTable(tconf, seed=0)
    table.begin_pass(ds.unique_keys())
    batch = next(ds.batches(drop_last=True))
    plan = table.plan_batch(batch)
    from paddlebox_tpu.sparse.table import pull_rows
    from paddlebox_tpu.train.trainer import _device_batch

    dev = _device_batch(batch, plan, S)
    rows = pull_rows(table.values, dev["idx"])
    args = (rows, dev["key_segments"], dev["dense"], B)
    l1 = np.asarray(serial.apply(params, *args))
    l2 = np.asarray(sharded.apply(params, *args))
    table.end_pass()
    ds.close()
    np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)
