"""The model half of the training step comes from the model
(train/step_loss.py): a model without ``loss`` still computes yesterday's
step, a model's own ``loss`` is plumbing only, and the benchmark
reference's occurrence order is the program's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn, MMoE
from paddlebox_tpu.models.layers import bce_with_logits
from paddlebox_tpu.sparse.table import SparseTable, pull_rows
from paddlebox_tpu.train.trainer import Trainer, _device_batch

S, DENSE, B = 3, 2, 16


def dataset(tmp_path, n_task_labels=0):
    files = write_synth_files(
        str(tmp_path), n_files=1, ins_per_file=2 * B + 5, n_sparse_slots=S,
        vocab_per_slot=30, dense_dim=DENSE, seed=3,
        n_task_labels=n_task_labels)
    conf = make_synth_config(n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
                             max_feasigns_per_ins=12,
                             n_task_labels=n_task_labels)
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds


def models(tconf):
    return {
        "ctr_dnn": (CtrDnn(S, tconf.row_width, dense_dim=DENSE,
                           hidden=(16, 8)), 0),
        "mmoe": (MMoE(S, tconf.row_width, dense_dim=DENSE, n_tasks=2,
                      n_experts=2, expert_hidden=(8,), expert_dim=8,
                      tower_hidden=(4,)), 1),
    }


def by_hand_pass(model, trainer, table, ds):
    """Yesterday's step written out: pull -> apply -> mean sigmoid
    cross-entropy over the real instances (mean over tasks) -> Adam; the
    table's push is the trainer's own and is read, not redone."""
    # a copy: the trainer's step donates its own buffers
    params = jax.tree.map(jnp.array, trainer.params)
    opt = optax.adam(trainer.conf.dense_lr)
    state = opt.init(params)
    losses = []
    n_tasks = getattr(model, "n_tasks", 1)
    for batch in ds.batches():
        dev = _device_batch(batch, table.plan_batch(batch), S)

        def loss_fn(p):
            rows = pull_rows(table.values, dev["idx"])
            logits = model.apply(p, rows, dev["key_segments"], dev["dense"],
                                 B)
            mask = dev["ins_mask"]
            if n_tasks > 1:
                per = bce_with_logits(logits, dev["task_labels"]).mean(axis=1)
            else:
                per = bce_with_logits(logits, dev["labels"])
            return (per * mask).sum() / jnp.maximum(mask.sum(), 1.0)

        loss, g = jax.value_and_grad(loss_fn)(params)
        updates, state = opt.update(g, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
        yield batch, params, losses


@pytest.mark.parametrize("name", ["ctr_dnn", "mmoe"])
def test_a_model_without_loss_computes_yesterdays_step(tmp_path, name):
    """Loss and dense updates of a pass equal the step written out by hand
    to 1e-6: the same operations in the same order, float32 on the CPU.
    (The rows change between the steps of a pass, so the hand-written step
    reads them from the same open table, one batch behind the trainer: a
    one-batch pass each.)"""
    tconf = SparseTableConfig(embedding_dim=4)
    model, n_task_labels = models(tconf)[name]
    ds = dataset(tmp_path, n_task_labels)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(), seed=0)
    assert not hasattr(model, "loss")
    table.begin_pass(ds.unique_keys())
    for batch, want_params, want_losses in by_hand_pass(
            model, trainer, table, ds):
        m = trainer.train_steps(table, [batch])
        assert m["loss"] == pytest.approx(want_losses[-1], rel=1e-6)
        for g, w in zip(jax.tree.leaves(trainer.params),
                        jax.tree.leaves(want_params)):
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)
        assert "counters" not in trainer.last_metric_state
    table.end_pass()
    ds.close()
    trainer.close()


class CtrDnnOwnLoss(CtrDnn):
    """The same model with its half of the step behind ``loss``, the
    cross-entropy written the textbook way."""

    def loss(self, params, rows, batch):
        logits = self.apply(params, rows, batch["key_segments"],
                            batch["dense"], batch["labels"].shape[0])
        p, y, mask = jax.nn.sigmoid(logits), batch["labels"], batch[
            "ins_mask"]
        per = -y * jnp.log(p) - (1.0 - y) * jnp.log(1.0 - p)
        return (per * mask).sum() / jnp.maximum(mask.sum(), 1.0), p


def test_a_loss_that_writes_bce_another_way_equals_the_default_branch(
        tmp_path):
    """The hook's plumbing alone (PERF.md section 7 (i) until PR 27): loss,
    AUC, dense parameters and rows after a pass agree to 1e-5 (log(sigmoid)
    against the stable form: float32 rounding of the two formulas)."""
    tconf = SparseTableConfig(embedding_dim=4)
    out = {}
    for cls in (CtrDnn, CtrDnnOwnLoss):
        ds = dataset(tmp_path / cls.__name__)
        model = cls(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf, TrainerConfig(), seed=0)
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(ds, table)
        rows = table.pass_state_dict()["values"]
        table.end_pass()
        out[cls] = (m["loss"], m["auc"], jax.tree.leaves(trainer.params),
                    rows)
        ds.close()
        trainer.close()
    (l0, a0, p0, r0), (l1, a1, p1, r1) = out[CtrDnn], out[CtrDnnOwnLoss]
    assert l1 == pytest.approx(l0, rel=1e-5) and a1 == pytest.approx(a0)
    for g, w in zip(p1, p0):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(r1, r0, rtol=1e-4, atol=1e-6)


def test_a_models_counters_need_its_own_loss():
    """``step_counters`` without a third value from ``loss`` is refused
    when the step is built, not silently dropped."""
    from paddlebox_tpu.train.step_loss import counter_names, make_model_loss

    class Counted(CtrDnnOwnLoss):
        step_counters = ("toy.count",)

    model = Counted(S, 6, dense_dim=DENSE, hidden=(4,))
    assert counter_names(model) == ("toy.count",)
    f = make_model_loss(model, 1)
    batch = {"key_segments": jnp.zeros((4,), jnp.int32),
             "dense": jnp.zeros((2, DENSE)), "labels": jnp.zeros((2,)),
             "ins_mask": jnp.ones((2,))}
    with pytest.raises(ValueError, match="returned 2 values"):
        f(model.init(jax.random.PRNGKey(0)), jnp.zeros((4, 6)), batch)


def test_batch_arrays_lists_occurrences_in_the_programs_order(tmp_path):
    """The contract a benchmark reference's ``loss`` is written against
    (benchmark/reference/common.py ``batch_arrays``): one entry an
    occurrence in (instance, slot, position in slot) order -- which is the
    order of the program's key buffer, ``HostBatch.keys`` / ``seq_pos``."""
    from benchmark import gen
    from benchmark.reference import common

    # two instances, two slots: 90 40 | 70 90 10 || 40 | 90 20
    keys = np.zeros((2, 2, 3), np.uint64)
    keys[0, 0, :2], keys[0, 1] = [90, 40], [70, 90, 10]
    keys[1, 0, :1], keys[1, 1, :2] = [40], [90, 20]
    data = gen.PassData(keys, np.array([1.0, 0.0], np.float32),
                        np.zeros((2, 1), np.float32),
                        np.zeros((2, 1), np.int32))
    table = np.array([5, 10, 20, 40, 70, 90], np.uint64)
    uniq, b = common.batch_arrays(data, 10, table)
    assert uniq.tolist() == [10, 20, 40, 70, 90]
    assert b["ins"].tolist() == [0, 0, 0, 0, 0, 1, 1, 1, 2, 2]
    assert b["slot"].tolist() == [0, 0, 1, 1, 1, 0, 1, 1, 2, 2]
    assert b["pos"].tolist() == [0, 1, 0, 1, 2, 0, 0, 1, 0, 0]
    assert b["inv"].tolist() == [4, 2, 3, 4, 0, 2, 4, 1, 9, 9]
    assert b["key_rank"].tolist() == [1, 2, 3, 4, 5, -1, -1, -1, -1, -1]
    # the program's packer, through the text the generator writes
    from paddlebox_tpu.config import DataFeedConfig, SlotConfig
    from paddlebox_tpu.data.dataset import DatasetFactory

    slots = [SlotConfig(name="click", type="float", is_dense=True,
                        shape=(1,)),
             SlotConfig(name="slot0", type="uint64"),
             SlotConfig(name="slot1", type="uint64"),
             SlotConfig(name="dense0", type="float", is_dense=True,
                        shape=(1,))]
    conf = DataFeedConfig(slots=slots, batch_size=2, label_slot="click",
                          batch_key_capacity=10, sequence_slot="slot1",
                          max_seq_len=3)
    ds = DatasetFactory().create_dataset("BoxPSDataset", conf)
    ds.set_filelist(gen.write_files(data, str(tmp_path), "p", 1))
    ds.load_into_memory()
    batch = next(ds.batches())
    assert batch.keys.tolist() == [90, 40, 70, 90, 10, 40, 90, 20, 0, 0]
    assert np.array_equal(uniq[b["inv"][:8]], batch.keys[:8])
    assert batch.key_segments[:8].tolist() == b["seg"][:8].tolist()
    assert batch.seq_pos.tolist() == [[2, 3, 4], [6, 7, 10]]
    ds.close()
