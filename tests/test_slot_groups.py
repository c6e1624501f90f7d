"""Per-slot learning-rate map + variable per-slot embedding dims.

Reference: the BoxPS LR map (box_wrapper.h:631 GetLRMap/SetLRMap) and the
FEATURE_VARIABLE per-slot-dim layout (box_wrapper.cc:404-566 dispatch).
Synth keys are slot-disjoint (slot s owns [s*VOCAB+1, (s+1)*VOCAB]), which
makes per-slot effects directly observable in the table.
"""

import numpy as np
import pytest

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable, _key_uniform
from paddlebox_tpu.train import Trainer

N_SLOTS, DENSE, B, VOCAB = 4, 4, 64, 100


@pytest.fixture(scope="module")
def synth(tmp_path_factory):
    td = tmp_path_factory.mktemp("slotgroups")
    conf = make_synth_config(
        n_sparse_slots=N_SLOTS, dense_dim=DENSE, batch_size=B,
        batch_key_capacity=B * N_SLOTS * 4,
    )
    paths = write_synth_files(
        str(td), n_files=2, ins_per_file=4 * B, n_sparse_slots=N_SLOTS,
        vocab_per_slot=VOCAB, dense_dim=DENSE, seed=21,
    )
    return paths, conf


def _train(paths, conf, tconf, model, passes=1):
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10))
    table = SparseTable(tconf)
    ds = PadBoxSlotDataset(conf)
    ds.set_filelist(paths)
    ds.load_into_memory()
    m = None
    for _ in range(passes):
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(
            ds, table, auc_state=trainer.last_metric_state)
        table.end_pass()
    ds.close()
    return m, table.state_dict()


def _slot_of(keys):
    return (np.asarray(keys, np.int64) - 1) // VOCAB


def test_uniform_lr_map_matches_scalar_lr(synth):
    """An LR map assigning every slot the default lr is bit-identical to
    the scalar path: the map machinery itself changes nothing."""
    paths, conf = synth

    def mk():
        return CtrDnn(n_sparse_slots=N_SLOTS, emb_width=10, dense_dim=DENSE,
                      hidden=(16,))

    base = SparseTableConfig(embedding_dim=8, learning_rate=0.05)
    mapped = SparseTableConfig(
        embedding_dim=8, learning_rate=0.05,
        slot_learning_rates=tuple((s, 0.05) for s in range(N_SLOTS)),
    )
    m1, sd1 = _train(paths, conf, base, mk())
    m2, sd2 = _train(paths, conf, mapped, mk())
    assert m1["loss"] == pytest.approx(m2["loss"], rel=1e-7)
    np.testing.assert_array_equal(sd1["keys"], sd2["keys"])
    np.testing.assert_allclose(sd1["values"], sd2["values"], rtol=1e-7)


def test_per_slot_lr_scales_updates(synth):
    """Slots with a 100x smaller lr must move their embeddings far less;
    a slot's lr must not leak into other slots' updates."""
    paths, conf = synth
    tconf = SparseTableConfig(
        embedding_dim=8, learning_rate=0.05,
        slot_learning_rates=((2, 0.0005), (3, 0.0005)),
    )
    model = CtrDnn(n_sparse_slots=N_SLOTS, emb_width=tconf.row_width,
                   dense_dim=DENSE, hidden=(16,))
    _, sd = _train(paths, conf, tconf, model)
    co, w = tconf.cvm_offset, tconf.row_width
    init = _key_uniform(sd["keys"], seed=0, n_cols=w - co,
                        rng_range=tconf.initial_range)
    moved = np.abs(sd["values"][:, co:w] - init).mean(axis=1)
    slot = _slot_of(sd["keys"])
    fast = moved[slot < 2].mean()
    slow = moved[slot >= 2].mean()
    assert slow > 0  # the slow group still trains...
    assert fast > 20 * slow  # ...but ~100x slower lr moves it far less


def test_variable_dims_freeze_masked_columns(synth):
    """Slots narrowed to dim 3 of 8 must keep their masked embedx columns
    exactly at the deterministic init (zero gradient by construction),
    while their active columns and other slots train normally."""
    paths, conf = synth
    tconf = SparseTableConfig(embedding_dim=8)
    model = CtrDnn(
        n_sparse_slots=N_SLOTS, emb_width=tconf.row_width, dense_dim=DENSE,
        hidden=(16,), slot_embed_dims=((1, 3),),
    )
    m, sd = _train(paths, conf, tconf, model, passes=2)
    assert np.isfinite(m["loss"])
    co, w = tconf.cvm_offset, tconf.row_width
    init = _key_uniform(sd["keys"], seed=0, n_cols=w - co,
                        rng_range=tconf.initial_range)
    slot = _slot_of(sd["keys"])
    narrowed = slot == 1
    # masked columns (3..8 of slot 1) frozen at init
    np.testing.assert_allclose(
        sd["values"][narrowed, co + 3 : w], init[narrowed, 3:], rtol=1e-6
    )
    # active columns of slot 1 did train
    active_moved = np.abs(
        sd["values"][narrowed, co : co + 3] - init[narrowed, :3]
    ).mean()
    assert active_moved > 1e-4
    # full-width slots train across all columns
    wide_moved = np.abs(sd["values"][~narrowed, co:w] - init[~narrowed]).mean()
    assert wide_moved > 1e-4


def test_bad_configs_rejected(synth):
    with pytest.raises(ValueError):
        CtrDnn(n_sparse_slots=2, emb_width=10, slot_embed_dims=((5, 3),))
    with pytest.raises(ValueError):
        CtrDnn(n_sparse_slots=2, emb_width=10, slot_embed_dims=((0, 99),))
    model = CtrDnn(n_sparse_slots=2, emb_width=10)
    with pytest.raises(ValueError):
        Trainer(
            model,
            SparseTableConfig(embedding_dim=8,
                              slot_learning_rates=((7, 0.1),)),
        )


N_DEV = 8


def _train_sharded(paths, tconf, model, n_dev=N_DEV):
    """Train one pass on the 8-device mesh: same files as _train, split into
    per-device batches of B // n_dev so the global batch matches."""
    import jax

    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.parallel import (
        MultiChipTrainer,
        ShardedSparseTable,
        make_mesh,
    )

    assert len(jax.devices()) >= n_dev, "conftest must force 8 CPU devices"
    mesh = make_mesh(n_dev)
    conf = make_synth_config(
        n_sparse_slots=N_SLOTS, dense_dim=DENSE, batch_size=B // n_dev,
        batch_key_capacity=B * N_SLOTS * 4 // n_dev,
    )
    ds = PadBoxSlotDataset(conf)
    ds.set_filelist(paths)
    ds.load_into_memory()
    trainer = MultiChipTrainer(
        model, tconf, mesh, TrainerConfig(auc_buckets=1 << 10), seed=0
    )
    table = ShardedSparseTable(tconf, mesh, seed=0, bucket_slack=float(n_dev))
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    sd = table.state_dict()
    ds.close()
    return m, sd


def test_sharded_uniform_lr_map_matches_scalar(synth):
    """On the 8-device mesh a uniform LR map must be bit-identical to the
    scalar path — the sharded LR plumbing itself changes nothing."""
    paths, _ = synth

    def mk():
        return CtrDnn(n_sparse_slots=N_SLOTS, emb_width=10, dense_dim=DENSE,
                      hidden=(16,))

    base = SparseTableConfig(embedding_dim=8, learning_rate=0.05)
    mapped = SparseTableConfig(
        embedding_dim=8, learning_rate=0.05,
        slot_learning_rates=tuple((s, 0.05) for s in range(N_SLOTS)),
    )
    m1, sd1 = _train_sharded(paths, base, mk())
    m2, sd2 = _train_sharded(paths, mapped, mk())
    assert m1["loss"] == pytest.approx(m2["loss"], rel=1e-7)
    np.testing.assert_array_equal(sd1["keys"], sd2["keys"])
    np.testing.assert_allclose(sd1["values"], sd2["values"], rtol=1e-7)


def test_sharded_per_slot_lr_matches_single_chip(synth):
    """The LR map must act identically on the sharded path and the
    single-chip path: one pass over the same instances (global batch B as
    8 x B/8), same seeds, table states must agree feature-by-feature
    (reference: the LR map applies in the production multi-GPU push,
    box_wrapper.h:631 / box_wrapper.cc:404-566)."""
    paths, conf = synth
    tconf = SparseTableConfig(
        embedding_dim=8, learning_rate=0.05,
        slot_learning_rates=((2, 0.0005), (3, 0.0005)),
    )

    def mk():
        return CtrDnn(n_sparse_slots=N_SLOTS, emb_width=tconf.row_width,
                      dense_dim=DENSE, hidden=(16,))

    _, sd1 = _train(paths, conf, tconf, mk())
    _, sd8 = _train_sharded(paths, tconf, mk())
    np.testing.assert_array_equal(sd1["keys"], sd8["keys"])
    np.testing.assert_allclose(sd1["values"], sd8["values"], atol=2e-4)
    # and the per-slot effect itself is visible on the sharded table
    co, w = tconf.cvm_offset, tconf.row_width
    init = _key_uniform(sd8["keys"], seed=0, n_cols=w - co,
                        rng_range=tconf.initial_range)
    moved = np.abs(sd8["values"][:, co:w] - init).mean(axis=1)
    slot = _slot_of(sd8["keys"])
    assert moved[slot < 2].mean() > 20 * moved[slot >= 2].mean()
