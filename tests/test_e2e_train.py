"""End-to-end single-chip training.

Mirrors the reference e2e template (python/paddle/fluid/tests/unittests/
test_paddlebox_datafeed.py:22-120): write slot files, run the full pass
lifecycle — load -> key census -> begin_pass -> train -> end_pass — and
assert the model actually learns (loss drops, AUC beats chance).
"""

import numpy as np
import pytest

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import DatasetFactory
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse import SparseTable
from paddlebox_tpu.train import Trainer

N_SLOTS = 3
DENSE = 4


@pytest.fixture(scope="module")
def synth(tmp_path_factory):
    d = tmp_path_factory.mktemp("synth")
    paths = write_synth_files(
        str(d), n_files=2, ins_per_file=512, n_sparse_slots=N_SLOTS,
        vocab_per_slot=50, dense_dim=DENSE, seed=7,
    )
    conf = make_synth_config(
        n_sparse_slots=N_SLOTS, dense_dim=DENSE, batch_size=64,
        max_feasigns_per_ins=16,
    )
    return paths, conf


def _make_world(conf, seed=0):
    tconf = SparseTableConfig(embedding_dim=8, learning_rate=0.5, initial_range=0.05)
    table = SparseTable(tconf, seed=seed)
    model = CtrDnn(
        n_sparse_slots=N_SLOTS, emb_width=tconf.row_width, dense_dim=DENSE,
        hidden=(32, 16),
    )
    trainer = Trainer(
        model, tconf, TrainerConfig(dense_lr=3e-3, auc_buckets=1 << 12), seed=seed
    )
    return table, trainer


def test_e2e_loss_decreases_and_auc_beats_chance(synth):
    paths, conf = synth
    ds = DatasetFactory().create_dataset("BoxPSDataset", conf)
    ds.set_filelist(paths)
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 1024

    table, trainer = _make_world(conf)
    per_pass = []
    for p in range(4):
        ds.local_shuffle(seed=p)
        table.begin_pass(ds.unique_keys())
        metrics = trainer.train_from_dataset(ds, table)
        table.end_pass()
        per_pass.append(metrics)
    ds.close()

    losses = [m["loss"] for m in per_pass]
    assert losses[-1] < losses[0] * 0.9, f"loss did not decrease: {losses}"
    assert per_pass[-1]["auc"] > 0.65, f"AUC barely above chance: {per_pass[-1]}"
    # table persisted features across passes
    assert table.n_features > 0
    assert table.missing_key_count == 0  # census covered every batch key


def test_e2e_preload_overlap_lifecycle(synth):
    """The double-buffered day pipeline: preload pass N+1 while training N
    (reference: BoxHelper::PreLoadIntoMemory / WaitFeedPassDone)."""
    paths, conf = synth
    with DatasetFactory().create_dataset("BoxPSDataset", conf) as ds:
        ds.set_filelist(paths)
        ds.preload_into_memory()
        table, trainer = _make_world(conf, seed=1)
        ds.wait_preload_done()
        table.begin_pass(ds.unique_keys())
        ds.preload_into_memory()  # next pass reads while we train
        m1 = trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.wait_preload_done()
        table.begin_pass(ds.unique_keys())
        m2 = trainer.train_from_dataset(ds, table)
        table.end_pass()
    assert m1["steps"] == m2["steps"] == 16
    assert m2["loss"] < m1["loss"]


def test_check_nan_inf_catches_poisoned_lr(synth):
    """FLAGS_check_nan_inf analog actually fires."""
    paths, conf = synth
    with DatasetFactory().create_dataset("BoxPSDataset", conf) as ds:
        ds.set_filelist(paths)
        ds.load_into_memory()
        tconf = SparseTableConfig(embedding_dim=8)
        table = SparseTable(tconf)
        model = CtrDnn(
            n_sparse_slots=N_SLOTS, emb_width=tconf.row_width, dense_dim=DENSE,
            hidden=(16,),
        )
        trainer = Trainer(
            model, tconf,
            TrainerConfig(dense_lr=1e30, auc_buckets=1 << 10, check_nan_inf=True),
        )
        table.begin_pass(ds.unique_keys())
        with pytest.raises(FloatingPointError):
            trainer.train_from_dataset(ds, table)
