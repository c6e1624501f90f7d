"""Criteo-format adapter: TSV parse, hashing stability, conversion into
the canonical pipeline, and an e2e learnability gate on the spec-exact
sample (reference analog: the dist-CTR e2e tier, ctr_dataset_reader.py,
whose data download is unavailable offline)."""

import numpy as np
import pytest

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.criteo import (
    CRITEO_N_CAT,
    CRITEO_N_DENSE,
    CriteoTSVGenerator,
    convert_criteo_files,
    criteo_feed_config,
    criteo_key,
    dense_transform,
    write_criteo_format_sample,
)
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer


def test_key_hash_stable_and_slot_mixed():
    assert criteo_key(0, "68fd1e64") == criteo_key(0, "68fd1e64")
    assert criteo_key(0, "68fd1e64") != criteo_key(1, "68fd1e64")
    assert criteo_key(3, "") != 0 and criteo_key(5, "x") != 0
    assert 0 < criteo_key(7, "abc") < (1 << 64)


def test_dense_transform_recipe():
    assert dense_transform("") == 0.0
    assert dense_transform(None) == 0.0
    assert dense_transform("junk") == 0.0
    assert dense_transform("nan") == 0.0  # must not poison the pass
    assert dense_transform("inf") == 0.0
    assert dense_transform("-3") == 0.0  # clipped at zero
    assert dense_transform("0") == 0.0
    assert dense_transform("1") == pytest.approx(np.log1p(1.0))
    assert dense_transform("100") == pytest.approx(np.log1p(100.0))


def test_tsv_line_parses_with_empty_fields():
    conf = criteo_feed_config(8)
    gen = CriteoTSVGenerator(conf)
    ints = ["5", ""] + ["2"] * (CRITEO_N_DENSE - 2)
    cats = ["aa11bb22", ""] + ["cc33dd44"] * (CRITEO_N_CAT - 2)
    line = "\t".join(["1"] + ints + cats)
    (ins,) = list(gen.generate_sample(line))
    by = dict(ins)
    assert by["click"] == [1.0]
    assert len(by["dense0"]) == CRITEO_N_DENSE
    assert by["dense0"][0] == pytest.approx(np.log1p(5.0))
    assert by["dense0"][1] == 0.0
    assert by["cat0"] == [criteo_key(0, "aa11bb22")]
    assert by["cat1"] == []  # empty categorical emits no key
    # ragged line (short tail) still parses
    (ins2,) = list(gen.generate_sample("0\t1\t2"))
    by2 = dict(ins2)
    assert by2["click"] == [0.0] and by2["cat25"] == []


def test_convert_and_pipeline_roundtrip(tmp_path):
    tsv = write_criteo_format_sample(str(tmp_path / "s.tsv"), n_lines=256,
                                     seed=3)
    shards = convert_criteo_files([tsv], str(tmp_path / "out"),
                                  batch_size=64, lines_per_shard=100)
    assert len(shards) == 3  # 256 lines / 100 per shard
    conf = criteo_feed_config(64)
    ds = PadBoxSlotDataset(conf, read_threads=2)
    ds.set_filelist(shards)
    ds.load_into_memory()
    batches = list(ds.batches(drop_last=False))
    total = sum(int(b.ins_mask.sum()) for b in batches)
    assert total == 256
    b0 = batches[0]
    assert b0.n_sparse_slots == CRITEO_N_CAT
    assert b0.dense.shape[1] == CRITEO_N_DENSE
    assert b0.n_keys > 0 and (b0.keys[: b0.n_keys] > 0).all()
    labels = np.concatenate(
        [b.labels[b.ins_mask.astype(bool)] for b in batches])
    assert set(np.unique(labels)) <= {0.0, 1.0} and 0 < labels.mean() < 1
    ds.close()


def test_gzip_input(tmp_path):
    import gzip

    tsv = write_criteo_format_sample(str(tmp_path / "s.tsv"), n_lines=32)
    gz = str(tmp_path / "s.tsv.gz")
    with open(tsv, "rb") as f, gzip.open(gz, "wb") as g:
        g.write(f.read())
    shards = convert_criteo_files([gz], str(tmp_path / "out"), batch_size=8)
    conf = criteo_feed_config(8)
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(shards)
    ds.load_into_memory()
    assert sum(int(b.ins_mask.sum()) for b in ds.batches(drop_last=False)) == 32
    ds.close()


def test_criteo_sample_e2e_learns(tmp_path):
    """The full path on the spec-exact sample: convert -> native parse ->
    3-pass CTR-DNN -> the planted signal must be learned (AUC gate)."""
    tsv = write_criteo_format_sample(str(tmp_path / "s.tsv"), n_lines=2048,
                                     seed=1)
    shards = convert_criteo_files([tsv], str(tmp_path / "out"),
                                  batch_size=128)
    conf = criteo_feed_config(128)
    ds = PadBoxSlotDataset(conf, read_threads=2)
    ds.set_filelist(shards)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=8)
    model = CtrDnn(CRITEO_N_CAT, tconf.row_width, dense_dim=CRITEO_N_DENSE,
                   hidden=(64, 32))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 12),
                      seed=0)
    m = None
    for _ in range(3):
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(
            ds, table, auc_state=trainer.last_metric_state)
        table.end_pass()
    ds.close()
    assert m["count"] == 3 * 2048
    assert np.isfinite(m["loss"])
    assert m["auc"] > 0.62, f"planted Criteo signal not learned: {m['auc']}"
