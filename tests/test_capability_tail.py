"""Capability tail: evaluate (infer_from_dataset), AUC-runner slot
importance, dump fields/params, InputTable / ReplicaCache, disk spill."""

import os

import numpy as np
import pytest

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

S, DENSE, B = 3, 2, 32


def _world(tmp_path, n_ins=192, **synth_kw):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B, max_feasigns_per_ins=16
    )
    files = write_synth_files(
        str(tmp_path / "data"), n_files=2, ins_per_file=n_ins // 2,
        n_sparse_slots=S, vocab_per_slot=40, dense_dim=DENSE, seed=2, **synth_kw,
    )
    ds = PadBoxSlotDataset(conf, read_threads=2)
    ds.set_filelist(files)
    tconf = SparseTableConfig(embedding_dim=4)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16,))
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10))
    table = SparseTable(tconf, seed=0)
    return conf, ds, trainer, table


def _train_passes(trainer, table, ds, n=4):
    for _ in range(n):
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(ds, table)
        table.end_pass()
    return m


def test_evaluate_no_updates(tmp_path):
    _, ds, trainer, table = _world(tmp_path)
    ds.load_into_memory()
    _train_passes(trainer, table, ds)
    store_before = table.state_dict()["values"].copy()
    params_before = [np.asarray(x).copy() for x in
                     __import__("jax").tree.leaves(trainer.params)]
    table.begin_pass(ds.unique_keys())
    m = trainer.evaluate(ds, table)
    table.end_pass()
    assert m["count"] == ds.get_memory_data_size()
    assert m["auc"] > 0.55
    np.testing.assert_array_equal(table.state_dict()["values"], store_before)
    for a, b in zip(__import__("jax").tree.leaves(trainer.params), params_before):
        np.testing.assert_array_equal(np.asarray(a), b)
    ds.close()


def test_auc_runner_slot_importance(tmp_path):
    from paddlebox_tpu.train.auc_runner import AucRunner

    _, ds, trainer, table = _world(tmp_path)
    ds.load_into_memory()
    _train_passes(trainer, table, ds, n=6)
    runner = AucRunner(trainer, table, seed=3)
    out = runner.run(
        ds, {"g_slot0": ["slot0"], "g_all": ["slot0", "slot1", "slot2"]}
    )
    assert out["baseline"]["auc"] > 0.55
    # replacing every slot destroys more signal than replacing one
    assert out["g_all"]["delta"] >= out["g_slot0"]["delta"] - 1e-6
    assert out["g_all"]["delta"] > 0.01
    # dataset block restored
    m2 = None
    table.begin_pass(ds.unique_keys())
    m2 = trainer.evaluate(ds, table)
    table.end_pass()
    assert m2["auc"] == pytest.approx(out["baseline"]["auc"], abs=1e-9)
    ds.close()


def test_dump_fields_and_params(tmp_path):
    conf, ds, trainer, table = _world(tmp_path)
    ds.load_into_memory()
    trainer.conf.need_dump_field = True
    trainer.conf.need_dump_param = True
    trainer.conf.dump_fields = ("dense",)
    trainer.conf.dump_fields_path = str(tmp_path / "dump")
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()
    files = sorted(os.listdir(tmp_path / "dump"))
    dump_txt = [f for f in files if f.startswith("dump-")]
    assert dump_txt
    lines = open(tmp_path / "dump" / dump_txt[0]).read().splitlines()
    assert len(lines) == ds.get_memory_data_size()
    cols = lines[0].split("\t")
    assert cols[1] in ("0", "1")  # label
    assert 0.0 <= float(cols[2]) <= 1.0  # pred
    assert cols[3].startswith("dense:")
    assert any(f.startswith("param-") and f.endswith(".dense.npz") for f in files)
    ds.close()


def test_input_table_and_replica_cache():
    import jax.numpy as jnp

    from paddlebox_tpu.sparse.aux_tables import InputTable, ReplicaCache

    t = InputTable(dim=3)
    i1 = t.add_row("ad-1", [1.0, 2.0, 3.0])
    i2 = t.add_row("ad-2", [4.0, 5.0, 6.0])
    assert (i1, i2) == (1, 2)
    idx = t.lookup_idx(["ad-2", "missing", "ad-1"])
    np.testing.assert_array_equal(idx, [2, 0, 1])
    rows = t.lookup_rows(["ad-2", "missing"])
    np.testing.assert_allclose(rows, [[4, 5, 6], [0, 0, 0]])
    # device gather path
    dev = np.asarray(jnp.take(t.rows_device(), jnp.asarray(idx), axis=0))
    np.testing.assert_allclose(dev, [[4, 5, 6], [0, 0, 0], [1, 2, 3]])
    # state roundtrip
    t2 = InputTable(dim=3)
    t2.load_state_dict(t.state_dict())
    np.testing.assert_array_equal(t2.lookup_idx(["ad-1", "ad-2"]), [1, 2])

    cache = ReplicaCache(np.array([[1.0, 1.0], [2.0, 2.0]]))
    out = np.asarray(cache.pull(np.array([1, 2, 0, 99])))
    np.testing.assert_allclose(out, [[1, 1], [2, 2], [0, 0], [0, 0]])


def test_disk_spill_roundtrip(tmp_path):
    conf, ds, trainer, table = _world(tmp_path)
    # memory path reference result
    ds.load_into_memory()
    mem_keys = ds.unique_keys()
    mem_ins = ds.get_memory_data_size()
    mem_batches = [b.keys[: b.n_keys].copy() for b in ds.batches()]
    ds.release_memory()

    ds.preload_into_disk(str(tmp_path / "spill"))
    ds.wait_preload_done()
    assert ds.get_memory_data_size() == mem_ins
    np.testing.assert_array_equal(ds.unique_keys(), mem_keys)
    disk_batches = [b.keys[: b.n_keys].copy() for b in ds.batches()]
    assert len(disk_batches) == len(mem_batches)
    for a, b in zip(disk_batches, mem_batches):
        np.testing.assert_array_equal(a, b)
    # trains from disk
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    assert m["steps"] == len(disk_batches)
    spill_files = list((tmp_path / "spill").glob("*.bin"))
    assert spill_files
    ds.release_memory()
    assert not list((tmp_path / "spill").glob("*.bin"))
    ds.close()


def test_profiler_report(tmp_path):
    _, ds, trainer, table = _world(tmp_path)
    ds.load_into_memory()
    trainer.conf.profile = True
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    prof = m["profile"]
    assert prof["steps"] == m["steps"]
    for stage in ("plan", "feed", "step"):
        assert prof[f"{stage}_sec"] >= 0.0
        assert f"{stage}_ms_per_step" in prof
    assert prof["step_sec"] > 0.0
    # the pass's own delta, not the process's total; ``step`` is the
    # enqueue and ``complete`` the device's side, one sample a dispatch
    assert prof["step_count"] == prof["plan_count"] == m["steps"]
    assert prof["complete_count"] == m["steps"]
    assert prof["complete_sec"] > 0.0
    ds.close()


def test_disk_spill_bounded_memory(tmp_path):
    """Streaming spill keeps at most read_threads parsed blocks in flight;
    batches stream back identical to the memory path."""
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files

    S, DENSE, B = 3, 2, 8
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=8,
    )
    files = write_synth_files(
        str(tmp_path), n_files=12, ins_per_file=20, n_sparse_slots=S,
        vocab_per_slot=50, dense_dim=DENSE, seed=11,
    )
    k = 2
    ds = PadBoxSlotDataset(conf, read_threads=k)
    ds.set_filelist(files)
    ds.load_into_memory()
    mem = [b.keys[: b.n_keys].copy() for b in ds.batches()]
    mem_keys = ds.unique_keys()
    ds.release_memory()

    ds.preload_into_disk(str(tmp_path / "spill"))
    ds.wait_preload_done()
    # bounded high-water mark: never more than k parsed blocks resident
    assert 1 <= ds.spill_peak_inflight <= k
    # one archive per input file, streamed incrementally
    assert len(list((tmp_path / "spill").glob("*.bin"))) == len(files)
    np.testing.assert_array_equal(ds.unique_keys(), mem_keys)
    disk = [b.keys[: b.n_keys].copy() for b in ds.batches()]
    assert len(disk) == len(mem)
    for a, b in zip(disk, mem):
        np.testing.assert_array_equal(a, b)
    ds.release_memory()
    ds.close()
