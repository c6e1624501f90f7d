"""Multi-chip correctness on the 8-device virtual CPU mesh (SURVEY.md §4
tier 3 — the TPU analog of the reference's localhost-subprocess distributed
tests, test_dist_base.py:642: distributed loss must equal local loss)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models.ctr_dnn import CtrDnn
from paddlebox_tpu.parallel import (
    MultiChipTrainer,
    ShardedSparseTable,
    make_mesh,
)
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N_DEV, "conftest must force 8 CPU devices"
    return make_mesh(N_DEV)


def _make_data(tmp_path, n_ins, batch_size, **kw):
    conf = make_synth_config(
        n_sparse_slots=3, dense_dim=2, batch_size=batch_size,
        max_feasigns_per_ins=16, **kw,
    )
    files = write_synth_files(
        str(tmp_path), n_files=2, ins_per_file=n_ins // 2,
        n_sparse_slots=3, vocab_per_slot=50, dense_dim=2, seed=7,
    )
    ds = PadBoxSlotDataset(conf, read_threads=2)
    ds.set_filelist(files)
    ds.load_into_memory()
    return conf, ds


# --------------------------------------------------------------------------- #
# Sharded table unit behavior
# --------------------------------------------------------------------------- #
class TestShardedTable:
    def test_begin_pass_shards_by_mod(self, mesh):
        tconf = SparseTableConfig(embedding_dim=4)
        table = ShardedSparseTable(tconf, mesh, seed=0)
        keys = np.arange(1, 100, dtype=np.uint64)
        table.begin_pass(keys)
        assert table.values.shape[0] == N_DEV
        for o, sk in enumerate(table._shard_keys):
            assert (sk % np.uint64(N_DEV) == o).all()
        assert sum(len(sk) for sk in table._shard_keys) == 99
        table.end_pass()
        assert table.n_features == 99

    def test_roundtrip_preserves_rows(self, mesh):
        tconf = SparseTableConfig(embedding_dim=4, initial_range=0.1)
        table = ShardedSparseTable(tconf, mesh, seed=0)
        keys = np.array([3, 11, 19, 27, 64, 123], dtype=np.uint64)
        table.begin_pass(keys)
        table.end_pass()
        st = table.state_dict()
        # second pass must resolve the same rows back
        table.begin_pass(keys)
        vals = np.asarray(table.values)
        for o, sk in enumerate(table._shard_keys):
            for i, k in enumerate(sk):
                row_in_store = st["values"][np.searchsorted(st["keys"], k)]
                np.testing.assert_allclose(
                    vals[o, i], row_in_store[:-1], rtol=1e-6
                )
        table.end_pass()

    def test_plan_routes_to_owner(self, mesh):
        tconf = SparseTableConfig(embedding_dim=4)
        table = ShardedSparseTable(tconf, mesh, seed=0, bucket_slack=8.0)
        keys = np.arange(1, 65, dtype=np.uint64)
        table.begin_pass(keys)
        from paddlebox_tpu.data.feed import HostBatch

        K = 16
        batches = []
        for d in range(N_DEV):
            kb = np.zeros(K, dtype=np.uint64)
            kb[:4] = [d * 4 + 1, d * 4 + 2, d * 4 + 3, d * 4 + 4]
            batches.append(HostBatch(
                keys=kb, key_segments=np.zeros(K, np.int32), n_keys=4,
                dense=np.zeros((2, 1), np.float32), labels=np.zeros(2, np.float32),
                ins_mask=np.ones(2, np.float32), batch_size=2, n_sparse_slots=2,
            ))
        plan = table.plan_group(batches)
        assert plan.n_missing == 0 and plan.n_overflow == 0
        for d in range(N_DEV):
            for k in batches[d].keys[:4]:
                o = int(k % N_DEV)
                sk = table._shard_keys[o]
                row = int(np.searchsorted(sk, k))
                # shard o must serve that row to requester d, and the dedup
                # map must point the pair at it
                assert row in plan.serve_rows[o, d], (d, k, o)
                assert row in plan.serve_uniq[o], (d, k, o)
        # single-chip plan entry points must be refused on the sharded table
        with pytest.raises(TypeError):
            table.plan_batch(batches[0])
        table.end_pass()

    def test_skewed_group_bumps_capacity_no_drops(self, mesh):
        """A group whose keys all hash to ONE shard must grow the a2a
        bucket (power-of-two bump), not silently drop keys.  Every key must resolve to its
        owner's row."""
        from paddlebox_tpu.data.feed import HostBatch

        tconf = SparseTableConfig(embedding_dim=4)
        # tight slack -> base bucket C = K*1.0/8 shards rounded to 8
        table = ShardedSparseTable(tconf, mesh, seed=0, bucket_slack=1.0)
        K = 64
        # all keys ≡ 0 mod 8: every key owned by shard 0 (worst skew)
        keys = np.arange(1, K + 1, dtype=np.uint64) * np.uint64(N_DEV)
        table.begin_pass(keys)
        base_C = table.bucket_capacity(K)
        assert base_C < K  # the skewed batch cannot fit the base bucket
        batches = []
        for d in range(N_DEV):
            kb = np.zeros(K, dtype=np.uint64)
            kb[:] = keys  # every device asks shard 0 for ALL K keys
            batches.append(HostBatch(
                keys=kb, key_segments=np.zeros(K, np.int32), n_keys=K,
                dense=np.zeros((2, 1), np.float32),
                labels=np.zeros(2, np.float32),
                ins_mask=np.ones(2, np.float32), batch_size=2,
                n_sparse_slots=2,
            ))
        plan = table.plan_group(batches)
        assert plan.n_overflow == 0, "no key may ever be dropped"
        assert table.capacity_bumps == 1
        C = plan.serve_rows.shape[2]
        assert C >= K and C % base_C == 0  # power-of-two bump over base
        # every key's row is actually served by shard 0 to every requester
        sk = table._shard_keys[0]
        for d in range(N_DEV):
            for k in keys:
                row = int(np.searchsorted(sk, k))
                assert row in plan.serve_rows[0, d]
        # occ routes each occurrence into shard 0's bucket (never the sink)
        assert (plan.occ_flat < N_DEV * C).all()
        assert (plan.occ_flat // C == 0).all()
        table.end_pass()


class TestMultiChipPrefetch:
    def test_prefetch_matches_serial(self, mesh, tmp_path):
        """The background plan+stack+H2D producer must be a pure overlap:
        bitwise-identical metrics to the serial path."""
        tconf = SparseTableConfig(embedding_dim=8)

        def run(prefetch, sub):
            conf, ds = _make_data(tmp_path / sub, 256, 8)
            model = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(16,))
            tr = MultiChipTrainer(
                model, tconf, mesh,
                TrainerConfig(auc_buckets=1 << 10,
                              prefetch_batches=prefetch),
                seed=1,
            )
            table = ShardedSparseTable(tconf, mesh, seed=2)
            table.begin_pass(ds.unique_keys())
            m = tr.train_from_dataset(ds, table)
            table.end_pass()
            sd = table.state_dict()
            ds.close()
            return m, sd

        m0, sd0 = run(0, "serial")
        m2, sd2 = run(2, "prefetch")
        assert m0["steps"] == m2["steps"] > 0
        assert m0["loss"] == pytest.approx(m2["loss"], rel=1e-6)
        assert m0["auc"] == pytest.approx(m2["auc"], rel=1e-6)
        np.testing.assert_array_equal(sd0["keys"], sd2["keys"])
        np.testing.assert_allclose(sd0["values"], sd2["values"], rtol=1e-6)


# --------------------------------------------------------------------------- #
# The tier-3 gate: multi-chip == single-chip
# --------------------------------------------------------------------------- #
class TestMultiChipEqualsSingleChip:
    def test_loss_and_table_match(self, mesh, tmp_path):
        n_ins = 256
        B = 16  # per-device batch; single-chip uses B * N_DEV
        tconf = SparseTableConfig(embedding_dim=8, learning_rate=0.05)
        trconf = TrainerConfig(dense_lr=1e-3, sync_dense_mode="step",
                               auc_buckets=1 << 12)

        # ---- single chip on the concatenated global batch ----
        conf1, ds1 = _make_data(tmp_path / "a", n_ins, B * N_DEV)
        model1 = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(32, 16))
        t1 = Trainer(model1, tconf, trconf, seed=3)
        table1 = SparseTable(tconf, seed=5)
        table1.begin_pass(ds1.unique_keys())
        m1 = t1.train_from_dataset(ds1, table1)
        table1.end_pass()

        # ---- multi chip: same instances split into per-device batches ----
        conf8, ds8 = _make_data(tmp_path / "b", n_ins, B)
        model8 = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(32, 16))
        t8 = MultiChipTrainer(model8, tconf, mesh, trconf, seed=3)
        table8 = ShardedSparseTable(tconf, mesh, seed=5, bucket_slack=float(N_DEV))
        table8.begin_pass(ds8.unique_keys())
        m8 = t8.train_from_dataset(ds8, table8)
        table8.end_pass()

        assert m8["steps"] * N_DEV == m1["steps"] * N_DEV  # same data volume
        # losses are means over the same instances -> must match closely
        assert abs(m1["loss"] - m8["loss"]) < 2e-4, (m1["loss"], m8["loss"])
        assert abs(m1["auc"] - m8["auc"]) < 5e-3, (m1["auc"], m8["auc"])
        assert m1["count"] == m8["count"] == n_ins

        # ---- the sparse tables must agree feature-by-feature ----
        s1, s8 = table1.state_dict(), table8.state_dict()
        np.testing.assert_array_equal(s1["keys"], s8["keys"])
        np.testing.assert_allclose(s1["values"], s8["values"], atol=2e-4)

    def test_kstep_sync_runs_and_learns(self, mesh, tmp_path):
        tconf = SparseTableConfig(
            embedding_dim=8, learning_rate=0.5, initial_range=0.05
        )
        trconf = TrainerConfig(sync_dense_mode="kstep", sync_weight_step=4,
                               dense_lr=3e-3, auc_buckets=1 << 12)
        conf, ds = _make_data(tmp_path / "k", 512, 16)
        model = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(32, 16))
        tr = MultiChipTrainer(model, tconf, mesh, trconf, seed=0)
        table = ShardedSparseTable(tconf, mesh, seed=0)
        results = []
        for _ in range(4):
            table.begin_pass(ds.unique_keys())
            results.append(tr.train_from_dataset(ds, table))
            table.end_pass()
        assert results[-1]["loss"] < results[0]["loss"]
        assert results[-1]["auc"] > 0.6
        # after a sync step the replicas must be identical
        p = jax.tree.leaves(tr.params)[0]
        np.testing.assert_allclose(np.asarray(p)[0], np.asarray(p)[-1], rtol=1e-6)

    def test_dump_fields_multichip(self, mesh, tmp_path):
        """Per-instance field dumping on the mesh (reference: DumpField in
        the production multi-GPU workers, device_worker.cc): every real
        instance dumps exactly once, ragged-tail pad batches dump nothing,
        line format matches the single-chip dumper."""
        import os

        tconf = SparseTableConfig(embedding_dim=4)
        trconf = TrainerConfig(
            auc_buckets=1 << 10, need_dump_field=True,
            dump_fields=("dense",), dump_fields_path=str(tmp_path / "dump"),
        )
        conf, ds = _make_data(tmp_path / "d", 150, 16)  # ragged tail
        model = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(16,))
        tr = MultiChipTrainer(model, tconf, mesh, trconf, seed=0)
        table = ShardedSparseTable(tconf, mesh, seed=0)
        table.begin_pass(ds.unique_keys())
        m = tr.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()
        assert m["count"] == 150
        files = [f for f in os.listdir(tmp_path / "dump")
                 if f.startswith("dump-")]
        assert len(files) == 1  # single-process: one file
        lines = open(tmp_path / "dump" / files[0]).read().splitlines()
        assert len(lines) == 150
        cols = lines[0].split("\t")
        assert cols[1] in ("0", "1")  # label
        assert 0.0 <= float(cols[2]) <= 1.0  # pred (sigmoid)
        assert cols[3].startswith("dense:")

    def test_ragged_tail_padding(self, mesh, tmp_path):
        """Instance count not divisible by n_dev * B: padded empty batches
        must contribute nothing."""
        tconf = SparseTableConfig(embedding_dim=4)
        trconf = TrainerConfig(auc_buckets=1 << 10)
        conf, ds = _make_data(tmp_path / "r", 150, 16)  # 150 = 9 batches + tail
        model = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(16,))
        tr = MultiChipTrainer(model, tconf, mesh, trconf, seed=0)
        table = ShardedSparseTable(tconf, mesh, seed=0)
        table.begin_pass(ds.unique_keys())
        m = tr.train_from_dataset(ds, table)
        table.end_pass()
        assert m["count"] == 150


def test_multichip_multitask_metrics_evaluate(tmp_path):
    """Multi-chip parity for the single-chip feature set: MMoE multi-task
    loss + per-task AUC, cmatch/rank metric groups, forward-only evaluate."""
    import jax

    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.metrics import MetricGroup, MetricSpec
    from paddlebox_tpu.models import MMoE
    from paddlebox_tpu.parallel import MultiChipTrainer, ShardedSparseTable, make_mesh

    n_dev = min(4, len(jax.devices()))
    mesh = make_mesh(n_dev)
    S, DENSE, B = 3, 2, 16
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=16, n_task_labels=1, parse_logkey=True,
    )
    files = write_synth_files(
        str(tmp_path), n_files=2, ins_per_file=B * n_dev * 2, n_sparse_slots=S,
        vocab_per_slot=40, dense_dim=DENSE, seed=4, n_task_labels=1,
        with_logkey=True,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()

    tconf = SparseTableConfig(embedding_dim=4)
    group = MetricGroup(
        [MetricSpec("all"), MetricSpec("cm222", cmatch_values=(222,))],
        n_buckets=1 << 10,
    )
    model = MMoE(S, tconf.row_width, dense_dim=DENSE, n_tasks=2, n_experts=2,
                 expert_hidden=(8,), expert_dim=4, tower_hidden=(4,))
    trainer = MultiChipTrainer(
        model, tconf, mesh, TrainerConfig(auc_buckets=1 << 10),
        metric_group=group,
    )
    table = ShardedSparseTable(tconf, mesh, seed=0)
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    assert np.isfinite(m["loss"])
    assert "task1/auc" in m and m["task1/count"] == m["count"]
    assert m["all/count"] == m["count"]
    assert 0 < m["cm222/count"] < m["all/count"]
    # forward-only evaluation inside the same pass
    ev = trainer.evaluate(ds, table)
    assert ev["count"] == ds.get_memory_data_size()
    table.end_pass()
    ds.close()
