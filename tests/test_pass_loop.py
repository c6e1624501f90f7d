"""One pass protocol, two trainers (train/pass_loop.py): every test here
holds ``Trainer`` and ``MultiChipTrainer`` (four virtual devices, dense sync
every step) to the same ``run_pass`` — its stages and their order, its
teardown after a step that raised, its pass report, its batch checks."""

import threading

import pytest

from paddlebox_tpu.config import (
    LivenessConfig,
    SparseTableConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.parallel import (
    MultiChipTrainer,
    ShardedSparseTable,
    make_mesh,
    watchdog,
)
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train import pass_loop
from paddlebox_tpu.train.trainer import Trainer
from paddlebox_tpu.utils import faults
from paddlebox_tpu.utils.profiler import StatsProfiler

S, DENSE, B, N_DEV = 3, 2, 16, 4
KINDS = ["single", "sharded"]


def _world(kind, tmp_path, **trainer_kw):
    """(dataset, trainer, table): 12 batches of 16 — 12 single-chip steps,
    3 sharded ones."""
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=8,
    )
    files = write_synth_files(
        str(tmp_path / "data"), n_files=1, ins_per_file=12 * B,
        n_sparse_slots=S, vocab_per_slot=40, dense_dim=DENSE, seed=3,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=4)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
    trconf = TrainerConfig(
        auc_buckets=1 << 10, sync_dense_mode="step", **trainer_kw)
    if kind == "single":
        return ds, Trainer(model, tconf, trconf, seed=0), SparseTable(
            tconf, seed=0)
    mesh = make_mesh(N_DEV)
    return ds, MultiChipTrainer(model, tconf, mesh, trconf, seed=0), (
        ShardedSparseTable(tconf, mesh, seed=0))


def _producers() -> list:
    return [t for t in threading.enumerate() if t.name == "feed-prefetch"]


@pytest.mark.parametrize("kind", KINDS)
def test_one_pass_enters_every_stage_in_the_protocols_order(
        kind, tmp_path, monkeypatch):
    order = []
    real = StatsProfiler.stage

    def spy(self, name):
        if self.family == "trainer":
            order.append(name)
        return real(self, name)

    monkeypatch.setattr(StatsProfiler, "stage", spy)
    ds, trainer, table = _world(kind, tmp_path)
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    trainer.close()
    ds.close()
    assert {"open", "batch", "plan", "feed", "step", "drain", "readback",
            "observe"} <= set(order)
    steps = [i for i, s in enumerate(order) if s == "step"]
    assert len(steps) == m["steps"] == (12 if kind == "single" else 3)
    assert order.count("open") == 1 and order.index("open") < steps[0]
    tail = [s for s in order if s in ("drain", "readback", "observe")]
    assert tail == ["drain", "readback", "observe"]
    assert order.index("drain") > steps[-1]
    assert order[-3:] == tail  # nothing of the pass runs after them
    for key in ("steps", "samples", "duration_s", "loss", "grad_norm",
                "weight_norm"):
        assert key in m, key
    assert m["samples"] == 12 * B
    assert trainer._pass_idx == 1


@pytest.mark.parametrize("kind", KINDS)
def test_a_step_that_raises_leaves_the_table_live_and_nothing_running(
        kind, tmp_path, monkeypatch):
    made = []
    real = watchdog.for_trainer

    def spy(conf, namespace):
        made.append(real(conf, namespace))
        return made[-1]

    monkeypatch.setattr(watchdog, "for_trainer", spy)
    ds, trainer, table = _world(
        kind, tmp_path, liveness=LivenessConfig(deadline_s=600.0))
    table.begin_pass(ds.unique_keys())
    with faults.fault_plan({"train.step": "at:1"}):
        with pytest.raises(faults.FaultInjected):
            trainer.train_from_dataset(ds, table)
    assert trainer.global_step == 1  # the second dispatch never happened
    assert not _producers()
    (wd,) = made
    assert wd._thread is None and wd._stop.is_set()
    assert watchdog.current() is None
    # the step donated the pass's first buffers: the table holds live ones
    assert not table.values.is_deleted() and not table.g2sum.is_deleted()
    table.end_pass()
    # and the trainer runs the next pass whole
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    assert m["steps"] == (12 if kind == "single" else 3)
    trainer.close()
    ds.close()


@pytest.fixture(scope="module")
def single_chip_report(tmp_path_factory):
    ds, trainer, table = _world(
        "single", tmp_path_factory.mktemp("ref"), profile=True)
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    trainer.close()
    ds.close()
    return m["profile"]


@pytest.mark.parametrize("kind", KINDS)
def test_profile_reports_the_same_stages_for_both(
        kind, tmp_path, single_chip_report, capsys):
    ds, trainer, table = _world(kind, tmp_path, profile=True)
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    trainer.close()
    ds.close()
    report = m["profile"]
    assert set(report) == set(single_chip_report)
    assert report["steps"] == m["steps"]
    assert report["step_count"] == report["complete_count"] == m["steps"]
    assert set(report["stage_quantiles"]) == set(
        single_chip_report["stage_quantiles"])
    assert "[profile] steps=" in capsys.readouterr().out


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("needs,message", [
    ("uses_rank_offset", "PV-merged batches"),
    ("uses_seq_pos", "ordered behavior sequence"),
    ("n_tasks", "task label columns"),
])
def test_a_batch_the_model_cannot_use_is_refused_by_name(
        kind, needs, message, tmp_path):
    ds, trainer, table = _world(kind, tmp_path)
    if needs == "n_tasks":
        trainer.n_tasks = 2  # the feed configures no task label slots
    else:
        setattr(trainer.model, needs, True)
    table.begin_pass(ds.unique_keys())
    with pytest.raises(RuntimeError, match=message):
        trainer.train_from_dataset(ds, table)
    assert not _producers()
    if needs != "n_tasks":  # the forward-only loop checks these two
        with pytest.raises(RuntimeError, match=message):
            trainer.evaluate(ds, table)
    table.end_pass()
    trainer.close()
    ds.close()


def test_validate_batch_checks_only_what_it_is_asked(tmp_path):
    ds, _, _ = _world("single", tmp_path)
    batch = next(iter(ds.batches()))
    ds.close()
    pass_loop.validate_batch(batch, False, False, 1)
    for args, message in (
            ((True, False, 1), "PV-merged batches"),
            ((False, True, 1), "ordered behavior sequence"),
            ((False, False, 3), "carries 0 task label columns")):
        with pytest.raises(RuntimeError, match=message):
            pass_loop.validate_batch(batch, *args)
