"""Kill-and-resume determinism for the AutoCheckpointer."""

import numpy as np

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train import AutoCheckpointer, Trainer

S, DENSE, B = 3, 2, 16
N_PASSES = 4


def _world(tmp_path, seed=0):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=8,
    )
    files = write_synth_files(
        str(tmp_path / "data"), n_files=2, ins_per_file=64, n_sparse_slots=S,
        vocab_per_slot=60, dense_dim=DENSE, seed=9,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=4)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    table = SparseTable(tconf, seed=seed)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10), seed=seed)
    return ds, table, trainer


def _run_passes(ds, table, trainer, lo, hi, acp=None, mstate=None):
    m = None
    for p in range(lo, hi):
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(ds, table, auc_state=mstate)
        table.end_pass()
        mstate = trainer.last_metric_state
        if acp is not None:
            acp.after_pass(p, table, trainer, metric_state=mstate)
    return m, mstate


def test_kill_and_resume_reproduces_uninterrupted_metrics(tmp_path):
    # --- uninterrupted reference run ---
    ds, table, trainer = _world(tmp_path)
    ref, _ = _run_passes(ds, table, trainer, 0, N_PASSES)
    ref_state = table.state_dict()

    # --- run A: passes 0..1 with auto-checkpoint, then "die" ---
    ds2, table_a, trainer_a = _world(tmp_path)
    acp_a = AutoCheckpointer(str(tmp_path / "acp"), job_id="job1")
    _run_passes(ds2, table_a, trainer_a, 0, 2, acp=acp_a)
    del table_a, trainer_a, acp_a  # the "kill"

    # --- run B: fresh objects, resume, replay passes 2..3 ---
    ds3, table_b, trainer_b = _world(tmp_path)
    acp_b = AutoCheckpointer(str(tmp_path / "acp"), job_id="job1")
    status, mstate = acp_b.resume(
        table_b, trainer_b, metric_template=trainer_b._init_mstate()
    )
    assert status is not None and status["next_pass"] == 2
    got, _ = _run_passes(
        ds3, table_b, trainer_b, status["next_pass"], N_PASSES,
        acp=acp_b, mstate=mstate,
    )

    # metrics and table state match the uninterrupted run exactly
    assert got["count"] == ref["count"]
    np.testing.assert_allclose(got["auc"], ref["auc"], atol=1e-6)
    np.testing.assert_allclose(got["loss"], ref["loss"], rtol=1e-5)
    got_state = table_b.state_dict()
    ia = np.argsort(ref_state["keys"])
    ib = np.argsort(got_state["keys"])
    np.testing.assert_array_equal(
        ref_state["keys"][ia], got_state["keys"][ib]
    )
    np.testing.assert_allclose(
        ref_state["values"][ia], got_state["values"][ib], rtol=1e-5, atol=1e-6
    )
    for d in (ds, ds2, ds3):
        d.close()


def test_fresh_job_resume_is_none(tmp_path):
    ds, table, trainer = _world(tmp_path)
    acp = AutoCheckpointer(str(tmp_path / "acp"), job_id="nope")
    status, mstate = acp.resume(table, trainer)
    assert status is None and mstate is None
    ds.close()


def test_crash_between_checkpoint_and_status_rereuns_pass(tmp_path):
    """A checkpoint without its status line must be invisible to resume:
    the pass re-runs rather than being skipped (write order guarantees
    at-least-once pass execution)."""
    ds, table, trainer = _world(tmp_path)
    acp = AutoCheckpointer(str(tmp_path / "acp"), job_id="job2")
    _run_passes(ds, table, trainer, 0, 1, acp=acp)
    # simulate the crash: checkpoint for pass 1 lands, status write doesn't
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()
    acp.ckpt.save_delta("job2-p000001", table, *trainer.dense_state())
    # (no status update)

    ds2, table_b, trainer_b = _world(tmp_path)
    acp_b = AutoCheckpointer(str(tmp_path / "acp"), job_id="job2")
    status, _ = acp_b.resume(table_b, trainer_b)
    assert status["next_pass"] == 1  # pass 1 will re-run
    ds.close()
    ds2.close()
