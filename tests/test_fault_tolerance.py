"""Integration tests for the fault-tolerance layer: bad-input quarantine,
checkpoint integrity + fallback resume, donefile-last publish discipline
under injected failures, and the trainer's NaN policies."""

import json
import os

import jax
import numpy as np
import pytest

from paddlebox_tpu.checkpoint import (
    CheckpointCorrupt,
    CheckpointManager,
    verify_checkpoint_dir,
)
from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train import (
    AutoCheckpointer,
    PassRolledBack,
    Trainer,
)
from paddlebox_tpu.utils import faults
from paddlebox_tpu.utils.faults import fault_plan
from paddlebox_tpu.utils.fs import FsError, publish_checkpoint
from paddlebox_tpu.utils.monitor import stats

S, DENSE, B = 3, 2, 16


@pytest.fixture(autouse=True)
def _fast_and_clean(monkeypatch):
    """Fast retries, no leftover plans/stats between tests."""
    monkeypatch.setenv("PBOX_RETRY_BASE_DELAY_S", "0.001")
    monkeypatch.setenv("PBOX_RETRY_MAX_DELAY_S", "0.002")
    stats.reset()
    faults.clear()
    yield
    faults.clear()


def _world(tmp_path, seed=0, n_files=2, trainer_conf=None, sub="data"):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=8,
    )
    files = write_synth_files(
        str(tmp_path / sub), n_files=n_files, ins_per_file=64,
        n_sparse_slots=S, vocab_per_slot=60, dense_dim=DENSE, seed=9,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=4)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    table = SparseTable(tconf, seed=seed)
    trainer = Trainer(
        model, tconf,
        trainer_conf or TrainerConfig(auc_buckets=1 << 10),
        seed=seed,
    )
    return ds, table, trainer


def _run_pass(ds, table, trainer):
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    return m


# --------------------------------------------------------------------------- #
# bad-input quarantine
# --------------------------------------------------------------------------- #
class TestQuarantine:
    def _conf_files(self, tmp_path, policy, frac=0.5, n_bad=2):
        conf = make_synth_config(
            n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
            malformed_policy=policy, quarantine_abort_frac=frac,
        )
        files = write_synth_files(
            str(tmp_path / "q"), n_files=2, ins_per_file=32,
            n_sparse_slots=S, dense_dim=DENSE, seed=4,
        )
        # corruption appended at the END of the last file: quarantining it
        # restores the clean instance stream byte-for-byte
        with open(files[-1], "a") as fh:
            for i in range(n_bad):
                fh.write("garbage line %d\n" % i if i % 2 else "1\n")
        return conf, files

    def test_skip_policy_restores_clean_stream(self, tmp_path):
        conf, files = self._conf_files(tmp_path, "skip")
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        assert ds.get_memory_data_size() == 64  # the 2 bad lines are gone
        assert ds.parser.quarantined_lines == 2
        assert ds.parser.quarantined_files == 1
        snap = stats.snapshot()
        assert snap["data.quarantined_lines"] == 2
        assert snap["data.quarantined_files"] == 1
        # block content identical to a clean parse
        clean_conf = make_synth_config(
            n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        )
        clean = PadBoxSlotDataset(clean_conf, read_threads=1)
        clean_files = write_synth_files(
            str(tmp_path / "qc"), n_files=2, ins_per_file=32,
            n_sparse_slots=S, dense_dim=DENSE, seed=4,
        )
        clean.set_filelist(clean_files)
        clean.load_into_memory()
        np.testing.assert_array_equal(ds._block.keys, clean._block.keys)
        np.testing.assert_array_equal(ds._block.labels, clean._block.labels)
        ds.close()
        clean.close()

    def test_raise_policy_aborts(self, tmp_path):
        conf, files = self._conf_files(tmp_path, "raise")
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        with pytest.raises(ValueError, match="malformed"):
            ds.load_into_memory()
        ds.close()

    def test_abort_threshold(self, tmp_path):
        # 8 bad lines over 64 good = 11% > 10% threshold -> the load fails
        conf, files = self._conf_files(tmp_path, "skip", frac=0.10, n_bad=8)
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        with pytest.raises(RuntimeError, match="quarantined"):
            ds.load_into_memory()
        assert stats.get("data.quarantine_aborts") == 1
        ds.close()

    def test_mid_line_corruption_rolls_back_partial_appends(self, tmp_path):
        """A line that fails mid-instance (after appending some keys) must
        not leak its partial keys into the block."""
        conf, files = self._conf_files(tmp_path, "skip", n_bad=0)
        # valid label + first slot, then garbage where slot1's count should be
        with open(files[0], "a") as fh:
            fh.write("1 1 2 5 7 nope\n")
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        assert ds.get_memory_data_size() == 64
        assert ds.parser.quarantined_lines == 1
        # offsets stay consistent: total keys == last offset
        assert ds._block.keys.shape[0] == ds._block.key_offsets[-1]
        ds.close()


# --------------------------------------------------------------------------- #
# data-read retry
# --------------------------------------------------------------------------- #
def test_transient_read_failure_is_retried(tmp_path):
    conf = make_synth_config(n_sparse_slots=S, dense_dim=DENSE, batch_size=B)
    files = write_synth_files(
        str(tmp_path / "d"), n_files=2, ins_per_file=32,
        n_sparse_slots=S, dense_dim=DENSE,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    with fault_plan({"data.read": "first:1"}):
        ds.load_into_memory()  # first read fails, retry succeeds
    assert ds.get_memory_data_size() == 64
    assert stats.get("faults.injected.data.read") == 1
    assert stats.get("retry.data.read.retries") >= 1
    ds.close()


def test_parse_errors_never_retry(tmp_path):
    conf = make_synth_config(n_sparse_slots=S, dense_dim=DENSE, batch_size=B)
    bad = tmp_path / "bad.txt"
    bad.write_text("definitely not slot format\n")
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist([str(bad)])
    with pytest.raises(ValueError):
        ds.load_into_memory()
    assert stats.get("retry.data.read.retries") == 0
    ds.close()


# --------------------------------------------------------------------------- #
# checkpoint integrity
# --------------------------------------------------------------------------- #
def _saved_manager(tmp_path, n_passes=1):
    ds, table, trainer = _world(tmp_path)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    for p in range(n_passes):
        _run_pass(ds, table, trainer)
        save = mgr.save_base if p == 0 else mgr.save_delta
        save(f"t{p}", table, *trainer.dense_state())
    ds.close()
    return mgr, table, trainer


class TestCheckpointIntegrity:
    def test_manifest_written_and_verifies(self, tmp_path):
        mgr, _, _ = _saved_manager(tmp_path)
        d = mgr.list_checkpoints()[0].dirname
        manifest = json.load(open(os.path.join(d, "manifest.json")))
        assert set(manifest["files"]) >= {"sparse.npz", "meta.json"}
        verify_checkpoint_dir(d)  # no raise

    def test_truncated_file_detected(self, tmp_path):
        mgr, _, _ = _saved_manager(tmp_path)
        d = mgr.list_checkpoints()[0].dirname
        path = os.path.join(d, "sparse.npz")
        data = open(path, "rb").read()
        open(path, "wb").write(data[: len(data) // 2])
        with pytest.raises(CheckpointCorrupt, match="size"):
            verify_checkpoint_dir(d)

    def test_bitflip_detected(self, tmp_path):
        mgr, _, _ = _saved_manager(tmp_path)
        d = mgr.list_checkpoints()[0].dirname
        path = os.path.join(d, "dense.npz")
        data = bytearray(open(path, "rb").read())
        data[len(data) // 2] ^= 0xFF
        open(path, "wb").write(bytes(data))
        with pytest.raises(CheckpointCorrupt, match="sha256"):
            verify_checkpoint_dir(d)

    def test_load_refuses_corrupt_chain(self, tmp_path):
        mgr, table, trainer = _saved_manager(tmp_path)
        d = mgr.list_checkpoints()[0].dirname
        os.remove(os.path.join(d, "sparse.npz"))
        t2 = SparseTable(SparseTableConfig(embedding_dim=4), seed=0)
        with pytest.raises(CheckpointCorrupt):
            mgr.load(t2)

    def test_find_valid_tag_walks_back(self, tmp_path):
        mgr, _, _ = _saved_manager(tmp_path, n_passes=3)
        assert mgr.find_valid_tag() == "t2"
        d2 = [c for c in mgr.list_checkpoints() if c.tag == "t2"][0].dirname
        path = os.path.join(d2, "sparse.npz")
        data = open(path, "rb").read()
        open(path, "wb").write(data[: len(data) // 2])
        assert mgr.find_valid_tag() == "t1"
        # corrupting the base kills every chain
        d0 = [c for c in mgr.list_checkpoints() if c.tag == "t0"][0].dirname
        os.remove(os.path.join(d0, "sparse.npz"))
        assert mgr.find_valid_tag() is None


# --------------------------------------------------------------------------- #
# publish: donefile-last discipline under injected failures (satellite)
# --------------------------------------------------------------------------- #
class TestPublish:
    def test_failed_upload_never_exposes_donefile(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PBOX_RETRY_MAX_ATTEMPTS", "2")
        mgr, _, _ = _saved_manager(tmp_path)
        remote = str(tmp_path / "pub")
        with fault_plan({"publish.upload": "first:10"}):
            # retries exhaust and the last failure (the injected one)
            # propagates
            with pytest.raises((FsError, faults.FaultInjected)):
                publish_checkpoint(mgr, "t0", remote)
        # the remote donefile must not exist: consumers see NO tag rather
        # than a tag whose data may be partial
        assert not os.path.exists(os.path.join(remote, "donefile.txt"))

    def test_transient_failure_retries_to_completion(self, tmp_path):
        mgr, _, _ = _saved_manager(tmp_path)
        remote = str(tmp_path / "pub2")
        with fault_plan(
            {"publish.upload": "first:1", "publish.donefile": "first:1"}
        ):
            publish_checkpoint(mgr, "t0", remote)
        assert os.path.exists(os.path.join(remote, "donefile.txt"))
        lines = open(os.path.join(remote, "donefile.txt")).read()
        assert '"tag": "t0"' in lines
        # the published copy verifies against its manifest
        verify_checkpoint_dir(os.path.join(remote, "base-t0"))
        assert stats.get("faults.injected.publish.upload") == 1
        assert stats.get("retry.publish.upload.retries") >= 1

    def test_corrupt_remote_copy_fails_before_donefile(
        self, tmp_path, monkeypatch
    ):
        """Post-upload verification: if the remote bytes are wrong, publish
        fails BEFORE the donefile lands."""
        from paddlebox_tpu.utils.fs import LocalFS

        monkeypatch.setenv("PBOX_RETRY_MAX_ATTEMPTS", "1")
        mgr, _, _ = _saved_manager(tmp_path)
        remote = str(tmp_path / "pub3")

        class CorruptingFS(LocalFS):
            def upload(self, local, dest):
                super().upload(local, dest)
                if os.path.isdir(dest):  # truncate one uploaded file
                    p = os.path.join(dest, "sparse.npz")
                    data = open(p, "rb").read()
                    open(p, "wb").write(data[:10])

        with pytest.raises(CheckpointCorrupt):
            publish_checkpoint(mgr, "t0", remote, fs=CorruptingFS())
        assert not os.path.exists(os.path.join(remote, "donefile.txt"))


# --------------------------------------------------------------------------- #
# corrupt-checkpoint fallback resume (satellite)
# --------------------------------------------------------------------------- #
def test_resume_falls_back_to_previous_valid_tag(tmp_path):
    """Truncate the newest checkpoint; resume must recover from the
    previous tag and the replay must reproduce the uninterrupted run."""
    N = 4
    # uninterrupted reference
    ds, table, trainer = _world(tmp_path)
    for _ in range(N):
        ref = _run_pass(ds, table, trainer)
    ref_state = table.state_dict()
    ds.close()

    # run A: passes 0..2 checkpointed, then "die"
    ds2, table_a, trainer_a = _world(tmp_path)
    acp_a = AutoCheckpointer(str(tmp_path / "acp"), job_id="jf")
    for p in range(3):
        _run_pass(ds2, table_a, trainer_a)
        acp_a.after_pass(p, table_a, trainer_a)
    ds2.close()

    # truncate the newest checkpoint's sparse payload
    newest = acp_a.ckpt.list_checkpoints()[-1]
    assert newest.tag == "jf-p000002"
    path = os.path.join(newest.dirname, "sparse.npz")
    data = open(path, "rb").read()
    open(path, "wb").write(data[: len(data) // 2])

    # run B: fresh objects; resume falls back to pass 1's tag
    ds3, table_b, trainer_b = _world(tmp_path)
    acp_b = AutoCheckpointer(str(tmp_path / "acp"), job_id="jf")
    status, mstate = acp_b.resume(table_b, trainer_b)
    assert status["fallback"] is True
    assert status["tag"] == "jf-p000001"
    assert status["next_pass"] == 2
    assert mstate is None  # the snapshot belonged to the lost pass
    assert stats.get("ckpt.resume_fallback") == 1

    got = None
    for p in range(status["next_pass"], N):
        got = _run_pass(ds3, table_b, trainer_b)
        acp_b.after_pass(p, table_b, trainer_b)
    ds3.close()

    # replay reproduces the uninterrupted run exactly
    assert got["count"] == ref["count"]
    np.testing.assert_allclose(got["auc"], ref["auc"], atol=1e-6)
    np.testing.assert_allclose(got["loss"], ref["loss"], rtol=1e-5)
    got_state = table_b.state_dict()
    ia, ib = np.argsort(ref_state["keys"]), np.argsort(got_state["keys"])
    np.testing.assert_array_equal(
        ref_state["keys"][ia], got_state["keys"][ib]
    )
    np.testing.assert_allclose(
        ref_state["values"][ia], got_state["values"][ib], rtol=1e-5, atol=1e-6
    )


# --------------------------------------------------------------------------- #
# NaN policies
# --------------------------------------------------------------------------- #
class TestNanPolicy:
    def test_raise_policy(self, tmp_path):
        ds, table, trainer = _world(
            tmp_path, trainer_conf=TrainerConfig(
                auc_buckets=1 << 10, nan_policy="raise", check_nan_inf=True,
            ),
        )
        table.begin_pass(ds.unique_keys())
        with fault_plan({"train.nan": "first:1"}):
            with pytest.raises(FloatingPointError):
                trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()

    @pytest.mark.parametrize(
        "bad", [(1,), (1, 2), (7,)], ids=["one", "two_in_a_row", "last"])
    def test_skip_batch_discards_only_the_bad_batch(self, tmp_path, bad):
        """A skipped batch leaves nothing behind, wherever it falls in the
        pass: the run equals, bit for bit, the same program fed the pass
        without the poisoned batches."""
        conf = TrainerConfig(auc_buckets=1 << 10, nan_policy="skip_batch")
        ref_ds, ref_table, ref_trainer = _world(
            tmp_path, sub="c", trainer_conf=conf)
        ref_table.begin_pass(ref_ds.unique_keys())
        m_ref = ref_trainer.train_steps(
            ref_table,
            (b for i, b in enumerate(ref_ds.batches()) if i not in bad))
        ref_table.end_pass()
        ref_ds.close()

        ds, table, trainer = _world(tmp_path, sub="c", trainer_conf=conf)
        with fault_plan({"train.nan": "at:" + ",".join(map(str, bad))}):
            m = _run_pass(ds, table, trainer)
        ds.close()
        assert m["steps"] == m_ref["steps"] == 128 // B - len(bad)
        assert trainer.global_step == m["steps"]
        assert stats.get("train.nan_skipped_steps") == len(bad)
        assert stats.get("train.nan_skipped_ins") == len(bad) * B
        # skipped batches' instances are absent from the metrics
        assert m["count"] == m_ref["count"] == 128 - len(bad) * B
        assert m["loss"] == m_ref["loss"] and np.isfinite(m["loss"])
        assert m["auc"] == m_ref["auc"]
        got, want = table.state_dict(), ref_table.state_dict()
        np.testing.assert_array_equal(got["keys"], want["keys"])
        np.testing.assert_array_equal(got["values"], want["values"])
        for got, want in zip(jax.tree.leaves(trainer.params),
                             jax.tree.leaves(ref_trainer.params)):
            np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("policy", ["raise", "skip_batch"])
    def test_poisoned_batch_blast_radius(self, tmp_path, policy):
        """What one poisoned batch (the third of eight) leaves behind at
        one step a dispatch: the table holds live buffers again and
        end_pass() succeeds; rows of keys the batch does not touch equal
        the run of the clean batches that were dispatched; under
        ``raise`` no later batch is dispatched, under ``skip_batch`` not
        even the batch's own rows move."""
        from paddlebox_tpu import telemetry

        BAD = 2
        conf = TrainerConfig(
            auc_buckets=1 << 10, nan_policy=policy, check_nan_inf=True)
        ran = (0, 1) if policy == "raise" else tuple(
            i for i in range(128 // B) if i != BAD)
        ref_ds, ref_table, ref_trainer = _world(
            tmp_path, sub="b", trainer_conf=conf)
        batches = list(ref_ds.batches())
        bad_keys = batches[BAD].keys[: batches[BAD].n_keys]
        ref_table.begin_pass(ref_ds.unique_keys())
        ref_trainer.train_steps(ref_table, (batches[i] for i in ran))
        ref_table.end_pass()
        ref_ds.close()

        def dispatches():
            return telemetry.registry.snapshot()["counters"].get(
                "trainer.dispatches", 0)

        ds, table, trainer = _world(tmp_path, sub="b", trainer_conf=conf)
        table.begin_pass(ds.unique_keys())
        n0 = dispatches()
        with fault_plan({"train.nan": f"at:{BAD}"}):
            if policy == "raise":
                with pytest.raises(FloatingPointError):
                    trainer.train_from_dataset(ds, table)
            else:
                trainer.train_from_dataset(ds, table)
        assert dispatches() - n0 == (BAD + 1 if policy == "raise" else 8)
        assert trainer.global_step == len(ran)
        # the donated buffers were handed back: readable, and end_pass works
        assert np.asarray(table.values).shape[0] == np.asarray(
            table.g2sum).shape[0]
        table.end_pass()
        ds.close()
        got, want = table.state_dict(), ref_table.state_dict()
        np.testing.assert_array_equal(got["keys"], want["keys"])
        clean = ~np.isin(got["keys"], bad_keys)
        assert clean.any() and not clean.all()
        np.testing.assert_array_equal(
            got["values"][clean], want["values"][clean])
        if policy == "skip_batch":
            np.testing.assert_array_equal(got["values"], want["values"])

    def test_skip_batch_is_deterministic(self, tmp_path):
        runs = []
        for _ in range(2):
            faults.clear()
            ds, table, trainer = _world(
                tmp_path, sub="c2",
                trainer_conf=TrainerConfig(
                    auc_buckets=1 << 10, nan_policy="skip_batch",
                ),
            )
            with fault_plan({"train.nan": "at:1"}):
                runs.append(_run_pass(ds, table, trainer))
            ds.close()
        assert runs[0]["auc"] == runs[1]["auc"]
        assert runs[0]["loss"] == runs[1]["loss"]

    def test_rollback_restores_last_completed_pass(self, tmp_path):
        # uninterrupted 2-pass reference
        ds0, table0, trainer0 = _world(tmp_path, sub="r")
        _run_pass(ds0, table0, trainer0)
        ref = _run_pass(ds0, table0, trainer0)
        ref_state = table0.state_dict()
        ds0.close()

        ds, table, trainer = _world(
            tmp_path, sub="r",
            trainer_conf=TrainerConfig(
                auc_buckets=1 << 10, nan_policy="rollback",
            ),
        )
        acp = AutoCheckpointer(str(tmp_path / "acp_rb"), job_id="rb")
        trainer.checkpointer = acp
        _run_pass(ds, table, trainer)
        acp.after_pass(0, table, trainer)
        step_after_p0 = trainer.global_step

        # pass 1 hits a NaN batch -> rolled back to pass 0's checkpoint
        table.begin_pass(ds.unique_keys())
        with fault_plan({"train.nan": "first:1"}):
            with pytest.raises(PassRolledBack) as exc:
                trainer.train_from_dataset(ds, table)
        assert exc.value.status["next_pass"] == 1
        assert not table._in_pass  # pass was aborted, no end_pass needed
        assert trainer.global_step == step_after_p0
        assert stats.get("train.nan_rollback") == 1

        # re-run pass 1 clean: reproduces the uninterrupted run exactly
        got = _run_pass(ds, table, trainer)
        ds.close()
        np.testing.assert_allclose(got["auc"], ref["auc"], atol=1e-6)
        np.testing.assert_allclose(got["loss"], ref["loss"], rtol=1e-5)
        got_state = table.state_dict()
        ia = np.argsort(ref_state["keys"])
        ib = np.argsort(got_state["keys"])
        np.testing.assert_array_equal(
            ref_state["keys"][ia], got_state["keys"][ib]
        )
        np.testing.assert_allclose(
            ref_state["values"][ia], got_state["values"][ib],
            rtol=1e-5, atol=1e-6,
        )

    def test_rollback_without_checkpointer_raises(self, tmp_path):
        ds, table, trainer = _world(
            tmp_path, sub="r2",
            trainer_conf=TrainerConfig(
                auc_buckets=1 << 10, nan_policy="rollback",
            ),
        )
        table.begin_pass(ds.unique_keys())
        with fault_plan({"train.nan": "first:1"}):
            with pytest.raises(FloatingPointError):
                trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()

    def test_bad_policy_rejected(self):
        tconf = SparseTableConfig(embedding_dim=4)
        model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
        with pytest.raises(ValueError, match="nan_policy"):
            Trainer(model, tconf, TrainerConfig(nan_policy="ignore"))


# --------------------------------------------------------------------------- #
# satellites: spill-rm accounting, prefetch close timeout
# --------------------------------------------------------------------------- #
def test_spill_rm_failure_counted(tmp_path):
    from paddlebox_tpu.data.dataset import _DiskSpill

    conf = make_synth_config(n_sparse_slots=S, dense_dim=DENSE, batch_size=B)
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds._spill = _DiskSpill(
        paths=[str(tmp_path / "gone-1.bin"), str(tmp_path / "gone-2.bin")],
        unique_keys=np.empty(0, np.uint64), n_ins=0,
    )  # paths never existed -> both removals fail
    ds.release_memory()
    assert stats.get("dataset.spill_rm_failed") == 2
    assert ds._spill is None
    ds.close()


class TestServerErrorPaths:
    """Satellite: /healthz readiness + 400 (client) vs 500 (server) split.
    Uses a stubbed score_lines so no artifact/device work is involved —
    the classification mapping is what's under test."""

    def _server(self):
        from types import SimpleNamespace

        from paddlebox_tpu.inference.server import ScoringServer

        s = ScoringServer()
        entry = SimpleNamespace(  # enough for start() and /healthz
            requests=0, instances=0,
            predictor=SimpleNamespace(bucket_shapes=[], n_features=0),
        )
        s._models = {"m": entry}
        s._default = "m"
        port = s.start()
        return s, port

    def _post(self, port, path, body=b"x"):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("POST", path, body=body)
        r = conn.getresponse()
        out = (r.status, json.loads(r.read().decode()))
        conn.close()
        return out

    def _get(self, port, path):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("GET", path)
        r = conn.getresponse()
        out = (r.status, json.loads(r.read().decode()))
        conn.close()
        return out

    def test_malformed_payload_is_400(self):
        s, port = self._server()
        try:
            s.score_lines = lambda text, name=None: (_ for _ in ()).throw(
                ValueError("bad slot line")
            )
            code, body = self._post(port, "/score")
            assert code == 400
            assert "bad slot line" in body["error"]
        finally:
            s.stop()

    def test_internal_error_is_500(self):
        s, port = self._server()
        try:
            s.score_lines = lambda text, name=None: (_ for _ in ()).throw(
                RuntimeError("device fell over")
            )
            code, body = self._post(port, "/score")
            assert code == 500
            assert "device fell over" in body["error"]
        finally:
            s.stop()

    def test_unknown_model_is_404(self):
        s, port = self._server()
        try:
            s.score_lines = lambda text, name=None: (_ for _ in ()).throw(
                KeyError(name)
            )
            code, _ = self._post(port, "/score/nope")
            assert code == 404
        finally:
            s.stop()

    def test_healthz_readiness(self):
        s, port = self._server()
        try:
            code, body = self._get(port, "/healthz")
            assert code == 200 and body["ready"] is True
            s._models = {}  # models drained -> not ready
            code, body = self._get(port, "/healthz")
            assert code == 503 and body["ready"] is False
        finally:
            s.stop()


def test_prefetch_close_timeout_counted(monkeypatch):
    import threading

    from paddlebox_tpu.train import pass_loop as trainer_mod

    monkeypatch.setattr(trainer_mod, "_PREFETCH_JOIN_S", 0.05)
    release = threading.Event()

    def stuck_gen():
        release.wait()  # simulates planning/H2D stuck past close()
        yield 1

    pf = trainer_mod._FeedPrefetcher(stuck_gen(), depth=1)
    pf.close()
    assert stats.get("trainer.prefetch_close_timeout") == 1
    release.set()  # let the daemon thread exit
