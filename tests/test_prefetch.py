"""Double-buffered device feed: prefetch path must be bit-identical to the
serial path (same batches, same order — only overlap changes), and producer
exceptions must surface at the train loop."""

import numpy as np
import pytest

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer, _FeedPrefetcher

S, DENSE, B = 3, 2, 8


def _run(tmp_path, prefetch: int):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B, max_feasigns_per_ins=16
    )
    files = write_synth_files(
        str(tmp_path / f"d{prefetch}"), n_files=1, ins_per_file=96,
        n_sparse_slots=S, vocab_per_slot=60, dense_dim=DENSE, seed=2,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=8)
    trconf = TrainerConfig(auc_buckets=1 << 10, prefetch_batches=prefetch)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, trconf, seed=0)
    table.begin_pass(ds.unique_keys())
    metrics = trainer.train_from_dataset(ds, table)
    table.end_pass()
    ds.close()
    state = table.state_dict()
    return metrics, state["values"].copy()


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_prefetch_parity(tmp_path, depth):
    """Every queue depth runs the serial feed's batches in its order: depth
    1 hands over one feed at a time, 4 keeps the producer ahead of the
    whole 12-batch pass's first third."""
    m_serial, v_serial = _run(tmp_path, prefetch=0)
    m_pre, v_pre = _run(tmp_path, prefetch=depth)
    assert m_pre["steps"] == m_serial["steps"] == 12
    assert m_pre["loss"] == m_serial["loss"]
    assert m_pre["auc"] == m_serial["auc"]
    np.testing.assert_array_equal(v_pre, v_serial)


def test_producer_exception_propagates():
    def bad_gen():
        yield 1, {}
        raise ValueError("producer exploded")

    pf = _FeedPrefetcher(bad_gen(), depth=2)
    out = next(pf)
    assert out[0] == 1
    with pytest.raises(ValueError, match="producer exploded"):
        next(pf)
    pf.close()


def test_close_unblocks_full_queue():
    def slow_gen():
        for i in range(100):
            yield i

    pf = _FeedPrefetcher(slow_gen(), depth=1)
    next(pf)
    pf.close()  # producer blocked on a full queue must exit
    assert not pf._thread.is_alive()
