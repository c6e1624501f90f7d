"""Pipeline parallelism over the REAL CTR tower (models/pipelined_ctr.py).

One model from models/ trains pipelined to parity:
PipelinedCtrDnn is CtrDnn's tower as GPipe stages, driven by the
unmodified Trainer with stage 0 consuming pooled sparse features.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.pipelined_ctr import PipelinedCtrDnn, _split_stages
from paddlebox_tpu.parallel.pipeline import PIPE_AXIS

N_SLOTS, DENSE, B = 3, 2, 64
HIDDEN = (48, 32, 16)
P_STAGES = 4


def _mesh():
    return Mesh(np.array(jax.devices()[:P_STAGES]), (PIPE_AXIS,))


def _models(tconf, microbatches=8):
    plain = CtrDnn(n_sparse_slots=N_SLOTS, emb_width=tconf.row_width,
                   dense_dim=DENSE, hidden=HIDDEN)
    piped = PipelinedCtrDnn(
        _mesh(), n_sparse_slots=N_SLOTS, emb_width=tconf.row_width,
        dense_dim=DENSE, hidden=HIDDEN, microbatches=microbatches,
    )
    return plain, piped


def test_split_stages():
    assert _split_stages(4, 4) == [[0], [1], [2], [3]]
    assert _split_stages(6, 4) == [[0, 1], [2, 3], [4], [5]]
    with pytest.raises(ValueError):
        _split_stages(3, 4)


def test_forward_parity_with_ctr_dnn():
    """Same init key -> pipelined logits == plain CtrDnn logits (padding
    and the schedule are exact, not approximate)."""
    tconf = SparseTableConfig(embedding_dim=8)
    plain, piped = _models(tconf)
    key = jax.random.PRNGKey(7)
    p_plain = plain.init(key)
    p_piped = piped.init(key)

    rng = np.random.default_rng(0)
    K = B * N_SLOTS
    rows = rng.normal(size=(K, tconf.row_width)).astype(np.float32)
    segs = np.repeat(np.arange(B) * N_SLOTS, N_SLOTS) + np.tile(
        np.arange(N_SLOTS), B
    )
    dense = rng.normal(size=(B, DENSE)).astype(np.float32)

    l_plain = np.asarray(plain.apply(p_plain, rows, segs, dense, B))
    l_piped = np.asarray(piped.apply(p_piped, rows, segs, dense, B))
    np.testing.assert_allclose(l_piped, l_plain, rtol=2e-5, atol=2e-5)


def test_pack_unpack_roundtrip():
    tconf = SparseTableConfig(embedding_dim=8)
    _, piped = _models(tconf)
    layers = [
        {"w": np.full((a, b), i + 1, np.float32), "b": np.arange(b, dtype=np.float32)}
        for i, (a, b) in enumerate(zip(piped.dims[:-1], piped.dims[1:]))
    ]
    packed = {"stages": piped.pack_tower(layers)}
    back = piped.unpack_tower(packed)
    for l0, l1 in zip(layers, back):
        np.testing.assert_array_equal(l0["w"], l1["w"])
        np.testing.assert_array_equal(l0["b"], l1["b"])


def test_trains_pipelined_to_parity(tmp_path):
    """The full gate: the same dataset trains CtrDnn and PipelinedCtrDnn
    (same seeds) to matching loss/AUC through the unmodified Trainer —
    sparse pull/push, metrics, prefetch included."""
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train import Trainer

    conf = make_synth_config(
        n_sparse_slots=N_SLOTS, dense_dim=DENSE, batch_size=B,
        batch_key_capacity=B * N_SLOTS * 4,
    )
    paths = write_synth_files(
        str(tmp_path), n_files=2, ins_per_file=2 * B, n_sparse_slots=N_SLOTS,
        vocab_per_slot=60, dense_dim=DENSE, seed=13,
    )
    tconf = SparseTableConfig(embedding_dim=8)

    def run(model):
        trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                          seed=0)
        table = SparseTable(tconf, seed=0)
        ds = PadBoxSlotDataset(conf)
        ds.set_filelist(paths)
        ds.load_into_memory()
        m = None
        for _ in range(2):
            table.begin_pass(ds.unique_keys())
            m = trainer.train_from_dataset(
                ds, table, auc_state=trainer.last_metric_state)
            table.end_pass()
        ds.close()
        return m, table.state_dict()

    plain, piped = _models(tconf)
    m1, sd1 = run(plain)
    m2, sd2 = run(piped)
    assert m2["loss"] == pytest.approx(m1["loss"], rel=1e-4)
    assert m2["auc"] == pytest.approx(m1["auc"], abs=1e-4)
    # the sparse tables saw identical gradients through both towers
    np.testing.assert_array_equal(sd1["keys"], sd2["keys"])
    np.testing.assert_allclose(sd1["values"], sd2["values"], rtol=1e-4,
                               atol=1e-6)


def test_batch_not_divisible_rejected():
    tconf = SparseTableConfig(embedding_dim=8)
    _, piped = _models(tconf, microbatches=7)
    rows = np.zeros((B * N_SLOTS, tconf.row_width), np.float32)
    segs = np.zeros(B * N_SLOTS, np.int32)
    dense = np.zeros((B, DENSE), np.float32)
    with pytest.raises(ValueError):
        piped.apply(piped.init(jax.random.PRNGKey(0)), rows, segs, dense, B)


def test_bf16_compute_dtype_honored():
    """TrainerConfig.compute_dtype must actually flip the pipelined tower
    to bf16 (not be silently dropped with a warning), and stay close to the
    bf16 CtrDnn, which shares the cast policy."""
    import warnings

    import jax.numpy as jnp

    from paddlebox_tpu.models.layers import apply_compute_dtype_override

    tconf = SparseTableConfig(embedding_dim=8)
    plain, piped = _models(tconf)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the no-attribute path warns
        apply_compute_dtype_override(plain, "bfloat16")
        apply_compute_dtype_override(piped, "bfloat16")
    assert piped.compute_dtype == jnp.bfloat16

    key = jax.random.PRNGKey(7)
    p_plain, p_piped = plain.init(key), piped.init(key)
    rng = np.random.default_rng(0)
    K = B * N_SLOTS
    rows = rng.normal(size=(K, tconf.row_width)).astype(np.float32)
    rows[:, :2] = np.abs(rows[:, :2])  # sane show/clk counters
    segs = np.repeat(np.arange(B) * N_SLOTS, N_SLOTS) + np.tile(
        np.arange(N_SLOTS), B
    )
    dense = rng.normal(size=(B, DENSE)).astype(np.float32)
    lp = np.asarray(plain.apply(p_plain, rows, segs, dense, B))
    lq = np.asarray(piped.apply(p_piped, rows, segs, dense, B))
    assert lq.dtype == np.float32
    np.testing.assert_allclose(lq, lp, rtol=2e-2, atol=2e-2)  # bf16 noise
