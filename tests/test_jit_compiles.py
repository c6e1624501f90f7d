"""Runtime retrace witness (telemetry/compiles.py): per-stage
``jit.compiles`` attribution, and the steady-state ZERO-retrace pins —
after warmup, a training pass (both trainer paths) and a serving
predict must trigger no XLA compile at all.  A moving per-stage count
is the silent regression the ``jit-retrace-hazard`` static pass exists
to catch; these pins witness it at runtime."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models.ctr_dnn import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.telemetry import compiles
from paddlebox_tpu.train.trainer import Trainer

S, DENSE = 3, 2


def _counts() -> dict:
    return compiles.compiles_by_stage()


def _delta(before: dict, after: dict) -> dict:
    """Per-stage compile-count movement, zero entries dropped."""
    out = {}
    for stage, n in after.items():
        d = n - before.get(stage, 0)
        if d:
            out[stage] = d
    return out


# --------------------------------------------------------------------------- #
# witness units
# --------------------------------------------------------------------------- #
def test_counted_jit_counts_per_stage_and_stops_when_cached():
    f = compiles.counted_jit(lambda x: x * 3, stage="unit.counted")
    before = _counts().get("unit.counted", 0)
    f(jnp.ones(3))
    warm = _counts().get("unit.counted", 0)
    assert warm > before, "warmup compile must land on the stage label"
    assert f.retraces() == 1
    f(jnp.ones(3))
    assert _counts().get("unit.counted", 0) == warm, \
        "a cached dispatch must not move jit.compiles"
    f(jnp.ones(5))  # new shape: a real retrace
    assert _counts().get("unit.counted", 0) > warm
    assert f.retraces() == 2


def test_counted_jit_decorator_form_and_static_args():
    @compiles.counted_jit(stage="unit.deco", static_argnames=("flag",))
    def g(x, flag=False):
        return -x if flag else x

    out = g(jnp.ones(2), flag=True)
    assert np.asarray(out)[0] == -1.0
    assert _counts().get("unit.deco", 0) >= 1
    # attribute passthrough: the wrapper still looks like the jitted fn
    assert hasattr(g, "lower")


def test_stage_scope_innermost_wins():
    with compiles.stage_scope("outer"):
        with compiles.stage_scope("inner.scope"):
            jax.jit(lambda x: x + 7)(jnp.ones(4))
    assert _counts().get("inner.scope", 0) >= 1
    assert compiles.current_stage() == compiles.UNTAGGED


def test_listener_install_is_idempotent():
    assert compiles.install_compile_listener()
    assert compiles.install_compile_listener()
    before = _counts().get("unit.idem", 0)
    with compiles.stage_scope("unit.idem"):
        jax.jit(lambda x: x * 11)(jnp.ones(6))
    # exactly one registration: one compile is not double-counted
    assert _counts().get("unit.idem", 0) - before <= 2


# --------------------------------------------------------------------------- #
# steady-state pins
# --------------------------------------------------------------------------- #
def _make_data(td, n_ins=64, batch_size=8):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=batch_size,
        max_feasigns_per_ins=16,
    )
    files = write_synth_files(
        str(td), n_files=1, ins_per_file=n_ins, n_sparse_slots=S,
        vocab_per_slot=50, dense_dim=DENSE, seed=11,
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(files)
    ds.load_into_memory()
    return conf, ds


def test_steady_state_zero_retrace_single_chip_trainer(tmp_path):
    """After warmup, every pass over same-shape feeds is dispatch-only —
    across EVERY stage, untagged pass-boundary ops included.  Warmup is
    TWO passes: pass 1 compiles the step, pass 2 recompiles it once when
    the table capacity shrinks from the cold-census default to the
    fitted size (and the HBM cache transitions cold->warm); from pass 3
    on, zero compiles.  This is the tier-1 pin for the single-chip path."""
    conf, ds = _make_data(tmp_path)
    tconf = SparseTableConfig(embedding_dim=8)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(
        model, tconf, TrainerConfig(auc_buckets=1 << 10), seed=0)
    keys = ds.unique_keys()

    for _ in range(2):  # warmup: compile + capacity-fit recompile
        table.begin_pass(keys)
        trainer.train_from_dataset(ds, table)
        table.end_pass()

    before = _counts()
    for _ in range(2):  # steady state
        table.begin_pass(keys)
        trainer.train_from_dataset(ds, table)
        table.end_pass()
    moved = _delta(before, _counts())
    ds.close()
    assert not moved, (
        f"steady-state single-chip passes recompiled: {moved} — a "
        "shape-varying feed or fresh jit wrapper crept into the loop"
    )


def test_steady_state_zero_retrace_multichip_trainer(tmp_path):
    """The SPMD path's pin: shard_mapped step/sync stages stay cached
    across steady-state passes on the 8-device mesh.  Warmup is THREE
    passes: the first two as on one chip, and pass 3 is where a key first
    reaches the planner's enter frequency (1, 1.8, 2.44 >= 2.0), so the
    first hot set is promoted out of the per-shard caches there — one
    padded gather per shard device, compiled once for the table's life."""
    from paddlebox_tpu.parallel import (
        MultiChipTrainer,
        ShardedSparseTable,
        make_mesh,
    )

    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    mesh = make_mesh(8)
    conf, ds = _make_data(tmp_path, n_ins=128, batch_size=8)
    tconf = SparseTableConfig(embedding_dim=8, learning_rate=0.05)
    trconf = TrainerConfig(dense_lr=1e-3, sync_dense_mode="step",
                           auc_buckets=1 << 10)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    trainer = MultiChipTrainer(model, tconf, mesh, trconf, seed=3)
    table = ShardedSparseTable(tconf, mesh, seed=5, bucket_slack=8.0)
    keys = ds.unique_keys()

    for _ in range(3):  # warmup: compile, capacity fit, first promotion
        table.begin_pass(keys)
        trainer.train_from_dataset(ds, table)
        table.end_pass()
    assert table.hot_resident_keys().shape[0], "no hot set was realized"

    before = _counts()
    table.begin_pass(keys)
    trainer.train_from_dataset(ds, table)
    table.end_pass()
    moved = _delta(before, _counts())
    ds.close()
    assert not moved, (
        f"steady-state SPMD pass recompiled: {moved} — the padded-bucket "
        "shape discipline or the cached step wrapper broke"
    )


def test_steady_state_zero_retrace_serving_predictor(tmp_path):
    """The serving pin: after the exported bucket program's first call,
    every same-bucket predict is dispatch-only (the micro-batching fast
    path's latency floor depends on it)."""
    import os

    from paddlebox_tpu.inference import Predictor, export_model

    conf, ds = _make_data(tmp_path / "data")
    tconf = SparseTableConfig(embedding_dim=8)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(
        model, tconf, TrainerConfig(auc_buckets=1 << 10), seed=0)
    table.begin_pass(ds.unique_keys())
    trainer.train_from_dataset(ds, table)
    table.end_pass()

    art = str(tmp_path / "artifact")
    kcap = conf.batch_key_capacity or (8 * conf.max_feasigns_per_ins)
    export_model(model, trainer.params, table, art,
                 batch_size=8, key_capacity=kcap, dense_dim=DENSE)
    assert os.path.exists(os.path.join(art, "meta.json"))

    pred = Predictor.load(art)
    batches = list(ds.batches(drop_last=False))
    pred.predict(batches[0])  # warmup: deserialization + first compile
    warm = _counts()
    assert warm.get("serve.predict", 0) >= 1, \
        "warmup compile must be attributed to serve.predict"

    for b in batches[:4] + batches[:4]:  # steady state, same bucket
        pred.predict(b)
    moved = _delta(warm, _counts())
    ds.close()
    assert not moved, (
        f"steady-state serving predict recompiled: {moved} — the bucket "
        "ladder stopped absorbing shape variance"
    )
