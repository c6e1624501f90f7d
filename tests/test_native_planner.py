"""Native C++ batch planner (_native/plan_resolve.cpp): exact parity with
the numpy plan_keys path on every output, including scratch-row layout,
missing keys, duplicates, and padding."""

import numpy as np
import pytest

from paddlebox_tpu._native import build_census_index
from paddlebox_tpu.config import SparseTableConfig, flags
from paddlebox_tpu.sparse.table import SparseTable

native_available = build_census_index(np.arange(4, dtype=np.uint64)) is not None
pytestmark = pytest.mark.skipif(
    not native_available, reason="native planner did not build"
)


def _plans(pass_keys, keys, n_real, conf=None):
    """(native plan, numpy plan) for identical inputs through the REAL
    SparseTable.plan_keys — flag-flipped, so the test also pins that the
    flag routes."""
    conf = conf or SparseTableConfig(embedding_dim=4, plan_scratch_rows=64)
    plans = {}
    for native in (True, False):
        flags.set("use_native_planner", native)
        try:
            t = SparseTable(conf, seed=0)
            t.begin_pass(pass_keys)
            plans[native] = (t.plan_keys(keys, n_real), t.missing_key_count)
            t.end_pass()
        finally:
            flags.set("use_native_planner", True)
    return plans[True], plans[False]


def _assert_equal(a, b):
    """Order-insensitive plan equivalence: the native planner numbers
    unique slots in first-seen order (numpy: sorted order), so compare
    the training-visible quantities — idx (order-free), mask, missing
    counts — and the per-occurrence PUSH TARGET uniq_idx[inverse[occ]],
    which must agree wherever it aims at a live row (scratch targets
    differ by slot numbering; their deltas are zero or discarded)."""
    plan_a, miss_a = a
    plan_b, miss_b = b
    np.testing.assert_array_equal(plan_a.idx, plan_b.idx)
    np.testing.assert_array_equal(plan_a.key_mask, plan_b.key_mask)
    assert plan_a.n_missing == plan_b.n_missing
    assert miss_a == miss_b
    # per-occurrence push target: for occurrences whose key is IN the
    # census, the target is the pull row (order-free, must match exactly);
    # missing-key occurrences aim at scratch rows whose numbering is
    # slot-order-dependent — assert both sides agree on WHICH occurrences
    # those are, and that their targets are valid scratch/dead rows
    tgt_a = plan_a.uniq_idx[plan_a.inverse]
    tgt_b = plan_b.uniq_idx[plan_b.inverse]
    found_a = (plan_a.idx == tgt_a) & (plan_a.key_mask > 0)
    found_b = (plan_b.idx == tgt_b) & (plan_b.key_mask > 0)
    np.testing.assert_array_equal(found_a, found_b)
    np.testing.assert_array_equal(tgt_a[found_a], plan_b.idx[found_b])
    # occurrences sharing a key must share a slot (both planners)
    for plan in (plan_a, plan_b):
        real = plan.key_mask > 0
        inv = plan.inverse[real]
        assert len(set(zip(inv.tolist(), plan.idx[real].tolist()))) == \
            len(set(inv.tolist()))


def test_parity_random_batches():
    rng = np.random.default_rng(0)
    pass_keys = np.unique(rng.integers(1, 1 << 40, 5000).astype(np.uint64))
    for trial in range(5):
        K = int(rng.integers(64, 512))
        n_real = int(rng.integers(0, K + 1))
        keys = np.zeros(K, np.uint64)
        # mix of census keys (with duplicates) and unseen keys
        n_hit = n_real * 3 // 4
        keys[:n_hit] = rng.choice(pass_keys, n_hit)
        keys[n_hit:n_real] = rng.integers(1 << 41, 1 << 42,
                                          n_real - n_hit).astype(np.uint64)
        _assert_equal(*_plans(pass_keys, keys, n_real))


def test_parity_edge_cases():
    pass_keys = np.array([5, 9, 12, 700], dtype=np.uint64)
    K = 16
    # all-padding batch
    _assert_equal(*_plans(pass_keys, np.zeros(K, np.uint64), 0))
    # every key the same (heavy duplication)
    keys = np.full(K, 9, np.uint64)
    _assert_equal(*_plans(pass_keys, keys, K))
    # keys below/above the whole census (boundary searches)
    keys = np.array([1, 1, 900, 900, 5, 700] + [0] * 10, np.uint64)
    _assert_equal(*_plans(pass_keys, keys, 6))


def test_parity_under_provisioned_scratch():
    """Scratch clamping (the dead-row fallback) must match bit-for-bit."""
    conf = SparseTableConfig(embedding_dim=4, plan_scratch_rows=2)
    pass_keys = np.arange(1, 900, dtype=np.uint64)
    rng = np.random.default_rng(1)
    K = 256
    keys = np.zeros(K, np.uint64)
    keys[:100] = rng.choice(pass_keys, 100)
    _assert_equal(*_plans(pass_keys, keys, 100, conf=conf))


def _bucket_case(K, n_hit, n_miss, n_real):
    """(census, K-slot key buffer): n_real occurrences drawn with duplicates
    from n_hit census keys and n_miss keys the census lacks, then padding."""
    rng = np.random.default_rng(n_real)
    pass_keys = np.arange(1000, 9000, dtype=np.uint64)
    pool = np.concatenate([
        rng.choice(pass_keys, n_hit, replace=False),
        np.arange(1 << 40, (1 << 40) + n_miss, dtype=np.uint64)])
    keys = np.zeros(K, np.uint64)
    keys[:n_real] = np.concatenate(
        [pool, rng.choice(pool, n_real - pool.shape[0])
         if n_real else pool])[rng.permutation(n_real)]
    return pass_keys, keys


@pytest.mark.parametrize("n_hit,n_miss,n_real", [
    (500, 60, 3000),   # duplicates, census-missing keys and padding
    (819, 0, 1500),    # 1.25 x 819 + 1 = 1,024: the headroom just fits
    (816, 4, 1024),    # one key more: a fresh table takes the next bucket
    (0, 0, 0),         # all padding
])
def test_parity_bucketed_unique_side(n_hit, n_miss, n_real):
    """Both planners emit the unique side at the same bucket U_b < K, count
    the same distinct keys, and park the padding at slot U_b - 1."""
    K = 4096
    pass_keys, keys = _bucket_case(K, n_hit, n_miss, n_real)
    native, numpy_ = _plans(pass_keys, keys, n_real, conf=SparseTableConfig(
        embedding_dim=4, plan_scratch_rows=K))
    _assert_equal(native, numpy_)
    n_uniq = n_hit + n_miss
    want = 1024 if n_uniq + n_uniq // 4 + 1 <= 1024 else 2048
    for plan, _ in (native, numpy_):
        assert plan.uniq_idx.shape[0] == want < K
        assert plan.n_uniq == n_uniq and plan.n_missing == n_miss
        assert (plan.inverse[:n_real] < n_uniq).all()
        assert (plan.inverse[n_real:] == want - 1).all()
        # targets pairwise distinct (the scratch region holds them all)
        assert np.unique(plan.uniq_idx).shape[0] == want
    # the slots past the keys are the same scratch rows on both sides
    np.testing.assert_array_equal(native[0].uniq_idx[n_uniq:],
                                  numpy_[0].uniq_idx[n_uniq:])


@pytest.mark.parametrize("n_hit,n_miss,n_real,L", [
    (500, 60, 3000, 4096),    # duplicates, census-missing keys and padding
    (900, 0, 3855, 4096),     # 3855 + 240 = 4,095: the headroom just fits
    (2000, 50, 3857, 5120),   # 3857 + 241 = 4,098: the next step of 1,024
    (0, 0, 0, 1024),          # all padding
    (700, 0, 16384, 16384),   # a full buffer (the decoder's shape): L == K
])
def test_parity_bucketed_occurrence_side(n_hit, n_miss, n_real, L):
    """Both planners resolve the buffer's first L slots (L <= K, the
    occurrence bucket): the same idx and mask, the same push target an
    occurrence, padding in [n, L) at the dead row / slot U_b - 1 / mask 0."""
    K = 16384
    pass_keys, keys = _bucket_case(K, n_hit, n_miss, n_real)
    native, numpy_ = _plans(pass_keys, keys, n_real, conf=SparseTableConfig(
        embedding_dim=4, plan_scratch_rows=K))
    _assert_equal(native, numpy_)
    for plan, _ in (native, numpy_):
        U = plan.uniq_idx.shape[0]
        assert U <= L
        for a in (plan.idx, plan.inverse, plan.key_mask):
            assert a.shape == (L,)
        assert (plan.key_mask[:n_real] == 1).all()
        assert (plan.key_mask[n_real:] == 0).all()
        assert (plan.inverse[n_real:] == U - 1).all()
        # one row past the census and its scratch region: the dead row
        assert (plan.idx[n_real:] >= pass_keys.shape[0] + K).all()
        assert np.unique(plan.idx[n_real:]).shape[0] <= 1
        assert plan.n_uniq == n_hit + n_miss and plan.n_missing == n_miss


def test_e2e_training_same_result(tmp_path):
    """One real training pass, native vs numpy planner: identical loss and
    table state (the planner feeds the jitted step, so full-step parity is
    the end-to-end proof)."""
    from paddlebox_tpu.config import TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.train.trainer import Trainer

    conf = make_synth_config(n_sparse_slots=3, dense_dim=2, batch_size=32,
                             max_feasigns_per_ins=8)
    files = write_synth_files(str(tmp_path), n_files=1, ins_per_file=128,
                              n_sparse_slots=3, vocab_per_slot=40,
                              dense_dim=2, seed=3)

    def run(native):
        flags.set("use_native_planner", native)
        try:
            ds = PadBoxSlotDataset(conf, read_threads=1)
            ds.set_filelist(files)
            ds.load_into_memory()
            tconf = SparseTableConfig(embedding_dim=4)
            model = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(8,))
            table = SparseTable(tconf, seed=0)
            trainer = Trainer(model, tconf,
                              TrainerConfig(auc_buckets=1 << 10), seed=0)
            table.begin_pass(ds.unique_keys())
            m = trainer.train_from_dataset(ds, table)
            table.end_pass()
            state = table.state_dict()
            ds.close()
            return m, state
        finally:
            flags.set("use_native_planner", True)

    m1, s1 = run(True)
    m2, s2 = run(False)
    assert m1["loss"] == m2["loss"]
    np.testing.assert_array_equal(s1["keys"], s2["keys"])
    np.testing.assert_array_equal(s1["values"], s2["values"])


def test_sharded_plan_native_matches_numpy(tmp_path):
    """Sharded plan_group, native vs numpy: one multi-chip training pass
    must produce identical metrics and table state (the sharded analog of
    the single-chip e2e equality above)."""
    import jax

    from paddlebox_tpu.config import TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.parallel.sharded_table import ShardedSparseTable
    from paddlebox_tpu.parallel.trainer import MultiChipTrainer

    conf = make_synth_config(n_sparse_slots=3, dense_dim=2, batch_size=16,
                             max_feasigns_per_ins=8)
    files = write_synth_files(str(tmp_path), n_files=1, ins_per_file=256,
                              n_sparse_slots=3, vocab_per_slot=40,
                              dense_dim=2, seed=6)

    def run(native):
        flags.set("use_native_planner", native)
        try:
            ds = PadBoxSlotDataset(conf, read_threads=1)
            ds.set_filelist(files)
            ds.load_into_memory()
            mesh = make_mesh(4)
            tconf = SparseTableConfig(embedding_dim=4)
            model = CtrDnn(3, tconf.row_width, dense_dim=2, hidden=(8,))
            table = ShardedSparseTable(tconf, mesh, seed=0)
            trainer = MultiChipTrainer(
                model, tconf, mesh, TrainerConfig(auc_buckets=1 << 10),
                seed=0,
            )
            table.begin_pass(ds.unique_keys())
            m = trainer.train_from_dataset(ds, table)
            table.end_pass()
            state = table.state_dict()
            ds.close()
            return m, state
        finally:
            flags.set("use_native_planner", True)

    m1, s1 = run(True)
    m2, s2 = run(False)
    assert m1["loss"] == m2["loss"]
    assert m1["auc"] == m2["auc"]
    np.testing.assert_array_equal(s1["keys"], s2["keys"])
    np.testing.assert_array_equal(s1["values"], s2["values"])


# --------------------------------------------------------------------------- #
# The row cache's directory resolve rides the planner's library (ISSUE 49)
# --------------------------------------------------------------------------- #
@pytest.fixture
def fresh_plan_lib():
    """``get_plan_lib`` loads anew inside the test; the process's own
    library is put back after it."""
    from paddlebox_tpu import _native

    saved = (_native._plan_lib, _native._plan_tried)
    _native._plan_lib, _native._plan_tried = None, False
    yield _native
    _native._plan_lib, _native._plan_tried = saved


@pytest.mark.parametrize("symbol", ["pbx_cache_lookup", "pbx_cache_touch"])
def test_require_native_fails_on_a_library_without_the_resolve(
        fresh_plan_lib, monkeypatch, symbol):
    """A loaded library that lacks a directory entry point is no planner
    library: a run that must not degrade stops, it does not fall to the
    numpy form at every boundary."""
    import ctypes

    _native = fresh_plan_lib
    load = ctypes.CDLL

    class Without:
        """The real library with one symbol taken out."""

        def __init__(self, path):
            self._lib = load(path)

        def __getattr__(self, name):
            if name == symbol:
                raise AttributeError(f"undefined symbol: {name}")
            return getattr(self._lib, name)

    monkeypatch.setattr(_native.ctypes, "CDLL", Without)
    assert _native.get_plan_lib() is None
    with pytest.raises(RuntimeError, match="planner did not build"):
        _native.require_native()


def test_require_native_loads_the_resolve(fresh_plan_lib):
    loaded = fresh_plan_lib.require_native()
    assert loaded["planner"]
    lib = fresh_plan_lib.get_plan_lib()
    assert lib.pbx_cache_lookup and lib.pbx_cache_touch


def test_begin_pass_resolves_its_census_natively_once():
    """On a cached table every ``begin_pass`` is one native lookup, timed
    by the span it always had."""
    from paddlebox_tpu import telemetry

    def read():
        snap = telemetry.registry.snapshot()
        return (snap["counters"].get("cache.lookups{form=native}", 0.0),
                snap["counters"].get("cache.lookups{form=numpy}", 0.0),
                snap["histograms"].get("pass.stage_seconds{stage=lookup}",
                                       {"count": 0})["count"])

    t = SparseTable(
        SparseTableConfig(embedding_dim=4, plan_scratch_rows=64,
                          hbm_cache_rows=1 << 10, placement="hash"), seed=0)
    t.begin_pass(np.arange(1, 401, dtype=np.uint64))
    t.end_pass()
    for census in (np.arange(1, 51), np.arange(30, 600, 7)):
        native, fallback, spans = read()
        t.begin_pass(census.astype(np.uint64))
        native1, fallback1, spans1 = read()
        assert native1 - native == 1.0
        assert fallback1 == fallback
        assert spans1 - spans == 1
        t.end_pass()
    t.flush()
