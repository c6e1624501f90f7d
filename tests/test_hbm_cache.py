"""Device-resident embedding engine correctness (ARCHITECTURE.md
"Device-resident embedding engine").

The acceptance bar (ISSUE 6): the cached lifecycle — persistent HBM
hot-key cache, miss-only promotion fetch, in-place hit update, LFU-with-
aging admission/eviction, dirty-row drain at barriers — must be BIT-exact
vs ``hbm_cache_rows=0`` over multiple passes with overlapping censuses on
BOTH trainer paths (keys, values, g2sum, AUC), including a checkpoint
save/restore and a shrink mid-run.  Plus: the begin-pass promotion patch
shrinks to the cold-key count, the chaos sites ``cache.fetch`` /
``cache.admit`` degrade without corrupting rows, and the cache telemetry
rides the per-pass ``pass_end`` JSONL record.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from paddlebox_tpu.config import (
    SparseTableConfig,
    TelemetryConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer
from paddlebox_tpu.utils import faults

N_SLOTS = 3
DENSE = 2
N_PASSES = 3


def _tconf(cache_rows: int, **kw) -> SparseTableConfig:
    # placement="hash": this suite pins the HBM-cache engine itself.  The
    # default (realized hybrid placement) would promote the tiny toy
    # census into the replicated hot block after a couple of passes,
    # leaving the cache no cold tail to exercise — the hybrid lifecycle
    # has its own suite (test_placement.py).
    kw.setdefault("placement", "hash")
    return SparseTableConfig(
        embedding_dim=4, learning_rate=0.4, initial_range=0.05,
        store_buckets=16, plan_scratch_rows=64, hbm_cache_rows=cache_rows,
        **kw,
    )


def _load_passes(tmp_path_factory, name: str, **synth_kw):
    conf = make_synth_config(
        n_sparse_slots=N_SLOTS, dense_dim=DENSE, batch_size=64,
        max_feasigns_per_ins=16,
    )
    datasets = []
    for p in range(N_PASSES):
        d = tmp_path_factory.mktemp(f"{name}{p}")
        files = write_synth_files(
            str(d), n_files=2, ins_per_file=192, n_sparse_slots=N_SLOTS,
            dense_dim=DENSE, seed=23 + p, **synth_kw,
        )
        ds = PadBoxSlotDataset(conf, read_threads=2)
        ds.set_filelist(files)
        ds.load_into_memory()
        datasets.append(ds)
    return conf, datasets


@pytest.fixture(scope="module")
def pass_datasets(tmp_path_factory):
    """N_PASSES loaded datasets over a SHARED key space (vocab 40: heavy
    census overlap, so steady-state passes have real cache hits)."""
    conf, datasets = _load_passes(
        tmp_path_factory, "cpass", vocab_per_slot=40)
    yield conf, datasets
    for ds in datasets:
        ds.close()


@pytest.fixture(scope="module")
def zipf_datasets(tmp_path_factory):
    """N_PASSES over a Zipf-skewed stream (a = 1.3 over 300 ids a slot): a
    hot head every pass sees and a cold tail that turns over, which is
    what the row cache is for."""
    conf, datasets = _load_passes(
        tmp_path_factory, "zpass", vocab_per_slot=300, zipf_a=1.3)
    yield conf, datasets
    for ds in datasets:
        ds.close()


def _run_single_chip(datasets, cache_rows: int, shrink_at: int = 1,
                     ckpt_at: int = 1):
    """Train N_PASSES with prepare_pass staging, a checkpoint snapshot +
    restore round-trip at ``ckpt_at`` and a shrink at ``shrink_at``."""
    tconf = _tconf(cache_rows, show_decay_rate=0.5)
    table = SparseTable(tconf, seed=3)
    model = CtrDnn(N_SLOTS, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    trainer = Trainer(
        model, tconf, TrainerConfig(dense_lr=3e-3, auc_buckets=1 << 12),
        seed=3,
    )
    auc_state = None
    metrics = None
    for p, ds in enumerate(datasets):
        table.begin_pass(ds.unique_keys())
        nxt = (
            datasets[p + 1].unique_keys if p + 1 < len(datasets) else None
        )
        metrics = trainer.train_from_dataset(
            ds, table, auc_state=auc_state, drop_last=True,
            next_pass_keys=nxt,
        )
        auc_state = trainer.last_metric_state
        table.end_pass()
        if p == ckpt_at:
            # checkpoint save/restore round-trip mid-run: the drained
            # state must be complete, and the restore must invalidate
            # whatever the cache held
            snap = table.state_dict()
            table.load_state_dict(snap)
        if p == shrink_at:
            table.shrink()
    sd = table.state_dict()
    delta = table.pop_delta()
    return sd, delta, metrics, table


def _assert_state_equal(a, b):
    assert np.array_equal(a["keys"], b["keys"])
    # values carry [show, clk, embed..., g2sum]: exact equality pins the
    # counters, the embeddings AND the optimizer state bit-for-bit
    assert np.array_equal(a["values"], b["values"])


class TestBitExact:
    # zipf: no checkpoint restore, no shrink — nothing empties the cache,
    # so the last pass reads its hot head from it
    @pytest.mark.parametrize("stream, kw", [
        ("pass_datasets", {}),
        ("zipf_datasets", {"shrink_at": -1, "ckpt_at": -1}),
    ], ids=["uniform", "zipf"])
    def test_single_chip_cached_matches_uncached(self, request, stream, kw):
        _, datasets = request.getfixturevalue(stream)
        sd_u, delta_u, m_u, _ = _run_single_chip(datasets, 0, **kw)
        sd_c, delta_c, m_c, table = _run_single_chip(datasets, 1 << 16, **kw)
        _assert_state_equal(sd_u, sd_c)
        _assert_state_equal(delta_u, delta_c)
        assert m_u["auc"] == m_c["auc"]
        assert m_u["loss"] == m_c["loss"]
        # the cache actually participated: post-shrink passes re-warm it
        assert table.last_cache_hits + table.last_cache_misses > 0
        if stream == "zipf_datasets":
            # the host supplied the cold tail only, not the census
            census = int(datasets[-1].unique_keys().shape[0])
            assert table.last_cache_hits > 0
            assert 0 < table.last_cache_misses < census
            assert table.last_cache_hits + table.last_cache_misses == census

    def test_single_chip_tiny_cache_eviction_churn(self, pass_datasets):
        # capacity far below the working set: admission + eviction every
        # pass, rows bouncing cache<->store — still bit-exact
        _, datasets = pass_datasets
        sd_u, delta_u, m_u, _ = _run_single_chip(datasets, 0)
        sd_c, delta_c, m_c, table = _run_single_chip(datasets, 8)
        _assert_state_equal(sd_u, sd_c)
        _assert_state_equal(delta_u, delta_c)
        assert m_u["auc"] == m_c["auc"]
        assert table._caches()[0].resident <= 8

    def test_multichip_cached_matches_uncached(self, pass_datasets):
        if len(jax.devices()) < 8:
            pytest.skip("needs the conftest 8-device CPU mesh")
        from paddlebox_tpu.parallel import (
            MultiChipTrainer,
            ShardedSparseTable,
            make_mesh,
        )

        _, datasets = pass_datasets

        def run(cache_rows):
            mesh = make_mesh(8)
            tconf = _tconf(cache_rows, show_decay_rate=0.5)
            table = ShardedSparseTable(tconf, mesh, seed=3)
            model = CtrDnn(
                N_SLOTS, tconf.row_width, dense_dim=DENSE, hidden=(16, 8)
            )
            trainer = MultiChipTrainer(
                model, tconf, mesh,
                TrainerConfig(dense_lr=3e-3, auc_buckets=1 << 12), seed=3,
            )
            metrics = None
            for p, ds in enumerate(datasets):
                table.begin_pass(ds.unique_keys())
                nxt = (
                    datasets[p + 1].unique_keys
                    if p + 1 < len(datasets) else None
                )
                metrics = trainer.train_from_dataset(
                    ds, table, drop_last=True, next_pass_keys=nxt,
                )
                table.end_pass()
                if p == 1:
                    snap = table.state_dict()
                    table.load_state_dict(snap)
                    table.shrink()
            return table.state_dict(), table.pop_delta(), metrics, table

        sd_u, delta_u, m_u, _ = run(0)
        sd_c, delta_c, m_c, table = run(1 << 16)
        _assert_state_equal(sd_u, sd_c)
        _assert_state_equal(delta_u, delta_c)
        assert m_u["auc"] == m_c["auc"]
        # the shrink at pass 1 invalidated the cache, so the FINAL pass is
        # an all-miss re-warm; the per-shard hit path itself is pinned by
        # TestCacheBehavior::test_sharded_hot_rows_skip_store
        assert table.last_cache_misses > 0


class TestCacheBehavior:
    def test_promotion_patch_shrinks_to_cold_keys(self):
        from paddlebox_tpu import telemetry

        t = SparseTable(_tconf(1 << 16), seed=0)
        keys = np.arange(1, 100, dtype=np.uint64)
        t.begin_pass(keys)
        assert t.last_cache_misses == 99 and t.last_cache_hits == 0
        t.values = t.values + 1.0
        t.end_pass()
        # same census again: everything is hot, the host supplies nothing
        t.begin_pass(keys)
        assert t.last_cache_hits == 99 and t.last_cache_misses == 0
        assert (np.asarray(t.values)[:99, 0] == 1.0).all()
        g = telemetry.registry.snapshot()["gauges"]
        assert g["cache.hit_rate"] == 1.0
        t.end_pass()
        # a half-new census fetches exactly the cold half
        keys2 = np.arange(50, 150, dtype=np.uint64)
        t.begin_pass(keys2)
        assert t.last_cache_hits == 50 and t.last_cache_misses == 50
        t.end_pass()
        t.flush()

    def test_hot_rows_skip_store_until_drain(self):
        """Hits never leave HBM: the store stays empty across passes and
        only the flush() barrier (drain) lands the rows."""
        t = SparseTable(_tconf(1 << 16), seed=0)
        keys = np.arange(1, 50, dtype=np.uint64)
        for p in range(3):
            t.begin_pass(keys)
            t.values = t.values + 1.0
            t.end_pass()
        assert t._store.n == 0  # nothing cold, nothing evicted
        assert t.n_features == 49  # the barrier drains the dirty rows
        vals, found = t._store.lookup(keys)
        assert found.all() and (vals[:, 0] == 3.0).all()

    def test_eviction_writes_rows_back(self):
        from paddlebox_tpu import telemetry

        before = telemetry.registry.snapshot()["counters"].get(
            "cache.evicted_rows", 0
        )
        t = SparseTable(_tconf(8), seed=0)
        a = np.arange(1, 9, dtype=np.uint64)
        b = np.arange(100, 108, dtype=np.uint64)
        t.begin_pass(a)
        t.values = t.values + 7.0
        t.end_pass()
        # disjoint census twice: a's aged-out rows must be evicted for b
        # and their values preserved through the store
        for _ in range(2):
            t.begin_pass(b)
            t.end_pass()
        t.flush()
        vals, found = t._store.lookup(a)
        assert found.all() and (vals[:, 0] == 7.0).all()
        after = telemetry.registry.snapshot()["counters"]["cache.evicted_rows"]
        assert after > before
        assert t._caches()[0].resident <= 8

    def test_sharded_hot_rows_skip_store(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs the conftest 8-device CPU mesh")
        from paddlebox_tpu.parallel import ShardedSparseTable, make_mesh

        t = ShardedSparseTable(_tconf(1 << 16), make_mesh(8), seed=0)
        keys = np.arange(1, 80, dtype=np.uint64)
        for _ in range(2):
            t.begin_pass(keys)
            t.values = t.values + 1.0
            t.end_pass()
        assert t.last_cache_hits == 79
        assert t._store.n == 0
        assert t.n_features == 79


class _DenseRule:
    """The eager form of the directory's policy, kept as the oracle of the
    age-on-read one: every pass multiplies ALL resident frequencies by
    ``aging``, then credits the census's hits."""

    def __init__(self, capacity: int, aging: float):
        self.aging = aging
        self.keys = np.zeros(capacity, np.uint64)
        self.used = np.zeros(capacity, bool)
        self.freq = np.zeros(capacity, np.float64)
        self.last_seen = np.full(capacity, -1, np.int64)
        self.tick = 0

    def observe(self, pk: np.ndarray) -> dict:
        """One census through lookup, touch, plan_update, commit_update."""
        slot_of = {int(k): s for s, k in enumerate(self.keys) if self.used[s]}
        hit = np.array([int(k) in slot_of for k in pk])
        hit_slots = np.array([slot_of[int(k)] for k in pk[hit]], np.int64)
        self.freq[self.used] *= self.aging
        self.freq[hit_slots] += 1.0
        self.last_seen[hit_slots] = self.tick
        self.tick += 1
        miss_pos = np.nonzero(~hit)[0]
        free = np.nonzero(~self.used)[0][: miss_pos.shape[0]]
        evictable = self.used & (self.freq < 1.0)
        evictable[hit_slots] = False
        cand = np.nonzero(evictable)[0]
        order = np.lexsort((cand, self.last_seen[cand], self.freq[cand]))
        victims = cand[order[: miss_pos.shape[0] - free.shape[0]]]
        victim_keys = self.keys[victims]
        admit_slots = np.concatenate([free, victims])
        admit_pos = miss_pos[: admit_slots.shape[0]]
        self.keys[admit_slots] = pk[admit_pos]
        self.used[admit_slots] = True
        self.freq[admit_slots] = 1.0
        self.last_seen[admit_slots] = self.tick
        return dict(hit_slots=hit_slots, admit_slots=admit_slots,
                    victim_slots=victims, victim_keys=victim_keys,
                    cold_pos=miss_pos[admit_slots.shape[0]:])


class TestAgeOnRead:
    """The directory ages a frequency when it is read, not every slot at
    every boundary: it must decide what the dense rule decides."""

    # the long cases cross the point where the stored unit is folded back
    # into the frequencies (aging ** -passes is past 2 ** 512; at 0.5 it
    # would be past float64 altogether)
    @pytest.mark.parametrize("aging, n_passes", [
        (0.5, 60), (0.8, 60), (0.95, 60), (0.5, 1100), (0.8, 1700),
    ])
    def test_decides_what_the_dense_rule_decides(self, aging, n_passes):
        from paddlebox_tpu.sparse.engine.hbm_cache import HbmCache

        rng = np.random.default_rng(7)
        cache = HbmCache(256, 4, aging=aging, materialize_rows=False)
        dense = _DenseRule(256, aging)
        n_victims = n_cold = 0
        for p in range(n_passes):
            # a Zipf head that stays and a tail that turns over: two
            # passes of ~110 distinct keys fill the free slots, then
            # 60-370 a pass, so the third pass on evicts and the large
            # ones leave misses cold
            size = 200 if p < 2 else int(rng.integers(100, 900))
            pk = np.unique(rng.zipf(1.2, size=size).astype(np.uint64)
                           % np.uint64(5000))
            plan = cache.lookup(pk)
            cache.touch(plan)
            upd = cache.plan_update(pk, plan)
            cache.commit_update(plan, upd)
            want = dense.observe(pk)
            got = dict(hit_slots=plan.hit_slots, admit_slots=upd.admit_slots,
                       victim_slots=upd.victim_slots,
                       victim_keys=upd.victim_keys, cold_pos=upd.cold_pos)
            for name, w in want.items():
                assert np.array_equal(got[name], w), (p, name)
            assert np.array_equal(cache.used, dense.used), p
            used = np.nonzero(dense.used)[0]
            np.testing.assert_allclose(
                cache.frequency(used), dense.freq[used], rtol=1e-12, atol=0)
            assert np.array_equal(cache.last_seen[used],
                                  dense.last_seen[used]), p
            if p >= 2:
                n_victims += upd.victim_slots.shape[0]
                n_cold += upd.cold_pos.shape[0]
        assert n_victims > n_passes and n_cold > 0  # there was turnover
        assert np.isfinite(cache._freq).all()
        if -np.log2(aging) * n_passes > 512:
            assert cache._unit < 2.0 ** 513  # the rescale ran

    def test_touch_ages_the_census_hits_and_resolves_once(self):
        """Over passes whose census is all hits, ``cache.aged_slots``
        follows the census, not the resident rows, and the directory is
        resolved once a begin_pass."""
        from paddlebox_tpu import telemetry

        def read():
            snap = telemetry.registry.snapshot()
            return (snap["counters"].get("cache.aged_slots", 0.0),
                    snap["histograms"].get(
                        "pass.stage_seconds{stage=lookup}",
                        {"count": 0})["count"])

        t = SparseTable(_tconf(1 << 10), seed=0)
        t.begin_pass(np.arange(1, 401, dtype=np.uint64))
        t.end_pass()
        assert t._caches()[0].resident == 400
        census = np.arange(1, 51, dtype=np.uint64)
        for _ in range(3):
            aged, lookups = read()
            t.begin_pass(census)
            assert t.last_cache_hits == 50 and t.last_cache_misses == 0
            aged1, lookups1 = read()
            assert aged1 - aged == 50
            assert lookups1 - lookups == 1
            t.end_pass()
        t.flush()


class TestChaos:
    def test_fetch_fault_falls_back_to_host_resolve(self, pass_datasets):
        """An injected cache.fetch failure must degrade to the synchronous
        host resolve — the run stays bit-exact with the uncached one."""
        _, datasets = pass_datasets
        sd_u, delta_u, m_u, _ = _run_single_chip(datasets, 0)
        with faults.fault_plan({"cache.fetch": "at:1"}):
            sd_c, delta_c, m_c, _ = _run_single_chip(datasets, 1 << 16)
            assert faults.active().hits("cache.fetch") > 0
        _assert_state_equal(sd_u, sd_c)
        _assert_state_equal(delta_u, delta_c)
        assert m_u["auc"] == m_c["auc"]

    def test_fetch_fault_in_stage_and_sync(self, pass_datasets):
        # first:2 fails the staged fetch AND the sync fallback fetch: the
        # pass must degrade all the way to the uncached resolve
        from paddlebox_tpu import telemetry

        _, datasets = pass_datasets
        sd_u, delta_u, m_u, _ = _run_single_chip(datasets, 0)
        with faults.fault_plan({"cache.fetch": "first:2"}):
            sd_c, delta_c, m_c, _ = _run_single_chip(datasets, 1 << 16)
        _assert_state_equal(sd_u, sd_c)
        assert m_u["auc"] == m_c["auc"]
        counters = telemetry.registry.snapshot()["counters"]
        assert counters.get("cache.fetch_fallbacks", 0) >= 1

    def test_admit_fault_falls_back_to_full_writeback(self, pass_datasets):
        from paddlebox_tpu import telemetry

        _, datasets = pass_datasets
        sd_u, delta_u, m_u, _ = _run_single_chip(datasets, 0)
        with faults.fault_plan({"cache.admit": "at:1"}):
            sd_c, delta_c, m_c, _ = _run_single_chip(datasets, 1 << 16)
            assert faults.active().hits("cache.admit") > 0
        _assert_state_equal(sd_u, sd_c)
        _assert_state_equal(delta_u, delta_c)
        assert m_u["auc"] == m_c["auc"]
        counters = telemetry.registry.snapshot()["counters"]
        assert counters.get("cache.admit_fallbacks", 0) >= 1

    def test_fetch_fault_simple_lifecycle_values_survive(self):
        """Direct (trainer-free) check: rows trained before the fault are
        intact after the degraded pass."""
        with faults.fault_plan({"cache.fetch": "at:1"}):
            t = SparseTable(_tconf(1 << 16), seed=0)
            keys = np.arange(1, 40, dtype=np.uint64)
            t.begin_pass(keys)  # fetch hit 0: clean
            t.values = t.values + 5.0
            t.end_pass()
            t.begin_pass(keys)  # fetch hit 1: injected -> degraded resolve
            assert (np.asarray(t.values)[:39, 0] == 5.0).all()
            t.values = t.values + 1.0
            t.end_pass()
            t.flush()
            sd = t.state_dict()
            assert (sd["values"][:, 0] == 6.0).all()


class TestTelemetryAndKillSwitch:
    def test_pass_end_jsonl_carries_cache_metrics(self, pass_datasets,
                                                  tmp_path):
        from paddlebox_tpu.telemetry import events

        _, datasets = pass_datasets
        path = str(tmp_path / "events.jsonl")
        events.close_event_log()
        tconf = _tconf(1 << 16)
        table = SparseTable(tconf, seed=1)
        model = CtrDnn(N_SLOTS, tconf.row_width, dense_dim=DENSE,
                       hidden=(8,))
        trainer = Trainer(
            model, tconf,
            TrainerConfig(auc_buckets=1 << 10,
                          telemetry=TelemetryConfig(events_path=path)),
            seed=1,
        )
        try:
            for ds in datasets[:2]:
                table.begin_pass(ds.unique_keys())
                trainer.train_from_dataset(ds, table, drop_last=True)
                table.end_pass()
            table.flush()
        finally:
            events.close_event_log()
        recs = [json.loads(ln) for ln in open(path)]
        passes = [r for r in recs if r["event"] == "pass_end"]
        assert len(passes) == 2
        gauges = passes[-1]["telemetry"]["gauges"]
        assert "cache.hit_rate" in gauges
        assert gauges["cache.hit_rate"] > 0  # overlapping censuses hit
        hists = passes[0]["telemetry"]["histograms"]
        # the misses' host-tier fetch is a stage of the boundary
        assert hists["pass.stage_seconds{stage=fetch}"]["count"] >= 1

    def test_kill_switch_disables_cache(self, monkeypatch):
        monkeypatch.setenv("PBOX_HBM_CACHE", "0")
        t = SparseTable(_tconf(1 << 16), seed=0)
        keys = np.arange(1, 30, dtype=np.uint64)
        t.begin_pass(keys)
        t.end_pass()
        assert t._caches() == []
        t.flush()  # the write-back merge is async under overlap
        assert t._store.n == 29  # full write-back: the uncached lifecycle

    def test_store_stats_report_host_tier_pressure(self, tmp_path):
        from paddlebox_tpu.sparse.store import BucketStore

        store = BucketStore(
            n_cols=3, n_buckets=8, spill_dir=str(tmp_path / "spill"),
            max_resident=2,
        )
        keys = np.arange(0, 4000, dtype=np.uint64)
        store.update(keys, np.ones((4000, 3), np.float32))
        st = store.stats()
        assert st["n"] == 4000
        assert st["spilled_buckets"] > 0  # max_resident 2 of 8 buckets
        assert 0 < st["resident_rows"] < 4000
        ram = BucketStore(n_cols=3, n_buckets=8)
        ram.update(keys, np.ones((4000, 3), np.float32))
        st = ram.stats()
        assert st["spilled_buckets"] == 0 and st["resident_rows"] == 4000


# --------------------------------------------------------------------------- #
# The directory resolve's two forms (ISSUE 49): the native merge of
# _native/plan_resolve.cpp pbx_cache_lookup against the numpy form it
# replaced, which stays as the fallback and is the oracle here
# --------------------------------------------------------------------------- #
def _directory(keys: np.ndarray, seed: int = 0):
    """A metadata-only cache holding ``keys`` in shuffled slots."""
    from paddlebox_tpu.sparse.engine.hbm_cache import HbmCache

    keys = np.asarray(keys, dtype=np.uint64)
    cache = HbmCache(max(int(keys.shape[0]), 1) + 3, 4,
                     materialize_rows=False)
    slots = np.random.default_rng(seed).permutation(cache.capacity)
    slots = slots[: keys.shape[0]]
    cache.keys[slots] = keys
    cache.used[slots] = True
    cache._rebuild_index()
    return cache


def _u64(*values) -> np.ndarray:
    return np.array(values, dtype=np.uint64)


def _interleaved(seed: int):
    rng = np.random.default_rng(seed)
    resident = np.unique(rng.integers(0, 1 << 40, 3000, dtype=np.uint64))
    census = np.unique(np.concatenate([
        rng.choice(resident, 900, replace=False),
        rng.integers(0, 1 << 40, 900, dtype=np.uint64)]))
    return _directory(resident, seed), census


def _after_evictions():
    """A directory whose slots are no longer in key order: a small cache
    run through passes that admit into freed slots."""
    from paddlebox_tpu.sparse.engine.hbm_cache import HbmCache

    rng = np.random.default_rng(11)
    cache = HbmCache(256, 4, aging=0.5, materialize_rows=False)
    n_victims = 0
    for p in range(8):
        pk = np.unique(rng.integers(0, 4000, 200 if p < 2 else 500)
                       .astype(np.uint64))
        plan = cache.lookup(pk)
        cache.touch(plan)
        upd = cache.plan_update(pk, plan)
        cache.commit_update(plan, upd)
        n_victims += upd.victim_slots.shape[0]
    assert n_victims > 0 and np.any(np.diff(cache._sorted_slots) < 0)
    return cache, np.unique(rng.integers(0, 4000, 700).astype(np.uint64))


def _long_census():
    """Long enough for every range of the native form's threads (4 x
    32,768 census keys), with hits and misses on both sides of every
    range's edge and of every block of the directory's sample."""
    rng = np.random.default_rng(5)
    resident = np.unique(rng.integers(0, 1 << 22, 400_000, dtype=np.uint64))
    census = np.unique(rng.integers(0, 1 << 22, 300_000, dtype=np.uint64))
    assert census.shape[0] >= 4 * 32768
    return _directory(resident), census


_HIGH = np.uint64(1) << np.uint64(63)

_RESOLVE_CASES = {
    "empty_census": lambda: (_directory(np.arange(10, 50)), _u64()),
    "empty_directory": lambda: (_directory(_u64()),
                                np.arange(5, 40, dtype=np.uint64)),
    "all_hits": lambda: (_directory(np.arange(3, 900, 3)),
                         np.arange(3, 900, 6, dtype=np.uint64)),
    "no_hit": lambda: (_directory(np.arange(3, 900, 3)),
                       np.arange(4, 900, 3, dtype=np.uint64)),
    "below_first_and_above_last": lambda: (
        _directory(np.arange(100, 200)),
        _u64(0, 7, 99, 100, 150, 199, 200, 5000, 2 ** 64 - 1)),
    "keys_past_2_63": lambda: (
        _directory(np.concatenate([
            np.arange(1, 40, dtype=np.uint64),
            _HIGH + np.arange(0, 80, 2, dtype=np.uint64)])),
        np.concatenate([
            _u64(5, 39, 40),
            _HIGH - np.uint64(1) + np.arange(0, 90, dtype=np.uint64),
            _u64(2 ** 64 - 1)])),
    "census_larger_than_directory": lambda: (
        _directory(np.arange(0, 170, 10)), np.arange(0, 400, dtype=np.uint64)),
    "one_key_hit": lambda: (_directory(np.arange(20, 60)), _u64(33)),
    "one_key_miss": lambda: (_directory(np.arange(20, 60, 2)), _u64(33)),
    "one_resident_key": lambda: (_directory(_u64(33)),
                                 np.arange(30, 36, dtype=np.uint64)),
    "interleaved_seed0": lambda: _interleaved(0),
    "interleaved_seed1": lambda: _interleaved(1),
    "interleaved_seed2": lambda: _interleaved(2),
    "after_commit_update_with_evictions": _after_evictions,
    "long_census_across_every_range": _long_census,
    # directory sizes and census lengths on both sides of the sample's
    # stride (16) and of the prefetch ring (32)
    **{f"edges_dir{n_dir}_census{n}": (
        lambda n_dir=n_dir, n=n: (_directory(np.arange(0, 2 * n_dir, 2)),
                                  np.arange(n, dtype=np.uint64)))
       for n_dir in (15, 16, 17, 33) for n in (31, 32, 33, 65)},
}


def _lookup_both(cache, pk):
    from paddlebox_tpu.config import flags

    native = cache.lookup(pk)
    flags.set("use_native_planner", False)
    try:
        oracle = cache.lookup(pk)
    finally:
        flags.set("use_native_planner", True)
    return native, oracle


def _lookups() -> dict:
    from paddlebox_tpu import telemetry

    counters = telemetry.registry.snapshot()["counters"]
    return {form: counters.get(f"cache.lookups{{form={form}}}", 0.0)
            for form in ("native", "numpy")}


@pytest.mark.parametrize("case", list(_RESOLVE_CASES))
def test_native_resolve_equals_the_numpy_form(case):
    from paddlebox_tpu._native import get_plan_lib

    if get_plan_lib() is None:
        pytest.skip("native planner did not build")
    cache, pk = _RESOLVE_CASES[case]()
    before = _lookups()
    native, oracle = _lookup_both(cache, pk)
    after = _lookups()
    assert {f: after[f] - before[f] for f in after} == {
        "native": 1.0, "numpy": 1.0}
    for name in ("hit_mask", "hit_pos", "hit_slots"):
        got, want = getattr(native, name), getattr(oracle, name)
        assert got.dtype == want.dtype, name
        np.testing.assert_array_equal(got, want, err_msg=name)
    assert native.hit_mask.shape == pk.shape
    # and the touch of those hits writes what numpy's two indexed writes do
    freq, seen = cache._freq.copy(), cache.last_seen.copy()
    cache.touch(native)
    want_freq, want_seen = freq.copy(), seen.copy()
    want_freq[oracle.hit_slots] += cache._unit
    want_seen[oracle.hit_slots] = cache.tick - 1
    np.testing.assert_array_equal(cache._freq, want_freq)
    np.testing.assert_array_equal(cache.last_seen, want_seen)


def test_without_the_library_the_numpy_form_answers(monkeypatch):
    """The planner's library made unavailable: lookup and touch fall to
    numpy, and ``cache.lookups{form=numpy}`` says so."""
    from paddlebox_tpu import _native

    cache, pk = _interleaved(3)
    with_library = cache.lookup(pk)
    monkeypatch.setattr(_native, "get_plan_lib", lambda: None)
    before = _lookups()
    plan = cache.lookup(pk)
    after = _lookups()
    assert after["numpy"] - before["numpy"] == 1.0
    assert after["native"] == before["native"]
    for name in ("hit_mask", "hit_pos", "hit_slots"):
        np.testing.assert_array_equal(getattr(plan, name),
                                      getattr(with_library, name))
    freq = cache._freq.copy()
    cache.touch(plan)
    assert np.count_nonzero(cache._freq != freq) == plan.n_hits > 0
