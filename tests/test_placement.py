"""Sparsity-aware hybrid parallelism (ISSUE 15): the placement planner's
hysteresis-bounded hot-set decisions, the shared-dictionary census
exchange over a simulated 2-rank fleet (pk equality vs the legacy union,
mirror-vs-real cache membership, cached-vs-uncached lifecycle equality,
byte collapse, loud protocol failures), the bit-exact planned-vs-hash
trained-store pin on both trainer paths, and the zero-retrace pin under
plan churn."""

import dataclasses
import threading

import numpy as np
import pytest

import jax

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig, flags
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models.ctr_dnn import CtrDnn
from paddlebox_tpu.parallel import (
    MultiChipTrainer,
    ShardedSparseTable,
    make_mesh,
)
from paddlebox_tpu.parallel.census import (
    CensusExchange,
    CensusProtocolError,
    FleetCacheMirror,
    InProcessCensusGroup,
    LoopbackTransport,
    legacy_union,
)
from paddlebox_tpu.sparse.placement import PlacementPlanner
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

S, DENSE = 3, 2


def _make_data(tmp_path, seed=7, n_ins=256, bsz=16, vocab=60, zipf_a=0.0):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=bsz,
        max_feasigns_per_ins=16,
    )
    files = write_synth_files(
        str(tmp_path), n_files=2, ins_per_file=n_ins // 2,
        n_sparse_slots=S, vocab_per_slot=vocab, dense_dim=DENSE, seed=seed,
        zipf_a=zipf_a,
    )
    ds = PadBoxSlotDataset(conf, read_threads=2)
    ds.set_filelist(files)
    ds.load_into_memory()
    return conf, ds


# --------------------------------------------------------------------------- #
# planner units
# --------------------------------------------------------------------------- #
class TestPlanner:
    def test_topk_by_aged_frequency(self):
        p = PlacementPlanner(hot_capacity=2, aging=0.5, enter_freq=1.5,
                             exit_freq=1.0, update_interval=1)
        hot = np.asarray([7, 9], dtype=np.uint64)
        cold = np.asarray([100, 200, 300], dtype=np.uint64)
        for i in range(4):
            census = np.concatenate(
                [hot, cold[i % cold.shape[0]:i % cold.shape[0] + 1]]
            )
            p.observe(census)
        plan = p.update_plan()
        np.testing.assert_array_equal(plan.hot_keys, hot)
        assert plan.version >= 1

    def test_hysteresis_bounds_plan_churn(self):
        """The hot set may mutate at most once per update_interval passes,
        and an incumbent survives down to exit_freq while a challenger
        needs enter_freq — no flapping at the boundary."""
        p = PlacementPlanner(hot_capacity=1, aging=0.5, enter_freq=1.6,
                             exit_freq=0.9, update_interval=3)
        a = np.asarray([11], dtype=np.uint64)
        b = np.asarray([22], dtype=np.uint64)
        for _ in range(4):
            p.observe(a)
        v1 = p.update_plan().version
        np.testing.assert_array_equal(p.plan().hot_keys, a)
        # b becomes the frequent one; a decays but stays >= exit for a while
        p.observe(np.concatenate([a, b]))
        assert p.update_plan().version == v1, \
            "plan changed before update_interval elapsed"
        p.observe(b)
        assert p.update_plan().version == v1
        p.observe(b)
        plan = p.update_plan()  # 3 passes since last update: may change
        assert plan.version == v1 + 1
        np.testing.assert_array_equal(plan.hot_keys, b)

    def test_incumbent_survives_between_exit_and_enter(self):
        p = PlacementPlanner(hot_capacity=4, aging=0.5, enter_freq=1.9,
                             exit_freq=0.9, update_interval=1)
        a = np.asarray([5], dtype=np.uint64)
        for _ in range(5):
            p.observe(a)  # freq -> 1.9375
        p.update_plan()
        np.testing.assert_array_equal(p.plan().hot_keys, a)
        # one absent pass ages it to ~0.97: below enter (a challenger at
        # this freq could never get in) but above exit -> incumbent stays
        p.observe(np.asarray([999], dtype=np.uint64))
        plan = p.update_plan()
        assert 5 in plan.hot_keys.tolist(), \
            "incumbent above exit_freq must not churn out"
        # three absent passes push it below exit_freq -> it leaves
        for _ in range(3):
            p.observe(np.asarray([999], dtype=np.uint64))
            p.update_plan()
        assert 5 not in p.plan().hot_keys.tolist()

    def test_seed_merges_external_frequency(self):
        p = PlacementPlanner(hot_capacity=2, enter_freq=1.5,
                             update_interval=1)
        p.seed(np.asarray([42, 43], np.uint64), np.asarray([5.0, 0.1]))
        p.observe(np.asarray([42, 99], np.uint64))
        plan = p.update_plan()
        assert 42 in plan.hot_keys.tolist()
        assert 43 not in plan.hot_keys.tolist()

    def test_determinism_across_instances(self):
        """Two planners fed the same census stream emit identical plans —
        the property the no-collective dictionary derivation rests on."""
        rng = np.random.default_rng(3)
        p1 = PlacementPlanner(hot_capacity=16, update_interval=2)
        p2 = PlacementPlanner(hot_capacity=16, update_interval=2)
        for _ in range(6):
            census = rng.zipf(1.2, 500).astype(np.uint64) % 300
            p1.observe(census)
            p2.observe(census)
            a, b = p1.update_plan(), p2.update_plan()
            assert a.version == b.version
            np.testing.assert_array_equal(a.hot_keys, b.hot_keys)

    def test_validation(self):
        with pytest.raises(ValueError):
            PlacementPlanner(aging=1.5)
        with pytest.raises(ValueError):
            PlacementPlanner(enter_freq=1.0, exit_freq=2.0)
        with pytest.raises(ValueError):
            PlacementPlanner(update_interval=0)


# --------------------------------------------------------------------------- #
# census exchange: simulated 2-rank fleet
# --------------------------------------------------------------------------- #
def _run_ranks(n, fn):
    out = [None] * n
    errs = []

    def wrap(r):
        try:
            out[r] = fn(r)
        except BaseException as e:  # surfaced below
            errs.append(e)

    ts = [threading.Thread(target=wrap, args=(r,)) for r in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise errs[0]
    return out


def _rank_censuses(n_ranks, n_passes, seed=5):
    rng = np.random.default_rng(seed)
    shared = np.arange(0, 400, 3, dtype=np.uint64)
    out = []
    for _ in range(n_passes):
        per_rank = [
            np.unique(np.concatenate([
                shared,
                rng.integers(1000, 4000, 60, dtype=np.uint64),
            ]))
            for _ in range(n_ranks)
        ]
        out.append(per_rank)
    return out


def test_two_rank_exchange_equals_legacy_union():
    """Every rank decodes the identical global census, byte-equal to the
    legacy allgather-union, under planner+mirror+varint."""
    n, passes = 2, 5
    censuses = _rank_censuses(n, passes)
    group = InProcessCensusGroup(n)

    def rank_fn(r):
        ex = CensusExchange(
            group.transport(r),
            planner=PlacementPlanner(hot_capacity=256, update_interval=1),
            mirror=FleetCacheMirror(n, 64, 0.8),
        )
        return [ex.exchange(censuses[p][r]) for p in range(passes)]

    results = _run_ranks(n, rank_fn)
    for p in range(passes):
        want = legacy_union([censuses[p][r] for r in range(n)])
        for r in range(n):
            np.testing.assert_array_equal(results[r][p], want)


def test_two_rank_bytes_collapse_and_codec_ratio():
    """Steady state: planned+varint wire bytes collapse far below the raw
    full-census baseline (O(working set) -> O(cold + dictionary bits)),
    and the codec alone is >= 4x on the sorted censuses."""
    n, passes = 2, 6
    censuses = _rank_censuses(n, passes)

    def arm(planner_on, codec):
        group = InProcessCensusGroup(n)

        def rank_fn(r):
            ex = CensusExchange(
                group.transport(r),
                planner=(
                    PlacementPlanner(hot_capacity=4096, enter_freq=1.5,
                                     update_interval=1)
                    if planner_on else None
                ),
                mirror=FleetCacheMirror(n, 512, 0.8) if planner_on else None,
                codec=codec,
            )
            wire = []
            for p in range(passes):
                ex.exchange(censuses[p][r])
                wire.append(ex.last_wire_bytes)
            return wire
        wires = _run_ranks(n, rank_fn)
        # steady state: skip pass 0 (dictionary empty, all cold)
        return sum(sum(w[1:]) for w in wires) / (passes - 1)

    raw = arm(False, "raw")
    varint = arm(False, "varint")
    planned = arm(True, "varint")
    assert raw / varint >= 4.0, f"codec alone {raw / varint:.2f}x < 4x"
    assert planned < varint < raw
    assert raw / planned >= 8.0, (
        f"planned collapse only {raw / planned:.2f}x "
        f"({raw:.0f} -> {planned:.0f} B/pass)"
    )


def test_mirror_tracks_real_cache_membership():
    """Each rank holds a REAL HbmCache for its own shard; every rank's
    metadata mirror must predict every shard's membership exactly (no
    faults injected) — the property that makes 'exchange only cache
    misses' a pure encoding decision."""
    from paddlebox_tpu.sparse.engine import HbmCache

    n, passes = 2, 5
    censuses = _rank_censuses(n, passes)
    group = InProcessCensusGroup(n)
    cap = 64

    def rank_fn(r):
        ex = CensusExchange(
            group.transport(r),
            mirror=FleetCacheMirror(n, cap, 0.8),
        )
        real = HbmCache(cap, 4, aging=0.8)  # this rank's own shard r
        residents = []
        for p in range(passes):
            pk = ex.exchange(censuses[p][r])
            sk = pk[pk % np.uint64(n) == np.uint64(r)]
            # the real per-shard cached lifecycle: begin (lookup+touch),
            # end (plan_update+commit) — same order the sharded table runs
            plan = real.lookup(sk)
            real.touch(plan)
            upd = real.plan_update(sk, plan)
            real.commit_update(plan, upd)
            residents.append(real.snapshot_keys().copy())
        return ex, residents

    results = _run_ranks(n, rank_fn)
    for owner in range(n):
        _, owner_residents = results[owner]
        for r in range(n):
            ex, _ = results[r]
            np.testing.assert_array_equal(
                ex.mirror.shard_resident(owner), owner_residents[-1],
                err_msg=f"rank {r}'s mirror diverged from shard {owner}",
            )


def test_cached_vs_uncached_lifecycle_equality():
    """The multi-host cached lifecycle (mirror dictionary riding the
    census) and the uncached one (no dictionary) agree on every pass's
    global census — cache state compresses the wire, never changes it."""
    n, passes = 2, 5
    censuses = _rank_censuses(n, passes, seed=11)

    def arm(with_mirror):
        group = InProcessCensusGroup(n)

        def rank_fn(r):
            ex = CensusExchange(
                group.transport(r),
                mirror=FleetCacheMirror(n, 128, 0.8) if with_mirror else None,
            )
            return [ex.exchange(censuses[p][r]) for p in range(passes)]
        return _run_ranks(n, rank_fn)

    cached = arm(True)
    uncached = arm(False)
    for p in range(passes):
        np.testing.assert_array_equal(cached[0][p], uncached[0][p])
        np.testing.assert_array_equal(cached[1][p], uncached[0][p])


def test_protocol_errors_are_loud():
    # a peer speaking a different wire format entirely
    ex = CensusExchange(LoopbackTransport())
    with pytest.raises(CensusProtocolError) as ei:
        ex._decode(b"garbage-not-a-census", sender=1,
                   known=np.empty(0, np.uint64))
    assert ei.value.sender == 1
    # dictionary divergence: rank 1 derives a different hot set (e.g. a
    # mis-configured planner) -> digest mismatch names the sender
    n = 2
    group = InProcessCensusGroup(n)
    censuses = _rank_censuses(n, 3, seed=13)

    def rank_fn(r):
        ex = CensusExchange(
            group.transport(r),
            planner=PlacementPlanner(
                hot_capacity=64 if r == 0 else 8,  # the misconfiguration
                enter_freq=1.0, exit_freq=1.0, update_interval=1,
            ),
        )
        for p in range(3):
            ex.exchange(censuses[p][r])

    with pytest.raises(CensusProtocolError) as ei:
        _run_ranks(n, rank_fn)
    assert "different dictionary" in str(ei.value)


def test_truncated_message_is_loud():
    ex = CensusExchange(LoopbackTransport())
    payload = ex._encode(np.arange(50, dtype=np.uint64),
                         np.empty(0, np.uint64))
    with pytest.raises(CensusProtocolError):
        ex._decode(payload[:-3], sender=0, known=np.empty(0, np.uint64))


# --------------------------------------------------------------------------- #
# bit-exact: planned placement vs hash-only, both trainer paths
# --------------------------------------------------------------------------- #
def _train_sharded(tmp_path, placement, n_passes=3, n_dev=None):
    mesh = make_mesh(n_dev or min(8, len(jax.devices())))
    tconf = SparseTableConfig(
        embedding_dim=4, placement=placement, placement_update_interval=1,
        placement_hot_capacity=64, hbm_cache_rows=64,
    )
    trconf = TrainerConfig(auc_buckets=1 << 10)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
    trainer = MultiChipTrainer(model, tconf, mesh, trconf, seed=3)
    table = ShardedSparseTable(tconf, mesh, seed=5, bucket_slack=8.0)
    auc_state = None
    m = {}
    for p in range(n_passes):
        conf, ds = _make_data(tmp_path / f"{placement}-{p}", seed=20 + p)
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(ds, table, auc_state=auc_state,
                                       drop_last=True)
        auc_state = trainer.last_metric_state
        table.end_pass()
        ds.close()
    st = table.state_dict()
    plan = table.placement_plan()
    table.close()
    return st, float(m["auc"]), plan


def test_bitexact_planned_vs_hash_sharded_trainer(tmp_path):
    """3 overlapping-census passes through the MultiChipTrainer: the full
    placement wire path (loopback: encode -> decode in every begin_pass,
    planner + mirrors live) must leave keys, values, g2sum AND AUC
    byte-identical to the hash-only run — placement moves bytes, never
    floats."""
    st_hash, auc_hash, _ = _train_sharded(tmp_path, "hash")
    st_plan, auc_plan, plan = _train_sharded(tmp_path, "loopback")
    assert plan is not None and plan.version >= 1 and plan.n_hot > 0, \
        "the planner never actually planned — the test proved nothing"
    np.testing.assert_array_equal(st_hash["keys"], st_plan["keys"])
    np.testing.assert_array_equal(st_hash["values"], st_plan["values"])
    assert auc_hash == auc_plan


def test_bitexact_single_chip_placement_inert(tmp_path, monkeypatch):
    """Single-chip path: the placement flag must be inert on SparseTable
    (no sharded wire exists) — training under PBOX_PLACEMENT=loopback
    equals the hash run bit-for-bit."""
    states = {}
    for mode in ("hash", "loopback"):
        monkeypatch.setenv("PBOX_PLACEMENT", mode)
        conf, ds = _make_data(tmp_path / f"sc-{mode}", seed=3)
        tconf = SparseTableConfig(embedding_dim=4)
        model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf,
                          TrainerConfig(auc_buckets=1 << 10), seed=0)
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(ds, table)
        table.end_pass()
        st = table.state_dict()
        st["auc"] = float(m["auc"])
        states[mode] = st
        table.close()
        ds.close()
    np.testing.assert_array_equal(states["hash"]["keys"],
                                  states["loopback"]["keys"])
    np.testing.assert_array_equal(states["hash"]["values"],
                                  states["loopback"]["values"])
    assert states["hash"]["auc"] == states["loopback"]["auc"]


# --------------------------------------------------------------------------- #
# realized hybrid placement: deterministic reduction + host-plane pins
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_hybrid_reduce_bitexact_across_reruns(tmp_path, n_dev):
    """The hot-gradient reduction is an explicitly ordered fold (level-1
    segment_sum over in-batch occurrences, level-2 all_gather + unrolled
    device-ascending adds), so two identical runs on the realized hybrid
    layout must produce byte-identical stores — keys, values AND the
    g2sum column — and the same AUC, at every simulated device count."""
    if len(jax.devices()) < n_dev:
        pytest.skip(f"needs {n_dev} devices")
    st_a, auc_a, plan_a = _train_sharded(tmp_path, "loopback", n_dev=n_dev)
    st_b, auc_b, plan_b = _train_sharded(tmp_path, "loopback", n_dev=n_dev)
    assert plan_a is not None and plan_a.n_hot > 0, (
        "the plan never realized — the reduction under test never ran"
    )
    assert plan_b is not None and plan_b.n_hot == plan_a.n_hot
    np.testing.assert_array_equal(st_a["keys"], st_b["keys"])
    np.testing.assert_array_equal(st_a["values"], st_b["values"])
    assert auc_a == auc_b


_HOST_CTRS = ("pass.host_row_bytes_in", "pass.host_row_bytes_out",
              "placement.hot_row_host_bytes")


def _host_plane_run(passes, hot_capacity, realize=True):
    """Train ``passes`` (datasets) on a loopback-placement sharded table
    with no row cache, so every host row move is counted.  Returns each
    pass's counter deltas over (begin_pass, train, end_pass), the resident
    hot keys and the final store."""
    from paddlebox_tpu.telemetry import registry

    mesh = make_mesh(min(8, len(jax.devices())))
    tconf = SparseTableConfig(
        embedding_dim=4, placement="loopback",
        placement_update_interval=1, placement_hot_capacity=hot_capacity,
        placement_realize=realize, hbm_cache_rows=0,
    )
    trconf = TrainerConfig(auc_buckets=1 << 10)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
    trainer = MultiChipTrainer(model, tconf, mesh, trconf, seed=3)
    table = ShardedSparseTable(tconf, mesh, seed=5, bucket_slack=8.0)

    def ctrs():
        c = registry.snapshot()["counters"]
        return np.array([c.get(name, 0) for name in _HOST_CTRS])

    deltas = []
    for ds in passes:
        s0 = ctrs()
        table.begin_pass(ds.unique_keys())
        s1 = ctrs()
        trainer.train_from_dataset(ds, table)
        s2 = ctrs()
        table.end_pass()
        deltas.append((s1 - s0, s2 - s1, ctrs() - s2))
    hot = table.hot_resident_keys()
    state = table.state_dict()
    table.close()
    return deltas, hot, state, 4 * (tconf.row_width + 1)


@pytest.mark.parametrize("case", ["steady_census", "zipf_against_wire"])
def test_hybrid_zero_host_row_bytes_inside_pass(tmp_path, case):
    """The structural pin of the realized layout: once a key is hot and
    resident, its rows NEVER cross the host plane — zero row bytes of any
    kind inside a pass.  ``steady_census``: boundary traffic exactly
    O(cold rows), and no churn -> zero hot migration bytes too.
    ``zipf_against_wire``: three passes of a Zipf-skewed stream, each
    with its own census, against the wire-plane-only arm
    (``placement_realize=False``): the realized arm's last begin_pass
    pays less host row traffic, and the stores are bit-identical."""
    if case == "steady_census":
        conf, ds = _make_data(tmp_path / "pin", seed=11)
        keys = ds.unique_keys()
        # three passes: aged frequency clears enter_freq, the block
        # realizes; the fourth is the one measured
        deltas, hot, _, row_b = _host_plane_run([ds] * 4, hot_capacity=32)
        ds.close()
        assert hot.shape[0] > 0, "hot block never realized"
        n_cold = int(keys.shape[0]) - hot.shape[0]
        begin, inside, end = deltas[-1]
        # inside the pass: zero host-plane row bytes, hot or cold
        assert not inside.any(), dict(zip(_HOST_CTRS, inside))
        # steady census: zero hot-tier migration bytes across the boundary
        assert begin[2] == 0 and end[2] == 0
        # boundary traffic is exactly the cold tail: resident hot rows ride
        # neither the begin_pass fill nor the end_pass write-back
        assert begin[0] == n_cold * row_b
        assert end[1] == n_cold * row_b
        return
    passes = [
        _make_data(tmp_path / f"z{p}", seed=91 + p, vocab=300, zipf_a=1.3)[1]
        for p in range(3)
    ]
    wire, hot_w, st_w, _ = _host_plane_run(passes, 512, realize=False)
    hybrid, hot_h, st_h, _ = _host_plane_run(passes, 512)
    for ds in passes:
        ds.close()
    assert hot_w.shape[0] == 0 and hot_h.shape[0] > 0, \
        "hybrid arm never realized"
    for _, inside, _ in wire + hybrid:
        assert not inside.any(), dict(zip(_HOST_CTRS, inside))
    assert hybrid[-1][0][0] < wire[-1][0][0], \
        "realized hot rows still paying begin-pass host traffic"
    np.testing.assert_array_equal(st_w["keys"], st_h["keys"])
    np.testing.assert_array_equal(st_w["values"], st_h["values"])


# --------------------------------------------------------------------------- #
# zero-retrace under plan churn (the PR-14 pins must hold)
# --------------------------------------------------------------------------- #
def test_plan_churn_zero_retrace(tmp_path):
    """Plan-version churn (update_interval=1, shifting censuses) must be
    invisible to jit: after warmup, passes with a MUTATING hot set
    trigger zero XLA compiles across every stage — the placement plan
    lives on the wire, never in a traced shape."""
    from paddlebox_tpu.telemetry import compiles

    mesh = make_mesh(min(8, len(jax.devices())))
    tconf = SparseTableConfig(
        embedding_dim=4, placement="loopback",
        placement_update_interval=1, placement_hot_capacity=32,
        hbm_cache_rows=64,
    )
    trconf = TrainerConfig(auc_buckets=1 << 10)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
    trainer = MultiChipTrainer(model, tconf, mesh, trconf, seed=3)
    table = ShardedSparseTable(tconf, mesh, seed=5, bucket_slack=8.0)
    conf, ds = _make_data(tmp_path / "churn", seed=9)
    keys = ds.unique_keys()

    # warmup: compile + capacity-fit recompile, plus the pass where the
    # planner's hot set first clears the hysteresis gate and the hybrid
    # layout realizes on device (first promotion compiles its static-[H]
    # migration machinery once, like the step itself)
    for _ in range(3):
        table.begin_pass(keys)
        trainer.train_from_dataset(ds, table)
        table.end_pass()
    assert table.hot_resident_keys().shape[0] > 0, (
        "warmup never realized the hot block — the measured window "
        "would not cover the hybrid path"
    )

    before = compiles.compiles_by_stage()
    versions = []
    for _ in range(2):
        table.begin_pass(keys)
        trainer.train_from_dataset(ds, table)
        table.end_pass()
        versions.append(table.placement_plan().version)
    after = compiles.compiles_by_stage()
    moved = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    ds.close()
    table.close()
    assert not moved, (
        f"plan churn recompiled: {moved} — placement leaked into a "
        "traced shape"
    )
    assert versions[0] >= 1, "the planner never planned"
