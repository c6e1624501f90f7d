"""Sparse table: pull/push/update parity vs a numpy oracle + pass lifecycle.

Covers numeric parity for pull/push/update and the
begin_pass -> train -> end_pass -> shrink cycle (reference semantics:
fleet/box_wrapper_impl.h:24-255, box_wrapper.cc:609-673,496-499).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu import telemetry
from paddlebox_tpu._native import build_census_index
from paddlebox_tpu.config import SparseTableConfig, TrainerConfig, flags
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.sparse import SparseTable, pull_rows, push_and_update
from paddlebox_tpu.train.trainer import Trainer


def _conf(**kw):
    base = dict(embedding_dim=4, learning_rate=0.1, initial_g2sum=1.0,
                initial_range=0.5, grad_clip=10.0)
    base.update(kw)
    return SparseTableConfig(**base)


def _plan_arrays(plan):
    return (jnp.asarray(plan.idx), jnp.asarray(plan.uniq_idx),
            jnp.asarray(plan.inverse), jnp.asarray(plan.key_mask))


def test_begin_pass_initializes_new_rows():
    t = SparseTable(_conf(), seed=0)
    keys = np.array([7, 3, 3, 99], dtype=np.uint64)
    t.begin_pass(keys)
    assert t.capacity >= 4  # 3 unique + dead row, padded
    vals = np.asarray(t.values)
    # show/clk start at 0; embeddings within init range
    np.testing.assert_allclose(vals[:3, :2], 0.0)
    assert (np.abs(vals[:3, 2:]) <= 0.5).all()
    assert np.abs(vals[:3, 2:]).sum() > 0  # actually initialized
    # dead row zero
    np.testing.assert_allclose(vals[t.dead_row], 0.0)


def _serial_table():
    return SparseTable(_conf(), seed=0)


def _sharded_table():
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.parallel.sharded_table import ShardedSparseTable

    return ShardedSparseTable(_conf(), make_mesh(8), seed=0)


def _rows_by_key(t):
    """{key: row} of a finished pass, whatever the plane."""
    st = t.state_dict()
    return dict(zip(st["keys"].tolist(), map(tuple, st["values"].tolist())))


_SORTED = np.array([3, 7, 19, 64, 99, 123, 1 << 40], dtype=np.uint64)


@pytest.mark.parametrize("make", [_serial_table, _sharded_table],
                         ids=["serial", "sharded"])
@pytest.mark.parametrize("census,resorted", [
    (_SORTED, 0),
    (_SORTED[::-1].copy(), 1),
    (np.concatenate([_SORTED, _SORTED[2:5]]), 1),
    (_SORTED.astype(np.int64), 0),  # another dtype: converted, not sorted
    (np.empty(0, np.uint64), 0),
], ids=["sorted", "unsorted", "repeats", "int64", "empty"])
def test_begin_pass_takes_a_sorted_census_as_it_is(make, census, resorted):
    """begin_pass's first stage: a census that ascends strictly (what a
    dataset's unique_keys() hands over) is not sorted again; any other
    gives the pass table np.unique gave before."""
    c = telemetry.counter("pass.census_resorted")
    want = make()
    want.begin_pass(np.unique(census.astype(np.uint64)))
    want.end_pass()
    before = c.value()
    t = make()
    t.begin_pass(census)
    assert c.value() - before == resorted
    if make is _serial_table:
        np.testing.assert_array_equal(t._pass_keys, np.unique(census))
        assert t._pass_keys.dtype == np.uint64
        # taken as it is: the table holds the caller's array, not a copy
        assert np.shares_memory(t._pass_keys, census) == (
            resorted == 0 and census.dtype == np.uint64 and census.size > 0)
    t.end_pass()
    assert _rows_by_key(t) == _rows_by_key(want)


def test_prepare_pass_takes_a_sorted_census_as_it_is():
    """The staged path sorts through the same helper as begin_pass."""
    c = telemetry.counter("pass.census_resorted")
    t = SparseTable(_conf(), seed=0)
    before = c.value()
    t.prepare_pass(_SORTED)
    t.begin_pass(_SORTED)
    t.end_pass()
    assert c.value() == before
    t.prepare_pass(_SORTED[::-1].copy())
    t.begin_pass(_SORTED)
    assert c.value() == before + 1
    np.testing.assert_array_equal(t._pass_keys, _SORTED)
    t.end_pass()


def test_pull_gathers_and_dead_row_reads_zero():
    t = SparseTable(_conf())
    t.begin_pass(np.array([10, 20, 30], dtype=np.uint64))
    K = 6
    keys = np.zeros(K, dtype=np.uint64)
    keys[:4] = [20, 10, 20, 555]  # 555 not in pass census
    plan = t.plan_keys(keys, 4)
    assert plan.n_missing == 1
    rows = np.asarray(pull_rows(t.values, jnp.asarray(plan.idx)))
    vals = np.asarray(t.values)
    pk = np.array([10, 20, 30], dtype=np.uint64)
    np.testing.assert_allclose(rows[0], vals[np.searchsorted(pk, 20)])
    np.testing.assert_allclose(rows[1], vals[np.searchsorted(pk, 10)])
    np.testing.assert_allclose(rows[3], 0.0)  # missing key
    np.testing.assert_allclose(rows[4:], 0.0)  # padding


def test_push_matches_numpy_adagrad_oracle():
    conf = _conf()
    t = SparseTable(conf, seed=1)
    pk = np.array([5, 9, 14], dtype=np.uint64)
    t.begin_pass(pk)
    v0 = np.asarray(t.values).copy()
    K = 8
    keys = np.zeros(K, dtype=np.uint64)
    batch_keys = [9, 5, 9, 14]  # key 9 occurs twice -> grads must merge
    keys[:4] = batch_keys
    clicks = np.array([1.0, 0.0, 0.0, 1.0])
    plan = t.plan_keys(keys, 4)
    rng = np.random.default_rng(2)
    row_grads = np.zeros((K, conf.row_width), dtype=np.float32)
    row_grads[:4, 2:] = rng.normal(size=(4, 4)).astype(np.float32)
    key_clicks = np.zeros(K, dtype=np.float32)
    key_clicks[:4] = clicks

    idx, uniq_idx, inverse, mask = _plan_arrays(plan)
    new_v, new_g2 = push_and_update(
        t.values, t.g2sum, jnp.asarray(row_grads), idx, uniq_idx, inverse,
        mask, jnp.asarray(key_clicks), conf,
    )
    new_v, new_g2 = np.asarray(new_v), np.asarray(new_g2)

    # numpy oracle
    exp_v, exp_g2 = v0.copy(), np.zeros(v0.shape[0], dtype=np.float32)
    for key in set(batch_keys):
        occ = [i for i, k in enumerate(batch_keys) if k == key]
        row = int(np.searchsorted(pk, key))
        g = row_grads[occ, 2:].sum(axis=0)
        g = np.clip(g, -conf.grad_clip, conf.grad_clip)
        add_g2 = float((g * g).mean())
        scale = conf.learning_rate * np.sqrt(
            conf.initial_g2sum / (conf.initial_g2sum + add_g2)
        )
        exp_v[row, 2:] -= scale * g
        exp_v[row, 0] += len(occ)  # show
        exp_v[row, 1] += clicks[occ].sum()  # clk
        exp_g2[row] += add_g2
    np.testing.assert_allclose(new_v, exp_v, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(new_g2, exp_g2, rtol=1e-5, atol=1e-6)
    # dead row still zero
    np.testing.assert_allclose(new_v[t.dead_row], 0.0)


def _merged_push_case(co, rng):
    """A hand-made plan over a 12-row pass table (row 11 dead, row 10 a
    scratch row): K = 16 occurrences of which 10 real — five live keys
    with duplicates and one census-missing key at its scratch row — and 6
    padding (key_mask 0, inverse at the last slot); U = 8 slots of which
    the last two clamp to the dead row."""
    conf = _conf(cvm_offset=co)
    P, W, K, dead = 12, conf.row_width, 16, 11
    uniq_idx = np.array([3, 0, 7, 5, 9, 10, dead, dead], dtype=np.int32)
    inverse = np.array([0, 1, 0, 2, 3, 3, 3, 4, 5, 1] + [7] * 6, np.int32)
    mask = np.array([1.0] * 10 + [0.0] * 6, dtype=np.float32)
    values = rng.normal(size=(P, W)).astype(np.float32)
    values[:, :co] = rng.integers(0, 50, size=(P, co))
    g2sum = rng.uniform(0.0, 2.0, size=P).astype(np.float32)
    values[dead] = 0.0
    g2sum[dead] = 0.0
    row_grads = np.zeros((K, W), dtype=np.float32)
    row_grads[:, co:] = rng.normal(size=(K, W - co))  # padding rows too
    clicks = (rng.integers(0, 2, size=K) * mask).astype(np.float32)
    extras = (rng.integers(0, 3, size=(K, co - 2)) * mask[:, None]).astype(
        np.float32)
    lr = rng.uniform(0.01, 0.2, size=uniq_idx.shape[0]).astype(np.float32)
    return conf, values, g2sum, row_grads, uniq_idx, inverse, mask, clicks, \
        extras, lr


def _numpy_push(conf, values, g2sum, row_grads, uniq_idx, inverse, mask,
                clicks, extras, lr):
    """Independent reference: np.add.at per column, adagrad per slot."""
    co, U, dead = conf.cvm_offset, uniq_idx.shape[0], values.shape[0] - 1
    inc = np.zeros((U, co), dtype=np.float32)
    np.add.at(inc[:, 0], inverse, mask)
    np.add.at(inc[:, 1], inverse, clicks)
    if extras is not None:
        for c in range(co - 2):
            np.add.at(inc[:, 2 + c], inverse, extras[:, c])
    g = np.zeros((U, values.shape[1] - co), dtype=np.float32)
    for c in range(g.shape[1]):
        np.add.at(g[:, c], inverse, row_grads[:, co + c])
    g = np.clip(g, -conf.grad_clip, conf.grad_clip)
    add_g2 = (g * g).mean(axis=1)
    scale = (conf.learning_rate if lr is None else lr) * np.sqrt(
        conf.initial_g2sum / (conf.initial_g2sum + g2sum[uniq_idx] + add_g2))
    exp_v, exp_g2 = values.copy(), g2sum.copy()
    for u, row in enumerate(uniq_idx):
        if row == dead:
            continue
        exp_v[row, :co] += inc[u]
        exp_v[row, co:] -= scale[u] * g[u]
        exp_g2[row] += add_g2[u]
    return exp_v, exp_g2


@pytest.mark.parametrize("garbage", [False, True], ids=["clean", "garbage"])
@pytest.mark.parametrize("with_lr", [False, True], ids=["lr0", "uniq_lr"])
@pytest.mark.parametrize("co,with_extras", [(2, False), (3, False), (3, True)],
                         ids=["co2", "co3-noext", "co3-ext"])
def test_push_one_reduction_matches_numpy_reference(co, with_extras, with_lr,
                                                    garbage):
    """The push's one occurrence-sized reduction against np.add.at per
    column: duplicates, padding at the last slot, a census-missing key at
    its scratch row, dead-clamped slots.  Counters bit for bit, also when
    the cotangent holds garbage in its show/clk columns (they are
    replaced, not added to)."""
    rng = np.random.default_rng(100 * co + 10 * with_extras + with_lr)
    (conf, values, g2sum, row_grads, uniq_idx, inverse, mask, clicks,
     extras, lr) = _merged_push_case(co, rng)
    extras = extras if with_extras else None
    lr = lr if with_lr else None
    exp_v, exp_g2 = _numpy_push(conf, values, g2sum, row_grads, uniq_idx,
                                inverse, mask, clicks, extras, lr)
    if garbage:
        row_grads = row_grads.copy()
        row_grads[:, :co] = rng.normal(size=(row_grads.shape[0], co)) * 1e3
    new_v, new_g2 = jax.jit(functools.partial(push_and_update, conf=conf))(
        jnp.asarray(values), jnp.asarray(g2sum), jnp.asarray(row_grads),
        jnp.zeros(inverse.shape[0], jnp.int32), jnp.asarray(uniq_idx),
        jnp.asarray(inverse), jnp.asarray(mask), jnp.asarray(clicks),
        key_extras=None if extras is None else jnp.asarray(extras),
        uniq_lr=None if lr is None else jnp.asarray(lr),
    )
    new_v, new_g2 = np.asarray(new_v), np.asarray(new_g2)
    np.testing.assert_array_equal(new_v[:, :co], exp_v[:, :co])
    np.testing.assert_allclose(new_v[:, co:], exp_v[:, co:],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(new_g2, exp_g2, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(new_v[-1], 0.0)
    assert new_g2[-1] == 0.0


def _scatter_adds(jaxpr):
    """Every scatter-add equation of a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_scatter_adds(sub))
    return found


@pytest.mark.parametrize("co", [2, 3])
def test_push_holds_one_occurrence_sized_scatter_add(co):
    """Structural: of the jitted push's scatter-adds exactly ONE takes
    updates with K rows (there were three, four with extras): show, click
    and the extras ride the merge's index pass."""
    rng = np.random.default_rng(co)
    (conf, values, g2sum, row_grads, uniq_idx, inverse, mask, clicks,
     extras, lr) = _merged_push_case(co, rng)
    K, U = inverse.shape[0], uniq_idx.shape[0]
    closed = jax.make_jaxpr(functools.partial(push_and_update, conf=conf))(
        values, g2sum, row_grads, np.zeros(K, np.int32), uniq_idx, inverse,
        mask, clicks, key_extras=extras if co > 2 else None, uniq_lr=lr)
    rows = [e.invars[2].aval.shape[0] for e in _scatter_adds(closed.jaxpr)]
    # the merge over the K occurrences, the two scatters over the U slots
    assert sorted(rows) == [U, U, K], rows


def test_missing_key_grads_do_not_corrupt_dead_row():
    conf = _conf()
    t = SparseTable(conf)
    t.begin_pass(np.array([1], dtype=np.uint64))
    K = 4
    keys = np.zeros(K, dtype=np.uint64)
    keys[:2] = [1, 777]  # 777 missing -> dead row
    plan = t.plan_keys(keys, 2)
    grads = np.ones((K, conf.row_width), dtype=np.float32)
    idx, uniq_idx, inverse, mask = _plan_arrays(plan)
    new_v, new_g2 = push_and_update(
        t.values, t.g2sum, jnp.asarray(grads), idx, uniq_idx, inverse,
        mask, jnp.zeros(K), conf,
    )
    np.testing.assert_allclose(np.asarray(new_v)[t.dead_row], 0.0)
    np.testing.assert_allclose(np.asarray(new_g2)[t.dead_row], 0.0)


def test_pass_roundtrip_persists_and_second_pass_sees_updates():
    conf = _conf()
    t = SparseTable(conf, seed=3)
    t.begin_pass(np.array([2, 4], dtype=np.uint64))
    # manually bump a row as if trained
    t.values = t.values.at[0, 2:].set(7.0)
    t.values = t.values.at[0, 0].add(5.0)  # show
    t.end_pass()
    assert t.n_features == 2
    # next pass: one old key, one new
    t.begin_pass(np.array([2, 8], dtype=np.uint64))
    vals = np.asarray(t.values)
    np.testing.assert_allclose(vals[0, 2:], 7.0)  # key 2 kept its update
    np.testing.assert_allclose(vals[0, 0], 5.0)
    t.end_pass()
    assert t.n_features == 3


def test_create_threshold_hides_cold_embeddings():
    conf = _conf(create_threshold=3.0)
    t = SparseTable(conf, seed=4)
    t.begin_pass(np.array([1, 2], dtype=np.uint64))
    t.values = t.values.at[0, 0].set(5.0)  # key 1 hot
    t.values = t.values.at[1, 0].set(1.0)  # key 2 cold
    t.values = t.values.at[:2, 2:].set(1.5)
    keys = np.array([1, 2], dtype=np.uint64)
    plan = t.plan_keys(keys, 2)
    rows = np.asarray(
        pull_rows(t.values, jnp.asarray(plan.idx), create_threshold=3.0)
    )
    np.testing.assert_allclose(rows[0, 2:], 1.5)  # visible
    np.testing.assert_allclose(rows[1, 2:], 0.0)  # hidden
    np.testing.assert_allclose(rows[1, 0], 1.0)  # counters still visible


def test_shrink_decays_and_evicts():
    conf = _conf(delete_threshold=1.0, show_decay_rate=0.5)
    t = SparseTable(conf)
    t.begin_pass(np.array([1, 2], dtype=np.uint64))
    t.values = t.values.at[0, 0].set(4.0)  # -> 2.0 after decay, kept
    t.values = t.values.at[1, 0].set(1.0)  # -> 0.5 after decay, evicted
    t.end_pass()
    evicted = t.shrink()
    assert evicted == 1
    assert t.n_features == 1
    sd = t.state_dict()
    assert sd["keys"][0] == 1
    np.testing.assert_allclose(sd["values"][0, 0], 2.0)


def test_delta_tracking():
    conf = _conf()
    t = SparseTable(conf, seed=5)
    t.begin_pass(np.array([1, 2], dtype=np.uint64))
    t.end_pass()
    delta = t.pop_delta()
    assert set(delta["keys"].tolist()) == {1, 2}
    t.begin_pass(np.array([2, 3], dtype=np.uint64))
    t.end_pass()
    delta = t.pop_delta()
    assert set(delta["keys"].tolist()) == {2, 3}
    # apply_delta restores rows on a fresh table
    t2 = SparseTable(conf)
    t2.apply_delta(delta)
    assert t2.n_features == 2


# -- the plan's unique side: a bucket over the distinct keys, not K -------- #
_NATIVE = build_census_index(np.arange(4, dtype=np.uint64)) is not None
_PLANNERS = [
    pytest.param(False, id="numpy"),
    pytest.param(True, id="native", marks=pytest.mark.skipif(
        not _NATIVE, reason="native planner did not build")),
]
_CENSUS = np.arange(1000, 9000, dtype=np.uint64)


@pytest.fixture
def planner(request):
    flags.set("use_native_planner", request.param)
    yield request.param
    flags.set("use_native_planner", True)


def _buffer(K, n_hit, n_miss, n_real, seed=0):
    """A K-slot key buffer: n_real occurrences drawn (with duplicates) from
    n_hit census keys and n_miss keys the census lacks, then padding."""
    rng = np.random.default_rng(seed)
    pool = np.concatenate([
        rng.choice(_CENSUS, n_hit, replace=False),
        np.arange(1 << 40, (1 << 40) + n_miss, dtype=np.uint64)])
    keys = np.zeros(K, np.uint64)
    keys[:n_real] = np.concatenate(
        [pool, rng.choice(pool, n_real - pool.shape[0])])[rng.permutation(n_real)]
    return keys


def _plan_counters(side):
    return tuple(telemetry.counter(f"plan.{side}_{n}").value()
                 for n in ("keys", "slots", "grows"))


_uniq_counters = functools.partial(_plan_counters, "uniq")
_occ_counters = functools.partial(_plan_counters, "occ")


@pytest.mark.parametrize("planner", _PLANNERS, indirect=True)
def test_plan_unique_side_follows_distinct_keys(planner):
    """Duplicates, census-missing keys and padding in one buffer: uniq_idx
    is U_b < K long, its targets are distinct, the keys sit at the front
    and the padding at the bucket's last slot."""
    t = SparseTable(_conf(), seed=0)
    t.begin_pass(_CENSUS)
    K, n_real, n_uniq = 4096, 3000, 560
    keys = _buffer(K, 500, 60, n_real)
    plan = t.plan_keys(keys, n_real)
    U = plan.uniq_idx.shape[0]
    assert U == 1024 < K  # 1.25 x 560 rounds up to the smallest bucket
    assert plan.n_uniq == n_uniq and plan.n_missing == 60
    for a in (plan.idx, plan.inverse, plan.key_mask):
        assert a.shape == (K,)  # the occurrence side keeps the capacity
    assert plan.inverse[:n_real].max() == n_uniq - 1 <= U - 2
    assert (plan.inverse[n_real:] == U - 1).all()
    assert (plan.idx[n_real:] == t.dead_row).all()
    live = plan.uniq_idx[plan.uniq_idx != t.dead_row]
    assert np.unique(live).shape[0] == live.shape[0] == U  # scratch fits
    # the keys' slots: the live row when found, the slot's scratch row else
    tgt = plan.uniq_idx[plan.inverse[:n_real]]
    found = np.isin(keys[:n_real], _CENSUS)
    np.testing.assert_array_equal(
        tgt[found], np.searchsorted(_CENSUS, keys[:n_real][found]))
    n = _CENSUS.shape[0]
    np.testing.assert_array_equal(
        tgt[~found], n + plan.inverse[:n_real][~found])
    # every slot past the keys aims at its own scratch row
    np.testing.assert_array_equal(
        plan.uniq_idx[n_uniq:], n + np.arange(n_uniq, U))


@pytest.mark.parametrize("planner", _PLANNERS, indirect=True)
def test_plan_bucket_grows_once_and_never_shrinks(planner):
    t = SparseTable(_conf(), seed=0)
    t.begin_pass(_CENSUS)
    K = 4096
    k0, s0, g0 = _uniq_counters()
    sizes = []
    # (distinct keys, bucket): 1023 keys still leave slot 1023 to the
    # padding; 1024 do not, and the bucket jumps past 1.25 x 1024
    for n_uniq, want in ((300, 1024), (1023, 1024), (1024, 2048),
                         (200, 2048), (1600, 2048)):
        plan = t.plan_keys(_buffer(K, n_uniq, 0, n_uniq + 50, seed=n_uniq),
                           n_uniq + 50)
        assert plan.n_uniq == n_uniq
        assert plan.uniq_idx.shape[0] == want
        assert plan.inverse[:n_uniq + 50].max() == n_uniq - 1 <= want - 2
        assert (plan.inverse[n_uniq + 50:] == want - 1).all()
        sizes.append(want)
    k1, s1, g1 = _uniq_counters()
    assert k1 - k0 == 300 + 1023 + 1024 + 200 + 1600
    assert s1 - s0 == sum(sizes)
    assert g1 - g0 == 2  # the first plan's 0 -> 1024, then 1024 -> 2048
    # the mark is the table's: it outlives the pass, and sizes the next
    # pass's scratch region in place of the key capacity
    t.end_pass()
    assert t._stage_cap(8000) == 16384  # 8000 + 1 + 2048, not + 4096
    t.begin_pass(_CENSUS)
    assert t.plan_keys(_buffer(K, 10, 0, 20), 20).uniq_idx.shape[0] == 2048
    assert _uniq_counters()[2] - g0 == 2


@pytest.mark.parametrize("planner", _PLANNERS, indirect=True)
def test_plan_bucket_stops_at_the_key_capacity(planner):
    """A buffer with no room for a bucket (all keys distinct, no padding)
    plans at U == K as before: every batch fits there by construction."""
    t = SparseTable(_conf(), seed=0)
    t.begin_pass(_CENSUS)
    K = 1500
    keys = _CENSUS[:K].copy()
    plan = t.plan_keys(keys, K)
    assert plan.uniq_idx.shape[0] == K and plan.n_uniq == K
    np.testing.assert_array_equal(np.sort(plan.uniq_idx), np.arange(K))
    # a smaller buffer than the mark plans at its own capacity
    small = t.plan_keys(_CENSUS[:64].copy(), 40)
    assert small.uniq_idx.shape[0] == 64
    assert (small.inverse[40:] == 63).all()


def _assert_same_job(got, want, counters=(0,)):
    """Two runs' (metrics, live rows and g2sum, store state, dense leaves)
    are bit-identical, and the counter columns counted something."""
    (m_a, live_a, state_a, params_a), (m_b, live_b, state_b, params_b) = (
        got, want)
    assert m_a["loss"] == m_b["loss"]
    for a, b in zip(live_a + tuple(params_a), live_b + tuple(params_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for c in counters:  # shows (and the third counter) were counted
        assert live_a[0][:, c].sum() > 0
    np.testing.assert_array_equal(state_a["keys"], state_b["keys"])
    np.testing.assert_array_equal(state_a["values"], state_b["values"])


# case -> (per-slot lr, unique side of the run under test, of the run it
# must equal).  "bucket" is the plan as the table emits it, "capacity" the
# same plan with the scratch slots it dropped appended again (uniq_idx at
# K, as before the bucket), "moved" the bucket for the first batch and K
# from the second on: a mark that moves between two steps of one pass.
_PUSH_CASES = {
    "plain": (False, "bucket", "capacity"),
    "slot_lr": (True, "bucket", "capacity"),
    "moved": (False, "moved", "bucket"),
    # the feed's uniq_lr follows the plan's length from step to step
    "moved_slot_lr": (True, "moved", "bucket"),
}


@pytest.mark.parametrize("case", list(_PUSH_CASES))
def test_bucketed_push_equals_push_at_capacity(tmp_path, case):
    """Same work, same result: three train_from_dataset steps with the
    plan's unique side at its bucket against the same steps with uniq_idx
    at the key capacity K — live rows, g2sum, show/click and the dense
    parameters are bit-identical."""
    S, B, K = 3, 64, 64 * 32
    slot_lr, under_test, reference = _PUSH_CASES[case]
    conf = make_synth_config(n_sparse_slots=S, dense_dim=2, batch_size=B,
                             max_feasigns_per_ins=32)
    files = write_synth_files(str(tmp_path), n_files=1, ins_per_file=3 * B,
                              n_sparse_slots=S, vocab_per_slot=40,
                              dense_dim=2, seed=5)

    def run(side):
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        tconf = _conf(slot_learning_rates=((0, 0.3), (2, 0.02))
                      if slot_lr else ())
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(
            CtrDnn(S, tconf.row_width, dense_dim=2, hidden=(8,)), tconf,
            TrainerConfig(auc_buckets=1 << 10), seed=0)
        assert (trainer._slot_lr_vec is not None) == slot_lr
        plan_keys = table.plan_keys
        lengths = []

        def plan_at(keys, n_real):
            plan = plan_keys(keys, n_real)
            U = plan.uniq_idx.shape[0]
            if side == "capacity" or (side == "moved" and lengths):
                tail = np.minimum(
                    table._pass_keys.shape[0] + np.arange(U, K), table.dead_row)
                plan = dataclasses.replace(plan, uniq_idx=np.concatenate(
                    [plan.uniq_idx, tail.astype(np.int32)]))
            lengths.append(plan.uniq_idx.shape[0])
            return plan

        table.plan_keys = plan_at
        table.begin_pass(ds.unique_keys())
        m = trainer.train_from_dataset(ds, table)
        assert m["steps"] == 3
        assert lengths == {"bucket": [1024] * 3, "capacity": [K] * 3,
                           "moved": [1024, K, K]}[side]
        n = table._pass_keys.shape[0]
        live = (np.asarray(table.values)[:n].copy(),
                np.asarray(table.g2sum)[:n].copy())
        table.end_pass()
        state = table.state_dict()
        ds.close()
        return m, live, state, jax.tree.leaves(trainer.params)

    _assert_same_job(run(under_test), run(reference))


# -- the plan's occurrence side: a bucket over the real occurrences, not K - #
@pytest.mark.parametrize("n_real,want", [
    (0, 1024), (1, 1024), (964, 1024), (965, 2048),  # 964 + 60 = 1,024: full
    (3000, 4096), (15421, 16384), (15422, 17408),
    (106_496, 114_688), (108_000, 122_880),  # ctr_dnn_steady: 2048 x 52
    (212_992, 229_376), (216_000, 245_760),  # xdeepfm_steady: 4096 x 52
])
def test_occ_bucket_rule(n_real, want):
    """A sixteenth of headroom, rounded up to a sixteenth of the count's
    power of two, 1,024 at least: whole tiles and one length for a count
    that is steady to a percent."""
    from paddlebox_tpu.sparse.table import _occ_bucket

    got = _occ_bucket(n_real)
    assert got == want and got % 1024 == 0
    assert got >= n_real + n_real // 16


@pytest.mark.parametrize("planner", _PLANNERS, indirect=True)
def test_plan_occurrence_side_follows_real_occurrences(planner):
    """Duplicates, census-missing keys and padding in one buffer: idx,
    inverse and key_mask are L < K long, equal to the plan at K over the
    real occurrences, and the padding in [n, L) keeps its meaning (dead
    row, the bucket's last unique slot, mask 0)."""
    K, n_real, n_uniq, L = 16384, 3000, 560, 4096
    keys = _buffer(K, 500, 60, n_real)
    plans = {}
    for side in ("bucket", "capacity"):
        t = SparseTable(_conf(), seed=0)
        t.begin_pass(_CENSUS)
        if side == "capacity":
            t._plan_occ_slots = K  # the mark at K: the plan as it was
        plans[side] = t.plan_keys(keys, n_real)
    plan, full = plans["bucket"], plans["capacity"]
    U = plan.uniq_idx.shape[0]
    assert U == full.uniq_idx.shape[0] == 1024
    for a, b in ((plan.idx, full.idx), (plan.inverse, full.inverse),
                 (plan.key_mask, full.key_mask)):
        assert a.shape == (L,) and b.shape == (K,)
        np.testing.assert_array_equal(a, b[:L])  # padding alike: b's is longer
    np.testing.assert_array_equal(plan.uniq_idx, full.uniq_idx)
    assert (plan.n_uniq, plan.n_missing) == (n_uniq, 60) == (
        full.n_uniq, full.n_missing)
    assert (plan.key_mask[:n_real] == 1).all()
    assert (plan.idx[n_real:] == t.dead_row).all()
    assert (plan.inverse[n_real:] == U - 1).all()
    assert (plan.key_mask[n_real:] == 0).all()
    found = np.isin(keys[:n_real], _CENSUS)
    np.testing.assert_array_equal(
        plan.idx[:n_real][found],
        np.searchsorted(_CENSUS, keys[:n_real][found]))
    assert (plan.idx[:n_real][~found] == t.dead_row).all()


@pytest.mark.parametrize("planner", _PLANNERS, indirect=True)
def test_occ_bucket_grows_once_and_never_shrinks(planner):
    t = SparseTable(_conf(), seed=0)
    t.begin_pass(_CENSUS)
    K = 16384
    k0, s0, g0 = _occ_counters()
    sizes = []
    # (real occurrences, bucket): 4096 occurrences fill the bucket to its
    # last slot and fit; one more moves it past 4097 + 256
    for n_real, want in ((3000, 4096), (4096, 4096), (4097, 5120),
                         (200, 5120), (5000, 5120)):
        plan = t.plan_keys(_buffer(K, 150, 10, n_real, seed=n_real), n_real)
        for a in (plan.idx, plan.inverse, plan.key_mask):
            assert a.shape == (want,)
        assert int(plan.key_mask.sum()) == n_real
        assert plan.uniq_idx.shape[0] == 1024  # the unique side's own mark
        sizes.append(want)
    k1, s1, g1 = _occ_counters()
    assert k1 - k0 == 3000 + 4096 + 4097 + 200 + 5000
    assert s1 - s0 == sum(sizes)
    assert g1 - g0 == 2  # the first plan's 0 -> 4096, then 4096 -> 5120
    # the mark is the table's: it outlives the pass and meets the next
    # dataset's batches at the same length
    t.end_pass()
    t.begin_pass(_CENSUS)
    assert t.plan_keys(_buffer(K, 10, 0, 20), 20).idx.shape[0] == 5120
    # never past K, where every batch fits by construction
    assert t.plan_keys(_buffer(K, 150, 10, 16000), 16000).idx.shape == (K,)
    assert t.plan_keys(_buffer(K, 150, 10, K), K).idx.shape == (K,)
    assert _occ_counters()[2] - g0 == 3


@pytest.mark.parametrize("planner", _PLANNERS, indirect=True)
def test_occ_bucket_is_the_capacity_where_the_buffer_is_full(planner):
    """A buffer whose every slot is a real occurrence (a decoder's token
    buffer: duplicates, no padding) plans at L == K with a fill of 1.0, and
    the unique side is capped by it."""
    t = SparseTable(_conf(), seed=0)
    t.begin_pass(_CENSUS)
    K = 6000
    k0, s0, g0 = _occ_counters()
    for seed in (0, 1):
        plan = t.plan_keys(_buffer(K, 700, 0, K, seed=seed), K)
        for a in (plan.idx, plan.inverse, plan.key_mask):
            assert a.shape == (K,)
        assert (plan.key_mask == 1).all()
        assert plan.uniq_idx.shape[0] == 1024
    k1, s1, g1 = _occ_counters()
    assert (k1 - k0, s1 - s0, g1 - g0) == (2 * K, 2 * K, 1)
    # all distinct and no padding: the unique side stops at L == K too
    plan = t.plan_keys(_CENSUS[:K].copy(), K)
    assert plan.uniq_idx.shape[0] == K and plan.n_uniq == K
    # a shorter buffer than the mark plans at its own capacity
    assert t.plan_keys(_CENSUS[:64].copy(), 40).idx.shape == (64,)


# case -> (table and trainer options, occurrence side of the run under
# test, of the run it must equal).  "bucket" is the plan as the table emits
# it (L < K), "capacity" the same job with the mark set to K beforehand
# (every occurrence-sized leaf at the key capacity, as before the bucket),
# "moved" the bucket for the first batch and K from the second on.
_OCC_CASES = {
    "plain": ({}, "bucket", "capacity"),
    "slot_lr": ({"slot_lr": True}, "bucket", "capacity"),
    "counter_label_tasks": ({"conv": True}, "bucket", "capacity"),
    "sequence_slot": ({"seq": True}, "bucket", "capacity"),
    "moved": ({}, "moved", "bucket"),
    "moved_counter_label_tasks": ({"conv": True}, "moved", "bucket"),
}


@pytest.mark.parametrize("case", list(_OCC_CASES))
def test_occurrence_bucket_equals_occurrences_at_capacity(tmp_path, case):
    """Same work, same result: three train_from_dataset steps with the
    occurrence side at its bucket against the same steps at the key
    capacity K — live rows, g2sum, show/click (and the third counter), the
    dense parameters and the loss are bit-identical.  Padding contributed
    zeros before and is absent now."""
    from paddlebox_tpu.models import LongSeqCtrDnn
    from paddlebox_tpu.train.trainer import _host_batch_dict

    S, B, T = 3, 64, 8
    K, L = B * 128, 1024
    opts, under_test, reference = _OCC_CASES[case]
    conv, seq = opts.get("conv", False), opts.get("seq", False)
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=2, batch_size=B, max_feasigns_per_ins=128,
        n_task_labels=int(conv),
        **(dict(sequence_slot="slot0", max_seq_len=T) if seq else {}))
    files = write_synth_files(str(tmp_path), n_files=1, ins_per_file=3 * B,
                              n_sparse_slots=S, vocab_per_slot=40,
                              dense_dim=2, seed=5, n_task_labels=int(conv))

    def run(side):
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        tconf = _conf(
            slot_learning_rates=((0, 0.3), (2, 0.02))
            if opts.get("slot_lr") else (),
            cvm_offset=3 if conv else 2)
        table = SparseTable(tconf, seed=0)
        if seq:
            model = LongSeqCtrDnn(S, tconf.row_width, dense_dim=2,
                                  hidden=(8,), max_seq_len=T)
        else:
            model = CtrDnn(S, tconf.row_width, dense_dim=2, hidden=(8,),
                           **(dict(layout="conv", cvm_offset=3)
                              if conv else {}))
        trainer = Trainer(model, tconf, TrainerConfig(
            auc_buckets=1 << 10,
            counter_label_tasks=(1,) if conv else ()), seed=0)
        plan_keys = table.plan_keys
        lengths = []
        if side == "capacity":
            table._plan_occ_slots = K

        def plan_at(keys, n_real):
            plan = plan_keys(keys, n_real)
            lengths.append(plan.idx.shape[0])
            if side == "moved":
                table._plan_occ_slots = K  # as a batch that did not fit
            return plan

        table.plan_keys = plan_at
        table.begin_pass(ds.unique_keys())
        if seq:  # the feed's padding index follows the plan's length
            batch = next(ds.batches())
            plan = plan_keys(batch.keys, batch.n_keys)
            n = plan.idx.shape[0]
            assert batch.seq_pos.max() == K  # the builder pads with K
            pos = _host_batch_dict(batch, plan, S)["seq_pos"]
            assert pos.max() == n  # one past the pulled rows: the zero row
            np.testing.assert_array_equal(pos == n, batch.seq_pos == K)
            np.testing.assert_array_equal(pos[pos < n],
                                          batch.seq_pos[batch.seq_pos < K])
        m = trainer.train_from_dataset(ds, table)
        assert m["steps"] == 3
        assert lengths == {"bucket": [L] * 3, "capacity": [K] * 3,
                           "moved": [L, K, K]}[side]
        n = table._pass_keys.shape[0]
        live = (np.asarray(table.values)[:n].copy(),
                np.asarray(table.g2sum)[:n].copy())
        table.end_pass()
        state = table.state_dict()
        ds.close()
        return m, live, state, jax.tree.leaves(trainer.params)

    _assert_same_job(run(under_test), run(reference),
                     counters=(0, 2) if conv else (0,))
