"""Sparse table: pull/push/update parity vs a numpy oracle + pass lifecycle.

Covers numeric parity for pull/push/update and the
begin_pass -> train -> end_pass -> shrink cycle (reference semantics:
fleet/box_wrapper_impl.h:24-255, box_wrapper.cc:609-673,496-499).
"""

import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config import SparseTableConfig
from paddlebox_tpu.sparse import SparseTable, pull_rows, push_and_update


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
