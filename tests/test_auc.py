"""Streaming AUC vs sklearn oracle.

Reference: BasicAucCalculator (fleet/box_wrapper.h:61-138, bucket kernels
box_wrapper.cu:1035-1060, final reduction box_wrapper.cc:321-400).
"""

import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.metrics import (
    compute_metrics,
    init_auc_state,
    merge_auc_states,
    update_auc_state,
)

try:
    from sklearn.metrics import roc_auc_score

    HAVE_SKLEARN = True
except ImportError:  # fall back to a direct pairwise oracle
    HAVE_SKLEARN = False


def _oracle_auc(preds, labels):
    if HAVE_SKLEARN:
        return roc_auc_score(labels, preds)
    pos = preds[labels == 1][:, None]
    neg = preds[labels == 0][None, :]
    return float(((pos > neg).mean() + 0.5 * (pos == neg).mean()))


def test_auc_matches_oracle_exactly_on_bucket_centers():
    nb = 1 << 16
    rng = np.random.default_rng(0)
    n = 4000
    # quantize predictions to bucket centers so bucketing is exact
    preds = (rng.integers(0, nb, size=n) + 0.5) / nb
    labels = (rng.random(n) < preds).astype(np.float64)  # correlated
    state = init_auc_state(nb)
    # feed in chunks with masks, like training batches
    for lo in range(0, n, 512):
        chunk = slice(lo, lo + 512)
        p, l = preds[chunk], labels[chunk]
        pad = 512 - p.shape[0]
        mask = np.concatenate([np.ones_like(p), np.zeros(pad)])
        p = np.concatenate([p, np.full(pad, 0.99)])  # padding must not count
        l = np.concatenate([l, np.ones(pad)])
        state = update_auc_state(
            state, jnp.asarray(p), jnp.asarray(l), jnp.asarray(mask)
        )
    m = compute_metrics(state)
    assert abs(m["auc"] - _oracle_auc(preds, labels)) < 1e-6
    np.testing.assert_allclose(m["mae"], np.abs(preds - labels).mean(), rtol=1e-5)
    np.testing.assert_allclose(
        m["rmse"], np.sqrt(((preds - labels) ** 2).mean()), rtol=1e-5
    )
    np.testing.assert_allclose(m["actual_ctr"], labels.mean(), rtol=1e-5)
    np.testing.assert_allclose(m["predicted_ctr"], preds.mean(), rtol=1e-5)
    assert m["count"] == n


def test_auc_merge_states_equals_single_stream():
    nb = 1 << 12
    rng = np.random.default_rng(1)
    n = 1024
    preds = (rng.integers(0, nb, size=n) + 0.5) / nb
    labels = (rng.random(n) < 0.3).astype(np.float64)
    ones = jnp.ones(n // 2)
    s1 = update_auc_state(
        init_auc_state(nb), jnp.asarray(preds[: n // 2]),
        jnp.asarray(labels[: n // 2]), ones,
    )
    s2 = update_auc_state(
        init_auc_state(nb), jnp.asarray(preds[n // 2 :]),
        jnp.asarray(labels[n // 2 :]), ones,
    )
    merged = compute_metrics(merge_auc_states(s1, s2))
    full = compute_metrics(
        update_auc_state(
            init_auc_state(nb), jnp.asarray(preds), jnp.asarray(labels), jnp.ones(n)
        )
    )
    assert abs(merged["auc"] - full["auc"]) < 1e-12
    assert merged["count"] == full["count"]


def test_degenerate_single_class_auc():
    state = update_auc_state(
        init_auc_state(64), jnp.asarray([0.2, 0.7]), jnp.asarray([1.0, 1.0]),
        jnp.ones(2),
    )
    assert compute_metrics(state)["auc"] == 0.5  # no negatives -> undefined -> 0.5


def test_exact_accumulation_past_2pow24():
    """f32 saturates at 2^24 (x + 1.0 == x); uint32 buckets and Kahan moment
    sums must keep counting exactly."""
    import jax
    from paddlebox_tpu.metrics.auc import kahan_value

    state = init_auc_state(64)
    big = np.uint32(1 << 24)
    # pre-seed the accumulators as if 2^24 positives already landed in one
    # bucket (walking there one batch at a time would take minutes)
    state = state._replace(
        pos=state.pos.at[32].set(big),
        count=jnp.asarray(big),
        label_sum=jnp.asarray(big),
        abserr=jnp.asarray([float(1 << 24), 0.0], dtype=jnp.float32),
    )

    # 1000 more single-positive updates, jit-rolled like the train step
    def body(_, s):
        return update_auc_state(
            s, jnp.asarray([32.5 / 64]), jnp.asarray([1.0]), jnp.ones(1)
        )

    state = jax.jit(
        lambda s: jax.lax.fori_loop(0, 1000, body, s)
    )(state)
    assert int(state.pos[32]) == (1 << 24) + 1000  # f32 would stay at 2^24
    assert int(state.count) == (1 << 24) + 1000
    assert int(state.label_sum) == (1 << 24) + 1000
    # Kahan: adding 1000 * |pred-label| ≈ 0.492 increments to a 2^24-sized
    # sum; a plain f32 sum would absorb every one of them (0.492 < ulp=2.0)
    got = kahan_value(state.abserr) - float(1 << 24)
    want = 1000 * (1.0 - 32.5 / 64)
    assert abs(got - want) < 0.05 * want
