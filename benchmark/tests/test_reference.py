"""Each plain reference against the system's model at toy size: the same
weights and the same pulled rows give the same logits, and a whole step
gives the same loss, gradients and rows (on the CPU both are float32)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common
from benchmark.tests.toy import toy_cell


@pytest.mark.parametrize("config", ["ctr_dnn_criteo", "xdeepfm_criteo"])
def test_forward_agrees_with_the_system_model(config):
    from paddlebox_tpu.config import SparseTableConfig

    cfg = toy_cell(config).cfg
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    model = importlib.import_module("benchmark.models." + cfg["model"]).build(
        cfg, SparseTableConfig(embedding_dim=cfg["embedding_dim"]))
    params = ref.init_params(cfg, jax.random.PRNGKey(5))
    B, S, W = cfg["batch_size"], cfg["n_sparse_slots"], 2 + cfg[
        "embedding_dim"]
    rng = np.random.default_rng(0)
    K = B * S * 2
    rows = rng.normal(size=(K, W)).astype(np.float32) * 0.05
    rows[:, 0] = rng.integers(1, 9, K)
    rows[:, 1] = np.floor(rows[:, 0] * rng.random(K))
    seg = np.repeat(np.arange(B * S), 2).astype(np.int32)
    dense = rng.normal(size=(B, cfg["dense_dim"])).astype(np.float32)
    got = model.apply(params, jnp.asarray(rows), jnp.asarray(seg),
                      jnp.asarray(dense), B)
    feats = common.pooled_features(jnp.asarray(rows), jnp.asarray(seg), B, S)
    want = ref.logits(cfg, common.Ops(), params, feats, jnp.asarray(dense))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_the_float8_control_moves_the_logits():
    cfg = toy_cell("ctr_dnn_criteo").cfg
    ref = importlib.import_module("benchmark.reference.ctr_dnn")
    params = ref.init_params(cfg, jax.random.PRNGKey(1))
    feats = jnp.asarray(np.random.default_rng(1).normal(
        size=(8, cfg["n_sparse_slots"], 2 + cfg["embedding_dim"])),
        jnp.float32)
    dense = jnp.zeros((8, cfg["dense_dim"]), jnp.float32)
    a = ref.logits(cfg, common.Ops(), params, feats, dense)
    b = ref.logits(cfg, common.Ops("float8"), params, feats, dense)
    rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
    assert 0.005 < rel < 0.5
