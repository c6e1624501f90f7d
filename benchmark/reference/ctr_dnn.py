"""CTR-DNN, plainly: pooled slot features and the dense features side by
side through a ReLU tower to one logit (PaddleRec models/rank/dnn's
shape; the tower's widths come from the configuration)."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.reference import common


def input_dim(cfg: dict) -> int:
    return cfg["n_sparse_slots"] * (2 + cfg["embedding_dim"]) + cfg[
        "dense_dim"]


def init_params(cfg: dict, key) -> dict:
    return {"tower": common.init_mlp(key, input_dim(cfg), cfg["hidden"], 1)}


def logits(cfg: dict, ops, params: dict, feats, dense):
    x = jnp.concatenate([feats.reshape(feats.shape[0], -1), dense], axis=1)
    return common.mlp(ops, params["tower"], x)[:, 0]
