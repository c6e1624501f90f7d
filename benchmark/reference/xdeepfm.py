"""xDeepFM (Lian et al., KDD 2018, arXiv:1803.05170), plainly.

CIN layer k over the field matrix X0 [B, m, D] (eq. 6):
    X_k[b,h,d] = sum_ij W_k[h,i,j] * X_{k-1}[b,i,d] * X0[b,j,d]
written as the outer product Z = X_{k-1} (x) X0 per embedding column and
its compression by W_k; each layer's maps are sum-pooled over D (eq. 7),
concatenated with the DNN's output and the linear term, and one linear
unit gives the logit (eq. 9).  Departures, as the repo's model has them:
the fields are the 26 sparse slots' pooled embeddings (the paper buckets
the 13 integer features into 13 more fields; here they reach the DNN and
the linear part as dense inputs), every CIN map goes to the output (no
split-half), and the DNN's last layer is linear with the hidden width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common


def input_dim(cfg: dict) -> int:
    return cfg["n_sparse_slots"] * (2 + cfg["embedding_dim"]) + cfg[
        "dense_dim"]


def init_params(cfg: dict, key) -> dict:
    m = cfg["n_sparse_slots"]
    cin_sizes, hidden = cfg["cin_layers"], cfg["hidden"]
    ks = jax.random.split(key, len(cin_sizes) + 3)
    cin, prev = [], m
    for i, h in enumerate(cin_sizes):
        s = float(1.0 / np.sqrt(prev * m))
        cin.append(jax.random.uniform(ks[i], (h, prev, m), jnp.float32,
                                      -s, s))
        prev = h
    d_in = input_dim(cfg)
    return {
        "cin": cin,
        "deep": common.init_mlp(ks[-3], d_in, hidden, hidden[-1]),
        "linear": common.xavier(ks[-2], d_in, 1),
        "head": common.xavier(ks[-1], sum(cin_sizes) + hidden[-1] + 1, 1),
    }


def logits(cfg: dict, ops, params: dict, feats, dense):
    B = feats.shape[0]
    x0 = feats[:, :, 2:]  # [B, m, D]: embeddings without the counters
    flat = jnp.concatenate([feats.reshape(B, -1), dense], axis=1)
    xk, maps = x0, []
    for w in params["cin"]:
        z = ops.einsum("bid,bjd->bijd", xk, x0)  # [B, H_{k-1}, m, D]
        xk = ops.einsum("hij,bijd->bhd", w, z)
        maps.append(xk.sum(axis=2))
    deep = common.mlp(ops, params["deep"], flat)
    lin = ops.dot(flat, params["linear"]["w"]) + params["linear"]["b"]
    z = jnp.concatenate(maps + [deep, lin], axis=1)
    return (ops.dot(z, params["head"]["w"]) + params["head"]["b"])[:, 0]
