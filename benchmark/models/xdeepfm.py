"""Program side of the ``xdeepfm`` model name."""

from __future__ import annotations

from benchmark import costs


def build(cfg: dict, table_conf):
    from paddlebox_tpu.models import XDeepFM

    return XDeepFM(cfg["n_sparse_slots"], table_conf.row_width,
                   dense_dim=cfg["dense_dim"], hidden=tuple(cfg["hidden"]),
                   cin_layers=tuple(cfg["cin_layers"]))


def step_cost(cfg: dict, distinct_keys: float) -> dict:
    """Counted: the sparse step on distinct keys; the DNN, the linear term
    and the head as matmuls with weights, optimizer state and activations
    once; each CIN layer as its contraction (2*B*H_k*H_{k-1}*m*D flops
    forward, three times that for a training step) with its weights and
    its [B, H_k, D] maps.  Left out: the [B, H_{k-1}, m, D] outer product
    as memory traffic (the contraction need not materialise it), the
    occurrence-expanded rows, loss, AUC and metric state, the host feed."""
    B, m, D = cfg["batch_size"], cfg["n_sparse_slots"], cfg["embedding_dim"]
    d_in = m * (2 + D) + cfg["dense_dim"]
    hidden = cfg["hidden"]
    parts = [
        costs.sparse_step(distinct_keys, 2 + D),
        costs.mlp_train(B, [d_in, *hidden, hidden[-1]]),
        costs.mlp_train(B, [d_in, 1]),
        costs.mlp_train(B, [sum(cfg["cin_layers"]) + hidden[-1] + 1, 1]),
    ]
    prev = m
    for h in cfg["cin_layers"]:
        parts.append(costs.cin_layer_train(B, h, prev, m, D))
        prev = h
    return costs.total(parts)
