"""Program side of the ``ctr_dnn`` model name: the system's model object
and the least work one training step needs (see benchmark/costs.py)."""

from __future__ import annotations

from benchmark import costs


def build(cfg: dict, table_conf):
    from paddlebox_tpu.models import CtrDnn

    return CtrDnn(cfg["n_sparse_slots"], table_conf.row_width,
                  dense_dim=cfg["dense_dim"], hidden=tuple(cfg["hidden"]))


def step_cost(cfg: dict, distinct_keys: float) -> dict:
    """Counted: the sparse step on distinct keys, and the tower's matmuls,
    weights, optimizer state and activations once.  Left out: the
    occurrence-expanded rows and pooled features (a fused pull could pool
    on the fly), the loss, AUC histogram and metric state, the host feed."""
    B = cfg["batch_size"]
    d_in = cfg["n_sparse_slots"] * (2 + cfg["embedding_dim"]) + cfg[
        "dense_dim"]
    parts = [costs.sparse_step(distinct_keys, 2 + cfg["embedding_dim"]),
             costs.mlp_train(B, [d_in, *cfg["hidden"], 1])]
    return costs.total(parts)
