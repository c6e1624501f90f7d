"""Program side of the ``mellum2`` model name: the system's decoder
language model at the configuration's sizes, and the least work one
training step needs, whole (``step_cost``) and by part (``attn_cost``,
``moe_cost``, ``head_cost``: what the per-part roofline shares divide by).

Least work: a training step is three times its forward products (forward,
gradient by inputs, gradient by weights; recomputation does not count);
attention's scores only where the mask allows them (a causal triangle, or
a band of ``sliding_window`` keys), the experts only for the token-expert
pairs routed to an expert held here.  So no share can read over 100%."""

from __future__ import annotations

from benchmark import costs, gen

F32 = costs.F32


def build(cfg: dict, table_conf):
    from paddlebox_tpu.models import DecoderMoeLM

    rope = cfg["rope_parameters"]
    yarn = {k: v for k, v in rope["full_attention"].items()
            if k not in ("rope_type", "rope_theta")}
    if rope["sliding_attention"]["rope_type"] != "default" or rope[
            "full_attention"]["rope_type"] != "yarn":
        raise SystemExit("mellum2: plain rotary on sliding layers and YaRN "
                         "on full ones is what the model builds")
    # the vocabulary is the mix's key space: the table's sorted keys, which
    # is what the reference's key_rank ranks
    # (gen.key_space reads ``slot_vocab`` alone)
    vocab_keys = gen.key_space({"slot_vocab": cfg["vocab_size"]},
                               cfg["n_sparse_slots"])
    if vocab_keys.shape[0] != cfg["vocab_size"]:
        raise SystemExit(
            f"the mix's key space has {vocab_keys.shape[0]} keys, the "
            f"configuration's vocabulary {cfg['vocab_size']}")
    return DecoderMoeLM(
        table_conf.row_width, vocab_keys,
        max_seq_len=cfg["feed"]["max_seq_len"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=cfg["layer_types"][: cfg["num_hidden_layers"]],
        window=cfg["sliding_window"], n_experts=cfg["num_experts"],
        n_experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        experts_held=(0, cfg["num_experts_held"]),
        rope_theta=float(rope["sliding_attention"]["rope_theta"]),
        yarn=yarn, rms_eps=cfg["rms_norm_eps"])


def tokens(cfg: dict) -> int:
    return cfg["batch_size"] * cfg["feed"]["max_seq_len"]


def _products(n_rows: float, d_in: int, d_out: int) -> dict:
    """A weight matrix applied to ``n_rows`` rows in a training step: three
    products; the weights read forward and backward and their gradient
    written; inputs and outputs written once and read once."""
    return {"flops": 3 * 2.0 * n_rows * d_in * d_out,
            "bytes": 3.0 * d_in * d_out * F32
            + 2.0 * n_rows * (d_in + d_out) * F32}


def attn_cost(cfg: dict) -> dict:
    """All attention layers of one step: q, k, v and o projections, and
    the two score products on the unmasked (query, key) pairs only."""
    N, T = tokens(cfg), cfg["feed"]["max_seq_len"]
    H, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    W = min(cfg["sliding_window"], T)
    pairs = {"full_attention": T * (T + 1) / 2,
             "sliding_attention": W * (W + 1) / 2 + (T - W) * W}
    parts = []
    for kind in cfg["layer_types"][: cfg["num_hidden_layers"]]:
        parts += [_products(N, H, hq), _products(N, H, hkv),
                  _products(N, H, hkv), _products(N, hq, H)]
        # q k^T and p v: 2 * 2 * hq flops a pair, three times
        parts.append({"flops": 3 * 4.0 * hq * pairs[kind] * cfg["batch_size"],
                      "bytes": 0.0})
    return costs.total(parts)


def moe_cost(cfg: dict, pairs_local: float) -> dict:
    """Router and experts of all layers of one step; ``pairs_local`` is the
    step's token-expert pairs routed to experts held here, over all layers
    (the program's ``moe.pairs_local`` counter)."""
    N, L = tokens(cfg), cfg["num_hidden_layers"]
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    parts = [_products(N, H, cfg["num_experts"]) for _ in range(L)]
    weights = 3.0 * 3 * H * F * cfg["num_experts_held"] * L * F32
    parts.append({"flops": 3 * 3 * 2.0 * pairs_local * H * F,
                  "bytes": weights + 2.0 * pairs_local * 2 * H * F32})
    return costs.total(parts)


def head_cost(cfg: dict) -> dict:
    return _products(tokens(cfg), cfg["hidden_size"], cfg["vocab_size"])


def n_dense_params(cfg: dict) -> int:
    H, d, F = cfg["hidden_size"], cfg["head_dim"], cfg[
        "moe_intermediate_size"]
    layer = (2 * H * cfg["num_attention_heads"] * d
             + 2 * H * cfg["num_key_value_heads"] * d + H * cfg["num_experts"]
             + 2 * H + 3 * H * F * cfg["num_experts_held"])
    return cfg["num_hidden_layers"] * layer + H + cfg["vocab_size"] * H


def step_cost(cfg: dict, distinct_keys: float) -> dict:
    """Counted: the sparse step on distinct keys at the row's width; the
    parts above, the experts at the mean load (``num_experts_per_tok *
    num_experts_held / num_experts`` pairs a token and layer); Adam
    reading and writing parameter and both moments once.  Left out: norms,
    rotary codes, softmaxes, the loss, recomputation, the metric state."""
    pairs = (tokens(cfg) * cfg["num_hidden_layers"]
             * cfg["num_experts_per_tok"] * cfg["num_experts_held"]
             / cfg["num_experts"])
    return costs.total([
        costs.sparse_step(distinct_keys, 2 + cfg["embedding_dim"]),
        attn_cost(cfg), moe_cost(cfg, pairs), head_cost(cfg),
        {"flops": 0.0, "bytes": 6.0 * n_dense_params(cfg) * F32},
    ])
