"""Program side of the ``kimi_linear`` model name: the system's decoder
language model described as the configuration's file has it (KDA, a gated
delta rule with a decay a channel and a carried state, in three layers of
four; latent attention without a positional code in the fourth; a leading
dense feed-forward, then sigmoid-routed experts with a selection bias
beside a shared one), and the least work one training step needs, whole
(``step_cost``) and by part (``kda_cost``, ``kda_scan_cost``, ``attn_cost``,
``ffn_cost``, ``moe_cost``, ``head_cost``: what the per-part roofline
shares divide by).

Least work: a training step is three times its forward products (forward,
gradient by inputs, gradient by weights; recomputation, norms, gates and
softmaxes do not count); attention's scores only on the causal triangle's
(query, key) pairs, the routed experts only for the token-expert pairs
routed to an expert held here; the delta rule as the recurrence states it,
token by token -- the state decayed and read, updated and read out, 6 x
128 x 128 operations a head and token -- whatever implements it: no chunk
length, no triangular solve and no rematerialised forward enters.  So no
share can read over 100%."""

from __future__ import annotations

from benchmark import costs, gen
# a weight matrix's three products with their traffic, and a SwiGLU's three
# matrices: kanana2's, until benchmark/costs.py holds them (PERF.md (y))
from benchmark.models.kanana2 import _products, _swiglu

F32 = costs.F32


def held_layers(cfg: dict) -> list:
    """(operator kind, feed-forward kind) of each layer held here, by its
    published number from 1 (``layers_held``): KDA where
    ``linear_attn_config`` lists it under ``kda_layers``, latent attention
    under ``full_attn_layers``; the feed-forward dense where l <=
    ``first_k_dense_replace``."""
    lin = cfg["linear_attn_config"]
    if len(cfg["layers_held"]) != cfg["num_hidden_layers"]:
        raise SystemExit("kimi_linear: layers_held does not list "
                         "num_hidden_layers layers")
    out = []
    for l in cfg["layers_held"]:
        if (l in lin["kda_layers"]) == (l in lin["full_attn_layers"]):
            raise SystemExit(f"kimi_linear: layer {l} is not one of KDA and "
                             "full attention")
        out.append(("kda" if l in lin["kda_layers"] else "latent_attention",
                    "dense" if l <= cfg["first_k_dense_replace"]
                    else "sparse"))
    return out


def build(cfg: dict, table_conf):
    from paddlebox_tpu.models import DecoderMoeLM

    stated = {"moe_router_activation_func": "sigmoid",
              "use_grouped_topk": True, "num_expert_group": 1,
              "topk_group": 1, "moe_renormalize": True, "mla_use_nope": True,
              "q_lora_rank": None, "rope_scaling": None,
              "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
    off = {k: cfg[k] for k, v in stated.items() if cfg[k] != v}
    if off:
        raise SystemExit(f"kimi_linear: the model builds {stated}, the "
                         f"configuration states {off}")
    # the vocabulary is the mix's key space: the table's sorted keys, which
    # is what the reference's key_rank ranks
    # (gen.key_space reads ``slot_vocab`` alone)
    vocab_keys = gen.key_space({"slot_vocab": cfg["vocab_size"]},
                               cfg["n_sparse_slots"])
    if vocab_keys.shape[0] != cfg["vocab_size"]:
        raise SystemExit(
            f"the mix's key space has {vocab_keys.shape[0]} keys, the "
            f"configuration's vocabulary {cfg['vocab_size']}")
    ops, mlps = zip(*held_layers(cfg))
    lin = cfg["linear_attn_config"]
    return DecoderMoeLM(
        table_conf.row_width, vocab_keys,
        max_seq_len=cfg["feed"]["max_seq_len"],
        n_heads=cfg["num_attention_heads"],
        # grouped-query widths, unused: no layer is grouped-query attention
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=0, layer_types=ops, mlp_types=mlps,
        kda={"n_heads": lin["num_heads"], "head_dim": lin["head_dim"],
             "conv_kernel": lin["short_conv_kernel_size"],
             "gate_rank": cfg["kda_gate_rank"]},
        latent={"kv_rank": cfg["kv_lora_rank"],
                "qk_nope": cfg["qk_nope_head_dim"],
                "qk_rope": cfg["qk_rope_head_dim"],
                "v_dim": cfg["v_head_dim"], "rotary": False},
        dense_width=cfg["intermediate_size"],
        n_experts=cfg["num_experts"],
        n_experts_per_tok=cfg["num_experts_per_token"],
        expert_width=cfg["moe_intermediate_size"],
        experts_held=(0, cfg["num_experts_held"]),
        shared_width=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        router_score="sigmoid", router_bias=True,
        router_scale=cfg["routed_scaling_factor"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"])


def tokens(cfg: dict) -> int:
    return cfg["batch_size"] * cfg["feed"]["max_seq_len"]


def count(cfg: dict, kind: str) -> int:
    """How many of the held layers have ``kind`` as operator or as
    feed-forward."""
    return sum(kind in pair for pair in held_layers(cfg))


def kda_scan_cost(cfg: dict) -> dict:
    """The recurrences of all KDA layers of one step, from q, k, v, g, beta
    to o: a head and token the state [d, d] is decayed and read against k
    (2 d d), updated by a rank-one term (2 d d) and read out against q
    (2 d d) -- 6 d d operations, three times (forward and the two
    gradients); q, k, v, g and o, each [tokens, heads x d], and their five
    cotangents written once and read once.  The state stays on the chip:
    no byte is counted for it."""
    lin = cfg["linear_attn_config"]
    N, nh, d = tokens(cfg), lin["num_heads"], lin["head_dim"]
    return {"flops": 3 * 6.0 * d * d * nh * N * count(cfg, "kda"),
            "bytes": 2.0 * 10 * N * nh * d * F32 * count(cfg, "kda")}


def kda_cost(cfg: dict) -> dict:
    """All KDA operators of one step: the recurrences (``kda_scan_cost``);
    the projections to q, k and v, the two low-rank pairs (decay, output
    gate), the step size's and the output's, three products each; and per
    token and channel of the three convolved streams
    ``short_conv_kernel_size`` multiply-adds, three times like a product.
    What lies between a projection and the recurrence is the first's
    output and the second's input, written once and read once there: no
    byte is counted for it again."""
    lin = cfg["linear_attn_config"]
    N, H, R = tokens(cfg), cfg["hidden_size"], cfg["kda_gate_rank"]
    W, K = lin["num_heads"] * lin["head_dim"], lin["short_conv_kernel_size"]
    layer = [_products(N, H, W)] * 3 + [
        _products(N, H, R), _products(N, R, W),  # the decay
        _products(N, H, lin["num_heads"]),  # the step size
        _products(N, H, R), _products(N, R, W),  # the output gate
        _products(N, W, H),
        {"flops": 3 * 3 * 2.0 * K * N * W, "bytes": 0.0}]
    return costs.total(layer * count(cfg, "kda") + [kda_scan_cost(cfg)])


def attn_cost(cfg: dict) -> dict:
    """All latent-attention layers of one step: the query projection, the
    down-projection to latent and shared key slice, the up-projection to
    the heads' keys and values (two products in one matrix), the output
    projection, and the two score products on the causal triangle's pairs:
    2 * heads * (qk_head_dim + v_head_dim) flops a pair."""
    N, T = tokens(cfg), cfg["feed"]["max_seq_len"]
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    pairs = T * (T + 1) / 2 * cfg["batch_size"]
    layer = [
        _products(N, H, nh * qk),
        _products(N, H, rank + cfg["qk_rope_head_dim"]),
        _products(N, rank, nh * (cfg["qk_nope_head_dim"] + dv)),
        _products(N, nh * dv, H),
        {"flops": 3 * 2.0 * nh * (qk + dv) * pairs, "bytes": 0.0},
    ]
    return costs.total(layer * count(cfg, "latent_attention"))


def ffn_cost(cfg: dict) -> dict:
    """What every token goes through whatever the routing: the leading
    dense layer's SwiGLU and the sparse layers' shared expert."""
    N, H = tokens(cfg), cfg["hidden_size"]
    shared = cfg["num_shared_experts"] * cfg["moe_intermediate_size"]
    return costs.total(
        _swiglu(N, H, cfg["intermediate_size"]) * count(cfg, "dense")
        + _swiglu(N, H, shared) * count(cfg, "sparse"))


def moe_cost(cfg: dict, pairs_local: float) -> dict:
    """Router and routed experts of all sparse layers of one step;
    ``pairs_local`` is the step's token-expert pairs routed to experts held
    here, over all layers (the program's ``moe.pairs_local`` counter)."""
    N, L = tokens(cfg), count(cfg, "sparse")
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    parts = [_products(N, H, cfg["num_experts"]) for _ in range(L)]
    weights = 3.0 * 3 * H * F * cfg["num_experts_held"] * L * F32
    parts.append({"flops": 3 * 3 * 2.0 * pairs_local * H * F,
                  "bytes": weights + 2.0 * pairs_local * 2 * H * F32})
    return costs.total(parts)


def head_cost(cfg: dict) -> dict:
    return _products(tokens(cfg), cfg["hidden_size"], cfg["vocab_size"])


def n_dense_params(cfg: dict) -> int:
    lin = cfg["linear_attn_config"]
    H, nh, E = cfg["hidden_size"], cfg["num_attention_heads"], cfg[
        "num_experts"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv, F = cfg["kv_lora_rank"], cfg["v_head_dim"], cfg[
        "moe_intermediate_size"]
    W, R = lin["num_heads"] * lin["head_dim"], cfg["kda_gate_rank"]
    per = {
        "kda": (3 * H * W + 3 * lin["short_conv_kernel_size"] * W
                + lin["num_heads"] + W + 2 * (H * R + R * W)
                + H * lin["num_heads"] + lin["head_dim"] + W * H),
        "latent_attention": (H * nh * (nope + rope) + H * (rank + rope)
                             + rank + rank * nh * (nope + dv) + nh * dv * H),
        "dense": 3 * H * cfg["intermediate_size"],
        "sparse": H * E + E + 3 * H * F * (
            cfg["num_shared_experts"] + cfg["num_experts_held"]),
    }
    return (sum(per[op] + per[mlp] + 2 * H for op, mlp in held_layers(cfg))
            + H + cfg["vocab_size"] * H)


def step_cost(cfg: dict, distinct_keys: float) -> dict:
    """Counted: the sparse step on distinct keys at the row's width; the
    parts above, the routed experts at the mean load
    (``num_experts_per_token * num_experts_held / num_experts`` pairs a
    token and sparse layer); Adam reading and writing parameter and both
    moments once.  Left out: norms, gates, softmaxes, the loss,
    recomputation, the metric state."""
    pairs = (tokens(cfg) * count(cfg, "sparse")
             * cfg["num_experts_per_token"] * cfg["num_experts_held"]
             / cfg["num_experts"])
    return costs.total([
        costs.sparse_step(distinct_keys, 2 + cfg["embedding_dim"]),
        kda_cost(cfg), attn_cost(cfg), ffn_cost(cfg), moe_cost(cfg, pairs),
        head_cost(cfg),
        {"flops": 0.0, "bytes": 6.0 * n_dense_params(cfg) * F32},
    ])
