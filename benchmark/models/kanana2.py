"""Program side of the ``kanana2`` model name: the system's decoder language
model described as the configuration's file has it (latent attention in
every layer, a leading dense feed-forward, then sigmoid-routed experts with
a selection bias beside shared experts), and the least work one training
step needs, whole (``step_cost``) and by part (``attn_cost``, ``ffn_cost``,
``moe_cost``, ``head_cost``: what the per-part roofline shares divide by).

Least work: a training step is three times its forward products (forward,
gradient by inputs, gradient by weights; recomputation, norms, rotary
codes and softmaxes do not count); attention's scores only on the causal
triangle's (query, key) pairs, the routed experts only for the token-expert
pairs routed to an expert held here.  So no share can read over 100%."""

from __future__ import annotations

from benchmark import costs, gen

F32 = costs.F32


def build(cfg: dict, table_conf):
    from paddlebox_tpu.models import DecoderMoeLM

    stated = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
              "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
              "q_lora_rank": None, "rope_scaling": None, "moe_layer_freq": 1}
    off = {k: cfg[k] for k, v in stated.items() if cfg[k] != v}
    if off:
        raise SystemExit(f"kanana2: the model builds {stated}, the "
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
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return DecoderMoeLM(
        table_conf.row_width, vocab_keys,
        max_seq_len=cfg["feed"]["max_seq_len"],
        n_heads=cfg["num_attention_heads"],
        # grouped-query widths, unused: every layer is latent
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=0,
        layer_types=("latent_attention",) * L,
        mlp_types=("dense",) * dense + ("sparse",) * (L - dense),
        latent={"kv_rank": cfg["kv_lora_rank"],
                "qk_nope": cfg["qk_nope_head_dim"],
                "qk_rope": cfg["qk_rope_head_dim"],
                "v_dim": cfg["v_head_dim"],
                "interleaved": cfg["rope_interleave"]},
        dense_width=cfg["intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        n_experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        experts_held=(0, cfg["num_experts_held"]),
        shared_width=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        router_score=cfg["scoring_func"], router_bias=True,
        router_scale=cfg["routed_scaling_factor"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"])


def tokens(cfg: dict) -> int:
    return cfg["batch_size"] * cfg["feed"]["max_seq_len"]


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def _products(n_rows: float, d_in: int, d_out: int) -> dict:
    """A weight matrix applied to ``n_rows`` rows in a training step: three
    products; the weights read forward and backward and their gradient
    written; inputs and outputs written once and read once."""
    return {"flops": 3 * 2.0 * n_rows * d_in * d_out,
            "bytes": 3.0 * d_in * d_out * F32
            + 2.0 * n_rows * (d_in + d_out) * F32}


def _swiglu(n_rows: float, d: int, width: int) -> list:
    return [_products(n_rows, d, width), _products(n_rows, d, width),
            _products(n_rows, width, d)]


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
    return costs.total(layer * cfg["num_hidden_layers"])


def ffn_cost(cfg: dict) -> dict:
    """What every token goes through whatever the routing: the leading
    dense layers' SwiGLU and the sparse layers' shared experts."""
    N, H = tokens(cfg), cfg["hidden_size"]
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return costs.total(
        _swiglu(N, H, cfg["intermediate_size"]) * cfg["first_k_dense_replace"]
        + _swiglu(N, H, shared) * sparse_layers(cfg))


def moe_cost(cfg: dict, pairs_local: float) -> dict:
    """Router and routed experts of all sparse layers of one step;
    ``pairs_local`` is the step's token-expert pairs routed to experts held
    here, over all layers (the program's ``moe.pairs_local`` counter)."""
    N, L = tokens(cfg), sparse_layers(cfg)
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    parts = [_products(N, H, cfg["n_routed_experts"]) for _ in range(L)]
    weights = 3.0 * 3 * H * F * cfg["num_experts_held"] * L * F32
    parts.append({"flops": 3 * 3 * 2.0 * pairs_local * H * F,
                  "bytes": weights + 2.0 * pairs_local * 2 * H * F32})
    return costs.total(parts)


def head_cost(cfg: dict) -> dict:
    return _products(tokens(cfg), cfg["hidden_size"], cfg["vocab_size"])


def n_dense_params(cfg: dict) -> int:
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv, F = cfg["kv_lora_rank"], cfg["v_head_dim"], cfg[
        "moe_intermediate_size"]
    attn = (H * nh * (nope + rope) + H * (rank + rope) + rank
            + rank * nh * (nope + dv) + nh * dv * H + 2 * H)
    dense = 3 * H * cfg["intermediate_size"]
    sparse = (H * cfg["n_routed_experts"] + cfg["n_routed_experts"]
              + 3 * H * F * (cfg["n_shared_experts"]
                             + cfg["num_experts_held"]))
    return (cfg["num_hidden_layers"] * attn
            + cfg["first_k_dense_replace"] * dense
            + sparse_layers(cfg) * sparse + H + cfg["vocab_size"] * H)


def step_cost(cfg: dict, distinct_keys: float) -> dict:
    """Counted: the sparse step on distinct keys at the row's width; the
    parts above, the routed experts at the mean load (``num_experts_per_tok
    * num_experts_held / n_routed_experts`` pairs a token and sparse
    layer); Adam reading and writing parameter and both moments once.
    Left out: norms, rotary codes, softmaxes, the loss, recomputation, the
    metric state."""
    pairs = (tokens(cfg) * sparse_layers(cfg) * cfg["num_experts_per_tok"]
             * cfg["num_experts_held"] / cfg["n_routed_experts"])
    return costs.total([
        costs.sparse_step(distinct_keys, 2 + cfg["embedding_dim"]),
        attn_cost(cfg), ffn_cost(cfg), moe_cost(cfg, pairs), head_cost(cfg),
        {"flops": 0.0, "bytes": 6.0 * n_dense_params(cfg) * F32},
    ])
