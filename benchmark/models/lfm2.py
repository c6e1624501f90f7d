"""Program side of the ``lfm2`` model name: the system's decoder language
model described as the configuration's file has it (a gated short
convolution in three layers of four, grouped-query attention with a norm on
every query and key head in the fourth, a leading dense feed-forward, then
sigmoid-routed experts with a selection bias), and the least work one
training step needs, whole (``step_cost``) and by part (``conv_cost``,
``attn_cost``, ``ffn_cost``, ``moe_cost``, ``head_cost``: what the per-part
roofline shares divide by).

Least work: a training step is three times its forward products (forward,
gradient by inputs, gradient by weights; recomputation, norms, rotary
codes and softmaxes do not count); attention's scores only on the causal
triangle's (query, key) pairs, the routed experts only for the token-expert
pairs routed to an expert held here, the convolution's gate-conv-gate chain
read and written once.  So no share can read over 100%."""

from __future__ import annotations

from benchmark import costs, gen

F32 = costs.F32


def held_layers(cfg: dict) -> list:
    """(operator kind, feed-forward kind) of each layer held here, by its
    published number (``layers_held``): layer l's operator is
    ``layer_types[l]``, its feed-forward dense where l <
    ``num_dense_layers``."""
    if len(cfg["layers_held"]) != cfg["num_hidden_layers"]:
        raise SystemExit("lfm2: layers_held does not list "
                         "num_hidden_layers layers")
    return [(cfg["layer_types"][l],
             "dense" if l < cfg["num_dense_layers"] else "sparse")
            for l in cfg["layers_held"]]


def build(cfg: dict, table_conf):
    from paddlebox_tpu.models import DecoderMoeLM

    stated = {"norm_topk_prob": True, "use_expert_bias": True,
              "conv_bias": False}
    off = {k: cfg[k] for k, v in stated.items() if cfg[k] != v}
    if off or cfg["rope_parameters"]["rope_type"] != "default":
        raise SystemExit(f"lfm2: the model builds {stated} and a plain "
                         f"rotary code, the configuration states {off} and "
                         f"{cfg['rope_parameters']}")
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
    return DecoderMoeLM(
        table_conf.row_width, vocab_keys,
        max_seq_len=cfg["feed"]["max_seq_len"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=0, layer_types=ops, mlp_types=mlps, qk_norm=True,
        conv_kernel=cfg["conv_L_cache"],
        dense_width=cfg["intermediate_size"],
        n_experts=cfg["num_experts"],
        n_experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        experts_held=(0, cfg["num_experts_held"]),
        router_score="sigmoid", router_bias=True,
        router_scale=cfg["routed_scaling_factor"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_eps=cfg["norm_eps"])


def tokens(cfg: dict) -> int:
    return cfg["batch_size"] * cfg["feed"]["max_seq_len"]


def count(cfg: dict, kind: str) -> int:
    """How many of the held layers have ``kind`` as operator or as
    feed-forward."""
    return sum(kind in pair for pair in held_layers(cfg))


def _products(n_rows: float, d_in: int, d_out: int) -> dict:
    """A weight matrix applied to ``n_rows`` rows in a training step: three
    products; the weights read forward and backward and their gradient
    written; inputs and outputs written once and read once."""
    return {"flops": 3 * 2.0 * n_rows * d_in * d_out,
            "bytes": 3.0 * d_in * d_out * F32
            + 2.0 * n_rows * (d_in + d_out) * F32}


def conv_cost(cfg: dict) -> dict:
    """All gated short convolutions of one step: the projection in (to the
    two gates and the convolved third) and out, three products each; per
    token and channel ``conv_L_cache`` multiply-adds and the two gates'
    multiplies, three times like a product.  The chain between the two
    projections ([N, 3H] in, [N, H] out) is the first's outputs and the
    second's inputs, written once and read once in ``_products``: no byte
    is counted for it again."""
    N, H, K = tokens(cfg), cfg["hidden_size"], cfg["conv_L_cache"]
    layer = [_products(N, H, 3 * H), _products(N, H, H),
             {"flops": 3 * (2.0 * K + 2.0) * N * H, "bytes": 0.0}]
    return costs.total(layer * count(cfg, "conv"))


def attn_cost(cfg: dict) -> dict:
    """All attention layers of one step: q, k, v and o projections, and
    the two score products on the causal triangle's pairs: 2 * query heads
    * (head_dim + head_dim) flops a pair."""
    N, T = tokens(cfg), cfg["feed"]["max_seq_len"]
    H, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = T * (T + 1) / 2 * cfg["batch_size"]
    layer = [_products(N, H, nq * d), _products(N, H, nkv * d),
             _products(N, H, nkv * d), _products(N, nq * d, H),
             {"flops": 3 * 2.0 * nq * (d + d) * pairs, "bytes": 0.0}]
    return costs.total(layer * count(cfg, "full_attention"))


def ffn_cost(cfg: dict) -> dict:
    """What every token goes through whatever the routing: the leading
    dense layers' SwiGLU (no shared expert in this model)."""
    N, H, D = tokens(cfg), cfg["hidden_size"], cfg["intermediate_size"]
    layer = [_products(N, H, D), _products(N, H, D), _products(N, D, H)]
    return costs.total(layer * count(cfg, "dense"))


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
    H, d, E = cfg["hidden_size"], cfg["head_dim"], cfg["num_experts"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per = {
        "conv": H * 3 * H + cfg["conv_L_cache"] * H + H * H,
        "full_attention": 2 * H * nq * d + 2 * H * nkv * d + 2 * d,
        "dense": 3 * H * cfg["intermediate_size"],
        "sparse": H * E + E + 3 * H * cfg["moe_intermediate_size"]
        * cfg["num_experts_held"],
    }
    return (sum(per[op] + per[mlp] + 2 * H for op, mlp in held_layers(cfg))
            + H + cfg["vocab_size"] * H)


def step_cost(cfg: dict, distinct_keys: float) -> dict:
    """Counted: the sparse step on distinct keys at the row's width; the
    parts above, the routed experts at the mean load (``num_experts_per_tok
    * num_experts_held / num_experts`` pairs a token and sparse layer);
    Adam reading and writing parameter and both moments once.  Left out:
    norms, rotary codes, softmaxes, the loss, recomputation, the metric
    state."""
    pairs = (tokens(cfg) * count(cfg, "sparse") * cfg["num_experts_per_tok"]
             * cfg["num_experts_held"] / cfg["num_experts"])
    return costs.total([
        costs.sparse_step(distinct_keys, 2 + cfg["embedding_dim"]),
        conv_cost(cfg), attn_cost(cfg), ffn_cost(cfg), moe_cost(cfg, pairs),
        head_cost(cfg),
        {"flops": 0.0, "bytes": 6.0 * n_dense_params(cfg) * F32},
    ])
