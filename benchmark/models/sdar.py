"""Program side of the ``sdar`` model name: the system's decoder language
model described as the configuration's file has it (grouped-query attention
with a norm on every query and key head, softmax-routed experts, no shared
one) under the block-diffusion objective (a noised and a clean stream under
one block mask, a learned [MASK] input, a masked-token loss weighted by
1/p), and the least work one training step needs, whole (``step_cost``)
and by part (``bd_attn_cost``, ``moe_cost``, ``head_cost``: what the
per-part roofline shares divide by).

Least work, whatever implements it: a training step is three times its
forward products (forward, gradient by inputs, gradient by weights;
recomputation, norms, rotary codes and softmaxes do not count); attention's
scores only on the block mask's (query, key) pairs, T * (T + L) a sequence
over both streams; the routed experts only for the position-expert pairs
routed to an expert held here; the head on the masked positions, the only
ones scored; and of the LAST held layer only what the loss needs -- the
clean stream's keys and values, but neither its queries nor its output
projection nor its feed-forward.  So no share can read over 100%."""

from __future__ import annotations

from benchmark import costs, gen

F32 = costs.F32


def build(cfg: dict, table_conf):
    from paddlebox_tpu.models import DecoderMoeLM

    stated = {"norm_topk_prob": True, "rope_scaling": None,
              "use_sliding_window": False, "mlp_only_layers": [],
              "decoder_sparse_step": 1, "attention_bias": False,
              "tie_word_embeddings": False}
    off = {k: cfg[k] for k, v in stated.items() if cfg[k] != v}
    if off:
        raise SystemExit(f"sdar: the model builds {stated}, the "
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
    return DecoderMoeLM(
        table_conf.row_width, vocab_keys,
        max_seq_len=cfg["feed"]["max_seq_len"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=0, layer_types=("full_attention",) * cfg["num_hidden_layers"],
        qk_norm=True, n_experts=cfg["num_experts"],
        n_experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        experts_held=(0, cfg["num_experts_held"]),
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        objective="block_diffusion", diffusion=dict(cfg["diffusion"]))


def tokens(cfg: dict) -> int:
    """Tokens of a step's sequences: each goes through the layers twice,
    once in each stream."""
    return cfg["batch_size"] * cfg["feed"]["max_seq_len"]


def masked_share(cfg: dict) -> float:
    """The mean share of positions masked, and so scored: the noise level
    is uniform over the dense feature's grid, p = eps + (1 - eps) t."""
    eps = cfg["diffusion"]["eps"]
    return eps + (1.0 - eps) * 0.5


def mask_pairs(cfg: dict) -> float:
    """(query, key) pairs of one sequence under the block mask, both
    streams: a noised query of block b sees its block's L noised keys and
    the b * L clean keys before it, a clean one the (b + 1) * L clean keys
    up to its block's end."""
    T, L = cfg["feed"]["max_seq_len"], cfg["diffusion"]["block_len"]
    ends = [min((b + 1) * L, T) for b in range(-(-T // L))]
    return float(sum(2 * (e - b * L) * e for b, e in enumerate(ends)))


def _products(n_rows: float, d_in: int, d_out: int) -> dict:
    """A weight matrix applied to ``n_rows`` rows in a training step: three
    products; the weights read forward and backward and their gradient
    written; inputs and outputs written once and read once."""
    return {"flops": 3 * 2.0 * n_rows * d_in * d_out,
            "bytes": 3.0 * d_in * d_out * F32
            + 2.0 * n_rows * (d_in + d_out) * F32}


def bd_attn_cost(cfg: dict) -> dict:
    """All attention layers of one step: the q, k, v and o projections on
    both streams' positions and the two score products on the block mask's
    pairs only, 2 * query heads * (head_dim + head_dim) flops a pair; in
    the last held layer the noised stream's queries alone (half the
    positions for q and o, half the pairs)."""
    N = tokens(cfg)
    H, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = mask_pairs(cfg) * cfg["batch_size"]
    parts = []
    for last in [False] * (cfg["num_hidden_layers"] - 1) + [True]:
        n_q = N if last else 2 * N
        parts += [_products(n_q, H, nq * d), _products(2 * N, H, nkv * d),
                  _products(2 * N, H, nkv * d), _products(n_q, nq * d, H),
                  {"flops": 3 * 2.0 * nq * (d + d) * pairs
                   * (0.5 if last else 1.0), "bytes": 0.0}]
    return costs.total(parts)


def ffn_positions(cfg: dict) -> list:
    """Positions whose feed-forward the loss needs, layer by layer: both
    streams', but the noised stream's alone in the last held layer."""
    N = tokens(cfg)
    return [2 * N] * (cfg["num_hidden_layers"] - 1) + [N]


def moe_cost(cfg: dict, pairs_local: float) -> dict:
    """Router and experts of all layers of one step; ``pairs_local`` is the
    position-expert pairs routed to experts held here that the loss needs,
    over all layers."""
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    parts = [_products(n, H, cfg["num_experts"]) for n in ffn_positions(cfg)]
    weights = (3.0 * 3 * H * F * cfg["num_experts_held"]
               * cfg["num_hidden_layers"] * F32)
    parts.append({"flops": 3 * 3 * 2.0 * pairs_local * H * F,
                  "bytes": weights + 2.0 * pairs_local * 2 * H * F32})
    return costs.total(parts)


def head_cost(cfg: dict) -> dict:
    """The head on the masked positions: the others' logits are never
    needed."""
    return _products(tokens(cfg) * masked_share(cfg), cfg["hidden_size"],
                     cfg["vocab_size"])


def n_dense_params(cfg: dict) -> int:
    H, d, F = cfg["hidden_size"], cfg["head_dim"], cfg[
        "moe_intermediate_size"]
    layer = (2 * H * cfg["num_attention_heads"] * d
             + 2 * H * cfg["num_key_value_heads"] * d + 2 * d
             + H * cfg["num_experts"] + 2 * H
             + 3 * H * F * cfg["num_experts_held"])
    # the final norm, the head and the [MASK] input
    return (cfg["num_hidden_layers"] * layer + H + cfg["vocab_size"] * H + H)


def step_cost(cfg: dict, distinct_keys: float) -> dict:
    """Counted: the sparse step on distinct keys at the row's width; the
    parts above, the routed experts at the mean load
    (``num_experts_per_tok * num_experts_held / num_experts`` pairs a
    position whose feed-forward the loss needs); Adam reading and writing
    parameter and both moments once.  Left out: norms, rotary codes,
    softmaxes, the noise draw, the loss, recomputation, the metric
    state."""
    pairs = (sum(ffn_positions(cfg)) * cfg["num_experts_per_tok"]
             * cfg["num_experts_held"] / cfg["num_experts"])
    return costs.total([
        costs.sparse_step(distinct_keys, 2 + cfg["embedding_dim"]),
        bd_attn_cost(cfg), moe_cost(cfg, pairs), head_cost(cfg),
        {"flops": 0.0, "bytes": 6.0 * n_dense_params(cfg) * F32},
    ])
