"""Program side of the ``ouro`` model name: the system's decoder language
model described as the configuration's file has it (a stack of
full-attention layers with a dense SwiGLU, the sandwich residual form, run
``total_ut_steps`` times over the same weights with ``norm_f`` between the
rounds, a learned exit gate, the loss weighted by the exit distribution),
and the least work one training step needs, whole (``step_cost``) and by
part (``stack_cost``: the layers, every use of them; ``exit_head_cost``:
the head passes and the gate: what the per-part roofline shares divide
by).

Least work, whatever implements it: a training step is three times its
forward products (forward, gradient by inputs, gradient by weights;
recomputation, norms, rotary codes, softmaxes and the exit distribution do
not count); every layer product once a USE, R uses a step -- the rounds
share their weights, not their work; attention's scores on the causal
(query, key) pairs only; the head once a round on the positions that have
a next token, the only ones scored.  So no share can read over 100%."""

from __future__ import annotations

from benchmark import costs, gen

F32 = costs.F32


def build(cfg: dict, table_conf):
    from paddlebox_tpu.models import DecoderMoeLM

    stated = {"rope_scaling": None, "use_sliding_window": False,
              "tie_word_embeddings": False, "hidden_act": "silu"}
    off = {k: cfg[k] for k, v in stated.items() if cfg[k] != v}
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    if off or set(kinds) != {"full_attention"}:
        raise SystemExit(f"ouro: the model builds {stated} over "
                         f"full_attention layers, the configuration states "
                         f"{off or sorted(set(kinds))}")
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
        window=0, layer_types=kinds, mlp_types=("dense",) * len(kinds),
        dense_width=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        loops=cfg["total_ut_steps"], sandwich=True,
        objective="looped_exit", exit=dict(cfg["exit"]))


def tokens(cfg: dict) -> int:
    """Tokens of a step's sequences: each goes through every layer
    ``total_ut_steps`` times."""
    return cfg["batch_size"] * cfg["feed"]["max_seq_len"]


def scored(cfg: dict) -> int:
    """Positions with a next token: all but a sequence's last."""
    return cfg["batch_size"] * (cfg["feed"]["max_seq_len"] - 1)


def layer_uses(cfg: dict) -> int:
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def _products(n_rows: float, d_in: int, d_out: int) -> dict:
    """A weight matrix applied to ``n_rows`` rows in a training step: three
    products; the weights read forward and backward and their gradient
    written; inputs and outputs written once and read once."""
    return {"flops": 3 * 2.0 * n_rows * d_in * d_out,
            "bytes": 3.0 * d_in * d_out * F32
            + 2.0 * n_rows * (d_in + d_out) * F32}


def stack_cost(cfg: dict) -> dict:
    """Every use of every layer in one step: the q, k, v and o
    projections, the two score products on the causal pairs (2 * 2 *
    heads * head_dim flops a pair) and the SwiGLU's three products."""
    N, T = tokens(cfg), cfg["feed"]["max_seq_len"]
    H, F, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    pairs = cfg["batch_size"] * T * (T + 1) / 2
    use = costs.total([
        _products(N, H, hq), _products(N, H, hkv), _products(N, H, hkv),
        _products(N, hq, H), {"flops": 3 * 4.0 * hq * pairs, "bytes": 0.0},
        _products(N, H, F), _products(N, H, F), _products(N, F, H)])
    return {k: layer_uses(cfg) * v for k, v in use.items()}


def exit_head_cost(cfg: dict) -> dict:
    """The head on the scored positions and the gate on every position,
    once a round."""
    R, H = cfg["total_ut_steps"], cfg["hidden_size"]
    one = costs.total([_products(scored(cfg), H, cfg["vocab_size"]),
                       _products(tokens(cfg), H, 1)])
    return {k: R * v for k, v in one.items()}


def n_dense_params(cfg: dict) -> int:
    H, d, F = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    layer = (2 * H * cfg["num_attention_heads"] * d
             + 2 * H * cfg["num_key_value_heads"] * d + 3 * H * F + 4 * H)
    # the final norm, the gate (a weight a channel and a bias) and the head
    return (cfg["num_hidden_layers"] * layer + H + H + 1
            + cfg["vocab_size"] * H)


def step_cost(cfg: dict, distinct_keys: float) -> dict:
    """Counted: the sparse step on distinct keys at the row's width; the
    two parts above; Adam reading and writing parameter and both moments
    once (a leaf is updated once, however often it is used).  Left out:
    norms, rotary codes, softmaxes, the exit distribution, the loss,
    recomputation, the metric state."""
    return costs.total([
        costs.sparse_step(distinct_keys, 2 + cfg["embedding_dim"]),
        stack_cost(cfg), exit_head_cost(cfg),
        {"flops": 0.0, "bytes": 6.0 * n_dense_params(cfg) * F32},
    ])
