"""The decoder's other description -- latent attention in every layer, a
leading dense feed-forward, then sigmoid-routed experts with a selection
bias beside shared experts -- against its plain reference
(benchmark/reference/kanana2.py, which imports nothing of the program), at
toy sizes on the CPU: hidden 64, 4 heads over a latent of 32, query/key
head 16 + 8 (the 8 carry the rotary code), value head 12, dense width 96,
16 experts of width 32 with 4 a token, 2 shared, sequences of 32 tokens, a
vocabulary of 64.

Tolerances as in tests/test_decoder_lm.py: both sides compute in float32
on the CPU and differ only in the order of their sums (1e-6 .. 1e-5); each
tolerance is some ten times that and a hundred times under what bfloat16
operands give, so a product computed in a lower precision fails it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gen
from benchmark.reference import common
from benchmark.reference import kanana2 as ref
from paddlebox_tpu.config import (
    DataFeedConfig,
    SlotConfig,
    SparseTableConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import DatasetFactory
from paddlebox_tpu.models import DecoderMoeLM
from paddlebox_tpu.parallel.expert import (
    route_tokens,
    routed_experts,
    swiglu,
)
from paddlebox_tpu.parallel.sequence import (
    apply_rotary,
    full_attention,
    rotary_tables,
)
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

H, NH, RANK, NOPE, ROPE, DV = 64, 4, 32, 16, 8, 12
FD, F, E, TOPK, SHARED, V, T, B = 96, 32, 16, 4, 2, 64, 32, 2
HELD = 4  # this share: experts 0..3 of the 16
SCALE = 2.448
VOCAB = np.sort(np.random.default_rng(7).choice(
    np.arange(1000, 9000, dtype=np.uint64), V, replace=False))
OPS = common.Ops()

# the reference's words: the keys of the model's published config
CFG = {
    "hidden_size": H, "num_attention_heads": NH, "kv_lora_rank": RANK,
    "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE, "v_head_dim": DV,
    "intermediate_size": FD, "moe_intermediate_size": F,
    "n_shared_experts": SHARED, "n_routed_experts": E,
    "num_experts_held": HELD, "num_experts_per_tok": TOPK, "vocab_size": V,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "feed": {"max_seq_len": T}, "rope_theta": 10000.0, "rope_scaling": None,
    "rope_interleave": True, "rms_norm_eps": 1e-6, "scoring_func": "sigmoid",
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": SCALE,
}


def make_model(tconf, held=(0, HELD)):
    return DecoderMoeLM(
        tconf.row_width, VOCAB, max_seq_len=T, n_heads=NH, n_kv_heads=NH,
        head_dim=ROPE, window=0, layer_types=("latent_attention",) * 3,
        mlp_types=("dense", "sparse", "sparse"), dense_width=FD,
        latent={"kv_rank": RANK, "qk_nope": NOPE, "qk_rope": ROPE,
                "v_dim": DV, "interleaved": True},
        n_experts=E, n_experts_per_tok=TOPK, expert_width=F,
        experts_held=held, shared_width=SHARED * F, router_score="sigmoid",
        router_bias=True, router_scale=SCALE, rope_theta=10000.0,
        block_q=16, loss_chunk=24)


def rel(got, want):
    """Norm of the difference over the norm of what it is compared with."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def highest(f):
    """``f`` as one compiled program with float32 products."""
    @jax.jit
    def g(*a):
        with jax.default_matmul_precision("highest"):
            return f(*a)
    return g


# ------------------------------------------------------ the tree, described
def test_the_described_tree_is_the_reference_tree():
    """``init`` gives the leaves the reference's ``init_params`` gives, by
    name and shape: what the benchmark's ``same_structure`` asks."""
    model = make_model(SparseTableConfig(embedding_dim=H))
    got = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda k: ref.init_params(CFG, k),
                          jax.random.PRNGKey(0))
    flat = lambda t: [(jax.tree_util.keystr(p), x.shape) for p, x in
                      jax.tree_util.tree_flatten_with_path(t)[0]]
    assert flat(got) == flat(want)
    assert set(got["layers"][0]) == {
        "n1", "n2", "n_kv", "wq", "wkv_a", "wkv_b", "wo", "mlp_gate",
        "mlp_up", "mlp_down"}
    assert got["layers"][1]["router_bias"].shape == (E,)
    assert got["layers"][1]["shared_gate"].shape == (H, SHARED * F)
    assert got["layers"][1]["wkv_b"].shape == (RANK, NH * (NOPE + DV))


@pytest.mark.parametrize("change, match", [
    ({"dense_width": 0}, "dense_width"),
    ({"latent": None}, "latent"),
    ({"latent": {"kv_rank": RANK}}, "latent"),
    ({"mlp_types": ("dense", "sparse")}, "mlp_types"),
    ({"mlp_types": ("dense", "sparse", "gated")}, "mlp_types"),
    ({"router_score": "tanh"}, "router score"),
    ({"layer_types": ("latent_attention", "linear_attention",
                      "latent_attention")}, "layer types"),
])
def test_a_description_that_cannot_be_built_is_refused(change, match):
    kw = dict(
        max_seq_len=T, n_heads=NH, n_kv_heads=NH, head_dim=ROPE, window=0,
        layer_types=("latent_attention",) * 3,
        mlp_types=("dense", "sparse", "sparse"), dense_width=FD,
        latent={"kv_rank": RANK, "qk_nope": NOPE, "qk_rope": ROPE,
                "v_dim": DV, "interleaved": True},
        n_experts=E, n_experts_per_tok=TOPK, expert_width=F)
    with pytest.raises(ValueError, match=match):
        DecoderMoeLM(H + 2, VOCAB, **{**kw, **change})


# ---------------------------------------------------------- rotary, strips
def test_rotary_on_a_slice_turns_adjacent_pairs():
    """Tables of the slice's width, each angle at 2i and 2i + 1; the turn
    is the complex product (x[2i] + i x[2i+1]) * exp(i t theta_i), and the
    reference's ``turn``; the head's other dimensions are not touched."""
    cos, sin = rotary_tables(jnp.arange(T), ROPE, 10000.0, interleaved=True)
    want_cos, want_sin = ref.rotary(CFG, T)
    # angles up to ~T in float32: 1e-6 absolute
    np.testing.assert_allclose(cos[:, 0::2], want_cos, atol=5e-6)
    np.testing.assert_allclose(cos[:, 1::2], want_cos, atol=5e-6)
    np.testing.assert_allclose(sin[:, 1::2], want_sin, atol=5e-6)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, NH, NOPE + ROPE))
    got = apply_rotary(x[..., NOPE:], cos, sin, interleaved=True)
    z = np.asarray(x[..., NOPE::2]) + 1j * np.asarray(x[..., NOPE + 1::2])
    inv = 10000.0 ** (-2.0 * np.arange(ROPE // 2) / ROPE)
    z = z * np.exp(1j * np.arange(T)[None, :, None, None] * inv)
    np.testing.assert_allclose(got[..., 0::2], z.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], z.imag, atol=1e-5)
    np.testing.assert_allclose(
        got[0], ref.turn(x[0, ..., NOPE:], want_cos, want_sin), atol=1e-5)
    # rotate-half, the default, pairs i with i + D / 2: another code
    half = apply_rotary(x[..., NOPE:], *rotary_tables(
        jnp.arange(T), ROPE, 10000.0))
    assert rel(half, got) > 0.1


def dense_mask_attention(q, k, v, window):
    """[B, T, H, D] queries and keys, [B, T, H, Dv] values, a [T, T] mask
    from positions."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    mask = j <= i
    if window is not None:
        mask &= i - j < window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_blockwise_attention_with_a_narrower_value_head(window, what):
    """A value head of 12 under a query/key head of 24; 37 positions in
    blocks of 16 (the last block is short, a window of 8 ends inside one);
    one key-value head a query head, as latent attention has them."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (2, 37, NH, NOPE + ROPE))
    k = jax.random.normal(ks[1], (2, 37, NH, NOPE + ROPE))
    v = jax.random.normal(ks[2], (2, 37, NH, DV))
    tgt = jax.random.normal(ks[3], (2, 37, NH, DV))

    @jax.jit
    def blockwise(q, k, v):
        return full_attention(q, k, v, causal=True, window=window, block_q=16)

    if what == "forward":
        got = blockwise(q, k, v)
        assert got.shape == (2, 37, NH, DV)
        # float32 sums in another order: 1e-6
        np.testing.assert_allclose(
            got, dense_mask_attention(q, k, v, window), atol=2e-6)
        return
    got = jax.jit(jax.grad(lambda *a: ((blockwise(*a) - tgt) ** 2).sum(),
                           argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(
        lambda *a: ((dense_mask_attention(*a, window) - tgt) ** 2).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        assert rel(g, w) < 1e-5


def test_the_dense_form_takes_a_narrower_value_head_too():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 9, 2, 6))
    k = jax.random.normal(ks[1], (1, 9, 2, 6))
    v = jax.random.normal(ks[2], (1, 9, 2, 4))
    np.testing.assert_allclose(
        full_attention(q, k, v, causal=True),
        dense_mask_attention(q, k, v, None), atol=2e-6)


# --------------------------------------------------------- latent attention
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_latent_attention_is_its_reference(what):
    """The model's attention half of a layer (x + attention(n1 x)) against
    the reference's, a sequence at a time: output, and the gradient by the
    input and by every leaf it reads."""
    model = make_model(SparseTableConfig(embedding_dim=H))
    lp = model.init(jax.random.PRNGKey(4))["layers"][1]
    # norm scales away from 1, so that a scale left out shows
    lp = {**lp, "n1": lp["n1"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), (H,)), "n_kv": lp["n_kv"] + 0.3 *
        jax.random.normal(jax.random.PRNGKey(6), (RANK,))}
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, H))
    names = ("n1", "n_kv", "wq", "wkv_a", "wkv_b", "wo")

    @highest
    def program(lp, x):
        return model._attend(lp, x, "latent_attention") - x

    @highest
    def reference(lp, x):
        return jnp.stack([ref.attention(
            CFG, OPS, lp, ref.rms_norm(x[b], lp["n1"], 1e-6))
            for b in range(B)])

    if what == "forward":
        assert rel(program(lp, x), reference(lp, x)) < 1e-5
        return
    tgt = jax.random.normal(jax.random.PRNGKey(8), (B, T, H))
    got = jax.grad(lambda lp, x: ((program(lp, x) - tgt) ** 2).sum(),
                   argnums=(0, 1))(lp, x)
    want = jax.grad(lambda lp, x: ((reference(lp, x) - tgt) ** 2).sum(),
                    argnums=(0, 1))(lp, x)
    assert rel(got[1], want[1]) < 1e-5
    for name in names:
        assert rel(got[0][name], want[0][name]) < 1e-5, name


# --------------------------------------------------------- the routed layer
def layer_params(key, held=E):
    ks = jax.random.split(key, 8)
    w = lambda k, *s: jax.random.normal(k, s) / np.sqrt(s[-2])
    return {
        "router": w(ks[0], H, E),
        "router_bias": 0.3 * jax.random.normal(ks[1], (E,)),
        "w_gate": w(ks[2], held, H, F), "w_up": w(ks[3], held, H, F),
        "w_down": w(ks[4], held, F, H),
        "shared_gate": w(ks[5], H, SHARED * F),
        "shared_up": w(ks[6], H, SHARED * F),
        "shared_down": w(ks[7], SHARED * F, H),
    }


def reference_route(lp, x):
    """The reference's lines: sigmoid scores, the choice by s + b, the
    weights 2.448 * s / (sum of the chosen s + 1e-20)."""
    s = jax.nn.sigmoid(x @ lp["router"])
    _, top_e = jax.lax.top_k(s + lp["router_bias"], TOPK)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    return SCALE * top_s / (top_s.sum(-1, keepdims=True) + 1e-20), top_e


def test_the_sigmoid_router_weighs_by_unbiased_scores_scaled():
    lp = layer_params(jax.random.PRNGKey(9))
    x = jax.random.normal(jax.random.PRNGKey(10), (B * T, H))
    with jax.default_matmul_precision("highest"):
        top_w, top_e = route_tokens(x, lp["router"], TOPK, "sigmoid",
                                    lp["router_bias"], SCALE)
        want_w, want_e = reference_route(lp, x)
    assert top_e.dtype == jnp.int32 and np.array_equal(top_e, want_e)
    np.testing.assert_allclose(top_w, want_w, rtol=1e-6)
    # the k weights sum to the scale, not to 1
    np.testing.assert_allclose(top_w.sum(-1), SCALE, rtol=1e-6)
    # softmax, no bias, scale 1: the router it was
    soft_w, soft_e = route_tokens(x, lp["router"], TOPK)
    p = jax.nn.softmax(x @ lp["router"], axis=-1)
    w, e = jax.lax.top_k(p, TOPK)
    assert np.array_equal(soft_e, e)
    np.testing.assert_allclose(soft_w, w / w.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_the_selection_bias_moves_choices_never_weights():
    """With the bias some tokens choose other experts; the weight of a
    chosen expert is its unbiased score over the chosen scores' sum
    whatever the bias; and the bias has no gradient."""
    lp = layer_params(jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (B * T, H))
    route = lambda b: route_tokens(x, lp["router"], TOPK, "sigmoid", b, SCALE)
    w_b, e_b = route(lp["router_bias"])
    w_0, e_0 = route(None)
    moved = np.sort(e_b, -1) != np.sort(e_0, -1)
    assert 0.1 < moved.any(-1).mean()  # some tokens choose otherwise
    s = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    chosen = np.take_along_axis(s, np.asarray(e_b), -1)
    np.testing.assert_allclose(
        w_b, SCALE * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    # a bias that lifts every expert alike changes nothing at all
    w_c, e_c = route(jnp.full((E,), 5.0))
    assert np.array_equal(e_c, e_0)
    np.testing.assert_array_equal(w_c, w_0)

    def through(bias, router):
        top_w, top_e = route_tokens(x, router, TOPK, "sigmoid", bias, SCALE)
        y, _ = routed_experts(x, top_w, top_e, lp["w_gate"], lp["w_up"],
                              lp["w_down"], 0)
        return (y ** 2).sum()

    g_bias, g_router = jax.grad(through, argnums=(0, 1))(
        lp["router_bias"], lp["router"])
    assert not np.asarray(g_bias).any()
    assert np.abs(np.asarray(g_router)).max() > 0


def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: what each share computes for the tokens
    routed to its experts, plus the shared expert counted ONCE, is the
    reference's layer that holds all sixteen."""
    lp = layer_params(jax.random.PRNGKey(13))
    x = jax.random.normal(jax.random.PRNGKey(14), (B * T, H))
    with jax.default_matmul_precision("highest"):
        whole = ref.routed({**CFG, "num_experts_held": E}, OPS, lp, x)
        top_w, top_e = route_tokens(x, lp["router"], TOPK, "sigmoid",
                                    lp["router_bias"], SCALE)
        parts, loads = zip(*[routed_experts(
            x, top_w, top_e, lp["w_gate"][lo:lo + 4], lp["w_up"][lo:lo + 4],
            lp["w_down"][lo:lo + 4], lo) for lo in range(0, E, 4)])
        shared = swiglu(x, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
        # one share as the reference holds it: experts 0..3 and the shared
        share0 = ref.routed(CFG, OPS, {**lp, **{
            k: lp[k][:4] for k in ("w_gate", "w_up", "w_down")}}, x)
    # float32 sums in another order: 1e-6
    assert rel(sum(parts) + shared, whole) < 1e-5
    assert rel(parts[0] + shared, share0) < 1e-5
    for part in parts:  # every share does part of the work
        assert rel(part, whole) > 0.05
    # counted four times the shared expert would show
    assert rel(sum(parts) + 4 * shared, whole) > 0.1
    # every token's k choices are counted by exactly one share
    assert int(sum(l.sum() for l in loads)) == B * T * TOPK


# ------------------------------------------------- through the pass loop
def feed_config():
    slots = [
        SlotConfig(name="click", type="float", is_dense=True, shape=(1,)),
        SlotConfig(name="slot0", type="uint64"),
        SlotConfig(name="dense0", type="float", is_dense=True, shape=(1,)),
    ]
    return DataFeedConfig(
        slots=slots, batch_size=B, label_slot="click",
        batch_key_capacity=B * T, sequence_slot="slot0", max_seq_len=T)


def token_dataset(path, tokens, labels):
    with open(path, "w") as f:
        for seq, y in zip(tokens, labels):
            keys = " ".join(str(int(VOCAB[t])) for t in seq)
            f.write(f"1 {int(y)} {len(seq)} {keys} 1 0.5\n")
    ds = DatasetFactory().create_dataset("BoxPSDataset", feed_config())
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    return ds


def test_latent_decoder_trains_through_the_pass_loop_like_its_reference(
        tmp_path):
    """One dense and two sparse layers: BoxPSDataset -> begin_pass ->
    Trainer.train_from_dataset -> end_pass, two passes of one step each,
    default TrainerConfig and table config bar the embedding width; the
    reference's ``loss`` on the same batches (common.batch_arrays: the
    occurrences in file order), differentiated by ``jax.grad``, with the
    documented optimizers applied by hand."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, V, size=(2 * B, T))
    tokens[1, 20:] = tokens[1, :12]  # repeated keys inside a sequence
    labels = np.array([1, 0, 1, 1], np.float32)
    steps = [token_dataset(tmp_path / f"s{i}", tokens[i * B:(i + 1) * B],
                           labels[i * B:(i + 1) * B]) for i in range(2)]
    tconf = SparseTableConfig(embedding_dim=H)
    trconf = TrainerConfig()
    model = make_model(tconf)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, trconf, seed=0)
    params = jax.tree.map(np.asarray, trainer.params)
    bias0 = [lp["router_bias"].copy() for lp in params["layers"][1:]]
    census = np.unique(VOCAB[tokens])

    table.begin_pass(census)
    sd = table.pass_state_dict()
    table.end_pass()
    rows = {int(k): v.astype(np.float64) for k, v in
            zip(sd["keys"], sd["values"])}  # [show, click, emb.., g2sum]

    lr, b1, b2, eps = trconf.dense_lr, 0.9, 0.999, 1e-8
    mu = jax.tree.map(np.zeros_like, params)
    nu = jax.tree.map(np.zeros_like, params)
    for i, ds in enumerate(steps):
        table.begin_pass(census)
        m = trainer.train_from_dataset(ds, table)
        got_rows = table.pass_state_dict()
        table.end_pass()
        assert m["steps"] == 1 and m["samples"] == B

        # ---- the reference's step on the same batch
        sl = slice(i * B, (i + 1) * B)
        data = gen.PassData(
            keys=VOCAB[tokens[sl]][:, None, :], labels=labels[sl],
            dense=np.full((B, 1), 0.5, np.float32),
            dense_q=np.full((B, 1), 500, np.int32))
        uniq, batch = common.batch_arrays(data, B * T, VOCAB)
        batch = dict(batch, B=B, S=1)
        r_uniq = np.stack([rows[int(k)] for k in uniq])
        r_pad = np.zeros((B * T, r_uniq.shape[1] - 1), np.float32)
        r_pad[:len(uniq)] = r_uniq[:, :-1]

        @highest
        def loss_fn(p, r):
            return ref.loss(CFG, OPS, p, r[batch["inv"]], batch)

        want_loss, (gp, gr) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, jnp.asarray(r_pad))
        gr = np.asarray(gr, np.float64)[:len(uniq)]
        # order of float32 sums only: 1e-6 .. 1e-5
        assert abs(m["loss"] - float(want_loss)) < 2e-5 * float(want_loss)

        # every dense gradient leaf, as the optimizer got it
        got_mu = jax.tree.map(np.asarray, trainer.opt_state[0].mu)
        mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * np.asarray(g),
                          mu, gp)
        nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * np.asarray(g) ** 2,
                          nu, gp)
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(got_mu)[0],
                jax.tree.leaves(mu)):
            name = jax.tree_util.keystr(path)
            if "router_bias" in name:  # in the choice only: no gradient
                assert not g.any() and not np.asarray(w).any(), name
            else:
                assert rel(g, w) < 1e-4, name
        t = i + 1
        params = jax.tree.map(
            lambda p, a, v: (p - lr * (a / (1 - b1 ** t)) / (
                np.sqrt(v / (1 - b2 ** t)) + eps)).astype(np.float32),
            params, mu, nu)

        # rows: the row gradient through adagrad, counters exactly
        g = np.clip(gr[:, 2:], -tconf.grad_clip, tconf.grad_clip)
        g2 = r_uniq[:, -1] + (g * g).mean(axis=1)
        emb = r_uniq[:, 2:-1] - (tconf.learning_rate * np.sqrt(
            tconf.initial_g2sum / (tconf.initial_g2sum + g2)))[:, None] * g
        occ = VOCAB[tokens[sl]].reshape(-1)
        inv = np.searchsorted(uniq, occ)
        show = r_uniq[:, 0] + np.bincount(inv, minlength=len(uniq))
        click = r_uniq[:, 1] + np.bincount(
            inv, weights=np.repeat(labels[sl], T), minlength=len(uniq))
        for j, k in enumerate(uniq):
            rows[int(k)] = np.concatenate(
                [[show[j], click[j]], emb[j], [g2[j]]])
        got = got_rows["values"][np.searchsorted(got_rows["keys"], uniq)]
        want = np.stack([rows[int(k)] for k in uniq])
        assert np.array_equal(got[:, :2], want[:, :2])  # show, click: whole
        assert rel(got[:, 2:-1] - r_uniq[:, 2:-1],
                   want[:, 2:-1] - r_uniq[:, 2:-1]) < 1e-4
        counters = {k: m[k] for k in model.step_counters}

    # the dense parameters after two Adam steps (by norm, as the other
    # decoder's test: an entry whose gradient is rounding noise may differ
    # by a whole lr); the selection bias is where it was, to the bit
    p0 = jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(0)))
    for (path, g), w, p in zip(
            jax.tree_util.tree_flatten_with_path(
                jax.tree.map(np.asarray, trainer.params))[0],
            jax.tree.leaves(params), jax.tree.leaves(p0)):
        name = jax.tree_util.keystr(path)
        if "router_bias" not in name:
            assert rel(g - p, w - p) < 1e-3, name
    for lp, b0 in zip(trainer.params["layers"][1:], bias0):
        assert np.array_equal(np.asarray(lp["router_bias"]), b0)

    # the step's counters: pairs over the SPARSE layers only
    assert counters["trainer.tokens"] == B * (T - 1)
    assert counters["moe.pairs_routed"] == B * T * TOPK * 2
    assert 0 < counters["moe.pairs_local"] < counters["moe.pairs_routed"]
    assert counters["moe.expert_load_mean"] == pytest.approx(
        counters["moe.pairs_local"] / HELD)
    assert counters["moe.expert_load_max"] >= counters["moe.expert_load_mean"]
    for ds in steps:
        ds.close()
    trainer.close()
