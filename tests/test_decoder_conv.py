"""The decoder's fourth operator kind and the query/key head norm -- a gated
short convolution in the layers that do not attend, grouped-query attention
with a learned norm on every query and key head in the one that does, a
leading dense feed-forward, then sigmoid-routed experts with a selection
bias -- against its plain reference (benchmark/reference/lfm2.py, which
imports nothing of the program), at toy sizes on the CPU: hidden 64, 4
query heads over 2 key-value heads of 16, 3 taps, dense width 96, 16
experts of width 32 with 4 a token, sequences of 32 tokens, a vocabulary
of 64.

Tolerances as in tests/test_decoder_lm.py: both sides compute in float32
(``highest``) on the CPU and differ only in the order of their sums
(1e-6 .. 1e-5); each tolerance is some ten times that and a hundred times
under what bfloat16 operands give (the last test of the operator's block
shows it), so a product computed in a lower precision fails it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gen
from benchmark.reference import common
from benchmark.reference import lfm2 as ref
from paddlebox_tpu.config import (
    DataFeedConfig,
    SlotConfig,
    SparseTableConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import DatasetFactory
from paddlebox_tpu.models import DecoderMoeLM
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

H, NQ, NKV, HD, K = 64, 4, 2, 16, 3
FD, F, E, TOPK, V, T, B = 96, 32, 16, 4, 64, 32, 2
HELD = 2  # one of eight shares: experts 0, 1 of the 16
EPS = 1e-5
OPS_OF = ("conv", "full_attention", "conv", "conv", "conv")
MLPS = ("dense", "sparse", "sparse", "sparse", "sparse")
VOCAB = np.sort(np.random.default_rng(7).choice(
    np.arange(1000, 9000, dtype=np.uint64), V, replace=False))
OPS = common.Ops()

# the reference's words: the keys of the model's published config; layer 0
# and layers 2-5 of a pattern whose period is conv, conv, attention, conv
CFG = {
    "hidden_size": H, "num_attention_heads": NQ, "num_key_value_heads": NKV,
    "head_dim": HD, "conv_L_cache": K, "conv_bias": False,
    "intermediate_size": FD, "moe_intermediate_size": F, "num_experts": E,
    "num_experts_held": HELD, "num_experts_per_tok": TOPK, "vocab_size": V,
    "num_hidden_layers": 5, "num_dense_layers": 2,
    "layers_held": [0, 2, 3, 4, 5],
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "feed": {"max_seq_len": T}, "norm_eps": EPS, "norm_topk_prob": True,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "use_expert_bias": True, "routed_scaling_factor": 1,
}


def make_model(held=(0, HELD), **change):
    kw = dict(
        max_seq_len=T, n_heads=NQ, n_kv_heads=NKV, head_dim=HD, window=0,
        layer_types=OPS_OF, mlp_types=MLPS, qk_norm=True, conv_kernel=K,
        dense_width=FD, n_experts=E, n_experts_per_tok=TOPK, expert_width=F,
        experts_held=held, router_score="sigmoid", router_bias=True,
        rope_theta=10000.0, rms_eps=EPS, block_q=16, loss_chunk=24)
    return DecoderMoeLM(H + 2, VOCAB, **{**kw, **change})


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


def lively(lp, key, names):
    """Norm scales away from 1, so that a scale left out shows."""
    ks = jax.random.split(key, len(names))
    return {**lp, **{n: lp[n] + 0.3 * jax.random.normal(k, lp[n].shape)
                     for n, k in zip(names, ks)}}


# ------------------------------------------------------ the tree, described
def test_the_described_tree_is_the_reference_tree():
    """``init`` gives the leaves the reference's ``init_params`` gives, by
    name and shape: what the benchmark's ``same_structure`` asks."""
    got = jax.eval_shape(make_model().init, jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda k: ref.init_params(CFG, k),
                          jax.random.PRNGKey(0))
    flat = lambda t: [(jax.tree_util.keystr(p), x.shape) for p, x in
                      jax.tree_util.tree_flatten_with_path(t)[0]]
    assert flat(got) == flat(want)
    assert set(got["layers"][0]) == {
        "n1", "n2", "conv_in", "conv_w", "conv_out", "mlp_gate", "mlp_up",
        "mlp_down"}
    assert set(got["layers"][1]) == {
        "n1", "n2", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "router",
        "router_bias", "w_gate", "w_up", "w_down"}
    assert got["layers"][2]["conv_in"].shape == (H, 3 * H)
    assert got["layers"][2]["conv_w"].shape == (K, H)
    assert got["layers"][1]["q_norm"].shape == (HD,)
    # a description that asks for no head norm has no such leaves
    bare = jax.eval_shape(make_model(qk_norm=False).init,
                          jax.random.PRNGKey(0))
    assert "q_norm" not in bare["layers"][1]


@pytest.mark.parametrize("change, match", [
    ({"conv_kernel": 0}, "conv_kernel"),
    ({"layer_types": ("conv", "linear_attention", "conv", "conv", "conv")},
     "layer types"),
])
def test_a_description_that_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        make_model(**change)


# ----------------------------------------------------------- the operator
def conv_layer(key):
    return lively(make_model().init(key)["layers"][2], key, ("n1",))


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_gated_short_convolution_is_its_reference(what):
    """The model's operator half of a conv layer (x + conv(n1 x)) against
    the reference's, a sequence at a time: output, and the gradient by the
    input and by every leaf it reads."""
    model = make_model()
    lp = conv_layer(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, H))

    @highest
    def program(lp, x):
        return model._conv_mix(lp, x) - x

    @highest
    def reference(lp, x):
        return jnp.stack([ref.conv_mixer(
            CFG, OPS, lp, ref.rms_norm(x[b], lp["n1"], EPS))
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
    for name in ("n1", "conv_in", "conv_w", "conv_out"):
        assert rel(got[0][name], want[0][name]) < 1e-5, name


def test_the_convolution_is_causal_and_starts_from_zeros():
    """A change at position t moves no output before t; what the first two
    positions see before the sequence is zero, so position 0 is its own
    tap alone and position 1 the last two taps."""
    model = make_model()
    lp = conv_layer(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T, H))
    mix = highest(lambda x: model._conv_mix(lp, x) - x)
    base = np.asarray(mix(x))
    t = 11
    moved = np.asarray(mix(x.at[0, t].add(1.0)))
    assert np.array_equal(moved[0, :t], base[0, :t])
    # ... and reaches exactly the K positions from t on
    changed = np.abs(moved[0] - base[0]).max(axis=-1) > 0
    assert changed[t:t + K].all() and not changed[t + K:].any()
    with jax.default_matmul_precision("highest"):
        h = ref.rms_norm(x[0], lp["n1"], EPS)
        b, c, u = jnp.split(h @ lp["conv_in"], 3, axis=-1)
        z, w = b * u, lp["conv_w"]
        want0 = (c[0] * (w[2] * z[0])) @ lp["conv_out"]
        want1 = (c[1] * (w[2] * z[1] + w[1] * z[0])) @ lp["conv_out"]
    np.testing.assert_allclose(base[0, 0], want0, atol=2e-6)
    np.testing.assert_allclose(base[0, 1], want1, atol=2e-6)


def test_bfloat16_operands_fail_the_operators_tolerance():
    """The stated tolerance (1e-5) is a hundred times under what the
    operator reads with its products' operands rounded to bfloat16."""
    lp = conv_layer(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(7), (T, H))
    h = ref.rms_norm(x, lp["n1"], EPS)
    run = lambda ops: highest(lambda h: ref.conv_mixer(CFG, ops, lp, h))(h)
    assert rel(run(common.Ops("bfloat16")), run(OPS)) > 1e-3


# ------------------------------------------------------ the head norm
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_attention_with_a_norm_on_every_head_is_its_reference(what):
    """The model's attention half of a layer (x + attention(n1 x)) with
    the learned norm on each query and key head before the rotary code,
    against the reference's: output, and the gradient by the input and by
    every leaf it reads, the two 16-float scales among them."""
    model = make_model()
    lp = lively(model.init(jax.random.PRNGKey(4))["layers"][1],
                jax.random.PRNGKey(6), ("n1", "q_norm", "k_norm"))
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, H))

    @highest
    def program(lp, x):
        return model._attend(lp, x, "full_attention") - x

    @highest
    def reference(lp, x):
        return jnp.stack([ref.attention(
            CFG, OPS, lp, ref.rms_norm(x[b], lp["n1"], EPS))
            for b in range(B)])

    if what == "forward":
        assert rel(program(lp, x), reference(lp, x)) < 1e-5
        # the norm is in the result: without it the layer reads otherwise
        bare = make_model(qk_norm=False)
        off = highest(lambda lp, x: bare._attend(lp, x, "full_attention") - x)
        assert rel(off(lp, x), reference(lp, x)) > 0.05
        return
    tgt = jax.random.normal(jax.random.PRNGKey(8), (B, T, H))
    got = jax.grad(lambda lp, x: ((program(lp, x) - tgt) ** 2).sum(),
                   argnums=(0, 1))(lp, x)
    want = jax.grad(lambda lp, x: ((reference(lp, x) - tgt) ** 2).sum(),
                    argnums=(0, 1))(lp, x)
    assert rel(got[1], want[1]) < 1e-5
    for name in ("n1", "q_norm", "k_norm", "wq", "wk", "wv", "wo"):
        assert rel(got[0][name], want[0][name]) < 1e-5, name


# ------------------------------------------------- the shares of a layer
@pytest.mark.parametrize("layer", [1, 2], ids=["attention", "conv"])
def test_the_eight_shares_add_up_to_the_uncut_layer(layer):
    """16 experts in 8 shares of 2: what each share's whole layer gives,
    with the residual and the operator -- which every share computes alike
    -- counted ONCE, is the reference's layer that holds all sixteen."""
    whole_model = make_model(held=(0, E))
    lp = whole_model.init(jax.random.PRNGKey(13))["layers"][layer]
    x = jax.random.normal(jax.random.PRNGKey(14), (B, T, H))
    valid = jnp.ones((B, T), bool)
    kinds = (OPS_OF[layer], MLPS[layer])
    op = ref.conv_mixer if kinds[0] == "conv" else ref.attention

    @highest
    def reference(lp, x):
        def one(x):
            x = x + op(CFG, OPS, lp, ref.rms_norm(x, lp["n1"], EPS))
            return x + ref.routed({**CFG, "num_experts_held": E}, OPS, lp,
                                  ref.rms_norm(x, lp["n2"], EPS))
        return jnp.stack([one(x[b]) for b in range(B)])

    def share(lo):
        model = make_model(held=(lo, lo + HELD))
        mine = {**lp, **{k: lp[k][lo:lo + HELD]
                         for k in ("w_gate", "w_up", "w_down")}}
        return highest(lambda x: model._layer(mine, x, valid, kinds))(x)

    outs, counts = zip(*[share(lo) for lo in range(0, E, HELD)])
    after_op = highest(
        lambda x: whole_model._conv_mix(lp, x) if kinds[0] == "conv"
        else whole_model._attend(lp, x, kinds[0]))(x)
    want = reference(lp, x)
    parts = [out - after_op for out in outs]
    # float32 sums in another order: 1e-6
    assert rel(after_op + sum(parts), want) < 1e-5
    for part in parts:  # every share does part of the work
        assert rel(part, want - after_op) > 0.05
    # counted eight times, the residual and the operator would show
    assert rel(sum(outs), want) > 1.0
    # every token's k choices are counted by exactly one share
    assert int(sum(c[0] for c in counts)) == B * T * TOPK


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


def test_conv_decoder_trains_through_the_pass_loop_like_its_reference(
        tmp_path):
    """Layer 0 and one period -- conv + dense, then attention, conv, conv,
    conv over routed experts: BoxPSDataset -> begin_pass ->
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
    model = make_model()
    assert tconf.row_width == model.emb_width
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
    # decoders' tests: an entry whose gradient is rounding noise may differ
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

    # the step's counters: pairs over the four SPARSE layers only
    assert counters["trainer.tokens"] == B * (T - 1)
    assert counters["moe.pairs_routed"] == B * T * TOPK * 4
    assert 0 < counters["moe.pairs_local"] < counters["moe.pairs_routed"]
    assert counters["moe.expert_load_mean"] == pytest.approx(
        counters["moe.pairs_local"] / HELD)
    assert counters["moe.expert_load_max"] >= counters["moe.expert_load_mean"]
    for ds in steps:
        ds.close()
    trainer.close()
