"""A decoder language model through the pass loop, against its plain
reference (paddlebox_tpu/models/reference/decoder_lm.py), at toy sizes on
the CPU: hidden 64, 4 query heads over 2 key-value heads of 16, window 8,
sequences of 32 tokens, 8 experts of width 32 with 4 a token, a vocabulary
of 64.

Tolerances.  Both sides compute in float32 on the CPU and differ only in
the order of their sums (blockwise softmax, chunked loss, gathers back
through inverse maps): relative differences of 1e-6 .. 1e-5.  Each
tolerance below is some ten times that and a hundred times under what
bfloat16 operands give (2**-8 = 4e-3 an operand, 1e-2 and more on a
gradient), so a product computed in a lower precision fails it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.config import (
    DataFeedConfig,
    SlotConfig,
    SparseTableConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import DatasetFactory
from paddlebox_tpu.data.feed import key_classes
from paddlebox_tpu.models import DecoderMoeLM
from paddlebox_tpu.models.reference import decoder_lm as ref
from paddlebox_tpu.parallel.expert import route_tokens, routed_experts
from paddlebox_tpu.parallel.sequence import (
    apply_rotary,
    full_attention,
    rotary_tables,
)
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

H, NQ, NKV, HD, WINDOW, T, E, TOPK, F, V = 64, 4, 2, 16, 8, 32, 8, 4, 32, 64
B = 2
HELD = (2, 6)  # this share: experts 2..5 of the 8
LAYERS = ("sliding_attention", "sliding_attention", "sliding_attention",
          "full_attention")
YARN = {"factor": 4.0, "original_max_position_embeddings": 16,
        "beta_fast": 4.0, "beta_slow": 1.0, "attention_factor": 1.1386}
VOCAB = np.sort(np.random.default_rng(5).choice(
    np.arange(1000, 9000, dtype=np.uint64), V, replace=False))

SIZES = {
    "hidden": H, "n_heads": NQ, "n_kv_heads": NKV, "head_dim": HD,
    "layer_types": LAYERS, "window": WINDOW, "rope_theta": 10000.0,
    "yarn": YARN, "n_experts": E, "n_experts_per_tok": TOPK,
    "experts_held": HELD, "rms_eps": 1e-6,
}


def make_model(tconf, held=HELD):
    return DecoderMoeLM(
        tconf.row_width, VOCAB, max_seq_len=T, n_heads=NQ, n_kv_heads=NKV,
        head_dim=HD, layer_types=LAYERS, window=WINDOW, n_experts=E,
        n_experts_per_tok=TOPK, expert_width=F, experts_held=held,
        rope_theta=10000.0, yarn=YARN, block_q=16, loss_chunk=24)


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
    """``tokens`` [n, T] ids into VOCAB -> a loaded BoxPSDataset of n
    sequences: one instance a line, the slot's keys in order."""
    with open(path, "w") as f:
        for seq, y in zip(tokens, labels):
            keys = " ".join(str(int(VOCAB[t])) for t in seq)
            f.write(f"1 {int(y)} {len(seq)} {keys} 1 0.5\n")
    ds = DatasetFactory().create_dataset("BoxPSDataset", feed_config())
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    return ds


def rel(got, want):
    """Norm of the difference over the norm of what it is compared with."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# ------------------------------------------------- (a) through the pass loop
def test_decoder_trains_through_the_pass_loop_like_its_reference(tmp_path):
    """BoxPSDataset -> begin_pass -> Trainer.train_from_dataset -> end_pass,
    two passes of one step each, default TrainerConfig and table config bar
    the embedding width; the reference follows the same two steps with the
    documented optimizers (Adam, sparse adagrad, show/click counters)."""
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, V, size=(2 * B, T))
    tokens[1, 20:] = tokens[1, :12]  # repeated keys inside a sequence
    labels = np.array([1, 0, 1, 1])
    steps = [token_dataset(tmp_path / f"s{i}", tokens[i * B:(i + 1) * B],
                           labels[i * B:(i + 1) * B]) for i in range(2)]
    tconf = SparseTableConfig(embedding_dim=H)
    trconf = TrainerConfig()
    model = make_model(tconf)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, trconf, seed=0)
    params = jax.tree.map(np.asarray, trainer.params)
    census = np.unique(VOCAB[tokens])

    # the rows as the table seeds them, read from an open pass
    table.begin_pass(census)
    sd = table.pass_state_dict()
    table.end_pass()
    assert np.array_equal(sd["keys"], census)
    rows = {int(k): v.astype(np.float64) for k, v in
            zip(sd["keys"], sd["values"])}  # [show, click, emb.., g2sum]

    lr, b1, b2, eps = trconf.dense_lr, 0.9, 0.999, 1e-8
    mu = jax.tree.map(np.zeros_like, params)
    nu = jax.tree.map(np.zeros_like, params)
    counters = None
    for i, ds in enumerate(steps):
        table.begin_pass(census)
        m = trainer.train_from_dataset(ds, table)
        got_rows = table.pass_state_dict()
        table.end_pass()
        assert m["steps"] == 1 and m["samples"] == B

        # ---- the reference's step on the same batch
        seqs, ys = tokens[i * B:(i + 1) * B], labels[i * B:(i + 1) * B]
        occ = VOCAB[seqs].reshape(-1)  # occurrences in file order
        uniq, inv = np.unique(occ, return_inverse=True)
        r_uniq = np.stack([rows[int(k)] for k in uniq])
        seq_pos = np.arange(B * T, dtype=np.int32).reshape(B, T)
        cls = np.searchsorted(VOCAB, occ).astype(np.int32)

        def loss_fn(p, r):
            return ref.loss(SIZES, p, r[inv], jnp.asarray(seq_pos),
                            jnp.asarray(cls))

        want_loss, (gp, gr) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, jnp.asarray(r_uniq[:, :-1], jnp.float32))
        # order of float32 sums only: 1e-6 .. 1e-5 (module docstring)
        assert abs(m["loss"] - float(want_loss)) < 2e-5 * float(want_loss)

        # every dense gradient leaf, as the optimizer got it: Adam's first
        # moment after the step is b1 * mu + (1 - b1) * g
        got_mu = jax.tree.map(np.asarray, trainer.opt_state[0].mu)
        mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * np.asarray(g),
                          mu, gp)
        nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * np.asarray(g) ** 2,
                          nu, gp)
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(got_mu)[0],
                jax.tree.leaves(mu)):
            assert rel(g, w) < 1e-4, jax.tree_util.keystr(path)
        t = i + 1
        params = jax.tree.map(
            lambda p, a, v: (p - lr * (a / (1 - b1 ** t)) / (
                np.sqrt(v / (1 - b2 ** t)) + eps)).astype(np.float32),
            params, mu, nu)

        # rows: gradient through g2sum's growth and the adagrad step,
        # counters exactly
        g = np.clip(np.asarray(gr, np.float64)[:, 2:], -tconf.grad_clip,
                    tconf.grad_clip)
        g2 = r_uniq[:, -1] + (g * g).mean(axis=1)
        emb = r_uniq[:, 2:-1] - (tconf.learning_rate * np.sqrt(
            tconf.initial_g2sum / (tconf.initial_g2sum + g2)))[:, None] * g
        show = r_uniq[:, 0] + np.bincount(inv, minlength=len(uniq))
        click = r_uniq[:, 1] + np.bincount(
            inv, weights=np.repeat(ys, T), minlength=len(uniq))
        for j, k in enumerate(uniq):
            rows[int(k)] = np.concatenate(
                [[show[j], click[j]], emb[j], [g2[j]]])
        got = got_rows["values"][np.searchsorted(got_rows["keys"], uniq)]
        want = np.stack([rows[int(k)] for k in uniq])
        assert np.array_equal(got[:, :2], want[:, :2])  # show, click: whole
        # the rows' change in this step (the row gradient through adagrad)
        assert rel(got[:, 2:-1] - r_uniq[:, 2:-1],
                   want[:, 2:-1] - r_uniq[:, 2:-1]) < 1e-4
        assert rel(got[:, -1] - r_uniq[:, -1],
                   want[:, -1] - r_uniq[:, -1]) < 2e-4  # g2sum: squares
        counters = {k: m[k] for k in model.step_counters}

    # the dense parameters after two Adam steps: the update's direction is
    # g / (|g| + eps), so an entry whose gradient is rounding noise may
    # differ by a whole lr; by norm, over a leaf, those are lost in 1e-3
    for (path, g), w, p0 in zip(
            jax.tree_util.tree_flatten_with_path(
                jax.tree.map(np.asarray, trainer.params))[0],
            jax.tree.leaves(params),
            jax.tree.leaves(jax.tree.map(np.asarray, model.init(
                jax.random.PRNGKey(0))))):
        assert rel(g - p0, w - p0) < 1e-3, jax.tree_util.keystr(path)

    # the step's counters, published at the read-back
    assert counters["trainer.tokens"] == B * (T - 1)
    assert counters["moe.pairs_routed"] == B * T * TOPK * len(LAYERS)
    assert 0 < counters["moe.pairs_local"] < counters["moe.pairs_routed"]
    assert counters["moe.expert_load_mean"] == pytest.approx(
        counters["moe.pairs_local"] / (HELD[1] - HELD[0]))
    assert counters["moe.expert_load_max"] >= counters["moe.expert_load_mean"]
    from paddlebox_tpu import telemetry

    snap = telemetry.registry.snapshot()["counters"]
    assert snap["trainer.tokens"] >= 2 * B * (T - 1)
    for ds in steps:
        ds.close()
    trainer.close()


def test_rotary_tables_match_the_reference():
    """Plain and YaRN tables, float32 against the reference's float64."""
    for yarn in (None, YARN):
        cos, sin = rotary_tables(jnp.arange(T), HD, 10000.0, yarn)
        want_cos, want_sin = ref.rotary(T, HD, 10000.0, yarn)
        # angles up to ~T in float32: 1e-6 absolute
        np.testing.assert_allclose(cos, want_cos, atol=5e-6)
        np.testing.assert_allclose(sin, want_sin, atol=5e-6)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, NQ, HD))
    got = apply_rotary(x, cos, sin)[0]
    np.testing.assert_allclose(got, ref.turn(x[0], want_cos, want_sin),
                               atol=1e-5)


# --------------------------------------------- (b) blockwise = dense mask
def dense_mask_attention(q, k, v, window):
    """[B, T, H, D] with K and V repeated over their query heads and a
    [T, T] mask from positions."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    mask = j <= i
    if window is not None:
        mask &= i - j < window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window", [None, WINDOW])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_blockwise_attention_equals_the_dense_mask(window, what):
    """37 positions in blocks of 16: the last block is short, a window of 8
    ends inside a block.  Grouped queries: 4 heads over 2 key-value heads."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (2, 37, NQ, HD))
    k = jax.random.normal(ks[1], (2, 37, NKV, HD))
    v = jax.random.normal(ks[2], (2, 37, NKV, HD))
    tgt = jax.random.normal(ks[3], (2, 37, NQ, HD))

    def blockwise(q, k, v):
        return full_attention(q, k, v, causal=True, window=window, block_q=16)

    if what == "forward":
        # float32 sums in another order: 1e-6
        np.testing.assert_allclose(
            blockwise(q, k, v), dense_mask_attention(q, k, v, window),
            atol=2e-6)
        return
    got = jax.grad(lambda *a: ((blockwise(*a) - tgt) ** 2).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda *a: ((dense_mask_attention(*a, window) - tgt) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert rel(g, w) < 1e-5


def test_a_window_needs_causal_attention():
    x = jnp.zeros((1, 8, 2, 4))
    with pytest.raises(ValueError, match="causal"):
        full_attention(x, x, x, causal=False, window=4)


# ------------------------------------------------ (c), (d) the routed layer
def layer_params(key, router_bias=None):
    ks = jax.random.split(key, 4)
    router = jax.random.normal(ks[0], (H, E)) / np.sqrt(H)
    if router_bias is not None:
        router = router + router_bias
    return {
        "router": router,
        "w_gate": jax.random.normal(ks[1], (E, H, F)) / np.sqrt(H),
        "w_up": jax.random.normal(ks[2], (E, H, F)) / np.sqrt(H),
        "w_down": jax.random.normal(ks[3], (E, F, H)) / np.sqrt(F),
    }


def share_output(lp, x, lo, hi):
    top_w, top_e = route_tokens(x, lp["router"], TOPK)
    return routed_experts(x, top_w, top_e, lp["w_gate"][lo:hi],
                          lp["w_up"][lo:hi], lp["w_down"][lo:hi], lo)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each: what each computes for the tokens
    routed to its experts sums to the reference's layer over all eight
    (no shared expert: nothing is counted twice)."""
    lp = layer_params(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (B * T, H))
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_layer(SIZES, lp, x, held=(0, E))
        parts, loads = zip(*[share_output(lp, x, lo, lo + 2)
                             for lo in range(0, E, 2)])
    # float32 sums in another order: 1e-6
    assert rel(sum(parts), whole) < 1e-5
    for part in parts:  # every share does part of the work
        assert rel(part, whole) > 0.1
    # every token's k choices are counted by exactly one share
    assert int(sum(l.sum() for l in loads)) == B * T * TOPK


def test_no_token_is_dropped_when_one_held_expert_takes_most():
    """A router biased (through a constant feature) so that expert 3 is
    among every token's choices and expert 2 of the same share among
    none's: the share holding them still equals the reference, output and
    gradient."""
    bias = jnp.zeros((H, E)).at[0, 3].set(2.0).at[0, 2].set(-10.0)
    lp = layer_params(jax.random.PRNGKey(5), router_bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(6), (B * T, H)).at[:, 0].set(5.0)
    held = {k: (v[2:6] if k != "router" else v) for k, v in lp.items()}

    def program(router, x):
        return share_output({**lp, "router": router}, x, 2, 6)[0]

    def reference(router, x):
        with jax.default_matmul_precision("highest"):
            return ref.routed_layer(
                SIZES, {**held, "router": router}, x, held=(2, 6))

    _, load = share_output(lp, x, 2, 6)
    assert int(load[1]) == B * T > B * T // 2 and int(load[0]) == 0
    assert rel(program(lp["router"], x), reference(lp["router"], x)) < 1e-5
    tgt = jax.random.normal(jax.random.PRNGKey(7), (B * T, H))
    got = jax.grad(lambda r, x: ((program(r, x) - tgt) ** 2).sum(),
                   argnums=(0, 1))(lp["router"], x)
    want = jax.grad(lambda r, x: ((reference(r, x) - tgt) ** 2).sum(),
                    argnums=(0, 1))(lp["router"], x)
    for g, w in zip(got, want):
        assert rel(g, w) < 1e-4


# ------------------------------------------------------------ (e) key_class
def test_key_class_is_each_occurrence_rank_in_the_vocabulary(tmp_path):
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, V, size=(B, T))
    ds = token_dataset(tmp_path / "d", tokens, [1, 0])
    batch = next(ds.batches())
    table = SparseTable(SparseTableConfig(embedding_dim=4), seed=0)
    table.begin_pass(ds.unique_keys())
    plan = table.plan_batch(batch)
    for inverse in (plan.inverse, None):
        cls = key_classes(batch.keys, batch.n_keys, VOCAB, inverse)
        assert cls.dtype == np.int32 and cls.shape == batch.keys.shape
        # the classes follow seq_pos: position t of sequence b is tokens[b, t]
        assert np.array_equal(cls[batch.seq_pos], tokens)
    # padding reads -1
    short = key_classes(batch.keys, batch.n_keys - 3, VOCAB)
    assert (short[batch.n_keys - 3:] == -1).all()
    # a key outside the vocabulary is an error, never a silent class
    lacking = np.delete(VOCAB, tokens[0, 0])
    for inverse in (plan.inverse, None):
        with pytest.raises(ValueError, match="outside the model's vocab"):
            key_classes(batch.keys, batch.n_keys, lacking, inverse)
    table.end_pass()
    ds.close()


def test_vocab_keys_must_be_sorted_and_distinct():
    with pytest.raises(ValueError, match="sorted"):
        DecoderMoeLM(
            H + 2, VOCAB[::-1], max_seq_len=T, n_heads=NQ, n_kv_heads=NKV,
            head_dim=HD, layer_types=LAYERS, window=WINDOW, n_experts=E,
            n_experts_per_tok=TOPK, expert_width=F)


# ------------------------------------------------- the multi-chip trainer
def test_decoder_on_two_chips_takes_the_single_chip_step(tmp_path):
    """The same model half in parallel/trainer.py: two devices with two
    sequences each (gradients psummed, the loss the mean over the axis)
    make the step one device makes on the four -- loss, dense parameters
    and the counters, to the order of float32 sums (1e-5; Adam's first
    step is sign-like, so parameters are compared by norm at 1e-3)."""
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.parallel.sharded_table import ShardedSparseTable
    from paddlebox_tpu.parallel.trainer import MultiChipTrainer

    rng = np.random.default_rng(2)
    tokens = rng.integers(0, V, size=(2 * B, T))
    labels = [1, 0, 0, 1]
    tconf = SparseTableConfig(embedding_dim=H)
    one = feed_config()
    one.batch_size, one.batch_key_capacity = 2 * B, 2 * B * T

    ds1 = DatasetFactory().create_dataset("BoxPSDataset", one)
    ds2 = token_dataset(tmp_path / "d", tokens, labels)
    ds1.set_filelist([str(tmp_path / "d")])
    ds1.load_into_memory()

    table = SparseTable(tconf, seed=0)
    single = Trainer(make_model(tconf), tconf, TrainerConfig(), seed=0)
    p0 = jax.tree.map(np.asarray, single.params)
    table.begin_pass(ds1.unique_keys())
    m1 = single.train_from_dataset(ds1, table)
    table.end_pass()

    mesh = make_mesh(2)
    st = ShardedSparseTable(tconf, mesh, seed=0)
    multi = MultiChipTrainer(make_model(tconf), tconf, mesh, TrainerConfig(),
                             seed=0)
    st.begin_pass(ds2.unique_keys())
    m2 = multi.train_from_dataset(ds2, st)
    st.end_pass()
    assert m1["steps"] == m2["steps"] == 1
    assert m2["loss"] == pytest.approx(m1["loss"], rel=2e-5)
    for name in DecoderMoeLM.step_counters[:3]:
        assert m2[name] == m1[name], name
    for a, b, p in zip(jax.tree.leaves(multi.dense_state()[0]),
                       jax.tree.leaves(single.params), jax.tree.leaves(p0)):
        assert rel(np.asarray(a) - p, np.asarray(b) - p) < 1e-3
    for t in (single, multi):
        t.close()
    ds1.close()
    ds2.close()
