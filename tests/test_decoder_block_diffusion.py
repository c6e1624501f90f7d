"""The decoder's second objective -- denoising over blocks: a noised and a
clean stream in one pass under the block mask, a learned [MASK] input, the
noise level from the instance's dense feature, a masked-token loss weighted
by 1/p -- over grouped-query attention with head norms and softmax-routed
experts, against its plain reference (benchmark/reference/sdar.py, which
imports nothing of the program and writes the mask out as a boolean
[2T, 2T] matrix), at toy sizes on the CPU: hidden 64, 4 query heads over 2
key-value heads of 16, 32 experts of width 32 with 4 a token, sequences of
32 tokens in blocks of 4, a vocabulary of 64.

Tolerances as in tests/test_decoder_lm.py: both sides compute in float32
(``highest``) on the CPU and differ only in the order of their sums
(1e-6 .. 1e-5); each tolerance is some ten times that and a hundred times
under what bfloat16 operands give.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gen
from benchmark.reference import common
from benchmark.reference import sdar as ref
from paddlebox_tpu.config import (
    DataFeedConfig,
    SlotConfig,
    SparseTableConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import DatasetFactory
from paddlebox_tpu.models import DecoderMoeLM
from paddlebox_tpu.parallel.sequence import full_attention
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

H, NQ, NKV, HD = 64, 4, 2, 16
F, E, TOPK, V, T, B, L = 32, 32, 4, 64, 32, 2, 4
HELD = 8  # one of four shares: experts 0..7 of the 32
LAYERS = 2
EPS = 1e-6
DIFFUSION = {"block_len": L, "eps": 1e-3, "noise_seed": 0}
VOCAB = np.sort(np.random.default_rng(7).choice(
    np.arange(1000, 9000, dtype=np.uint64), V, replace=False))
OPS = common.Ops()

# the reference's words: the keys of the model's published config
CFG = {
    "hidden_size": H, "num_attention_heads": NQ, "num_key_value_heads": NKV,
    "head_dim": HD, "moe_intermediate_size": F, "num_experts": E,
    "num_experts_held": HELD, "num_experts_per_tok": TOPK, "vocab_size": V,
    "num_hidden_layers": LAYERS, "feed": {"max_seq_len": T},
    "rms_norm_eps": EPS, "norm_topk_prob": True, "rope_theta": 10000.0,
    "rope_scaling": None, "diffusion": DIFFUSION,
}


def make_model(held=(0, HELD), **change):
    kw = dict(
        max_seq_len=T, n_heads=NQ, n_kv_heads=NKV, head_dim=HD, window=0,
        layer_types=("full_attention",) * LAYERS, qk_norm=True, n_experts=E,
        n_experts_per_tok=TOPK, expert_width=F, experts_held=held,
        rope_theta=10000.0, rms_eps=EPS, block_q=8, loss_chunk=24,
        objective="block_diffusion", diffusion=DIFFUSION)
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


# ------------------------------------------- the strips under the block mask
def dense_attention(q, k, v, block_len):
    """The [2T, 2T] masked softmax, the mask the reference's."""
    g = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, g, axis=2) for a in (k, v))
    mask = ref.block_mask(q.shape[1] // 2, block_len)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(t, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, 2 * t, NQ, HD)),
            jax.random.normal(ks[1], (B, 2 * t, NKV, HD)),
            jax.random.normal(ks[2], (B, 2 * t, NKV, 12)),
            jax.random.normal(ks[3], (B, 2 * t, NQ, 12)))


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("strips", ["a_block", "four_blocks", "one_strip",
                                    "ragged"])
@pytest.mark.parametrize("block_len", [1, 4, 8])
def test_the_strips_equal_the_dense_block_mask(block_len, strips, what):
    """``full_attention(block_diffusion=L)`` against the dense [2T, 2T]
    masked softmax: strips of one block, of four, one strip for all, and a
    length that is no multiple of the strip (nor, at L = 8, of the block)."""
    t = 44 if strips == "ragged" else 32
    block_q = {"a_block": block_len, "four_blocks": 4 * block_len,
               "one_strip": t, "ragged": 3 * block_len}[strips]
    if strips == "one_strip":  # a multiple of the block at or past the end
        block_q = -(-t // block_len) * block_len
    q, k, v, w = qkv(t, seed=block_len)
    got = highest(lambda q, k, v: full_attention(
        q, k, v, block_q=block_q, block_diffusion=block_len))
    want = highest(lambda q, k, v: dense_attention(q, k, v, block_len))
    if what == "forward":
        assert rel(got(q, k, v), want(q, k, v)) < 1e-5
        return
    g_got = jax.grad(lambda *a: (got(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g_want = jax.grad(lambda *a: (want(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for name, a, b in zip("qkv", g_got, g_want):
        assert rel(a, b) < 1e-5, name


@pytest.mark.parametrize("what", [
    "a_later_block", "its_own_blocks_clean_keys", "a_noised_key_from_clean"])
def test_a_query_never_reads_outside_the_mask(what):
    """Perturb keys and values the mask hides from some queries: their
    outputs do not move by a bit; the queries that do see them move."""
    t, blk = 32, 4
    q, k, v, _ = qkv(t, seed=9)
    attend = highest(lambda q, k, v: full_attention(
        q, k, v, block_q=8, block_diffusion=blk))
    at = np.zeros(2 * t, bool)
    if what == "a_later_block":  # block 5, both streams
        at[20:24] = at[t + 20:t + 24] = True
        blind = np.r_[0:20, t:t + 20]  # every query of blocks 0-4
        seeing = np.r_[20:24, t + 20:t + 24, 24:t]
    elif what == "its_own_blocks_clean_keys":  # clean keys of block 3
        at[t + 12:t + 16] = True
        blind = np.r_[0:16]  # noised queries up to and with block 3
        seeing = np.r_[16:t, t + 12:2 * t]
    else:  # noised keys of block 2
        at[8:12] = True
        blind = np.r_[t:2 * t, 0:8, 12:t]  # all but block 2's noised ones
        seeing = np.r_[8:12]
    bump = jnp.asarray(at)[None, :, None, None] * 3.0
    base, moved = attend(q, k, v), attend(q, k + bump, v - bump)
    assert np.array_equal(np.asarray(base[:, blind]),
                          np.asarray(moved[:, blind]))
    diff = np.abs(np.asarray(moved - base)).max(axis=(0, 2, 3))
    assert np.all(diff[seeing] > 1e-3)


def test_the_blockwise_form_names_its_three_masks():
    q, k, v, _ = qkv(8)
    with pytest.raises(ValueError, match="causal, window, block_diffusion"):
        full_attention(q, k, v, block_diffusion=4,
                       key_valid=jnp.ones((B, 16), bool))
    with pytest.raises(ValueError, match="causal, window, block_diffusion"):
        full_attention(q, k, v, causal=True, block_q=8,
                       key_valid=jnp.ones((B, 16), bool))


@pytest.mark.parametrize("kw, match", [
    ({"block_diffusion": 4, "causal": True}, "mask of its own"),
    ({"block_diffusion": 4, "window": 8}, "mask of its own"),
    ({"block_diffusion": 3, "block_q": 8}, "no multiple"),
])
def test_a_mask_that_is_not_described_is_refused(kw, match):
    q, k, v, _ = qkv(8)
    with pytest.raises(ValueError, match=match):
        full_attention(q, k, v, **kw)


# ------------------------------------------------------ the tree, described
def test_the_described_tree_is_the_reference_tree():
    """``init`` gives the leaves the reference's ``init_params`` gives, by
    name, shape and value; ``mask_embed`` comes from a key of its own, so
    every other leaf is what the description gives without the objective."""
    model = make_model()
    got = model.init(jax.random.PRNGKey(5))
    want = ref.init_params(CFG, jax.random.PRNGKey(5))
    flat = lambda t: [(jax.tree_util.keystr(p), x.shape) for p, x in
                      jax.tree_util.tree_flatten_with_path(t)[0]]
    assert flat(got) == flat(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert got["mask_embed"].shape == (H,)
    plain = make_model(objective="next_token", diffusion=None).init(
        jax.random.PRNGKey(5))
    assert "mask_embed" not in plain
    rest = {k: v for k, v in got.items() if k != "mask_embed"}
    for a, b in zip(jax.tree.leaves(rest), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert model.step_counters == DecoderMoeLM.step_counters + (
        "diffusion.positions",)


@pytest.mark.parametrize("change, match", [
    ({"layer_types": ("full_attention", "kda"),
      "kda": {"n_heads": 4, "head_dim": 16, "conv_kernel": 4,
              "gate_rank": 8}}, r"two-stream form of \['kda'\]"),
    ({"layer_types": ("sliding_attention", "full_attention")},
     r"two-stream form of \['sliding_attention'\]"),
    ({"layer_types": ("conv", "full_attention"), "conv_kernel": 3},
     r"two-stream form of \['conv'\]"),
    ({"layer_types": ("full_attention", "latent_attention"),
      "latent": {"kv_rank": 32, "qk_nope": 16, "qk_rope": 8, "v_dim": 12,
                 "interleaved": True}},
     r"two-stream form of \['latent_attention'\]"),
    ({"diffusion": {"block_len": 4, "eps": 1e-3}}, "missing .'noise_seed'"),
    ({"diffusion": None}, "diffusion"),
    ({"block_q": 6}, "no multiple of the block length"),
    ({"objective": "next_token"}, "block_diffusion objective only"),
    ({"objective": "span_corruption"}, "unknown objective"),
])
def test_a_description_that_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        make_model(**change)


# ------------------------------------------------------------- the noise
def instances(n, seed=1):
    """``n`` token sequences with their noise levels on the generator's
    grid (the dense feature: the level less a half)."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, V, size=(n, T))
    level_q = rng.integers(0, 1001, size=n)
    return tokens, ((level_q - 500) / 1000.0).astype(np.float32)[:, None]


def program_mask(model, params, tokens, dense):
    """``_noised``'s targets >= 0: the positions the program masks."""
    cls = jnp.asarray(tokens, jnp.int32)
    x0 = jnp.zeros((*tokens.shape, H))
    _, target, weight = model._noised(
        params, x0, cls, jnp.ones(tokens.shape, bool), jnp.asarray(dense))
    return np.asarray(target >= 0), np.asarray(weight)


@pytest.mark.parametrize("part", range(5))
def test_the_mask_is_the_references_bit_for_bit(part):
    """Fifty instances, ten a case: the program's draw and the
    reference's, written on its own, mask the same positions, and the same
    instance draws the same mask again; the loss weight is 1 / p."""
    model = make_model()
    params = model.init(jax.random.PRNGKey(0))
    tokens, dense = instances(50)
    sl = slice(10 * part, 10 * part + 10)
    got, weight = program_mask(model, params, tokens[sl], dense[sl])
    want, p = ref.noise(CFG, jnp.asarray(tokens[sl], jnp.int32),
                        jnp.asarray(dense[sl]))
    assert np.array_equal(got, np.asarray(want))
    assert np.allclose(weight, 1.0 / np.asarray(p), rtol=1e-6)
    again, _ = program_mask(model, params, tokens[sl][::-1], dense[sl][::-1])
    assert np.array_equal(again[::-1], got)
    level = np.round(1000 * dense[sl, 0]) / 1000 + 0.5
    assert np.allclose(np.asarray(p), 1e-3 + (1 - 1e-3) * level, atol=1e-6)
    # the share masked follows the level (32 positions: loosely)
    assert abs(got.mean() - np.asarray(p).mean()) < 0.15


# ----------------------------------------------- the loss and its gradients
def loss_inputs(dense, seed=2):
    model = make_model()
    params = model.init(jax.random.PRNGKey(seed))
    # norm scales and the [MASK] input away from their seeds
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 8)
    params["mask_embed"] = 0.5 * jax.random.normal(ks[0], (H,))
    params["norm_f"] = params["norm_f"] + 0.3 * jax.random.normal(ks[1], (H,))
    for i, lp in enumerate(params["layers"]):
        for j, n in enumerate(("n1", "n2", "q_norm", "k_norm")):
            lp[n] = lp[n] + 0.3 * jax.random.normal(
                jax.random.fold_in(ks[2 + i], j), lp[n].shape)
    tokens = np.random.default_rng(seed).integers(0, V, size=(B, T))
    tokens[1, 20:] = tokens[1, :12]  # repeated keys inside a sequence
    data = gen.PassData(
        keys=VOCAB[tokens][:, None, :], labels=np.ones(B, np.float32),
        dense=np.asarray(dense, np.float32)[:, None],
        dense_q=np.zeros((B, 1), np.int32))
    uniq, batch = common.batch_arrays(data, B * T, VOCAB)
    batch = dict(batch, B=B, S=1)
    rows = jnp.zeros((B * T, H + 2)).at[:len(uniq)].set(
        0.3 * jax.random.normal(ks[7], (len(uniq), H + 2)))
    # the program's feed: one row an occurrence, in file order
    feed = {"seq_pos": jnp.arange(B * T, dtype=jnp.int32).reshape(B, T),
            "key_class": jnp.asarray(batch["key_rank"][batch["inv"]]),
            "dense": jnp.asarray(data.dense)}
    return model, params, rows, batch, feed


def both_sides(dense):
    model, params, rows, batch, feed = loss_inputs(dense)
    inv = jnp.asarray(batch["inv"])

    @highest
    def program(p, r):
        return model.loss(p, r[inv], feed)

    @highest
    def reference(p, r):
        return ref.loss(CFG, OPS, p, r[inv], batch)

    got, g_got = jax.value_and_grad(
        lambda p, r: program(p, r)[0], argnums=(0, 1))(params, rows)
    want, g_want = jax.value_and_grad(reference, argnums=(0, 1))(params, rows)
    return program(params, rows), got, g_got, want, g_want


@pytest.fixture(scope="module")
def sides():
    return both_sides([-0.2, 0.31])  # noise levels 0.3 and 0.81


@pytest.mark.parametrize("what", ["loss", "leaves", "mask_embed", "rows"])
def test_the_loss_and_its_gradients_are_the_references(sides, what):
    """The program's ``loss`` on a batch, its gradient by every dense
    leaf, by ``mask_embed`` and by the rows -- each row's from its clean
    position and, where that is not masked, its noised one -- against the
    reference's ``loss`` under ``jax.grad``: 1e-4 relative."""
    (_, preds, counts), got, g_got, want, g_want = sides
    if what == "loss":
        assert abs(float(got) - float(want)) < 1e-5 * float(want)
        assert float(want) > 1.0 and np.all((0 < preds) & (preds <= 1))
    elif what == "leaves":
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(g_got[0])[0],
                jax.tree.leaves(g_want[0])):
            assert rel(a, b) < 1e-4, jax.tree_util.keystr(path)
    elif what == "mask_embed":
        assert float(jnp.linalg.norm(g_want[0]["mask_embed"])) > 1e-3
        assert rel(g_got[0]["mask_embed"], g_want[0]["mask_embed"]) < 1e-4
    else:
        assert float(jnp.linalg.norm(g_want[1][:, 2:])) > 1e-3
        assert rel(g_got[1][:, 2:], g_want[1][:, 2:]) < 1e-4
        assert not np.asarray(g_got[1][:, :2]).any()  # show, click


@pytest.mark.parametrize("level", ["nothing_masked", "everything_masked"])
def test_the_ends_of_the_noise_schedule(level):
    """At t = 0 (p = eps) a sequence of 32 tokens has, as a rule, no masked
    position: its loss term is 0, its prediction 1, nothing is NaN; at
    t = 1 (p = 1) every position is masked and scored, weight 1."""
    dense = [-0.5, -0.5] if level == "nothing_masked" else [0.5, 0.5]
    (_, preds, counts), got, g_got, want, g_want = both_sides(dense)
    names = make_model().step_counters
    count = dict(zip(names, np.asarray(counts)))
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(g_got))
    if level == "nothing_masked":
        assert count["trainer.tokens"] == 0 and float(got) == 0.0
        assert float(want) == 0.0 and np.all(np.asarray(preds) == 1.0)
        assert not any(np.asarray(x).any() for x in jax.tree.leaves(g_got))
    else:
        assert count["trainer.tokens"] == B * T
        assert abs(float(got) - float(want)) < 1e-5 * float(want)
        # every position scored once, weight 1 / 1: the plain mean
        assert float(got) == pytest.approx(
            -np.log(np.asarray(preds)).mean(), rel=1e-5)
    assert count["diffusion.positions"] == 2 * B * T
    assert count["moe.pairs_routed"] == 2 * B * T * TOPK * LAYERS


# ------------------------------------------------- the shares of a layer
def test_the_four_shares_add_up_to_the_uncut_layer():
    """32 experts in 4 shares of 8, on the 2T positions of two streams:
    what each share's whole layer gives, with the residual and the
    attention -- which every share computes alike -- counted ONCE, is the
    reference's layer that holds all thirty-two."""
    whole_model = make_model(held=(0, E))
    lp = whole_model.init(jax.random.PRNGKey(13))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(14), (B, 2 * T, H))
    valid = jnp.ones((B, 2 * T), bool)
    kinds = ("full_attention", "sparse")

    @highest
    def reference(lp, x):
        def one(x):
            x = x + ref.attention(CFG, OPS, lp, ref.rms_norm(x, lp["n1"], EPS))
            return x + ref.routed({**CFG, "num_experts_held": E}, OPS, lp,
                                  ref.rms_norm(x, lp["n2"], EPS))
        return jnp.stack([one(x[b]) for b in range(B)])

    def share(lo):
        model = make_model(held=(lo, lo + HELD))
        mine = {**lp, **{k: lp[k][lo:lo + HELD]
                         for k in ("w_gate", "w_up", "w_down")}}
        return highest(lambda x: model._layer(mine, x, valid, kinds))(x)

    outs, counts = zip(*[share(lo) for lo in range(0, E, HELD)])
    after_op = highest(lambda x: whole_model._attend(lp, x, kinds[0]))(x)
    want = reference(lp, x)
    parts = [out - after_op for out in outs]
    assert rel(after_op + sum(parts), want) < 1e-5
    for part in parts:  # every share does part of the work
        assert rel(part, want - after_op) > 0.05
    # counted four times, the residual and the attention would show
    assert rel(sum(outs), want) > 1.0
    # every position's k choices are counted by exactly one share
    assert int(sum(c[0] for c in counts)) == 2 * B * T * TOPK


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


def token_dataset(path, tokens, labels, dense_q):
    with open(path, "w") as f:
        for seq, y, d in zip(tokens, labels, dense_q):
            keys = " ".join(str(int(VOCAB[t])) for t in seq)
            f.write(f"1 {int(y)} {len(seq)} {keys} 1 {d / 1000:.3f}\n")
    ds = DatasetFactory().create_dataset("BoxPSDataset", feed_config())
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    return ds


def test_denoising_trains_through_the_pass_loop_like_its_reference(tmp_path):
    """BoxPSDataset -> begin_pass -> Trainer.train_from_dataset ->
    end_pass, two passes of one step each, default TrainerConfig and table
    config bar the embedding width, the noise level read from the slot
    text's dense feature; the reference's ``loss`` on the same batches
    (common.batch_arrays: the occurrences in file order), differentiated
    by ``jax.grad``, with the documented optimizers applied by hand."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, V, size=(2 * B, T))
    tokens[1, 20:] = tokens[1, :12]  # repeated keys inside a sequence
    labels = np.array([1, 0, 1, 1], np.float32)
    dense_q = np.array([-137, 402, 250, -480], np.int32)
    steps = [token_dataset(tmp_path / f"s{i}", tokens[i * B:(i + 1) * B],
                           labels[i * B:(i + 1) * B],
                           dense_q[i * B:(i + 1) * B]) for i in range(2)]
    tconf = SparseTableConfig(embedding_dim=H)
    trconf = TrainerConfig()
    model = make_model()
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, trconf, seed=0)
    params = jax.tree.map(np.asarray, trainer.params)
    assert params["mask_embed"].any()
    census = np.unique(VOCAB[tokens])

    table.begin_pass(census)
    sd = table.pass_state_dict()
    table.end_pass()
    rows = {int(k): v.astype(np.float64) for k, v in
            zip(sd["keys"], sd["values"])}  # [show, click, emb.., g2sum]

    lr, b1, b2, eps = trconf.dense_lr, 0.9, 0.999, 1e-8
    mu = jax.tree.map(np.zeros_like, params)
    nu = jax.tree.map(np.zeros_like, params)
    live = jax.tree.map(lambda x: np.ones(x.shape, bool), params)
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
            dense=(dense_q[sl, None] / 1000.0).astype(np.float32),
            dense_q=dense_q[sl, None])
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
            assert rel(g, w) < 1e-4, jax.tree_util.keystr(path)
        live = jax.tree.map(
            lambda on, g: on & (np.abs(g) > 1e-6 * np.abs(g).max()), live,
            jax.tree.map(np.asarray, gp))
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

        # the step's counters: the masked positions are the ones scored,
        # both streams' positions go through the layers and the router
        masked, _ = ref.noise(CFG, jnp.asarray(tokens[sl], jnp.int32),
                              jnp.asarray(data.dense))
        assert m["trainer.tokens"] == int(np.asarray(masked).sum()) > 0
        assert m["diffusion.positions"] == 2 * B * T
        assert m["moe.pairs_routed"] == 2 * B * T * TOPK * LAYERS
        assert 0 < m["moe.pairs_local"] < m["moe.pairs_routed"]
        assert m["moe.expert_load_mean"] == pytest.approx(
            m["moe.pairs_local"] / HELD)

    # the dense parameters after two Adam steps, by norm; the [MASK] input
    # is one of them, under Adam like the rest.  From a fresh Adam the
    # update's direction is g / (|g| + eps), so an entry whose gradient is
    # rounding noise differs by a whole lr: here whole columns of the last
    # layer's router, which only the masked positions' choices reach (the
    # renormalised weights of the chosen do not depend on the other
    # experts' logits: their gradient is zero but for rounding).  Such
    # entries (|g| under a millionth of the leaf's largest) are left out
    p0 = jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(0)))
    for (path, g), w, p, on in zip(
            jax.tree_util.tree_flatten_with_path(
                jax.tree.map(np.asarray, trainer.params))[0],
            jax.tree.leaves(params), jax.tree.leaves(p0),
            jax.tree.leaves(live)):
        assert on.mean() > 0.2, jax.tree_util.keystr(path)
        assert rel((g - p)[on], (w - p)[on]) < 1e-3, jax.tree_util.keystr(path)
    assert np.abs(np.asarray(trainer.params["mask_embed"])
                  - p0["mask_embed"]).max() > 1e-4
    for ds in steps:
        ds.close()
    trainer.close()
