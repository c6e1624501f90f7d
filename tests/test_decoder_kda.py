"""The decoder's fifth operator kind and the latent layer without a
positional code -- KDA (a gated delta rule with a decay a channel and a
state carried along the sequence) in three layers of four, latent attention
whose 8-wide slice stays unturned in the fourth, a leading dense
feed-forward, then sigmoid-routed experts with a selection bias beside a
shared one -- against its plain reference (benchmark/reference/
kimi_linear.py, which imports nothing of the program and runs the
recurrence token by token), at toy sizes on the CPU: hidden 64, 4 KDA heads
of 16 with a gate rank of 8 and 4 taps, 4 latent heads over a latent of 32
with a query/key head of 16 + 8 and a value head of 12, dense width 96, 32
experts of width 32 with 4 a token, sequences of 37 tokens (no multiple of
the chunk of 8), a vocabulary of 64.

Tolerances as in tests/test_decoder_lm.py: both sides compute in float32
(``highest``) on the CPU and differ in the order of their sums and, for the
operator, in its whole form (chunks, a triangular solve and a scan against
one token at a time): 1e-6 on the operator, so 1e-4 there and 1e-5
elsewhere, a hundred times under what bfloat16 operands give.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gen
from benchmark.reference import common
from benchmark.reference import kimi_linear as ref
from paddlebox_tpu.config import (
    DataFeedConfig,
    SlotConfig,
    SparseTableConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import DatasetFactory
from paddlebox_tpu.models import DecoderMoeLM, decoder_lm
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import (
    Trainer,
    _host_batch_dict,
    _to_device,
)

H, NH, HD, K, R = 64, 4, 16, 4, 8
RANK, NOPE, ROPE, DV = 32, 16, 8, 12
FD, F, E, TOPK, V, T, B = 96, 32, 32, 4, 64, 37, 2
HELD = 8  # one of four shares: experts 0..7 of the 32
CHUNK = 8
EPS, SCALE = 1e-5, 2.446
OPS_OF = ("kda", "kda", "kda", "latent_attention", "kda")
MLPS = ("dense", "sparse", "sparse", "sparse", "sparse")
KDA_LEAVES = (
    "n1", "kda_q", "kda_k", "kda_v", "kda_conv_q", "kda_conv_k",
    "kda_conv_v", "kda_fa", "kda_fb", "kda_A_log", "kda_dt_bias", "kda_beta",
    "kda_ga", "kda_gb", "kda_o_norm", "kda_o")
VOCAB = np.sort(np.random.default_rng(7).choice(
    np.arange(1000, 9000, dtype=np.uint64), V, replace=False))
OPS = common.Ops()

# the reference's words: the keys of the model's published config; layers
# 1-5 of a pattern numbered from 1 whose period is KDA, KDA, KDA, MLA
CFG = {
    "hidden_size": H, "num_attention_heads": NH, "kv_lora_rank": RANK,
    "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE, "v_head_dim": DV,
    "q_lora_rank": None, "mla_use_nope": True,
    "linear_attn_config": {
        "num_heads": NH, "head_dim": HD, "short_conv_kernel_size": K,
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8]},
    "kda_gate_rank": R, "intermediate_size": FD, "moe_intermediate_size": F,
    "num_shared_experts": 1, "num_experts": E, "num_experts_held": HELD,
    "num_experts_per_token": TOPK, "vocab_size": V, "num_hidden_layers": 5,
    "layers_held": [1, 2, 3, 4, 5], "first_k_dense_replace": 1,
    "feed": {"max_seq_len": T}, "rms_norm_eps": EPS,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "moe_renormalize": True,
    "routed_scaling_factor": SCALE,
}
LATENT = {"kv_rank": RANK, "qk_nope": NOPE, "qk_rope": ROPE, "v_dim": DV,
          "rotary": False}
KDA = {"n_heads": NH, "head_dim": HD, "conv_kernel": K, "gate_rank": R}


@pytest.fixture(autouse=True)
def chunks_of_eight(monkeypatch):
    """The toy sequences are a few chunks long at ``CHUNK`` positions."""
    monkeypatch.setattr(decoder_lm, "KDA_CHUNK", CHUNK)


def make_model(held=(0, HELD), **change):
    kw = dict(
        max_seq_len=T, n_heads=NH, n_kv_heads=NH, head_dim=ROPE, window=0,
        layer_types=OPS_OF, mlp_types=MLPS, kda=KDA,
        latent=LATENT, dense_width=FD, n_experts=E, n_experts_per_tok=TOPK,
        expert_width=F, experts_held=held, shared_width=F,
        router_score="sigmoid", router_bias=True, router_scale=SCALE,
        rms_eps=EPS, block_q=16, loss_chunk=24)
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


# three regimes of decay: A = 1 (weak: a token's state lives on for tens of
# tokens), A = 16, and A = 16 with dt_bias + 3, where a chunk's decays
# multiply to under 1e-38 and (k_j exp -G_j) overflows float32
REGIMES = {"weak": (1.0, 0.0), "strong": (16.0, 0.0),
           "overflowing": (16.0, 3.0)}


def kda_layer(regime: str, key=jax.random.PRNGKey(4)):
    lp = lively(make_model().init(key)["layers"][1], key,
                ("n1", "kda_o_norm"))
    A, shift = REGIMES[regime]
    return {**lp, "kda_A_log": jnp.full((NH,), np.log(A), jnp.float32),
            "kda_dt_bias": lp["kda_dt_bias"] + shift}


# ------------------------------------------------------ the tree, described
def test_the_described_tree_is_the_reference_tree():
    """``init`` gives the leaves the reference's ``init_params`` gives, by
    name and shape (what the benchmark's ``same_structure`` asks), and the
    decays are seeded as the family seeds them."""
    got = jax.eval_shape(make_model().init, jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda k: ref.init_params(CFG, k),
                          jax.random.PRNGKey(0))
    flat = lambda t: [(jax.tree_util.keystr(p), x.shape) for p, x in
                      jax.tree_util.tree_flatten_with_path(t)[0]]
    assert flat(got) == flat(want)
    assert set(got["layers"][0]) == set(KDA_LEAVES) | {
        "n2", "mlp_gate", "mlp_up", "mlp_down"}
    assert set(got["layers"][3]) == {
        "n1", "n2", "n_kv", "wq", "wkv_a", "wkv_b", "wo", "router",
        "router_bias", "w_gate", "w_up", "w_down", "shared_gate",
        "shared_up", "shared_down"}
    W = NH * HD
    shapes = {k: v.shape for k, v in got["layers"][1].items()}
    assert shapes["kda_q"] == shapes["kda_k"] == shapes["kda_v"] == (H, W)
    assert shapes["kda_conv_q"] == (K, W) and shapes["kda_fa"] == (H, R)
    assert shapes["kda_fb"] == shapes["kda_gb"] == (R, W)
    assert shapes["kda_A_log"] == (NH,) and shapes["kda_dt_bias"] == (W,)
    assert shapes["kda_beta"] == (H, NH) and shapes["kda_o_norm"] == (HD,)
    assert shapes["kda_o"] == (W, H)
    lp = make_model().init(jax.random.PRNGKey(3))["layers"][1]
    A, dt = np.exp(lp["kda_A_log"]), np.asarray(
        jax.nn.softplus(lp["kda_dt_bias"]))
    assert (1 <= A).all() and (A < 16).all()
    assert (1e-3 * 0.999 <= dt).all() and (dt <= 1e-1 * 1.001).all()
    assert dt.max() / dt.min() > 20  # weak to strong
    assert np.array_equal(lp["kda_o_norm"], np.ones(HD, np.float32))
    want = ref.init_params(CFG, jax.random.PRNGKey(3))["layers"][1]
    for name in ("kda_A_log", "kda_dt_bias", "kda_q", "kda_o"):
        np.testing.assert_allclose(lp[name], want[name], rtol=1e-6)


@pytest.mark.parametrize("change, match", [
    ({"layer_types": ("kda", "linear_attention", "kda", "kda", "kda")},
     "kinds are .*sliding_attention.*full_attention.*latent_attention"
     ".*conv.*kda"),
    ({"kda": None}, "kda layers need"),
    ({"kda": {**KDA, "chunk": 8}}, r"unknown \['chunk'\]"),
    ({"latent": {k: v for k, v in LATENT.items() if k != "v_dim"}},
     r"missing \['v_dim'\]"),
    ({"latent": {**LATENT, "rope": True}}, r"unknown \['rope'\]"),
    ({"latent": {k: v for k, v in LATENT.items() if k != "rotary"}},
     "interleaved"),
])
def test_a_description_that_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        make_model(**change)


def test_an_optional_key_left_out_is_the_accepted_description():
    """Required keys present, unknown keys refused: the accepted latent
    description (the four widths and ``interleaved``) builds as it did,
    and so does one that says ``rotary`` True in words."""
    accepted = {**{k: v for k, v in LATENT.items() if k != "rotary"},
                "interleaved": True}
    assert make_model(latent=accepted).latent == accepted
    make_model(latent={**accepted, "rotary": True})


# ----------------------------------------------------------- the operator
def operator_pair(model):
    @highest
    def program(lp, x):
        return model._kda_mix(lp, x) - x

    @highest
    def reference(lp, x):
        return jnp.stack([ref.kda(
            CFG, OPS, lp, ref.rms_norm(x[b], lp["n1"], EPS))
            for b in range(B)])

    return program, reference


@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_chunked_operator_is_the_token_recurrence(what, regime):
    """The model's operator half of a KDA layer (x + kda(n1 x)), in chunks
    of 8 over 37 positions, against the reference's recurrence token by
    token: output, and the gradient by the input and by every leaf it
    reads, at each regime of decay."""
    program, reference = operator_pair(make_model())
    lp = kda_layer(regime)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, H))
    if what == "forward":
        out = program(lp, x)
        assert np.isfinite(np.asarray(out)).all()
        assert rel(out, reference(lp, x)) < 1e-4
        return
    tgt = jax.random.normal(jax.random.PRNGKey(8), (B, T, H))
    got = jax.grad(lambda lp, x: ((program(lp, x) - tgt) ** 2).sum(),
                   argnums=(0, 1))(lp, x)
    want = jax.grad(lambda lp, x: ((reference(lp, x) - tgt) ** 2).sum(),
                    argnums=(0, 1))(lp, x)
    assert rel(got[1], want[1]) < 1e-4
    for name in KDA_LEAVES:
        assert np.linalg.norm(want[0][name]) > 0, name
        assert rel(got[0][name], want[0][name]) < 1e-4, name


def test_the_factored_form_overflows_where_the_operator_does_not():
    """What the chunked form avoids: in the third regime a chunk's
    cumulative log-decay passes -88, so exp(-G) is infinite in float32."""
    model = make_model()
    lp = kda_layer("overflowing")
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, H))
    h = decoder_lm.rms_norm(x, lp["n1"], EPS)
    g = -jnp.exp(lp["kda_A_log"])[:, None] * jax.nn.softplus(
        ((h @ lp["kda_fa"]) @ lp["kda_fb"] + lp["kda_dt_bias"]).reshape(
            B, T, NH, HD))
    G = np.cumsum(np.asarray(g)[:, :CHUNK], axis=1)
    with np.errstate(over="ignore"):
        assert G.min() < -88 and np.isinf(np.exp(-G.astype(np.float32))).any()
    assert np.isfinite(np.asarray(model._kda_mix(lp, x))).all()


@pytest.mark.parametrize("regime", list(REGIMES))
def test_the_state_is_carried_from_chunk_to_chunk(regime, monkeypatch):
    """32 positions as 4 chunks of 8 against 1 chunk of 32: the same
    outputs, so what a chunk hands the next is the state the recurrence
    has there; and as 5 chunks of 7, the last one padded."""
    lp = kda_layer(regime)
    x = jax.random.normal(jax.random.PRNGKey(9), (B, 32, H))
    runs = {}
    for c in (8, 32, 7):
        monkeypatch.setattr(decoder_lm, "KDA_CHUNK", c)
        runs[c] = highest(lambda x: make_model(
            max_seq_len=32)._kda_mix(lp, x) - x)(x)
    assert rel(runs[8], runs[32]) < 1e-5 and rel(runs[7], runs[32]) < 1e-5
    if regime == "weak":  # ... and the state matters: cut off, it shows
        monkeypatch.setattr(decoder_lm, "KDA_CHUNK", CHUNK)
        halves = jnp.concatenate([highest(lambda x: make_model(
            max_seq_len=16)._kda_mix(lp, x) - x)(x[:, lo:lo + 16])
            for lo in (0, 16)], axis=1)
        assert rel(halves[:, :16], runs[32][:, :16]) < 1e-5
        assert rel(halves[:, 16:], runs[32][:, 16:]) > 0.05


def test_the_groups_of_chunks_change_nothing(monkeypatch):
    """The chunks go through the pairs' sums and the scan in groups
    (``KDA_PAIR_ELEMS``): one chunk a group, or all at once, is the same
    operator."""
    lp = kda_layer("strong")
    x = jax.random.normal(jax.random.PRNGKey(9), (B, T, H))
    whole = highest(lambda x: make_model()._kda_mix(lp, x))(x)
    monkeypatch.setattr(decoder_lm, "KDA_PAIR_ELEMS", 1)
    single = highest(lambda x: make_model()._kda_mix(lp, x))(x)
    assert rel(single, whole) < 1e-6


def test_the_convolutions_see_zeros_before_the_first_token():
    """A change at position t moves no output before t; position 0 sees
    its own tap alone: S_1 = beta k v^T, so o_0 = beta (k . q) v, by hand."""
    model = make_model()
    lp = kda_layer("weak")
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T, H))
    mix = highest(lambda x: model._kda_mix(lp, x) - x)
    base = np.asarray(mix(x))
    t = 11
    moved = np.asarray(mix(x.at[0, t].add(1.0)))
    assert np.array_equal(moved[0, :t], base[0, :t])
    assert np.abs(moved[0, t:] - base[0, t:]).max(axis=-1).all()
    with jax.default_matmul_precision("highest"):
        h = ref.rms_norm(x[0, 0], lp["n1"], EPS)
        tap = lambda n: jax.nn.silu(
            lp["kda_conv_" + n][K - 1] * (h @ lp["kda_" + n])).reshape(NH, HD)
        unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)
        q, k, v = unit(tap("q")) / np.sqrt(HD), unit(tap("k")), tap("v")
        beta = jax.nn.sigmoid(h @ lp["kda_beta"])
        o = (beta * (q * k).sum(-1))[:, None] * v
        gate = jax.nn.sigmoid(
            ((h @ lp["kda_ga"]) @ lp["kda_gb"]).reshape(NH, HD))
        want = (ref.rms_norm(o, lp["kda_o_norm"], EPS) * gate).reshape(
            -1) @ lp["kda_o"]
    np.testing.assert_allclose(base[0, 0], want, atol=2e-6)


def test_bfloat16_operands_fail_the_operators_tolerance():
    """The stated tolerance (1e-4) is under a tenth of what the operator
    reads with its products' operands rounded to bfloat16."""
    lp = kda_layer("weak")
    x = jax.random.normal(jax.random.PRNGKey(7), (T, H))
    h = ref.rms_norm(x, lp["n1"], EPS)
    run = lambda ops: highest(lambda h: ref.kda(CFG, ops, lp, h))(h)
    assert rel(run(common.Ops("bfloat16")), run(OPS)) > 1e-3


# ------------------------------------- latent attention, nothing turned
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_latent_attention_without_a_positional_code_is_its_reference(
        what, monkeypatch):
    """The model's attention half of the latent layer (strips of scores,
    no [T, T] tensor) against the reference's dense [T, T] form with the
    8-wide slice unturned; neither ``rotary_tables`` nor ``apply_rotary``
    is called on the way."""
    def never(*a, **k):
        raise AssertionError("a rotary code entered the program")

    monkeypatch.setattr(decoder_lm, "rotary_tables", never)
    monkeypatch.setattr(decoder_lm, "apply_rotary", never)
    model = make_model()
    lp = lively(model.init(jax.random.PRNGKey(4))["layers"][3],
                jax.random.PRNGKey(6), ("n1", "n_kv"))
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, H))

    @highest
    def program(lp, x):
        return model._attend(lp, x, "latent_attention") - x

    @highest
    def reference(lp, x):
        return jnp.stack([ref.attention(
            CFG, OPS, lp, ref.rms_norm(x[b], lp["n1"], EPS))
            for b in range(B)])

    if what == "forward":
        assert rel(program(lp, x), reference(lp, x)) < 1e-5
        # a layer that turns its slice reads otherwise
        monkeypatch.undo()
        turned = make_model(latent={**LATENT, "rotary": True,
                                    "interleaved": True})
        on = highest(lambda lp, x: turned._attend(
            lp, x, "latent_attention") - x)
        assert rel(on(lp, x), reference(lp, x)) > 0.05
        return
    tgt = jax.random.normal(jax.random.PRNGKey(8), (B, T, H))
    got = jax.grad(lambda lp, x: ((program(lp, x) - tgt) ** 2).sum(),
                   argnums=(0, 1))(lp, x)
    want = jax.grad(lambda lp, x: ((reference(lp, x) - tgt) ** 2).sum(),
                    argnums=(0, 1))(lp, x)
    assert rel(got[1], want[1]) < 1e-5
    for name in ("n1", "n_kv", "wq", "wkv_a", "wkv_b", "wo"):
        assert rel(got[0][name], want[0][name]) < 1e-5, name


# ------------------------------------------------- the shares of a layer
@pytest.mark.parametrize("layer", [1, 3], ids=["kda", "latent"])
def test_the_four_shares_add_up_to_the_uncut_layer(layer):
    """32 experts in 4 shares of 8: what each share's whole layer gives,
    with the residual, the operator and the shared expert -- which every
    share computes alike -- counted ONCE, is the reference's layer that
    holds all thirty-two."""
    whole_model = make_model(held=(0, E))
    lp = whole_model.init(jax.random.PRNGKey(13))["layers"][layer]
    x = jax.random.normal(jax.random.PRNGKey(14), (B, T, H))
    valid = jnp.ones((B, T), bool)
    kinds = (OPS_OF[layer], MLPS[layer])
    op = ref.kda if kinds[0] == "kda" else ref.attention

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

    @highest
    def alike(x):  # what every share computes: operator, shared expert
        x = (whole_model._kda_mix(lp, x) if kinds[0] == "kda"
             else whole_model._attend(lp, x, kinds[0]))
        h = decoder_lm.rms_norm(x, lp["n2"], EPS).reshape(B * T, H)
        return x + decoder_lm.swiglu(
            h, lp["shared_gate"], lp["shared_up"],
            lp["shared_down"]).reshape(B, T, H)

    outs, counts = zip(*[share(lo) for lo in range(0, E, HELD)])
    once, want = alike(x), reference(lp, x)
    parts = [out - once for out in outs]
    assert rel(once + sum(parts), want) < 1e-5
    for part in parts:  # every share does part of the work
        assert rel(part, want - once) > 0.05
    # counted four times, the residual, operator and shared expert show
    assert rel(sum(outs), want) > 1.0
    # every token's k choices are counted by exactly one share
    assert int(sum(c[0] for c in counts)) == B * T * TOPK


# ------------------------------------------------- through the pass loop
def feed_config(T=T):
    slots = [
        SlotConfig(name="click", type="float", is_dense=True, shape=(1,)),
        SlotConfig(name="slot0", type="uint64"),
        SlotConfig(name="dense0", type="float", is_dense=True, shape=(1,)),
    ]
    return DataFeedConfig(
        slots=slots, batch_size=B, label_slot="click",
        batch_key_capacity=B * T, sequence_slot="slot0", max_seq_len=T)


def token_dataset(path, tokens, labels, vocab=VOCAB):
    with open(path, "w") as f:
        for seq, y in zip(tokens, labels):
            keys = " ".join(str(int(vocab[t])) for t in seq)
            f.write(f"1 {int(y)} {len(seq)} {keys} 1 0.5\n")
    ds = DatasetFactory().create_dataset("BoxPSDataset", feed_config())
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    return ds


def test_kda_decoder_trains_through_the_pass_loop_like_its_reference(
        tmp_path):
    """Layer 1 and one period -- KDA + dense, then KDA, KDA, latent
    attention, KDA over routed experts beside a shared one: BoxPSDataset ->
    begin_pass -> Trainer.train_from_dataset -> end_pass, two passes of one
    step each, default TrainerConfig and table config bar the embedding
    width; the reference's ``loss`` on the same batches
    (common.batch_arrays: the occurrences in file order), differentiated
    by ``jax.grad``, with the documented optimizers applied by hand."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, V, size=(2 * B, T))
    tokens[1, 20:32] = tokens[1, :12]  # repeated keys inside a sequence
    labels = np.array([1, 0, 1, 1], np.float32)
    steps = [token_dataset(tmp_path / f"s{i}", tokens[i * B:(i + 1) * B],
                           labels[i * B:(i + 1) * B]) for i in range(2)]
    tconf = SparseTableConfig(embedding_dim=H)
    trconf = TrainerConfig()
    model = make_model()
    assert tconf.row_width == model.emb_width
    assert model.step_counters[-1] == "kda.tokens"
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
                # the first gradient to 3e-5; the second is taken where
                # one Adam step has put each side (an entry whose gradient
                # is rounding noise moves by a whole lr either way): 1.5e-4
                assert rel(g, w) < (1e-4 if i == 0 else 1e-3), name
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
                   want[:, 2:-1] - r_uniq[:, 2:-1]) < (
                       1e-4 if i == 0 else 1e-3)  # as the dense leaves
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

    # the step's counters: positions through the four KDA operators, pairs
    # over the four SPARSE layers only
    assert counters["kda.tokens"] == 4 * B * T
    assert counters["trainer.tokens"] == B * (T - 1)
    assert counters["moe.pairs_routed"] == B * T * TOPK * 4
    assert 0 < counters["moe.pairs_local"] < counters["moe.pairs_routed"]
    assert counters["moe.expert_load_mean"] == pytest.approx(
        counters["moe.pairs_local"] / HELD)
    for ds in steps:
        ds.close()
    trainer.close()


# ------------------------------------- what the accepted descriptions lower to
PIN_T, PIN_VOCAB = 32, np.sort(np.random.default_rng(7).choice(
    np.arange(1000, 9000, dtype=np.uint64), V, replace=False))
PIN_COMMON = dict(max_seq_len=PIN_T, n_heads=4, head_dim=16, window=8,
                  n_experts=16, n_experts_per_tok=4, expert_width=32,
                  block_q=16, loss_chunk=24)
PINNED = {  # description -> (train.step's StableHLO text, init's leaves)
    "mellum2": (dict(
        PIN_COMMON, n_kv_heads=2, experts_held=(2, 6),
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        yarn={"factor": 4.0, "original_max_position_embeddings": 16,
              "beta_fast": 4.0, "beta_slow": 1.0,
              "attention_factor": 1.1386}),
        630650,
        "115ba0e1d3259973930fad59cdd5308dae6772a242b2ea254291c331c543cca4",
        "fce56c46540bf1c545b4c07601aa05be8b897c69259e230f85fb99a9d78bc330"),
    "kanana2": (dict(
        PIN_COMMON, n_kv_heads=4, experts_held=(0, 4),
        layer_types=("latent_attention",) * 3,
        mlp_types=("dense", "sparse", "sparse"), dense_width=96,
        shared_width=64, router_score="sigmoid", router_bias=True,
        router_scale=2.448,
        latent={"kv_rank": 32, "qk_nope": 16, "qk_rope": 8, "v_dim": 12,
                "interleaved": True}),
        529676,
        "28d71126f9dd47d6406ba63698ebcf4a41024b202950ea27483c62b554d4af00",
        "7ff6a9af5ef341879bf78fb2460c9c998f9799cde86a98be436ef1ff49a98694"),
    "lfm2": (dict(
        PIN_COMMON, n_kv_heads=2, experts_held=(0, 2), rms_eps=1e-5,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        mlp_types=("dense",) + ("sparse",) * 4, qk_norm=True, conv_kernel=3,
        dense_width=96, router_score="sigmoid", router_bias=True),
        517322,
        "c0dfa9a8e03491d188df11a40361e376e5960bb75844b6fd0f9f4d260874d69a",
        "6cb580f06b34aa2f36f9118be8c391881525b2b81aabac1cb1f04afb838a3564"),
    # taken on 488ad8c (PR 40), the commit before the block-diffusion
    # objective, with this very code and this file's chunks of eight
    "kimi_linear": (dict(
        PIN_COMMON, n_kv_heads=4, experts_held=(0, 4), rms_eps=EPS,
        layer_types=OPS_OF, mlp_types=MLPS, kda=KDA, latent=LATENT,
        dense_width=96, shared_width=32, router_score="sigmoid",
        router_bias=True, router_scale=SCALE),
        1396629,
        "dd781c9a04320beb5fe97beccf5cab5c8f6e25b4166a9fc37a6f59ccf91431be",
        "6b3c3d8906f666eee9b9da2b4211d72ddb2cb6c638668a0593f6326d19c46a0d"),
}


@pytest.mark.parametrize("name", list(PINNED))
def test_an_accepted_description_lowers_to_the_program_it_lowered_to(
        name, tmp_path):
    """A description without ``"kda"`` and without ``"rotary"`` is the
    program it was before there were such words, and one without the
    block-diffusion objective the program it was before there was a second
    objective: the ``train.step`` StableHLO text of a toy description of
    each accepted decoder configuration, taken on the commit before this
    operator kind (9670c68, PR 38; the fourth, which has the kind, on
    488ad8c, PR 40, the commit before the second objective) with this very
    code, by length and sha256, and ``init``'s bits leaf by leaf (under tests/conftest.py's XLA flags: a normal draw's
    last bit follows the CPU's instruction set).  A later change to code
    these descriptions run moves the pins: take them anew on its parent
    first, and say so."""
    kw, n_chars, text_sha, init_sha = PINNED[name]
    tconf = SparseTableConfig(embedding_dim=H)
    model = DecoderMoeLM(tconf.row_width, PIN_VOCAB, **kw)
    tokens = np.random.default_rng(3).integers(0, V, size=(B, PIN_T))
    path = tmp_path / name
    with open(path, "w") as f:
        for seq in tokens:
            keys = " ".join(str(int(PIN_VOCAB[t])) for t in seq)
            f.write(f"1 1 {PIN_T} {keys} 1 0.5\n")
    ds = DatasetFactory().create_dataset("BoxPSDataset", feed_config(PIN_T))
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(), seed=0)
    table.begin_pass(ds.unique_keys())
    batch = next(iter(ds.batches()))
    dev = _to_device(_host_batch_dict(
        batch, table.plan_batch(batch), batch.n_sparse_slots,
        vocab_keys=model.vocab_keys))
    text = trainer._build_step().lower(
        trainer.params, trainer.opt_state, table.values, table.g2sum,
        trainer._init_mstate(), dev).as_text()
    table.abort_pass()
    ds.close()
    trainer.close()
    assert len(text) == n_chars
    assert hashlib.sha256(text.encode()).hexdigest() == text_sha
    bits = hashlib.sha256()
    for leaf in jax.tree.leaves(model.init(jax.random.PRNGKey(11))):
        bits.update(np.asarray(leaf).tobytes())
    assert bits.hexdigest() == init_sha
    assert model.step_counters == DecoderMoeLM.step_counters + (
        ("kda.tokens",) if "kda" in kw["layer_types"] else ())
