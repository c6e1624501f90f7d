"""A stack of layers that runs several times over the same weights -- the
sandwich residual form, ``norm_f`` between the rounds, a learned exit gate
and the third objective, the loss weighted by the exit distribution less
beta times its entropy -- against its plain reference
(benchmark/reference/ouro.py, which imports nothing of the program: R
Python rounds over L Python layers), at toy sizes on the CPU: hidden 64, 2
layers, 4 heads of 16 over as many key-value heads, a SwiGLU of 96,
sequences of 32 tokens, a vocabulary of 64, R = 3 and 4.

Tolerances as in tests/test_decoder_lm.py: both sides compute in float32
(``highest``) on the CPU and differ only in the order of their sums
(1e-6 .. 1e-5); each tolerance is some ten times that and, as
``test_bfloat16_products_fail_the_tolerances`` shows, more than ten times
under what bfloat16 products give.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gen
from benchmark.reference import common
from benchmark.reference import ouro as ref
from paddlebox_tpu.config import (
    DataFeedConfig,
    SlotConfig,
    SparseTableConfig,
    TrainerConfig,
)
from paddlebox_tpu.data.dataset import DatasetFactory
from paddlebox_tpu.models import DecoderMoeLM
from paddlebox_tpu.models.decoder_lm import exit_distribution
from paddlebox_tpu.sparse.table import SparseTable
from paddlebox_tpu.train.trainer import Trainer

H, NQ, HD, F, V, T, B, LAYERS = 64, 4, 16, 96, 64, 32, 2, 2
EPS, BETA = 1e-6, 0.05
VOCAB = np.sort(np.random.default_rng(7).choice(
    np.arange(1000, 9000, dtype=np.uint64), V, replace=False))
OPS = common.Ops()
LOSS_TOL, GRAD_TOL = 1e-5, 1e-4


def cfg_of(R):
    """The reference's words: the keys of the model's published config."""
    return {
        "hidden_size": H, "num_attention_heads": NQ,
        "num_key_value_heads": NQ, "head_dim": HD, "intermediate_size": F,
        "vocab_size": V, "num_hidden_layers": LAYERS, "total_ut_steps": R,
        "feed": {"max_seq_len": T}, "rms_norm_eps": EPS,
        "rope_theta": 10000.0, "rope_scaling": None, "exit": {"beta": BETA},
    }


def make_model(R=4, **change):
    kw = dict(
        max_seq_len=T, n_heads=NQ, n_kv_heads=NQ, head_dim=HD, window=0,
        layer_types=("full_attention",) * LAYERS,
        mlp_types=("dense",) * LAYERS, dense_width=F, rope_theta=10000.0,
        rms_eps=EPS, block_q=8, loss_chunk=24, loops=R, sandwich=True,
        objective="looped_exit", exit={"beta": BETA})
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


def lively(params, seed):
    """Norm scales and the gate away from their seeds: every one of a
    layer's four norms, ``norm_f`` and the gate's bias then carries a
    gradient that tells it from its neighbours."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    params = jax.tree.map(lambda x: x, params)
    params["norm_f"] = params["norm_f"] + 0.3 * jax.random.normal(
        next(ks), (H,))
    for lp in params["layers"]:
        for n in sorted({"n1", "n1b", "n2", "n2b"} & set(lp)):
            lp[n] = lp[n] + 0.3 * jax.random.normal(next(ks), (H,))
    if "exit_gate" in params:
        params["exit_gate"] = {
            "w": 2.0 * params["exit_gate"]["w"],
            "b": jnp.asarray([0.4], jnp.float32)}
    return params


def loss_inputs(model, seed=2):
    params = lively(model.init(jax.random.PRNGKey(seed)), seed + 1)
    tokens = np.random.default_rng(seed).integers(0, V, size=(B, T))
    tokens[1, 20:] = tokens[1, :12]  # repeated keys inside a sequence
    data = gen.PassData(
        keys=VOCAB[tokens][:, None, :], labels=np.ones(B, np.float32),
        dense=np.zeros((B, 1), np.float32),
        dense_q=np.zeros((B, 1), np.int32))
    uniq, batch = common.batch_arrays(data, B * T, VOCAB)
    batch = dict(batch, B=B, S=1)
    rows = jnp.zeros((B * T, H + 2)).at[:len(uniq)].set(
        0.3 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                (len(uniq), H + 2)))
    # the program's feed: one row an occurrence, in file order
    feed = {"seq_pos": jnp.arange(B * T, dtype=jnp.int32).reshape(B, T),
            "key_class": jnp.asarray(batch["key_rank"][batch["inv"]])}
    return params, rows, batch, feed


def both_sides(R):
    model = make_model(R)
    params, rows, batch, feed = loss_inputs(model)
    inv = jnp.asarray(batch["inv"])
    program = highest(lambda p, r: model.loss(p, r[inv], feed))
    reference = highest(
        lambda p, r: ref.loss_and_sums(cfg_of(R), OPS, p, r[inv], batch))
    got, g_got = jax.value_and_grad(
        lambda p, r: program(p, r)[0], argnums=(0, 1))(params, rows)
    want, g_want = jax.value_and_grad(
        lambda p, r: reference(p, r)[0], argnums=(0, 1))(params, rows)
    return (model, program(params, rows), got, g_got,
            reference(params, rows)[1], want, g_want)


@pytest.fixture(scope="module", params=[3, 4])
def sides(request):
    return request.param, both_sides(request.param)


# ------------------------------------- the reference against a plainer one
def plain_loss(R, rounds, rows_occ, batch):
    """The equations of the reference's docstring with nothing done for
    memory: every sequence at once, whole [T, T] scores of all heads, whole
    [B, T, V] logits of every round, no ``jax.checkpoint``, no ``lax.map``.
    ``rounds[r]`` is the tree round r + 1 reads: R times the same tree is
    the model, R different ones a stack whose rounds are untied."""
    inv = batch["inv"]
    x = jnp.zeros((B * T + 1, H)).at[
        jnp.where(batch["mask"] > 0, batch["ins"] * T + batch["pos"], B * T)
    ].add(rows_occ[:, 2:])[:B * T].reshape(B, T, H)
    cls = jnp.asarray(batch["key_rank"][inv][:B * T]).reshape(B, T)
    cos, sin = ref.rotary(cfg_of(R), T)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def norm(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale

    def turn(a):  # [B, T, heads, d]
        rot = jnp.concatenate([-a[..., HD // 2:], a[..., :HD // 2]], -1)
        return a * cos[:, None] + rot * sin[:, None]

    ces, gates = [], []
    for p in rounds:
        for lp in p["layers"]:
            h = norm(x, lp["n1"])
            q, k, v = (
                (h @ lp[w]).reshape(B, T, NQ, HD) for w in ("wq", "wk", "wv"))
            s = jnp.einsum("bqhd,bkhd->bhqk", turn(q), turn(k)) / np.sqrt(HD)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
                jnp.where(causal, s, -jnp.inf), -1), v)
            x = x + norm(a.reshape(B, T, -1) @ lp["wo"], lp["n1b"])
            h = norm(x, lp["n2"])
            x = x + norm((jax.nn.silu(h @ lp["mlp_gate"]) * (
                h @ lp["mlp_up"])) @ lp["mlp_down"], lp["n2b"])
        x = norm(x, p["norm_f"])
        logp = jax.nn.log_softmax(x @ p["head"].T, -1)
        ces.append(-jnp.take_along_axis(
            logp[:, :-1], cls[:, 1:, None], axis=2)[..., 0])
        gates.append(
            x[:, :-1] @ p["exit_gate"]["w"] + p["exit_gate"]["b"])
    lam = jax.nn.sigmoid(jnp.stack(gates))  # [R, B, T - 1]
    left = jnp.cumprod(1.0 - lam[:-1], axis=0)
    p = jnp.concatenate([lam[:1], lam[1:-1] * left[:-1], left[-1:]])
    entropy = -(p * jnp.log(p)).sum(0)
    return ((p * jnp.stack(ces)).sum(0) - BETA * entropy).mean()


@pytest.mark.parametrize("R", [3, 4])
def test_what_the_reference_does_for_memory_changes_no_number(R):
    """benchmark/reference/ouro.py (one sequence at a time, a head at a
    time, every layer application and block of logits rematerialised, the
    exit distribution in logarithms) against the form above: the loss and
    its gradient by every leaf and by the rows."""
    params, rows, batch, _ = loss_inputs(make_model(R))
    inv = jnp.asarray(batch["inv"])
    want, g_want = jax.value_and_grad(highest(
        lambda p, r: plain_loss(R, [p] * R, r[inv], batch)), (0, 1))(
            params, rows)
    got, g_got = jax.value_and_grad(highest(
        lambda p, r: ref.loss(cfg_of(R), OPS, p, r[inv], batch)), (0, 1))(
            params, rows)
    assert abs(float(got) - float(want)) < LOSS_TOL * float(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_got)[0],
                            jax.tree.leaves(g_want)):
        assert rel(a, b) < GRAD_TOL, jax.tree_util.keystr(path)


# ----------------------------------------------- the loss and its gradients
@pytest.mark.parametrize("what", ["loss", "leaves", "rows"])
def test_the_loss_and_its_gradients_are_the_references(sides, what):
    """The program's ``loss`` on a batch (the rounds one ``lax.scan``), its
    gradient by every dense leaf -- the four norms of a layer, ``norm_f``
    and the gate among them -- and by the rows, against the reference's
    ``loss`` under ``jax.grad``: 1e-4 relative."""
    R, (_, (_, preds, _), got, g_got, _, want, g_want) = sides
    if what == "loss":
        assert abs(float(got) - float(want)) < LOSS_TOL * float(want)
        assert float(want) > 1.0 and np.all((0 < preds) & (preds <= 1))
    elif what == "leaves":
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(g_got[0])[0],
                jax.tree.leaves(g_want[0])):
            assert float(jnp.linalg.norm(b)) > 1e-4
            assert rel(a, b) < GRAD_TOL, jax.tree_util.keystr(path)
    else:
        assert float(jnp.linalg.norm(g_want[1][:, 2:])) > 1e-3
        assert rel(g_got[1][:, 2:], g_want[1][:, 2:]) < GRAD_TOL
        assert not np.asarray(g_got[1][:, :2]).any()  # show, click


def test_bfloat16_products_fail_the_tolerances():
    """The stated tolerances (1e-5 on the loss, 1e-4 on a gradient) are
    under a tenth of what the reference reads with its products' operands
    rounded to bfloat16."""
    params, rows, batch, _ = loss_inputs(make_model(4))
    inv = jnp.asarray(batch["inv"])
    run = lambda ops: jax.value_and_grad(highest(  # noqa: E731
        lambda p, r: ref.loss(cfg_of(4), ops, p, r[inv], batch)), (0, 1))(
            params, rows)
    (low, g_low), (want, g_want) = run(common.Ops("bfloat16")), run(OPS)
    assert abs(float(low) - float(want)) > 10 * LOSS_TOL * float(want)
    assert rel(g_low[1], g_want[1]) > 10 * GRAD_TOL
    for a, b in zip(jax.tree.leaves(g_low[0]["layers"]),
                    jax.tree.leaves(g_want[0]["layers"])):
        assert rel(a, b) > 10 * GRAD_TOL


def test_a_shared_leafs_gradient_is_the_sum_over_its_rounds(sides):
    """R untied copies of the tree, round r reading copy r: the program's
    gradient of a leaf is the sum of the R copies' gradients, leaf by
    leaf."""
    R, (model, _, _, g_got, _, _, _) = sides
    params, rows, batch, _ = loss_inputs(model)
    inv = jnp.asarray(batch["inv"])
    copies = [jax.tree.map(lambda x: x + 0.0, params) for _ in range(R)]
    untied = jax.grad(highest(
        lambda ps, r: plain_loss(R, ps, r[inv], batch)))(copies, rows)
    summed = jax.tree.map(lambda *g: sum(g), *untied)
    for (path, a), b, parts in zip(
            jax.tree_util.tree_flatten_with_path(g_got[0])[0],
            jax.tree.leaves(summed), zip(*map(jax.tree.leaves, untied))):
        assert rel(a, b) < GRAD_TOL, jax.tree_util.keystr(path)
        if "layers" in jax.tree_util.keystr(path):
            # every round's use carries a part of its own
            assert all(rel(part, b) > 0.05 for part in parts)


# ------------------------------------------------- the exit distribution
def test_the_exit_distribution_sums_to_one_and_the_counters_are_its_sums(
        sides):
    R, (model, (_, _, counts), _, _, sums, _, _) = sides
    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(R), (R, B, T))
    p, entropy = exit_distribution(gates)
    assert np.allclose(np.asarray(p.sum(axis=0)), 1.0, atol=1e-6)
    assert np.all(np.asarray(p) > 0) and np.all(np.asarray(entropy) > 0)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(gates, np.float64)))
    assert np.allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-5)
    assert np.allclose(p[-1], np.prod(1 - lam[:-1], axis=0), rtol=1e-5)
    count = dict(zip(model.step_counters, np.asarray(counts, np.float64)))
    scored = B * (T - 1)
    assert model.step_counters[-R - 2:] == ("loop.layer_passes",) + tuple(
        f"loop.exit_mass_{r}" for r in range(1, R + 1)) + (
            "loop.exit_entropy",)
    assert count["trainer.tokens"] == scored == int(sums["scored"])
    assert count["loop.layer_passes"] == B * T * R * LAYERS
    mass = [count[f"loop.exit_mass_{r}"] for r in range(1, R + 1)]
    assert sum(mass) == pytest.approx(scored, rel=1e-6)
    assert np.allclose(mass, np.asarray(sums["exit_mass"]), rtol=1e-5)
    assert min(mass) > 0.02 * scored  # a lively gate: every round has mass
    assert count["loop.exit_entropy"] == pytest.approx(
        float(sums["exit_entropy"]), rel=1e-5)
    assert 0 < count["loop.exit_entropy"] < scored * np.log(R)
    # no routed expert: the MoE sums read 0
    assert not any(count[k] for k in model.step_counters[1:5])


@pytest.mark.parametrize("R", [3, 4])
@pytest.mark.parametrize("bias", [30.0, -30.0])
def test_a_gate_that_always_or_never_exits_scores_one_round(R, bias):
    """The gate's bias at +30: every position exits at round 1, and the
    loss is the same description with ``loops`` 1 under ``next_token``; at
    -30 no position exits early, and it is ``loops`` R under
    ``next_token``, the last round scored.  The entropy is 0 both ways."""
    model = make_model(R)
    params, rows, batch, feed = loss_inputs(model)
    params["exit_gate"] = {"w": 0.0 * params["exit_gate"]["w"],
                           "b": jnp.asarray([bias], jnp.float32)}
    loss, preds, counts = highest(lambda p, r: model.loss(p, r, feed))(
        params, rows[batch["inv"]])
    plain = make_model(R if bias < 0 else 1, objective="next_token",
                       exit=None)
    rest = {k: v for k, v in params.items() if k != "exit_gate"}
    want, want_preds, _ = highest(lambda p, r: plain.loss(p, r, feed))(
        rest, rows[batch["inv"]])
    assert abs(float(loss) - float(want)) < LOSS_TOL * float(want)
    assert np.allclose(preds, want_preds, rtol=1e-5)
    count = dict(zip(model.step_counters, np.asarray(counts)))
    assert count["loop.exit_entropy"] < 1e-9 * B * T
    at = 1 if bias > 0 else R
    assert count[f"loop.exit_mass_{at}"] == pytest.approx(B * (T - 1))
    reference, sums = highest(lambda p, r: ref.loss_and_sums(
        cfg_of(R), OPS, p, r, batch))(params, rows[batch["inv"]])
    assert abs(float(reference) - float(want)) < LOSS_TOL * float(want)
    assert float(sums["exit_entropy"]) < 1e-9 * B * T


# ------------------------------------------------------ the tree, described
def test_the_described_tree_is_the_reference_tree():
    """``init`` gives the leaves the reference's ``init_params`` gives, by
    name, shape and value; the gate comes from a key of its own and the
    sandwich's norms are ones, so every other leaf is what the description
    gives without them."""
    got = make_model().init(jax.random.PRNGKey(5))
    want = ref.init_params(cfg_of(4), jax.random.PRNGKey(5))
    flat = lambda t: [(jax.tree_util.keystr(p), x.shape) for p, x in  # noqa
                      jax.tree_util.tree_flatten_with_path(t)[0]]
    assert flat(got) == flat(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert got["exit_gate"]["w"].shape == (H,)
    plain = make_model(loops=1, sandwich=False, objective="next_token",
                       exit=None)
    assert plain.step_counters == DecoderMoeLM.step_counters
    plain = plain.init(jax.random.PRNGKey(5))
    assert "exit_gate" not in plain and "n1b" not in plain["layers"][0]
    for lp, lq in zip(got["layers"], plain["layers"]):
        assert all(np.array_equal(lp[k], lq[k]) for k in lq)
    assert np.array_equal(got["head"], plain["head"])


KDA = {"n_heads": 4, "head_dim": 16, "conv_kernel": 4, "gate_rank": 8}


@pytest.mark.parametrize("change, match", [
    ({"objective": "block_diffusion", "exit": None,
      "diffusion": {"block_len": 4, "eps": 1e-3, "noise_seed": 0}},
     "loops=4 under it is not written"),
    ({"layer_types": ("full_attention", "kda"), "kda": KDA,
      "sandwich": False}, r"loops=4 with \['kda'\] layers: a state carried"),
    ({"layer_types": ("conv", "full_attention"), "conv_kernel": 3,
      "sandwich": False}, r"loops=4 with \['conv'\] layers: a state carried"),
    ({"objective": "next_token"}, "exit describes the looped_exit objective"),
    ({"exit": None}, "missing .'beta'"),
    ({"exit": {"beta": 0.05, "threshold": 1.0}}, "unknown .'threshold'"),
    ({"loops": 1}, "runs more than once; loops=1"),
    ({"loops": 0}, "the stack runs 0 times"),
    ({"loops": 1, "objective": "next_token", "exit": None, "kda": KDA,
      "layer_types": ("kda", "full_attention")},
     r"sandwich form of \['kda'\] layers is not written"),
    ({"mlp_types": ("dense", "sparse"), "n_experts": 8, "expert_width": 32,
      "n_experts_per_tok": 2},
     r"sandwich form of \['sparse'\] layers is not written"),
    ({"mlp_types": ("dense", "sparse"), "sandwich": False},
     r"a sparse layer needs \['n_experts', 'n_experts_per_tok', "
     r"'expert_width'\]"),
    ({"mlp_types": ("dense", "sparse"), "n_experts": 8, "expert_width": 32,
      "sandwich": False}, r"a sparse layer needs \['n_experts_per_tok'\]"),
])
def test_a_description_that_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        make_model(**change)


def test_a_description_with_no_sparse_layer_names_no_expert():
    """Every ``mlp_types`` ``"dense"``: the three sizes of the experts are
    left out (``make_model`` gives none), nothing of them is a leaf, and
    the MoE ``step_counters`` read 0, as a dense layer's do in a model that
    has sparse ones too."""
    model = make_model(loops=1, sandwich=False, objective="next_token",
                       exit=None)
    assert (model.n_experts, model.top_k, model.expert_width) == (0, 0, 0)
    params, rows, batch, feed = loss_inputs(model)
    assert not {"router", "w_gate"} & set(params["layers"][0])
    loss, _, counts = highest(lambda p, r: model.loss(p, r, feed))(
        params, rows[batch["inv"]])
    count = dict(zip(model.step_counters, np.asarray(counts)))
    assert np.isfinite(float(loss)) and count["trainer.tokens"] == B * (T - 1)
    assert [count[k] for k in model.step_counters[1:]] == [0.0] * 4


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


def test_the_looped_stack_trains_through_the_pass_loop(tmp_path):
    """BoxPSDataset -> begin_pass -> Trainer.train_from_dataset ->
    end_pass, one pass of two steps, default TrainerConfig and table config
    bar the embedding width: the first step's loss and the gradient every
    dense leaf's optimizer got are the reference's on the same batch, and
    the ``loop.*`` sums come back in the pass's metrics and in
    telemetry."""
    from paddlebox_tpu import telemetry

    R = 4
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, V, size=(2 * B, T))
    path = tmp_path / "pass"
    with open(path, "w") as f:
        for seq in tokens:
            keys = " ".join(str(int(VOCAB[t])) for t in seq)
            f.write(f"1 1 {T} {keys} 1 0.0\n")
    ds = DatasetFactory().create_dataset("BoxPSDataset", feed_config())
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=H)
    model = make_model(R)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(), seed=0)
    params = jax.tree.map(np.asarray, trainer.params)
    before = telemetry.registry.snapshot()["counters"]

    # the first step alone, against the reference on the same batch
    first = tmp_path / "first"
    with open(path) as f, open(first, "w") as g:
        g.writelines(f.readlines()[:B])
    ds1 = DatasetFactory().create_dataset("BoxPSDataset", feed_config())
    ds1.set_filelist([str(first)])
    ds1.load_into_memory()
    table.begin_pass(ds.unique_keys())
    sd = table.pass_state_dict()
    m = trainer.train_from_dataset(ds1, table)
    table.end_pass()
    data = gen.PassData(
        keys=VOCAB[tokens[:B]][:, None, :], labels=np.ones(B, np.float32),
        dense=np.zeros((B, 1), np.float32),
        dense_q=np.zeros((B, 1), np.int32))
    uniq, batch = common.batch_arrays(data, B * T, VOCAB)
    batch = dict(batch, B=B, S=1)
    r_pad = np.zeros((B * T, H + 2), np.float32)
    r_pad[:len(uniq)] = sd["values"][
        np.searchsorted(sd["keys"], uniq)][:, :-1]
    (want, sums), gp = jax.value_and_grad(highest(
        lambda p: ref.loss_and_sums(
            cfg_of(R), OPS, p, jnp.asarray(r_pad)[batch["inv"]], batch)),
        has_aux=True)(params)
    assert m["steps"] == 1
    assert abs(m["loss"] - float(want)) < 2e-5 * float(want)
    got_mu = jax.tree.map(np.asarray, trainer.opt_state[0].mu)
    for (path_, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_mu)[0],
            jax.tree.leaves(gp)):
        assert rel(g, 0.1 * np.asarray(w)) < GRAD_TOL, \
            jax.tree_util.keystr(path_)
    assert m["loop.exit_entropy"] == pytest.approx(
        float(sums["exit_entropy"]), rel=1e-4)

    # a whole pass: two steps
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    scored = 2 * B * (T - 1)
    assert m["steps"] == 2 and m["trainer.tokens"] == scored
    assert m["loop.layer_passes"] == 2 * B * T * R * LAYERS
    mass = [m[f"loop.exit_mass_{r}"] for r in range(1, R + 1)]
    assert sum(mass) == pytest.approx(scored, rel=1e-5) and min(mass) > 0
    assert 0 < m["loop.exit_entropy"] < scored * np.log(R)
    assert m["moe.pairs_routed"] == m["moe.pairs_local"] == 0
    after = telemetry.registry.snapshot()["counters"]
    grew = lambda k: after[k] - before.get(k, 0.0)  # noqa: E731
    assert grew("loop.layer_passes") == 3 * B * T * R * LAYERS
    assert grew("loop.exit_entropy") > m["loop.exit_entropy"]
    assert sum(grew(f"loop.exit_mass_{r}") for r in range(1, R + 1)) == (
        pytest.approx(3 * B * (T - 1), rel=1e-5))
    ds.close()
    ds1.close()
    trainer.close()


# ------------------------------------------------ what a layer keeps on a TPU
@pytest.mark.parametrize("R", [1, 3])
def test_a_layer_keeps_its_kernels_output_on_a_tpu_only(monkeypatch, R):
    """``_stack`` asks ``sequence.kernel_residuals`` what a layer's
    checkpoint keeps, and the answer follows the backend alone.  As a TPU
    answers (the backend's name patched; traced, not run): every layer's
    attention is the kernel and the gradient's jaxpr holds 3
    ``pallas_call``s a layer -- forward, dq, dk/dv, the forward not again
    when the layer is rematerialised -- with the rounds as one ``lax.scan``
    body as without them.  On the CPU no policy is named and there is no
    kernel: the step is the text it was, which is what
    tests/test_decoder_kda.py's sha256 pins of the four accepted
    descriptions guard, unedited, for this change."""
    from jax._src.core import jaxprs_in_params
    from paddlebox_tpu.parallel import sequence
    t = 128  # a length the kernel's block divides
    loop = {} if R > 1 else dict(
        loops=1, objective="next_token", exit=None, sandwich=False)
    rows = jnp.ones((B * t, H + 2))
    feed = {"seq_pos": jnp.arange(B * t, dtype=jnp.int32).reshape(B, t),
            "key_class": jnp.arange(B * t, dtype=jnp.int32) % V}

    def calls(jaxpr) -> int:  # those of inner jaxprs too
        return sum((eqn.primitive.name == "pallas_call") + sum(
            calls(sub) for sub in jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    def kernel_calls() -> int:
        """``pallas_call``s of the gradient, of a model of its own: a
        traced layer is remembered."""
        model = make_model(R, max_seq_len=t, **loop)
        return calls(jax.make_jaxpr(jax.grad(
            lambda p: model.loss(p, rows, feed)[0]))(
                model.init(jax.random.PRNGKey(0))).jaxpr)

    assert sequence.kernel_residuals() is None
    assert kernel_calls() == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sequence.kernel_residuals() is not None
    assert kernel_calls() == 3 * LAYERS
