"""The Pallas kernels of the gated delta rule's chunk recurrence
(parallel/kda_kernel.py) against the two forms they stand beside.

  * the kernels, interpreted by Pallas on the CPU, against
    ``gated_delta_chunked``'s ``jax.numpy`` form and against the reference's
    recurrence token by token (benchmark/reference/kimi_linear.py
    ``delta_rule``): the output and the gradients of q, k, v, g and beta at
    the three regimes of decay of tests/test_decoder_kda.py, two chunk
    lengths, a length no chunk divides and two sequences a batch;
  * the state carried from chunk to chunk and from one grid step's block of
    chunks to the next, and left as it is by the padding;
  * the choice of form: the ``jax.numpy`` form on the CPU, ``_kda_mix``
    lowering to the text it lowered to before there were kernels, the
    kernels on a TPU for the shapes they take and the ``jax.numpy`` form --
    saying why -- for the others, ``kda.form`` counting each traced call;
  * the kernels compile for a described v5e at the cell's real shapes (no
    chip: the TPU's compiler alone; skipped where it is not installed).
"""

import functools
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common
from benchmark.reference import kimi_linear as ref
from paddlebox_tpu.models import DecoderMoeLM, decoder_lm
from paddlebox_tpu.parallel import kda_kernel as kk
from paddlebox_tpu.telemetry import metrics

B, T, NH, D = 2, 37, 8, 128
# g = -A softplus(x + shift): A = 1 (weak: a token's state lives on for
# tens of tokens), A = 16, and A = 16 with x + 3, where a chunk's decays
# multiply to under 1e-38 and (k_j exp -G_j) overflows float32
REGIMES = {"weak": (1.0, 0.0), "strong": (16.0, 0.0),
           "overflowing": (16.0, 3.0)}
GRADS = ("q", "k", "v", "g", "beta")


def operands(regime: str, t: int = T, b: int = B, key: int = 0):
    """(q, k, v, g, beta, the output's cotangent) as ``_kda_mix`` makes
    them: unit keys, queries scaled by 1 / sqrt(d), g <= 0."""
    ks = jax.random.split(jax.random.PRNGKey(key), 6)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    A, shift = REGIMES[regime]
    return (unit(jax.random.normal(ks[0], (b, t, NH, D))) * D ** -0.5,
            unit(jax.random.normal(ks[1], (b, t, NH, D))),
            jax.random.normal(ks[2], (b, t, NH, D)),
            -A * jax.nn.softplus(
                jax.random.normal(ks[3], (b, t, NH, D)) + shift),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, NH))),
            jax.random.normal(ks[5], (b, t, NH, D)))


def spec(chunk: int, chunks: int = 2, heads: int = 4, **kw) -> kk.Spec:
    """Float32 operands: the distance is the form's, not a rounding's."""
    return kk.Spec(chunk, chunks, heads, **{
        "operands": "float32", "interpret": True, **kw})


@functools.lru_cache(maxsize=None)
def _value_and_grads(form, chunk):
    """One compiled program a form and chunk, whatever the regime."""
    f = {"tokens": token_by_token,
         "chunked": lambda *x: decoder_lm.gated_delta_chunked(*x, chunk),
         }.get(form) or (lambda *x: kk.gated_delta(*x, form))

    @jax.jit
    def run(*x):
        *x, w = x
        with jax.default_matmul_precision("highest"):
            def loss(*x):
                o = f(*x)
                return (o * w).sum(), o
            (_, o), grads = jax.value_and_grad(
                loss, tuple(range(5)), has_aux=True)(*x)
        return (o,) + grads

    return run


def value_and_grads(form, *args, chunk=None):
    """(o, dq, dk, dv, dg, dbeta) under the cotangent args[-1] of ``form``:
    the kernels under a ``Spec``, "chunked" (the ``jax.numpy`` form in
    chunks of ``chunk``) or "tokens" (the reference's recurrence); float32
    products."""
    return _value_and_grads(form, chunk)(*args)


@functools.lru_cache(maxsize=None)
def kernels(sp: kk.Spec):
    """``gated_delta`` under ``sp`` as one compiled program."""
    return jax.jit(lambda *x: kk.gated_delta(*x, sp))


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def token_by_token(q, k, v, g, beta):
    """The reference's recurrence, a sequence at a time."""
    return jnp.stack([ref.delta_rule(common.Ops(), *(a[b] for a in (
        q, k, v, g, beta))) for b in range(q.shape[0])])


@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("regime", list(REGIMES))
def test_kernels_are_the_chunked_form_and_the_token_recurrence(regime, chunk):
    """37 positions (no multiple of either chunk, so the last chunk is
    padded and, at 8, a second block of chunks follows the first), two
    sequences: the output and all five gradients within 1e-4 of the
    recurrence and within 2e-5 of the chunked form."""
    args = operands(regime)
    got = value_and_grads(spec(chunk), *args)
    chunked = value_and_grads("chunked", *args, chunk=chunk)
    tokens = value_and_grads("tokens", *args)
    for name, a, b, c in zip(("o",) + GRADS, got, chunked, tokens):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.isfinite(np.asarray(a)).all(), name
        assert np.linalg.norm(c) > 0, name
        assert rel(a, c) < 1e-4, (name, rel(a, c))
        # ... and of the chunked form, or where that lies further from the
        # recurrence (the overflowing regime's gradient of g, 4e-6 in norm,
        # whose small terms it flushes to zero) the closer of the two
        assert rel(a, b) < 2e-5 or rel(a, c) < rel(b, c), (name, rel(a, b))


def rows(a):
    """[B, T, NH, D] as the kernels read it: [B, T * NH, D]."""
    return a.reshape(a.shape[0], -1, a.shape[-1])


def test_kernels_stay_finite_where_the_factored_form_overflows():
    """The third regime is the one the difference form exists for: a
    chunk's cumulative log-decay passes -88, exp(-G) is infinite, and the
    kernels' every exponent is <= 0; only the pairs below the diagonal are
    formed, so R is j < i and P j <= i in a row's first ``chunk`` lanes."""
    q, k, v, g, beta, _ = operands("overflowing", t=32)
    G = np.cumsum(np.asarray(g)[:, :8], axis=1)
    with np.errstate(over="ignore"):
        assert G.min() < -88 and np.isinf(np.exp(-G.astype(np.float32))).any()
    got = jax.jit(lambda *x: kk.pairs(*x, spec(8)))(
        rows(q), rows(k), rows(v), rows(g), jnp.swapaxes(beta, 1, 2))
    assert all(np.isfinite(np.asarray(a)).all() for a in got)
    G_, R, P, w, u = (np.asarray(a).reshape(B, 4, 8, NH, -1) for a in got)
    np.testing.assert_allclose(G_, np.cumsum(np.asarray(g).reshape(
        B, 4, 8, NH, D), axis=2), rtol=1e-6)
    assert np.abs(R).max() <= 1.0 + 1e-6  # unit keys, decays <= 1
    R, P = (a.transpose(0, 1, 3, 2, 4) for a in (R, P))  # [.., i, lane j]
    lower = np.tril(np.ones((8, 8), bool))
    assert (R[..., 8:] == 0).all() and (P[..., 8:] == 0).all()
    assert (R[..., :8][..., ~np.tril(lower, -1)] == 0).all()  # j < i
    assert (P[..., :8][..., ~lower] == 0).all()
    assert (P[..., :8][..., np.eye(8, dtype=bool)] != 0).all()  # q_i . k_i
    # a chunk's first position sees no other: X_0 = beta_0 [k_0 e^g_0 | v_0]
    b0 = np.asarray(beta).reshape(B, 4, 8, NH)[:, :, 0, :, None]
    np.testing.assert_allclose(
        u[:, :, 0], b0 * np.asarray(v).reshape(B, 4, 8, NH, D)[:, :, 0],
        rtol=1e-6)


@pytest.mark.parametrize("regime", list(REGIMES))
def test_the_state_is_carried_from_chunk_to_chunk(regime):
    """32 positions as 4 chunks of 8 against 1 chunk of 32: the same
    outputs, so what a chunk hands the next -- inside a grid step's block
    (4 chunks a step) and across grid steps (1 and 2 a step) -- is the
    state the recurrence has there."""
    q, k, v, g, beta, _ = operands(regime, t=32, key=1)
    whole = kernels(spec(32, 1))(q, k, v, g, beta)
    assert rel(whole, jax.jit(token_by_token)(q, k, v, g, beta)) < 1e-4
    for chunks in (4, 2, 1):
        got = kernels(spec(8, chunks))(q, k, v, g, beta)
        assert rel(got, whole) < 1e-5, chunks
    if regime == "weak":  # ... and the state matters: cut off, it shows
        halves = jnp.concatenate([kernels(spec(8, 2))(
            *(a[:, lo:lo + 16] for a in (q, k, v, g, beta)))
            for lo in (0, 16)], axis=1)
        assert rel(halves[:, :16], whole[:, :16]) < 1e-5
        assert rel(halves[:, 16:], whole[:, 16:]) > 0.05


def test_padding_leaves_the_state_as_it_is():
    """20 positions in chunks of 8, two a grid step: 12 padded positions,
    one whole chunk of them, and the first 20 outputs and every gradient
    are those of the 24-long call's first 20 with the tail cut off."""
    args = operands("weak", t=20, key=2)
    got = value_and_grads(spec(8), *args)
    want = value_and_grads("chunked", *args, chunk=8)
    for name, a, b in zip(("o",) + GRADS, got, want):
        assert a.shape == b.shape, name
        assert rel(a, b) < 2e-5, (name, rel(a, b))


def test_sixteen_heads_are_one_group_of_registers():
    """Heads in sixteens go two registers a value through the pairs'
    kernels (eight otherwise): the same operator, output and gradients."""
    args = [jnp.concatenate([a, jnp.roll(a, 3, axis=1)], axis=2)
            for a in operands("strong", t=16, b=1, key=7)]
    assert args[0].shape == (1, 16, 16, D)
    got = value_and_grads(spec(8), *args)
    want = value_and_grads("chunked", *args, chunk=8)
    for name, a, b in zip(("o",) + GRADS, got, want):
        assert rel(a, b) < 2e-5, (name, rel(a, b))


def test_heads_interleaved_change_nothing():
    q, k, v, g, beta, _ = operands("strong", t=32, key=3)
    one, two, three = (kernels(spec(8, 2, heads))(q, k, v, g, beta)
                       for heads in (1, 2, 8))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(three))


def test_the_walk_rounds_its_operands_to_bfloat16():
    """The default: the walk's products' operands in bfloat16, sums and the
    state in float32 -- the distance of one bfloat16 pass, not more and not
    none; the pairs' sums are float32 whatever the operands."""
    args = operands("weak", t=32, key=4)
    assert kk.Spec(8, 2, 2).operands == "bfloat16"
    want, got = (value_and_grads(sp, *args)
                 for sp in (spec(8), spec(8, operands="bfloat16")))
    gaps = [rel(a, b) for a, b in zip(got, want)]
    assert all(1e-4 < gap < 3e-2 for gap in gaps), gaps
    q, k, v, g, beta, _ = args
    flat = (rows(q), rows(k), rows(v), rows(g), jnp.swapaxes(beta, 1, 2))
    for a, b in zip(*(jax.jit(lambda *x: kk.pairs(*x, sp))(*flat) for sp in (
            spec(8), spec(8, operands="bfloat16")))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_backward_keeps_no_decay_and_one_state_a_block():
    """The footprint's guard off the chip, by the tracer's own count of
    what the gradient saves: nothing of [chunk, chunk, dk] a chunk, and of
    the walk one state a block of chunks."""
    from jax._src.ad_checkpoint import saved_residuals
    q, k, v, g, beta, _ = operands("weak", t=64, b=1, key=5)
    saved = saved_residuals(
        lambda *x: kk.gated_delta(*x, spec(8, 4)).sum(), q, k, v, g, beta)
    states = [a.shape for a, _ in saved if a.shape[-2:] == (D, D)]
    assert states == [(1, 2, NH, D, D)]  # 64 / (8 * 4) blocks
    rest = [int(np.prod(a.shape)) for a, _ in saved
            if a.shape[-2:] != (D, D)]
    # the widest kept is a row of 128 floats a position and head (q, k, v,
    # g, G, R, P, w, u0); a chunk's decays would be 8 such rows
    assert max(rest) == 64 * NH * D


def _forms() -> dict:
    count = metrics.counter("kda.form")
    return {form: count.value(form=form) for form in ("kernel", "chunks")}


def _counted(before: dict) -> dict:
    return {k: v - before[k] for k, v in _forms().items() if v != before[k]}


def _toy_model() -> DecoderMoeLM:
    """tests/test_decoder_kda.py's description: hidden 64, 4 KDA heads of
    16 (no lane tile: the ``jax.numpy`` form on every backend)."""
    vocab = np.sort(np.random.default_rng(7).choice(
        np.arange(1000, 9000, dtype=np.uint64), 64, replace=False))
    return DecoderMoeLM(
        66, vocab, max_seq_len=37, n_heads=4, n_kv_heads=4, head_dim=8,
        window=0, layer_types=("kda",), mlp_types=("dense",),
        kda={"n_heads": 4, "head_dim": 16, "conv_kernel": 4, "gate_rank": 8},
        dense_width=96, n_experts=32, n_experts_per_tok=4, expert_width=32,
        rms_eps=1e-5, block_q=16, loss_chunk=24)


# sha256 of ``_kda_mix``'s StableHLO text on the CPU, taken at the parent
# commit (PR 44), before gated_delta_chunked had a second form
KDA_MIX_ON_THE_CPU = (
    "cebb15a90b69dbafa13552a9e43da0c1eeaa6d4c9c0762a72b9d8c77a759d768")


def test_cpu_lowers_to_the_chunked_form():
    """On the CPU the operator is the ``jax.numpy`` form, as before the
    kernels: counted once a traced call, not once a run, and ``_kda_mix``
    lowers to the text it lowered to at the parent commit (tests/
    test_decoder_kda.py pins the five descriptions' whole step)."""
    model = _toy_model()
    lp = model.init(jax.random.PRNGKey(0))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 37, 64))
    before = _forms()
    text = jax.jit(model._kda_mix).lower(lp, x).as_text()
    assert _counted(before) == {"chunks": 1}
    assert "tpu_custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == KDA_MIX_ON_THE_CPU
    before = _forms()
    mix = jax.jit(model._kda_mix)
    for _ in range(3):
        mix(lp, x)
    assert _counted(before) == {"chunks": 1}


def test_tpu_takes_the_kernels_and_says_when_not(monkeypatch, caplog):
    """The choice as a TPU makes it (the backend's name patched, the
    kernels interpreted): float operands with heads one lane tile (128)
    wide, in whole sublane tiles, and a chunk of whole sublane tiles are the
    kernels'; a head of 16, a chunk of 12, four heads or an integer operand
    is the ``jax.numpy`` form's, counted and logged with the reason."""
    from jax.experimental.pallas import tpu as pltpu
    q, k, v, g, beta, _ = operands("strong", t=32, key=6)
    assert decoder_lm._kda_form(q, k, v, g, beta, 8) == ("chunks",
                                                         "not a TPU")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decoder_lm._kda_form(q, k, v, g, beta, 8) == (
        "kernel", kk.Spec(8, 4, 4))
    # the cell's shapes: 256 chunks of 32 in blocks of 4 (128 positions)
    assert kk.spec_for(8192, 32, 128, 128, 32) == (kk.Spec(32, 4, 4), "")
    assert kk.spec_for(37, 8, 128, 128, 32)[0] == kk.Spec(32, 2, 4)
    assert kk.spec_for(20, 8, 128, 128, 32)[0] == kk.Spec(24, 1, 4)
    assert kk.spec_for(17 * 8, 16, 128, 128, 8)[0] == kk.Spec(8, 16, 4)
    assert "256, 256 are not one lane tile" in kk.spec_for(
        32, 8, 256, 256, 8)[1]
    assert "24 do not fill blocks of 128" in kk.spec_for(
        8192, 8, 128, 128, 24)[1]
    assert "4 heads are no whole sublane tiles" in kk.spec_for(
        32, 4, 128, 128, 8)[1]
    assert "128, 256 are not one lane tile" in kk.spec_for(
        32, 8, 128, 256, 8)[1]
    before = _forms()
    with pltpu.force_tpu_interpret_mode():
        got = decoder_lm.gated_delta_chunked(q, k, v, g, beta, 8)
    assert _counted(before) == {"kernel": 1}
    monkeypatch.undo()
    want = decoder_lm.gated_delta_chunked(q, k, v, g, beta, 8)
    assert 1e-5 < rel(got, want) < 2e-2  # the walk's bfloat16 operands
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    narrow = [a[..., :16] for a in (q, k, v, g)] + [beta]
    form, why = decoder_lm._kda_form(*narrow, 8)
    assert form == "chunks" and "16, 16 are not one lane tile" in why
    form, why = decoder_lm._kda_form(q, k, v, g, beta, 12)
    assert form == "chunks" and "12 is no whole sublane tiles" in why
    form, why = decoder_lm._kda_form(q, k, v, g, beta.astype(jnp.int32), 8)
    assert form == "chunks" and "int32" in why
    assert decoder_lm._kda_form(
        q, k.astype(jnp.bfloat16), v, g, beta, 8)[0] == "kernel"
    before = _forms()
    with caplog.at_level("DEBUG", logger=decoder_lm.__name__):
        got = decoder_lm.gated_delta_chunked(*narrow, 8)
    assert _counted(before) == {"chunks": 1}
    assert "not one lane tile" in caplog.text
    monkeypatch.undo()
    np.testing.assert_array_equal(
        got, decoder_lm.gated_delta_chunked(*narrow, 8))


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler without a TPU."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    described = {"TPU_LOG_DIR": "disabled", "TPU_SKIP_MDS_QUERY": "1",
                 "TPU_ACCELERATOR_TYPE": "v5litepod-4",
                 "TPU_WORKER_HOSTNAMES": "localhost"}
    kept = {name: os.environ.get(name) for name in described}
    os.environ.update({n: v for n, v in described.items() if kept[n] is None})
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        for name, value in kept.items():
            if value is None:
                os.environ.pop(name, None)
    return SingleDeviceSharding(topo.devices[0])


def test_kernels_compile_for_a_v5e(one_chip):
    """The four kernels at ``kimi_linear_ep32_train_8k``'s shapes and the
    blocks its shapes give: what Mosaic refuses (a slice off the tiling, a
    product it does not take, too much VMEM) it refuses here."""
    t, nh = 8192, 32
    found, _ = kk.spec_for(t, nh, D, D, decoder_lm.KDA_CHUNK)

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    wide = shape(1, t, nh, D)
    compiled = jax.jit(jax.value_and_grad(
        lambda *x: kk.gated_delta(*x, found).sum(), tuple(range(5)))
    ).lower(wide, wide, wide, wide, shape(1, t, nh)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 4
