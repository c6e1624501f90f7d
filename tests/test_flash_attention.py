"""The flash-form kernel of ``full_attention``'s blockwise form
(parallel/flash_attention.py) against the strips it stands in for on a TPU.

  * the kernel, interpreted by Pallas on the CPU, against the strips: the
    output and the gradients of q, k and v under each of the three described
    masks at each of the accepted configurations' head shapes (grouped
    queries, a value head narrower than the key head, heads of 64), with a
    window and a diffusion block that cross tile edges;
  * the table the grid runs over against the dense mask: no tile with a
    visible pair is skipped, no tile the edge crosses goes unmasked;
  * the choice of form: strips on the CPU (the lowered text holds no custom
    call), the kernel on a TPU for shapes it takes, the strips -- saying
    why -- for those it does not, ``attn.form`` counting each traced call;
  * what a rematerialised layer keeps: under the policy a TPU names
    (``sequence.kernel_residuals``) the kernel's output and log-sum-exp
    beside the layer's input, so the gradient holds the forward kernel once
    and not twice, to the same bits; around the strips the policy keeps
    nothing; ``attn.kept`` counts each traced forward rule;
  * the kernels compile for a described v5e at the six cells' real shapes,
    and so does each cell's whole ``train.step``: one forward kernel a
    layer, under the memory the chip has
    (no chip: the TPU's compiler alone; skipped where it is not installed).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.parallel import flash_attention as fa
from paddlebox_tpu.parallel import sequence as sq
from paddlebox_tpu.telemetry import metrics

MASKS = {
    "causal": {"causal": True},
    # 100 keys: neither a tile's 128 nor a strip's 32
    "window": {"causal": True, "window": 100},
    # two streams of 192 in blocks of 48: the streams' border (192) and the
    # blocks' (96..144) both lie inside a tile of 128
    "block_diffusion": {"block_diffusion": 48},
}
HEADS = {  # G, D, Dv
    "g8_d128": (8, 128, 128),       # mellum2, sdar
    "g1_d192_dv128": (1, 192, 128),  # kanana2, kimi_linear
    "g4_d64": (4, 64, 64),          # lfm2
}
T, HKV, STRIP = 384, 2, 48


def _spec(mask: str, heads: int = 1, **kw) -> fa.Spec:
    args = MASKS[mask]
    return fa.Spec(mask, args.get("window", args.get("block_diffusion")),
                   128, 128, heads, **kw)


def _qkvw(g: int, d: int, dv: int, t: int = T, hkv: int = HKV):
    ks = jax.random.split(jax.random.PRNGKey(g + d), 4)
    return (jax.random.normal(ks[0], (1, t, hkv * g, d), jnp.float32),
            jax.random.normal(ks[1], (1, t, hkv, d), jnp.float32),
            jax.random.normal(ks[2], (1, t, hkv, dv), jnp.float32),
            jax.random.normal(ks[3], (1, t, hkv * g, dv), jnp.float32))


def _strips(mask: str, block_q: int = STRIP):
    return lambda q, k, v: sq.full_attention(
        q, k, v, block_q=block_q, **MASKS[mask])


def _value_and_grads(f, q, k, v, w):
    """(out, dq, dk, dv) of f under the cotangent w, in one trace."""
    def loss(q, k, v):
        out = f(q, k, v)
        return (out * w).sum(), out
    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
        q, k, v)
    return (out,) + grads


def _gap(a, b) -> float:
    return float(jnp.abs(a - b).max() / jnp.abs(a).max())


@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("mask", list(MASKS))
def test_kernel_matches_strips(mask, heads):
    """Output and the three gradients, the operands left in float32 so the
    distance is the algorithm's and not a rounding's."""
    # two tiles a side, the block mask's two streams three; two key-value
    # heads where each has one query head, else one
    g, d, dv = HEADS[heads]
    hkv = 1 if g == 8 else HKV
    q, k, v, w = _qkvw(g, d, dv, t=T if mask == "block_diffusion"
                       else 256, hkv=hkv)
    with jax.default_matmul_precision("highest"):  # few strips: they are
        want = _value_and_grads(  # the oracle here, not what is tested
            _strips(mask, 2 * STRIP), q, k, v, w)
    # a block's columns as on the chip: one head at D 128, a pair at D 192
    # / Dv 128 and at D 64
    spec = _spec(mask, fa.heads_a_step(hkv, d, dv), operands="float32",
                 interpret=True)
    got = _value_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, spec), q, k, v, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), want, got):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _gap(a, b) < 2e-5, (name, _gap(a, b))


def test_kernel_rounds_operands_to_bfloat16():
    """The default: every product's operands in bfloat16, sums in float32
    -- the distance of one bfloat16 pass from the same kernel on float32
    operands, not more and not none."""
    q, k, v, w = _qkvw(2, 128, 128, t=256, hkv=1)
    spec = _spec("causal", interpret=True)
    assert spec.operands == "bfloat16"
    want, got = (_value_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, sp), q, k, v, w)
        for sp in (spec._replace(operands="float32"), spec))
    gaps = [_gap(a, b) for a, b in zip(want, got)]
    assert all(1e-4 < g < 2e-2 for g in gaps), gaps


@pytest.mark.parametrize("heads", list(HEADS))
def test_backward_keeps_no_second_copy(heads):
    """The footprint's guard off the chip: what the forward hands the
    backward is q, k and v themselves, the output it returns and a row's
    log-sum-exp -- five arrays, none of them a transposed or recast copy --
    and the three gradients come back where and as their operands are."""
    g, d, dv = HEADS[heads]
    q, k, v, w = _qkvw(g, d, dv, t=256)
    spec = _spec("causal", fa.heads_a_step(HKV, d, dv), interpret=True)
    out, kept = fa._flash_fwd(q, k, v, spec)
    assert len(kept) == 5
    assert kept[0] is q and kept[1] is k and kept[2] is v
    assert kept[3] is out
    assert (out.shape, out.dtype) == ((1, 256, HKV * g, dv), q.dtype)
    lse = kept[4]
    assert lse.dtype == jnp.float32 and lse.size == HKV * g * 256
    # the whole of it, by the tracer's own count of what the gradient saves
    from jax._src.ad_checkpoint import saved_residuals
    saved = saved_residuals(
        lambda q, k, v: fa.flash_attention(q, k, v, spec).sum(), q, k, v)
    big = sorted(tuple(a.shape) for a, _ in saved if a.size >= lse.size)
    assert big == sorted([q.shape, k.shape, v.shape, out.shape, lse.shape])
    grads = fa._flash_bwd(spec, kept, w)
    for x, dx in zip((q, k, v), grads):
        assert (dx.shape, dx.dtype) == (x.shape, x.dtype)


@pytest.mark.parametrize("hkv,d,dv,want", [
    (4, 128, 128, 1),    # mellum2, sdar: a head is a lane tile
    (32, 192, 128, 2),   # kanana2, kimi_linear: 384 and 256 columns
    (8, 64, 64, 2),      # lfm2: 128 columns
    (2, 16, 16, 2),      # a toy head: no fewer make whole tiles -> all
    (3, 192, 128, 3),    # ... nor does a divisor of an odd count
])
def test_heads_a_grid_step(hkv, d, dv, want):
    assert fa.heads_a_step(hkv, d, dv) == want
    blocks = fa.blocks_for(4096, 4096, 1, d, dv, hkv)
    assert blocks[2] == want
    assert hkv % want == 0


def _dense_mask(mask: str, t: int) -> np.ndarray:
    """[t, t] bool from full_attention's docstring, written densely."""
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    args = MASKS[mask]
    if mask != "block_diffusion":
        seen = j <= i
        return seen & (i - j < args["window"]) if "window" in args else seen
    half, n = t // 2, args["block_diffusion"]
    qb, kb = (i % half) // n, (j % half) // n
    q_clean, k_clean = i >= half, j >= half
    return np.where(q_clean, k_clean & (kb <= qb),
                    np.where(k_clean, kb < qb, kb == qb))


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("mask", list(MASKS))
def test_visit_table_covers_the_mask(mask, blocks):
    t = 768
    bq, bkv = blocks
    spec = fa.Spec(mask, _spec(mask).n, bq, bkv)
    dense = _dense_mask(mask, t)
    tiles = dense.reshape(t // bq, bq, t // bkv, bkv)
    want = np.where(tiles.any(axis=(1, 3)),
                    np.where(tiles.all(axis=(1, 3)), 2, 1), 0)
    kinds = fa._tile_kinds(spec, t, t)
    np.testing.assert_array_equal(kinds, want)
    # the in-kernel mask of a tile, from its positions
    seen = fa._visible(spec, t, jnp.arange(t, dtype=jnp.int32)[:, None],
                       jnp.arange(t, dtype=jnp.int32)[None, :])
    np.testing.assert_array_equal(np.asarray(seen), dense)
    # the grid's table: each row's visible columns in order, then repeats
    index, kind, steps = fa._visits(kinds)
    index = np.asarray(index).reshape(-1, steps)
    kind = np.asarray(kind).reshape(-1, steps)
    assert steps == (want > 0).sum(axis=1).max()
    for r in range(t // bq):
        cols = np.nonzero(want[r])[0]
        np.testing.assert_array_equal(index[r, :len(cols)], cols)
        np.testing.assert_array_equal(kind[r, :len(cols)], want[r, cols])
        assert (index[r, len(cols):] == cols[-1]).all()
        assert (kind[r, len(cols):] == 0).all()


def _forms() -> dict:
    count = metrics.counter("attn.form")
    return {(form, mask): count.value(form=form, mask=mask)
            for form in ("kernel", "strips")
            for mask in ("causal", "window", "block_diffusion", "none")}


def _counted(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _forms().items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("mask", list(MASKS))
def test_cpu_lowers_to_the_strips(mask):
    """On the CPU the blockwise form is the strips, as before the kernel
    (tests/test_decoder_kda.py pins the five descriptions' whole step)."""
    q, k, v, _ = _qkvw(2, 16, 16, t=192)
    before = _forms()
    text = jax.jit(_strips(mask)).lower(q, k, v).as_text()
    assert _counted(before) == {("strips", mask): 1}
    assert "custom_call" not in text
    args = MASKS[mask]
    direct = (
        sq._block_diffusion_attention(q, k, v, args["block_diffusion"], STRIP)
        if mask == "block_diffusion" else
        sq._blockwise_attention(q, k, v, True, args.get("window"), STRIP))
    np.testing.assert_array_equal(_strips(mask)(q, k, v), direct)


def test_tpu_takes_the_kernel_and_says_when_not(monkeypatch, caplog):
    """The choice as a TPU makes it (the backend's name patched, the kernels
    interpreted): a described mask over lengths a block divides is the
    kernel; a length no block divides, or no mask, is the strips, counted
    and logged with the reason."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v, _ = _qkvw(2, 64, 64, t=256)
    assert sq._attention_form(q, k, v, "causal") == ("kernel", (256, 256, 2))
    before = _forms()
    with pltpu.force_tpu_interpret_mode():
        got = sq.full_attention(q, k, v, causal=True, window=100, block_q=64)
    assert _counted(before) == {("kernel", "window"): 1}
    want = sq._blockwise_attention(q, k, v, True, 100, 64)
    assert _gap(want, got) < 2e-2

    odd = [x[:, :200] for x in (q, k, v)]  # 200 = 8 * 25: no block of 128
    form, why = sq._attention_form(*odd, "causal")
    assert form == "strips" and "200" in why
    before = _forms()
    with caplog.at_level("DEBUG", logger=sq.__name__):
        got = sq.full_attention(*odd, causal=True, block_q=64)
        none = sq.full_attention(q, k, v, block_q=64)
    assert _counted(before) == {("strips", "causal"): 1,
                                ("strips", "none"): 1}
    assert "no block divides 200" in caplog.text
    assert "the kernel takes the described three" in caplog.text
    np.testing.assert_array_equal(
        got, sq._blockwise_attention(*odd, True, None, 64))
    np.testing.assert_array_equal(
        none, sq._blockwise_attention(q, k, v, False, None, 64))
    mixed = sq._attention_form(q, k.astype(jnp.bfloat16), v, "causal")
    assert mixed[0] == "strips" and "bfloat16" in mixed[1]
    assert sq._attention_form(q, k, v, "block_diffusion")[0] == "kernel"


def test_form_counts_once_a_traced_call():
    """The choice is static: a jitted caller counts when it is traced, not
    when it runs."""
    q, k, v, _ = _qkvw(2, 64, 64, t=128)
    f = jax.jit(lambda q, k, v: sq.full_attention(
        q, k, v, block_diffusion=4, block_q=32))
    before = _forms()
    for _ in range(3):
        f(q, k, v)
    assert _counted(before) == {("strips", "block_diffusion"): 1}


# ------------------------------------------ what a rematerialised layer keeps
LAYER_H = 64  # the toy layer's width; its heads are the kernel's own sizes


def _layer_inputs(g=2, d=128, t=256):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    w = {name: 0.1 * jax.random.normal(k, shape) for name, k, shape in (
        ("q", ks[0], (LAYER_H, g * d)), ("k", ks[1], (LAYER_H, d)),
        ("v", ks[2], (LAYER_H, d)), ("o", ks[3], (g * d, LAYER_H)))}
    return w, jax.random.normal(ks[4], (1, t, LAYER_H))


def _layer(attend):
    """A layer as the decoder's: q, k and v projected from the input, one
    key-value head, the attention, the output projection, the residual."""
    def layer(w, x):
        b, t, _ = x.shape
        q, k, v = ((x @ w[n]).reshape(b, t, -1, w["k"].shape[1])
                   for n in "qkv")
        return x + attend(q, k, v).reshape(b, t, -1) @ w["o"]
    return layer


def _kernel_layer():
    spec = _spec("causal", interpret=True)
    return _layer(lambda q, k, v: fa.flash_attention(q, k, v, spec))


def _tpu_policy(monkeypatch):
    """The policy as a TPU names it; the CPU names none."""
    assert sq.kernel_residuals() is None
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        policy = sq.kernel_residuals()
    assert policy is not None
    return policy


def _loss(layer, policy, rounds=None):
    """The sum of the layer's output under ``jax.checkpoint`` (a loss that
    saves nothing of its own); with ``rounds`` the layer that many times
    over the same weights as the body of a ``lax.scan`` (the looped
    decoder's ``_rounds``)."""
    def loss(w, x):
        f = jax.checkpoint(layer, policy=policy)
        if rounds is not None:
            x, _ = jax.lax.scan(lambda x, _: (f(w, x), None), x, None,
                                length=rounds)
        else:
            x = f(w, x)
        return x.sum()
    return loss


def _kernel_calls(f, *args) -> int:
    """``pallas_call``s of ``f``'s jaxpr, those of its inner jaxprs too."""
    from jax._src.core import jaxprs_in_params

    def calls(jaxpr):
        return sum((eqn.primitive.name == "pallas_call") + sum(
            calls(sub) for sub in jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)
    return calls(jax.make_jaxpr(f)(*args).jaxpr)


def _residuals(loss, w, x) -> list:
    """(shape, how it is described) of what the gradient saves beside
    its arguments."""
    from jax._src.ad_checkpoint import saved_residuals
    return [(tuple(a.shape), why) for a, why in saved_residuals(loss, w, x)
            if "argument" not in why and "constant" not in why]


@pytest.mark.parametrize("rounds", [None, 3])
def test_a_layers_checkpoint_keeps_the_kernels_output(monkeypatch, rounds):
    """Under the policy the gradient's jaxpr holds 3 ``pallas_call``s an
    attending layer -- forward, dq, dk/dv -- where it holds 4 without it
    (the forward again when the layer is rematerialised); what is saved is
    the layer's input (the argument itself, or the rounds' inputs stacked)
    and, only under the policy, the output -- flat, [B, T, H * Dv], the
    kernel's own array and no relayout of it -- and the log-sum-exp; as the
    body of a ``lax.scan`` both stacked [R, ...], one a round."""
    w, x = _layer_inputs()
    policy = _tpu_policy(monkeypatch)
    stacked = () if rounds is None else (rounds,)
    carried = [] if rounds is None else [stacked + x.shape]
    for keep, calls, kept in (
            (None, 4, []),
            (policy, 3, [stacked + (1, 256, 2 * 128),  # as the kernel wrote
                         stacked + (1, 1, 2, 256)])):
        loss = _loss(_kernel_layer(), keep, rounds)
        assert _kernel_calls(jax.grad(loss), w, x) == calls
        saved = _residuals(loss, w, x)
        assert sorted(s for s, _ in saved) == sorted(kept + carried)
        if keep is not None and rounds is None:
            assert any(fa.ATTN_LSE in why for _, why in saved)


def test_the_policy_changes_no_bit_of_the_gradient(monkeypatch):
    """The same kernel output is used, once: gradients by the weights and
    by the input with and without the policy are bit-equal."""
    w, x = _layer_inputs()
    policy = _tpu_policy(monkeypatch)
    want, got = (jax.jit(jax.grad(_loss(_kernel_layer(), keep), (0, 1)))(
        w, x) for keep in (None, policy))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.abs(np.asarray(a)).max() > 0


def test_the_policy_keeps_nothing_of_the_strips(monkeypatch):
    """Only the kernel's forward rule names anything: a layer whose
    attention fell back to the strips saves under the policy what it saves
    without it, the layer's input alone."""
    w, x = _layer_inputs(d=16, t=192)
    policy = _tpu_policy(monkeypatch)
    for keep in (None, policy):
        loss = _loss(_layer(_strips("causal")), keep)
        assert _kernel_calls(jax.grad(loss), w, x) == 0
        assert _residuals(loss, w, x) == []


def test_kept_counts_once_a_traced_forward_rule():
    """``attn.kept`` counts where the names are applied, in the kernel's
    forward rule: once a traced call that is differentiated, whatever its
    caller's policy, by the mask; a call that is only evaluated names
    nothing and counts nothing."""
    kept = metrics.counter("attn.kept")
    w, x = _layer_inputs()
    spec = _spec("window", interpret=True)
    layer = _layer(lambda q, k, v: fa.flash_attention(q, k, v, spec))
    before = kept.value(mask="window"), kept.value(mask="causal")
    jax.make_jaxpr(layer)(w, x)
    assert kept.value(mask="window") == before[0]
    f = jax.jit(jax.grad(_loss(layer, None)))
    for _ in range(2):
        f(w, x)
    assert kept.value(mask="window") == before[0] + 1
    assert kept.value(mask="causal") == before[1]


# the six decoder cells' attention: mask, its number, B, T, H, Hkv, D, Dv
CELLS = {
    "mellum2_full": ("causal", None, 4, 4096, 32, 4, 128, 128),
    "mellum2_window": ("window", 1024, 4, 4096, 32, 4, 128, 128),
    "kanana2": ("causal", None, 4, 4096, 32, 32, 192, 128),
    "lfm2": ("causal", None, 4, 4096, 32, 8, 64, 64),
    "kimi_linear": ("causal", None, 1, 8192, 32, 32, 192, 128),
    "sdar": ("block_diffusion", 4, 2, 8192, 32, 4, 128, 128),
    "ouro": ("causal", None, 1, 4096, 16, 16, 128, 128),
}


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


def test_step_schedule_is_named_on_a_tpu_only(monkeypatch, one_chip):
    """``Trainer``'s step names the compiler's memory scheduler on a TPU
    and nothing anywhere else; the TPU's compiler takes the option (a
    described v5e: a name it did not know would fail the compile, and with
    it every cell's set-up)."""
    from paddlebox_tpu.train import trainer
    assert trainer._step_schedule() == {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    named = trainer._step_schedule()
    assert named == {"compiler_options": {"xla_memory_scheduler": "list"}}
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a: jnp.tanh(a @ a).sum(), **named).lower(
        x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


@pytest.mark.parametrize("cell", list(CELLS))
def test_kernels_compile_for_a_v5e(one_chip, cell):
    """Forward and both backward kernels at the cell's real shapes and the
    blocks its shapes give: what Mosaic refuses (a slice off the tiling, a
    head width it does not take, too much VMEM) it refuses here."""
    kind, n, b, t, h, hkv, d, dv = CELLS[cell]
    blocks = fa.blocks_for(t, t, h // hkv, d, dv, hkv)
    assert blocks is not None
    spec = fa.Spec(kind, n, *blocks)

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(
        lambda q, k, v: fa.flash_attention(q, k, v, spec).sum(), (0, 1, 2))
    ).lower(shape(b, t, h, d), shape(b, t, hkv, d),
            shape(b, t, hkv, dv)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


# each decoder cell's whole step: the attending layers of its stack (a
# looped stack's rounds are one ``lax.scan`` body: its layers once), the
# other kernel calls of the step (kimi_linear's KDA kernels: pairs and scan,
# forward, rematerialised and backward, in four layers), and the most the
# compiler may count for it in GB (PERF.md section 6, PR 48: this change's
# counts and a few percent of room; five under the 14.5 at which
# kimi_linear's boundary was read sound, PR 44, and ouro, the fullest step
# there is, at 14.85 of the chip's 16.9)
STEPS = {
    "mellum2_ep8_train_4k": (4, 0, 10.5),
    "kanana2_ep16_train_4k": (5, 0, 11.2),
    "sdar_ep16_denoise_4k": (6, 0, 11.6),
    "lfm2_ep8_train_4k": (1, 0, 11.9),
    "kimi_linear_ep32_train_8k": (1, 24, 13.8),
    "ouro_loop4_train_4k": (8, 0, 15.2),
}


def _cell_step(workload: str, chip, monkeypatch, work):
    """The cell's ``train.step`` as ``Trainer`` jits it on a TPU, compiled
    for the described chip from shapes alone: the cell's own model, table
    and feed at full size (one batch of random tokens through the dataset
    and the table's plan), the dense parameters and Adam's state as
    ``jax.eval_shape`` gives them."""
    import importlib

    import optax
    from benchmark import run as harness
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import DatasetFactory
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer, _host_batch_dict

    cfg = harness.Cell.resolve(workload).cfg
    tconf = SparseTableConfig(embedding_dim=cfg["embedding_dim"],
                              hbm_cache_rows=cfg["hbm_cache_rows"])
    model = importlib.import_module(
        "benchmark.models." + cfg["model"]).build(cfg, tconf)
    init, adam = model.init, optax.adam

    def shapes_of_adam(lr):
        tx = adam(lr)
        return optax.GradientTransformation(
            lambda params: jax.eval_shape(tx.init, params), tx.update)

    with monkeypatch.context() as m:  # no parameter is ever made
        m.setattr(model, "init", lambda key: jax.eval_shape(init, key))
        m.setattr(optax, "adam", shapes_of_adam)
        trainer = Trainer(model, tconf, TrainerConfig(), seed=0)
    b, t = cfg["batch_size"], cfg["feed"]["max_seq_len"]
    vocab = np.asarray(model.vocab_keys)
    path = work / "part-0"
    with open(path, "w") as f:
        for seq in np.random.default_rng(3).integers(0, len(vocab), (b, t)):
            keys = " ".join(str(int(vocab[i])) for i in seq)
            f.write(f"1 1 {t} {keys} 1 0.25\n")
    ds = DatasetFactory().create_dataset(
        "BoxPSDataset", harness.feed_config(cfg))
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    table = SparseTable(tconf, seed=0)
    table.begin_pass(ds.unique_keys())
    batch = next(iter(ds.batches()))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=chip),
        (trainer.params, trainer.opt_state, table.values, table.g2sum,
         trainer._init_mstate(), _host_batch_dict(
             batch, table.plan_batch(batch), batch.n_sparse_slots,
             vocab_keys=model.vocab_keys)))
    table.abort_pass()
    ds.close()
    trainer.close()
    with monkeypatch.context() as m:  # the forms and options a TPU takes
        m.setattr(jax, "default_backend", lambda: "tpu")
        return trainer._build_step().lower(*args).compile()


@pytest.mark.parametrize("workload", list(STEPS))
def test_a_cells_step_runs_the_forward_kernel_once_a_layer(
        one_chip, monkeypatch, tmp_path, workload):
    """The whole ``train.step`` of each decoder cell, compiled for a
    described v5e: an attending layer is three kernel calls -- forward, dq,
    dk/dv -- and none of them in the rematerialised half of the backward
    pass (the layer's checkpoint kept the forward's output and log-sum-exp);
    and the step stays under the memory the cell has had on the chip, so a
    change that fills a cell fails here and not at the pass boundary there
    (PERF.md section 6, PR 43: 17.3 GB doubled ``pass_gap_ms``)."""
    import re
    layers, others, most_gb = STEPS[workload]
    compiled = _cell_step(workload, one_chip, monkeypatch, tmp_path)
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    attn = [name for name in calls if "/attn" in name]
    assert len(calls) - len(attn) == others
    forward = [name for name in attn if "transpose(" not in name]
    again = [name for name in attn if "rematted_computation" in name]
    assert (len(forward), len(again), len(attn)) == (layers, 0, 3 * layers)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes
             + m.generated_code_size_in_bytes)
    assert total <= most_gb * 1e9, total
