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
  * the kernels compile for a described v5e at the five cells' real shapes
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


# the five decoder cells' attention: mask, its number, B, T, H, Hkv, D, Dv
CELLS = {
    "mellum2_full": ("causal", None, 4, 4096, 32, 4, 128, 128),
    "mellum2_window": ("window", 1024, 4, 4096, 32, 4, 128, 128),
    "kanana2": ("causal", None, 4, 4096, 32, 32, 192, 128),
    "lfm2": ("causal", None, 4, 4096, 32, 8, 64, 64),
    "kimi_linear": ("causal", None, 1, 8192, 32, 32, 192, 128),
    "sdar": ("block_diffusion", 4, 2, 8192, 32, 4, 128, 128),
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
