"""Numpy-oracle parity for the seqpool-CVM variant family + fused_concat +
rank_attention2 + quant pull descale + conv counter push.

Oracles transcribe the reference CUDA kernel semantics directly
(fused_seqpool_cvm_with_conv_op.cu:63-83, _with_diff_thres_op.cu:100-127,
_with_pcoc_op.cu:120-155, fused_concat_op.cu:34-50, box_wrapper.cu quant
pull) — SURVEY.md §4 tier 1, same pattern as the reference's OpTest files.
"""

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config import SparseTableConfig
from paddlebox_tpu.ops import (
    fused_concat,
    fused_seqpool_cvm,
    fused_seqpool_cvm_with_conv,
    fused_seqpool_cvm_with_diff_thres,
    fused_seqpool_cvm_with_pcoc,
    rank_attention,
    rank_attention2,
)
from paddlebox_tpu.sparse.table import pull_rows


def _mk(rng, B, S, W, max_len=4, cvm_cols=2):
    lens = rng.integers(0, max_len, size=(B, S))
    K_real = int(lens.sum())
    K = B * S * max_len
    rows = rng.normal(size=(K, W)).astype(np.float32)
    rows[:, :cvm_cols] = rng.integers(0, 8, size=(K, cvm_cols))
    segs = np.full(K, B * S, dtype=np.int32)
    segs[:K_real] = np.repeat(np.arange(B * S), lens.reshape(-1))
    rows[K_real:] = 0.0
    return rows, segs


def _pool(rows, segs, B, S, W):
    out = np.zeros((B, S, W), dtype=np.float64)
    for k in range(rows.shape[0]):
        if segs[k] < B * S:
            out[segs[k] // S, segs[k] % S] += rows[k]
    return out


def test_conv_variant_cvm_columns():
    rng = np.random.default_rng(0)
    B, S, W = 3, 2, 7  # [show, clk, conv, 4 embeds]
    rows, segs = _mk(rng, B, S, W, cvm_cols=3)
    got = np.asarray(
        fused_seqpool_cvm_with_conv(
            jnp.asarray(rows), jnp.asarray(segs), B, S, cvm_offset=3
        )
    ).reshape(B, S, W)
    p = _pool(rows, segs, B, S, W)
    exp = p.copy()
    exp[..., 0] = np.log(p[..., 0] + 1)
    exp[..., 1] = np.log(p[..., 1] + 1)  # conv layout: log click, NOT ctr
    exp[..., 2] = np.log(p[..., 2] + 1) - np.log(p[..., 1] + 1)
    np.testing.assert_allclose(got, exp, rtol=1e-4, atol=1e-5)


def test_conv_variant_show_filter_drops_show_col():
    rng = np.random.default_rng(1)
    B, S, W = 2, 2, 6
    rows, segs = _mk(rng, B, S, W, cvm_cols=3)
    got = np.asarray(
        fused_seqpool_cvm_with_conv(
            jnp.asarray(rows), jnp.asarray(segs), B, S, cvm_offset=3,
            show_filter=True,
        )
    )
    assert got.shape == (B, S * (W - 1))
    p = _pool(rows, segs, B, S, W)
    exp = np.concatenate(
        [
            np.log(p[..., 1:2] + 1),
            np.log(p[..., 2:3] + 1) - np.log(p[..., 1:2] + 1),
            p[..., 3:],
        ],
        axis=-1,
    ).reshape(B, -1)
    np.testing.assert_allclose(got, exp, rtol=1e-4, atol=1e-5)


def test_diff_thres_per_slot_thresholds():
    """Slot 0 threshold filters its occurrence; slot 1's lower threshold
    keeps an identical occurrence (the xbox_diff_thres_filter path)."""
    B, S, W = 1, 2, 4
    rows = np.zeros((4, W), dtype=np.float32)
    rows[0] = [5, 1, 3.0, 3.0]  # score (5-1)*0.2+1 = 1.8
    rows[1] = [5, 1, 7.0, 7.0]  # same score, slot 1
    segs = np.array([0, 1, B * S, B * S], dtype=np.int32)
    got = np.asarray(
        fused_seqpool_cvm_with_diff_thres(
            jnp.asarray(rows), jnp.asarray(segs), B, S,
            threshold_vec=[2.0, 1.0],  # slot0 filters (1.8 < 2), slot1 keeps
            use_cvm=False, show_coeff=0.2, clk_coeff=1.0,
        )
    ).reshape(S, W - 2)
    np.testing.assert_allclose(got[0], [0.0, 0.0])
    np.testing.assert_allclose(got[1], [7.0, 7.0])


def test_quant_ratio_rounds_embeds_before_pooling():
    B, S, W = 1, 1, 4
    rows = np.array(
        [[2, 1, 0.1234, -0.077], [1, 0, 0.5061, 0.25]], dtype=np.float32
    )
    segs = np.array([0, 0], dtype=np.int32)
    ratio = 128
    got = np.asarray(
        fused_seqpool_cvm(
            jnp.asarray(rows), jnp.asarray(segs), B, S, use_cvm=False,
            quant_ratio=ratio,
        )
    )[0]
    # reference rounding: int(v * ratio + 0.5) / ratio (C trunc toward zero)
    q = np.trunc(rows[:, 2:] * ratio + 0.5) / ratio
    np.testing.assert_allclose(got, q.sum(axis=0), rtol=1e-6)


def test_pcoc_variant_cvm_columns():
    rng = np.random.default_rng(2)
    p_num = 3
    mco = 4 + p_num  # [show, clk, d0, d1, q0..q2]
    B, S, W = 2, 2, mco + 4
    rows, segs = _mk(rng, B, S, W, cvm_cols=mco)
    got = np.asarray(
        fused_seqpool_cvm_with_pcoc(
            jnp.asarray(rows), jnp.asarray(segs), B, S, pclk_num=p_num
        )
    ).reshape(B, S, -1)
    p = _pool(rows, segs, B, S, W)
    show, clk = p[..., 0], p[..., 1]
    d0, d1 = p[..., 2], p[..., 3]
    q = p[..., 4 : 4 + p_num]
    exp = np.concatenate(
        [
            np.log(show + 1)[..., None],
            (np.log(clk + 1) - np.log(show + 1))[..., None],
            np.log(q + 1) - np.log(d0 + 1)[..., None],
            np.log(q + 1) - np.log(d1 + 1)[..., None],
            p[..., mco:],
        ],
        axis=-1,
    )
    assert got.shape == exp.shape  # 2 + 2*pclk_num + embeds
    np.testing.assert_allclose(got, exp, rtol=1e-4, atol=1e-5)


def test_fused_concat_column_spec():
    rng = np.random.default_rng(3)
    B = 4
    x1 = [jnp.asarray(rng.normal(size=(B, 5)).astype(np.float32)) for _ in range(2)]
    x2 = [jnp.asarray(rng.normal(size=(B, 3)).astype(np.float32)) for _ in range(2)]
    spec = [(0, 0), (0, 4), (1, 2), (1, 0)]
    outs = fused_concat(x1, x2, spec)
    assert len(outs) == 2
    for s in range(2):
        exp = np.stack(
            [
                np.asarray(x1[s])[:, 0],
                np.asarray(x1[s])[:, 4],
                np.asarray(x2[s])[:, 2],
                np.asarray(x2[s])[:, 0],
            ],
            axis=1,
        )
        np.testing.assert_array_equal(np.asarray(outs[s]), exp)


def test_fused_concat_differentiable():
    x1 = [jnp.ones((2, 3))]
    x2 = [jnp.ones((2, 2))]

    def f(a):
        return fused_concat([a], x2, [(0, 1), (1, 0)])[0].sum()

    g = jax.grad(f)(x1[0])
    np.testing.assert_array_equal(np.asarray(g), [[0, 1, 0], [0, 1, 0]])


def test_rank_attention2_is_rank_attention():
    """The two reference ops compute the same contraction (v1 via scratch +
    batched GEMM, v2 directly); here one einsum serves both names."""
    assert rank_attention2 is rank_attention


def test_quant_pull_descale():
    """Descale hits embedx only: [show, click, embed_w, embedx...] keeps
    embed_w unscaled (the reference stores it unquantized)."""
    values = jnp.asarray(
        np.array(
            [[3, 1, 10.0, 20.0, 12.0], [5, 2, -4.0, 8.0, 0.5]],
            dtype=np.float32,
        )
    )
    idx = jnp.asarray([1, 0, 1], dtype=jnp.int32)
    rows = np.asarray(pull_rows(values, idx, pull_embedx_scale=0.25))
    exp = np.asarray(values)[np.asarray(idx)]
    exp[:, 3:] *= 0.25  # counters + embed_w untouched, embedx descaled
    np.testing.assert_allclose(rows, exp, rtol=1e-6)


def test_conv_counter_push_end_to_end(tmp_path):
    """cvm_offset=3 table + counter_label_tasks: the third (conv) counter
    accumulates the conversion task label of each key's instance
    (parser -> push counter update -> CVM)."""
    from paddlebox_tpu.config import (
        DataFeedConfig,
        SlotConfig,
        TrainerConfig,
    )
    from paddlebox_tpu.data.data_generator import format_instance
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    rng = np.random.default_rng(4)
    slots = [
        SlotConfig("click", "float", is_dense=True, shape=(1,)),
        SlotConfig("conv", "float", is_dense=True, shape=(1,)),
        SlotConfig("d0", "float", is_dense=True, shape=(2,)),
        SlotConfig("s0"),
        SlotConfig("s1"),
    ]
    conf = DataFeedConfig(
        slots=slots, batch_size=8, max_feasigns_per_ins=4,
        task_label_slots=("conv",),
    )
    path = str(tmp_path / "part-0")
    n_conv = 0
    with open(path, "w") as fh:
        for i in range(64):
            click = int(rng.integers(0, 2))
            convl = int(click and rng.integers(0, 2))
            n_conv += convl
            ins = [
                ("click", [float(click)]),
                ("conv", [float(convl)]),
                ("d0", rng.normal(size=2).round(3).tolist()),
                ("s0", rng.integers(0, 30, size=2).tolist()),
                ("s1", rng.integers(30, 50, size=1).tolist()),
            ]
            fh.write(format_instance(conf, ins) + "\n")
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist([path])
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=4, cvm_offset=3)
    # task_labels col 0 = primary label (click); col 1 = the "conv" slot
    trconf = TrainerConfig(
        auc_buckets=1 << 10, counter_label_tasks=(1,)
    )
    model = CtrDnn(
        2, tconf.row_width, dense_dim=2, hidden=(8,), layout="conv",
        cvm_offset=3,
    )
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, trconf, seed=0)
    table.begin_pass(ds.unique_keys())
    m = trainer.train_from_dataset(ds, table)
    table.end_pass()
    ds.close()
    assert np.isfinite(m["loss"])
    state = table.state_dict()
    # each instance contributes 3 key occurrences (2 in s0, 1 in s1):
    # conv counter total = 3 * n_conv, show total = 3 * 64
    np.testing.assert_allclose(state["values"][:, 0].sum(), 3 * 64, rtol=1e-5)
    np.testing.assert_allclose(
        state["values"][:, 2].sum(), 3 * n_conv, rtol=1e-5
    )
