"""The ``sdar_30b_ep16`` configuration at toy size through ``run_cell`` on
the CPU: the program's decoder under the block-diffusion objective (a
noised and a clean stream under one block mask, a learned [MASK] input, the
noise level from the instance's dense feature, a masked-token loss weighted
by 1/p; grouped-query attention with head norms, softmax-routed experts) on
the normal pass loop against ``reference/sdar.py``.  New files only: the
toy cell is the real configuration's file with its sizes cut (hidden 64, 4
query heads over 2 key-value heads of 16, 16 experts of width 32 with 4 a
token of which 4 are held, 2 layers, sequences of 32 in blocks of 4, a
vocabulary of 64)."""

import numpy as np
import pytest

from benchmark import run
from benchmark.reference import common
from benchmark.run import HERE, ROOT, Cell, load_json

TOY_MIX = {
    "key_distribution": "zipf", "zipf_exponent": 1.0, "slot_vocab": 64,
    "keys_per_slot": [32, 32], "instances_per_pass": 8,
    "distinct_passes": 2, "signal_scale": 4.0, "dense_range": 0.5,
}


def toy_cell() -> Cell:
    cfg = load_json(HERE, "configs", "sdar_30b_ep16.json")
    cfg.update(
        hidden_size=64, embedding_dim=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
        num_experts=16, num_experts_per_tok=4, num_experts_held=4,
        num_hidden_layers=2, vocab_size=64, batch_size=2,
        keys_per_instance_capacity=32, hbm_cache_rows=65,
        feed={"sequence_slot": "slot0", "max_seq_len": 32})
    # on the CPU both sides are float32: the sound program reads ~1e-5 and
    # the float8 control 0.1 and more, so the toy limits sit between
    cfg["limits"] = {k: (0.0 if k == "counter_gap" else 0.02)
                     for k in cfg["limits"]}
    manifest = load_json(ROOT, "BENCHMARK.json")
    return Cell(name="toy", chips=1, cfg=cfg, mix=dict(TOY_MIX),
                end_to_end=manifest["end_to_end"],
                per_layer=manifest["per_layer"])


def test_the_toy_denoising_cell_is_correct():
    r = run.run_cell(toy_cell(), 2 ** 31 + 42, 3.0, False,
                     require_chip=False)
    assert r["correct"] is True and r["failed"] == 0
    assert len(r["checks"]) == 7 and all(c["ok"] for c in r["checks"])
    assert r["counts"]["window_compile_requests"] == 0
    assert r["counts"]["passes"] >= 2 and r["metrics"] == {}


def test_control_the_toy_denoising_cell_in_float8_is_not_correct(monkeypatch):
    """The reference computed as float8 training is done, in the
    program's place on the float32 side of the comparison, fails
    ``row_step_excess`` (and is not a zero gradient)."""
    real = common.run_steps

    def control(*a, precision=""):
        return real(*a, precision=precision or "float8")

    monkeypatch.setattr(common, "run_steps", control)
    r = run.run_cell(toy_cell(), 2 ** 31 + 42, 3.0, False,
                     require_chip=False)
    assert r["correct"] is False
    got = {c["name"]: c for c in r["checks"]}
    assert not got["row_step_excess"]["ok"]


def test_the_models_parts_count_the_least_work():
    """The parts by hand at the cell's size, ``n_dense_params`` pinned to
    the configuration's ``dense_parameters``, ``bd_attn_cost``'s pairs
    equal to a count over the reference's written-out mask, and
    ``step_cost`` = the parts' sum with the sparse step and the
    optimizer's traffic."""
    from benchmark import costs
    from benchmark.models import sdar
    from benchmark.reference import sdar as ref

    cfg = load_json(HERE, "configs", "sdar_30b_ep16.json")
    assert sdar.n_dense_params(cfg) == 380_237_312
    assert "380,237,312" in cfg["deployment"]["dense_parameters"]
    T, L, B, H = 4096, 4, 2, 2048
    N = B * T
    assert sdar.mask_pairs(cfg) == T * (T + L) == 16_793_600
    for t, blk in ((37, 4), (32, 8), (20, 1)):  # a ragged last block too
        toy = {"feed": {"max_seq_len": t}, "diffusion": {"block_len": blk}}
        mask = np.asarray(ref.block_mask(t, blk))
        assert sdar.mask_pairs(toy) == mask.sum()
        # the last layer's share: the noised stream's queries have half
        assert 2 * mask[:t].sum() == mask.sum()
    proj = lambda n_q: 3 * 2.0 * (  # noqa: E731
        2 * n_q * H * 4096 + 2 * 2 * N * H * 512)
    scores = 3 * 2.0 * 32 * (128 + 128) * B * T * (T + L)
    assert sdar.bd_attn_cost(cfg)["flops"] == pytest.approx(
        5 * (proj(2 * N) + scores) + proj(N) + scores / 2)
    one = sdar.moe_cost(cfg, 1.0)["flops"] - sdar.moe_cost(cfg, 0.0)["flops"]
    assert one == pytest.approx(3 * 3 * 2.0 * H * 768)
    assert sdar.moe_cost(cfg, 0.0)["flops"] == pytest.approx(
        3 * 2.0 * 11 * N * H * 128)
    assert sdar.masked_share(cfg) == pytest.approx(0.5005)
    assert sdar.head_cost(cfg)["flops"] == pytest.approx(
        3 * 2.0 * N * 0.5005 * H * 18992)
    pairs = 11 * N * 8 * 8 / 128  # 1,024 positions a held expert and layer
    assert 2 * N * 8 / 128 == 1024
    parts = [costs.sparse_step(2850.0, 2050), sdar.bd_attn_cost(cfg),
             sdar.moe_cost(cfg, pairs), sdar.head_cost(cfg)]
    whole = sdar.step_cost(cfg, 2850.0)
    assert whole["flops"] == pytest.approx(sum(p["flops"] for p in parts))
    assert whole["bytes"] == pytest.approx(
        sum(p["bytes"] for p in parts) + 6.0 * 380_237_312 * 4)


def test_the_new_readers_read_a_reduced_trace():
    """The cell's three readers against ``run.trace`` as
    trace_reduce.reduce leaves it and the registry's snapshots;
    ``step_roofline_share`` reads for the cell too; without a trace, or on
    a program whose step has no ``attn_block_diffusion`` scope, each reads
    None and none raises.  No share can pass 100%: at the least time itself
    it reads 100."""
    import importlib
    import types

    from benchmark import costs
    from benchmark.models import sdar

    names = ("bd_attn_device_ms", "bd_attn_roofline_share",
             "denoise_tokens_per_s")
    readers = {n: importlib.import_module("benchmark.layer_metrics." + n)
               for n in names}
    cell = Cell.resolve("sdar_ep16_denoise_4k")
    assert {m["name"] for m in cell.per_layer} >= set(names) | {
        "step_roofline_share", "device_step_ms"}
    assert not {"attn_device_ms", "mla_device_ms", "train_tokens_per_s"} & {
        m["name"] for m in cell.per_layer}
    run_ = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite", distinct_keys_per_step=2850.0,
        step_cost=lambda: sdar.step_cost(cell.cfg, 2850.0),
        trace={"step_busy_s": 5.2,
               "scope_s": [["attn_block_diffusion", 3.6], ["experts", 0.9],
                           ["lm_head", 0.1], ["noise", 0.01],
                           ["unscoped", 0.2]]},
        before={"counters": {"trainer.tokens": 1000.0}, "histograms": {}},
        after={"counters": {"trainer.tokens": 41000.0}, "histograms": {}})
    got = {n: r.read(run_) for n, r in readers.items()}
    assert got["bd_attn_device_ms"] == pytest.approx(900.0)
    assert got["denoise_tokens_per_s"] == pytest.approx(8000.0)
    peaks = costs.load_peaks("TPU v5 lite")
    least, bound = costs.roofline_seconds(sdar.bd_attn_cost(cell.cfg), peaks)
    assert bound == "flops"
    assert got["bd_attn_roofline_share"] == pytest.approx(100 * least / 0.9)
    assert 0 < got["bd_attn_roofline_share"] < 100
    run_.trace["scope_s"][0][1] = 4 * least
    assert readers["bd_attn_roofline_share"].read(run_) == pytest.approx(
        100.0)
    whole = importlib.import_module(
        "benchmark.layer_metrics.step_roofline_share").read(run_)
    assert 0 < whole < 100
    bare = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite", trace={"scope_s": [["push", 1.0]]},
        before={"counters": {}, "histograms": {}},
        after={"counters": {}, "histograms": {}})
    assert all(r.read(bare) is None for r in readers.values())
    bare.trace = None
    assert all(r.read(bare) is None for r in readers.values())


def test_the_noise_level_covers_the_grid():
    """The mix's dense feature is the noise level less a half on the
    generator's grid of 1/1000: 0 .. 1 inclusive."""
    from benchmark import gen

    mix = load_json(HERE, "traffic", "token_stream_4k_v18992.json")
    assert mix["dense_range"] == 0.5
    q = np.concatenate([p.dense_q[:, 0] for p in gen.make_passes(
        dict(TOY_MIX, instances_per_pass=4096), 1, 1, 5)])
    assert q.min() == -500 and q.max() == 500
