"""The ``lfm2_24b_ep8`` configuration at toy size through ``run_cell`` on
the CPU: the program's decoder (a gated short convolution in the layers
that do not attend, grouped-query attention with a norm on every query and
key head, a leading dense layer, sigmoid-routed experts with a selection
bias) on the normal pass loop against ``reference/lfm2.py``.  New files
only: the toy cell is the real configuration's file with its sizes cut
(hidden 64, 4 query heads over 2 key-value heads of 16, 3 taps, dense width
96, 16 experts of width 32 with 4 a token of which 4 are held, the same
five layers, sequences of 32, a vocabulary of 64)."""

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
    cfg = load_json(HERE, "configs", "lfm2_24b_ep8.json")
    cfg.update(
        hidden_size=64, embedding_dim=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=16, num_experts_held=4,
        vocab_size=64, batch_size=2, keys_per_instance_capacity=32,
        hbm_cache_rows=65,
        rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
        feed={"sequence_slot": "slot0", "max_seq_len": 32})
    # on the CPU both sides are float32: the sound program reads ~1e-5 and
    # the float8 control 0.1 and more, so the toy limits sit between
    cfg["limits"] = {k: (0.0 if k == "counter_gap" else 0.02)
                     for k in cfg["limits"]}
    manifest = load_json(ROOT, "BENCHMARK.json")
    return Cell(name="toy", chips=1, cfg=cfg, mix=dict(TOY_MIX),
                end_to_end=manifest["end_to_end"],
                per_layer=manifest["per_layer"])


def test_the_toy_conv_decoder_cell_is_correct():
    r = run.run_cell(toy_cell(), 2 ** 31 + 34, 0.5, False,
                     require_chip=False)
    assert r["correct"] is True and r["failed"] == 0
    assert len(r["checks"]) == 7 and all(c["ok"] for c in r["checks"])
    assert r["counts"]["window_compile_requests"] == 0
    assert r["counts"]["passes"] >= 2 and r["metrics"] == {}


def test_control_the_toy_conv_decoder_in_float8_is_not_correct(monkeypatch):
    """The reference computed as float8 training is done, in the
    program's place on the float32 side of the comparison, fails
    ``row_step_excess`` (and is not a zero gradient)."""
    real = common.run_steps

    def control(*a, precision=""):
        return real(*a, precision=precision or "float8")

    monkeypatch.setattr(common, "run_steps", control)
    r = run.run_cell(toy_cell(), 2 ** 31 + 34, 0.5, False,
                     require_chip=False)
    assert r["correct"] is False
    got = {c["name"]: c for c in r["checks"]}
    assert not got["row_step_excess"]["ok"]


def test_the_models_parts_count_the_least_work():
    """The parts by hand at the cell's size, and ``step_cost`` = their sum
    with the sparse step and the optimizer's traffic."""
    from benchmark import costs
    from benchmark.models import lfm2

    cfg = load_json(HERE, "configs", "lfm2_24b_ep8.json")
    assert lfm2.held_layers(cfg) == [
        ("conv", "dense"), ("full_attention", "sparse"), ("conv", "sparse"),
        ("conv", "sparse"), ("conv", "sparse")]
    N, T, H = 4 * 4096, 4096, 2048
    conv = 3 * 2.0 * N * (H * 3 * H + H * H) + 3 * (2 * 3 + 2) * N * H
    assert lfm2.conv_cost(cfg)["flops"] == pytest.approx(4 * conv)
    # the chain between the projections is in their bytes, once each way
    assert lfm2.conv_cost(cfg)["bytes"] == pytest.approx(4 * 4 * (
        3.0 * (3 * H * H + H * H) + 2.0 * N * (H + 3 * H + H + H)))
    proj = 3 * 2.0 * N * H * (32 * 64 + 2 * 8 * 64 + 32 * 64)
    scores = 3 * 2.0 * 32 * (64 + 64) * 4 * T * (T + 1) / 2
    assert lfm2.attn_cost(cfg)["flops"] == pytest.approx(proj + scores)
    assert lfm2.ffn_cost(cfg)["flops"] == pytest.approx(
        3 * 2.0 * N * 3 * H * 11776)
    one = lfm2.moe_cost(cfg, 1.0)["flops"] - lfm2.moe_cost(cfg, 0.0)["flops"]
    assert one == pytest.approx(3 * 3 * 2.0 * H * 1536)
    assert lfm2.moe_cost(cfg, 0.0)["flops"] == pytest.approx(
        4 * 3 * 2.0 * N * H * 64)
    assert lfm2.head_cost(cfg)["flops"] == pytest.approx(
        3 * 2.0 * N * H * 8192)
    assert lfm2.n_dense_params(cfg) == 469_285_248
    pairs = N * 4 * 4 * 8 / 64  # 1,024 tokens a held expert and layer
    assert pairs == 4 * 8 * 1024
    parts = [costs.sparse_step(3570.0, 2050), lfm2.conv_cost(cfg),
             lfm2.attn_cost(cfg), lfm2.ffn_cost(cfg),
             lfm2.moe_cost(cfg, pairs), lfm2.head_cost(cfg)]
    whole = lfm2.step_cost(cfg, 3570.0)
    assert whole["flops"] == pytest.approx(sum(p["flops"] for p in parts))
    assert whole["bytes"] == pytest.approx(
        sum(p["bytes"] for p in parts) + 6.0 * 469_285_248 * 4)
    # the convolutions' taps and gates are a thousandth of their products
    assert 3 * 8 * N * H / conv < 1e-3


def test_the_new_readers_read_a_reduced_trace():
    """The cell's two readers against ``run.trace`` as trace_reduce.reduce
    leaves it (``scope_s``: rows [scope, seconds]); ``step_roofline_share``
    reads for the cell too; without a trace, or on a program whose step has
    no ``conv_mixer`` scope (the parent's), each reads None and none
    raises.  The share cannot pass 100%: at the least time itself it reads
    100."""
    import importlib
    import types

    from benchmark import costs
    from benchmark.models import lfm2

    names = ("conv_device_ms", "conv_roofline_share")
    readers = {n: importlib.import_module("benchmark.layer_metrics." + n)
               for n in names}
    cell = Cell.resolve("lfm2_ep8_train_4k")
    assert {m["name"] for m in cell.per_layer} >= set(names) | {
        "step_roofline_share", "device_step_ms"}
    assert not {"attn_device_ms", "mla_device_ms"} & {
        m["name"] for m in cell.per_layer}
    run_ = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite", distinct_keys_per_step=3570.0,
        step_cost=lambda: lfm2.step_cost(cell.cfg, 3570.0),
        trace={"step_busy_s": 2.4,
               "scope_s": [["conv_mixer", 0.24], ["attn_full", 0.4],
                           ["experts", 1.2], ["lm_head", 0.1],
                           ["unscoped", 0.2]]},
        before={"counters": {}, "histograms": {}},
        after={"counters": {}, "histograms": {}})
    got = {n: r.read(run_) for n, r in readers.items()}
    assert got["conv_device_ms"] == pytest.approx(60.0)
    assert 0 < got["conv_roofline_share"] <= 100
    least, bound = costs.roofline_seconds(
        lfm2.conv_cost(cell.cfg), costs.load_peaks("TPU v5 lite"))
    assert bound == "flops"
    assert got["conv_roofline_share"] == pytest.approx(100 * least / 0.06)
    run_.trace["scope_s"][0][1] = 4 * least
    assert readers["conv_roofline_share"].read(run_) == pytest.approx(100.0)
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
