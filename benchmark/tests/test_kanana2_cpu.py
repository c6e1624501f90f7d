"""The ``kanana2_30b_ep16`` configuration at toy size through ``run_cell``
on the CPU: the program's decoder (latent attention, a leading dense layer,
sigmoid-routed experts with a selection bias beside shared experts) on the
normal pass loop against ``reference/kanana2.py``.  New files only: the toy
cell is the real configuration's file with its sizes cut (hidden 64, 4
heads with a latent of 32, query/key head 16 + 8, value head 12, dense
width 96, 16 experts of width 32 with 4 a token of which 4 are held, 2
shared, one dense and two sparse layers, sequences of 32, a vocabulary of
64)."""

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
    cfg = load_json(HERE, "configs", "kanana2_30b_ep16.json")
    cfg.update(
        hidden_size=64, embedding_dim=64, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=12,
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
        num_experts_held=4, num_experts_per_tok=4, num_hidden_layers=3,
        rope_theta=10000.0, vocab_size=64, batch_size=2,
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


def test_the_toy_latent_decoder_cell_is_correct():
    r = run.run_cell(toy_cell(), 2 ** 31 + 31, 0.5, False,
                     require_chip=False)
    assert r["correct"] is True and r["failed"] == 0
    assert len(r["checks"]) == 7 and all(c["ok"] for c in r["checks"])
    assert r["counts"]["window_compile_requests"] == 0
    assert r["counts"]["passes"] >= 2 and r["metrics"] == {}


def test_control_the_toy_latent_decoder_in_float8_is_not_correct(monkeypatch):
    """The reference computed as float8 training is done, in the
    program's place on the float32 side of the comparison, fails
    ``row_step_excess`` (and is not a zero gradient)."""
    real = common.run_steps

    def control(*a, precision=""):
        return real(*a, precision=precision or "float8")

    monkeypatch.setattr(common, "run_steps", control)
    r = run.run_cell(toy_cell(), 2 ** 31 + 31, 0.5, False,
                     require_chip=False)
    assert r["correct"] is False
    got = {c["name"]: c for c in r["checks"]}
    assert not got["row_step_excess"]["ok"]


def test_the_models_parts_count_the_least_work():
    """The parts by hand at the cell's size, and ``step_cost`` = their sum
    with the sparse step and the optimizer's traffic."""
    from benchmark import costs
    from benchmark.models import kanana2

    cfg = load_json(HERE, "configs", "kanana2_30b_ep16.json")
    N, T, H = 4 * 4096, 4096, 2048
    proj = 3 * 2.0 * N * (H * 32 * 192 + H * 576 + 512 * 32 * 256
                          + 32 * 128 * H)
    scores = 3 * 2.0 * 32 * (192 + 128) * 4 * T * (T + 1) / 2
    assert kanana2.attn_cost(cfg)["flops"] == pytest.approx(
        5 * (proj + scores))
    assert kanana2.ffn_cost(cfg)["flops"] == pytest.approx(
        3 * 2.0 * N * 3 * H * (6144 + 4 * 1536))
    one = kanana2.moe_cost(cfg, 1.0)["flops"] - kanana2.moe_cost(
        cfg, 0.0)["flops"]
    assert one == pytest.approx(3 * 3 * 2.0 * H * 768)
    assert kanana2.moe_cost(cfg, 0.0)["flops"] == pytest.approx(
        4 * 3 * 2.0 * N * H * 128)
    assert kanana2.head_cost(cfg)["flops"] == pytest.approx(
        3 * 2.0 * N * H * 16032)
    assert kanana2.n_dense_params(cfg) == 392_127_488
    pairs = N * 4 * 6 * 8 / 128
    parts = [costs.sparse_step(4400.0, 2050), kanana2.attn_cost(cfg),
             kanana2.ffn_cost(cfg), kanana2.moe_cost(cfg, pairs),
             kanana2.head_cost(cfg)]
    whole = kanana2.step_cost(cfg, 4400.0)
    assert whole["flops"] == pytest.approx(sum(p["flops"] for p in parts))
    assert whole["bytes"] == pytest.approx(
        sum(p["bytes"] for p in parts) + 6.0 * 392_127_488 * 4)
    # 706 MFLOP a token forward, attention two thirds of it
    assert whole["flops"] / (3 * N) == pytest.approx(706.3e6, rel=1e-3)
    assert kanana2.attn_cost(cfg)["flops"] / whole["flops"] == pytest.approx(
        0.67, abs=0.005)


def test_the_new_readers_read_a_reduced_trace():
    """The cell's five readers against ``run.trace`` as trace_reduce.reduce
    leaves it (``scope_s``: rows [scope, seconds]) and the registry's
    counters; without a trace, or on a program without the scopes and
    counters (the parent's), each reads None and none raises."""
    import importlib
    import types

    names = ("mla_device_ms", "mla_roofline_share", "shared_ffn_device_ms",
             "shared_ffn_roofline_share", "routed_products_excess")
    readers = {n: importlib.import_module("benchmark.layer_metrics." + n)
               for n in names}
    cell = Cell.resolve("kanana2_ep16_train_4k")
    assert {m["name"] for m in cell.per_layer} >= set(names)
    counters = {"moe.pairs_routed": 16384.0 * 6 * 4 * 10,
                "moe.pairs_local": 16384.0 * 6 * 4 * 10 / 16}
    run_ = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite",
        trace={"scope_s": [["attn_latent", 3.2], ["experts", 0.6],
                           ["shared_experts", 0.24], ["dense_mlp", 0.16],
                           ["lm_head", 0.1], ["unscoped", 0.2]]},
        before={"counters": {}, "histograms": {}},
        after={"counters": counters, "histograms": {}})
    got = {n: r.read(run_) for n, r in readers.items()}
    assert got["mla_device_ms"] == pytest.approx(800.0)
    assert got["shared_ffn_device_ms"] == pytest.approx(100.0)
    assert got["routed_products_excess"] == pytest.approx(128 / 6)
    assert 0 < got["mla_roofline_share"] < 100
    assert 0 < got["shared_ffn_roofline_share"] < 100
    bare = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite", trace={"scope_s": [["push", 1.0]]},
        before={"counters": {}, "histograms": {}},
        after={"counters": {}, "histograms": {}})
    assert all(r.read(bare) is None for r in readers.values())
    bare.trace = None
    assert all(r.read(bare) is None for r in readers.values())
