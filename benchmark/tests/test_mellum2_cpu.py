"""The ``mellum2_12b_ep8`` configuration at toy size through ``run_cell``
on the CPU: the program's decoder on the normal pass loop against
``reference/mellum2.py``.  New files only: the toy cell is the real
configuration's file with its sizes cut (hidden 64, 4 query heads over 2
key-value heads of 16, window 8, sequences of 32, 8 experts of width 32
with 4 a token of which 4 are held, a vocabulary of 64)."""

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
    cfg = load_json(HERE, "configs", "mellum2_12b_ep8.json")
    rope = cfg["rope_parameters"]
    rope["full_attention"].update(
        factor=4.0, original_max_position_embeddings=16, beta_fast=4.0,
        beta_slow=1.0, rope_theta=10000.0)
    rope["sliding_attention"]["rope_theta"] = 10000.0
    cfg.update(
        hidden_size=64, embedding_dim=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, sliding_window=8,
        num_experts=8, num_experts_held=4, num_experts_per_tok=4,
        moe_intermediate_size=32, vocab_size=64, batch_size=2,
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


def test_the_toy_decoder_cell_is_correct():
    r = run.run_cell(toy_cell(), 2 ** 31 + 27, 0.5, False,
                     require_chip=False)
    assert r["correct"] is True and r["failed"] == 0
    assert len(r["checks"]) == 7 and all(c["ok"] for c in r["checks"])
    assert r["counts"]["window_compile_requests"] == 0
    assert r["counts"]["passes"] >= 2 and r["metrics"] == {}


def test_control_the_toy_decoder_in_float8_is_not_correct(monkeypatch):
    """The reference computed as float8 training is done, in the
    program's place on the float32 side of the comparison, fails
    ``row_step_excess`` (and is not a zero gradient)."""
    real = common.run_steps

    def control(*a, precision=""):
        return real(*a, precision=precision or "float8")

    monkeypatch.setattr(common, "run_steps", control)
    r = run.run_cell(toy_cell(), 2 ** 31 + 27, 0.5, False,
                     require_chip=False)
    assert r["correct"] is False
    got = {c["name"]: c for c in r["checks"]}
    assert not got["row_step_excess"]["ok"]


def test_the_models_parts_count_the_least_work():
    """``attn_cost`` counts unmasked pairs only and ``moe_cost`` routed
    pairs only; the step's cost is their sum with the sparse step, the
    head and the optimizer's traffic."""
    from benchmark.models import mellum2

    cfg = load_json(HERE, "configs", "mellum2_12b_ep8.json")
    N, T, hq = 4 * 4096, 4096, 32 * 128
    proj = 3 * 2.0 * N * 2304 * (2 * hq + 2 * 512)
    full = 3 * 4.0 * hq * 4 * T * (T + 1) / 2
    band = 3 * 4.0 * hq * 4 * (1024 * 1025 / 2 + (T - 1024) * 1024)
    assert mellum2.attn_cost(cfg)["flops"] == pytest.approx(
        4 * proj + full + 3 * band)
    one = mellum2.moe_cost(cfg, 1.0)["flops"] - mellum2.moe_cost(
        cfg, 0.0)["flops"]
    assert one == pytest.approx(3 * 3 * 2.0 * 2304 * 896)
    assert mellum2.n_dense_params(cfg) == 312_037_632
    whole = mellum2.step_cost(cfg, 4100.0)
    assert whole["flops"] > mellum2.attn_cost(cfg)["flops"] + \
        mellum2.head_cost(cfg)["flops"]


def test_the_scope_readers_read_a_reduced_trace():
    """The decoder's per-layer readers against ``run.trace`` as
    trace_reduce.reduce leaves it (``scope_s``: rows [scope, seconds]) and
    the registry's counters; without a trace, or on a program without the
    scopes and counters (the parent's), each reads None and none raises."""
    import importlib
    import types

    names = ("attn_device_ms", "attn_roofline_share", "moe_device_ms",
             "moe_roofline_share", "lm_head_device_ms",
             "expert_load_max_share", "train_tokens_per_s")
    readers = {n: importlib.import_module("benchmark.layer_metrics." + n)
               for n in names}
    cell = Cell.resolve("mellum2_ep8_train_4k")
    counters = {"moe.pairs_local": 65536.0 * 10, "trainer.tokens": 16380.0 * 10,
                "moe.expert_load_max": 1200.0, "moe.expert_load_mean": 1000.0}
    run_ = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite",
        trace={"scope_s": [["experts", 0.8], ["attn_window", 0.6],
                           ["attn_full", 0.4], ["lm_head", 0.1],
                           ["router", 0.04], ["unscoped", 0.2]]},
        before={"counters": {}, "histograms": {}},
        after={"counters": counters, "histograms": {}})
    got = {n: r.read(run_) for n, r in readers.items()}
    assert got["attn_device_ms"] == pytest.approx(250.0)
    assert got["moe_device_ms"] == pytest.approx(210.0)
    assert got["lm_head_device_ms"] == pytest.approx(25.0)
    assert got["expert_load_max_share"] == pytest.approx(120.0)
    assert got["train_tokens_per_s"] == pytest.approx(32760.0)
    assert 0 < got["attn_roofline_share"] < 100
    assert 0 < got["moe_roofline_share"] < 100
    bare = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite", trace={"scope_s": [["push", 1.0]]},
        before={"counters": {}, "histograms": {}},
        after={"counters": {}, "histograms": {}})
    assert all(r.read(bare) is None for r in readers.values())
    bare.trace = None
    assert all(r.read(bare) is None for r in readers.values())
