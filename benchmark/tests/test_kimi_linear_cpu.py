"""The ``kimi_linear_48b_ep32`` configuration at toy size through
``run_cell`` on the CPU: the program's decoder (KDA, a gated delta rule
with a decay a channel, in the layers that do not attend; latent attention
without a positional code in the one that does; a leading dense layer,
sigmoid-routed experts with a selection bias beside a shared one) on the
normal pass loop against ``reference/kimi_linear.py``.  New files only:
the toy cell is the real configuration's file with its sizes cut (hidden
64, 4 KDA heads of 16 with a gate rank of 8, 4 taps, 4 latent heads over a
latent of 32 with a query/key head of 16 + 8 and a value head of 12, dense
width 96, 16 experts of width 32 with 4 a token of which 4 are held, the
same five layers, sequences of 32, a vocabulary of 64)."""

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
    cfg = load_json(HERE, "configs", "kimi_linear_48b_ep32.json")
    cfg.update(
        hidden_size=64, embedding_dim=64, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        linear_attn_config=dict(cfg["linear_attn_config"], num_heads=4,
                                head_dim=16),
        kda_gate_rank=8, intermediate_size=96, moe_intermediate_size=32,
        num_experts=16, num_experts_per_token=4, num_experts_held=4,
        vocab_size=64, batch_size=2, keys_per_instance_capacity=32,
        hbm_cache_rows=65, feed={"sequence_slot": "slot0", "max_seq_len": 32})
    # on the CPU both sides are float32: the sound program reads ~1e-5 and
    # the float8 control 0.1 and more, so the toy limits sit between
    cfg["limits"] = {k: (0.0 if k == "counter_gap" else 0.02)
                     for k in cfg["limits"]}
    manifest = load_json(ROOT, "BENCHMARK.json")
    return Cell(name="toy", chips=1, cfg=cfg, mix=dict(TOY_MIX),
                end_to_end=manifest["end_to_end"],
                per_layer=manifest["per_layer"])


def test_the_toy_kda_decoder_cell_is_correct():
    r = run.run_cell(toy_cell(), 2 ** 31 + 39, 3.0, False,
                     require_chip=False)
    assert r["correct"] is True and r["failed"] == 0
    assert len(r["checks"]) == 7 and all(c["ok"] for c in r["checks"])
    assert r["counts"]["window_compile_requests"] == 0
    assert r["counts"]["passes"] >= 2 and r["metrics"] == {}


def test_control_the_toy_kda_decoder_in_float8_is_not_correct(monkeypatch):
    """The reference computed as float8 training is done, in the
    program's place on the float32 side of the comparison, fails
    ``row_step_excess`` (and is not a zero gradient)."""
    real = common.run_steps

    def control(*a, precision=""):
        return real(*a, precision=precision or "float8")

    monkeypatch.setattr(common, "run_steps", control)
    r = run.run_cell(toy_cell(), 2 ** 31 + 39, 3.0, False,
                     require_chip=False)
    assert r["correct"] is False
    got = {c["name"]: c for c in r["checks"]}
    assert not got["row_step_excess"]["ok"]


def test_the_models_parts_count_the_least_work():
    """The parts by hand at the cell's size, ``n_dense_params`` pinned to
    the configuration's ``dense_parameters``, and ``step_cost`` = the
    parts' sum with the sparse step and the optimizer's traffic."""
    from benchmark import costs
    from benchmark.models import kimi_linear as km

    cfg = load_json(HERE, "configs", "kimi_linear_48b_ep32.json")
    assert km.held_layers(cfg) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("latent_attention", "sparse"), ("kda", "sparse")]
    assert km.n_dense_params(cfg) == 555_248_512
    assert "555,248,512" in cfg["deployment"]["dense_parameters"]
    N = T = 8192
    H, W, R = 2304, 4096, 128
    scan = km.kda_scan_cost(cfg)
    assert scan["flops"] == pytest.approx(4 * 3 * 6 * 128 * 128 * 32 * N)
    assert scan["bytes"] == pytest.approx(4 * 2 * 10 * N * W * 4)
    proj = 3 * 2.0 * N * (3 * H * W + 2 * (H * R + R * W) + H * 32 + W * H)
    taps = 3 * 3 * 2.0 * 4 * N * W
    assert km.kda_cost(cfg)["flops"] == pytest.approx(
        4 * (proj + taps) + scan["flops"])
    # the recurrence's least work is small by the architecture's design
    assert scan["flops"] / km.kda_cost(cfg)["flops"] < 0.04
    qk = 3 * 2.0 * N * (H * 32 * 192 + H * 576 + 512 * 32 * 256 + W * H)
    scores = 3 * 2.0 * 32 * (192 + 128) * T * (T + 1) / 2
    assert km.attn_cost(cfg)["flops"] == pytest.approx(qk + scores)
    assert km.ffn_cost(cfg)["flops"] == pytest.approx(
        3 * 2.0 * N * 3 * H * (9216 + 4 * 1024))
    one = km.moe_cost(cfg, 1.0)["flops"] - km.moe_cost(cfg, 0.0)["flops"]
    assert one == pytest.approx(3 * 3 * 2.0 * H * 1024)
    assert km.moe_cost(cfg, 0.0)["flops"] == pytest.approx(
        4 * 3 * 2.0 * N * H * 256)
    assert km.head_cost(cfg)["flops"] == pytest.approx(
        3 * 2.0 * N * H * 20480)
    pairs = N * 4 * 8 * 8 / 256  # 256 tokens a held expert and layer
    assert pairs == 4 * 8 * 256
    parts = [costs.sparse_step(2893.0, 2306), km.kda_cost(cfg),
             km.attn_cost(cfg), km.ffn_cost(cfg), km.moe_cost(cfg, pairs),
             km.head_cost(cfg)]
    whole = km.step_cost(cfg, 2893.0)
    assert whole["flops"] == pytest.approx(sum(p["flops"] for p in parts))
    assert whole["bytes"] == pytest.approx(
        sum(p["bytes"] for p in parts) + 6.0 * 555_248_512 * 4)


def test_the_kda_costs_know_no_chunk_length():
    """``kda_cost`` and ``kda_scan_cost`` are of the recurrence as stated,
    token by token: they see the file alone, no key of the file names a
    chunk, and neither of the operator's two sizes is read."""
    import inspect

    from benchmark.models import kimi_linear as km

    cfg = load_json(HERE, "configs", "kimi_linear_48b_ep32.json")
    for cost in (km.kda_cost, km.kda_scan_cost):
        assert list(inspect.signature(cost).parameters) == ["cfg"]
    assert not [k for k in cfg if "chunk" in k]
    assert "KDA_" not in inspect.getsource(km)


def test_the_new_readers_read_a_reduced_trace():
    """The cell's four readers against ``run.trace`` as trace_reduce.reduce
    leaves it (``scope_s``: rows [scope, seconds]); ``step_roofline_share``
    reads for the cell too; without a trace, or on a program whose step has
    no ``kda_mixer`` / ``kda_scan`` scope (the parent's), each reads None
    and none raises.  No share can pass 100%: at the least time itself it
    reads 100."""
    import importlib
    import types

    from benchmark import costs
    from benchmark.models import kimi_linear as km

    names = ("kda_device_ms", "kda_scan_device_ms", "kda_roofline_share",
             "kda_scan_roofline_share")
    readers = {n: importlib.import_module("benchmark.layer_metrics." + n)
               for n in names}
    cell = Cell.resolve("kimi_linear_ep32_train_8k")
    assert {m["name"] for m in cell.per_layer} >= set(names) | {
        "step_roofline_share", "device_step_ms"}
    assert not {"attn_device_ms", "mla_device_ms", "conv_device_ms"} & {
        m["name"] for m in cell.per_layer}
    run_ = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite", distinct_keys_per_step=2893.0,
        step_cost=lambda: km.step_cost(cell.cfg, 2893.0),
        trace={"step_busy_s": 3.2,
               "scope_s": [["kda_mixer", 0.4], ["kda_scan", 0.8],
                           ["attn_latent", 0.5], ["experts", 0.6],
                           ["lm_head", 0.1], ["unscoped", 0.2]]},
        before={"counters": {}, "histograms": {}},
        after={"counters": {}, "histograms": {}})
    got = {n: r.read(run_) for n, r in readers.items()}
    assert got["kda_device_ms"] == pytest.approx(300.0)
    assert got["kda_scan_device_ms"] == pytest.approx(200.0)
    peaks = costs.load_peaks("TPU v5 lite")
    least, bound = costs.roofline_seconds(km.kda_cost(cell.cfg), peaks)
    assert bound == "flops"
    assert got["kda_roofline_share"] == pytest.approx(100 * least / 0.3)
    least_scan, bound = costs.roofline_seconds(
        km.kda_scan_cost(cell.cfg), peaks)
    assert bound == "bytes"
    assert got["kda_scan_roofline_share"] == pytest.approx(
        100 * least_scan / 0.2)
    assert 0 < got["kda_scan_roofline_share"] < got["kda_roofline_share"] \
        <= 100
    run_.trace["scope_s"][0][1] = 0.0
    run_.trace["scope_s"][1][1] = 4 * least_scan
    assert readers["kda_scan_roofline_share"].read(run_) == pytest.approx(
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
