"""The ``ouro_2p6b_stage`` configuration at toy size through ``run_cell`` on
the CPU: the program's decoder as a looped stack (the layers run
``total_ut_steps`` times over the same weights, sandwich norms, ``norm_f``
between the rounds, a learned exit gate, the loss weighted by the exit
distribution less beta times its entropy) on the normal pass loop against
``reference/ouro.py``.  New files only: the toy cell is the real
configuration's file with its sizes cut (hidden 64, 4 heads of 16, a SwiGLU
of 96, 2 layers run 3 times, sequences of 32, a vocabulary of 64)."""

import math

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
    cfg = load_json(HERE, "configs", "ouro_2p6b_stage.json")
    cfg.update(
        hidden_size=64, embedding_dim=64, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, intermediate_size=96,
        num_hidden_layers=2, total_ut_steps=3, vocab_size=64, batch_size=2,
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


def test_the_toy_looped_cell_is_correct():
    r = run.run_cell(toy_cell(), 2 ** 31 + 47, 3.0, False,
                     require_chip=False)
    assert r["correct"] is True and r["failed"] == 0
    assert len(r["checks"]) == 7 and all(c["ok"] for c in r["checks"])
    assert r["counts"]["window_compile_requests"] == 0
    assert r["counts"]["passes"] >= 2 and r["metrics"] == {}


def test_control_the_toy_looped_cell_in_float8_is_not_correct(monkeypatch):
    """The reference computed as float8 training is done, in the
    program's place on the float32 side of the comparison, fails
    ``row_step_excess`` (and is not a zero gradient)."""
    real = common.run_steps

    def control(*a, precision=""):
        return real(*a, precision=precision or "float8")

    monkeypatch.setattr(common, "run_steps", control)
    r = run.run_cell(toy_cell(), 2 ** 31 + 47, 3.0, False,
                     require_chip=False)
    assert r["correct"] is False
    got = {c["name"]: c for c in r["checks"]}
    assert not got["row_step_excess"]["ok"]


def test_the_models_parts_count_the_least_work():
    """The parts by hand at the cell's size, ``n_dense_params`` pinned to
    the configuration's ``dense_parameters``, and ``step_cost`` = the
    parts' sum with the sparse step and the optimizer's traffic; ISSUE
    47's arithmetic (119.6 MFLOP a token and layer use forward, 13.9 GFLOP
    a token and step, 0.289 s at the chip's peak)."""
    from benchmark import costs
    from benchmark.models import ouro

    cfg = load_json(HERE, "configs", "ouro_2p6b_stage.json")
    assert ouro.n_dense_params(cfg) == 511_774_721
    assert "511,774,721" in cfg["deployment"]["dense_parameters"]
    T, H, F, V, R, L = 4096, 2048, 5632, 49152, 4, 8
    assert (ouro.tokens(cfg), ouro.scored(cfg), ouro.layer_uses(cfg)) == (
        T, T - 1, R * L)
    use = 2.0 * (4 * H * H + 3 * H * F) * T + 4.0 * H * T * (T + 1) / 2
    assert use / T == pytest.approx(119.6e6, rel=1e-3)
    assert ouro.stack_cost(cfg)["flops"] == pytest.approx(3 * R * L * use)
    head = R * 2.0 * H * ((T - 1) * V + T)
    assert ouro.exit_head_cost(cfg)["flops"] == pytest.approx(3 * head)
    assert head / (R * L * use + head) == pytest.approx(0.17, abs=0.005)
    # ... and in the 48-layer model
    assert head / (R * 48 * use + head) == pytest.approx(0.034, abs=0.002)
    parts = [costs.sparse_step(1923.0, 2050), ouro.stack_cost(cfg),
             ouro.exit_head_cost(cfg)]
    whole = ouro.step_cost(cfg, 1923.0)
    assert whole["flops"] == pytest.approx(sum(p["flops"] for p in parts))
    assert whole["bytes"] == pytest.approx(
        sum(p["bytes"] for p in parts) + 6.0 * 511_774_721 * 4)
    least, bound = costs.roofline_seconds(
        whole, costs.load_peaks("TPU v5 lite"))
    assert bound == "flops" and least == pytest.approx(0.289, abs=0.002)


def test_the_configuration_holds_the_catalogs_config_whole():
    """Every key of the catalog row's ``config`` under the same key with
    the same value, bar the depth; ``reduced`` names the depth and the two
    cuts of the stream and nothing else."""
    import json
    import os

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    cfg = load_json(HERE, "configs", "ouro_2p6b_stage.json")
    off = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert off == {"num_hidden_layers"} and cfg["num_hidden_layers"] == 8
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "instances_per_pass", "distinct_passes"}
    assert (cfg["total_ut_steps"], cfg["vocab_size"]) == (4, 49152)


def test_the_new_readers_read_a_reduced_trace():
    """The cell's six readers against ``run.trace`` as trace_reduce.reduce
    leaves it and the registry's snapshots; ``step_roofline_share`` reads
    for the cell too; without a trace, or on a program whose step has none
    of the scopes and counters (the parent's), each reads None and none
    raises.  No share can pass 100%: at the least time itself it reads
    100."""
    import importlib
    import types

    from benchmark import costs
    from benchmark.models import ouro

    names = ("loop_stack_device_ms", "loop_stack_roofline_share",
             "exit_head_device_ms", "exit_head_roofline_share",
             "loop_layer_passes_per_s", "exit_entropy_share")
    readers = {n: importlib.import_module("benchmark.layer_metrics." + n)
               for n in names}
    cell = Cell.resolve("ouro_loop4_train_4k")
    assert {m["name"] for m in cell.per_layer} >= set(names) | {
        "step_roofline_share", "device_step_ms"}
    assert not {"attn_device_ms", "moe_device_ms", "train_tokens_per_s"} & {
        m["name"] for m in cell.per_layer}
    scored, passes = 10 * 4095.0, 10 * 4096.0 * 32
    run_ = types.SimpleNamespace(
        cell=cell, traced_steps=4, steps=10, window_s=5.0,
        device_kind="TPU v5 lite", distinct_keys_per_step=1923.0,
        step_cost=lambda: ouro.step_cost(cell.cfg, 1923.0),
        trace={"step_busy_s": 3.6,
               "scope_s": [["attn_full", 1.6], ["dense_mlp", 1.2],
                           ["lm_head", 0.5], ["exit_gate", 0.02],
                           ["round_norm", 0.04], ["push", 0.03],
                           ["unscoped", 0.2]]},
        before={"counters": {"trainer.tokens": 500.0}, "histograms": {}},
        after={"counters": {"trainer.tokens": 500.0 + scored,
                            "loop.layer_passes": passes,
                            "loop.exit_entropy": 0.5 * scored * math.log(4)},
               "histograms": {}})
    got = {n: r.read(run_) for n, r in readers.items()}
    assert got["loop_stack_device_ms"] == pytest.approx(700.0)
    assert got["exit_head_device_ms"] == pytest.approx(140.0)
    assert got["loop_layer_passes_per_s"] == pytest.approx(passes / 5.0)
    assert got["exit_entropy_share"] == pytest.approx(50.0)
    peaks = costs.load_peaks("TPU v5 lite")
    for part, name, seconds in (
            ("stack", "loop_stack_roofline_share", 0.7),
            ("exit_head", "exit_head_roofline_share", 0.14)):
        least, bound = costs.roofline_seconds(
            getattr(ouro, part + "_cost")(cell.cfg), peaks)
        assert bound == "flops"
        assert got[name] == pytest.approx(100 * least / seconds)
        assert 0 < got[name] < 100
    least, _ = costs.roofline_seconds(ouro.stack_cost(cell.cfg), peaks)
    run_.trace["scope_s"][0][1] = 4 * least - 1.2
    assert readers["loop_stack_roofline_share"].read(run_) == pytest.approx(
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
    # another cell's configuration has no rounds to take a share of
    bare.after["counters"] = {"trainer.tokens": 10.0,
                              "loop.exit_entropy": 1.0}
    bare.cell = Cell.resolve("mellum2_ep8_train_4k")
    assert readers["exit_entropy_share"].read(bare) is None
