"""BENCHMARK.json against the files it names and the contract's limits on
names and units."""

import importlib
import json
import os
import re

from benchmark.run import HERE, ROOT, Cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_command():
    m = manifest()
    assert set(m) == KEYS
    assert m["paths"] == ["benchmark"]
    assert m["command"] == ["python3", "-m", "benchmark.run"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51


def test_names_units_and_lengths():
    m = manifest()
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    metrics = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["moves"] in {x["name"] for x in m["end_to_end"]}
        assert 0 < len(e["layer"]) <= 200 and "\n" not in e["layer"]
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 0 < len(c["source"]) <= 200 and 0 < len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)


def test_every_name_resolves_to_a_file():
    m = manifest()
    files = set()
    for c in m["configs"]:
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg["reduced"], key
        model = importlib.import_module("benchmark.models." + cfg["model"])
        assert callable(model.build) and callable(model.step_cost)
        ref = importlib.import_module(
            "benchmark.reference." + cfg["reference"])
        assert callable(ref.init_params) and callable(ref.logits)
        assert set(cfg["limits"]) == {
            "loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff",
            "row_step_excess", "row_diff", "counter_gap"}
        assert cfg["precision"]["products"] == "bfloat16"
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for w in m["workloads"]:
        assert os.path.exists(
            os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        cell = Cell.resolve(w["name"], m)
        assert {e["name"] for e in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
        assert not cell.mix["instances_per_pass"] % (
            cell.cfg["batch_size"] * cell.chips)
    for e in m["per_layer"]:
        reader = importlib.import_module(
            "benchmark.layer_metrics." + e["name"])
        assert callable(reader.read)
        for w in e.get("workloads", []):
            assert w in {x["name"] for x in m["workloads"]}


def test_reference_imports_nothing_of_the_program():
    ref_dir = os.path.join(HERE, "reference")
    for name in os.listdir(ref_dir):
        if name.endswith(".py"):
            with open(os.path.join(ref_dir, name)) as f:
                assert "paddlebox_tpu" not in f.read().replace(
                    "paddlebox_tpu's", ""), name
    with open(os.path.join(HERE, "gen.py")) as f:
        assert "import paddlebox_tpu" not in f.read()
