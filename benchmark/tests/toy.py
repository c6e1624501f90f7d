"""Toy cells for the CPU tests: the real configurations' structure at
sizes a test run can hold."""

from __future__ import annotations

import json
import os

from benchmark.run import HERE, ROOT, Cell

TOY_MIX = {
    "key_distribution": "zipf", "zipf_exponent": 1.1,
    "slot_vocab": [50, 7, 3000, 900, 40, 5],
    "keys_per_slot": [1, 3], "instances_per_pass": 256,
    "distinct_passes": 2, "signal_scale": 4.0, "dense_range": 0.5,
}


def toy_cell(config: str, chips: int = 1, **mix) -> Cell:
    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg.update(n_sparse_slots=6, dense_dim=3, batch_size=32,
               keys_per_instance_capacity=24, hbm_cache_rows=1 << 14)
    # on the CPU both sides are float32: the sound program reads ~1e-6 and
    # the float8 control 0.1 and more, so the toy limits sit between
    cfg["limits"] = {k: (0.0 if k == "counter_gap" else 0.02)
                     for k in cfg["limits"]}
    if "cin_layers" in cfg:
        cfg.update(cin_layers=[8, 8], hidden=[16, 16])
    else:
        cfg.update(hidden=[32, 16])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return Cell(name="toy", chips=chips, cfg=cfg, mix={**TOY_MIX, **mix},
                end_to_end=manifest["end_to_end"],
                per_layer=manifest["per_layer"])
