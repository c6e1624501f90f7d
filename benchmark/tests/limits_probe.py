"""Readings for the limits of ``correct``, on the chip, at a cell's own
size.  For each seed the control's numbers: the reference computed as
float8 training is done (reference/common.py ``Ops("float8")``) and
compared with the float32 reference exactly as a run compares the
program, the reference at the stated precision as ``base``.  For the first
``--sound`` seeds also the sound program's: a fresh system per seed,
driven through the first steps as a run drives them
(benchmark.run.program_check_steps), with the table holding the cycle's
keys only -- the numbers do not depend on what else the row cache holds,
and a whole key space per seed would take a minute each.  Every number of
check.py is read, with or without a limit in the configuration's file.
Training's readings need no window.

    python3 -m benchmark.tests.limits_probe --workload ctr_dnn_steady \\
        --seeds 6 --sound 6 --first-seed 1000

One JSON line per reading on stdout.  Run by hand (PERF.md section 2);
the benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import tempfile

import numpy as np

from benchmark import check, run

CONTROL = "float8"


def probe(workload: str, seeds: list, n_sound: int,
          require_chip: bool = True, cell=None) -> list:
    cell = cell or run.Cell.resolve(workload)
    cell = dataclasses.replace(cell, cfg={
        **cell.cfg, "table_prefill": {"keys": "cycle"}})
    devs = run.pin_platform(require_chip, cell.chips)
    if require_chip:
        from paddlebox_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    cfg = cell.cfg
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    common = importlib.import_module("benchmark.reference.common")
    out = []
    for i, seed in enumerate(seeds):
        with contextlib.ExitStack() as stack:
            work = stack.enter_context(tempfile.TemporaryDirectory())
            data = run.prepare_data(cell, seed, work, stack, n_passes=1)
            got = {}
            if i < n_sound:
                with contextlib.ExitStack() as inner:
                    _, table, trainer, params0, rows0 = run.fresh_system(
                        cell, devs, seed, data.all_keys, inner)
                    got["sound"] = run.program_check_steps(
                        cell, table, trainer, data.censuses[0],
                        data.step_ds, params0, data.all_keys, rows0)
                    table.close()
                del table, trainer
                run.free_device()
            else:
                import jax

                params, rows0 = run.seeded_weights(cell, seed, data.all_keys)
                params0 = jax.tree.map(np.asarray, params)
                del params  # the one device copy is run_steps' own
            steps = (ref, cfg, params0, data.all_keys, rows0, data.step_data,
                     run.key_capacity(cfg) * cell.chips)
            want = common.run_steps(*steps)
            base = common.run_steps(*steps,
                                    precision=cfg["precision"]["products"])
            got[CONTROL] = common.run_steps(*steps, precision=CONTROL)
            for arm, g in got.items():
                rec = {"workload": cell.name, "seed": seed, "arm": arm,
                       **{n["name"]: n["value"]
                          for n in check.compare(g, want, base, None)}}
                out.append(rec)
                print(json.dumps(rec), flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--sound", type=int, default=0)
    ap.add_argument("--first-seed", type=int, default=1000)
    a = ap.parse_args()
    recs = probe(a.workload, [a.first_seed + 7919 * i for i in range(a.seeds)],
                 a.sound)
    for arm in ("sound", CONTROL):
        rows = [r for r in recs if r["arm"] == arm]
        if not rows:
            continue
        for k in rows[0]:
            if k in ("workload", "seed", "arm"):
                continue
            v = [r[k] for r in rows]
            print(f"# {arm:9s} {k:16s} min {min(v):.4g} max {max(v):.4g} "
                  f"median {float(np.median(v)):.4g} n={len(v)}")


if __name__ == "__main__":
    main()
