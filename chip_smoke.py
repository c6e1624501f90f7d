#!/usr/bin/env python
"""Chip smoke: the train -> export -> score path, once, on the local TPU.

    python chip_smoke.py [--require-chips 4]

One process (a chip has one owner), no CPU fallback: the platform is pinned
to ``tpu`` before the first backend call, so without a chip JAX raises and
the script exits non-zero with no result line.  Every leg goes through the
entry points a user calls and fails by raising; nothing is caught.

  * train  — CTR-DNN at the repo's north-star width (26 sparse slots, 13
    dense, embedding 16, tower 512-256-128, batch 2048, vocab 1e6 per
    slot; seeded synthetic slot files) through BoxPSDataset ->
    SparseTable pass lifecycle -> Trainer.train_from_dataset with the
    DEFAULT trainer and table configs.  Three passes: the single-chip
    lifecycle compiles in pass 1 and once more in pass 2 (the table
    capacity is fitted to the census there), so the pass that must trigger
    zero backend compiles is the third.
  * serve  — export_model of that state -> an in-process ScoringServer ->
    POST /score requests of mixed sizes over loopback (two shape buckets),
    whose scores must reproduce Trainer.evaluate's statistics on the same
    instances.
  * sparse_ops — the gather and scatter-add the step is built from, at the
    train leg's shapes, against numpy (duplicate indices included).
  * attention — the flash-form kernel (parallel/flash_attention.py: what
    ``full_attention``'s blockwise form runs on a TPU) against the strips,
    at kanana2's and kimi_linear's published head shapes and sequence
    lengths (a value head narrower than the key head, 4 x 4,096 and 1 x
    8,192 positions): one line each with the distance of the output and of
    the gradients and the milliseconds of both forms.
  * four_chips — with >= 4 devices (demanded by ``--require-chips 4``):
    the train leg through make_mesh(4) + ShardedSparseTable +
    MultiChipTrainer, hash placement and the realized hybrid placement,
    with every shard, cache and hot-block copy checked to be on its own
    device.  Never runs on simulated devices.

Stdout is two JSON lines.  The first is the report: jax version, sizes, what
each leg did, compiles and compile seconds by stage, the compile cache
directory, peak device bytes, the native libraries loaded.  The last is the
verdict and nothing else: ``{"ok": true, "device": {"platform", "kind",
"count"}}`` with the device as JAX reports it.  tests/test_chip_smoke.py
drives the same leg functions at toy sizes on the CPU mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
import urllib.request

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The north-star width (examples/train_criteo.py, the benchmark's
    ctr_dnn configuration); depth is what is cut: steps per pass."""

    slots: int = 26
    dense: int = 13
    emb: int = 16
    hidden: tuple = (512, 256, 128)
    batch: int = 2048
    vocab: int = 1_000_000
    steps: int = 20  # per pass
    passes: int = 3
    # /score request sizes: both artifact buckets get traffic, and one
    # request is larger than the small bucket but not a full batch
    requests: tuple = (8, 64, 300, 2048)
    small_bucket: int = 64

    def key_capacity(self, batch: int) -> int:
        return batch * self.slots * 4  # room for 4 keys a slot


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def compiles_since(before: dict) -> dict:
    from paddlebox_tpu.telemetry.compiles import compiles_by_stage

    now = compiles_by_stage()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def make_dataset(sz: Sizes, work: str, seed: int = 7):
    """Seeded slot files -> a loaded BoxPSDataset, via the factory."""
    from paddlebox_tpu.data.dataset import DatasetFactory
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files

    conf = make_synth_config(
        n_sparse_slots=sz.slots, dense_dim=sz.dense, batch_size=sz.batch,
        max_feasigns_per_ins=64, batch_key_capacity=sz.key_capacity(sz.batch),
    )
    files = write_synth_files(
        os.path.join(work, "data"), n_files=4,
        ins_per_file=sz.steps * sz.batch // 4, n_sparse_slots=sz.slots,
        vocab_per_slot=sz.vocab, dense_dim=sz.dense, seed=seed,
    )
    ds = DatasetFactory().create_dataset("BoxPSDataset", conf)
    ds.set_filelist(files)
    ds.load_into_memory()
    return conf, files, ds


def _model(sz: Sizes, tconf):
    from paddlebox_tpu.models import CtrDnn

    return CtrDnn(sz.slots, tconf.row_width, dense_dim=sz.dense,
                  hidden=sz.hidden)


def leg_train(sz: Sizes, ds) -> tuple:
    """Passes through the single-chip Trainer with default configs.
    Returns (report, model, table, trainer)."""
    import jax

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.telemetry.compiles import compiles_by_stage
    from paddlebox_tpu.train.trainer import Trainer

    tconf = SparseTableConfig(embedding_dim=sz.emb)
    model = _model(sz, tconf)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(), seed=0)
    keys = ds.unique_keys()
    passes = []
    for p in range(sz.passes):
        before = compiles_by_stage()
        t0 = time.monotonic()
        table.begin_pass(keys)
        if table.values.devices() != {jax.devices()[0]}:
            raise AssertionError(
                f"table.values is on {table.values.devices()}, not on "
                f"{jax.devices()[0]}")
        capacity = int(table.values.shape[0])
        m = trainer.train_from_dataset(ds, table)
        table.end_pass()
        passes.append({
            "steps": int(m["steps"]), "loss": m["loss"], "auc": m["auc"],
            "capacity_rows": capacity,
            "seconds": round(time.monotonic() - t0, 2),
            "compiles": compiles_since(before),
        })
        log(f"train pass {p}: {passes[-1]}")
        if not np.isfinite(m["loss"]):
            raise AssertionError(f"pass {p} loss is {m['loss']}")
        if m["steps"] < sz.steps:
            raise AssertionError(f"pass {p} took {m['steps']} steps")
    if not passes[-1]["auc"] > 0.5:
        raise AssertionError(f"last pass AUC {passes[-1]['auc']} <= 0.5")
    if passes[-1]["compiles"]:
        raise AssertionError(
            f"steady-state pass compiled: {passes[-1]['compiles']}")
    report = {"passes": passes, "features": int(table.n_features),
              "key_capacity": sz.key_capacity(sz.batch),
              "row_width": tconf.row_width}
    return report, model, table, trainer


def _recombine(lines: list, n_slots: int) -> list:
    """Instances made of the training instances' own parts: label and
    dense features of instance i, sparse slot s of instance i + s + 1.
    At this vocabulary the trained instances themselves are memorized and
    score exactly 0 or 1, which would compare nothing; these are unseen
    combinations of seen keys, and score anywhere in between."""
    rows = []
    for line in lines:
        tok = line.split()
        parts, at = [tok[:2]], 2  # "1 <label>"
        for _ in range(n_slots):
            n = int(tok[at])
            parts.append(tok[at: at + 1 + n])
            at += 1 + n
        parts.append(tok[at:])  # "<dense_dim> v1 .. vn"
        rows.append(parts)
    n = len(rows)
    return [
        b" ".join(
            rows[i][0]
            + [t for s in range(n_slots)
               for t in rows[(i + s + 1) % n][1 + s]]
            + rows[i][-1]
        ) + b"\n"
        for i in range(n)
    ]


def leg_serve(sz: Sizes, conf, files, model, table, trainer, work: str) -> dict:
    """export_model -> Predictor (inside ScoringServer) -> POST /score.
    The served scores' mean, MAE and RMSE against the labels must equal
    what Trainer.evaluate accumulates over the same instances."""
    from paddlebox_tpu.data.dataset import DatasetFactory
    from paddlebox_tpu.inference import ScoringServer, export_model
    from paddlebox_tpu.telemetry.compiles import compiles_by_stage

    art = os.path.join(work, "artifact")
    export_model(
        model, trainer.params, table, art, batch_size=sz.batch,
        key_capacity=sz.key_capacity(sz.batch), dense_dim=sz.dense,
        batch_buckets=[(sz.small_bucket, sz.key_capacity(sz.small_bucket))],
        feed_conf=conf,
    )
    lines = []
    for path in files:
        with open(path, "rb") as f:
            lines += f.read().splitlines(keepends=True)
    if len(lines) < sum(sz.requests):
        raise AssertionError("not enough instances for the request mix")
    lines = _recombine(lines[: sum(sz.requests)], sz.slots)
    before = compiles_by_stage()
    server = ScoringServer()
    server.register("ctr", art)
    port = server.start(port=0)
    scores, at = [], 0
    try:
        for n in sz.requests:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/score",
                data=b"".join(lines[at: at + n]), method="POST")
            with urllib.request.urlopen(req, timeout=600) as resp:
                got = json.load(resp)["scores"]
            if len(got) != n:
                raise AssertionError(f"asked {n} scores, got {len(got)}")
            scores += got
            at += n
    finally:
        server.stop()
    scores = np.asarray(scores, np.float64)
    if not (np.isfinite(scores).all() and (scores >= 0).all()
            and (scores <= 1).all()):
        raise AssertionError("served scores are not probabilities")
    labels = np.asarray([float(l.split()[1]) for l in lines], np.float64)

    eval_path = os.path.join(work, "served-instances")
    with open(eval_path, "wb") as f:
        f.writelines(lines)
    ds = DatasetFactory().create_dataset("BoxPSDataset", conf)
    ds.set_filelist([eval_path])
    ds.load_into_memory()
    table.begin_pass(ds.unique_keys())
    want = trainer.evaluate(ds, table)
    table.end_pass()
    ds.close()
    err = scores - labels
    got = {"count": float(scores.shape[0]),
           "predicted_ctr": float(scores.mean()),
           "mae": float(np.abs(err).mean()),
           "rmse": float(np.sqrt((err * err).mean()))}
    for k, v in got.items():
        if abs(v - want[k]) > 2e-4:
            raise AssertionError(
                f"served {k} {v} != Trainer.evaluate's {want[k]}")
    return {"requests": list(sz.requests), "served": got,
            "evaluate": {k: want[k] for k in got},
            "compiles": compiles_since(before)}


def leg_sparse_ops(sz: Sizes, capacity_rows: int, row_width: int) -> dict:
    """jnp.take and the row scatter-add — what pull and push lower to — at
    the train leg's shapes (K = batch key capacity, W = row width, P = the
    pass capacity) against numpy, duplicate indices included, and the
    unique_indices claim on indices that are unique."""
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.sparse.table import scatter_add_rows

    K, W, P = sz.key_capacity(sz.batch), row_width, capacity_rows
    rng = np.random.default_rng(0)
    values = rng.normal(size=(P, W)).astype(np.float32)
    delta = rng.normal(size=(K, W)).astype(np.float32)
    dup = rng.integers(0, max(P // 64, 1), size=K).astype(np.int32)
    uniq = rng.permutation(P)[: min(K, P)].astype(np.int32)

    dv = jnp.asarray(values)
    got = np.asarray(jax.jit(lambda v, i: jnp.take(v, i, axis=0))(dv, dup))
    np.testing.assert_array_equal(got, values[dup])

    want = values.copy()
    np.add.at(want, dup, delta)
    got = np.asarray(jax.jit(lambda v, i, d: v.at[i].add(d))(dv, dup, delta))
    # duplicates accumulate in an order the backend chooses
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    want = values.copy()
    want[uniq] += delta[: uniq.shape[0]]
    got = np.asarray(
        jax.jit(scatter_add_rows)(dv, uniq, delta[: uniq.shape[0]]))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    return {"K": K, "W": W, "P": P, "checked": [
        "take", "scatter_add(duplicates)", "scatter_add(unique_indices)"]}


# two accepted decoder configurations' causal attention (benchmark/configs/),
# the widest heads and the longest sequence: name -> (B, T, H, Hkv, D, Dv)
ATTENTION = {
    "kanana2_30b_ep16.latent": (4, 4096, 32, 32, 192, 128),
    "kimi_linear_48b_ep32.latent": (1, 8192, 32, 32, 192, 128),
}


def leg_attention(shapes: dict, block_q: int = 256, repeats: int = 3) -> dict:
    """``full_attention``'s blockwise form under the causal mask as this
    backend takes it against the strips (the oracle, and every other
    backend's form), forward and gradients, at ``shapes``.  On a TPU the
    kernel is run whichever form ``full_attention`` takes there (``form``);
    its distance from the strips is one bfloat16 pass's (both round their
    products' operands to bfloat16, in another order)."""
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.parallel import sequence as sq

    def timed(f, *args):
        out = jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = f(*args)
        jax.block_until_ready(out)
        return out, 1e3 * (time.perf_counter() - t0) / repeats

    def gap(want, got) -> float:
        return float(jnp.linalg.norm((want - got).ravel())
                     / jnp.linalg.norm(want.ravel()))

    on_tpu = jax.default_backend() == "tpu"
    report = {}
    for name, (b, t, h, hkv, d, dv) in shapes.items():
        ks = jax.random.split(jax.random.PRNGKey(len(report)), 4)
        q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, t, hkv, dv), jnp.float32)
        w = jax.random.normal(ks[3], (b, t, h, dv), jnp.float32)

        def measure(tag, attend) -> dict:
            def loss(q, k, v, w):  # w an argument: closed over, a constant
                return (attend(q, k, v) * w).sum()
            out, line[f"{tag}_fwd_ms"] = timed(jax.jit(attend), q, k, v)
            (dq, dk, dv_), line[f"{tag}_grad_ms"] = timed(
                jax.jit(jax.grad(loss, (0, 1, 2))), q, k, v, w)
            return {"out": out, "dq": dq, "dk": dk, "dv": dv_}

        line = {"form": sq._attention_form(q, k, v, "causal")[0]}
        want = measure("strips", lambda q, k, v: sq._blockwise_attention(
            q, k, v, True, None, block_q))
        got = want  # off the chip there is one form: nothing to tell apart
        if on_tpu:  # the kernel itself, whichever form full_attention takes
            from paddlebox_tpu.parallel import flash_attention as fa
            line["blocks"] = fa.blocks_for(t, t, h // hkv, d, dv, hkv)
            spec = fa.Spec("causal", None, *line["blocks"])
            got = measure("kernel", lambda q, k, v: fa.flash_attention(
                q, k, v, spec))
        line["gap"] = {x: gap(want[x], got[x]) for x in want}
        if max(line["gap"].values()) > 2e-2:
            raise AssertionError(f"{name}: kernel from strips {line['gap']}")
        log(f"attention {name}: {json.dumps(line)}")
        report[name] = line
    return report


def _train_sharded(sz: Sizes, ds, mesh, placement: str, passes: int):
    """The train leg on a mesh; returns (report, final host state)."""
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.parallel import MultiChipTrainer, ShardedSparseTable
    from paddlebox_tpu.telemetry.compiles import compiles_by_stage

    devs = list(mesh.devices.flat)
    tconf = SparseTableConfig(embedding_dim=sz.emb, placement=placement)
    trainer = MultiChipTrainer(_model(sz, tconf), tconf, mesh,
                               TrainerConfig(), seed=0)
    table = ShardedSparseTable(tconf, mesh, seed=0)
    keys = ds.unique_keys()

    def on_every_device(x) -> bool:
        shards = [s.device for s in x.addressable_shards]
        return len(shards) == len(devs) and set(shards) == set(devs)

    report = {"passes": []}
    for p in range(passes):
        before = compiles_by_stage()
        table.begin_pass(keys)
        for name, arr in (("values", table.values), ("g2sum", table.g2sum)):
            if not on_every_device(arr):
                raise AssertionError(
                    f"{name} is not one shard on each of {devs}: "
                    f"{arr.sharding}")
        for i, cache in enumerate(table._caches()):
            if cache.rows.devices() != {devs[i]}:
                raise AssertionError(
                    f"shard {i}'s cache rows on {cache.rows.devices()}, "
                    f"its device is {devs[i]}")
        m = trainer.train_from_dataset(ds, table)
        table.end_pass()
        report["passes"].append({
            "steps": int(m["steps"]), "loss": m["loss"], "auc": m["auc"],
            "compiles": compiles_since(before)})
        log(f"{placement or 'hybrid'} pass {p}: {report['passes'][-1]}")
        if not np.isfinite(m["loss"]):
            raise AssertionError(f"pass {p} loss is {m['loss']}")
    if report["passes"][-1]["compiles"]:
        raise AssertionError(
            "steady-state pass compiled: "
            f"{report['passes'][-1]['compiles']}")
    report["cache_shards"] = len(table._caches())
    report["hot_rows"] = int(table.hot_resident_keys().shape[0])
    if table.hot_block_capacity:
        if not report["hot_rows"]:
            raise AssertionError("hybrid placement realized no hot set")
        if not on_every_device(table.hot_values):
            raise AssertionError(
                f"hot block is not one copy on each of {devs}: "
                f"{table.hot_values.sharding}")
        copies = [np.asarray(s.data) for s in
                  table.hot_values.addressable_shards]
        if any(not np.array_equal(copies[0], c) for c in copies[1:]):
            raise AssertionError("hot block replicas differ")
    state = table.state_dict()
    table.close()
    trainer.close()
    return report, state


def leg_four_chips(sz: Sizes, ds, n_devices: int = 4) -> dict:
    """Hash placement, then the realized hybrid placement, one pass more
    than the single-chip leg each: the hybrid lifecycle promotes its first
    hot set at the start of pass 3 (a key reaches the planner's enter
    frequency there), which is its last warm-up event.  How far the two
    arms' trained rows differ is reported, not asserted — they are two
    different XLA programs."""
    from paddlebox_tpu.parallel import make_mesh

    mesh = make_mesh(n_devices)
    hash_rep, hash_state = _train_sharded(sz, ds, mesh, "hash", sz.passes + 1)
    hyb_rep, hyb_state = _train_sharded(sz, ds, mesh, "", sz.passes + 1)
    if not np.array_equal(hash_state["keys"], hyb_state["keys"]):
        raise AssertionError("hash and hybrid runs ended with different keys")
    diff = np.abs(hash_state["values"] - hyb_state["values"])
    return {"devices": n_devices, "hash": hash_rep, "hybrid": hyb_rep,
            "hash_vs_hybrid_max_abs_diff": float(diff.max())}


def result_line(devs) -> dict:
    """The verdict, reached only when every leg passed: exactly these keys,
    the device as JAX reports it."""
    return {"ok": True,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--require-chips", type=int, default=1,
                    help="fail unless JAX reports at least this many "
                         "devices (4 also demands the four-chip leg)")
    ap.add_argument("--seed", type=int, default=7,
                    help="seed of the synthetic slot data")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "tpu")  # no chip -> JAX raises

    from paddlebox_tpu._native import require_native
    from paddlebox_tpu.telemetry.compiles import (
        compile_summary,
        install_compile_listener,
    )
    from paddlebox_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    install_compile_listener()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports {devs[0].platform}")
    if len(devs) < args.require_chips:
        raise SystemExit(
            f"--require-chips {args.require_chips}: JAX reports {len(devs)}")
    native = require_native()

    sz = Sizes()
    legs: dict = {}
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        conf, files, ds = make_dataset(sz, work, seed=args.seed)
        log(f"data: {sz.steps * sz.batch} instances in "
            f"{time.monotonic() - t0:.0f}s")
        legs["train"], model, table, trainer = leg_train(sz, ds)
        legs["serve"] = leg_serve(sz, conf, files, model, table, trainer,
                                  work)
        legs["sparse_ops"] = leg_sparse_ops(
            sz, legs["train"]["passes"][-1]["capacity_rows"],
            legs["train"]["row_width"])
        table.close()
        legs["attention"] = leg_attention(ATTENTION)
        if len(devs) >= 4:
            legs["four_chips"] = leg_four_chips(sz, ds)
        else:
            legs["four_chips"] = f"skipped: {len(devs)} device"
        ds.close()

    stats = devs[0].memory_stats() or {}
    print(json.dumps({
        "report": "chip_smoke",
        "jax": jax.__version__,
        "sizes": dataclasses.asdict(sz),
        "legs": legs,
        "compile": compile_summary(),
        "compile_cache_dir": cache_dir,
        "peak_device_bytes": stats.get("peak_bytes_in_use"),
        "native": native,
        "seconds": round(time.monotonic() - t0, 1),
    }))
    print(json.dumps(result_line(devs)), flush=True)


if __name__ == "__main__":
    main()
