#!/usr/bin/env python
"""End-to-end CTR-DNN throughput benchmark (driver entry).

Prints ONE JSON line to stdout:
    {"metric": "ctr_dnn_samples_per_sec", "value": N, "unit": "samples/sec",
     "vs_baseline": R}

The reference publishes no numbers, so ``vs_baseline`` is the
measured speedup of our pass-scoped design (host key planning + dedup merge +
fused segment-sum pooling, sparse/table.py) over a *naive JAX port* of the
same model (no dedup, per-slot masked pooling — what a line-for-line
translation of pull_box_sparse + sequence_pool would look like).  The
headline measures BOTH driver loops over that design — the plain async
loop and the prefetch+scan trainer path — and reports the better one,
labeled by the "path" field (plain | scan8), so the number tracks the
best honest configuration on the day's backend; stderr carries the
breakdown.  It measures on a TPU and exits non-zero without one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_RUN_IDENTITY: dict = {}


def _run_identity() -> dict:
    """Cached run-identity stamp (git sha, start time, backend, jax
    version, host) for every emitted row; init_backend() clears it once
    the platform is known."""
    if not _RUN_IDENTITY:
        try:
            from paddlebox_tpu.telemetry.flight import run_identity

            _RUN_IDENTITY.update(run_identity())
        except Exception as e:  # the stamp is telemetry, never a failure
            _RUN_IDENTITY.update({"error": repr(e)[:120]})
    return dict(_RUN_IDENTITY)


def _history_path() -> str:
    """Bench-history target: PBOX_BENCH_HISTORY overrides (empty string
    disables the append), default is BENCH_HISTORY.jsonl next to bench.py
    so repeated runs in one checkout accumulate the per-(metric, backend)
    trend tools/bench_trend.py gates on."""
    if "PBOX_BENCH_HISTORY" in os.environ:
        return os.environ["PBOX_BENCH_HISTORY"]
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_HISTORY.jsonl")


def emit(obj: dict) -> None:
    """Print a result JSON line to stdout and flush immediately.

    Called twice on the headline path: once right after the `ours`
    measurement (vs_baseline null) and once after the naive baseline
    completes.  The driver parses the LAST JSON line from the output tail,
    so the final line supersedes the partial one — and a process that dies
    mid-naive still leaves the flushed partial line.

    Every row is stamped with the cached run identity and appended to the
    bench history file (best-effort: a read-only checkout must not turn a
    measurement into a crash)."""
    if "run" not in obj:
        obj = {**obj, "run": _run_identity()}
    line = json.dumps(obj)
    print(line, flush=True)
    path = _history_path()
    if path:
        try:
            with open(path, "a") as f:
                f.write(line + "\n")
        except OSError:
            pass  # history append is best-effort; stdout is the artifact


def telemetry_summary(max_counters: int = 40) -> dict:
    """Compact registry snapshot for the emitted BENCH_*.json rows: the
    non-zero counters plus per-stage latency DISTRIBUTIONS (p50/p99 ms),
    so the perf trajectory carries tails, not just means.  Bounded size —
    a bench artifact is a JSON line, not a dump."""
    from paddlebox_tpu.telemetry import registry
    from paddlebox_tpu.telemetry.metrics import Histogram

    snap = registry.snapshot()
    counters = {
        k: v for k, v in sorted(snap["counters"].items()) if v
    }
    if len(counters) > max_counters:
        counters = dict(list(counters.items())[:max_counters])
    stages: dict = {}
    m = registry.get("trainer.stage_seconds")
    if isinstance(m, Histogram):
        seen = {
            dict(key).get("stage") for key in m.series()
        }
        for stage in sorted(s for s in seen if s):
            s = m.summary(stage=stage)
            if s["count"]:
                stages[stage] = {
                    "count": s["count"],
                    "mean_ms": round((s["mean"] or 0) * 1e3, 3),
                    "p50_ms": round((s["p50"] or 0) * 1e3, 3),
                    "p99_ms": round((s["p99"] or 0) * 1e3, 3),
                }
    # per-stage XLA compile counts (the retrace witness): a steady-state
    # bench row should show each stage compiling during warmup and NEVER
    # again — a growing count across rows is the silent-retrace regression
    # the jit-retrace-hazard lint pass exists to prevent
    from paddlebox_tpu.telemetry.compiles import compiles_by_stage

    return {"counters": counters, "stage_ms": stages,
            "jit_compiles": compiles_by_stage()}


def init_backend():
    """Query the devices, require a TPU, install the compile listener,
    record the platform in the run identity and return it.  A number from any other
    backend must never be emitted under a device metric's name, so there
    is no fallback: without a TPU the run ends here."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU and JAX found none (platform "
            f"{platform!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}): run it where a TPU "
            "is attached — in this sandbox, through the chip tool"
        )
    from paddlebox_tpu.telemetry.compiles import install_compile_listener
    from paddlebox_tpu.telemetry.flight import set_run_backend

    install_compile_listener()
    set_run_backend(platform)
    _RUN_IDENTITY.clear()  # re-resolve with the live backend
    log(f"backend: {len(devs)} x {devs[0].device_kind} ({platform})")
    return platform


def start_deadline(seconds: float) -> None:
    """Global run watchdog: exit(4) if the whole bench exceeds ``seconds``
    (<= 0 disables it).  The rows emitted so far are already flushed, so
    an internal exit keeps them where an outer kill at the caller's own
    time limit may cut a line in half."""
    import threading

    if seconds <= 0:
        return
    t0 = time.monotonic()

    def boom():
        while True:
            left = seconds - (time.monotonic() - t0)
            if left <= 0:
                log(f"FATAL: bench exceeded --max-seconds={seconds:.0f}; "
                    "exiting (see emit() partial line)")
                os._exit(4)
            time.sleep(min(left, 10.0))

    threading.Thread(target=boom, daemon=True).start()


def make_model(name: str, n_slots: int, row_width: int, dense_dim: int,
               hidden) -> tuple:
    """(model, n_task_labels) for the benchmark model zoo (BASELINE.json
    configs 1-5)."""
    from paddlebox_tpu.models import MMoE, DCN, CtrDnn, DeepFM, WideDeep, XDeepFM

    if name == "ctr_dnn":
        return CtrDnn(n_slots, row_width, dense_dim=dense_dim, hidden=hidden), 0
    if name == "deepfm":
        return DeepFM(n_slots, row_width, dense_dim=dense_dim), 0
    if name == "widedeep":
        return WideDeep(n_slots, row_width, dense_dim=dense_dim), 0
    if name == "xdeepfm":
        return XDeepFM(n_slots, row_width, dense_dim=dense_dim), 0
    if name == "dcn":
        return DCN(n_slots, row_width, dense_dim=dense_dim), 0
    if name == "mmoe":
        return MMoE(n_slots, row_width, dense_dim=dense_dim, n_tasks=2), 1
    raise ValueError(f"unknown --model {name!r}")


def build_data(td: str, n_slots: int, dense_dim: int, batch_size: int,
               n_ins: int, vocab_per_slot: int, n_task_labels: int = 0):
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files

    conf = make_synth_config(
        n_sparse_slots=n_slots, dense_dim=dense_dim, batch_size=batch_size,
        max_feasigns_per_ins=64, batch_key_capacity=batch_size * n_slots * 4,
        n_task_labels=n_task_labels,
    )
    files = write_synth_files(
        td, n_files=4, ins_per_file=n_ins // 4, n_sparse_slots=n_slots,
        vocab_per_slot=vocab_per_slot, dense_dim=dense_dim, seed=7,
        n_task_labels=n_task_labels,
    )
    ds = PadBoxSlotDataset(conf, read_threads=4)
    ds.set_filelist(files)
    t0 = time.perf_counter()
    ds.load_into_memory()
    parse_s = time.perf_counter() - t0
    log(f"host parse: {n_ins} ins in {parse_s:.2f}s = {n_ins / parse_s:,.0f} lines/s")
    return conf, ds, parse_s


def bench_ours(ds, tconf, trconf, model, seed=0):
    """Full pipeline: host plan_batch + jitted fused step."""
    import jax

    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer, _device_batch

    table = SparseTable(tconf, seed=seed)
    table.begin_pass(ds.unique_keys())
    trainer = Trainer(model, tconf, trconf, seed=seed)
    step_fn = trainer._build_step()
    mstate = trainer._init_mstate()
    values, g2sum = table.values, table.g2sum
    params, opt_state = trainer.params, trainer.opt_state

    batches = list(ds.batches(drop_last=True))
    n_slots = batches[0].n_sparse_slots
    B = batches[0].batch_size

    # warmup / compile on the first batch.  AOT (lower + compile) instead
    # of first-call jit: the ONE compile also yields XLA's cost analysis
    # (FLOPs / bytes accessed) for the utilization fields.
    plan = table.plan_batch(batches[0])
    dev = _device_batch(batches[0], plan, n_slots)
    t0 = time.perf_counter()
    try:
        step_fn = step_fn.lower(
            params, opt_state, values, g2sum, mstate, dev).compile()
        cost = _cost_analysis(step_fn)
    except Exception as e:  # pragma: no cover - backend-dependent
        log(f"AOT compile path unavailable ({e!r}); plain jit, no cost "
            "analysis")
        cost = {}
    params, opt_state, values, g2sum, mstate, loss, _, _ = step_fn(
        params, opt_state, values, g2sum, mstate, dev)
    loss.block_until_ready()
    log(f"ours: compile+first step {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    n = 0
    for b in batches[1:]:
        plan = table.plan_batch(b)
        dev = _device_batch(b, plan, n_slots)
        params, opt_state, values, g2sum, mstate, loss, _, _ = step_fn(
            params, opt_state, values, g2sum, mstate, dev)
        n += B
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    table.values, table.g2sum = values, g2sum
    table.end_pass()
    sps = n / dt
    log(f"ours: {n} samples in {dt:.2f}s = {sps:,.0f} samples/s "
        f"({len(batches) - 1} steps, batch {B})")
    return sps, cost


def bench_trainer_path(ds, tconf, trconf, model, seed=0):
    """Production-path bench: Trainer.train_from_dataset with feed prefetch
    + multi-step scan dispatch (one warmup pass for compile, one timed)."""
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    table = SparseTable(tconf, seed=seed)
    trainer = Trainer(model, tconf, trconf, seed=seed)
    table.begin_pass(ds.unique_keys())
    t0 = time.perf_counter()
    trainer.train_from_dataset(ds, table, drop_last=True)
    log(f"trainer path: warmup/compile pass {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    m = trainer.train_from_dataset(ds, table, drop_last=True)
    dt = time.perf_counter() - t0
    table.end_pass()
    n = int(m["count"])
    sps = n / dt
    log(f"trainer path (prefetch={trconf.prefetch_batches} "
        f"scan={trconf.scan_steps}): {n} samples in {dt:.2f}s = "
        f"{sps:,.0f} samples/s")
    return sps


_DEVICE_PEAKS = {
    # device_kind substring -> (peak matmul FLOP/s, HBM bytes/s), public
    # TPU specs (bf16 MXU peak; an f32 tower runs below it, so mfu is a
    # conservative lower bound).  The reference never reports utilization —
    # its per-op timers (boxps_worker.cc:657-760, box_wrapper.h:375-391
    # pull/push/nccl timers) stop at milliseconds; this is the roofline
    # anchor (absolute utilization next to samples/s).
    "v5 lite": (197e12, 819e9),   # v5e
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6": (918e12, 1640e9),       # v6e (Trillium)
}


def _device_peaks():
    import jax

    try:
        kind = jax.devices()[0].device_kind.lower()
    # pbox-lint: ignore[swallowed-exception] capability probe: no backend
    # means no peaks, which the caller reports as "unknown device"
    except Exception:
        return None, None
    for k, peaks in _DEVICE_PEAKS.items():
        if k in kind:
            return peaks
    return None, None


def _cost_analysis(compiled) -> dict:
    """XLA's own post-optimization cost model for a compiled executable:
    {"flops": ..., "bytes accessed": ...} (empty when the backend exposes
    no analysis)."""
    if compiled is None:
        return {}
    try:
        ca = compiled.cost_analysis()
    # pbox-lint: ignore[swallowed-exception] capability probe: backends
    # without a cost model legitimately return an empty analysis
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def util_fields(cost: dict, sps: float, batch_size: int,
                steps_per_call: int = 1) -> dict:
    """Absolute utilization next to samples/s: per-step FLOPs and HBM bytes
    (XLA cost analysis of the real compiled step) and, when the device's
    peak specs are known, achieved MFU and HBM-bandwidth fraction.  At CTR
    model sizes the step is HBM/feed-bound — hbm_util is the number that
    says whether a samples/s figure is near the roofline."""
    out: dict = {}
    if not cost or sps <= 0:
        return out
    try:
        flops = float(cost.get("flops", 0) or 0) / steps_per_call
        byts = float(cost.get("bytes accessed", 0) or 0) / steps_per_call
    except (TypeError, ValueError):
        return out
    step_s = batch_size / sps
    if flops > 0:
        out["flops_per_step"] = int(flops)
        out["model_tflops_per_s"] = round(flops / step_s / 1e12, 4)
    if byts > 0:
        out["bytes_per_step"] = int(byts)
        out["model_gb_per_s"] = round(byts / step_s / 1e9, 2)
    peak_f, peak_b = _device_peaks()
    if peak_f and flops > 0:
        out["mfu"] = round(flops / step_s / peak_f, 5)
    if peak_b and byts > 0:
        out["hbm_util"] = round(byts / step_s / peak_b, 5)
    return out


def _ablation_times(trainer, model, tconf, params, opt_state, values, g2sum,
                    dev, n_it: int = 30):
    """(times_dict, live_state_tuple): ms per step for progressively larger
    step programs — the decomposition that tells WHICH op group (tower fwd,
    bwd+dense update, sparse push, AUC) owns a step-time regression.
    Mirrors Trainer._build_step's structure on the same feed; the live
    state tuple hands back usable (possibly updated) buffers because the
    push stage donates its inputs like the real step does.

    Scope: the PLAIN model contract only.  Models needing extra feed
    inputs (rank_offset/seq_pos/multi-task labels) or push extras
    (counter_label_tasks, slot LR map) would need the trainer's full feed
    matrix mirrored here — rather than silently measuring a DIFFERENT
    program for them, the ablation skips and says so."""
    import jax
    import jax.numpy as jnp
    import optax

    from paddlebox_tpu.models.layers import bce_with_logits
    from paddlebox_tpu.sparse.table import pull_rows, push_and_update

    state = (params, opt_state, values, g2sum)
    if (
        getattr(model, "uses_rank_offset", False)
        or getattr(model, "uses_seq_pos", False)
        or getattr(model, "n_tasks", 1) > 1
        or trainer.conf.counter_label_tasks
        or tconf.slot_learning_rates
        or trainer.slot_mask is not None
    ):
        log("ablation skipped: model/config needs extra feed or push "
            "inputs the ablated programs do not mirror")
        return {}, state

    optimizer = trainer.optimizer
    bsz = dev["labels"].shape[0]

    def fwd(params, values, batch):
        rows = pull_rows(values, batch["idx"],
                         create_threshold=tconf.create_threshold,
                         cvm_offset=tconf.cvm_offset,
                         pull_embedx_scale=tconf.pull_embedx_scale)
        logits = model.apply(params, rows, batch["key_segments"],
                             batch["dense"], bsz)
        per_ins = bce_with_logits(logits, batch["labels"]) * batch["ins_mask"]
        return per_ins.sum() / jnp.maximum(batch["ins_mask"].sum(), 1.0)

    def fwd_only(params, opt_state, values, g2sum, batch):
        return fwd(params, values, batch)

    def with_bwd(params, opt_state, values, g2sum, batch):
        def loss_fn(p):  # grad wrt params only: a (0, 1) argnums would
            # declare a full-table cotangent that belongs to the push bucket
            return fwd(p, values, batch)

        loss, pg = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(pg, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def make_with_push(unique_indices):
        def with_push(params, opt_state, values, g2sum, batch):
            # mirrors Trainer._build_step: pull outside the grad, rows as a
            # differentiated argument, ONE backward for both cotangents
            rows = pull_rows(values, batch["idx"],
                             create_threshold=tconf.create_threshold,
                             cvm_offset=tconf.cvm_offset,
                             pull_embedx_scale=tconf.pull_embedx_scale)

            def loss_fn(p, r):
                logits = model.apply(p, r, batch["key_segments"],
                                     batch["dense"], bsz)
                per_ins = bce_with_logits(logits, batch["labels"]) \
                    * batch["ins_mask"]
                return per_ins.sum() / jnp.maximum(batch["ins_mask"].sum(), 1.0)

            loss, (pg, row_grads) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(params, rows)
            updates, opt_state = optimizer.update(pg, opt_state, params)
            params = optax.apply_updates(params, updates)
            v2, g2 = push_and_update(
                values, g2sum, row_grads, batch["idx"], batch["uniq_idx"],
                batch["inverse"], batch["key_mask"], batch["key_clicks"], tconf,
                unique_indices=unique_indices,
            )
            return params, opt_state, v2, g2, loss
        return with_push

    out = {}
    # donate like the real step does (its scatter updates the table
    # in place; without donation XLA copies the whole table per push and
    # the ablation overstates the push cost).  Each donated stage runs on
    # SNAPSHOT copies, so a mid-stage device error (async — it surfaces at
    # block_until_ready, after rebinding) can only poison the copies: the
    # caller always gets back the pristine pre-ablation state.
    # plus_push_dup is the SAME push without the unique_indices claim —
    # the A/B that quantifies the duplicate-safe scatter lowering's cost
    # on real hardware (the r4 step-regression hypothesis)
    stages = [("fwd", fwd_only, ()),
              ("fwd_bwd_dense", with_bwd, (0, 1)),
              ("plus_push", make_with_push(True), (0, 1, 2, 3)),
              ("plus_push_dup", make_with_push(False), (0, 1, 2, 3))]
    for name, fn, donate in stages:
        # pbox-lint: ignore[jit-retrace-hazard] ablation harness: each
        # stage jits its own distinct fn ONCE, then times many cached
        # dispatches of it — the wrap is per stage, not per step
        jf = jax.jit(fn, donate_argnums=donate)
        # snapshot ONLY the donated leaves (copying the whole table for the
        # dense-only stage would transiently double table memory)
        p, o = (jax.tree.map(jnp.array, (params, opt_state))
                if donate else (params, opt_state))
        v, g = ((jnp.array(values), jnp.array(g2sum))
                if 2 in donate else (values, g2sum))
        try:
            def rebind(res):
                # rebind whatever this stage donated so the next loop
                # iteration never re-passes a consumed buffer
                nonlocal p, o, v, g
                if donate == (0, 1):
                    p, o = res[0], res[1]
                elif donate == (0, 1, 2, 3):
                    p, o, v, g = res[0], res[1], res[2], res[3]
                return res

            res = rebind(jf(p, o, v, g, dev))
            jax.block_until_ready(res)
            t0 = time.perf_counter()
            for _ in range(n_it):
                res = rebind(jf(p, o, v, g, dev))
            jax.block_until_ready(res)
            out[name] = (time.perf_counter() - t0) / n_it * 1e3
        except Exception as e:
            log(f"ablation {name} failed: {e!r}")
            out[name] = float("nan")
    return ({k: round(v, 2) for k, v in out.items()},
            (params, opt_state, values, g2sum))


def device_profile(ds, tconf, trconf, model, scan_k: int = 8, seed=0):
    """Pin down WHERE per-step time goes on the real chip: device-step-only
    (feed reused, no host work), H2D-only, scan-group-only (stacked feed
    reused), then the composed async loop.  Each number isolates one stage
    of the pipeline; disagreement between their sum and the composed loop
    exposes serialization (the r4 diagnosis tool for the trainer-path
    regression)."""
    import dataclasses

    import jax
    import numpy as np

    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer, _host_batch_dict, _to_device

    table = SparseTable(tconf, seed=seed)
    table.begin_pass(ds.unique_keys())
    trainer = Trainer(model, tconf, trconf, seed=seed)
    trainer._step_fn = trainer._build_step()
    mstate = trainer._init_mstate()
    values, g2sum = table.values, table.g2sum
    params, opt_state = trainer.params, trainer.opt_state
    log(f"table rows: {values.shape}")

    batches = list(ds.batches(drop_last=True))
    n_slots = batches[0].n_sparse_slots
    B = batches[0].batch_size

    hosts = []
    t0 = time.perf_counter()
    for b in batches:
        plan = table.plan_batch(b)
        hosts.append(_host_batch_dict(b, plan, n_slots))
    host_ms = (time.perf_counter() - t0) / len(batches) * 1e3
    log(f"host plan+assemble: {host_ms:.2f} ms/batch")

    feed_mb = sum(np.asarray(v).nbytes for v in hosts[0].values()) / 1e6
    dev = _to_device(hosts[0])
    jax.block_until_ready(dev)
    t0 = time.perf_counter()
    for h in hosts[:10]:
        jax.block_until_ready(_to_device(h))
    h2d_ms = (time.perf_counter() - t0) / 10 * 1e3
    log(f"H2D: {feed_mb:.2f} MB/feed, {h2d_ms:.2f} ms/feed")

    # dispatch overhead: how much a single no-op device call costs, async
    # (pipelined, what the plain loop pays per step) and sync (adds the
    # round trip — what any per-step host readback would pay).  The scan
    # path exists to amortize exactly this; these two numbers say whether
    # it still needs to on the day's backend.
    import jax.numpy as jnp

    tiny = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8, jnp.float32)
    x = tiny(x)
    x.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(100):
        x = tiny(x)
    x.block_until_ready()
    dispatch_ms = (time.perf_counter() - t0) / 100 * 1e3
    t0 = time.perf_counter()
    for _ in range(20):
        tiny(x).block_until_ready()
    dispatch_sync_ms = (time.perf_counter() - t0) / 20 * 1e3
    log(f"dispatch: {dispatch_ms:.3f} ms async, {dispatch_sync_ms:.3f} ms "
        "sync")

    # device step alone: same feed, state carried, block only at the end
    out = trainer._step_fn(params, opt_state, values, g2sum, mstate, dev)
    jax.block_until_ready(out[5])
    params, opt_state, values, g2sum, mstate = out[:5]
    n_it = 30
    t0 = time.perf_counter()
    for _ in range(n_it):
        params, opt_state, values, g2sum, mstate, loss, _, _ = trainer._step_fn(
            params, opt_state, values, g2sum, mstate, dev)
    loss.block_until_ready()
    step_ms = (time.perf_counter() - t0) / n_it * 1e3
    log(f"device step only: {step_ms:.2f} ms -> {B / step_ms * 1e3:,.0f} samples/s")

    # ablated steps: where inside the step does the time go?  fwd -> +bwd
    # and dense update -> +sparse push -> (full, incl. AUC, above)
    ablate, (params, opt_state, values, g2sum) = _ablation_times(
        trainer, model, tconf, params, opt_state, values, g2sum, dev)
    for name, ms in ablate.items():
        log(f"ablation {name}: {ms:.2f} ms")

    # transfer/compute overlap: dispatch a step WITHOUT blocking, then time
    # a feed transfer issued while it runs.  Overlap -> ~h2d_ms; a
    # backend that serializes transfers behind compute -> ~step + h2d,
    # which voids the prefetcher's premise (ROADMAP S2: prefetch+scan once
    # measured 3x slower than the plain loop on chip while equal on CPU).
    during = []
    for i in range(5):  # averaged: a single race would be noise, and this
        # number is the serialization verdict
        out = trainer._step_fn(params, opt_state, values, g2sum, mstate, dev)
        t0 = time.perf_counter()
        jax.block_until_ready(_to_device(hosts[(i + 1) % len(hosts)]))
        during.append((time.perf_counter() - t0) * 1e3)
        params, opt_state, values, g2sum, mstate = out[:5]
        jax.block_until_ready(out[5])
    h2d_during_ms = sum(during) / len(during)
    log(f"H2D during a running step: {h2d_during_ms:.2f} ms "
        f"(idle: {h2d_ms:.2f} ms; >> idle means transfers serialize "
        "with compute)")

    # scan group alone: stacked feed reused
    scan_ms = None
    h2d_stacked_ms = None
    if scan_k > 1:
        scan_k = min(scan_k, len(hosts))  # ticks actually stacked
        trainer.conf = dataclasses.replace(trainer.conf, scan_steps=scan_k)
        scan_fn = trainer._build_scan_step()
        # the pass's LAST plans: the table's unique-slot bucket has settled
        # there (an early batch may have moved it; two lengths do not stack)
        stacked_host = {
            k: np.stack([h[k] for h in hosts[-scan_k:]]) for k in hosts[0]
        }
        stacked = _to_device(stacked_host)
        jax.block_until_ready(stacked)
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(_to_device(stacked_host))
        h2d_stacked_ms = (time.perf_counter() - t0) / 5 * 1e3
        log(f"H2D stacked [{scan_k}, ...] feed: {h2d_stacked_ms:.2f} ms "
            f"({h2d_stacked_ms / scan_k:.2f} ms/tick)")
        t0 = time.perf_counter()
        out = scan_fn(params, opt_state, values, g2sum, mstate, stacked)
        jax.block_until_ready(out[5])
        log(f"scan compile+first group: {time.perf_counter() - t0:.1f}s")
        params, opt_state, values, g2sum, mstate = out[:5]
        n_g = 5
        t0 = time.perf_counter()
        for _ in range(n_g):
            (params, opt_state, values, g2sum, mstate, loss_k, _) = scan_fn(
                params, opt_state, values, g2sum, mstate, stacked)
        jax.block_until_ready(loss_k)
        scan_ms = (time.perf_counter() - t0) / n_g / scan_k * 1e3
        log(f"scan group ({scan_k} ticks): {scan_ms:.2f} ms/tick -> "
            f"{B / scan_ms * 1e3:,.0f} samples/s")

    table.values, table.g2sum = values, g2sum
    table.end_pass()
    return {"host_ms": round(host_ms, 2), "h2d_ms": round(h2d_ms, 2),
            "h2d_during_step_ms": round(h2d_during_ms, 2),
            "h2d_stacked_ms": (
                None if h2d_stacked_ms is None else round(h2d_stacked_ms, 2)
            ),
            "dispatch_ms": round(dispatch_ms, 3),
            "dispatch_sync_ms": round(dispatch_sync_ms, 3),
            "step_ms": round(step_ms, 2),
            "scan_tick_ms": None if scan_ms is None else round(scan_ms, 2),
            "feed_mb": round(feed_mb, 2),
            "ablation": {
                k: (None if not np.isfinite(v) else v)
                for k, v in ablate.items()
            }}


def bench_naive(ds, tconf, trconf, model_hidden, seed=0):
    """Naive JAX port: embedding rows gathered per occurrence with NO dedup,
    per-slot masked mean... pooling via S separate masked segment matmuls,
    scatter-add per occurrence (duplicate keys collide serially), full-table
    adagrad state read-modify-write.  This is what translating
    pull_box_sparse/sequence_pool op-by-op yields."""
    import jax
    import jax.numpy as jnp
    import optax

    from paddlebox_tpu.models.layers import bce_with_logits, init_mlp, mlp
    from paddlebox_tpu.sparse.table import SparseTable

    table = SparseTable(tconf, seed=seed)
    table.begin_pass(ds.unique_keys())
    values, g2sum = table.values, table.g2sum

    batches = list(ds.batches(drop_last=True))
    n_slots = batches[0].n_sparse_slots
    B = batches[0].batch_size
    W = tconf.row_width
    in_dim = n_slots * W + batches[0].dense.shape[1]
    params = init_mlp(jax.random.PRNGKey(seed), in_dim, model_hidden, 1)
    optimizer = optax.adam(trconf.dense_lr)
    opt_state = optimizer.init(params)

    def step(params, opt_state, values, g2sum, batch):
        rows = jnp.take(values, batch["idx"], axis=0)  # [K, W] no dedup

        def loss_fn(p, r):
            # naive per-slot pooling: S one-hot matmuls instead of one
            # segment_sum over a fused segment index
            pooled = []
            seg = batch["key_segments"]
            for s in range(n_slots):
                sel = ((seg % n_slots) == s) & (seg < B * n_slots)
                onehot = (
                    (seg // n_slots)[:, None] == jnp.arange(B)[None, :]
                ) & sel[:, None]
                pooled.append(onehot.astype(r.dtype).T @ r)  # [B, W]
            x = jnp.concatenate(pooled + [batch["dense"]], axis=1)
            logits = mlp(p, x)[:, 0]
            per_ins = bce_with_logits(logits, batch["labels"]) * batch["ins_mask"]
            return per_ins.sum() / jnp.maximum(batch["ins_mask"].sum(), 1.0)

        loss, (pgrads, row_grads) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, rows)
        updates, opt_state = optimizer.update(pgrads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # per-occurrence scatter-add, then full-table dense adagrad
        grad_tab = jnp.zeros_like(values).at[batch["idx"]].add(row_grads)
        g2 = g2sum + (grad_tab[:, 2:] ** 2).mean(axis=1)
        scale = tconf.learning_rate / (jnp.sqrt(g2 + tconf.initial_g2sum))
        values = values - grad_tab * scale[:, None]
        return params, opt_state, values, g2, loss

    step = jax.jit(step, donate_argnums=(0, 1, 2, 3))

    def feed(b):
        plan = table.plan_batch(b)
        return {
            "idx": jnp.asarray(plan.idx),
            "key_segments": jnp.asarray(b.key_segments),
            "dense": jnp.asarray(b.dense),
            "labels": jnp.asarray(b.labels),
            "ins_mask": jnp.asarray(b.ins_mask),
        }

    t0 = time.perf_counter()
    params, opt_state, values, g2sum, loss = step(
        params, opt_state, values, g2sum, feed(batches[0]))
    loss.block_until_ready()
    log(f"naive: compile+first step {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    n = 0
    for b in batches[1:]:
        params, opt_state, values, g2sum, loss = step(
            params, opt_state, values, g2sum, feed(b))
        n += B
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    table.values, table.g2sum = values, g2sum
    table.end_pass()
    sps = n / dt
    log(f"naive: {n} samples in {dt:.2f}s = {sps:,.0f} samples/s")
    return sps


def bench_sustained(n_passes: int, tconf, trconf, n_slots: int, dense_dim: int,
                    batch_size: int, ins_per_pass: int, hidden, profile: bool,
                    vocab_per_slot: int = 100_000):
    """Sustained multi-pass throughput: pass p trains while pass p+1's files
    parse in the background (the production day-loop shape,
    examples/train_ctr_dnn.py).  This is the number that stresses the host
    pipeline — the per-pass steady-state bench hides parse cost entirely.
    Reports sustained samples/sec over the whole day (excluding only the
    first pass's un-overlappable parse + the compile) and, with profile,
    the profiler's per-stage report (plan/feed/step/complete) of the final
    pass."""
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    conf = make_synth_config(
        n_sparse_slots=n_slots, dense_dim=dense_dim, batch_size=batch_size,
        max_feasigns_per_ins=64,
        batch_key_capacity=batch_size * n_slots * 4,
    )
    model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense_dim, hidden=hidden)
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, trconf, seed=0)

    with tempfile.TemporaryDirectory() as td:
        def files_for(p):
            return write_synth_files(
                os.path.join(td, f"p{p}"), n_files=4,
                ins_per_file=ins_per_pass // 4, n_sparse_slots=n_slots,
                vocab_per_slot=vocab_per_slot, dense_dim=dense_dim,
                seed=7 + p,
            )

        all_files = [files_for(p) for p in range(n_passes)]
        ds = PadBoxSlotDataset(conf, read_threads=4)
        ds.set_filelist(all_files[0])
        ds.preload_into_memory()
        total = 0
        prev_count = 0
        t_start = None  # starts after pass 0's parse (un-overlappable)
        auc_state = None
        for p in range(n_passes):
            # overlapped tables: pass p's census resolve + init + staging
            # already ran on the table's background thread during pass
            # p-1's tail (the next_pass_keys hook below), and its callable
            # consumed the preload — read the census back instead of
            # re-waiting.  Serial tables stage nothing and wait here.
            staged = (
                table.staged_pass_keys()
                if hasattr(table, "staged_pass_keys") and p else None
            )
            if staged is None:
                ds.wait_preload_done()
                keys = ds.unique_keys()
            else:
                keys = staged
            if t_start is None:
                t_start = time.perf_counter()
            table.begin_pass(keys)
            nxt = None
            if p + 1 < n_passes:
                ds.set_filelist(all_files[p + 1])
                ds.preload_into_memory()
                # evaluated on the staging thread: blocks there (not on
                # the train loop) until the next pass's parse lands
                nxt = lambda: (ds.wait_preload_done(), ds.unique_keys())[1]
            metrics = trainer.train_from_dataset(
                ds, table, auc_state=auc_state, next_pass_keys=nxt)
            auc_state = trainer.last_metric_state
            table.end_pass()
            # metrics["count"] is CUMULATIVE across passes (the carried AUC
            # state keeps counting), so the latest value IS the running
            # total; accumulate the per-pass delta so a future auc_state
            # reset can't silently shrink the denominator
            total += int(metrics["count"]) - prev_count
            prev_count = int(metrics["count"])
            log(f"pass {p}: loss={metrics['loss']:.4f} auc={metrics['auc']:.4f} "
                f"count={metrics['count']:.0f}")
        dt = time.perf_counter() - t_start
        ds.close()
    # the first pass pays compile (~5s): report both raw and compile-adjusted
    sps = total / dt
    log(f"sustained: {total} samples / {n_passes} passes in {dt:.2f}s "
        f"= {sps:,.0f} samples/s (incl. compile in pass 0)")
    if profile:
        # one more pass with the per-stage report on (the same loop: the
        # report is the pass's delta of the always-on stage histograms)
        trainer.conf.profile = True
        files = files_for(n_passes)
        ds = PadBoxSlotDataset(conf, read_threads=4)
        ds.set_filelist(files)
        ds.load_into_memory()
        table.begin_pass(ds.unique_keys())
        trainer.train_from_dataset(ds, table, auc_state=auc_state)
        table.end_pass()
        ds.close()
    return sps


def bench_pass_boundary(n_passes: int, tconf0, trconf, n_slots: int,
                        dense: int, bsz: int, ins_per_pass: int, hidden,
                        vocab_per_slot: int = 100_000) -> dict:
    """Serial-vs-overlapped pass-lifecycle ablation: the SAME passes driven
    through the serial escape hatch (overlap_pass_boundary=False) and the
    overlapped pipeline (async end-pass write-back + next-pass
    pre-promotion via the trainer's next_pass_keys hook), measuring the
    inter-pass device-idle gap — end_pass call through the next
    begin_pass return — plus whole-run samples/s, and checking the two
    final stores are bit-exact.  All pass data is pre-loaded so the gap
    isolates the boundary cost, not parsing."""
    import dataclasses

    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    conf = make_synth_config(
        n_sparse_slots=n_slots, dense_dim=dense, batch_size=bsz,
        max_feasigns_per_ins=64,
        batch_key_capacity=bsz * n_slots * 4,
    )
    res: dict = {}
    states = {}
    with tempfile.TemporaryDirectory() as td:
        datasets = []
        for p in range(n_passes):
            files = write_synth_files(
                os.path.join(td, f"p{p}"), n_files=2,
                ins_per_file=ins_per_pass // 2, n_sparse_slots=n_slots,
                vocab_per_slot=vocab_per_slot, dense_dim=dense, seed=31 + p,
            )
            ds = PadBoxSlotDataset(conf, read_threads=2)
            ds.set_filelist(files)
            ds.load_into_memory()
            datasets.append(ds)
        try:
            for mode in ("serial", "overlapped"):
                tconf = dataclasses.replace(
                    tconf0, overlap_pass_boundary=(mode == "overlapped"))
                model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                               hidden=hidden)
                table = SparseTable(tconf, seed=0)
                trainer = Trainer(model, tconf, trconf, seed=0)
                gaps = []
                auc_state = None
                total = prev_count = 0
                prev_end_s = None
                t_all = time.perf_counter()
                for p, ds in enumerate(datasets):
                    t0 = time.perf_counter()
                    table.begin_pass(ds.unique_keys())
                    if prev_end_s is not None:
                        gaps.append(prev_end_s + time.perf_counter() - t0)
                    nxt = (
                        datasets[p + 1].unique_keys
                        if p + 1 < n_passes else None
                    )
                    m = trainer.train_from_dataset(
                        ds, table, auc_state=auc_state, drop_last=True,
                        next_pass_keys=nxt,
                    )
                    auc_state = trainer.last_metric_state
                    t0 = time.perf_counter()
                    table.end_pass()
                    prev_end_s = time.perf_counter() - t0
                    total += int(m["count"]) - prev_count
                    prev_count = int(m["count"])
                table.flush()
                dt = time.perf_counter() - t_all
                states[mode] = table.state_dict()
                gap_ms = sum(gaps) / max(len(gaps), 1) * 1e3
                res[f"{mode}_gap_ms"] = round(gap_ms, 2)
                res[f"{mode}_samples_per_sec"] = round(total / dt, 1)
                res[f"{mode}_auc"] = round(float(m["auc"]), 6)
                log(f"pass-boundary {mode}: mean inter-pass gap "
                    f"{gap_ms:.1f} ms, {total / dt:,.0f} samples/s "
                    f"(incl. compile pass 0)")
        finally:
            for ds in datasets:
                ds.close()
    res["bitexact"] = bool(
        np.array_equal(states["serial"]["keys"], states["overlapped"]["keys"])
        and np.array_equal(states["serial"]["values"],
                           states["overlapped"]["values"])
    )
    if res["serial_gap_ms"] > 0:
        res["gap_speedup"] = round(
            res["serial_gap_ms"] / max(res["overlapped_gap_ms"], 1e-6), 2)
    log(f"pass-boundary: bitexact={res['bitexact']} "
        f"gap {res['serial_gap_ms']}ms -> {res['overlapped_gap_ms']}ms")
    return res


def stage_pass_boundary(backend, args, tconf, trconf, n_slots, dense, bsz,
                        n_ins, hidden) -> None:
    res = bench_pass_boundary(
        4, tconf, trconf, n_slots, dense, bsz, max(n_ins // 2, 4 * bsz),
        hidden, vocab_per_slot=args.vocab,
    )
    emit({"metric": "pass_boundary_gap_ms",
          "value": res.get("overlapped_gap_ms"), "unit": "ms",
          "vs_baseline": None, "backend": backend, **res})


def bench_hbm_cache(n_passes: int, tconf0, trconf, n_slots: int, dense: int,
                    bsz: int, ins_per_pass: int, hidden,
                    vocab_per_slot: int = 4000, zipf_a: float = 1.3) -> dict:
    """HBM-cache ablation (ISSUE 6 acceptance): the SAME skewed key stream
    (Zipf-drawn ids — real CTR traffic's hot head) driven uncached
    (hbm_cache_rows=0, every pass round-trips its full working set through
    the host store) and cached (device-resident hot tier), measuring the
    per-pass PROMOTION PATCH — rows the host must supply at begin_pass —
    plus hit rate, inter-pass gap, samples/s and host-tier pressure
    (BucketStore.stats spilled_buckets/resident_rows), and checking the
    final stores bit-exact.  The row and byte counts are what a CPU run
    of this function (tier-1 imports it) can stand behind; its times
    cannot."""
    import dataclasses

    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    conf = make_synth_config(
        n_sparse_slots=n_slots, dense_dim=dense, batch_size=bsz,
        max_feasigns_per_ins=64,
        batch_key_capacity=bsz * n_slots * 4,
    )
    res: dict = {}
    states = {}
    with tempfile.TemporaryDirectory() as td:
        datasets = []
        for p in range(n_passes):
            files = write_synth_files(
                os.path.join(td, f"p{p}"), n_files=2,
                ins_per_file=ins_per_pass // 2, n_sparse_slots=n_slots,
                vocab_per_slot=vocab_per_slot, dense_dim=dense, seed=57 + p,
                zipf_a=zipf_a,
            )
            ds = PadBoxSlotDataset(conf, read_threads=2)
            ds.set_filelist(files)
            ds.load_into_memory()
            datasets.append(ds)
        try:
            for mode in ("uncached", "cached"):
                tconf = dataclasses.replace(
                    tconf0,
                    hbm_cache_rows=(
                        tconf0.hbm_cache_rows if mode == "cached" else 0
                    ),
                )
                model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                               hidden=hidden)
                table = SparseTable(tconf, seed=0)
                trainer = Trainer(model, tconf, trconf, seed=0)
                gaps, patch_rows, census_rows, hit_rates = [], [], [], []
                auc_state = None
                total = prev_count = 0
                prev_end_s = None
                t_all = time.perf_counter()
                for p, ds in enumerate(datasets):
                    t0 = time.perf_counter()
                    table.begin_pass(ds.unique_keys())
                    if prev_end_s is not None:
                        gaps.append(prev_end_s + time.perf_counter() - t0)
                    n_census = table._pass_keys.shape[0]
                    census_rows.append(n_census)
                    if mode == "cached":
                        patch_rows.append(table.last_cache_misses)
                        hit_rates.append(
                            table.last_cache_hits / max(n_census, 1)
                        )
                    else:  # no cache: the host supplies the full census
                        patch_rows.append(n_census)
                    nxt = (
                        datasets[p + 1].unique_keys
                        if p + 1 < n_passes else None
                    )
                    m = trainer.train_from_dataset(
                        ds, table, auc_state=auc_state, drop_last=True,
                        next_pass_keys=nxt,
                    )
                    auc_state = trainer.last_metric_state
                    t0 = time.perf_counter()
                    table.end_pass()
                    prev_end_s = time.perf_counter() - t0
                    total += int(m["count"]) - prev_count
                    prev_count = int(m["count"])
                table.flush()
                dt = time.perf_counter() - t_all
                states[mode] = table.state_dict()
                st = table._store.stats()
                res[f"{mode}_gap_ms"] = round(
                    sum(gaps) / max(len(gaps), 1) * 1e3, 2)
                res[f"{mode}_samples_per_sec"] = round(total / dt, 1)
                # steady-state promotion patch: skip pass 0 (all-miss warmup)
                res[f"{mode}_promotion_patch_rows"] = round(
                    sum(patch_rows[1:]) / max(len(patch_rows) - 1, 1), 1)
                res[f"{mode}_census_rows"] = round(
                    sum(census_rows[1:]) / max(len(census_rows) - 1, 1), 1)
                res[f"{mode}_spilled_buckets"] = st["spilled_buckets"]
                res[f"{mode}_store_resident_rows"] = st["resident_rows"]
                if mode == "cached":
                    res["cached_hit_rate"] = round(
                        sum(hit_rates[1:]) / max(len(hit_rates) - 1, 1), 4)
                log(f"hbm-cache {mode}: promotion patch "
                    f"{res[f'{mode}_promotion_patch_rows']:.0f} rows/pass "
                    f"(census {res[f'{mode}_census_rows']:.0f}), gap "
                    f"{res[f'{mode}_gap_ms']:.1f} ms, "
                    f"{total / dt:,.0f} samples/s")
        finally:
            for ds in datasets:
                ds.close()
    res["bitexact"] = bool(
        np.array_equal(states["uncached"]["keys"], states["cached"]["keys"])
        and np.array_equal(states["uncached"]["values"],
                           states["cached"]["values"])
    )
    if res["cached_promotion_patch_rows"] > 0:
        res["patch_shrink"] = round(
            res["uncached_promotion_patch_rows"]
            / res["cached_promotion_patch_rows"], 2)
    log(f"hbm-cache: bitexact={res['bitexact']} hit_rate="
        f"{res.get('cached_hit_rate')} patch "
        f"{res['uncached_promotion_patch_rows']:.0f} -> "
        f"{res['cached_promotion_patch_rows']:.0f} rows/pass")
    return res


def stage_hbm_cache(backend, args, tconf, trconf, n_slots, dense, bsz,
                    n_ins, hidden) -> None:
    res = bench_hbm_cache(
        4, tconf, trconf, n_slots, dense, bsz, max(n_ins // 2, 4 * bsz),
        hidden, vocab_per_slot=max(args.vocab // 25, 200),
    )
    emit({"metric": "hbm_cache_promotion_patch_rows",
          "value": res.get("cached_promotion_patch_rows"), "unit": "rows",
          "vs_baseline": res.get("uncached_promotion_patch_rows"),
          "backend": backend, **res})


def _rank(q: float, n: int) -> int:
    """Nearest-rank percentile index into a sorted length-n list
    (``int(n * q)`` would return the sample MAX for n <= 100 at q=0.99)."""
    import math

    return max(0, min(n - 1, math.ceil(q * n) - 1))


def _hostplane_census_arm(n_ranks, n_passes, censuses, placement, codec,
                          hot_capacity, cache_rows) -> dict:
    """One census-wire ablation arm over a simulated n-rank fleet
    (threads + InProcessCensusGroup — real multi-process JAX collectives
    can't run on the CPU backend; the wire logic is rank-identical).
    Returns bytes/pass, gather latencies and the agreed census sizes."""
    import threading

    from paddlebox_tpu.parallel.census import (
        CensusExchange, FleetCacheMirror, InProcessCensusGroup,
    )
    from paddlebox_tpu.sparse.placement import PlacementPlanner

    group = InProcessCensusGroup(n_ranks)
    out = {r: None for r in range(n_ranks)}
    gather_s: list = []

    def rank_fn(r):
        planner = mirror = None
        if placement == "hybrid":
            planner = PlacementPlanner(
                hot_capacity=hot_capacity, update_interval=1
            )
            if cache_rows:
                mirror = FleetCacheMirror(n_ranks, cache_rows, 0.8)
        ex = CensusExchange(group.transport(r), planner=planner,
                            mirror=mirror, codec=codec)
        pks, wire, raw = [], [], []
        for p in range(n_passes):
            t0 = time.perf_counter()
            pk = ex.exchange(censuses[p][r])
            if r == 0:
                gather_s.append(time.perf_counter() - t0)
            pks.append(pk)
            wire.append(ex.last_wire_bytes)
            raw.append(ex.last_raw_bytes)
        out[r] = (pks, wire, raw)

    threads = [
        threading.Thread(target=rank_fn, args=(r,), daemon=True)
        for r in range(n_ranks)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # fleet agreement is the correctness floor of the whole arm
    for p in range(n_passes):
        for r in range(1, n_ranks):
            assert np.array_equal(out[0][0][p], out[r][0][p]), (
                f"census divergence at pass {p} rank {r}"
            )
    # steady state: skip pass 0 (dictionary is empty, everything is cold)
    tail = range(1, n_passes)
    bytes_pp = [sum(out[r][1][p] for r in range(n_ranks)) for p in tail]
    raw_pp = [sum(out[r][2][p] for r in range(n_ranks)) for p in tail]
    lat = sorted(gather_s[1:])
    return {
        "bytes_per_pass": round(sum(bytes_pp) / max(len(bytes_pp), 1), 1),
        "raw_bytes_per_pass": round(sum(raw_pp) / max(len(raw_pp), 1), 1),
        "gather_p50_ms": round(lat[_rank(0.5, len(lat))] * 1e3, 3),
        "gather_p99_ms": round(lat[_rank(0.99, len(lat))] * 1e3, 3),
        "census_rows": int(out[0][0][-1].shape[0]),
    }


def bench_hostplane(n_passes: int, tconf0, trconf, n_slots: int, dense: int,
                    bsz: int, ins_per_pass: int, hidden,
                    vocab_per_slot: int = 4000, zipf_a: float = 1.3,
                    n_ranks: int = 2) -> dict:
    """Host-plane hybrid-parallelism ablation (ISSUE 15 acceptance).

    Three measurements off the same Zipf-skewed key universe (real CTR
    traffic's hot head):

      1. census wire bytes/pass over a simulated ``n_ranks`` fleet, in
         three arms — ``hash_raw`` (the legacy O(working set) baseline),
         ``hash_varint`` (codec only) and ``planned_varint`` (placement
         planner + fleet cache mirrors: dictionary keys ride as BITS, only
         the cold tail ships as varint deltas) — plus gather p50/p99;
      2. shuffle wire: one routed RecordBlock serialized legacy vs varint
         (the key-column compression TcpShuffler ships);
      3. the trained-arm ablation: the SAME dataset through the
         MultiChipTrainer in three arms — placement off (``hash``),
         wire-plane dictionary only (``wire`` — census encode->decode in
         begin_pass, ``placement_realize=False``) and the realized hybrid
         layout (``hybrid`` — replicated-hot device block, cold tail
         sharded).  Per arm: begin/end-pass host row bytes, hot-tier
         migration bytes, boundary gap and samples/s; final stores
         compared key-for-key, float-for-float across all three (the
         realized hot path must stay bit-exact, not just the wire).

    CPU-admissible by construction (ROADMAP bench caveat): no device
    collective runs; the host plane is the thing being measured.
    """
    import dataclasses

    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.parallel import (
        MultiChipTrainer, ShardedSparseTable, make_mesh,
    )

    res: dict = {}
    rng = np.random.default_rng(17)
    # per-pass, per-rank local censuses: a shared Zipf-hot head every rank
    # sees every pass + a cold uniform tail per rank per pass
    censuses = []
    for p in range(max(n_passes, 4)):
        per_rank = []
        for r in range(n_ranks):
            draws = rng.zipf(zipf_a, ins_per_pass * 4).astype(np.uint64)
            hot = draws % np.uint64(vocab_per_slot)
            cold = rng.integers(
                vocab_per_slot, vocab_per_slot * 8,
                ins_per_pass // 4, dtype=np.uint64,
            )
            per_rank.append(np.unique(np.concatenate([hot, cold])))
        censuses.append(per_rank)
    n_census_passes = len(censuses)
    cache_rows = max(tconf0.hbm_cache_rows // (n_ranks * 8), 1024)
    for arm, placement, codec in (
        ("hash_raw", "hash", "raw"),
        ("hash_varint", "hash", "varint"),
        ("planned_varint", "hybrid", "varint"),
    ):
        a = _hostplane_census_arm(
            n_ranks, n_census_passes, censuses, placement, codec,
            hot_capacity=tconf0.placement_hot_capacity,
            cache_rows=cache_rows,
        )
        for k, v in a.items():
            res[f"{arm}_{k}"] = v
        log(f"hostplane census {arm}: {a['bytes_per_pass']:.0f} B/pass "
            f"(raw equivalent {a['raw_bytes_per_pass']:.0f}), gather p50 "
            f"{a['gather_p50_ms']:.2f} ms p99 {a['gather_p99_ms']:.2f} ms")
    res["census_compression_x"] = round(
        res["hash_raw_bytes_per_pass"]
        / max(res["hash_varint_bytes_per_pass"], 1), 2)
    res["census_collapse_x"] = round(
        res["hash_raw_bytes_per_pass"]
        / max(res["planned_varint_bytes_per_pass"], 1), 2)

    # shuffle-wire key-column compression on one routed block
    from paddlebox_tpu.data import archive
    from paddlebox_tpu.data.record import RecordBlock

    n_keys = ins_per_pass * 4
    keys = (rng.zipf(zipf_a, n_keys) % vocab_per_slot).astype(np.uint64)
    blk = RecordBlock(
        n_ins=ins_per_pass, n_sparse_slots=n_slots, keys=keys,
        key_offsets=np.linspace(0, n_keys, ins_per_pass * n_slots + 1
                                ).astype(np.int64),
        dense=np.zeros((ins_per_pass, dense), np.float32),
        labels=np.zeros(ins_per_pass, np.float32),
    )
    _, raw_kb, _ = archive.block_to_wire(blk, "legacy")
    _, _, wire_kb = archive.block_to_wire(blk, "varint")
    res["shuffle_key_bytes_raw"] = raw_kb
    res["shuffle_key_bytes_encoded"] = wire_kb
    res["shuffle_key_compression_x"] = round(raw_kb / max(wire_kb, 1), 2)

    # bit-exact: hash vs the full loopback wire path through real training
    import jax

    conf = make_synth_config(
        n_sparse_slots=n_slots, dense_dim=dense, batch_size=bsz,
        max_feasigns_per_ins=64, batch_key_capacity=bsz * n_slots * 4,
    )
    n_dev = min(4, len(jax.devices()))
    mesh = make_mesh(n_dev)
    states = {}
    with tempfile.TemporaryDirectory() as td:
        datasets = []
        for p in range(n_passes):
            files = write_synth_files(
                os.path.join(td, f"p{p}"), n_files=2,
                ins_per_file=max(ins_per_pass // 2, bsz * n_dev),
                n_sparse_slots=n_slots, vocab_per_slot=vocab_per_slot,
                dense_dim=dense, seed=91 + p, zipf_a=zipf_a,
            )
            ds = PadBoxSlotDataset(conf, read_threads=2)
            ds.set_filelist(files)
            ds.load_into_memory()
            datasets.append(ds)
        try:
            from paddlebox_tpu.telemetry import registry

            _HOST_CTRS = ("pass.host_row_bytes_in",
                          "pass.host_row_bytes_out",
                          "placement.hot_row_host_bytes")
            t_train: dict = {}
            for arm, mode, realize in (
                ("hash", "hash", False),
                ("wire", "loopback", False),
                ("hybrid", "loopback", True),
            ):
                # cache off: the per-arm row counters must read the RAW
                # host plane (the default 64k-row HBM cache is larger than
                # the toy census and would absorb every arm's hot traffic
                # identically — that interplay is --hbm-cache's bench)
                tconf = dataclasses.replace(
                    tconf0, placement=mode,
                    placement_update_interval=1,
                    placement_realize=realize,
                    hbm_cache_rows=0,
                )
                model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                               hidden=hidden)
                table = ShardedSparseTable(tconf, mesh, seed=0)
                trainer = MultiChipTrainer(model, tconf, mesh, trconf)
                auc_state = None
                total = prev = 0
                snaps = [registry.snapshot()]
                t0 = time.perf_counter()
                for ds in datasets:
                    table.begin_pass(ds.unique_keys())
                    m = trainer.train_from_dataset(
                        ds, table, auc_state=auc_state, drop_last=True,
                    )
                    auc_state = trainer.last_metric_state
                    table.end_pass()
                    total += int(m["count"]) - prev
                    prev = int(m["count"])
                    snaps.append(registry.snapshot())
                table.flush()
                t_train[arm] = time.perf_counter() - t0
                # per-arm host-plane row traffic + boundary gap; the LAST
                # pass is the steady-state figure (the hybrid arm's plan
                # realizes after hysteresis clears, so early passes still
                # pay the pre-realization traffic)
                for c in _HOST_CTRS:
                    d = (snaps[-1]["counters"].get(c, 0)
                         - snaps[0]["counters"].get(c, 0))
                    key = c.split(".", 1)[1]
                    res[f"{arm}_{key}_per_pass"] = round(d / n_passes, 1)
                    res[f"{arm}_{key}_last_pass"] = round(
                        snaps[-1]["counters"].get(c, 0)
                        - snaps[-2]["counters"].get(c, 0), 1)
                g0 = snaps[0]["histograms"].get("pass.boundary_gap_seconds")
                g1 = snaps[-1]["histograms"].get(
                    "pass.boundary_gap_seconds")
                if g1 is not None:
                    dc = g1["count"] - (g0["count"] if g0 else 0)
                    dsum = g1["sum"] - (g0["sum"] if g0 else 0.0)
                    res[f"{arm}_boundary_gap_ms"] = round(
                        dsum / max(dc, 1) * 1e3, 3)
                res[f"{arm}_samples_per_sec"] = round(
                    total / t_train[arm], 1)
                states[arm] = table.state_dict()
                states[arm]["auc"] = float(m["auc"])
                if arm == "hybrid":
                    plan = table.placement_plan()
                    res["hot_keys"] = 0 if plan is None else plan.n_hot
                    res["plan_version"] = (
                        0 if plan is None else plan.version
                    )
                    res["hot_resident_rows"] = int(
                        table.hot_resident_keys().shape[0])
                table.close()
            res["samples_per_sec"] = res["hybrid_samples_per_sec"]
        finally:
            for ds in datasets:
                ds.close()
    res["bitexact"] = bool(all(
        np.array_equal(states["hash"]["keys"], states[arm]["keys"])
        and np.array_equal(states["hash"]["values"], states[arm]["values"])
        and states["hash"]["auc"] == states[arm]["auc"]
        for arm in ("wire", "hybrid")
    ))
    # the realized-placement headline: hot lookups stopped paying the
    # host plane — steady-state begin-pass row traffic collapses to the
    # cold tail (last pass = first fully-realized pass at toy scale)
    res["hybrid_host_in_collapse_x"] = round(
        res["wire_host_row_bytes_in_last_pass"]
        / max(res["hybrid_host_row_bytes_in_last_pass"], 1), 2)
    log(f"hostplane: bytes/pass {res['hash_raw_bytes_per_pass']:.0f} -> "
        f"{res['planned_varint_bytes_per_pass']:.0f} "
        f"({res['census_collapse_x']}x collapse, codec alone "
        f"{res['census_compression_x']}x), shuffle keys "
        f"{res['shuffle_key_compression_x']}x, "
        f"bitexact={res['bitexact']}")
    log(f"hostplane hybrid: steady-state begin-pass row bytes "
        f"{res['wire_host_row_bytes_in_last_pass']:.0f} -> "
        f"{res['hybrid_host_row_bytes_in_last_pass']:.0f} "
        f"({res['hybrid_host_in_collapse_x']}x, hot migration "
        f"{res['hybrid_hot_row_host_bytes_per_pass']:.0f} B/pass), "
        f"samples/s {res['wire_samples_per_sec']} -> "
        f"{res['hybrid_samples_per_sec']}, hot rows resident "
        f"{res['hot_resident_rows']}")
    return res


def stage_hostplane(backend, args, tconf, trconf, n_slots, dense, bsz,
                    n_ins, hidden) -> None:
    res = bench_hostplane(
        3, tconf, trconf, n_slots, dense, min(bsz, 256),
        max(n_ins // 16, 1024), hidden,
        vocab_per_slot=max(args.vocab // 25, 200),
    )
    emit({"metric": "hostplane_census_bytes_per_pass",
          "value": res.get("planned_varint_bytes_per_pass"),
          "unit": "bytes/pass (2-rank census wire)",
          "vs_baseline": res.get("hash_raw_bytes_per_pass"),
          "backend": backend, **res})
    emit({"metric": "hostplane_hybrid_row_bytes_per_pass",
          "value": res.get("hybrid_host_row_bytes_in_last_pass"),
          "unit": "steady-state begin-pass host row bytes (hybrid arm)",
          "vs_baseline": res.get("wire_host_row_bytes_in_last_pass"),
          "backend": backend,
          "samples_per_sec": res.get("hybrid_samples_per_sec"),
          "boundary_gap_ms": res.get("hybrid_boundary_gap_ms"),
          "hot_migration_bytes_per_pass":
              res.get("hybrid_hot_row_host_bytes_per_pass"),
          "bitexact": res.get("bitexact")})


def bench_serving(n_slots: int = 8, dense: int = 13, n_requests: int = 100):
    """Serving-path latency/throughput: train a small
    CTR-DNN, export a shape-bucket ladder, then score canonical slot-text
    requests through ScoringServer.score_lines — the exact HTTP handler
    body (parser -> BatchBuilder -> Predictor bucket dispatch), measured
    in-process so the numbers isolate the serving stack, plus one
    loopback-HTTP config for the wire-inclusive figure.  Reference bar:
    the AnalysisPredictor stack serves at production QPS
    (inference/api/analysis_predictor.cc); this is its packaged analog."""
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import ScoringServer, export_model
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    B = 256  # server-side batching width (largest bucket)
    tconf = SparseTableConfig(embedding_dim=8)
    res: dict = {}
    with tempfile.TemporaryDirectory() as td:
        conf = make_synth_config(
            n_sparse_slots=n_slots, dense_dim=dense, batch_size=B,
            max_feasigns_per_ins=32,
        )
        files = write_synth_files(
            td, n_files=1, ins_per_file=4 * B, n_sparse_slots=n_slots,
            vocab_per_slot=10_000, dense_dim=dense, seed=13,
        )
        ds = PadBoxSlotDataset(conf, read_threads=2)
        ds.set_filelist(files)
        ds.load_into_memory()
        model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                       hidden=(64, 32))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                          seed=0)
        table.begin_pass(ds.unique_keys())
        trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()
        kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
        art = os.path.join(td, "artifact")
        export_model(
            model, trainer.params, table, art, batch_size=B,
            key_capacity=kcap, dense_dim=dense,
            batch_buckets=[(8, max(kcap // 32, 64)),
                           (64, max(kcap // 4, 64)), (B, kcap)],
        )
        with open(files[0], "rb") as f:
            all_lines = f.read().splitlines()

        srv = ScoringServer()
        srv.register("m", art, conf)
        try:
            for nreq in (1, 8, 64, 256):
                body = b"\n".join(all_lines[:nreq]) + b"\n"
                for _ in range(3):  # warmup: compile + lazy program load
                    srv.score_lines(body)
                lat = []
                t0 = time.perf_counter()
                for _ in range(n_requests):
                    t1 = time.perf_counter()
                    scores = srv.score_lines(body)
                    lat.append((time.perf_counter() - t1) * 1e3)
                    assert len(scores) == nreq
                dt = time.perf_counter() - t0
                lat.sort()
                p50 = lat[len(lat) // 2]
                p99 = lat[_rank(0.99, len(lat))]
                res[f"b{nreq}_p50_ms"] = round(p50, 2)
                res[f"b{nreq}_p99_ms"] = round(p99, 2)
                res[f"b{nreq}_qps"] = round(n_requests / dt, 1)
                res[f"b{nreq}_ins_per_s"] = round(nreq * n_requests / dt, 1)
                log(f"serving b={nreq}: p50 {p50:.2f}ms p99 {p99:.2f}ms "
                    f"{nreq * n_requests / dt:,.0f} ins/s")
            # wire-inclusive: one loopback HTTP config at b=64
            import json as _json
            import urllib.request

            port = srv.start(port=0)
            body = b"\n".join(all_lines[:64]) + b"\n"
            lat = []
            for _ in range(max(n_requests // 2, 20)):
                t1 = time.perf_counter()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/score", data=body,
                    method="POST")
                with urllib.request.urlopen(req, timeout=30) as r:
                    _json.loads(r.read())
                lat.append((time.perf_counter() - t1) * 1e3)
            lat.sort()
            res["http_b64_p50_ms"] = round(lat[len(lat) // 2], 2)
            res["http_b64_p99_ms"] = round(lat[_rank(0.99, len(lat))], 2)
            log(f"serving http b=64: p50 {res['http_b64_p50_ms']}ms "
                f"p99 {res['http_b64_p99_ms']}ms")
        finally:
            srv.stop()
    return res


def stage_serving(backend) -> None:
    res = bench_serving()
    emit({"metric": "serving_score_latency", "value": res.get("b64_p50_ms"),
          "unit": "ms p50 (64-instance request)", "vs_baseline": None,
          "backend": backend, **res})


def _open_loop_http(port: int, body: bytes, qps: float, duration_s: float,
                    path: str = "/score", n_threads: int = 16,
                    timeout: float = 30.0) -> dict:
    """Drive one open-loop load point: request i leaves at
    ``start + i/qps`` no matter how request i-1 fared (closed-loop
    generators hide overload by slowing down with the server).  Returns
    p50/p99 of 200s, shed (429) and failed counts, achieved QPS."""
    import http.client
    import threading

    n_requests = max(1, int(qps * duration_s))
    idx = {"i": 0}
    lat_ok: list = []
    shed = failed = 0
    lock = threading.Lock()
    start = time.monotonic()

    def worker():
        nonlocal shed, failed
        while True:
            with lock:
                i = idx["i"]
                if i >= n_requests:
                    return
                idx["i"] = i + 1
            delay = start + i / qps - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t1 = time.perf_counter()
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=timeout)
                conn.request("POST", path, body=body)
                r = conn.getresponse()
                r.read()
                status = r.status
                conn.close()
            # pbox-lint: ignore[swallowed-exception] failure is recorded:
            # status=-1 counts as failed below
            except Exception:
                status = -1
            dt = (time.perf_counter() - t1) * 1e3
            with lock:
                if status == 200:
                    lat_ok.append(dt)
                elif status == 429:
                    shed += 1
                else:
                    failed += 1

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(min(n_threads, n_requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 120)
    wall = time.monotonic() - start
    lat_ok.sort()
    n_ok = len(lat_ok)
    return {
        "target_qps": qps,
        "requests": n_ok + shed + failed,
        "ok": n_ok,
        "shed": shed,
        "failed": failed,
        "p50_ms": round(lat_ok[n_ok // 2], 2) if n_ok else None,
        "p99_ms": round(lat_ok[_rank(0.99, n_ok)], 2) if n_ok else None,
        "achieved_qps": round((n_ok + shed + failed) / wall, 1),
    }


def bench_serving_sweep(qps_points, duration_s: float = 6.0,
                        n_slots: int = 8, dense: int = 13,
                        req_lines: int = 8, ins_per_file: int = 512,
                        max_batch=None, compare_unbatched: bool = True,
                        hidden=(64, 32)) -> dict:
    """The p50/p99-vs-QPS curve (ROADMAP item 1): train a small CTR-DNN
    once, export one artifact, then drive the OPEN-LOOP load through a
    live ScoringServer at each target QPS — once with continuous
    micro-batching (PBOX_SERVE_MAX_BATCH / ``max_batch``) and once with
    the one-at-a-time baseline (max_batch=1), same artifact, same
    request mix — so the batching win reads directly off the two curves
    (batched p99 lower at fixed QPS; shed onset at higher QPS)."""
    from paddlebox_tpu.config import (
        SparseTableConfig,
        TrainerConfig,
        flags,
    )
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import ScoringServer, export_model
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    B = 64
    max_batch = int(flags.serve_max_batch if max_batch is None else max_batch)
    res: dict = {"max_batch": max_batch, "duration_s": duration_s,
                 "req_lines": req_lines}
    with tempfile.TemporaryDirectory() as td:
        conf = make_synth_config(n_sparse_slots=n_slots, dense_dim=dense,
                                 batch_size=B, max_feasigns_per_ins=16)
        files = write_synth_files(
            td, n_files=1, ins_per_file=ins_per_file, n_sparse_slots=n_slots,
            vocab_per_slot=10_000, dense_dim=dense, seed=13,
        )
        ds = PadBoxSlotDataset(conf, read_threads=2)
        ds.set_filelist(files)
        ds.load_into_memory()
        tconf = SparseTableConfig(embedding_dim=8)
        model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                       hidden=tuple(hidden))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                          seed=0)
        table.begin_pass(ds.unique_keys())
        trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()
        kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
        art = os.path.join(td, "artifact")
        export_model(model, trainer.params, table, art, batch_size=B,
                     key_capacity=kcap, dense_dim=dense,
                     batch_buckets=[(8, max(kcap // 8, 64))],
                     feed_conf=conf)
        with open(files[0], "rb") as f:
            body = b"\n".join(f.read().splitlines()[:req_lines]) + b"\n"

        configs = [("batched", max_batch)]
        if compare_unbatched and max_batch > 1:
            configs.append(("unbatched", 1))
        for label, mb in configs:
            srv = ScoringServer(max_batch=mb)
            srv.register("m", art, conf)
            port = srv.start(port=0)
            try:
                for _ in range(5):  # compile + program-load warmup
                    srv.score_lines(body, "m")
                points = []
                for q in qps_points:
                    pt = _open_loop_http(port, body, float(q), duration_s)
                    points.append(pt)
                    emit({"metric": "serving_qps_sweep", "mode": label,
                          "max_batch": mb, "value": pt["p99_ms"],
                          "unit": "ms p99 (open loop)",
                          "vs_baseline": None, **pt})
                    log(f"sweep [{label} mb={mb}] qps={q}: p50 "
                        f"{pt['p50_ms']}ms p99 {pt['p99_ms']}ms shed "
                        f"{pt['shed']} achieved {pt['achieved_qps']}")
                res[f"{label}_curve"] = points
            finally:
                srv.stop()
    return res


def stage_serving_sweep(backend, args) -> None:
    points = [float(x) for x in args.qps_sweep.split(",") if x.strip()]
    res = bench_serving_sweep(points, duration_s=args.sweep_seconds)
    curve = res.get("batched_curve") or []
    emit({"metric": "serving_qps_sweep_curve",
          "value": curve[-1]["p99_ms"] if curve else None,
          "unit": f"ms p99 @ {points[-1] if points else '?'} qps",
          "vs_baseline": None, "backend": backend, **res})


# the fleet stages score in replica PROCESSES, and a chip belongs to one
# process — this one, which trained the artifact on it.  The replicas run
# --cpu, so what those stages measure is host-side serving (router,
# admission, failover) and their rows carry this platform, not the parent's
_REPLICA_BACKEND = "cpu"


def bench_fleet_sweep(qps_points, duration_s: float = 6.0,
                      n_replicas: int = 3, n_slots: int = 4,
                      dense: int = 4) -> dict:
    """The same open-loop sweep through a REAL fleet: N replica server
    processes + router (no chaos — this measures the capacity curve, the
    SIGKILL run stays bench_fleet's job).  Replica batching follows the
    inherited env (PBOX_SERVE_MAX_BATCH), so driving this twice with the
    flag flipped produces the fleet-level batched-vs-not curves."""
    import http.client

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig, flags
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import export_model
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.serving_fleet import (
        EJECTED,
        FleetRouter,
        ReplicaSupervisor,
    )
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    B = 64
    res: dict = {"n_replicas": n_replicas, "duration_s": duration_s,
                 "max_batch": flags.serve_max_batch}
    with tempfile.TemporaryDirectory() as td:
        conf = make_synth_config(n_sparse_slots=n_slots, dense_dim=dense,
                                 batch_size=B, max_feasigns_per_ins=8)
        files = write_synth_files(td, n_files=1, ins_per_file=2 * B,
                                  n_sparse_slots=n_slots, vocab_per_slot=500,
                                  dense_dim=dense, seed=17)
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        tconf = SparseTableConfig(embedding_dim=4)
        model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                       hidden=(16,))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                          seed=0)
        table.begin_pass(ds.unique_keys())
        trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()
        kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
        art = os.path.join(td, "artifact")
        export_model(model, trainer.params, table, art, batch_size=B,
                     key_capacity=kcap, dense_dim=dense, feed_conf=conf)
        with open(files[0], "rb") as f:
            body = b"\n".join(f.read().splitlines()[:8]) + b"\n"

        def argv_for(rid, port):
            # --replicas 0: children inherit this env (see bench_fleet)
            return [sys.executable, "-m", "paddlebox_tpu.serve",
                    "--replicas", "0",
                    "--artifact", art, "--port", str(port), "--cpu",
                    "--max-queue", "64"]

        sup = ReplicaSupervisor(n_replicas, argv_for,
                                log_dir=os.path.join(td, "logs"))
        sup.start()
        router = FleetRouter(sup.endpoints(), probe_interval_s=0.3)
        try:
            t0 = time.monotonic()
            while time.monotonic() - t0 < 600:
                router.probe_once()
                if all(r.state != EJECTED for r in router.replicas):
                    break
                time.sleep(0.5)
            else:
                raise RuntimeError(
                    "replicas never came healthy: "
                    f"{[r.last_error for r in router.replicas]}")
            port = router.start(port=0)
            for _ in range(5):  # warm every replica's compile path
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=60)
                conn.request("POST", "/score", body=body)
                conn.getresponse().read()
                conn.close()
            points = []
            for q in qps_points:
                pt = _open_loop_http(port, body, float(q), duration_s)
                points.append(pt)
                emit({"metric": "fleet_qps_sweep", "value": pt["p99_ms"],
                      "unit": "ms p99 (open loop, router)",
                      "vs_baseline": None, "backend": _REPLICA_BACKEND,
                      **pt})
                log(f"fleet sweep qps={q}: p50 {pt['p50_ms']}ms p99 "
                    f"{pt['p99_ms']}ms shed {pt['shed']} achieved "
                    f"{pt['achieved_qps']}")
            res["curve"] = points
        finally:
            router.stop()
            sup.stop()
    return res


def stage_fleet_sweep(backend, args) -> None:
    points = [float(x) for x in args.qps_sweep.split(",") if x.strip()]
    res = bench_fleet_sweep(points, duration_s=args.sweep_seconds)
    curve = res.get("curve") or []
    emit({"metric": "fleet_qps_sweep_curve",
          "value": curve[-1]["p99_ms"] if curve else None,
          "unit": f"ms p99 @ {points[-1] if points else '?'} qps",
          "vs_baseline": None, "backend": _REPLICA_BACKEND, **res})


def _rank_auc(scores, labels) -> float:
    """Tie-averaged rank AUC (Mann-Whitney), numpy only."""
    s = np.asarray(scores, np.float64)
    y = np.asarray(labels, np.float64)
    n_pos = float(y.sum())
    n_neg = float(len(y) - n_pos)
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    order = np.argsort(s, kind="mergesort")
    ss = s[order]
    ranks = np.empty(len(s), np.float64)
    i = 0
    while i < len(s):
        j = i
        while j + 1 < len(s) and ss[j + 1] == ss[i]:
            j += 1
        ranks[order[i: j + 1]] = (i + j) / 2.0 + 1.0
        i = j + 1
    return float(
        (ranks[y == 1].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)
    )


def bench_quantized(n_slots: int = 8, dense: int = 13,
                    embedding_dim: int = 64, ins_per_file: int = 1024,
                    dtypes=("fp32", "int8", "fp8")) -> dict:
    """Quantized-artifact evidence (ROADMAP item 1(b)): one trained
    model exported at each embedding dtype, reporting sparse payload
    bytes (the multi-TB delta-publish shrink) and the AUC of each
    artifact's scores on the synthetic CTR eval vs its labels — the
    acceptance bar is bytes <= ~30% of fp32 at production-shaped
    embedding widths with |AUC delta| < 0.005."""
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import Predictor, export_model
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    B = 128
    res: dict = {"embedding_dim": embedding_dim}
    with tempfile.TemporaryDirectory() as td:
        conf = make_synth_config(n_sparse_slots=n_slots, dense_dim=dense,
                                 batch_size=B, max_feasigns_per_ins=16)
        files = write_synth_files(
            td, n_files=1, ins_per_file=ins_per_file, n_sparse_slots=n_slots,
            vocab_per_slot=5_000, dense_dim=dense, seed=29,
        )
        ds = PadBoxSlotDataset(conf, read_threads=2)
        ds.set_filelist(files)
        ds.load_into_memory()
        tconf = SparseTableConfig(embedding_dim=embedding_dim)
        model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                       hidden=(64, 32))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                          seed=0)
        table.begin_pass(ds.unique_keys())
        trainer.train_from_dataset(ds, table)
        table.end_pass()
        kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
        labels = []
        for batch in ds.batches(drop_last=False):
            labels.extend(batch.labels[: batch.n_real_ins].tolist())
        for dt in dtypes:
            art = os.path.join(td, f"art-{dt}")
            export_model(model, trainer.params, table, art, batch_size=B,
                         key_capacity=kcap, dense_dim=dense,
                         embedding_dtype=dt)
            pred = Predictor.load(art)
            scores = np.concatenate(list(pred.predict_dataset(ds)))
            sp = os.path.join(art, "sparse")
            payload = sum(
                os.path.getsize(os.path.join(sp, f))
                for f in os.listdir(sp) if not f.startswith("keys")
            )
            res[f"{dt}_payload_bytes"] = payload
            res[f"{dt}_artifact_bytes"] = pred.artifact_bytes
            res[f"{dt}_auc"] = round(_rank_auc(scores, labels), 6)
        ds.close()
    for dt in dtypes:
        if dt == "fp32":
            continue
        res[f"{dt}_bytes_ratio"] = round(
            res[f"{dt}_payload_bytes"] / res["fp32_payload_bytes"], 4)
        res[f"{dt}_auc_delta"] = round(
            abs(res[f"{dt}_auc"] - res["fp32_auc"]), 6)
        log(f"quantized {dt}: payload {res[f'{dt}_payload_bytes']:,} B "
            f"({res[f'{dt}_bytes_ratio']:.2%} of fp32), AUC "
            f"{res[f'{dt}_auc']:.4f} (delta {res[f'{dt}_auc_delta']:.5f})")
    return res


def stage_quantized(backend) -> None:
    res = bench_quantized()
    emit({"metric": "quantized_artifact_bytes_ratio",
          "value": res.get("int8_bytes_ratio"),
          "unit": "int8/fp32 sparse payload bytes",
          "vs_baseline": 1.0, "backend": backend, **res})


def bench_storage(n_passes: int = 8, embedding_dim: int = 8,
                  hot_rows: int = 4000, cold_rows: int = 1500) -> list:
    """Durable-cold-tier storage ablation (ISSUE 17): the same churny
    training job checkpointed two ways — classic full snapshots
    (`CheckpointManager.save_base` every pass) vs log-structured
    incremental generations (`IncrementalCheckpointManager`: one base,
    then `save_delta` per pass over the keep-history LogStore).  Each arm
    reports bytes + seconds per checkpoint, restore wall time against the
    restored row count and the last delta's row count (the bounded-
    recovery claim: incremental save cost tracks the DELTA, not the
    table), and the census disk-reject rate — the fraction of absent
    census keys the table's own durable log rejected from bloom/min-max
    sidecars alone, without reading a segment."""
    from paddlebox_tpu.checkpoint import (
        CheckpointManager,
        IncrementalCheckpointManager,
    )
    from paddlebox_tpu.config import SparseTableConfig
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.utils.monitor import stats

    def du(path: str) -> int:
        total = 0
        for dirpath, _, files in os.walk(path):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(dirpath, f))
                except OSError:
                    pass
        return total

    def pass_keys(p: int) -> np.ndarray:
        # half the hot set revisits every pass; a disjoint cold slice is
        # new each pass — so deltas stay small while the table grows
        rs = np.random.RandomState(1000 + p)
        hot = rs.choice(hot_rows, size=hot_rows // 2,
                        replace=False).astype(np.uint64) + 1
        cold = np.arange(cold_rows, dtype=np.uint64) \
            + np.uint64(1_000_000 + p * cold_rows)
        return np.unique(np.concatenate([hot, cold]))

    import jax.numpy as jnp

    rows = []
    for arm in ("full", "incremental"):
        with tempfile.TemporaryDirectory() as td:
            conf = SparseTableConfig(
                embedding_dim=embedding_dim,
                overlap_pass_boundary=False, hbm_cache_rows=0,
                store_log_dir=os.path.join(td, "tlog"),
                store_log_buckets=4,
            )
            t = SparseTable(conf, seed=11)
            root = os.path.join(td, "ckpt")
            mgr = (CheckpointManager(root) if arm == "full"
                   else IncrementalCheckpointManager(root))
            save_s, bytes_per_save, rows_per_save = [], [], []
            for p in range(n_passes):
                t.begin_pass(pass_keys(p))
                t.values = t.values + 1.0
                t.end_pass()
                t.flush()
                tag = f"pass{p:03d}"
                pre = du(root)
                t0 = time.perf_counter()
                if arm == "full" or p == 0:
                    mgr.save_base(tag, t)
                else:
                    mgr.save_delta(tag, t)
                save_s.append(time.perf_counter() - t0)
                bytes_per_save.append(du(root) - pre)
            ents = (mgr.entries() if arm == "incremental"
                    else [c.meta for c in mgr.list_checkpoints()])
            rows_per_save = [int(e["n_sparse_rows"]) for e in ents]
            # census disk-reject rate, measured AFTER the last save so the
            # probe keys never pollute a checkpoint
            absent = np.arange(2_000, dtype=np.uint64) + np.uint64(1 << 40)
            pre_rej = stats.get("store.census_disk_rejects")
            t.begin_pass(absent)
            t.end_pass()
            reject_rate = (stats.get("store.census_disk_rejects") - pre_rej) \
                / float(absent.shape[0])
            final_rows = int(t.state_dict()["keys"].shape[0])
            t.close()

            conf2 = SparseTableConfig(
                embedding_dim=embedding_dim,
                overlap_pass_boundary=False, hbm_cache_rows=0,
            )
            t2 = SparseTable(conf2, seed=11)
            mgr2 = (CheckpointManager(root) if arm == "full"
                    else IncrementalCheckpointManager(root))
            upto = f"pass{n_passes - 1:03d}"
            t0 = time.perf_counter()
            mgr2.load(t2, upto=upto)
            restore_s = time.perf_counter() - t0
            restored_rows = int(t2.state_dict()["keys"].shape[0])
            t2.close()
            row = {
                "arm": arm,
                "n_passes": n_passes,
                "final_rows": final_rows,
                "restored_rows": restored_rows,
                "ckpt_bytes_total": int(sum(bytes_per_save)),
                "ckpt_seconds_total": round(sum(save_s), 4),
                "bytes_last_save": int(bytes_per_save[-1]),
                # median, because background compaction amortizes across
                # delta saves and spikes whichever save it rides on
                "bytes_median_save": int(np.median(bytes_per_save)),
                "seconds_last_save": round(save_s[-1], 4),
                "rows_last_save": rows_per_save[-1],
                "restore_seconds": round(restore_s, 4),
                "census_disk_reject_rate": round(reject_rate, 4),
            }
            rows.append(row)
            log(f"storage[{arm}]: last save {row['bytes_last_save']:,} B "
                f"({row['rows_last_save']:,} rows) in "
                f"{row['seconds_last_save']:.3f}s; restore "
                f"{row['restored_rows']:,} rows in {restore_s:.3f}s; "
                f"census disk-reject rate {reject_rate:.2%}")
    return rows


def stage_storage(backend) -> None:
    rows = bench_storage()
    by_arm = {r["arm"]: r for r in rows}
    for r in rows:  # one JSON row per arm, as the issue asks
        emit({"metric": f"storage_ckpt_{r['arm']}", "unit": "bytes/save",
              "value": r["bytes_last_save"], "backend": backend, **r})
    full, incr = by_arm["full"], by_arm["incremental"]
    emit({"metric": "storage_incremental_ckpt_bytes_ratio",
          "value": round(incr["ckpt_bytes_total"]
                         / max(1, full["ckpt_bytes_total"]), 4),
          "unit": "incr/full total checkpoint bytes",
          "vs_baseline": round(full["ckpt_bytes_total"]
                               / max(1, incr["ckpt_bytes_total"]), 2),
          "backend": backend,
          "full": full, "incremental": incr})


def bench_fleet(n_replicas: int = 3, qps: float = 25.0,
                duration_s: float = 12.0, kill_at_s: float = 4.0,
                n_slots: int = 4, dense: int = 4):
    """Serving-fleet SLO evidence, OPEN-LOOP (ROADMAP item 2(c)): train a
    tiny CTR-DNN, export one self-contained artifact, spawn N real
    replica server processes under the ReplicaSupervisor, put the
    FleetRouter in front, then drive a fixed-schedule request stream
    (send times set by the clock, NOT by response arrival — closed-loop
    generators hide overload by slowing down with the server) while
    chaos runs: a probabilistic fleet.probe fault plan plus a REAL
    SIGKILL of one replica mid-stream.  Reports p50/p99/achieved-QPS,
    shed and failed counts, the supervisor restart count, fleet-view
    convergence, and the hard zero-failed-requests check.  The whole run
    records into the postmortem plane (PBOX_FLIGHT_DIR; parent +
    replicas dump flight rings) and the emitted row carries
    pbox_doctor's parsed verdict — crash attribution + failover-traced
    request count."""
    import http.client
    import signal as _signal
    import subprocess
    import threading

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import export_model
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.serving_fleet import (
        EJECTED,
        FleetRouter,
        ReplicaSupervisor,
    )
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer
    from paddlebox_tpu.utils.faults import fault_plan

    from paddlebox_tpu import telemetry

    B = 64
    res: dict = {"n_replicas": n_replicas, "target_qps": qps,
                 "duration_s": duration_s}
    with tempfile.TemporaryDirectory() as td:
        # postmortem plane: the parent (router+supervisor) and every
        # replica child dump their flight rings here; pbox_doctor's
        # verdict on the run rides the emitted row
        flight_dir = os.path.join(td, "postmortem")
        os.environ["PBOX_FLIGHT_DIR"] = flight_dir
        telemetry.set_process_name("bench-fleet")
        conf = make_synth_config(n_sparse_slots=n_slots, dense_dim=dense,
                                 batch_size=B, max_feasigns_per_ins=8)
        files = write_synth_files(td, n_files=1, ins_per_file=2 * B,
                                  n_sparse_slots=n_slots, vocab_per_slot=500,
                                  dense_dim=dense, seed=17)
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        tconf = SparseTableConfig(embedding_dim=4)
        model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                       hidden=(16,))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                          seed=0)
        table.begin_pass(ds.unique_keys())
        trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()
        kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
        art = os.path.join(td, "artifact")
        export_model(model, trainer.params, table, art, batch_size=B,
                     key_capacity=kcap, dense_dim=dense, feed_conf=conf)
        with open(files[0], "rb") as f:
            body = b"\n".join(f.read().splitlines()[:8]) + b"\n"

        def argv_for(rid, port):
            # --replicas 0 pins single-server mode: the children inherit
            # this process's env, so a PBOX_SERVE_REPLICAS setting would
            # otherwise flip every replica into its own nested fleet
            return [sys.executable, "-m", "paddlebox_tpu.serve",
                    "--replicas", "0",
                    "--artifact", art, "--port", str(port), "--cpu",
                    "--max-queue", "64"]

        sup = ReplicaSupervisor(n_replicas, argv_for,
                                log_dir=os.path.join(td, "logs"))
        sup.start()
        router = FleetRouter(sup.endpoints(), probe_interval_s=0.3)
        lat_ok: list = []
        shed = failed = 0
        count_lock = threading.Lock()
        try:
            # replica startup = a full jax import + artifact load each
            # (simultaneous, so a 1-core box serializes them — the
            # allowance must cover the SUM of the imports, not one)
            t0 = time.monotonic()
            while time.monotonic() - t0 < 600:
                router.probe_once()
                if all(r.state != EJECTED for r in router.replicas):
                    break
                time.sleep(0.5)
            else:
                raise RuntimeError("replicas never came healthy: "
                                   f"{[r.last_error for r in router.replicas]}")
            log(f"fleet: {n_replicas} replicas healthy in "
                f"{time.monotonic() - t0:.0f}s")
            port = router.start(port=0)
            for _ in range(5):  # warm every replica's compile path
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=60)
                conn.request("POST", "/score", body=body)
                conn.getresponse().read()
                conn.close()

            n_requests = int(qps * duration_s)
            idx = {"i": 0}
            start = time.monotonic()
            killed = {"pid": None}

            def worker():
                nonlocal shed, failed
                while True:
                    with count_lock:
                        i = idx["i"]
                        if i >= n_requests:
                            return
                        idx["i"] = i + 1
                    # open loop: request i goes out at start + i/qps no
                    # matter how request i-1 fared
                    delay = start + i / qps - time.monotonic()
                    if delay > 0:
                        time.sleep(delay)
                    t1 = time.perf_counter()
                    try:
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", port, timeout=30)
                        conn.request("POST", "/score", body=body)
                        r = conn.getresponse()
                        r.read()
                        status = r.status
                        conn.close()
                    # pbox-lint: ignore[swallowed-exception] failure is
                    # recorded: status=-1 is counted as an error below
                    except Exception:
                        status = -1
                    dt = (time.perf_counter() - t1) * 1e3
                    with count_lock:
                        if status == 200:
                            lat_ok.append(dt)
                        elif status == 429:
                            shed += 1
                        else:
                            failed += 1

            # chaos: probabilistic probe faults (the PBOX_FAULT_PLAN
            # shape) + one real SIGKILL mid-stream
            with fault_plan({"fleet.probe": "p:0.05"}, seed=7):
                threads = [threading.Thread(target=worker, daemon=True)
                           for _ in range(16)]
                for t in threads:
                    t.start()
                time.sleep(kill_at_s)
                killed["pid"] = sup.kill_replica(0, _signal.SIGKILL)
                log(f"fleet: SIGKILLed replica 0 (pid {killed['pid']}) at "
                    f"t+{kill_at_s:.0f}s")
                for t in threads:
                    t.join(timeout=duration_s + 120)
            wall = time.monotonic() - start

            # convergence: the killed replica restarts (new pid) and the
            # fleet view returns to all-serving
            t0 = time.monotonic()
            converged = False
            while time.monotonic() - t0 < 300:
                router.probe_once()
                view = router.fleet_view()
                if view["n_serving"] == n_replicas \
                        and sup.restart_count() >= 1:
                    converged = True
                    break
                time.sleep(0.5)
        finally:
            router.stop()
            sup.stop()
            os.environ.pop("PBOX_FLIGHT_DIR", None)

        # offline correlation before the tempdir vanishes: the doctor's
        # parsed verdict (who crashed, which traces failed over) is part
        # of the bench evidence
        telemetry.dump_flight("fleet_run_end", {
            "requests": len(lat_ok) + shed + failed,
        }, dump_dir=flight_dir)
        try:
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            import pbox_doctor

            doc = pbox_doctor.analyze(td)
            res["postmortem"] = {
                "flight_dumps": doc["sources"]["dumps"],
                "dump_reasons": doc["dump_reasons"],
                "crashed_replicas": [
                    {"replica_id": c["replica_id"], "pid": c["pid"]}
                    for c in doc["crashes"]
                ],
                "traces": len(doc["traces"]),
                "traces_with_failover": sum(
                    1 for recs in doc["traces"].values()
                    if any(r["name"] == "fleet.failover" for r in recs)
                ),
            }
        except Exception as e:  # the doctor must never sink the bench
            res["postmortem"] = {"error": repr(e)[:200]}
        finally:
            sys.path.pop(0)

    lat_ok.sort()
    n_ok = len(lat_ok)
    res.update({
        "requests": n_ok + shed + failed,
        "ok": n_ok,
        "shed": shed,
        "failed_requests": failed,
        "zero_failed": failed == 0,
        "p50_ms": round(lat_ok[n_ok // 2], 2) if n_ok else None,
        "p99_ms": round(lat_ok[_rank(0.99, n_ok)], 2) if n_ok else None,
        "achieved_qps": round((n_ok + shed + failed) / wall, 1),
        "supervisor_restarts": sup.restart_count(),
        "killed_pid": killed["pid"],
        "fleet_converged": converged,
    })
    log(f"fleet: {n_ok} ok / {shed} shed / {failed} FAILED of "
        f"{res['requests']} @ {res['achieved_qps']} qps; p50 "
        f"{res['p50_ms']}ms p99 {res['p99_ms']}ms; restarts "
        f"{res['supervisor_restarts']} converged={converged}")
    return res


def stage_fleet(backend, args) -> None:
    res = bench_fleet(qps=args.fleet_qps, duration_s=args.fleet_seconds)
    emit({"metric": "fleet_router_p99_ms", "value": res.get("p99_ms"),
          "unit": "ms p99 (8-instance request, 1 replica SIGKILLed "
                  "mid-stream)", "vs_baseline": None,
          "backend": _REPLICA_BACKEND, **res})


def _elastic_reshard_pin(n_slots: int, dense: int, bsz: int = 16) -> dict:
    """The training-side half of the --elastic acceptance: a LIVE
    pass-boundary reshard (grow, e.g. 2 -> 4 shards) must be bit-exact —
    keys, values, g2sum, AUC — against a fixed-shard teardown-and-rebuild
    at the new shard count (the same pin tests/test_reshard.py holds; the
    bench re-proves it on the day's backend and reports it in the row)."""
    import jax

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.parallel import (
        MultiChipTrainer, ShardedSparseTable, make_mesh,
    )

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"reshard_bit_exact": None,
                "reshard_skipped": f"{n_dev} device(s): no second shard"}
    new_n = min(4, n_dev)
    old_n = max(1, new_n // 2)
    mesh_old, mesh_new = make_mesh(old_n), make_mesh(new_n)
    tconf = SparseTableConfig(embedding_dim=8)

    with tempfile.TemporaryDirectory() as td:
        conf = make_synth_config(n_sparse_slots=n_slots, dense_dim=dense,
                                 batch_size=bsz, max_feasigns_per_ins=16)
        # 8 per-device batches: divisible by both shard counts
        files = write_synth_files(td, n_files=2, ins_per_file=bsz * 4,
                                  n_sparse_slots=n_slots, vocab_per_slot=200,
                                  dense_dim=dense, seed=23)
        ds = PadBoxSlotDataset(conf, read_threads=2)
        ds.set_filelist(files)
        ds.load_into_memory()

        def trainer(mesh):
            model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                           hidden=(16,))
            return MultiChipTrainer(model, tconf, mesh,
                                    TrainerConfig(auc_buckets=1 << 10),
                                    seed=3)

        def run_pass(tr, table):
            table.begin_pass(ds.unique_keys())
            m = tr.train_from_dataset(ds, table)
            table.end_pass()
            return m

        live = ShardedSparseTable(tconf, mesh_old, seed=5)
        run_pass(trainer(mesh_old), live)
        t0 = time.perf_counter()
        moved = live.reshard(mesh_new)
        reshard_s = time.perf_counter() - t0
        m_live = run_pass(trainer(mesh_new), live)

        base = ShardedSparseTable(tconf, mesh_old, seed=5)
        run_pass(trainer(mesh_old), base)
        rebuilt = ShardedSparseTable(tconf, mesh_new, seed=5)
        rebuilt.load_state_dict(base.state_dict())
        m_base = run_pass(trainer(mesh_new), rebuilt)

        s_live, s_base = live.state_dict(), rebuilt.state_dict()
        exact = (np.array_equal(s_live["keys"], s_base["keys"])
                 and np.array_equal(s_live["values"], s_base["values"])
                 and m_live["auc"] == m_base["auc"])
        for t in (live, base, rebuilt):
            t.close()
        ds.close()
    return {
        "reshard_old_shards": old_n,
        "reshard_new_shards": new_n,
        "reshard_moved_rows": moved,
        "reshard_seconds": round(reshard_s, 3),
        "reshard_auc": round(m_live["auc"], 6),
        "reshard_bit_exact": bool(exact),
    }


def bench_elastic(duration_s: float = 24.0, base_qps: float = 10.0,
                  n_slots: int = 4, dense: int = 4) -> dict:
    """Elastic-fleet evidence (PR 16 acceptance), OPEN-LOOP: a diurnal
    rate curve (low -> peak -> low over the run) with a 4x flash crowd on
    the shoulder and a Zipf-drifting request mix, driven against a REAL
    replica fleet (2 seed replicas) with the FleetAutoscaler live.  The
    flash crowd must force >= 1 autoscale-up, the post-peak idle tail
    >= 1 drain-retire, and a rolling restart fires mid-stream while the
    load runs — with ZERO failed requests (sheds are admission control,
    not failures), a bounded p99, and the fleet freshness floor held at
    every sample (>= 1 serving replica reporting the model: min applied
    seq never vanishes mid-roll; static base artifact, so the deadline
    evidence is floor-never-empty + max observed age).  The emitted row
    also carries the training-side pin: a live pass-boundary reshard
    bit-exact vs a fixed-shard rebuild (_elastic_reshard_pin)."""
    import http.client
    import math
    import threading

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import export_model
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.serving_fleet import (
        EJECTED,
        AutoscalerConfig,
        FleetAutoscaler,
        FleetRouter,
        ReplicaSupervisor,
    )
    from paddlebox_tpu.serving_sync.syncer import fleet_min_freshness
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    from paddlebox_tpu import telemetry

    B = 32
    res: dict = {"base_qps": base_qps, "duration_s": duration_s}
    with tempfile.TemporaryDirectory() as td:
        telemetry.set_process_name("bench-elastic")
        conf = make_synth_config(n_sparse_slots=n_slots, dense_dim=dense,
                                 batch_size=B, max_feasigns_per_ins=8)
        files = write_synth_files(td, n_files=1, ins_per_file=4 * B,
                                  n_sparse_slots=n_slots, vocab_per_slot=500,
                                  dense_dim=dense, seed=17)
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        tconf = SparseTableConfig(embedding_dim=4)
        model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                       hidden=(16,))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 10),
                          seed=0)
        table.begin_pass(ds.unique_keys())
        trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()
        kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
        art = os.path.join(td, "artifact")
        export_model(model, trainer.params, table, art, batch_size=B,
                     key_capacity=kcap, dense_dim=dense, feed_conf=conf)

        # Zipf-drifting request mix: K distinct bodies (4 lines each);
        # the hot index rotates through the run so the popular request
        # shape at minute N is a cold one at minute N+1
        with open(files[0], "rb") as f:
            lines = f.read().splitlines()
        K = 16
        bodies = [b"\n".join(lines[(4 * i) % len(lines):
                                   (4 * i) % len(lines) + 4]) + b"\n"
                  for i in range(K)]
        zipf = np.minimum(np.random.default_rng(3).zipf(1.5, 1 << 14), K) - 1

        def argv_for(rid, port):
            return [sys.executable, "-m", "paddlebox_tpu.serve",
                    "--replicas", "0",
                    "--artifact", art, "--port", str(port), "--cpu",
                    "--max-queue", "8", "--request-deadline-ms", "2000"]

        sup = ReplicaSupervisor(2, argv_for,
                                log_dir=os.path.join(td, "logs"))
        sup.start()
        router = FleetRouter(sup.endpoints(), probe_interval_s=0.2)
        scaler = FleetAutoscaler(sup, router, AutoscalerConfig(
            min_replicas=2, max_replicas=4, interval_s=0.25, cooldown_s=3.0,
            up_queue_depth=2.0, up_wait_s=0.1, up_shed_rate=0.25,
            up_after=2, down_after=8, drain_timeout_s=5.0,
        ))
        lat_ok: list = []
        shed = failed = 0
        count_lock = threading.Lock()
        fresh = {"floor_held": True, "max_age_s": 0.0, "min_serving": 99,
                 "samples": 0}
        max_fleet = {"n": 2}
        stop_monitor = threading.Event()
        rolled: list = []
        try:
            t0 = time.monotonic()
            while time.monotonic() - t0 < 600:
                router.probe_once()
                if all(r.state != EJECTED for r in router.replicas):
                    break
                time.sleep(0.5)
            else:
                raise RuntimeError("replicas never came healthy: "
                                   f"{[r.last_error for r in router.replicas]}")
            log(f"elastic: 2 seed replicas healthy in "
                f"{time.monotonic() - t0:.0f}s")
            port = router.start(port=0)
            for i in range(4):  # warm each replica's compile path
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=60)
                conn.request("POST", "/score", body=bodies[i % K])
                conn.getresponse().read()
                conn.close()
            scaler.start()

            def monitor():
                # freshness floor + fleet-size high-water, sampled through
                # flash crowd, scale events and the roll
                while not stop_monitor.is_set():
                    view = router.fleet_view()
                    f = fleet_min_freshness(view)
                    with count_lock:
                        fresh["samples"] += 1
                        max_fleet["n"] = max(max_fleet["n"],
                                             len(sup.endpoints()))
                        fresh["min_serving"] = min(fresh["min_serving"],
                                                   f["n_serving"])
                        # static base artifact => no sync seq lineage; the
                        # floor evidence is "some serving replica reports
                        # the model" at EVERY sample through the roll
                        if f["n_serving"] < 1 \
                                or f["max_age_seconds"] is None:
                            fresh["floor_held"] = False
                        if f["max_age_seconds"] is not None:
                            fresh["max_age_s"] = max(fresh["max_age_s"],
                                                     f["max_age_seconds"])
                    stop_monitor.wait(0.15)

            # diurnal open-loop schedule: send times come from the rate
            # curve alone (a slow fleet slips the schedule and that shows
            # up as achieved_qps, never as a hidden slowdown)
            def rate_at(t):
                frac = t / duration_s
                r = base_qps * (0.25 + 0.75 *
                                (0.5 - 0.5 * math.cos(2 * math.pi * frac)))
                if 0.35 <= frac < 0.55:
                    r *= 4.0  # flash crowd on the diurnal shoulder
                return r

            times = []
            t = 0.0
            while t < duration_s:
                times.append(t)
                t += 1.0 / max(rate_at(t), 0.5)
            n_requests = len(times)
            idx = {"i": 0}
            start = time.monotonic()

            def worker():
                nonlocal shed, failed
                while True:
                    with count_lock:
                        i = idx["i"]
                        if i >= n_requests:
                            return
                        idx["i"] = i + 1
                    delay = start + times[i] - time.monotonic()
                    if delay > 0:
                        time.sleep(delay)
                    # Zipf mix whose hot index drifts with the clock
                    body = bodies[(int(zipf[i % zipf.shape[0]])
                                   + int(times[i] / duration_s * K)) % K]
                    t1 = time.perf_counter()
                    try:
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", port, timeout=30)
                        conn.request("POST", "/score", body=body)
                        r = conn.getresponse()
                        r.read()
                        status = r.status
                        conn.close()
                    # pbox-lint: ignore[swallowed-exception] failure is
                    # recorded: status=-1 counts as failed below
                    except Exception:
                        status = -1
                    dt = (time.perf_counter() - t1) * 1e3
                    with count_lock:
                        if status == 200:
                            lat_ok.append(dt)
                        elif status == 429:
                            shed += 1
                        else:
                            failed += 1

            # the flash crowd is a CLOSED-loop burst on top of the
            # open-loop diurnal stream: N clients hammering back-to-back
            # for the window — the open-loop pool alone cannot saturate a
            # fast fleet, and the whole point of the window is to force
            # real queue depth/sheds so the autoscaler has something to
            # act on.  Its requests ride the same zero-failed accounting.
            def flash_crowd():
                w0 = start + 0.35 * duration_s
                w1 = start + 0.55 * duration_s
                while time.monotonic() < w0:
                    if stop_monitor.is_set():
                        return
                    time.sleep(0.05)

                def blast():
                    nonlocal shed, failed
                    while time.monotonic() < w1:
                        t1 = time.perf_counter()
                        try:
                            conn = http.client.HTTPConnection(
                                "127.0.0.1", port, timeout=10)
                            conn.request("POST", "/score", body=bodies[0])
                            r = conn.getresponse()
                            r.read()
                            status = r.status
                            conn.close()
                        # pbox-lint: ignore[swallowed-exception] recorded
                        # as a failed request below
                        except Exception:
                            status = -1
                        dt = (time.perf_counter() - t1) * 1e3
                        with count_lock:
                            if status == 200:
                                lat_ok.append(dt)
                            elif status == 429:
                                shed += 1
                            else:
                                failed += 1

                bthreads = [threading.Thread(target=blast, daemon=True)
                            for _ in range(24)]
                for b in bthreads:
                    b.start()
                for b in bthreads:
                    b.join()

            mon = threading.Thread(target=monitor, daemon=True)
            mon.start()
            crowd = threading.Thread(target=flash_crowd, daemon=True)
            crowd.start()
            threads = [threading.Thread(target=worker, daemon=True)
                       for _ in range(8)]
            for th in threads:
                th.start()

            # rolling restart MID-STREAM, concurrent with the autoscaler
            # (the roll skips any replica a scale action retires under it)
            time.sleep(duration_s * 0.25)
            log("elastic: rolling restart starting mid-stream")
            rolled = scaler.rolling_restart(freshness_max_age_s=3600.0,
                                            replica_timeout_s=300.0)
            log(f"elastic: rolled replicas {rolled}")
            for th in threads:
                th.join(timeout=duration_s + 300)
            crowd.join(timeout=duration_s + 300)
            wall = time.monotonic() - start

            # idle tail: with the load gone, the down-streak + cooldown
            # must produce the drain-retire if the flash crowd's spawn
            # hasn't already been retired during the diurnal trough
            ac = telemetry.counter("fleet.autoscale")
            t0 = time.monotonic()
            while ac.value(direction="up") >= 1 \
                    and ac.value(direction="down") < 1 \
                    and time.monotonic() - t0 < 90:
                time.sleep(0.5)
        finally:
            stop_monitor.set()
            scaler.stop()
            router.stop()
            sup.stop()

    lat_ok.sort()
    n_ok = len(lat_ok)
    autoscale = telemetry.counter("fleet.autoscale")
    rolls = telemetry.counter("fleet.rolls")
    res.update({
        "requests": n_ok + shed + failed,
        "ok": n_ok,
        "shed": shed,
        "failed_requests": failed,
        "zero_failed": failed == 0,
        "p50_ms": round(lat_ok[n_ok // 2], 2) if n_ok else None,
        "p99_ms": round(lat_ok[_rank(0.99, n_ok)], 2) if n_ok else None,
        "achieved_qps": round((n_ok + shed + failed) / wall, 1),
        "autoscale_up": int(autoscale.value(direction="up")),
        "autoscale_down": int(autoscale.value(direction="down")),
        "retired_replicas": int(
            telemetry.counter("fleet.retires").value()),
        "max_fleet_size": max_fleet["n"],
        "rolled_replicas": rolled,
        "rolls_ok": int(rolls.value(outcome="ok")),
        "rolls_skipped": int(rolls.value(outcome="skipped")),
        "freshness_floor_held": fresh["floor_held"],
        "freshness_max_age_s": round(fresh["max_age_s"], 1),
        "freshness_min_serving": fresh["min_serving"],
        "freshness_samples": fresh["samples"],
    })
    log(f"elastic: {n_ok} ok / {shed} shed / {failed} FAILED of "
        f"{res['requests']} @ {res['achieved_qps']} qps; p50 "
        f"{res['p50_ms']}ms p99 {res['p99_ms']}ms; up "
        f"{res['autoscale_up']} down {res['autoscale_down']} "
        f"max_fleet {res['max_fleet_size']}; rolled {rolled}; "
        f"freshness floor held={res['freshness_floor_held']}")
    res.update(_elastic_reshard_pin(n_slots, dense))
    if res.get("reshard_bit_exact") is not None:
        log(f"elastic: reshard pin {res['reshard_old_shards']}->"
            f"{res['reshard_new_shards']} moved "
            f"{res['reshard_moved_rows']} rows in "
            f"{res['reshard_seconds']}s bit_exact="
            f"{res['reshard_bit_exact']}")
    return res


def stage_elastic(backend, args) -> None:
    res = bench_elastic(duration_s=args.elastic_seconds,
                        base_qps=args.elastic_qps)
    emit({"metric": "elastic_fleet_p99_ms", "value": res.get("p99_ms"),
          "unit": "ms p99 (diurnal open loop; autoscale + drain-retire + "
                  "rolling restart mid-stream)", "vs_baseline": None,
          "backend": _REPLICA_BACKEND, **res})


def bench_streaming(duration_s: float = 10.0, rate: float = 500.0,
                    max_staleness_s: float = 1.5, n_slots: int = 2,
                    dense: int = 2, bsz: int = 16) -> dict:
    """Streaming online-learning loop (ISSUE 8): a synthetic append-rate
    stream tailed by a TailingFileSource, trained in mini-pass windows by
    StreamingTrainer, published on the max-staleness deadline, hot-applied
    by a real Syncer into a live ScoringServer, with a probe scoring the
    served model throughout.  Reports the freshness distribution the loop
    actually delivered (event-time -> served-score p50/p99 from
    ``stream.freshness_seconds``), the mini-pass device-idle gap, the
    deadline-miss count and the trained samples/s — CPU-admissible (the
    loop is host/IO-bound; the ROADMAP bench caveat applies)."""
    import threading
    import urllib.request

    from paddlebox_tpu import telemetry
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.feed import BatchBuilder
    from paddlebox_tpu.data.slot_parser import SlotParser
    from paddlebox_tpu.data.synth import make_synth_config, stream_line
    from paddlebox_tpu.inference import ScoringServer
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.serving_sync import Publisher, Syncer
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.streaming import (
        DeadlinePublishPolicy,
        MiniPassScheduler,
        StreamingTrainer,
        TailingFileSource,
    )
    from paddlebox_tpu.streaming.minipass import MiniPassWindow, WindowDataset
    from paddlebox_tpu.train.trainer import Trainer

    rng = np.random.default_rng(0)
    conf = make_synth_config(n_sparse_slots=n_slots, dense_dim=dense,
                             batch_size=bsz, max_feasigns_per_ins=8)
    tconf = SparseTableConfig(embedding_dim=4, learning_rate=0.3,
                              store_buckets=8, plan_scratch_rows=64)
    model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense, hidden=(8,))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 12),
                      seed=0)

    def synth_line() -> str:
        return stream_line(rng, int(rng.integers(0, 2)),
                           n_sparse_slots=n_slots, dense_dim=dense,
                           vocab_per_slot=50)

    res: dict = {}
    with tempfile.TemporaryDirectory() as td:
        root = os.path.join(td, "publish")
        stream = os.path.join(td, "stream")
        os.makedirs(stream)

        # warm pass anchors the delta chain; jit/export warmup off-clock
        warm = [synth_line() for _ in range(4 * bsz)]
        block = SlotParser(conf).parse_lines(warm)
        w0 = MiniPassWindow(0, block, np.unique(block.keys), len(warm),
                            time.time(), time.time(), "warm", time.time())
        table.begin_pass(w0.census)
        trainer.train_from_dataset(WindowDataset(w0, BatchBuilder(conf)),
                                   table)
        table.end_pass()
        pub = Publisher(root, staging_dir=os.path.join(td, "staging"))
        pub.publish_base("base", model, trainer.params, table,
                         lineage="warmup", batch_size=bsz,
                         key_capacity=bsz * conf.max_feasigns_per_ins,
                         dense_dim=dense, feed_conf=conf)

        server = ScoringServer()
        syncer = Syncer(root, server, "live",
                        cache_dir=os.path.join(td, "cache"),
                        poll_interval_s=0.05)
        syncer.poll_once()
        syncer.start()
        port = server.start(port=0)
        probe = synth_line().encode()

        source = TailingFileSource(stream, poll_interval_s=0.02)
        sched = MiniPassScheduler(source, conf, window_records=4 * bsz,
                                  window_seconds=0.5)
        policy = DeadlinePublishPolicy(pub, max_staleness_s,
                                       scheduler=sched)
        runner = StreamingTrainer(
            trainer, table, sched, policy=policy, model=model,
            served_seq_fn=lambda: (server.model_version("live")
                                   or {}).get("seq"),
        )
        source.start()
        sched.start()

        scores_ok = [0]

        def writer():
            t0 = time.monotonic()
            with open(os.path.join(stream, "part-000"), "w",
                      buffering=1) as fh:
                while time.monotonic() - t0 < duration_s:
                    fh.write(synth_line())
                    time.sleep(1.0 / rate)
            runner.stop()

        def prober():
            while not runner._stop_evt.is_set():
                try:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{port}/score/live", data=probe,
                        method="POST")
                    with urllib.request.urlopen(req, timeout=10) as r:
                        r.read()
                    scores_ok[0] += 1
                # pbox-lint: ignore[swallowed-exception] liveness probe
                # during replica churn: only successes count, by design
                except Exception:
                    pass
                time.sleep(0.2)

        threading.Thread(target=writer, daemon=True).start()
        threading.Thread(target=prober, daemon=True).start()
        t0 = time.perf_counter()
        summary = runner.run()
        dt = time.perf_counter() - t0
        syncer.stop()
        server.stop()

    from paddlebox_tpu.telemetry.metrics import Histogram

    def _hist_ms(name):
        m = telemetry.registry.get(name)
        if not isinstance(m, Histogram):
            return {}
        s = m.summary()
        if not s["count"]:
            return {}
        return {"count": s["count"],
                "p50_ms": round((s["p50"] or 0) * 1e3, 2),
                "p99_ms": round((s["p99"] or 0) * 1e3, 2)}

    fresh = _hist_ms("stream.freshness_seconds")
    gap = _hist_ms("pass.boundary_gap_seconds")
    res.update(
        windows=summary["windows"],
        records=summary["records"],
        publishes=summary["publishes"],
        deadline_misses=summary["deadline_misses"],
        backpressure_widenings=summary["backpressure_widenings"],
        samples_per_sec=round(summary["records"] / max(dt, 1e-9), 1),
        freshness_p50_ms=fresh.get("p50_ms"),
        freshness_p99_ms=fresh.get("p99_ms"),
        freshness_confirms=fresh.get("count", 0),
        minipass_gap_p50_ms=gap.get("p50_ms"),
        minipass_gap_p99_ms=gap.get("p99_ms"),
        served_probe_ok=scores_ok[0],
        auc=summary.get("auc"),
    )
    log(f"streaming: {res['windows']} windows / {res['records']} records "
        f"@ {res['samples_per_sec']} samples/s, freshness p50 "
        f"{res['freshness_p50_ms']} ms p99 {res['freshness_p99_ms']} ms "
        f"({res['freshness_confirms']} served confirms), gap p50 "
        f"{res['minipass_gap_p50_ms']} ms, {res['deadline_misses']} "
        f"deadline misses, {res['served_probe_ok']} probe scores ok")
    return res


def stage_streaming(backend, args) -> None:
    res = bench_streaming(duration_s=args.stream_seconds,
                          rate=args.stream_rate,
                          max_staleness_s=args.stream_staleness)
    emit({"metric": "streaming_freshness_p99_ms",
          "value": res.get("freshness_p99_ms"),
          "unit": "ms p99 (event-time -> served score)",
          "vs_baseline": None, "backend": backend,
          "telemetry": telemetry_summary(), **res})


def step_cost_for_config(tconf, trconf, n_slots, dense, bsz, hidden,
                         vocab) -> dict:
    """XLA cost analysis (FLOPs / bytes per CALL) of the jitted step at an
    arbitrary config — one AOT lower+compile on a throwaway tiny dataset,
    executed zero times.  Used where the measured loop compiles a
    different program shape (the sustained bench's scan/prefetch path) but
    the per-step work is the same.  With ``trconf.scan_steps > 1`` the
    SCAN program is compiled and analyzed — the returned figures cover one
    k-step call; divide via util_fields(steps_per_call=k)."""
    import numpy as _np

    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import (
        Trainer,
        _host_batch_dict,
        _to_device,
    )

    ds = None
    with tempfile.TemporaryDirectory() as td:
        try:
            conf, ds, _ = build_data(td, n_slots, dense, bsz, 2 * bsz, vocab)
            model = CtrDnn(n_slots, tconf.row_width, dense_dim=dense,
                           hidden=hidden)
            table = SparseTable(tconf, seed=0)
            table.begin_pass(ds.unique_keys())
            trainer = Trainer(model, tconf, trconf, seed=0)
            b = next(ds.batches(drop_last=True))
            plan = table.plan_batch(b)
            host = _host_batch_dict(b, plan, b.n_sparse_slots)
            step_fn = trainer._build_step()  # also sets _step_body
            k = trconf.scan_steps
            if k > 1:
                stacked = _to_device(
                    {key: _np.stack([v] * k) for key, v in host.items()}
                )
                compiled = trainer._build_scan_step().lower(
                    trainer.params, trainer.opt_state, table.values,
                    table.g2sum, trainer._init_mstate(), stacked).compile()
            else:
                compiled = step_fn.lower(
                    trainer.params, trainer.opt_state, table.values,
                    table.g2sum, trainer._init_mstate(),
                    _to_device(host)).compile()
            table.end_pass()
            return _cost_analysis(compiled)
        except Exception as e:  # pragma: no cover - backend-dependent
            log(f"cost-for-config unavailable ({e!r})")
            return {}
        finally:
            if ds is not None:
                ds.close()


def stage_headline(backend, args, tconf, trconf, n_slots, dense, bsz, n_ins,
                   hidden, model_name: str, with_naive: bool) -> None:
    """The headline (or one model-zoo) measurement: bench_ours with the
    partial emit BEFORE the naive baseline, so a naive OOM/SIGKILL (which
    no try/except can catch) still leaves the ours line on stdout.  The
    ONE body behind both `python bench.py [--model X]` and run_all —
    single-metric CLI and --all capture cannot drift."""
    import dataclasses

    with tempfile.TemporaryDirectory() as td:
        conf, ds, _, model = _data_and_model(
            td, args, tconf, n_slots, dense, bsz, n_ins, hidden, model_name)
        try:
            ours, cost = bench_ours(ds, tconf, trconf, model)
            path = "plain"
            best_cost, best_spc = cost, 1  # cost analysis of the WINNING
            # program + its steps-per-call divisor (scan programs cover k
            # steps per call)
            util = util_fields(cost, ours, bsz)
            # partial emit FIRST: everything after this (scan variant,
            # naive) can die to an uncatchable OOM/SIGKILL without losing
            # the measured number — the driver parses the LAST line
            emit({"metric": f"{model_name}_samples_per_sec",
                  "value": round(ours, 1), "unit": "samples/sec",
                  "vs_baseline": None, "backend": backend, "path": path,
                  **util})
            naive = float("nan")
            if with_naive:
                # the true headline additionally tries the production path
                # (prefetch + scan dispatch): it wins when dispatch latency
                # dominates and loses when the scan program is slow on the
                # day's backend — report the best honest number, labeled
                # by "path" (same model/data/work; only the driver loop
                # differs).  Zoo rows stay single-pass for run_all time;
                # this measurement also stands in for a dedicated
                # trainer-path stage (its own metric line below).
                # two variants, not one: prefetch+scan8 and prefetch+scan1.
                # If scan8 loses while scan1 matches the plain loop, the
                # scan PROGRAM is slow on this backend; if both lose, the
                # prefetch overlap itself is broken (r4's open 3x question
                # — see also device_profile's h2d_during_step_ms).
                for scan_k in (8, 1):
                    try:
                        sps2 = bench_trainer_path(
                            ds, tconf,
                            dataclasses.replace(trconf, scan_steps=scan_k),
                            model)
                        suffix = "" if scan_k == 8 else f"_scan{scan_k}"
                        emit({"metric":
                              f"{model_name}_trainer_path{suffix}"
                              "_samples_per_sec",
                              "value": round(sps2, 1), "unit": "samples/sec",
                              "vs_baseline": None, "backend": backend})
                        if sps2 > ours:
                            ours, path = sps2, f"scan{scan_k}"
                            if scan_k > 1:
                                # MFU/HBM-util must come from the program
                                # that actually won — the scan program's
                                # own cost analysis, per k-step call —
                                # not the plain step's (ADVICE r5)
                                sc = step_cost_for_config(
                                    tconf,
                                    dataclasses.replace(
                                        trconf, scan_steps=scan_k),
                                    n_slots, dense, bsz, hidden, args.vocab)
                                if sc:
                                    best_cost, best_spc = sc, scan_k
                            else:
                                best_cost, best_spc = cost, 1
                            util = util_fields(best_cost, ours, bsz,
                                               steps_per_call=best_spc)
                            emit({"metric": f"{model_name}_samples_per_sec",
                                  "value": round(ours, 1),
                                  "unit": "samples/sec", "vs_baseline": None,
                                  "backend": backend, "path": path, **util})
                    except Exception as e:
                        log(f"trainer-path scan={scan_k} failed: {e!r}")
                log(f"headline path: {path} ({ours:,.0f} samples/s)")
                try:
                    naive = bench_naive(ds, tconf, trconf, hidden)
                except Exception as e:
                    log(f"naive baseline failed: {e!r}")
        finally:
            ds.close()  # run_all continues after a stage failure: don't
            # leak the dataset's reader thread pools into later stages
    if with_naive:
        vs = round(ours / naive, 3) if np.isfinite(naive) and naive > 0 \
            else None
        emit({"metric": f"{model_name}_samples_per_sec",
              "value": round(ours, 1), "unit": "samples/sec",
              "vs_baseline": vs, "backend": backend, "path": path,
              **util_fields(best_cost, ours, bsz, steps_per_call=best_spc),
              "telemetry": telemetry_summary()})


def stage_device_profile(backend, args, tconf, trconf, n_slots, dense, bsz,
                         n_ins, hidden, scan_k: int) -> None:
    with tempfile.TemporaryDirectory() as td:
        conf, ds, _, model = _data_and_model(
            td, args, tconf, n_slots, dense, bsz, n_ins, hidden, args.model)
        try:
            prof = device_profile(ds, tconf, trconf, model, scan_k=scan_k)
        finally:
            ds.close()
    emit({"metric": f"{args.model}_device_profile", "value": prof["step_ms"],
          "unit": "ms/step", "vs_baseline": None, "backend": backend, **prof})


def stage_trainer_path(backend, args, tconf, trconf, n_slots, dense, bsz,
                       n_ins, hidden) -> None:
    with tempfile.TemporaryDirectory() as td:
        conf, ds, _, model = _data_and_model(
            td, args, tconf, n_slots, dense, bsz, n_ins, hidden, args.model)
        try:
            sps = bench_trainer_path(ds, tconf, trconf, model)
        finally:
            ds.close()
    emit({"metric": f"{args.model}_trainer_path_samples_per_sec",
          "value": round(sps, 1), "unit": "samples/sec", "vs_baseline": None,
          "backend": backend, "telemetry": telemetry_summary()})


def stage_health(backend, args, tconf, trconf, n_slots, dense, bsz,
                 n_ins, hidden) -> None:
    """Run-health smoke: a short multi-pass training run with ONE injected
    degradation — a fault-plan pass whose batches are label-poisoned to
    NaN (site ``train.nan``, nan_policy=skip_batch) — and a hard assert
    that the health monitor converts it into an alert.  The row carries
    the monitor snapshot, the alert must show up in this row's telemetry
    counter summary (``health.alerts{...}``), and emit() lands the same
    row in BENCH_HISTORY.jsonl, so the smoke proves the whole plane:
    signal -> rule -> counter -> row -> history."""
    import dataclasses

    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.telemetry import get_monitor
    from paddlebox_tpu.train.trainer import Trainer
    from paddlebox_tpu.utils import faults

    monitor = get_monitor()
    trconf = dataclasses.replace(trconf, nan_policy="skip_batch",
                                 check_nan_inf=True, scan_steps=1)
    n_passes = max(monitor.warmup + 3, 6)
    bad_pass = n_passes - 2  # after warmup: the alert must fire, not bed in
    with tempfile.TemporaryDirectory() as td:
        conf, ds, _, model = _data_and_model(
            td, args, tconf, n_slots, dense, bsz, 6 * bsz, hidden,
            args.model)
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf, trconf, seed=0)
        try:
            for p in range(n_passes):
                table.begin_pass(ds.unique_keys())
                if p == bad_pass:
                    faults.install(faults.FaultPlan(
                        {"train.nan": "p:1.0"}, seed=0))
                try:
                    trainer.train_from_dataset(ds, table, drop_last=True)
                finally:
                    faults.clear()
                table.end_pass()
        finally:
            ds.close()
    snap = monitor.snapshot()
    alerts = [a["rule"] for a in snap.get("recent", [])]
    log(f"health smoke: {snap['alerts_total']} alert(s) over "
        f"{snap['windows']} window(s): {sorted(set(alerts))}")
    if not snap["alerts_total"]:
        raise RuntimeError(
            "health smoke failed: injected train.nan degradation fired "
            "no alert — the run-health plane is not watching")
    tele = telemetry_summary()
    if not any(k.startswith("health.alerts") for k in tele["counters"]):
        raise RuntimeError(
            "health smoke failed: alert fired but health.alerts{...} "
            "is missing from the row's telemetry counter summary")
    emit({"metric": "health_smoke_alerts",
          "value": snap["alerts_total"], "unit": "alerts",
          "vs_baseline": None, "backend": backend,
          "health": snap, "telemetry": tele})


def stage_ops(backend, args) -> None:
    """Per-op micro-benchmarks of the CTR op zoo on the live backend — the
    analog of the reference's op_tester harness
    (operators/benchmark/op_tester.cc): one jitted call per op at bench
    shapes, ms per call."""
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.ops import (
        fused_concat,
        fused_seqpool_cvm,
        rank_attention,
    )
    from paddlebox_tpu.ops.seqpool_cvm import (
        fused_seqpool_cvm_with_conv,
        fused_seqpool_cvm_with_pcoc,
    )

    rng = np.random.default_rng(0)
    B, S, W = 2048, args.slots, args.emb + 2
    K = B * S * 4
    rows = jnp.asarray(np.abs(rng.normal(size=(K, W))).astype(np.float32))
    rows_conv = jnp.asarray(
        np.abs(rng.normal(size=(K, W + 1))).astype(np.float32))
    rows_pcoc = jnp.asarray(
        np.abs(rng.normal(size=(K, W + 3))).astype(np.float32))
    segs = jnp.asarray(np.sort(rng.integers(0, B * S, K)).astype(np.int32))

    N, F, C, MR = 2048, 64, 32, 3
    x = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
    ro = np.full((N, 2 * MR + 1), -1, np.int32)
    ro[:, 0] = rng.integers(1, MR + 1, N)
    ro[:, 2] = rng.integers(0, N, N)
    ro[:, 1] = rng.integers(1, MR + 1, N)
    rparam = jnp.asarray(
        rng.normal(size=(MR * MR * F, C)).astype(np.float32))
    ro = jnp.asarray(ro)
    parts = [jnp.asarray(rng.normal(size=(B, 37)).astype(np.float32))
             for _ in range(4)]

    ops = {
        "fused_seqpool_cvm": (
            jax.jit(lambda r, s: fused_seqpool_cvm(r, s, B, S)), (rows, segs)),
        "seqpool_cvm_conv": (
            jax.jit(lambda r, s: fused_seqpool_cvm_with_conv(
                r, s, B, S, cvm_offset=3)), (rows_conv, segs)),
        "seqpool_cvm_pcoc": (
            jax.jit(lambda r, s: fused_seqpool_cvm_with_pcoc(
                r, s, B, S, pclk_num=1)), (rows_pcoc, segs)),
        "rank_attention": (
            jax.jit(lambda a, b, c: rank_attention(a, b, c, MR)),
            (x, ro, rparam)),
        "fused_concat": (
            jax.jit(lambda a, b, c, d: fused_concat(
                [a, b], [c, d],
                [(0, i) for i in range(16)] + [(1, i) for i in range(16)],
            )), tuple(parts)),
    }
    res = {}
    for name, (fn, fa) in ops.items():
        try:
            out = fn(*fa)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(50):
                out = fn(*fa)
            jax.block_until_ready(out)
            res[name] = round((time.perf_counter() - t0) / 50 * 1e3, 3)
            log(f"op {name}: {res[name]:.3f} ms")
        except Exception as e:
            log(f"op {name} failed: {e!r}")
            res[name] = None
    # "value" is ALWAYS fused_seqpool_cvm (the canonical hot op) so the
    # field means the same thing run-to-run; the per-op keys carry every
    # other measurement even when the canonical one failed (null)
    emit({"metric": "ctr_op_microbench",
          "value": res.get("fused_seqpool_cvm"),
          "unit": "ms", "vs_baseline": None, "backend": backend, **res})
    failed = [name for name, ms in res.items() if ms is None]
    if failed:
        raise RuntimeError(f"ops failed: {failed}")


def _data_and_model(td, args, tconf, n_slots, dense, bsz, n_ins, hidden,
                    model_name: str):
    model, n_tl = make_model(model_name, n_slots, tconf.row_width, dense,
                             hidden)
    conf, ds, parse_s = build_data(td, n_slots, dense, bsz, n_ins,
                                   args.vocab, n_task_labels=n_tl)
    return conf, ds, parse_s, model


def stage_models(backend, args, tconf, trconf, n_slots, dense, bsz, n_ins,
                 hidden) -> None:
    """The model-zoo sweep on its own: one measured samples/s row per
    BASELINE.json zoo model (DeepFM, Wide&Deep fused-seqpool, xDeepFM, DCN,
    MMoE) without paying for the full --all stage list.  Rows land in
    BENCH_HISTORY.jsonl with run identity, so tools/bench_trend.py gates
    their trend like any other metric."""
    failed = []
    for name in ("deepfm", "widedeep", "xdeepfm", "dcn", "mmoe"):
        t0 = time.perf_counter()
        try:
            stage_headline(backend, args, tconf, trconf, n_slots, dense,
                           bsz, n_ins, hidden, model_name=name,
                           with_naive=False)
            log(f"== model {name} done in {time.perf_counter() - t0:.0f}s")
        except Exception as e:
            log(f"== model {name} FAILED: {e!r}")
            failed.append(name)
            emit({"metric": f"{name}_samples_per_sec", "value": None,
                  "unit": "error", "vs_baseline": None, "backend": backend,
                  "error": repr(e)[:200]})
    if failed:
        raise RuntimeError(f"models failed: {failed}")


def bench_retrieval(qps: float = 50.0, duration_s: float = 6.0,
                    n_slots: int = 4, dense: int = 4, emb: int = 16,
                    vocab: int = 200, n_queries: int = 64,
                    k: int = 10) -> dict:
    """The retrieval serving row: train a TwoTower over synth data,
    publish the item-tower ANN artifact (publish_ann_base), hot-sync it
    into a live ScoringServer and drive open-loop /retrieve traffic
    THROUGH the fleet router — p50/p99/QPS of the full client path plus
    the int8-coarse-tier recall@10 against the exact scorer on the same
    query set."""
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import ScoringServer
    from paddlebox_tpu.inference.ann import AnnIndex
    from paddlebox_tpu.models import TwoTower
    from paddlebox_tpu.scenarios import MultiScenarioTrainer, ScenarioSpec
    from paddlebox_tpu.serving_fleet import FleetRouter
    from paddlebox_tpu.serving_sync import Publisher, Syncer
    from paddlebox_tpu.sparse.table import SparseTable

    B = 64
    res: dict = {"duration_s": duration_s, "k": k}
    with tempfile.TemporaryDirectory() as td:
        conf = make_synth_config(n_sparse_slots=n_slots, dense_dim=dense,
                                 batch_size=B, max_feasigns_per_ins=16)
        files = write_synth_files(
            td, n_files=2, ins_per_file=512, n_sparse_slots=n_slots,
            vocab_per_slot=vocab, dense_dim=dense, seed=13,
        )
        tconf = SparseTableConfig(embedding_dim=emb, learning_rate=0.5,
                                  initial_range=0.05)
        table = SparseTable(tconf, seed=0)
        item_slot = n_slots - 1
        model = TwoTower(n_sparse_slots=n_slots, emb_width=tconf.row_width,
                         item_slots=(item_slot,), dense_dim=dense,
                         hidden=(64, 32), temperature=0.05)
        mst = MultiScenarioTrainer(tconf, [ScenarioSpec(
            "retrieval", model, kind="retrieval",
            trainer_conf=TrainerConfig(dense_lr=3e-3, auc_buckets=1 << 12),
            seed=3,
        )])
        ds = PadBoxSlotDataset(conf, read_threads=2)
        ds.set_filelist(files)
        ds.load_into_memory()
        t0 = time.perf_counter()
        metrics = mst.train_pass({"retrieval": ds}, table)["retrieval"]
        res["train_samples_per_sec"] = round(
            metrics["samples"] / max(metrics["duration_s"], 1e-9), 1)
        res["train_auc"] = round(metrics.get("auc", 0.0), 4)
        ds.close()
        root = os.path.join(td, "pub")
        pub = Publisher(root, staging_dir=os.path.join(td, "stage"))
        lo, hi = item_slot * vocab + 1, (item_slot + 1) * vocab
        pub.publish_ann_base("r0", table, item_key_lo=lo, item_key_hi=hi,
                             meta={"scenario": "retrieval"})
        res["publish_s"] = round(time.perf_counter() - t0, 2)

        rng = np.random.default_rng(7)
        q = rng.normal(size=(n_queries, emb)).astype(np.float32)
        idx = AnnIndex.load(os.path.join(root, "base-r0"))
        res["n_items"] = idx.n_items
        ek, _ = idx.search(q, k=k, tier="exact")
        qk, _ = idx.search(q, k=k, tier="int8")
        res["recall_at_k_int8"] = round(float(np.mean([
            len(set(ek[i]) & set(qk[i])) / k for i in range(n_queries)
        ])), 4)

        srv = ScoringServer()
        syncer = Syncer(root, srv, "retrieval",
                        cache_dir=os.path.join(td, "cache"),
                        poll_interval_s=0.05)
        syncer.poll_once()
        port = srv.start(port=0, host="127.0.0.1")
        router = FleetRouter([f"127.0.0.1:{port}"])
        rport = router.start(port=0, host="127.0.0.1")
        try:
            body = json.dumps(
                {"queries": q[:8].tolist(), "k": k, "tier": "int8"}
            ).encode()
            load = _open_loop_http(rport, body, qps, duration_s,
                                   path="/retrieve/retrieval")
            res.update({f"router_{kk}": vv for kk, vv in load.items()})
        finally:
            router.stop()
            srv.stop()
    return res


def stage_retrieval(backend, args) -> None:
    res = bench_retrieval(qps=args.retrieval_qps,
                          duration_s=args.retrieval_seconds)
    emit({"metric": "retrieval_router_p99_ms",
          "value": res.get("router_p99_ms"),
          "unit": "ms p99 (8-query /retrieve, int8 tier)",
          "vs_baseline": None, "backend": backend, **res,
          "telemetry": telemetry_summary()})


def run_all(backend, args, tconf, trconf, n_slots, dense, bsz, n_ins,
            hidden) -> None:
    """Every measurement in ONE process (the chip has one owner, and one
    backend init serves them all).  Stages are isolated — a stage failure
    logs, emits its error row and moves on so one bad path can't cost the
    rest of the run — and any failure makes the run exit non-zero."""
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig

    failed = []

    def stage(name, fn, *a, **kw):
        t0 = time.perf_counter()
        try:
            fn(*a, **kw)
            log(f"== stage {name} done in {time.perf_counter() - t0:.0f}s")
        except Exception as e:
            log(f"== stage {name} FAILED: {e!r}")
            failed.append(name)
            emit({"metric": name, "value": None, "unit": "error",
                  "vs_baseline": None, "backend": backend,
                  "error": repr(e)[:200]})

    common = (backend, args, tconf, trconf, n_slots, dense, bsz, n_ins,
              hidden)
    stage("headline", stage_headline, *common, model_name="ctr_dnn",
          with_naive=True)
    stage("pass_boundary", stage_pass_boundary, *common)
    stage("hbm_cache", stage_hbm_cache, *common)
    stage("hostplane", stage_hostplane, *common)
    stage("device_profile", stage_device_profile, *common, scan_k=8)
    stage("ops", stage_ops, backend, args)
    stage("serving", stage_serving, backend)
    for name in ("deepfm", "widedeep", "xdeepfm", "dcn", "mmoe"):
        stage(f"zoo_{name}", stage_headline, *common, model_name=name,
              with_naive=False)

    def sustained():
        ns_tconf = SparseTableConfig(embedding_dim=16)
        ns_trconf = TrainerConfig(auc_buckets=1 << 20)
        sps = bench_sustained(
            4, ns_tconf, ns_trconf, 26, dense, bsz, 40 * bsz, hidden,
            profile=False, vocab_per_slot=1_000_000,
        )
        row = {"metric": "ctr_dnn_sustained_northstar_samples_per_sec",
               "value": round(sps, 1), "unit": "samples/sec",
               "vs_baseline": None, "backend": backend,
               "shape": "26 slots, emb 16, vocab 1e6, 4 passes",
               "telemetry": telemetry_summary()}
        # partial emit FIRST: the cost-analysis compile below can die to
        # an uncatchable OOM — never lose the measured number
        emit(row)
        cost = step_cost_for_config(ns_tconf, ns_trconf, 26, dense, bsz,
                                    hidden, 1_000_000)
        if cost:
            emit({**row, **util_fields(cost, sps, bsz)})

    stage("sustained_northstar", sustained)
    if failed:
        raise SystemExit(f"stages failed: {failed}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sustained", type=int, default=0, metavar="N_PASSES",
                    help="sustained multi-pass bench with preload overlap")
    ap.add_argument("--profile", action="store_true",
                    help="with --sustained: one more pass with the per-stage report")
    ap.add_argument("--compute-dtype", default="",
                    choices=["", "float32", "bfloat16"],
                    help="dense tower compute dtype (default: flags)")
    ap.add_argument("--trainer-path", action="store_true",
                    help="bench Trainer.train_from_dataset (prefetch+scan)")
    ap.add_argument("--scan", type=int, default=8,
                    help="scan_steps for --trainer-path")
    ap.add_argument("--model", default="ctr_dnn",
                    choices=["ctr_dnn", "deepfm", "widedeep", "xdeepfm",
                             "dcn", "mmoe"],
                    help="benchmark model (BASELINE.json model zoo)")
    ap.add_argument("--device-profile", action="store_true",
                    help="isolate host/H2D/step/scan stage timings")
    ap.add_argument("--pass-boundary", action="store_true",
                    help="serial vs overlapped pass-lifecycle ablation: "
                         "inter-pass device-idle gap, multi-pass samples/s "
                         "and bit-exactness of the two stores")
    ap.add_argument("--hbm-cache", action="store_true",
                    help="uncached vs HBM-cached pass lifecycle on a "
                         "skewed (Zipf) key stream: begin-pass promotion "
                         "patch rows, hit rate, inter-pass gap and "
                         "bit-exactness of the two stores")
    ap.add_argument("--hostplane", action="store_true",
                    help="host-plane hybrid-parallelism ablation: census "
                         "wire bytes/pass over a simulated 2-rank fleet "
                         "(hash vs planned placement, raw vs varint "
                         "codec), shuffle key-column compression, gather "
                         "p50/p99, and the bit-exact planned-vs-hash "
                         "trained-store check")
    ap.add_argument("--ops", action="store_true",
                    help="per-op micro-benchmarks of the CTR op zoo")
    ap.add_argument("--serving", action="store_true",
                    help="serving-path p50/p99 latency + QPS per shape "
                         "bucket (ScoringServer.score_lines + loopback "
                         "HTTP)")
    ap.add_argument("--fleet", action="store_true",
                    help="serving-fleet SLO run: open-loop QPS through "
                         "the health-checked router over 3 replica "
                         "processes while one is SIGKILLed mid-stream — "
                         "p50/p99, shed counts and the hard "
                         "zero-failed-requests check")
    ap.add_argument("--fleet-qps", type=float, default=25.0,
                    help="open-loop target QPS for --fleet")
    ap.add_argument("--fleet-seconds", type=float, default=12.0,
                    help="load duration for --fleet")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic-fleet run: diurnal open-loop load with "
                         "a flash crowd and Zipf request drift against a "
                         "live FleetAutoscaler (scale-up, drain-retire) "
                         "plus a rolling restart mid-stream — zero failed "
                         "requests, bounded p99, freshness floor held; "
                         "the row also carries the live-reshard "
                         "bit-exactness pin")
    ap.add_argument("--elastic-qps", type=float, default=10.0,
                    help="diurnal base QPS for --elastic (the flash "
                         "crowd peaks at 4x this)")
    ap.add_argument("--elastic-seconds", type=float, default=24.0,
                    help="load duration for --elastic")
    ap.add_argument("--qps-sweep", default="",
                    metavar="Q1,Q2,...",
                    help="open-loop QPS sweep: with --serving drive one "
                         "live ScoringServer (batched AND max_batch=1 "
                         "baselines) at each target, with --fleet drive "
                         "the 3-replica router; one emitted row per "
                         "point (p50/p99/shed/achieved) — the "
                         "p50/p99-vs-QPS curve")
    ap.add_argument("--sweep-seconds", type=float, default=6.0,
                    help="load duration per --qps-sweep point")
    ap.add_argument("--quantized", action="store_true",
                    help="quantized embedding artifacts: fp32 vs int8 "
                         "vs fp8 sparse payload bytes + synthetic-CTR "
                         "AUC delta")
    ap.add_argument("--storage", action="store_true",
                    help="durable cold tier ablation: full vs incremental "
                         "checkpoints (bytes+seconds per save, restore "
                         "time vs table/delta rows, census disk-reject "
                         "rate); one JSON row per arm")
    ap.add_argument("--streaming", action="store_true",
                    help="streaming online-learning loop: synthetic "
                         "append-rate stream -> StreamingTrainer -> "
                         "deadline publish_delta -> Syncer'd "
                         "ScoringServer; freshness p50/p99 (event-time "
                         "-> served score), mini-pass gap, deadline "
                         "misses, samples/s")
    ap.add_argument("--stream-seconds", type=float, default=10.0,
                    help="live-stream duration for --streaming")
    ap.add_argument("--stream-rate", type=float, default=500.0,
                    help="append rate (records/s) for --streaming")
    ap.add_argument("--stream-staleness", type=float, default=1.5,
                    help="freshness budget (s) for --streaming")
    ap.add_argument("--models", action="store_true",
                    help="model-zoo sweep: one measured samples/s row per "
                         "BASELINE.json zoo model (deepfm, widedeep, "
                         "xdeepfm, dcn, mmoe) without the rest of --all")
    ap.add_argument("--retrieval", action="store_true",
                    help="retrieval serving row: train a TwoTower, "
                         "publish the ANN item artifact, hot-sync it and "
                         "drive open-loop /retrieve through the fleet "
                         "router — p50/p99/QPS + int8-tier recall@10 vs "
                         "the exact scorer")
    ap.add_argument("--retrieval-qps", type=float, default=50.0,
                    help="open-loop target QPS for --retrieval")
    ap.add_argument("--retrieval-seconds", type=float, default=6.0,
                    help="load duration for --retrieval")
    ap.add_argument("--health", action="store_true",
                    help="run-health smoke: short multi-pass training run "
                         "with one injected degradation (a NaN-poisoned "
                         "pass); asserts the health monitor fires and the "
                         "alert lands in the row's telemetry summary and "
                         "BENCH_HISTORY.jsonl")
    ap.add_argument("--all", action="store_true",
                    help="one process, every measurement: headline (plain "
                         "AND scan trainer path) + naive, device profile, "
                         "op micro-bench, model zoo, sustained "
                         "north-star — one JSON line each")
    ap.add_argument("--slots", type=int, default=16,
                    help="sparse slots (north-star sustained shape: 26)")
    ap.add_argument("--emb", type=int, default=8,
                    help="embedding_dim (north-star sustained shape: 16)")
    ap.add_argument("--vocab", type=int, default=100_000,
                    help="per-slot vocab (north-star: 1000000)")
    ap.add_argument("--hidden", default="512,256,128",
                    help="dense tower widths, comma-separated (bf16-vs-f32 "
                         "comparisons need a bigger tower, e.g. "
                         "2048,1024,512)")
    ap.add_argument("--max-seconds", type=float, default=None,
                    help="global watchdog: graceful exit(4) past this "
                         "(default 1700; 5400 for --all's ~10 stages; "
                         "0 disables)")
    args = ap.parse_args()
    if args.max_seconds is None:
        args.max_seconds = 5400.0 if getattr(args, "all") else 1700.0
    start_deadline(args.max_seconds)

    if args.elastic:
        # the training-side reshard pin needs a multi-shard mesh even on
        # a single-CPU box; the flag only affects the host platform and
        # must land before the first backend init
        xf = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in xf:
            os.environ["XLA_FLAGS"] = (
                xf + " --xla_force_host_platform_device_count=8").strip()

    from paddlebox_tpu._native import require_native
    from paddlebox_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    backend = init_backend()
    require_native()  # never measure on the Python parser/planner fallback
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig

    N_SLOTS, DENSE, B = args.slots, 13, 2048
    N_INS = 40 * B  # 40 steps
    HIDDEN = tuple(int(x) for x in args.hidden.split(",") if x)
    tconf = SparseTableConfig(embedding_dim=args.emb)
    trconf = TrainerConfig(auc_buckets=1 << 20,
                           compute_dtype=args.compute_dtype,
                           scan_steps=args.scan if args.trainer_path else 1)

    common = (backend, args, tconf, trconf, N_SLOTS, DENSE, B, N_INS, HIDDEN)

    if args.ops:
        stage_ops(backend, args)
        return

    if args.qps_sweep:
        if args.fleet:
            stage_fleet_sweep(backend, args)
        else:
            stage_serving_sweep(backend, args)
        return

    if args.quantized:
        stage_quantized(backend)
        return

    if args.storage:
        stage_storage(backend)
        return

    if args.serving:
        stage_serving(backend)
        return

    if args.elastic:
        stage_elastic(backend, args)
        return

    if args.fleet:
        stage_fleet(backend, args)
        return

    if args.streaming:
        stage_streaming(backend, args)
        return

    if args.retrieval:
        stage_retrieval(backend, args)
        return

    if args.models:
        stage_models(*common)
        return

    if args.all:
        run_all(*common)
        return

    if args.device_profile:
        stage_device_profile(*common, scan_k=args.scan)
        return

    if args.health:
        stage_health(*common)
        return

    if args.pass_boundary:
        stage_pass_boundary(*common)
        return

    if args.hbm_cache:
        stage_hbm_cache(*common)
        return

    if args.hostplane:
        stage_hostplane(*common)
        return

    if args.trainer_path:
        stage_trainer_path(*common)
        return

    if args.sustained:
        sps = bench_sustained(
            args.sustained, tconf, trconf, N_SLOTS, DENSE, B, N_INS, HIDDEN,
            args.profile, vocab_per_slot=args.vocab,
        )
        row = {
            "metric": "ctr_dnn_sustained_samples_per_sec",
            "value": round(sps, 1),
            "unit": "samples/sec",
            "vs_baseline": None,
            "backend": backend,
            "telemetry": telemetry_summary(),
        }
        # partial emit FIRST (see run_all's sustained stage)
        emit(row)
        cost = step_cost_for_config(tconf, trconf, N_SLOTS, DENSE, B,
                                    HIDDEN, args.vocab)
        if cost:
            emit({**row, **util_fields(cost, sps, B)})
        return

    # the naive-port baseline is CTR-DNN-shaped; other models report ours only
    stage_headline(*common, model_name=args.model,
                   with_naive=args.model == "ctr_dnn")


if __name__ == "__main__":
    main()
