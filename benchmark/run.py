"""One benchmark cell, once, in one process, through the normal pass loop.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in BENCHMARK.json; its configuration, traffic mix,
model builder, reference and per-layer metric readers are files found by
the names there (benchmark/README.md).  Nothing in this file knows a model,
a mix or a metric by name, nor a model by shape: what a model feeds on
beyond the common slots is the ``feed`` object of its configuration, its
features and loss are its reference module's.

A run: set-up (seeded data as slot-text files -> BoxPSDataset.load_into_
memory, model / table / trainer with default configs, seeded weights for
the table's keys, their admission to the device's row cache, the first
three steps for the output check, a warm-up cycle) -> the window (whole
passes begin_pass -> train_from_dataset -> end_pass until --seconds have
passed) -> the float32 reference and the comparison -> one JSON line.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

from benchmark import check, gen, trace_reduce  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
CHECK_STEPS = 3
TRACED_PASSES = 2


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A workload entry of BENCHMARK.json with its files resolved."""

    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list  # metric entries this cell reports
    per_layer: list

    @staticmethod
    def resolve(workload: str, manifest: dict | None = None) -> "Cell":
        manifest = manifest or load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(
                f"unknown workload {workload!r}; have {sorted(cells)}")
        w = cells[workload]
        conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]

        def mine(entries):
            return [m for m in entries
                    if workload in m.get("workloads", [workload])]

        return Cell(
            name=workload, chips=int(w["chips"]),
            cfg=load_json(ROOT, conf["file"]),
            mix=load_json(HERE, "traffic", w["traffic"] + ".json"),
            end_to_end=mine(manifest["end_to_end"]),
            per_layer=mine(manifest["per_layer"]),
        )


@dataclasses.dataclass
class Run:
    """What the window left behind; the per-layer readers read this."""

    cell: Cell
    passes: list  # per window pass: steps, samples, loss, the three spans
    gaps_s: list  # train_from_dataset return -> next call, per boundary
    window_s: float
    before: dict  # telemetry registry snapshot at window start
    after: dict  # ... and at window end
    distinct_keys_per_step: float
    device_kind: str
    trace: dict | None = None  # trace_reduce.reduce()'s result
    traced_steps: int = 0

    def counter_delta(self, name: str) -> float:
        """Change over the window of a counter, summed over its series."""
        def total(snap):
            return sum(v for k, v in snap["counters"].items()
                       if k == name or k.startswith(name + "{"))
        return total(self.after) - total(self.before)

    def histogram_delta(self, series: str) -> tuple:
        """(sum, count) change over the window of one histogram series."""
        a = self.after["histograms"].get(series, {"sum": 0.0, "count": 0})
        b = self.before["histograms"].get(series, {"sum": 0.0, "count": 0})
        return a["sum"] - b["sum"], a["count"] - b["count"]

    def span_seconds(self, name: str) -> list:
        return [p[name] for p in self.passes]

    @property
    def steps(self) -> int:
        return sum(p["steps"] for p in self.passes)

    def step_cost(self) -> dict:
        model = importlib.import_module(
            "benchmark.models." + self.cell.cfg["model"])
        return model.step_cost(self.cell.cfg, self.distinct_keys_per_step)


# ------------------------------------------------------------------ set-up
def pin_platform(require_chip: bool, chips: int):
    import jax

    if require_chip:
        jax.config.update("jax_platforms", "tpu")  # no chip -> JAX raises
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(
            f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return devs[:chips]


FEED_KEYS = ("sequence_slot", "max_seq_len", "task_label_slots")


def feed_config(cfg: dict):
    """The feed every cell has -- ``click``, ``slot0`` .. ``slot{S-1}``,
    ``dense0`` -- and what the configuration's optional ``feed`` object
    adds in ``DataFeedConfig``'s own words: an ordered ``sequence_slot``
    with its ``max_seq_len``, and ``task_label_slots``, one more float
    slot each, written after ``click`` (gen.to_text)."""
    from paddlebox_tpu.config import DataFeedConfig, SlotConfig

    feed = dict(cfg.get("feed", {}))
    if set(feed) - set(FEED_KEYS):
        raise SystemExit(f"{cfg['name']}: feed has {sorted(feed)}, the "
                         f"harness passes on {FEED_KEYS}")
    tasks = feed["task_label_slots"] = tuple(feed.get("task_label_slots", ()))
    slots = [SlotConfig(name=name, type="float", is_dense=True, shape=(1,))
             for name in ("click", *tasks)]
    slots += [SlotConfig(name=f"slot{i}", type="uint64")
              for i in range(cfg["n_sparse_slots"])]
    slots.append(SlotConfig(name="dense0", type="float", is_dense=True,
                            shape=(cfg["dense_dim"],)))
    return DataFeedConfig(
        slots=slots, batch_size=cfg["batch_size"], label_slot="click",
        batch_key_capacity=key_capacity(cfg), **feed)


def key_capacity(cfg: dict) -> int:
    return cfg["batch_size"] * cfg["keys_per_instance_capacity"]


def make_dataset(conf, pass_data, work: str, stem: str, n_files: int):
    from paddlebox_tpu.data.dataset import DatasetFactory

    ds = DatasetFactory().create_dataset("BoxPSDataset", conf)
    ds.set_filelist(gen.write_files(pass_data, work, stem, n_files))
    ds.load_into_memory()
    return ds


def check_defaults(cfg: dict, tconf, trconf) -> None:
    """The configuration file states the optimizers the system runs with
    by default; the reference follows the file, so a default that moved
    must fail here and not as a mysterious mismatch."""
    o = cfg["optimizers"]
    got = {
        "sparse_adagrad_lr": tconf.learning_rate,
        "sparse_initial_g2sum": tconf.initial_g2sum,
        "sparse_grad_clip": tconf.grad_clip,
        "dense_adam_lr": trconf.dense_lr,
    }
    for k, v in got.items():
        if o[k] != v:
            raise SystemExit(
                f"{cfg['name']}: the file states {k}={o[k]}, the program's "
                f"default is {v}")
    if trconf.dense_optimizer != "adam" or tconf.cvm_offset != 2:
        raise SystemExit("the program's default optimizer or row layout "
                         "is not what the configuration states")


def build_system(cell: Cell, devs):
    """Model, table and trainer with default configs apart from the sizes
    the configuration file states."""
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig

    cfg = cell.cfg
    tconf = SparseTableConfig(embedding_dim=cfg["embedding_dim"],
                              hbm_cache_rows=cfg["hbm_cache_rows"])
    trconf = TrainerConfig()
    check_defaults(cfg, tconf, trconf)
    model = importlib.import_module("benchmark.models." + cfg["model"]).build(
        cfg, tconf)
    if cell.chips == 1:
        from paddlebox_tpu.sparse.table import SparseTable
        from paddlebox_tpu.train.trainer import Trainer

        return model, SparseTable(tconf, seed=0), Trainer(
            model, tconf, trconf, seed=0)
    from paddlebox_tpu.parallel import (
        MultiChipTrainer,
        ShardedSparseTable,
        make_mesh,
    )

    mesh = make_mesh(cell.chips)
    return model, ShardedSparseTable(tconf, mesh, seed=0), MultiChipTrainer(
        model, tconf, mesh, trconf, seed=0)


def seeded_weights(cell: Cell, seed: int, all_keys: np.ndarray):
    """Dense parameters (one jitted call on the device) and the rows of
    every key of the run, both the benchmark's own from the seed."""
    import jax

    ref = importlib.import_module(
        "benchmark.reference." + cell.cfg["reference"])
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    params = jax.jit(lambda k: ref.init_params(cell.cfg, k))(key)
    rows = gen.initial_rows(all_keys.shape[0], seed,
                            cell.cfg["embedding_dim"])
    return params, rows


@dataclasses.dataclass
class CellData:
    passes: list  # gen.PassData of the cycle
    censuses: list
    all_keys: np.ndarray  # sorted distinct keys of the cycle
    cycle: list  # loaded BoxPSDatasets, one per pass
    step_data: list  # the first CHECK_STEPS batches of pass 0
    step_ds: list  # ... each as a one-batch dataset


def prepare_data(cell: Cell, seed: int, work: str, stack,
                 n_passes: int | None = None) -> CellData:
    """The cell's passes from the seed, written as slot text and loaded
    through BoxPSDataset.load_into_memory."""
    from concurrent import futures

    cfg = cell.cfg
    conf = feed_config(cfg)
    passes = gen.make_passes(
        cell.mix, cfg["n_sparse_slots"], cfg["dense_dim"], seed, n_passes,
        len(conf.task_label_slots))
    b = cfg["batch_size"] * cell.chips  # one (group-)step
    step_data = [passes[0].rows(i * b, (i + 1) * b)
                 for i in range(CHECK_STEPS)]
    jobs = [(p, f"pass{i}", 8) for i, p in enumerate(passes)]
    jobs += [(d, f"step{i}", 1) for i, d in enumerate(step_data)]
    with futures.ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        loaded = list(pool.map(
            lambda j: make_dataset(conf, j[0], work, j[1], j[2]), jobs))
        censuses = list(pool.map(lambda p: p.census(), passes))
    for ds in loaded:
        stack.callback(ds.close)
    return CellData(
        passes=passes, censuses=censuses,
        all_keys=np.unique(np.concatenate(censuses)),
        cycle=loaded[: len(passes)], step_data=step_data,
        step_ds=loaded[len(passes):])


def table_keys(cell: Cell, cycle_keys: np.ndarray) -> np.ndarray:
    """The sorted keys the table holds when the window opens: the mix's
    whole key space where the configuration's ``table_prefill`` says so (a
    deployment whose table fits the chip and lives in the device's row
    cache), else the keys of the cycle."""
    prefill = cell.cfg.get("table_prefill", {}).get("keys", "cycle")
    if prefill == "cycle":
        return cycle_keys
    if prefill != "key_space":
        raise SystemExit(f"unknown table_prefill keys {prefill!r}")
    keys = gen.key_space(cell.mix, cell.cfg["n_sparse_slots"])
    if keys.shape[0] > cell.cfg["hbm_cache_rows"]:
        raise SystemExit(
            f"the key space ({keys.shape[0]}) does not fit the row cache "
            f"({cell.cfg['hbm_cache_rows']} rows)")
    pos = np.minimum(np.searchsorted(keys, cycle_keys), keys.shape[0] - 1)
    if not np.array_equal(keys[pos], cycle_keys):
        raise SystemExit("the cycle has keys outside the mix's key space")
    return keys


def admit(cell: Cell, table, keys: np.ndarray) -> None:
    """Every row of ``keys`` into the device's row cache, as in a job that
    has run for a while: passes over the keys that train nothing, so the
    seeded state is still what the check starts from.  In chunks, so that
    a pass table sized for a chunk and the cache fit the chip together;
    every seed has the same chunks, so their programs compile once."""
    chunk = int(cell.cfg.get("table_prefill", {}).get(
        "admission_chunk_keys", keys.shape[0]))
    for part in np.array_split(keys, max(1, -(-keys.shape[0] // chunk))):
        table.begin_pass(part)
        table.end_pass()


def fresh_system(cell: Cell, devs, seed: int, all_keys: np.ndarray, stack):
    """The system with the benchmark's seeded weights loaded: dense
    parameters into the trainer, Adam's state as the
    configuration's ``seeded_state`` has it, every key's row into the
    table's store (a job resumed from a checkpoint).  The trainer is
    closed with ``stack``; the table is the caller's to close or drop."""
    import jax

    model, table, trainer = build_system(cell, devs)
    stack.callback(trainer.close)
    params, rows0 = seeded_weights(cell, seed, all_keys)
    params0 = jax.tree.map(np.asarray, params)
    check.same_structure(
        check.first_device(trainer.params, cell.chips), params0)
    trainer.load_dense_state(params, check.seeded_adam_state(
        check.first_device(trainer.opt_state, cell.chips),
        cell.cfg["seeded_state"]))
    table.load_state_dict({"keys": all_keys, "values": rows0})
    return model, table, trainer, params0, rows0


def free_device() -> None:
    """Drop every array the process still holds on the devices (the
    program's run is over: what is compared is on the host by now)."""
    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def peak_bytes(devs) -> int:
    """The process's high-water mark of device memory on its fullest chip
    (it never falls again; 0 where the backend reports none)."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs))


def one_pass(table, trainer, ds) -> dict:
    """begin_pass -> train_from_dataset -> end_pass as the examples' loop
    has it; wall seconds of the three parts."""
    import jax

    ann = jax.profiler.TraceAnnotation
    t0 = time.monotonic()
    with ann("bench.begin_pass"):
        table.begin_pass(ds.unique_keys())
    t1 = time.monotonic()
    with ann("bench.train"):
        m = trainer.train_from_dataset(ds, table)
    t2 = time.monotonic()
    with ann("bench.end_pass"):
        table.end_pass()
    t3 = time.monotonic()
    return {"steps": int(m["steps"]), "samples": float(m["samples"]),
            "loss": float(m["loss"]), "begin_pass": t1 - t0,
            "train": t2 - t1, "end_pass": t3 - t2, "t_call": t1,
            "t_return": t2, "t_end": t3}


def program_check_steps(cell, table, trainer, census, step_ds, params0,
                        all_keys, rows0) -> dict:
    """The first CHECK_STEPS steps through the window's own call: each a
    one-batch dataset under the full pass's census, so the compiled step
    and the table's capacity are the window's.  The rows are read from the
    open pass (``pass_state_dict``, the in-pass dump), after the step and
    before ``end_pass``: the next step's ``begin_pass`` has them from
    wherever ``end_pass`` put them.  Returns what check.compare reads from
    the program's side."""
    import jax

    b1 = cell.cfg["optimizers"]["dense_adam_b1"]
    out = {"loss": []}
    touched = np.unique(np.concatenate([d.unique_keys() for d in step_ds]))
    pos0 = np.searchsorted(all_keys, touched)
    first = rows0[pos0]

    def touched_rows():
        sd = table.pass_state_dict()
        return sd["values"][np.searchsorted(sd["keys"], touched)]

    for i, ds in enumerate(step_ds):
        table.begin_pass(census)
        m = trainer.train_from_dataset(ds, table)
        if i == 0:
            # the first gradient as the optimizers got it: Adam's first
            # moment after one step is (1-b1)*g; adagrad's g2sum grew by
            # mean(g*g) per row
            mu = check.first_device(check.adam_mu(trainer.opt_state),
                                    cell.chips)
            out["grads"] = [np.asarray(x) / (1.0 - b1)
                            for x in jax.tree.leaves(mu)]
            out["grad_norms"] = check.leaf_norms(out["grads"])
            out["step1_rows"] = touched_rows()
            d_g2 = np.maximum(out["step1_rows"][:, -1] - first[:, -1], 0.0)
            out["grad_norms"].append(float(np.sqrt(
                d_g2.astype(np.float64).sum() * cell.cfg["embedding_dim"])))
        if i == len(step_ds) - 1:
            final = touched_rows()
        table.end_pass()
        if int(m["steps"]) != 1:
            raise AssertionError(f"check pass took {m['steps']} steps")
        out["loss"].append(float(m["loss"]))
    params = check.first_device(trainer.params, cell.chips)
    delta = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                         params, params0)
    out["update_norms"] = check.leaf_norms(delta) + check.leaf_norms(
        [final[:, 2:-1] - first[:, 2:-1]])
    out["touched_keys"] = touched
    out["final_rows"] = final
    return out


class PassWatch:
    """What the process did during each pass besides waiting: its CPU
    seconds and the full (oldest-generation) collections of Python's
    garbage collector with the wall time they took.  Read-only: it
    changes nothing the window runs.  A pass that stalls with neither is
    waiting on something outside the process (PERF.md section 5)."""

    def __init__(self):
        self.gc_n, self.gc_s, self._t = 0, 0.0, 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.gc_n += 1
            self.gc_s += time.monotonic() - self._t

    def read(self) -> dict:
        t = os.times()
        return {"cpu_s": t.user + t.system, "gc_n": self.gc_n,
                "gc_s": self.gc_s}


# ------------------------------------------------------------------ the run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, sabotage=None) -> dict:
    """Everything but the argument parsing.  ``require_chip=False`` is for
    benchmark/tests (CPU, toy cells): such a result carries counts
    and ``correct`` but no metric.  ``sabotage`` is the tests' too: a hook
    that breaks the timed path underneath."""
    devs = pin_platform(require_chip, cell.chips)
    import jax

    from paddlebox_tpu import telemetry
    from paddlebox_tpu.telemetry.compiles import (
        compile_summary,
        install_compile_listener,
    )

    if require_chip:
        from paddlebox_tpu._native import require_native
        from paddlebox_tpu.utils.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        require_native()
    install_compile_listener()
    cfg, mix = cell.cfg, cell.mix
    B = cfg["batch_size"] * cell.chips  # instances per (group-)step
    if mix["instances_per_pass"] % B or mix["instances_per_pass"] < \
            CHECK_STEPS * B:
        raise SystemExit("instances_per_pass does not hold whole steps")

    with contextlib.ExitStack() as stack:
        work = stack.enter_context(
            tempfile.TemporaryDirectory(prefix="benchmark_run_"))
        t = time.monotonic()
        data = prepare_data(cell, seed, work, stack)
        passes, censuses, all_keys = data.passes, data.censuses, data.all_keys
        cycle, step_data, step_ds = data.cycle, data.step_data, data.step_ds
        distinct = float(np.mean([
            np.unique(passes[0].keys[lo: lo + B]).shape[0] - 1
            for lo in range(0, min(passes[0].n, 8 * B), B)]))
        log(f"data: {len(passes)} passes of {passes[0].n}, census "
            f"{[c.shape[0] for c in censuses]}, {all_keys.shape[0]} keys, "
            f"{distinct:.0f} distinct keys a step, "
            f"{time.monotonic() - t:.1f}s")

        t = time.monotonic()
        all_keys = table_keys(cell, data.all_keys)
        model, table, trainer, params0, rows0 = fresh_system(
            cell, devs, seed, all_keys, stack)
        if sabotage is not None:
            sabotage(trainer, table)
        log(f"system and weights for {all_keys.shape[0]} keys: "
            f"{time.monotonic() - t:.1f}s")

        t = time.monotonic()
        admit(cell, table, all_keys)
        t_admit = time.monotonic() - t
        got = program_check_steps(cell, table, trainer, censuses[0],
                                  step_ds, params0, all_keys, rows0)
        log(f"admission {t_admit:.1f}s, check steps "
            f"{time.monotonic() - t - t_admit:.1f}s, loss {got['loss']}")

        # warm-up: one whole cycle in the state the window will see
        t = time.monotonic()
        for ds in cycle:
            one_pass(table, trainer, ds)
        log(f"warm-up cycle: {time.monotonic() - t:.1f}s; compiles "
            f"{compile_summary()}")

        # ---------------------------------------------------- the window
        watch = PassWatch()
        gc.callbacks.append(watch)
        stack.callback(gc.callbacks.remove, watch)
        trace_dir = os.path.join(work, "trace")
        before = telemetry.registry.snapshot()
        setup_s = time.monotonic() - _T_START
        t_w0 = time.monotonic()
        done, failed, i = [], 0, 0
        tracing = False
        while True:
            t_prof = time.monotonic()
            if trace and i == 1:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = True
            elif tracing and i == 1 + TRACED_PASSES:
                jax.profiler.stop_trace()
                tracing = False
            t_prof = time.monotonic() - t_prof
            w0 = watch.read()
            try:
                p = one_pass(table, trainer, cycle[i % len(cycle)])
            except FloatingPointError as e:
                log(f"pass {i} failed: {e}")
                failed += 1
                break
            p.update({k: v - w0[k] for k, v in watch.read().items()})
            p["traced"] = tracing
            p["profiler_s"] = t_prof  # start/stop before this pass: no gap
            done.append(p)
            i += 1
            if time.monotonic() - t_w0 >= seconds and not tracing and (
                    not trace or i > 1 + TRACED_PASSES):
                break
        window_s = time.monotonic() - t_w0
        if tracing:  # a failed pass ended the window inside the trace
            jax.profiler.stop_trace()
        after = telemetry.registry.snapshot()
        peak = peak_bytes(devs)
        run = Run(
            cell=cell, passes=done, window_s=window_s, before=before,
            after=after, distinct_keys_per_step=distinct,
            device_kind=devs[0].device_kind,
            gaps_s=[b["t_call"] - a["t_return"] - b["profiler_s"]
                    for a, b in zip(done, done[1:])],
        )
        if trace and not failed:
            path = trace_reduce.find_xplane(trace_dir)
            run.trace = trace_reduce.reduce(path, n_devices=cell.chips)
            run.traced_steps = sum(p["steps"] for p in done if p["traced"])

        # the program's state goes before the reference runs, so the peak
        # above is the program's and the reference has the device.  The
        # table is dropped, not closed: ``close`` is a checkpoint barrier
        # that first brings every row the cache holds down to the host
        # store (the whole table, some 15 s), and nothing reads the store
        # after the window; its workers are daemon threads
        t = time.monotonic()
        trainer.close()
        del model, table, trainer
        free_device()
        log(f"program state freed: {time.monotonic() - t:.1f}s")
        t = time.monotonic()
        ref = importlib.import_module("benchmark.reference."
                                      + cfg["reference"])
        steps = (ref, cfg, params0, all_keys, rows0, step_data,
                 key_capacity(cfg) * cell.chips)
        common = importlib.import_module("benchmark.reference.common")
        want = common.run_steps(*steps)
        t_want = time.monotonic() - t
        # the same steps at the precision the configuration states: how
        # far that alone lies from float32 on this seed
        base = common.run_steps(*steps, precision=cfg["precision"]["products"])
        ref_seconds = time.monotonic() - t
        # the mark is the process's: it shows the reference only where
        # the reference is the larger of the two
        ref_peak = peak_bytes(devs)
    numbers = check.compare(got, want, base, cfg["limits"])
    log("passes [begin_pass, train, end_pass ms; process CPU s; full "
        "collections, their s]: " + " ".join(
            f"[{1e3 * p['begin_pass']:.0f},{1e3 * p['train']:.0f},"
            f"{1e3 * p['end_pass']:.0f};{p['cpu_s']:.1f};{p['gc_n']},"
            f"{p['gc_s']:.2f}]" for p in done))
    finite = all(np.isfinite(p["loss"]) for p in done)
    correct = bool(all(n["ok"] for n in numbers) and finite and not failed
                   and len(done) >= 2)
    log(f"window: {len(done)} passes, {run.steps} steps in {window_s:.2f}s; "
        f"reference {ref_seconds:.1f}s (not in setup_s): float32 "
        f"{t_want:.1f}s, {cfg['precision']['products']} "
        f"{ref_seconds - t_want:.1f}s; peak_bytes_in_use {peak} before "
        f"it, {ref_peak} after; finite={finite}")
    for n in numbers:  # the last lines on stderr, and the line's last key
        log(f"check {n['name']}: {n['value']:.6g} (limit {n['limit']:g}) "
            f"{'ok' if n['ok'] else 'OVER'}")

    result = {
        "correct": correct,
        "attempted": len(done) + failed,
        "failed": failed,
        "metrics": {},
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": peak},
        "counts": {"passes": len(done), "steps": run.steps,
                   "instances": sum(p["samples"] for p in done),
                   "window_compile_requests": run.counter_delta(
                       "jit.compiles")},
    }
    if devs[0].platform != "tpu":
        # counts and correct only: no rate off the chip
        return {**result, "checks": numbers}
    if not trace:
        values = {
            "samples_per_s": sum(p["samples"] for p in done) / window_s,
            "pass_gap_ms": 1e3 * statistics.median(run.gaps_s),
            "setup_s": setup_s,
        }
        entries = cell.end_to_end
    else:
        values = {}
        for m in cell.per_layer:
            reader = importlib.import_module(
                "benchmark.layer_metrics." + m["name"])
            v = reader.read(run)
            if v is not None:
                values[m["name"]] = float(v)
        entries = cell.per_layer
        result["device"]["busy_s"] = run.trace["busy_s"]
        result["device"]["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace["top_ops"],
            "idle_gaps": run.trace["idle_gaps"],
            "scope_s": trace_reduce.top_scopes(run.trace["scope_s"]),
        }
    units = {m["name"]: m["unit"] for m in entries}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items() if k in units}
    return {**result, "checks": numbers}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(Cell.resolve(args.workload), args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
