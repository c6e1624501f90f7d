"""The pass protocol, written once for both trainers.

``Trainer.train_from_dataset`` and ``MultiChipTrainer.train_groups`` are the
same pass — open, dispatch loop, teardown, drain, read-back, observe — around
two different step programs (one jitted program against a ``shard_map`` with
exchanges) and two table lifecycles.  ``run_pass`` is that protocol; what a
trainer still says about a pass is a ``PassHooks`` object private to its own
module.  With it live the copies that hang on the loop and are not protocol:
the in-step metric half (``step_metrics``), the metric state's initialiser,
the read-back's common half, the batch checks and the feed prefetcher.

Nothing here imports ``parallel/`` at module level: the device-axis merges
come in as arguments, and the one reach the other way — the liveness
watchdog, which both trainers use and which lives under ``parallel/`` — is
the gated import of ``_watchdog_mod``.
"""

from __future__ import annotations

import logging
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu import telemetry
from paddlebox_tpu.config import TelemetryConfig
from paddlebox_tpu.metrics.auc import (
    compute_metrics,
    compute_metrics_stacked,
    init_auc_state,
    stack_auc_states,
    update_auc_state,
)
from paddlebox_tpu.metrics.variants import MetricGroup
from paddlebox_tpu.telemetry.compiles import stage_scope
from paddlebox_tpu.telemetry.events import _default_rank
from paddlebox_tpu.train.step_loss import counter_names, publish_counters
from paddlebox_tpu.utils import faults
from paddlebox_tpu.utils.monitor import stats
from paddlebox_tpu.utils.profiler import (
    HOST,
    CompletionWatcher,
    StatsProfiler,
    device_trace,
    pass_seconds,
)


def _watchdog_mod():
    """The liveness watchdog module (parallel/watchdog.py), or None on a
    build where the parallel package cannot import — the single-chip
    trainer must keep working there, just without liveness guarding."""
    try:
        from paddlebox_tpu.parallel import watchdog

        return watchdog
    # pbox-lint: ignore[swallowed-exception] gated-import fallback: a build
    # without the parallel package is the handled case
    except Exception:
        import sys

        return sys.modules.get("paddlebox_tpu.parallel.watchdog")


def stall_errors() -> tuple:
    """The watchdog's coordinated-abort error as an ``except`` clause's
    tuple: empty on a build without the watchdog."""
    wd_mod = _watchdog_mod()
    return () if wd_mod is None else (wd_mod.DistributedStallError,)


# how long close() waits for the producer thread before declaring it stuck
# (module-level so chaos tests can shrink it)
_PREFETCH_JOIN_S = 5.0


class _FeedPrefetcher:
    """Bounded background feed assembly: the producer thread runs host key
    planning + H2D staging up to ``depth`` batches ahead of the consumer
    (the pinned-arena double buffer of SURVEY.md §2.3, as a thread + queue;
    JAX's device_put already stages through pinned runtime buffers, so the
    missing piece was only the OVERLAP, provided here).  Exceptions raised
    by the producer re-raise at the consumer's next() call.

    Both sides of the queue are timed (``prof``, the trainer's
    StatsProfiler): ``feed_wait`` is the consumer blocked on an empty
    queue — the device's next feed was not ready — and ``feed_put_wait``
    the producer blocked on a full one, the host's slack."""

    _SENTINEL = object()

    def __init__(self, gen, depth: int, prof=None):
        import queue
        import threading

        from paddlebox_tpu.telemetry import trace

        self._q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._stop = False
        self._done = False
        self._prof = prof or StatsProfiler()
        # the producer's plan/feed spans name the consumer's open span
        # (the pass) as the span that caused them
        self._parent_span = trace.current_span()
        self._thread = threading.Thread(
            target=self._run, args=(gen,), name="feed-prefetch", daemon=True
        )
        self._thread.start()

    def _run(self, gen) -> None:
        from paddlebox_tpu.telemetry import trace
        from paddlebox_tpu.utils.queues import bounded_put

        trace.adopt_span(self._parent_span)

        def put(item) -> bool:
            # re-checks _stop: close() drains the queue, so a blocking put
            # would otherwise race it and the producer could keep planning
            # batches (and touching the table) after the caller ended the pass
            with self._prof.stage("feed_put_wait"):
                return bounded_put(self._q, item, lambda: self._stop)

        try:
            for item in gen:
                if self._stop or not put(item):
                    return
            # this thread lives one pass: its run-queue wait is told
            # before the sentinel lets the consumer go on
            HOST.thread("feed")
            put(self._SENTINEL)
        except BaseException as e:  # surfaced to the consumer
            put(e)

    def __iter__(self):
        return self

    def __next__(self):
        import queue

        if self._done:  # keep raising after exhaustion/producer death —
            raise StopIteration  # the producer will never put again
        wd_mod = _watchdog_mod()
        with self._prof.stage("feed_wait"):
            while True:
                # bounded get: a coordinated liveness abort must interrupt
                # a consumer blocked on a stalled producer within one poll
                # slice
                if wd_mod is not None:
                    wd_mod.check()
                try:
                    item = self._q.get(timeout=0.2)
                    break
                except queue.Empty:
                    continue
        if item is self._SENTINEL:
            self._done = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._done = True
            raise item
        return item

    def close(self) -> None:
        """Unblock and retire the producer (call on early exit)."""
        import queue

        self._stop = True
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=_PREFETCH_JOIN_S)
        if self._thread.is_alive():
            # the producer is stuck in planning/H2D staging; it will exit at
            # its next _stop check, but make the leak visible instead of
            # silent (advisor r3) — and countable, so chaos tests can assert
            # a stuck producer was detected rather than scraping logs
            stats.add("trainer.prefetch_close_timeout")
            logging.getLogger(__name__).warning(
                "feed-prefetch producer did not exit within 5s of close(); "
                "daemon thread will retire at its next stop check"
            )


# -- the copies that are not protocol ---------------------------------------- #
def validate_batch(batch, uses_rank: bool, uses_seq: bool, n_tasks: int) -> None:
    """What a model needs of a batch that only the data feed's configuration
    can give it (``n_tasks`` 1 checks no task labels: the evaluate loops)."""
    if uses_rank and batch.rank_offset is None:
        raise RuntimeError(
            "model requires PV-merged batches with rank_offset: "
            "set enable_pv_merge and call dataset.preprocess_instance()"
        )
    if uses_seq and batch.seq_pos is None:
        raise RuntimeError(
            "model consumes an ordered behavior sequence: set "
            "DataFeedConfig.sequence_slot (and max_seq_len) so "
            "batches carry seq_pos"
        )
    if n_tasks > 1 and (
        batch.task_labels is None or batch.task_labels.shape[1] != n_tasks
    ):
        got = 0 if batch.task_labels is None else batch.task_labels.shape[1]
        raise RuntimeError(
            f"model has {n_tasks} tasks but the batch carries "
            f"{got} task label columns: configure "
            "DataFeedConfig.task_label_slots with "
            f"{n_tasks - 1} slots (task 0 is the primary label)"
        )


def step_metrics(mstate, batch, loss, preds, primary, pgrads, row_grads, *,
                 n_tasks: int, has_group: bool, check_nan: bool):
    """The metric half of a step, traced inside both ``_build_step``s (on
    the sharded path per device: each stream is merged over the device axis
    at read time, and the body psums the finite flag after this)."""
    mstate["auc"] = update_auc_state(
        mstate["auc"], primary, batch["labels"], batch["ins_mask"]
    )
    if "gn" in mstate:
        # grad-norm health stream rides the donated metric state — no
        # step-signature change: [sum of squared global grad norms,
        # steps]; a skip_batch discard drops its sample too.  Sharded:
        # per device, summed at pass end; with psummed pgrads every
        # device's are identical, so sum/steps stays the step value.
        gsq = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(pgrads):
            gsq += jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        gsq += jnp.sum(jnp.square(row_grads.astype(jnp.float32)))
        mstate["gn"] = mstate["gn"] + jnp.stack(
            [gsq, jnp.ones((), jnp.float32)]
        )
    if n_tasks > 1:
        mstate["task"] = jax.vmap(
            lambda s, pr, lb: update_auc_state(s, pr, lb, batch["ins_mask"])
        )(mstate["task"], preds.T, batch["task_labels"].T)
    if has_group:
        mstate["group"] = MetricGroup.update(
            mstate["group"], primary, batch["labels"], batch["metric_masks"],
        )
    if check_nan:
        finite = jnp.isfinite(loss)
        for leaf in jax.tree.leaves(pgrads):
            finite &= jnp.isfinite(leaf).all()
        finite &= jnp.isfinite(row_grads).all()
    else:
        finite = jnp.array(True)
    return mstate, finite


def _same(x):
    return x


def init_metric_state(trainer, auc_state, place=_same, copy=None) -> dict:
    """Fresh metric state, or continuation: pass the previous pass's
    ``trainer.last_metric_state`` (a dict) to carry EVERY stream forward;
    a bare AucState continues only the primary stream and is rejected
    when task/group streams exist (they would silently reset).

    ``place`` puts a fresh leaf where the step wants it (the sharded
    trainer stacks one copy a device, mesh-sharded; merged by summing over
    devices at read time); ``copy`` gives a continued tree fresh buffers."""
    copy = copy or (lambda tree: jax.tree.map(jnp.array, tree))
    n_counters = len(counter_names(trainer.model))
    if isinstance(auc_state, dict):
        # the step donates mstate: copy so the caller's reference (often
        # trainer.last_metric_state itself) is not invalidated by the
        # first step's buffer donation
        out = copy(auc_state)
        if "gn" not in out:
            out["gn"] = place(jnp.zeros((2,), jnp.float32))
        if n_counters and "counters" not in out:
            out["counters"] = place(jnp.zeros((n_counters,), jnp.float32))
        return out
    if auc_state is not None and (trainer.n_tasks > 1 or trainer.metric_group):
        raise ValueError(
            "pass trainer.last_metric_state (dict) to continue metrics "
            "across passes — a bare AucState would reset the task/group "
            "streams while continuing the primary one"
        )
    buckets = trainer.conf.auc_buckets
    mstate = {
        "auc": copy(auc_state)
        if auc_state is not None
        else place(init_auc_state(buckets)),
        "gn": place(jnp.zeros((2,), jnp.float32)),
    }
    if n_counters:
        # the model's per-step sums (step_loss.counter_names)
        mstate["counters"] = place(jnp.zeros((n_counters,), jnp.float32))
    if trainer.n_tasks > 1:
        mstate["task"] = place(
            stack_auc_states(init_auc_state(buckets), trainer.n_tasks))
    if trainer.metric_group is not None:
        mstate["group"] = place(trainer.metric_group.init_state())
    return mstate


def read_back_common(mstate: dict, gn_base, params, n_tasks: int,
                     metric_group, own: dict, merge=_same, read=_same) -> dict:
    """The half of a pass's metrics both trainers read the same way: the
    AUC, task and group streams, then the trainer's ``own`` entries (its
    loss), then the gradient and weight norms with their gauges.  ``merge``
    sums a stream over the device axis (multi-host: jitted replicated sum +
    local read, the collect_data_nccl analog) and ``read`` takes one replica
    of a parameter leaf; a single chip needs neither."""
    metrics = compute_metrics(merge(mstate["auc"]))
    if n_tasks > 1:
        metrics.update(
            compute_metrics_stacked(
                merge(mstate["task"]), [f"task{t}" for t in range(n_tasks)]
            )
        )
    if metric_group is not None:
        metrics.update(metric_group.compute(merge(mstate["group"])))
    metrics.update(own)
    gn_now = np.asarray(merge(mstate["gn"]), dtype=np.float64)
    d_sq, d_n = gn_now[0] - gn_base[0], gn_now[1] - gn_base[1]
    if d_n > 0:
        grad_norm = float(np.sqrt(d_sq / d_n)) if d_sq >= 0 else float("nan")
        metrics["grad_norm"] = grad_norm
        telemetry.gauge(
            "train.grad_norm",
            "per-pass RMS global gradient norm (dense + sparse)",
        ).set(grad_norm)
    wsq = sum(
        float(jnp.sum(jnp.square(read(leaf).astype(jnp.float32))))
        for leaf in jax.tree.leaves(params)
    )
    metrics["weight_norm"] = math.sqrt(wsq) if wsq >= 0 else float("nan")
    telemetry.gauge(
        "train.weight_norm", "dense parameter L2 norm at pass end"
    ).set(metrics["weight_norm"])
    return metrics


# -- the protocol ------------------------------------------------------------ #
class PassHooks:
    """What a trainer says about one pass, and only that: a member exists
    where the two trainers differ.  ``run_pass`` fills in ``prof``, ``wd``,
    ``dumper`` and ``mstate`` before the first feed is asked for.  Every
    trainer gives:

    ``open()``: the step program for this pass and the state it carries;
    ``feeds()``: the producer's generator (validation, host planning, the
    transfer), run on the prefetch thread;
    ``dispatch(feed) -> (loss, finite)``: one step on the carried state;
    ``after_step(feed, finite) -> bool``: the nan policy, the field dump,
    the dense sync — False where the step contributed nothing and is not
    counted;
    ``hand_back()``: the live buffers back to the table (the step donated
    the old ones);
    ``read_back(losses, gn_base) -> dict``: ``read_back_common`` around the
    trainer's loss and ``samples``;
    ``index() -> (name, value)``: the index its pass records carry."""

    prof: StatsProfiler
    wd = None  # the pass's watchdog, where liveness is configured
    dumper = None  # the pass's FieldDumper, where a field dump is asked for
    mstate: dict  # the donated metric state, as the last step left it
    merge = staticmethod(_same)  # a metric leaf summed over the device axis
    dump_suffix = ""  # the field dump's file is per process when several run

    def finish_loop(self) -> None:
        """After the last step, still inside the pass span."""

    def dump_params(self) -> None:
        """The parameter dump, where the trainer honours need_dump_param."""

    def observe(self, metrics: dict, tele) -> None:
        """The trainer's own additions to the pass's record."""

    def close(self) -> None:
        """Success path only, after the read-back's collectives."""


def run_pass(trainer, per: PassHooks, table, auc_state=None,
             next_pass_keys=None) -> dict:
    """One pass of ``trainer`` over ``per.feeds()`` against ``table``.

    next_pass_keys: the NEXT pass's key census (array, or a zero-arg
    callable returning one — evaluated on the table's staging thread, so it
    may block on a dataset preload).  Handed to table.prepare_pass once
    this pass's feeds are exhausted, while the device still drains its
    queued tail steps — the pre-promotion half of pass-boundary pipelining
    (no-op on serial tables and multi-host)."""
    conf = trainer.conf
    # ONE profiler, always on, and the same loop whatever is asked for:
    # profile / the trace dirs only decide what is reported and written
    # after the pass, from the registry's delta over it
    prof = per.prof = StatsProfiler()
    # the pass's head: the step program, the metric state and its baselines
    # (eager programs and a read-back), the telemetry's own set-up, the
    # watchdog's -- the device idles under it, so it has a name
    with prof.stage("open"):
        per.open()
        merge = per.merge
        with stage_scope("train.init"):
            per.mstate = trainer._init_mstate(auc_state)
            # grad-norm baseline: the accumulator carries across continued
            # passes, so the per-pass value is a delta between host
            # snapshots (materialized NOW, a lockstep device-axis merge on
            # every rank — the first step donates the buffer)
            gn_base = np.asarray(merge(per.mstate["gn"]), dtype=np.float64)
            counters_base = np.asarray(
                merge(per.mstate["counters"]), dtype=np.float64
            ) if "counters" in per.mstate else None
        pass_t0 = time.monotonic()
        losses, n_steps = [], 0
        if conf.need_dump_field and conf.dump_fields_path:
            from paddlebox_tpu.train.dump import FieldDumper

            per.dumper = FieldDumper(
                os.path.join(
                    conf.dump_fields_path,
                    f"dump-{trainer.global_step}{per.dump_suffix}.txt"),
                conf.dump_fields,
            )
        # telemetry policy: explicit config wins, env flags otherwise
        # (PBOX_METRICS_PORT / PBOX_TRACE_DIR / PBOX_EVENTS_PATH — the
        # launcher's per-rank knobs).  The exporter/event log are
        # per-process singletons: first pass starts them, later passes
        # are no-ops.
        tele = conf.telemetry or TelemetryConfig.from_flags()
        telemetry.ensure_exporter(tele.metrics_port or None)
        event_log = telemetry.ensure_event_log(tele.events_path or None)
        # host span tracing: TrainerConfig.trace_dir (which also drives
        # the jax device trace) or the telemetry trace dir alone
        host_trace_dir = conf.trace_dir or tele.trace_dir
        if host_trace_dir:
            telemetry.enable_tracing(pid=_default_rank())

        watch = trainer._watch
        want_report = bool(conf.profile or host_trace_dir)
        prof_mark = prof.mark() if want_report else None
        complete_mark = CompletionWatcher.mark() if want_report else None

        # distributed-liveness watchdog: stage-reported progress (feed /
        # step) with a stall deadline; single-process runs get local stall
        # detection, multi-process runs additionally publish heartbeats
        # through the KV store the planning plane rides and converge on
        # coordinated abort (parallel/watchdog.py).  Namespaced per pass
        # (global_step advances in lockstep across processes) so heartbeat
        # keys from a previous aborted pass can never poison a fresh one.
        wd_mod = _watchdog_mod()
        if wd_mod is not None and conf.liveness is not None:
            per.wd = wd_mod.for_trainer(
                conf.liveness, namespace=f"train-{trainer.global_step}")
            if per.wd is not None:
                per.wd.start()
        wd = per.wd

    prefetcher = None
    try:
      try:
        with telemetry.span("pass", pass_idx=trainer._pass_idx,
                            global_step=trainer.global_step), \
             device_trace(conf.trace_dir or None):
          feed_iter = per.feeds()
          if conf.prefetch_batches > 0:
            # feed assembly overlaps the device step.  Started inside
            # the pass span: the producer's plan/feed spans inherit it
            # as their parent.
            feed_iter = prefetcher = _FeedPrefetcher(
                feed_iter, conf.prefetch_batches, prof)
          for feed in feed_iter:
            # chaos site: a hang here simulates a stalled device step;
            # the watchdog bounds it (fleet-wide) and names this process
            # + stage
            faults.inject("train.step")
            t_dispatch = time.perf_counter()
            with prof.stage("step"):
                loss, finite = per.dispatch(feed)
            watch.dispatched(loss, t_dispatch)
            if wd is not None:
                wd.report("step")
            if not per.after_step(feed, finite):
                continue
            losses.append(loss)  # device scalars; synced once at pass end
            n_steps += 1
            trainer.global_step += 1
          per.finish_loop()
      finally:
        # old buffers were donated to the jitted step: always hand the
        # live ones back so end_pass() can salvage the pass even after a
        # NaN raise.  The watchdog retires FIRST so its abort latch cannot
        # fire into the teardown itself.
        if wd is not None:
            wd.close()
        per.hand_back()
        if prefetcher is not None:
            prefetcher.close()
        if per.dumper is not None:
            per.dumper.close()
    except stall_errors():
        # coordinated abort: every process converges on the same
        # structured error (poison key); the pass is torn down (prefetcher
        # closed, buffers handed back).  What happens to the partial pass
        # is the trainer's caller's say (Trainer: rollback_on_abort).
        stats.add("train.stall_aborts")
        raise
    # pre-promotion: the feed loop is done but the device is still
    # draining queued steps (and the metric readback below blocks on
    # them) — exactly the tail window the next pass's census resolve +
    # init + staging can hide in
    if next_pass_keys is not None:
        prepare = getattr(table, "prepare_pass", None)
        if prepare is not None:
            prepare(next_pass_keys)
    per.dump_params()
    # the device's tail: the read-back below waits for the last queued
    # step anyway; waiting here first gives the wait its own name and
    # leaves ``readback`` the merges and eager metric programs alone
    with prof.stage("drain"):
        if losses:
            losses[-1].block_until_ready()
        watch.settle()
        # the device has nothing queued: did the host let the pass's
        # threads run (the feed producer answered before it exited)
        HOST.after_drain(watch)
    with stage_scope("train.readback"), prof.stage("readback"):
        metrics = per.read_back(losses, gn_base)
        if counters_base is not None:
            metrics.update(publish_counters(
                trainer.model,
                np.asarray(merge(per.mstate["counters"]), dtype=np.float64),
                counters_base))
    # the pass's tail is the telemetry's own -- the pass report, the fleet
    # view, the registry's delta over every series, the health rules, the
    # pass_end record -- with the device idle: it has a name too
    with prof.stage("observe"):
        metrics["steps"] = n_steps
        # samples/s without trace files: the pass_end record carries
        # wall-clock duration and the instance count it covered
        metrics["duration_s"] = time.monotonic() - pass_t0
        pass_seconds().observe(metrics["duration_s"])
        if want_report:
            metrics["profile"] = prof.report(prof_mark, n_steps, complete_mark)
            if conf.profile:
                print("[profile]", prof.log_line(metrics["profile"]))
        if host_trace_dir:
            telemetry.flush_trace(os.path.join(
                host_trace_dir,
                f"host-trace-r{_default_rank()}-pass{trainer._pass_idx}.json",
            ))
        per.observe(metrics, tele)
        # run-health plane: evaluate the rule catalog against the SAME
        # window the pass_end record carries (the delta snapshot resets
        # its baseline per call — there is exactly one consumer chain),
        # BEFORE the record is written so a consumer that tails up to
        # pass_end already has the window's health_alert events
        name, idx = per.index()
        snap = telemetry.registry.delta_snapshot()
        telemetry.observe_pass(idx, metrics=metrics, telemetry=snap,
                               table=table)
        if event_log is not None:
            event_log.log_pass(metrics, telemetry=snap, **{name: idx})
    per.close()
    trainer._pass_idx += 1
    trainer.last_auc_state = per.mstate["auc"]
    trainer.last_metric_state = per.mstate
    return metrics
