"""Single-chip training loop.

TPU-native redesign of ``BoxPSWorker::TrainFiles`` (reference:
framework/boxps_worker.cc:542-598) + ``Executor.train_from_dataset``
(python/paddle/fluid/executor.py:1643): instead of an op-by-op graph
interpreter, the whole step — pull (gather) -> fused_seqpool_cvm -> dense
tower -> logloss -> push (scatter + sparse adagrad) -> dense adam -> AUC
histogram — is ONE jitted function with donated state buffers, so XLA fuses
everything between the two table scatters and nothing syncs with the host
inside a step.  Host work per batch is only the numpy key->row planning
(plan_batch), the analog of the reference's CopyKeys/Dedup staging.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import time
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.feed import HostBatch, key_classes
from paddlebox_tpu.metrics.auc import (
    AucState,
    compute_metrics,
    compute_metrics_stacked,
    init_auc_state,
    stack_auc_states,
    update_auc_state,
)
from paddlebox_tpu.metrics.variants import MetricGroup
from paddlebox_tpu.sparse.table import SparseTable, pull_rows, push_and_update
from paddlebox_tpu.telemetry.compiles import counted_jit, stage_scope
from paddlebox_tpu.utils.profiler import (
    HOST,
    START,
    CompletionWatcher,
    StatsProfiler,
    device_trace,
    pass_seconds,
)
from paddlebox_tpu.utils import faults
from paddlebox_tpu.utils.monitor import stats


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


class NonFiniteBatchError(FloatingPointError):
    """A batch produced a non-finite loss/grad and the nan_policy did not
    absorb it (policy "raise", or "rollback" before the restore)."""


class PassRolledBack(RuntimeError):
    """nan_policy="rollback" fired: the in-flight pass was aborted and the
    table + dense state were restored to the last completed pass via the
    attached AutoCheckpointer.  ``status`` is the restored status dict —
    the driver re-runs from ``status["next_pass"]`` and must NOT call
    table.end_pass() for the aborted pass (it was already discarded)."""

    def __init__(self, status: dict):
        super().__init__(
            f"pass rolled back to checkpoint tag {status['tag']!r}; "
            f"re-run from pass {status['next_pass']}"
        )
        self.status = status


# shared per-slot policy helpers live in a leaf module (importable from
# parallel/trainer.py without the train <-> models <-> parallel cycle);
# re-exported here for their historical import path
from paddlebox_tpu.train.slot_policy import (  # noqa: E402,F401
    normalize_slot_mask,
    resolve_slot_lr_vec,
    slot_participation_vec,
)
from paddlebox_tpu.train.step_loss import (  # noqa: E402
    add_counts,
    counter_names,
    make_model_loss,
    publish_counters,
)


@dataclasses.dataclass
class TrainState:
    """Everything the jitted step reads and writes."""

    params: Any  # dense model params (pytree)
    opt_state: Any  # optax state
    values: jax.Array  # sparse table working set [P, W]
    g2sum: jax.Array  # [P]
    auc: AucState


def _host_batch_dict(
    batch: HostBatch, plan, n_slots: int, counter_label_tasks=(),
    slot_lr_vec: Optional[np.ndarray] = None,
    vocab_keys: Optional[np.ndarray] = None,
) -> dict:
    """Assemble the static-shape feed (numpy leaves) from a HostBatch +
    BatchPlan — _device_batch without the H2D transfer.

    Every occurrence-sized leaf has the plan's length L (the table's
    occurrence bucket, SparseTable._occ_slots), not the key buffer's K:
    the buffer's first L slots hold every real occurrence, and a padding
    position of ``seq_pos`` (K in the HostBatch) is L here, one past the
    pulled rows as before.

    vocab_keys: a model's fixed vocabulary (sorted feasigns); the feed
    then carries "key_class" [L], each occurrence's rank in it
    (data/feed.py key_classes).

    slot_lr_vec: [S] per-slot learning rates; when given the feed carries
    "uniq_lr" [U], each unique key's lr resolved from the slot of (one of)
    its occurrences — the host side of the BoxPS LR map
    (box_wrapper.h:631)."""
    L = plan.idx.shape[0]
    key_segments = batch.key_segments[:L]
    ins = np.minimum(key_segments // n_slots, batch.batch_size - 1)
    key_clicks = batch.labels[ins] * plan.key_mask
    dev = {
        "idx": plan.idx,
        "uniq_idx": plan.uniq_idx,
        "inverse": plan.inverse,
        "key_mask": plan.key_mask,
        "key_clicks": key_clicks,
        "key_segments": key_segments,
        "dense": batch.dense,
        "labels": batch.labels,
        "ins_mask": batch.ins_mask,
    }
    if batch.rank_offset is not None:
        dev["rank_offset"] = batch.rank_offset
    if batch.seq_pos is not None:
        dev["seq_pos"] = np.minimum(batch.seq_pos, L)
    if batch.task_labels is not None:
        dev["task_labels"] = batch.task_labels
    if vocab_keys is not None:
        dev["key_class"] = key_classes(
            batch.keys[:L], batch.n_keys, vocab_keys, plan.inverse)
    if counter_label_tasks:
        if batch.task_labels is None:
            raise RuntimeError(
                "counter_label_tasks configured but the batch carries no "
                "task labels: set DataFeedConfig.task_label_slots"
            )
        n_cols = batch.task_labels.shape[1]
        bad = [t for t in counter_label_tasks if not 0 <= t < n_cols]
        if bad:
            raise ValueError(
                f"counter_label_tasks {bad} out of range: the batch has "
                f"{n_cols} task-label columns (col 0 = primary label)"
            )
        # per-occurrence extra counter increments (conv/pcoc layouts)
        extras = np.stack(
            [
                batch.task_labels[ins, t] * plan.key_mask
                for t in counter_label_tasks
            ],
            axis=1,
        ).astype(np.float32)
        dev["key_extras"] = extras
    if slot_lr_vec is not None:
        uniq_lr = np.full(  # padding tail: any finite lr, its delta is 0
            plan.uniq_idx.shape[0], slot_lr_vec.mean(), np.float32)
        n_real = batch.n_keys
        if n_real:
            # inverse[:n_real] maps occurrences -> unique slots; last
            # assignment wins (keys never span slots in practice, and the
            # reference's slot-keyed pull makes the same assumption)
            uniq_lr[plan.inverse[:n_real]] = slot_lr_vec[
                batch.key_segments[:n_real] % n_slots
            ]
        dev["uniq_lr"] = uniq_lr
    return dev


def _to_device(host: dict) -> dict:
    """H2D staging of one (possibly stacked) host feed dict — the single
    place a staging change (pinned device_put, dtype cast) must land."""
    return {k: jnp.asarray(v) for k, v in host.items()}


def _device_batch(
    batch: HostBatch, plan, n_slots: int, counter_label_tasks=()
) -> dict:
    """Host feed + H2D transfer."""
    return _to_device(_host_batch_dict(batch, plan, n_slots, counter_label_tasks))


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


def _step_schedule() -> dict:
    """How the TPU's compiler orders the step: by its list scheduler, the
    one of its three memory schedulers (list, depth-first, post-order) that
    orders a step for the least memory.  Left to itself it runs all three
    and keeps the one whose own estimate is smallest, and for a decoder
    step that estimate flips with what the step contains: with attention
    in unrolled strips it kept list, with attention as a kernel (or left
    out altogether) it keeps depth-first, whose schedule of the very same
    layers fills the chip -- 17.2 GB against 14.5 for a step that holds
    7.3 GB of state -- and leaves the pass boundary's and the read-back's
    eager programs no room beside it (PERF.md section 6, PR 44).  Off the
    TPU nothing is named: the option is the TPU compiler's."""
    if jax.default_backend() != "tpu":
        return {}
    return {"compiler_options": {"xla_memory_scheduler": "list"}}


class Trainer:
    """Drives model + SparseTable over a dataset's batches."""

    @START.wrap("trainer_init")
    def __init__(
        self,
        model,
        table_conf: SparseTableConfig,
        trainer_conf: Optional[TrainerConfig] = None,
        seed: int = 0,
        metric_group: Optional[MetricGroup] = None,
        slot_mask: Optional[Iterable[int]] = None,
    ):
        """slot_mask: participating sparse-slot indices (None = all slots).
        Excluded slots are fully absent from this trainer's program — their
        pooled features read zero, their embeddings receive no gradients,
        and their show/clk counters do not increment — the per-phase slot
        participation of the reference's join/update two-phase training
        (each phase runs a different program; box_wrapper.h:627-630,
        train/two_phase.py)."""
        self.model = model
        self.table_conf = table_conf
        self.conf = trainer_conf or TrainerConfig()
        self.slot_mask = normalize_slot_mask(slot_mask, model.n_sparse_slots)
        from paddlebox_tpu.models.layers import apply_compute_dtype_override

        apply_compute_dtype_override(model, self.conf.compute_dtype)
        n_extra = len(self.conf.counter_label_tasks)
        if n_extra and n_extra != table_conf.cvm_offset - 2:
            raise ValueError(
                f"counter_label_tasks has {n_extra} entries but the table's "
                f"cvm_offset={table_conf.cvm_offset} leaves "
                f"{table_conf.cvm_offset - 2} extra counter column(s)"
            )
        self.metric_group = metric_group
        self.n_tasks = getattr(model, "n_tasks", 1)
        # per-slot LR map (reference: BoxPS GetLRMap/SetLRMap,
        # box_wrapper.h:631): resolved host-side into a [S] vector; the
        # feed carries per-unique-key lr ("uniq_lr") when configured
        self._slot_lr_vec = resolve_slot_lr_vec(
            table_conf, model.n_sparse_slots
        )
        if self.conf.dense_optimizer == "adam":
            self.optimizer = optax.adam(self.conf.dense_lr)
        elif self.conf.dense_optimizer == "sgd":
            self.optimizer = optax.sgd(self.conf.dense_lr)
        else:
            raise ValueError(f"unknown dense optimizer {self.conf.dense_optimizer!r}")
        if self.conf.nan_policy not in ("raise", "skip_batch", "rollback"):
            raise ValueError(
                f"unknown nan_policy {self.conf.nan_policy!r} "
                "(want raise | skip_batch | rollback)"
            )
        # AutoCheckpointer for nan_policy="rollback" (assign after
        # construction); without one, rollback degrades to raise
        self.checkpointer = None
        with stage_scope("train.init"):
            self.params = model.init(jax.random.PRNGKey(seed))
            self.opt_state = self.optimizer.init(self.params)
        self._step_fn = None
        self._step_body = None
        self._eval_fn = None
        self.global_step = 0
        self._pass_idx = 0
        self.last_metric_state = None
        self._watch = CompletionWatcher()  # thread starts at first dispatch

    def close(self) -> None:
        """Retire the completion watcher's thread (the per-pass prefetcher
        is closed by train_from_dataset itself).  The trainer stays usable:
        the next dispatch starts a new watcher thread."""
        self._watch.close()

    @property
    def _check_nan(self) -> bool:
        """Per-batch finiteness check: explicit flag, or implied by any
        nan_policy that must SEE the flag to act on it."""
        return self.conf.check_nan_inf or self.conf.nan_policy != "raise"

    # -- the fused step ---------------------------------------------------- #
    def _build_step(self):
        model = self.model
        tconf = self.table_conf
        optimizer = self.optimizer
        check_nan = self._check_nan
        n_tasks = self.n_tasks
        has_group = self.metric_group is not None
        part_vec = slot_participation_vec(
            self.slot_mask, model.n_sparse_slots
        )
        # the model half of the step: the model's own ``loss`` where it
        # defines one, else apply -> sigmoid cross-entropy
        model_loss = make_model_loss(model, n_tasks)

        # named scopes are metadata on the same operations: they put a
        # stage's name into every op of a device trace (pull / seqpool_cvm
        # / tower / dense_opt / push / metrics) where XLA alone numbers its
        # fusions anew with every change
        def step(params, opt_state, values, g2sum, mstate, batch):
            with jax.named_scope("pull"):
                rows = pull_rows(
                    values, batch["idx"],
                    create_threshold=tconf.create_threshold,
                    cvm_offset=tconf.cvm_offset,
                    pull_embedx_scale=tconf.pull_embedx_scale,
                )
            if part_vec is not None:
                # occurrence-level participation: seg = ins*S + slot, so
                # seg % S is the slot (padding occurrences are already
                # key_mask=0).  Gating inside loss_fn (below) zeroes both
                # the pooled features AND, via the chain rule, the row
                # gradients of excluded slots.
                key_part = part_vec[batch["key_segments"] % part_vec.shape[0]]
            else:
                key_part = None

            @jax.named_scope("tower")
            def loss_fn(p, r):
                if key_part is not None:
                    r = r * key_part[:, None]
                return model_loss(p, r, batch)

            (loss, (preds, counts)), (pgrads, row_grads) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(params, rows)

            with jax.named_scope("dense_opt"):
                updates, opt_state = optimizer.update(
                    pgrads, opt_state, params)
                params = optax.apply_updates(params, updates)
            key_mask = batch["key_mask"]
            key_clicks = batch["key_clicks"]
            key_extras = batch.get("key_extras")
            if key_part is not None:
                # excluded slots increment no show/clk/extra counters either
                key_mask = key_mask * key_part
                key_clicks = key_clicks * key_part
                if key_extras is not None:
                    key_extras = key_extras * key_part[:, None]
            with jax.named_scope("push"):
                values, g2sum = push_and_update(
                    values, g2sum, row_grads, batch["idx"], batch["uniq_idx"],
                    batch["inverse"], key_mask, key_clicks, tconf,
                    key_extras=key_extras,
                    uniq_lr=batch.get("uniq_lr"),
                )
            primary = preds[:, 0] if n_tasks > 1 else preds
            mstate = add_counts(dict(mstate), counts)
            with jax.named_scope("metrics"):
                mstate, finite = step_metrics(
                    mstate, batch, loss, preds, primary, pgrads, row_grads)
            return params, opt_state, values, g2sum, mstate, loss, finite, primary

        def step_metrics(mstate, batch, loss, preds, primary, pgrads,
                         row_grads):
            mstate["auc"] = update_auc_state(
                mstate["auc"], primary, batch["labels"], batch["ins_mask"]
            )
            if "gn" in mstate:
                # grad-norm health stream rides the donated metric state —
                # no step-signature change: [sum of squared global grad
                # norms, steps]; a skip_batch discard drops its sample too
                gsq = jnp.zeros((), jnp.float32)
                for leaf in jax.tree.leaves(pgrads):
                    gsq += jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                gsq += jnp.sum(jnp.square(row_grads.astype(jnp.float32)))
                mstate["gn"] = mstate["gn"] + jnp.stack(
                    [gsq, jnp.ones((), jnp.float32)]
                )
            if n_tasks > 1:
                mstate["task"] = jax.vmap(
                    lambda s, pr, lb: update_auc_state(
                        s, pr, lb, batch["ins_mask"]
                    )
                )(mstate["task"], preds.T, batch["task_labels"].T)
            if has_group:
                mstate["group"] = MetricGroup.update(
                    mstate["group"], primary, batch["labels"],
                    batch["metric_masks"],
                )
            if check_nan:
                finite = jnp.isfinite(loss)
                for leaf in jax.tree.leaves(pgrads):
                    finite &= jnp.isfinite(leaf).all()
                finite &= jnp.isfinite(row_grads).all()
            else:
                finite = jnp.array(True)
            return mstate, finite

        self._step_body = step
        if check_nan and self.conf.nan_policy == "skip_batch":
            # skip_batch must discard the bad batch's updates, but the step
            # donates its state buffers — so the decision lives ON DEVICE:
            # run the body, then select pre- or post-batch state on the
            # finite flag.  The skipped batch contributes neither updates
            # nor metric counts; the host only observes finite=False.
            body = step

            def guarded(params, opt_state, values, g2sum, mstate, batch):
                out = body(params, opt_state, values, g2sum, mstate, batch)
                new_state, (loss, finite, primary) = out[:5], out[5:]
                old_state = (params, opt_state, values, g2sum, mstate)
                state = jax.lax.cond(
                    finite, lambda _: new_state, lambda _: old_state, None
                )
                return (*state, loss, finite, primary)

            return counted_jit(
                guarded, stage="train.step", donate_argnums=(0, 1, 2, 3, 4),
                **_step_schedule())
        return counted_jit(
            step, stage="train.step", donate_argnums=(0, 1, 2, 3, 4),
            **_step_schedule())

    def _init_mstate(self, auc_state=None) -> dict:
        """Fresh metric state, or continuation: pass the previous pass's
        ``trainer.last_metric_state`` (a dict) to carry EVERY stream forward;
        a bare AucState continues only the primary stream and is rejected
        when task/group streams exist (they would silently reset)."""
        n_counters = len(counter_names(self.model))
        if isinstance(auc_state, dict):
            # the step donates mstate: copy so the caller's reference (often
            # trainer.last_metric_state itself) is not invalidated by the
            # first step's buffer donation
            out = jax.tree.map(jnp.array, auc_state)
            if "gn" not in out:
                out["gn"] = jnp.zeros((2,), jnp.float32)
            if n_counters and "counters" not in out:
                out["counters"] = jnp.zeros((n_counters,), jnp.float32)
            return out
        if auc_state is not None and (self.n_tasks > 1 or self.metric_group):
            raise ValueError(
                "pass trainer.last_metric_state (dict) to continue metrics "
                "across passes — a bare AucState would reset the task/group "
                "streams while continuing the primary one"
            )
        mstate = {
            "auc": jax.tree.map(jnp.array, auc_state)
            if auc_state is not None
            else init_auc_state(self.conf.auc_buckets),
            "gn": jnp.zeros((2,), jnp.float32),
        }
        if n_counters:
            # the model's per-step sums (step_loss.counter_names)
            mstate["counters"] = jnp.zeros((n_counters,), jnp.float32)
        if self.n_tasks > 1:
            mstate["task"] = stack_auc_states(
                init_auc_state(self.conf.auc_buckets), self.n_tasks
            )
        if self.metric_group is not None:
            mstate["group"] = self.metric_group.init_state()
        return mstate

    # -- dense persistence -------------------------------------------------- #
    def dense_state(self) -> tuple:
        """(params, opt_state) for CheckpointManager.save_*."""
        return self.params, self.opt_state

    @START.wrap("dense_load")
    def load_dense_state(self, params, opt_state=None) -> None:
        if params is not None:
            self.params = params
        if opt_state is not None:
            self.opt_state = opt_state

    def _rollback_to_checkpoint(self, table) -> None:
        """nan_policy="rollback": abort the poisoned pass and restore the
        last completed pass from the attached AutoCheckpointer, then raise
        PassRolledBack.  Falls through (returning) when no checkpointer is
        attached or no pass ever completed — the caller re-raises the
        original NonFiniteBatchError."""
        acp = self.checkpointer
        if acp is None:
            logging.getLogger(__name__).warning(
                "nan_policy='rollback' but no checkpointer attached "
                "(set trainer.checkpointer) — raising instead"
            )
            return
        if acp.status() is None:
            logging.getLogger(__name__).warning(
                "nan_policy='rollback' but no completed pass recorded — "
                "raising instead"
            )
            return
        table.abort_pass()
        status, _ = acp.resume(table, self)
        stats.add("train.nan_rollback")
        # postmortem capture before the raise: the flight ring still
        # holds the spans/events leading into the poisoned pass
        from paddlebox_tpu import telemetry

        telemetry.dump_flight("pass_rollback", {
            "restored_pass": (status or {}).get("pass_idx")
            if isinstance(status, dict) else None,
            "pass_idx": self._pass_idx,
        })
        raise PassRolledBack(status)

    # -- public API --------------------------------------------------------- #
    def train_from_dataset(
        self,
        dataset,
        table: SparseTable,
        auc_state: Optional[AucState] = None,
        drop_last: bool = False,
        next_pass_keys=None,
    ) -> dict:
        """Run one pass over the dataset's batches (the TrainFiles analog).

        The caller owns the pass lifecycle: table.begin_pass() before,
        table.end_pass() after.  Returns the pass metrics.

        next_pass_keys: the NEXT pass's key census (array, or a zero-arg
        callable returning one — evaluated on the table's staging thread,
        so it may block on a dataset preload).  Handed to
        table.prepare_pass once this pass's feeds are exhausted, while the
        device still drains its queued tail steps — the pre-promotion half
        of pass-boundary pipelining (no-op on serial tables).

        Non-finite batches follow TrainerConfig.nan_policy: "raise" aborts
        (NonFiniteBatchError), "skip_batch" discards the batch on device
        and continues, "rollback" (with trainer.checkpointer set) restores
        the last completed pass and raises PassRolledBack — in that one
        case the pass was aborted and the caller must skip end_pass().
        """
        # ONE profiler, always on, and the same loop whatever is asked for:
        # profile / the trace dirs only decide what is reported and written
        # after the pass, from the registry's delta over it
        prof = StatsProfiler()
        # the pass's head: the metric state and its baselines (eager
        # programs and a read-back), the telemetry's own set-up, the
        # watchdog's -- the device idles under it, so it has a name
        with prof.stage("open"):
            if self._step_fn is None:
                self._step_fn = self._build_step()
            with stage_scope("train.init"):
                mstate = self._init_mstate(auc_state)
                # grad-norm baseline: the accumulator carries across
                # continued passes, so the per-pass value is a delta
                # between host snapshots (materialized NOW — the first
                # step donates the buffer)
                gn_base = np.asarray(mstate["gn"], dtype=np.float64)
                counters_base = np.asarray(
                    mstate.get("counters", ()), dtype=np.float64)
            vocab_keys = getattr(self.model, "vocab_keys", None)
            pass_t0 = time.monotonic()
            n_samples = [0.0]
            values, g2sum = table.values, table.g2sum
            losses, n_steps = [], 0
            uses_rank = getattr(self.model, "uses_rank_offset", False)
            uses_seq = getattr(self.model, "uses_seq_pos", False)
            dumper = None
            if self.conf.need_dump_field and self.conf.dump_fields_path:
                from paddlebox_tpu.train.dump import FieldDumper

                dumper = FieldDumper(
                    os.path.join(self.conf.dump_fields_path,
                                 f"dump-{self.global_step}.txt"),
                    self.conf.dump_fields,
                )
            from paddlebox_tpu import telemetry

            # telemetry policy: explicit config wins, env flags otherwise
            # (PBOX_METRICS_PORT / PBOX_TRACE_DIR / PBOX_EVENTS_PATH — the
            # launcher's per-rank knobs).  The exporter/event log are
            # per-process singletons: first pass starts them, later passes
            # are no-ops.
            from paddlebox_tpu.config import TelemetryConfig

            tele = self.conf.telemetry or TelemetryConfig.from_flags()
            telemetry.ensure_exporter(tele.metrics_port or None)
            event_log = telemetry.ensure_event_log(tele.events_path or None)
            # host span tracing: TrainerConfig.trace_dir (which also drives
            # the jax device trace) or the telemetry trace dir alone
            host_trace_dir = self.conf.trace_dir or tele.trace_dir
            if host_trace_dir:
                from paddlebox_tpu.telemetry.events import _default_rank

                telemetry.enable_tracing(pid=_default_rank())

            watch = self._watch
            want_report = bool(self.conf.profile or host_trace_dir)
            prof_mark = prof.mark() if want_report else None
            complete_mark = CompletionWatcher.mark() if want_report else None

            # distributed-liveness watchdog: stage-reported progress (feed
            # / step) with a stall deadline; single-process runs get local
            # stall detection, multi-process runs additionally publish
            # heartbeats and converge on coordinated abort
            # (parallel/watchdog.py)
            wd_mod = _watchdog_mod()
            wd = None
            stall_exc: tuple = ()
            if wd_mod is not None:
                stall_exc = (wd_mod.DistributedStallError,)
                if self.conf.liveness is not None:
                    wd = wd_mod.for_trainer(
                        self.conf.liveness,
                        namespace=f"train-{self.global_step}")
                    if wd is not None:
                        wd.start()

        def feeds():
            """(batch, device feed) stream: validation, host planning and
            the transfer."""
            for batch in prof.iterate(
                    "batch", dataset.batches(drop_last=drop_last)):
                if wd is not None:
                    wd.report("feed")
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
                if self.n_tasks > 1 and (
                    batch.task_labels is None
                    or batch.task_labels.shape[1] != self.n_tasks
                ):
                    got = (
                        0 if batch.task_labels is None
                        else batch.task_labels.shape[1]
                    )
                    raise RuntimeError(
                        f"model has {self.n_tasks} tasks but the batch carries "
                        f"{got} task label columns: configure "
                        "DataFeedConfig.task_label_slots with "
                        f"{self.n_tasks - 1} slots (task 0 is the primary label)"
                    )
                with prof.stage("plan"):
                    plan = table.plan_batch(batch)
                with prof.stage("feed"):
                    host = _host_batch_dict(
                        batch, plan, batch.n_sparse_slots,
                        self.conf.counter_label_tasks,
                        slot_lr_vec=self._slot_lr_vec,
                        vocab_keys=vocab_keys,
                    )
                    if self.metric_group is not None:
                        host["metric_masks"] = self.metric_group.masks(batch)
                if faults.fire("train.nan"):
                    # chaos injection: poison this batch's labels so the
                    # loss/grads genuinely go NaN and the configured
                    # nan_policy is exercised end to end on device
                    host["labels"] = np.full_like(host["labels"], np.nan)
                n_samples[0] += float(batch.ins_mask.sum())
                with prof.stage("feed"):
                    dev = _to_device(host)
                yield batch, dev

        prefetcher = None
        check_nan = self._check_nan
        skip_batches = check_nan and self.conf.nan_policy == "skip_batch"
        try:
          try:
            with telemetry.span("pass", pass_idx=self._pass_idx,
                                global_step=self.global_step), \
                 device_trace(self.conf.trace_dir or None):
              if self.conf.prefetch_batches > 0:
                # feed assembly overlaps the device step.  Started inside
                # the pass span: the producer's plan/feed spans inherit it
                # as their parent.
                prefetcher = _FeedPrefetcher(
                    feeds(), self.conf.prefetch_batches, prof)
                feed_iter = prefetcher
              else:
                feed_iter = feeds()
              for batch, dev in feed_iter:
                # chaos site: a hang here simulates a stalled device step;
                # the watchdog bounds it and names this process + stage
                faults.inject("train.step")
                t_dispatch = time.perf_counter()
                with prof.stage("step"):
                    (self.params, self.opt_state, values, g2sum, mstate,
                     loss, finite, preds) = (
                        self._step_fn(self.params, self.opt_state, values,
                                      g2sum, mstate, dev)
                    )
                watch.dispatched(loss, t_dispatch)
                if wd is not None:
                    wd.report("step")
                # pbox-lint: ignore[host-sync-in-hot-loop] nan gate: with
                # check_nan on, the per-step finite readback IS the
                # feature (opt-in; default-off config pays nothing —
                # `check_nan and` short-circuits before bool(finite))
                if check_nan and not bool(finite):
                    if skip_batches:
                        # the guarded step already returned the pre-batch
                        # state: this batch contributed nothing — no
                        # update, no metrics, no dump, no step count
                        stats.add("train.nan_skipped_steps")
                        stats.add(
                            "train.nan_skipped_ins",
                            float(batch.ins_mask.sum()),
                        )
                        continue
                    raise NonFiniteBatchError(
                        f"non-finite loss/grad at step {self.global_step} "
                        "(FLAGS_check_nan_inf analog)"
                    )
                if dumper is not None:
                    with prof.stage("dump"):
                        dumper.dump_batch(batch, np.asarray(preds))
                losses.append(loss)  # device scalars; synced once at pass end
                n_steps += 1
                self.global_step += 1
          finally:
            # old buffers were donated to the jitted step: always hand the
            # live ones back so end_pass() works even after a NaN raise.
            # The watchdog retires FIRST so its abort latch cannot fire
            # into the teardown itself.
            if wd is not None:
                wd.close()
            table.values, table.g2sum = values, g2sum
            if prefetcher is not None:
                prefetcher.close()
            if dumper is not None:
                dumper.close()
        except NonFiniteBatchError:
            if self.conf.nan_policy == "rollback":
                self._rollback_to_checkpoint(table)  # raises PassRolledBack
            raise
        except stall_exc:
            # coordinated abort: the pass is torn down (prefetcher closed,
            # buffers handed back).  With rollback_on_abort + an attached
            # checkpointer, restore the last completed pass so no
            # partially-applied pass survives; resumed replay is then
            # bit-exact (PassRolledBack tells the driver where to re-run).
            stats.add("train.stall_aborts")
            if (
                self.conf.liveness is not None
                and self.conf.liveness.rollback_on_abort
            ):
                self._rollback_to_checkpoint(table)  # raises PassRolledBack
            raise
        # pre-promotion: the feed loop is done but the device is still
        # draining queued steps (and the metric readback below blocks on
        # them) — exactly the tail window the next pass's census resolve +
        # init + staging can hide in
        if next_pass_keys is not None:
            prepare = getattr(table, "prepare_pass", None)
            if prepare is not None:
                prepare(next_pass_keys)
        if self.conf.need_dump_param and self.conf.dump_fields_path:
            from paddlebox_tpu.train.dump import dump_params

            dump_params(
                os.path.join(
                    self.conf.dump_fields_path, f"param-{self.global_step}"
                ),
                self.params,
                table=table,
                select=self.conf.dump_param,
            )
        # the device's tail: the read-back below waits for the last queued
        # step anyway; waiting here first gives the wait its own name and
        # leaves ``readback`` the eager metric programs alone
        with prof.stage("drain"):
            if losses:
                losses[-1].block_until_ready()
            watch.settle()
            # the device has nothing queued: did the host let the pass's
            # threads run (the feed producer answered before it exited)
            HOST.after_drain(watch)
        with stage_scope("train.readback"), prof.stage("readback"):
            metrics = self._read_back(mstate, losses, gn_base)
            if "counters" in mstate:
                metrics.update(publish_counters(
                    self.model,
                    np.asarray(mstate["counters"], dtype=np.float64),
                    counters_base))
        # the pass's tail is the telemetry's own -- the pass report, the
        # registry's delta over every series, the health rules, the
        # pass_end record -- with the device idle: it has a name too
        with prof.stage("observe"):
            metrics["steps"] = n_steps
            # samples/s without trace files: the pass_end record carries
            # wall-clock duration and the instance count it covered
            metrics["duration_s"] = time.monotonic() - pass_t0
            metrics["samples"] = float(n_samples[0])
            pass_seconds().observe(metrics["duration_s"])
            if want_report:
                metrics["profile"] = prof.report(
                    prof_mark, n_steps, complete_mark)
                if self.conf.profile:
                    print("[profile]", prof.log_line(metrics["profile"]))
            if host_trace_dir:
                from paddlebox_tpu.telemetry.events import _default_rank

                telemetry.flush_trace(os.path.join(
                    host_trace_dir,
                    f"host-trace-r{_default_rank()}-pass{self._pass_idx}"
                    ".json",
                ))
            # run-health plane: evaluate the rule catalog against the SAME
            # window the pass_end record carries (the delta snapshot resets
            # its baseline per call — there is exactly one consumer chain),
            # BEFORE the record is written so a consumer that tails up to
            # pass_end already has the window's health_alert events
            snap = telemetry.registry.delta_snapshot()
            telemetry.observe_pass(
                self._pass_idx, metrics=metrics, telemetry=snap, table=table
            )
            if event_log is not None:
                event_log.log_pass(metrics, telemetry=snap,
                                   pass_idx=self._pass_idx)
        self._pass_idx += 1
        self.last_auc_state = mstate["auc"]
        self.last_metric_state = mstate
        return metrics

    def _read_back(self, mstate: dict, losses: list, gn_base) -> dict:
        """The pass's metrics from the device's metric state: AUC streams,
        mean loss, gradient and weight norms (eager programs, tagged
        ``train.readback`` by the caller)."""
        from paddlebox_tpu import telemetry

        metrics = compute_metrics(mstate["auc"])
        if self.n_tasks > 1:
            metrics.update(
                compute_metrics_stacked(
                    mstate["task"], [f"task{t}" for t in range(self.n_tasks)]
                )
            )
        if self.metric_group is not None:
            metrics.update(self.metric_group.compute(mstate["group"]))
        metrics["loss"] = (
            float(
                jnp.concatenate([jnp.atleast_1d(l) for l in losses]).mean()
            )
            if losses
            else 0.0
        )
        gn_now = np.asarray(mstate["gn"], dtype=np.float64)
        d_sq, d_n = gn_now[0] - gn_base[0], gn_now[1] - gn_base[1]
        if d_n > 0:
            grad_norm = float(np.sqrt(d_sq / d_n)) if d_sq >= 0 else float(
                "nan")
            metrics["grad_norm"] = grad_norm
            telemetry.gauge(
                "train.grad_norm",
                "per-pass RMS global gradient norm (dense + sparse)",
            ).set(grad_norm)
        wsq = sum(
            float(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for leaf in jax.tree.leaves(self.params)
        )
        metrics["weight_norm"] = math.sqrt(wsq) if wsq >= 0 else float("nan")
        telemetry.gauge(
            "train.weight_norm", "dense parameter L2 norm at pass end"
        ).set(metrics["weight_norm"])
        return metrics

    # -- inference / evaluation -------------------------------------------- #
    def _build_eval_step(self):
        model = self.model
        tconf = self.table_conf
        uses_rank = getattr(model, "uses_rank_offset", False)
        uses_seq = getattr(model, "uses_seq_pos", False)
        n_tasks = self.n_tasks

        def step(params, values, auc, batch):
            rows = pull_rows(
                values, batch["idx"],
                create_threshold=tconf.create_threshold,
                cvm_offset=tconf.cvm_offset,
                pull_embedx_scale=tconf.pull_embedx_scale,
            )
            bsz = batch["labels"].shape[0]
            extra = {"rank_offset": batch["rank_offset"]} if uses_rank else {}
            if uses_seq:
                extra["seq_pos"] = batch["seq_pos"]
            logits = model.apply(
                params, rows, batch["key_segments"], batch["dense"], bsz, **extra
            )
            preds = jax.nn.sigmoid(logits[:, 0] if n_tasks > 1 else logits)
            auc = update_auc_state(auc, preds, batch["labels"], batch["ins_mask"])
            return auc

        return counted_jit(step, stage="train.eval", donate_argnums=(2,))

    def evaluate(self, dataset, table: SparseTable, drop_last: bool = False) -> dict:
        """Forward-only pass: no table/param updates, streaming AUC only —
        the ``infer_from_dataset`` analog (reference: executor.py:1520
        infer_from_dataset; BoxPS SetTestMode).  Requires an open pass."""
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_step()
        uses_rank = getattr(self.model, "uses_rank_offset", False)
        uses_seq = getattr(self.model, "uses_seq_pos", False)
        auc = init_auc_state(self.conf.auc_buckets)
        for batch in dataset.batches(drop_last=drop_last):
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
            plan = table.plan_batch(batch)
            dev = _device_batch(batch, plan, batch.n_sparse_slots)
            auc = self._eval_fn(self.params, table.values, auc, dev)
        return compute_metrics(auc)

    def train_steps(self, table: SparseTable, batches: Iterable[HostBatch]) -> dict:
        """Lower-level entry: train over an explicit batch iterable."""

        class _Wrapper:
            def __init__(self, it):
                self._it = it

            def batches(self, drop_last=False):
                return iter(self._it)

        return self.train_from_dataset(_Wrapper(batches), table)
