"""Single-chip trainer: the step program and what it says about a pass (the
pass loop itself is train/pass_loop.py run_pass, shared with the sharded
trainer).

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
import os
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
    init_auc_state,
    update_auc_state,
)
from paddlebox_tpu.metrics.variants import MetricGroup
from paddlebox_tpu.sparse.table import SparseTable, pull_rows, push_and_update
from paddlebox_tpu.telemetry.compiles import counted_jit, stage_scope
from paddlebox_tpu.train import pass_loop
# the feed prefetcher lives with the pass protocol; re-exported here for
# its historical import path
from paddlebox_tpu.train.pass_loop import _FeedPrefetcher  # noqa: F401
from paddlebox_tpu.utils.profiler import START, CompletionWatcher
from paddlebox_tpu.utils import faults
from paddlebox_tpu.utils.monitor import stats


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
from paddlebox_tpu.train.step_loss import add_counts, make_model_loss  # noqa: E402


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


class _Pass(pass_loop.PassHooks):
    """What the single-chip trainer says about one pass: its producer, its
    step on ``values, g2sum, mstate``, its nan policies, its field and
    parameter dumps (the protocol is pass_loop.run_pass)."""

    def __init__(self, trainer: "Trainer", dataset, table, drop_last: bool):
        self.trainer, self.dataset, self.table = trainer, dataset, table
        self.drop_last = drop_last
        self.n_samples = 0.0

    def open(self) -> None:
        t = self.trainer
        if t._step_fn is None:
            t._step_fn = t._build_step()
        self.values, self.g2sum = self.table.values, self.table.g2sum
        self.check_nan = t._check_nan
        self.skip_batches = (
            self.check_nan and t.conf.nan_policy == "skip_batch")

    def feeds(self):
        """(batch, device feed) stream: validation, host planning and
        the transfer."""
        t, table, prof, wd = self.trainer, self.table, self.prof, self.wd
        vocab_keys = getattr(t.model, "vocab_keys", None)
        uses_rank = getattr(t.model, "uses_rank_offset", False)
        uses_seq = getattr(t.model, "uses_seq_pos", False)
        for batch in prof.iterate(
                "batch", self.dataset.batches(drop_last=self.drop_last)):
            if wd is not None:
                wd.report("feed")
            pass_loop.validate_batch(batch, uses_rank, uses_seq, t.n_tasks)
            with prof.stage("plan"):
                plan = table.plan_batch(batch)
            with prof.stage("feed"):
                host = _host_batch_dict(
                    batch, plan, batch.n_sparse_slots,
                    t.conf.counter_label_tasks,
                    slot_lr_vec=t._slot_lr_vec,
                    vocab_keys=vocab_keys,
                )
                if t.metric_group is not None:
                    host["metric_masks"] = t.metric_group.masks(batch)
            if faults.fire("train.nan"):
                # chaos injection: poison this batch's labels so the
                # loss/grads genuinely go NaN and the configured
                # nan_policy is exercised end to end on device
                host["labels"] = np.full_like(host["labels"], np.nan)
            self.n_samples += float(batch.ins_mask.sum())
            with prof.stage("feed"):
                dev = _to_device(host)
            yield batch, dev

    def dispatch(self, feed) -> tuple:
        t = self.trainer
        (t.params, t.opt_state, self.values, self.g2sum, self.mstate,
         loss, finite, self.preds) = t._step_fn(
            t.params, t.opt_state, self.values, self.g2sum, self.mstate,
            feed[1])
        return loss, finite

    def after_step(self, feed, finite) -> bool:
        # pbox-lint: ignore[host-sync-in-hot-loop] nan gate: with
        # check_nan on, the per-step finite readback IS the
        # feature (opt-in; default-off config pays nothing —
        # `check_nan and` short-circuits before bool(finite))
        if self.check_nan and not bool(finite):
            if self.skip_batches:
                # the guarded step already returned the pre-batch
                # state: this batch contributed nothing — no
                # update, no metrics, no dump, no step count
                stats.add("train.nan_skipped_steps")
                stats.add(
                    "train.nan_skipped_ins", float(feed[0].ins_mask.sum()))
                return False
            raise NonFiniteBatchError(
                f"non-finite loss/grad at step {self.trainer.global_step} "
                "(FLAGS_check_nan_inf analog)"
            )
        if self.dumper is not None:
            with self.prof.stage("dump"):
                self.dumper.dump_batch(feed[0], np.asarray(self.preds))
        return True

    def hand_back(self) -> None:
        self.table.values, self.table.g2sum = self.values, self.g2sum

    def dump_params(self) -> None:
        conf = self.trainer.conf
        if conf.need_dump_param and conf.dump_fields_path:
            from paddlebox_tpu.train.dump import dump_params

            dump_params(
                os.path.join(
                    conf.dump_fields_path,
                    f"param-{self.trainer.global_step}"),
                self.trainer.params,
                table=self.table,
                select=conf.dump_param,
            )

    def read_back(self, losses: list, gn_base) -> dict:
        metrics = self.trainer._read_back(self.mstate, losses, gn_base)
        metrics["samples"] = self.n_samples
        return metrics

    def index(self) -> tuple:
        return "pass_idx", self.trainer._pass_idx


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
                mstate, finite = pass_loop.step_metrics(
                    mstate, batch, loss, preds, primary, pgrads, row_grads,
                    n_tasks=n_tasks, has_group=has_group, check_nan=check_nan)
            return params, opt_state, values, g2sum, mstate, loss, finite, primary

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
        """Fresh metric state, or the continuation of ``auc_state``
        (pass_loop.init_metric_state)."""
        return pass_loop.init_metric_state(self, auc_state)

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
        """Run one pass over the dataset's batches (the TrainFiles analog):
        pass_loop.run_pass around this trainer's producer and step.

        The caller owns the pass lifecycle: table.begin_pass() before,
        table.end_pass() after.  Returns the pass metrics.

        next_pass_keys: the NEXT pass's key census, handed to
        table.prepare_pass once this pass's feeds are exhausted
        (pass_loop.run_pass).

        Non-finite batches follow TrainerConfig.nan_policy: "raise" aborts
        (NonFiniteBatchError), "skip_batch" discards the batch on device
        and continues, "rollback" (with trainer.checkpointer set) restores
        the last completed pass and raises PassRolledBack — in that one
        case the pass was aborted and the caller must skip end_pass().
        """
        try:
            return pass_loop.run_pass(
                self, _Pass(self, dataset, table, drop_last), table,
                auc_state, next_pass_keys)
        except NonFiniteBatchError:
            if self.conf.nan_policy == "rollback":
                self._rollback_to_checkpoint(table)  # raises PassRolledBack
            raise
        except pass_loop.stall_errors():
            # coordinated abort: the pass is torn down (prefetcher closed,
            # buffers handed back).  With rollback_on_abort + an attached
            # checkpointer, restore the last completed pass so no
            # partially-applied pass survives; resumed replay is then
            # bit-exact (PassRolledBack tells the driver where to re-run).
            if (
                self.conf.liveness is not None
                and self.conf.liveness.rollback_on_abort
            ):
                self._rollback_to_checkpoint(table)  # raises PassRolledBack
            raise

    def _read_back(self, mstate: dict, losses: list, gn_base) -> dict:
        """The pass's metrics from the device's metric state: AUC streams,
        mean loss, gradient and weight norms (eager programs, tagged
        ``train.readback`` by the caller)."""
        loss = (
            float(
                jnp.concatenate([jnp.atleast_1d(l) for l in losses]).mean()
            )
            if losses
            else 0.0
        )
        return pass_loop.read_back_common(
            mstate, gn_base, self.params, self.n_tasks, self.metric_group,
            {"loss": loss})

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
            pass_loop.validate_batch(batch, uses_rank, uses_seq, 1)
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
