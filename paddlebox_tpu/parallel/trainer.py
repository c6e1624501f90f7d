"""Multi-chip training: data parallel over the mesh, sparse pull/push via
all_to_all against the key-sharded table.

TPU-native redesign of the reference's multi-GPU path (SURVEY.md §2.9/§3.2):

  * sparse pull  — the reference calls ``boxps_ptr_->PullSparseGPU`` whose
    closed lib resolves remote shards over NVLink/MPI.  Here the host plan
    (sharded_table.plan_group) already bucketed row requests per owner, so
    the device does: all_to_all(requested rows) -> local HBM gather ->
    all_to_all(rows back) -> occurrence scatter.  All static shapes, all on
    ICI.
  * sparse push  — transpose of pull: segment-sum per-occurrence grads into
    per-owner buckets, all_to_all, scatter-add into the local shard's
    accumulator, then ONE vectorized sparse-adagrad update over the shard
    (rows untouched this batch see zero grad and are left exactly unchanged).
    Duplicate keys across chips merge in the accumulator — same semantics as
    the reference's ``PushMergeCopy`` + closed-lib update
    (box_wrapper_impl.h:165-255).
  * dense sync   — ``sync_dense_mode="step"``: psum gradients every step (the
    allreduce path, transpiler/collective.py:196-287); ``"kstep"``: local
    updates + param pmean every ``sync_weight_step`` steps (the reference's
    DenseKStep sync, boxps_worker.cc:481-521).
  * metrics      — per-device AUC histograms, merged at read time
    (box_wrapper.cc:230-273 collect_data_nccl analog is a host-side sum here;
    use metrics.auc.psum_auc_state to fold it into the step if desired).

The whole step runs under one jit(shard_map(...)) with donated state, so XLA
overlaps the all_to_alls with the dense tower compute where possible.  The
pass around it is train/pass_loop.py run_pass, the single-chip trainer's too.
"""

from __future__ import annotations

from typing import Iterable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu import telemetry
from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.feed import HostBatch, empty_like, key_classes
from paddlebox_tpu.metrics.auc import (
    AucState,
    compute_metrics,
    init_auc_state,
    update_auc_state,
)
from paddlebox_tpu.metrics.variants import MetricGroup
from paddlebox_tpu.parallel.mesh import DATA_AXIS
from paddlebox_tpu.parallel.multiprocess import (
    global_from_local,
    host_allgather,
    is_multiprocess,
    local_device_indices,
    local_view,
    merge_device_axis,
    read_replicated,
)
from paddlebox_tpu.parallel.sharded_table import ShardedBatchPlan, ShardedSparseTable
from paddlebox_tpu.sparse.optimizer import sparse_adagrad_update
from paddlebox_tpu.sparse.table import merge_occurrences, scatter_add_rows
from paddlebox_tpu.telemetry.compiles import counted_jit, stage_scope
from paddlebox_tpu.train import pass_loop
from paddlebox_tpu.train.slot_policy import (
    normalize_slot_mask,
    resolve_slot_lr_vec,
    slot_participation_vec,
)
from paddlebox_tpu.train.step_loss import add_counts, make_model_loss
from paddlebox_tpu.utils.profiler import START, CompletionWatcher


# process-wide pass counter for host-plane channel names: advances once per
# training pass in every process (all processes drive passes in lockstep,
# the same assumption collectives already impose), so channels stay unique
# even across multiple MultiChipTrainer instances
_PLAN_CHANNEL_SEQ = [0]

# pass-boundary fleet-snapshot sequence (same lockstep argument): every
# process gathers its metric snapshot under this seq so rank 0 can log ONE
# merged fleet view per pass
_FLEET_SNAP_SEQ = [0]


def _stack_group(
    batches: Sequence[HostBatch],
    plan: ShardedBatchPlan,
    n_slots: int,
    metric_group: Optional[MetricGroup] = None,
    vocab_keys: Optional[np.ndarray] = None,
) -> dict:
    """Stack per-device batches + plan into [D, ...] arrays (numpy).
    ``vocab_keys``: the model's fixed vocabulary; the feed then carries
    each occurrence's class (data/feed.py key_classes)."""
    key_clicks = []
    for b, m in zip(batches, plan.key_mask):
        ins = np.minimum(b.key_segments // n_slots, b.batch_size - 1)
        key_clicks.append(b.labels[ins] * m)
    extra = {}
    if batches[0].rank_offset is not None:
        extra["rank_offset"] = np.stack([b.rank_offset for b in batches])
    if batches[0].seq_pos is not None:
        extra["seq_pos"] = np.stack([b.seq_pos for b in batches])
    if batches[0].task_labels is not None:
        extra["task_labels"] = np.stack([b.task_labels for b in batches])
    if vocab_keys is not None:
        extra["key_class"] = np.stack(
            [key_classes(b.keys, b.n_keys, vocab_keys) for b in batches])
    if metric_group is not None:
        extra["metric_masks"] = np.stack(
            [metric_group.masks(b) for b in batches]
        )
    if plan.serve_lr is not None:
        extra["uniq_lr"] = plan.serve_lr
    if plan.hot_occ is not None:
        # realized hybrid placement: hot routing rides the feed like every
        # other plan array — padded [D, K]/[D, H] shapes, so the jitted
        # step never sees the live plan (zero-retrace under plan churn)
        extra["hot_occ"] = plan.hot_occ
        extra["hot_lr"] = plan.hot_lr
    return {
        **extra,
        "serve_rows": plan.serve_rows,
        "occ_flat": plan.occ_flat,
        "serve_map": plan.serve_map,
        "serve_uniq": plan.serve_uniq,
        "key_mask": plan.key_mask,
        "key_clicks": np.stack(key_clicks),
        "key_segments": np.stack([b.key_segments for b in batches]),
        "dense": np.stack([b.dense for b in batches]),
        "labels": np.stack([b.labels for b in batches]),
        "ins_mask": np.stack([b.ins_mask for b in batches]),
    }


def sharded_pull(values: jax.Array, serve_rows: jax.Array, occ_flat: jax.Array,
                 create_threshold: float, cvm_offset: int) -> jax.Array:
    """Device-local half of a cross-chip pull (call inside shard_map).

    The host plan already told this shard which rows to serve, so there is no
    key-exchange round trip (reference pays CopyKeys + DedupKeysAndFillIdx,
    box_wrapper_impl.h:95-122): local gather -> ONE all_to_all -> occurrence
    scatter.

    values: [cap, W] local shard; serve_rows: [n, C] rows this shard serves
    to each requester; occ_flat: [K] into the received [n, C] response.
    Returns pulled rows [K, W].
    """
    n, C = serve_rows.shape
    W = values.shape[1]
    served = jnp.take(values, serve_rows.reshape(-1), axis=0)  # [n*C, W]
    with jax.named_scope("exchange"):
        got = jax.lax.all_to_all(served.reshape(n, C, W), DATA_AXIS, 0, 0)
    got_flat = jnp.concatenate(
        [got.reshape(n * C, W), jnp.zeros((1, W), values.dtype)]
    )
    rows = jnp.take(got_flat, occ_flat, axis=0)  # [K, W]
    if create_threshold > 0.0:
        visible = (rows[..., 0:1] >= create_threshold).astype(rows.dtype)
        rows = jnp.concatenate(
            [rows[..., :cvm_offset], rows[..., cvm_offset:] * visible], axis=-1
        )
    return rows


def hybrid_pull(
    values: jax.Array,
    hot_values: jax.Array,
    serve_rows: jax.Array,
    occ_flat: jax.Array,
    hot_occ: jax.Array,
    create_threshold: float,
    cvm_offset: int,
) -> jax.Array:
    """Hybrid-placement pull (call inside shard_map): cold occurrences ride
    the existing all_to_all path, hot occurrences gather from the
    REPLICATED local hot block — zero host-plane and zero ICI row bytes for
    the skewed-hot head (the Parallax/Parameter-Box replication payoff).

    hot_values: [H, W] this device's copy of the replicated hot block.
    hot_occ: [K] slot into the hot block, H = cold/padding sink (those
    occurrences carry a real cold route in occ_flat; hot occurrences carry
    the cold n*C sink, so the two selects partition exactly).
    create_threshold is applied AFTER the select so hot and cold rows see
    the identical visibility rule.
    """
    rows = sharded_pull(values, serve_rows, occ_flat, 0.0, cvm_offset)
    H, W = hot_values.shape
    hot_ext = jnp.concatenate(
        [hot_values, jnp.zeros((1, W), hot_values.dtype)]
    )
    hrows = jnp.take(hot_ext, hot_occ, axis=0)
    rows = jnp.where((hot_occ < H)[:, None], hrows, rows)
    if create_threshold > 0.0:
        visible = (rows[..., 0:1] >= create_threshold).astype(rows.dtype)
        rows = jnp.concatenate(
            [rows[..., :cvm_offset], rows[..., cvm_offset:] * visible], axis=-1
        )
    return rows


def hybrid_hot_update(
    hot_values: jax.Array,
    hot_g2sum: jax.Array,
    row_grads: jax.Array,
    hot_occ: jax.Array,
    hot_lr: jax.Array,
    key_mask: jax.Array,
    key_clicks: jax.Array,
    conf: SparseTableConfig,
):
    """Replica-identical hot-block update (call inside shard_map).

    Level 1 mirrors the cold path's occurrence merge (segment_sum in
    occurrence order); level 2 is the DETERMINISTIC-ORDER psum: an
    all_gather followed by an unrolled device-ascending fold, the same
    requester-major device order the cold path's serve_map segment-sum
    folds in — so a key served hot reduces its cross-device contributions
    in exactly the order it would have reduced them cold, and the
    planned-vs-hash bit-exactness pin holds (ARCHITECTURE.md "Hybrid
    placement", reduction-order argument).

    The adagrad apply is UNCONDITIONAL over all H padded slots: an
    untouched slot has an exactly-zero merged gradient, and sparse adagrad
    of a zero gradient is an exactly-zero delta (zero clip, zero scaled
    update), so padding and unreferenced residents stay bitwise unchanged
    without any fill-mask data dependence.  hot_lr is 0.0 on devices
    without an occurrence of the slot; the pmax fold recovers the one real
    lr (max{lr, 0} = lr) identically on every replica.
    """
    H, W = hot_values.shape
    co = conf.cvm_offset
    # the one occurrence merge, counters in the first co columns; segment
    # H is the sink of occurrences served cold, dropped
    contrib = merge_occurrences(
        row_grads, key_mask, key_clicks, None, hot_occ, H + 1, co,
        hot_values.dtype,
    )[:H]  # [H, W]
    with jax.named_scope("hot_fold"):
        gathered = jax.lax.all_gather(contrib, DATA_AXIS)  # [n, H, W]
        acc = gathered[0]
        for i in range(1, gathered.shape[0]):  # unrolled: fixed fold order
            acc = acc + gathered[i]
        lr = jax.lax.pmax(hot_lr, DATA_AXIS)
    w_delta, g2_delta = sparse_adagrad_update(
        hot_g2sum, acc[:, co:], lr, conf.initial_g2sum, conf.grad_clip,
    )
    hot_values = hot_values + jnp.concatenate([acc[:, :co], w_delta], axis=1)
    hot_g2sum = hot_g2sum + g2_delta
    return hot_values, hot_g2sum


def sharded_push_and_update(
    values: jax.Array,
    g2sum: jax.Array,
    row_grads: jax.Array,
    occ_flat: jax.Array,
    serve_map: jax.Array,
    serve_uniq: jax.Array,
    key_mask: jax.Array,
    key_clicks: jax.Array,
    conf: SparseTableConfig,
    uniq_lr: Optional[jax.Array] = None,
):
    """Device-local half of a cross-chip push (call inside shard_map).

    Merges occurrence grads into per-owner buckets, exchanges them (the one
    push all_to_all), folds contributions from all requesters of the same row
    into one segment via the host-precomputed dedup (serve_map/serve_uniq),
    and applies show/clk counters + sparse adagrad to exactly the touched
    rows — O(batch), not O(shard).

    uniq_lr: optional [US] per-served-unique-row learning rates (the LR-map
    analog on the sharded path, planned host-side by plan_group — reference:
    box_wrapper.h:631 GetLRMap applied in the multi-GPU push).  None = the
    scalar conf.learning_rate.
    """
    n, C = serve_map.shape
    co = conf.cvm_offset
    cap, W = values.shape
    US = serve_uniq.shape[0]
    nseg = n * C + 1  # last segment = padding/overflow sink, dropped
    send = merge_occurrences(
        row_grads, key_mask, key_clicks, None, occ_flat, nseg, co,
        values.dtype,
    )[: n * C].reshape(n, C, W)
    with jax.named_scope("exchange"):
        recv = jax.lax.all_to_all(send, DATA_AXIS, 0, 0)  # [n, C, W]
    # cross-requester merge: duplicate rows across devices fold together
    acc = jax.ops.segment_sum(
        recv.reshape(n * C, W), serve_map.reshape(-1), num_segments=US
    )  # [US, W]
    g2_rows = jnp.take(g2sum, serve_uniq)
    lr = conf.learning_rate if uniq_lr is None else uniq_lr
    w_delta, g2_delta = sparse_adagrad_update(
        g2_rows, acc[:, co:], lr, conf.initial_g2sum, conf.grad_clip,
    )
    delta = jnp.concatenate([acc[:, :co], w_delta], axis=1)
    # serve_uniq targets are unique EXCEPT possibly repeated dead-row
    # entries (np.unique's own dead entry for census-missing keys, plus
    # scratch-clamped pad slots — sharded_table.plan_group).  Dead-row
    # gradients are discarded by the scrub below regardless, so zero every
    # dead-targeted delta first: duplicates then only write unchanged
    # bytes and the unique_indices claim stays benign under any lowering.
    ok = (serve_uniq != cap - 1).astype(delta.dtype)
    values = scatter_add_rows(values, serve_uniq, delta * ok[:, None])
    g2sum = g2sum.at[serve_uniq].add(g2_delta * ok, unique_indices=True)
    values = values.at[cap - 1].set(0.0)
    g2sum = g2sum.at[cap - 1].set(0.0)
    return values, g2sum


class _GroupPass(pass_loop.PassHooks):
    """What the sharded trainer says about one pass: its producer (the
    ragged-tail barrier, the group plan, the stack), its step on the state
    it carries (the table's buffers, the hot block, the per-device counts,
    the async gradient), its dense sync (the protocol is
    pass_loop.run_pass)."""

    merge = staticmethod(merge_device_axis)

    def __init__(self, trainer: "MultiChipTrainer", table, groups):
        self.trainer, self.table, self.groups = trainer, table, groups
        self.counts: list = []
        self.pending_grads: list = []  # device grads fetched one step behind
        self.plan_channel = None

    def open(self) -> None:
        t, table, conf = self.trainer, self.table, self.trainer.conf
        self.hot_cap = hot_cap = int(getattr(table, "hot_block_capacity", 0))
        if t._step_fn is None or t._step_hot_cap != hot_cap:
            t._step_fn = t._build_step(hot_cap)
            t._step_hot_cap = hot_cap
        if t._sync_fn is None and conf.sync_dense_mode == "kstep":
            t._sync_fn = t._build_sync()
        self.multiproc = multiproc = is_multiprocess()
        self.async_dense = conf.sync_dense_mode == "async"
        if self.async_dense and t.async_dense is None:
            from paddlebox_tpu.parallel.async_dense import AsyncDenseTable

            # every process hosts an identical table fed identical
            # replicated grads, so multi-host needs no extra dense comm
            # (the reference runs one table per node the same way)
            p0 = jax.tree.map(lambda x: local_view(x)[0], t.params)
            t.async_dense = AsyncDenseTable(
                p0, optimizer=conf.dense_optimizer, lr=conf.dense_lr,
            )
        self.sync_every = max(conf.sync_weight_step, 1)
        self.values, self.g2sum = table.values, table.g2sum
        self.hot_values = self.hot_g2sum = None
        if hot_cap:
            with stage_scope("train.init"):
                self.hot_values, self.hot_g2sum = t._hot_state(table, hot_cap)
        # the producer's collectives must be HOST-side: it runs concurrent
        # with the consumer's device step, and two threads racing device
        # collectives onto the queues in different orders across processes
        # is a cross-process deadlock.  Each pass gets its own KV channel
        # (deterministic name: every process increments in lockstep).
        self.plan_gather = host_allgather  # no-op [1, ...] wrap
        if multiproc:
            from paddlebox_tpu.parallel.host_plane import KvChannel

            _PLAN_CHANNEL_SEQ[0] += 1
            self.plan_channel = KvChannel(
                f"plan-{_PLAN_CHANNEL_SEQ[0]}",
                timeout_s=(
                    conf.liveness.hostplane_timeout_s
                    if conf.liveness is not None
                    else conf.host_plane_timeout_s
                ),
            )
            self.plan_gather = self.plan_channel.allgather
            # per-process file (the reference's per-node dump discipline):
            # each process dumps exactly its local devices' instances
            self.dump_suffix = f"-r{jax.process_index()}"

    def feeds(self):
        """Barrier + host planning + stack + H2D for every group.

        Runs on the prefetch thread so the per-batch want-matrix
        allgather and feed assembly overlap the device step (the
        single-chip _FeedPrefetcher discipline).
        All its cross-process exchanges ride the host-plane KV channel
        above — it never touches the device queues, so it cannot
        deadlock against the consumer's step collectives."""
        t, table, sprof, wd = self.trainer, self.table, self.prof, self.wd
        multiproc, plan_gather = self.multiproc, self.plan_gather
        vocab_keys = getattr(t.model, "vocab_keys", None)
        uses_rank = getattr(t.model, "uses_rank_offset", False)
        uses_seq = getattr(t.model, "uses_seq_pos", False)
        dumping = self.dumper is not None
        groups_it = sprof.iterate("batch", self.groups)
        template = None  # last real batch: shapes for tail-padding
        n_slots = None
        while True:
            if wd is not None:
                wd.report("feed")
            group = next(groups_it, None)
            if multiproc:
                # ragged-tail barrier: a process out of groups must keep
                # stepping with empty batches while any peer still has
                # data, or the peers hang in the next all_to_all
                left = plan_gather(
                    np.asarray([0 if group is None else 1], np.int64)
                )
                if int(left.sum()) == 0:
                    return
                if group is None:
                    if template is None:
                        raise RuntimeError(
                            "this process received no batches at all: "
                            "give every process at least one file"
                        )
                    group = [empty_like(template)] * t.n_local
                else:
                    template = group[0]
            elif group is None:
                return
            if n_slots is None:
                n_slots = group[0].n_sparse_slots
            pass_loop.validate_batch(group[0], uses_rank, uses_seq, t.n_tasks)
            with sprof.stage("plan"):
                plan = table.plan_group(
                    group, gather=plan_gather,
                    slot_lr_vec=t._slot_lr_vec, n_slots=n_slots,
                )
            with sprof.stage("feed"):
                feed = _stack_group(
                    group, plan, n_slots, t.metric_group,
                    vocab_keys=vocab_keys,
                )
            yield (
                global_from_local(t._sharding, feed),
                group if dumping else None,
            )

    def dispatch(self, feed) -> tuple:
        t = self.trainer
        hot = (self.hot_values, self.hot_g2sum) if self.hot_cap else ()
        out = t._step_fn(
            t.params, t.opt_state, self.values, self.g2sum, self.mstate,
            feed[0], *hot,
        )
        # what follows the fixed outputs: the async gradient, then the
        # field dump's predictions, each only in its mode
        if self.hot_cap:
            (t.params, t.opt_state, self.values, self.g2sum, self.hot_values,
             self.hot_g2sum, self.mstate, loss, self.cnt, finite,
             *self.extra) = out
        else:
            (t.params, t.opt_state, self.values, self.g2sum, self.mstate,
             loss, self.cnt, finite, *self.extra) = out
        return loss, finite

    def after_step(self, feed, finite) -> bool:
        t = self.trainer
        if self.dumper is not None:
            with self.prof.stage("dump"):
                # [L, B] local predictions; pad batches dump nothing
                preds = local_view(self.extra[-1])
                for d, b in enumerate(feed[1]):
                    self.dumper.dump_batch(b, np.asarray(preds[d]))
        if self.async_dense:
            # push one step BEHIND: step t's grad is already computed
            # when step t+1 dispatches, so reading it never stalls
            # the device pipeline
            self.pending_grads.append(self.extra[0])
            if len(self.pending_grads) > 1:
                t._push_async_grad(self.pending_grads.pop(0))
            if (t.global_step + 1) % self.sync_every == 0:
                t.params = t._stack_local(t.async_dense.pull())
        if t.conf.check_nan_inf and not bool(local_view(finite).all()):
            raise FloatingPointError(
                f"non-finite loss/grad at step {t.global_step} "
                "(FLAGS_check_nan_inf analog)"
            )
        self.counts.append(self.cnt)
        if (
            t.conf.sync_dense_mode == "kstep"
            and (t.global_step + 1) % self.sync_every == 0
        ):
            t.params, t.opt_state = t._sync_fn(t.params, t.opt_state)
        return True

    def finish_loop(self) -> None:
        if self.async_dense:
            # pass boundary: flush the lagged grad, wait for the master
            # copy to absorb everything, refresh device params
            t = self.trainer
            for g in self.pending_grads:
                t._push_async_grad(g)
            self.pending_grads.clear()
            t.async_dense.drain()
            t.params = t._stack_local(t.async_dense.pull())

    def hand_back(self) -> None:
        table = self.table
        table.values, table.g2sum = self.values, self.g2sum
        if self.hot_cap and self.hot_values is not None:
            table.hot_values, table.hot_g2sum = (
                self.hot_values, self.hot_g2sum)

    def read_back(self, losses: list, gn_base) -> dict:
        return self.trainer._read_back(
            self.mstate, losses, self.counts, gn_base, self.multiproc)

    def observe(self, metrics: dict, tele) -> None:
        table = self.table
        metrics["missing_keys"] = table.missing_key_count
        metrics["overflow_keys"] = table.overflow_key_count  # always 0 now
        metrics["capacity_bumps"] = table.capacity_bumps
        # pass-boundary fleet view: allgather every rank's metric snapshot
        # over the coordination-service KV and log ONE merged view on rank
        # 0 (per-rank stage p99s, counters) — the PrintSyncTimer analog.
        # Telemetry must never kill a healthy pass: failures log and move
        # on.  Every rank participates (lockstep, like the collectives).
        if self.multiproc and tele.fleet_snapshot:
            _FLEET_SNAP_SEQ[0] += 1
            try:
                from paddlebox_tpu.parallel.watchdog import CoordKv

                merged = telemetry.gather_fleet_snapshot(
                    CoordKv(), rank=jax.process_index(),
                    world=jax.process_count(), seq=_FLEET_SNAP_SEQ[0],
                    namespace="pass", timeout_s=60.0,
                )
                if jax.process_index() == 0:
                    # print, not logger: the per-pass fleet line is the
                    # PrintSyncTimer/log_for_profile analog and must land
                    # in the rank-0 log without logging configuration
                    print(telemetry.format_fleet_view(
                        merged,
                        prefix=f"fleet pass step={self.trainer.global_step}",
                    ), flush=True)
            except Exception:
                import logging

                logging.getLogger(__name__).warning(
                    "fleet snapshot gather failed", exc_info=True
                )

    def index(self) -> tuple:
        return "global_step", self.trainer.global_step

    def close(self) -> None:
        if self.plan_channel is not None:
            # every peer has joined the metric collectives above, which it
            # can only do after its producer read ALL of this channel's
            # keys — deleting the final two sequences is now race-free.
            # (Skipped on the exception path: peers may still be blocked on
            # a get; two leaked keys on a dying pass is the lesser evil.)
            self.plan_channel.close()


class MultiChipTrainer:
    """Drives model + ShardedSparseTable over a mesh (BoxPSTrainer analog:
    one worker per device — here, one shard_map body per device)."""

    @START.wrap("trainer_init")
    def __init__(
        self,
        model,
        table_conf: SparseTableConfig,
        mesh: Mesh,
        trainer_conf: Optional[TrainerConfig] = None,
        seed: int = 0,
        metric_group: Optional[MetricGroup] = None,
        slot_mask: Optional[Iterable[int]] = None,
    ):
        """slot_mask: participating sparse-slot indices (None = all) — the
        per-phase slot participation of join/update two-phase training on
        the multi-chip path (same semantics as the single-chip Trainer:
        excluded slots read zero pooled features, receive zero gradients,
        and increment no counters; reference box_wrapper.h:627-630 phase
        state applied in the production multi-GPU workers)."""
        self.model = model
        self.table_conf = table_conf
        self.mesh = mesh
        self.slot_mask = normalize_slot_mask(slot_mask, model.n_sparse_slots)
        self.n_dev = int(mesh.shape[DATA_AXIS])  # data shards (==
        # devices on a 1-D mesh; a composed mesh's inner axis splits
        # dense compute inside the step, invisible to feeds/params)
        # local (this-process) device count: feeds/params are assembled from
        # per-process slices, so multi-host runs need no global host arrays
        self.n_local = int(local_device_indices(mesh).shape[0])
        self.conf = trainer_conf or TrainerConfig()
        from paddlebox_tpu.models.layers import apply_compute_dtype_override

        apply_compute_dtype_override(model, self.conf.compute_dtype)
        self.metric_group = metric_group
        self.n_tasks = getattr(model, "n_tasks", 1)
        # per-slot LR map, same resolution/validation as the single-chip
        # Trainer; consumed by plan_group -> plan.serve_lr -> the push
        self._slot_lr_vec = resolve_slot_lr_vec(
            table_conf, getattr(model, "n_sparse_slots", 0)
        )
        if self.conf.dense_optimizer == "adam":
            self.optimizer = optax.adam(self.conf.dense_lr)
        elif self.conf.dense_optimizer == "sgd":
            self.optimizer = optax.sgd(self.conf.dense_lr)
        else:
            raise ValueError(f"unknown dense optimizer {self.conf.dense_optimizer!r}")
        # params/opt_state are stored stacked [D, ...] and mesh-sharded: in
        # "step" mode every device holds an identical copy (grads are
        # psummed); in "kstep" mode copies drift and sync_params() re-averages
        # them (the reference's CopyParameters broadcast + K-step SyncParam).
        self._sharding = NamedSharding(mesh, P(DATA_AXIS))
        with stage_scope("train.init"):
            p0 = model.init(jax.random.PRNGKey(seed))
            o0 = self.optimizer.init(p0)
            self.params = self._stack_local(p0)
            self.opt_state = self._stack_local(o0)
        self._step_fn = None
        self._step_hot_cap = -1  # hot capacity the step was built for
        self._sync_fn = None
        self._eval_fn = None
        self._eval_hot_cap = -1
        self._copy_fn = None
        self.async_dense = None  # lazily created in "async" mode
        self.global_step = 0
        self._pass_idx = 0
        self.last_metric_state = None  # dict after a pass (Trainer parity)
        self._watch = CompletionWatcher()  # thread starts at first dispatch

    # -- jitted bodies ----------------------------------------------------- #
    def _build_step(self, hot_cap: int = 0):
        """hot_cap: padded hot-block capacity H (table.hot_block_capacity).
        0 compiles the pure hash-sharded step; > 0 compiles the hybrid step
        (two extra donated [D, H(, W)] state arrays, hybrid pull/push).
        STATIC for the table's lifetime — the step specializes on the
        capacity, never on the live plan."""
        model = self.model
        tconf = self.table_conf
        optimizer = self.optimizer
        conf = self.conf
        # "async" shares the "step" loss/denominator math (psummed grads and
        # loss, replicated across the axis) but applies NO dense optimizer on
        # device: the psummed grad is returned for the host-side
        # AsyncDenseTable push (reference: BoxPSAsynDenseTable, the NCCL
        # aggregate feeding the CPU double buffer, boxps_worker.cc:37-297)
        sync_step = conf.sync_dense_mode in ("step", "async")
        async_dense = conf.sync_dense_mode == "async"
        dump_preds = bool(conf.need_dump_field and conf.dump_fields_path)
        check_nan = conf.check_nan_inf
        n_tasks = self.n_tasks
        has_group = self.metric_group is not None
        part_vec = slot_participation_vec(
            self.slot_mask, model.n_sparse_slots
        )
        # the model half of the step, the single-chip trainer's (the
        # model's own ``loss``, else apply -> sigmoid cross-entropy); with
        # psummed gradients the mean runs over the whole axis
        model_loss = make_model_loss(
            model, n_tasks, mean_axis=DATA_AXIS if sync_step else None)

        def body(params, opt_state, values, g2sum, mstate, batch,
                 hot_values=None, hot_g2sum=None):
            # local blocks all carry a leading device axis of size 1
            unstack = lambda t: jax.tree.map(lambda x: x[0], t)
            params, opt_state = unstack(params), unstack(opt_state)
            mstate = unstack(mstate)
            values, g2sum = values[0], g2sum[0]
            batch = unstack(batch)

            # named scopes: metadata on the same operations (the names a
            # device trace shows; the single-chip step's, plus ``exchange``
            # and ``hot_fold`` inside pull / push)
            if hot_cap:
                hot_values, hot_g2sum = hot_values[0], hot_g2sum[0]
                with jax.named_scope("pull"):
                    rows = hybrid_pull(
                        values, hot_values, batch["serve_rows"],
                        batch["occ_flat"], batch["hot_occ"],
                        tconf.create_threshold, tconf.cvm_offset,
                    )
            else:
                with jax.named_scope("pull"):
                    rows = sharded_pull(
                        values, batch["serve_rows"], batch["occ_flat"],
                        tconf.create_threshold, tconf.cvm_offset,
                    )
            if part_vec is not None:
                # occurrence-level participation (seg = ins*S + slot):
                # gating inside loss_fn zeroes excluded slots' pooled
                # features AND, via the chain rule, their row gradients —
                # identical to the single-chip step
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
                if sync_step:
                    pgrads = jax.lax.psum(pgrads, DATA_AXIS)
                    loss = jax.lax.psum(loss, DATA_AXIS)
                if not async_dense:
                    updates, opt_state = optimizer.update(
                        pgrads, opt_state, params)
                    params = optax.apply_updates(params, updates)
            key_mask = batch["key_mask"]
            key_clicks = batch["key_clicks"]
            if key_part is not None:
                # excluded slots increment no show/clk counters either
                key_mask = key_mask * key_part
                key_clicks = key_clicks * key_part
            with jax.named_scope("push"):
                values, g2sum = sharded_push_and_update(
                    values, g2sum, row_grads, batch["occ_flat"],
                    batch["serve_map"], batch["serve_uniq"], key_mask,
                    key_clicks, tconf, uniq_lr=batch.get("uniq_lr"),
                )
                if hot_cap:
                    # hot occurrences carried the cold sink above, so their
                    # grads/counters reach exactly one of the two updates
                    hot_values, hot_g2sum = hybrid_hot_update(
                        hot_values, hot_g2sum, row_grads, batch["hot_occ"],
                        batch["hot_lr"], key_mask, key_clicks, tconf,
                    )
            primary = preds[:, 0] if n_tasks > 1 else preds
            mstate = add_counts(dict(mstate), counts)
            with jax.named_scope("metrics"):
                mstate, finite = pass_loop.step_metrics(
                    mstate, batch, loss, preds, primary, pgrads, row_grads,
                    n_tasks=n_tasks, has_group=has_group, check_nan=check_nan)
                if check_nan:
                    # globalize: every device (hence every process) sees
                    # the same verdict, so a multi-host raise can't strand
                    # the other ranks mid-collective
                    bad = jax.lax.psum((~finite).astype(jnp.int32), DATA_AXIS)
                    finite = bad == 0
            restack = lambda t: jax.tree.map(lambda x: x[None], t)
            cnt = batch["ins_mask"].sum()
            hot_out = (
                (hot_values[None], hot_g2sum[None]) if hot_cap else ()
            )
            out = (
                restack(params), restack(opt_state), values[None], g2sum[None],
            ) + hot_out + (
                restack(mstate), loss[None], cnt[None], finite[None],
            )
            if async_dense:
                out = out + (restack(pgrads),)
            if dump_preds:
                # per-instance predictions for the field dumper — an extra
                # output only in dump mode, so the normal step never pays
                # the readback surface (reference: DumpField runs in the
                # production multi-GPU workers, device_worker.cc)
                out = out + (primary[None],)
            return out

        spec = P(DATA_AXIS)
        n_state = 8 if hot_cap else 6
        n_out = n_state + 2 + int(async_dense) + int(dump_preds)
        mapped = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(spec,) * n_state,
            out_specs=(spec,) * n_out,
            axis_names={DATA_AXIS},
        )
        donate = (0, 1, 2, 3, 4, 6, 7) if hot_cap else (0, 1, 2, 3, 4)
        return counted_jit(mapped, stage="spmd.step", donate_argnums=donate)

    def _build_sync(self):
        """K-step param sync: average drifted replicas (reference: SyncParam
        ncclAllReduce / reduce-scatter+allgather then scale, boxps_worker.cc:481-521)."""

        def body(params, opt_state):
            def avg(x):
                # integer leaves (adam's step count) are identical across
                # replicas by construction and a pmean would promote them
                # to float — pass them through untouched
                if not jnp.issubdtype(x.dtype, jnp.floating):
                    return x
                return jax.lax.pmean(x[0], DATA_AXIS)[None]

            pm = jax.tree.map(avg, params)
            om = jax.tree.map(avg, opt_state)
            return pm, om

        spec = P(DATA_AXIS)
        mapped = jax.shard_map(
            body, mesh=self.mesh, in_specs=(spec, spec),
            out_specs=(spec, spec), axis_names={DATA_AXIS},
        )
        return counted_jit(mapped, stage="spmd.sync", donate_argnums=(0, 1))

    # -- dense persistence -------------------------------------------------- #
    def dense_state(self) -> tuple:
        """(params, opt_state) with the device axis dropped — this process's
        first local replica (in kstep mode call sync first if drift
        matters; in step mode every replica is identical)."""
        take0 = lambda t: jax.tree.map(lambda x: local_view(x)[0], t)
        return take0(self.params), take0(self.opt_state)

    @START.wrap("dense_load")
    def load_dense_state(self, params, opt_state=None) -> None:
        if params is not None:
            self.params = self._stack_local(params)
        if opt_state is not None:
            self.opt_state = self._stack_local(opt_state)

    # -- public API --------------------------------------------------------- #
    def _stack_local(self, tree):
        """Stack one per-device copy for each LOCAL device and assemble the
        global [n_dev, ...] mesh-sharded tree.  Stacked on the host, so
        each copy goes straight to its own device instead of all of them
        staging through the default device's memory."""
        return global_from_local(
            self._sharding,
            jax.tree.map(
                lambda x: np.broadcast_to(
                    np.asarray(x), (self.n_local, *np.shape(x))
                ),
                tree,
            ),
        )

    def _copy_state(self, tree):
        """Fresh buffers for a donated-state continuation (works on
        non-fully-addressable multi-host arrays, where jnp.array would not)."""
        if self._copy_fn is None:
            self._copy_fn = counted_jit(
                lambda t: jax.tree.map(lambda x: x + jnp.zeros((), x.dtype), t),
                stage="spmd.copy",
            )
        return self._copy_fn(tree)

    def _push_async_grad(self, g) -> None:
        """Hand one replicated [D, ...] grad tree to the host table (reads
        this process's first shard — the psum made every shard identical)."""
        self.async_dense.push(jax.tree.map(lambda x: local_view(x)[0], g))

    def close(self) -> None:
        """Stop background machinery (the completion watcher's thread,
        the async dense update thread)."""
        self._watch.close()
        if self.async_dense is not None:
            try:
                self.async_dense.stop()  # raises if the update thread died
            finally:
                self.async_dense = None

    def _hot_state(self, table: ShardedSparseTable, hot_cap: int) -> tuple:
        """(hot_values [D, H, W], hot_g2sum [D, H]) for the hybrid step —
        the table's live block, or all-zeros before the first plan
        realizes (nothing routes hot then: hot_occ is all-sink, and a
        zero block receives exactly-zero updates)."""
        if table.hot_values is None:
            w = self.table_conf.row_width
            table.hot_values = self._stack_local(
                jnp.zeros((hot_cap, w), jnp.float32)
            )
            table.hot_g2sum = self._stack_local(
                jnp.zeros((hot_cap,), jnp.float32)
            )
        return table.hot_values, table.hot_g2sum

    def init_auc(self) -> AucState:
        return self._stack_local(init_auc_state(self.conf.auc_buckets))

    def _init_mstate(self, auc_state=None) -> dict:
        """Per-device metric streams, each leaf stacked [n_dev, ...] and
        mesh-sharded (merged by summing over devices at read time)."""
        return pass_loop.init_metric_state(
            self, auc_state, place=self._stack_local, copy=self._copy_state)

    def train_from_dataset(
        self,
        dataset,
        table: ShardedSparseTable,
        auc_state: Optional[AucState] = None,
        drop_last: bool = False,
        next_pass_keys=None,
    ) -> dict:
        """One pass over the dataset, one batch per LOCAL device at a time
        (the caller owns begin_pass/end_pass, as in the single-chip Trainer).
        Multi-host: each process feeds its own dataset shard; group counts
        may differ across processes only by the ragged tail, which
        train_groups pads to a common step count."""
        return self.train_groups(
            table,
            _group_batches(dataset.batches(drop_last=drop_last), self.n_local),
            auc_state=auc_state,
            next_pass_keys=next_pass_keys,
        )

    def train_groups(
        self,
        table: ShardedSparseTable,
        groups: Iterator[Sequence[HostBatch]],
        auc_state: Optional[AucState] = None,
        next_pass_keys=None,
    ) -> dict:
        """One pass over ``groups``: pass_loop.run_pass around this
        trainer's producer and step.

        next_pass_keys: next pass's census (array or zero-arg callable),
        staged via table.prepare_pass once this pass's groups are exhausted
        — the sharded half of pass-boundary pipelining (single-process
        only; multi-host prepare_pass no-ops, see sharded_table.py).

        A coordinated liveness abort (DistributedStallError) leaves recovery
        to the driver: restart the job and resume from the newest valid
        checkpoint (AutoCheckpointer.resume / find_valid_tag) — the aborted
        pass never reached after_pass, so nothing partial survives the
        replay."""
        return pass_loop.run_pass(
            self, _GroupPass(self, table, groups), table, auc_state,
            next_pass_keys)

    def _read_back(self, mstate: dict, losses: list, counts: list,
                   gn_base, multiproc: bool) -> dict:
        """The pass's metrics from the devices' metric state (eager
        programs and lockstep device-axis merges, tagged ``train.readback``
        by the caller)."""
        own = {"loss": 0.0, "samples": 0.0}
        if losses:
            # [T, L] local views; multi-host: gather to [T, D]
            per_step = np.stack([local_view(l) for l in losses])
            cnts = np.stack([local_view(c) for c in counts])
            if multiproc:
                per_step = np.moveaxis(
                    host_allgather(per_step), 0, 1
                ).reshape(len(losses), -1)
                cnts = np.moveaxis(
                    host_allgather(cnts), 0, 1
                ).reshape(len(counts), -1)
            if self.conf.sync_dense_mode == "kstep":
                # local losses are local means: recombine weighted by real
                # instance counts so padded empty batches don't bias the pass
                num = (per_step * cnts).sum(axis=1)
                den = np.maximum(cnts.sum(axis=1), 1.0)
                own["loss"] = float((num / den).mean())
            else:
                # psummed loss is replicated across the axis
                own["loss"] = float(per_step[:, 0].mean())
            own["samples"] = float(cnts.sum())
        return pass_loop.read_back_common(
            mstate, gn_base, self.params, self.n_tasks, self.metric_group,
            own, merge=merge_device_axis, read=read_replicated)

    # -- inference / evaluation -------------------------------------------- #
    def _build_eval(self, hot_cap: int = 0):
        model = self.model
        tconf = self.table_conf
        uses_rank = getattr(model, "uses_rank_offset", False)
        uses_seq = getattr(model, "uses_seq_pos", False)
        n_tasks = self.n_tasks

        def body(params, values, auc, batch, hot_values=None):
            unstack = lambda t: jax.tree.map(lambda x: x[0], t)
            params, auc, batch = unstack(params), unstack(auc), unstack(batch)
            values = values[0]
            if hot_cap:
                rows = hybrid_pull(
                    values, hot_values[0], batch["serve_rows"],
                    batch["occ_flat"], batch["hot_occ"],
                    tconf.create_threshold, tconf.cvm_offset,
                )
            else:
                rows = sharded_pull(
                    values, batch["serve_rows"], batch["occ_flat"],
                    tconf.create_threshold, tconf.cvm_offset,
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
            return jax.tree.map(lambda x: x[None], auc)

        spec = P(DATA_AXIS)
        n_in = 5 if hot_cap else 4
        mapped = jax.shard_map(
            body, mesh=self.mesh, in_specs=(spec,) * n_in, out_specs=spec,
            axis_names={DATA_AXIS},
        )
        return counted_jit(mapped, stage="spmd.eval", donate_argnums=(2,))

    def evaluate(self, dataset, table: ShardedSparseTable,
                 drop_last: bool = False) -> dict:
        """Forward-only multi-chip pass (infer_from_dataset analog): no
        table/param updates, per-device AUC merged at the end."""
        hot_cap = int(getattr(table, "hot_block_capacity", 0))
        if self._eval_fn is None or self._eval_hot_cap != hot_cap:
            self._eval_fn = self._build_eval(hot_cap)
            self._eval_hot_cap = hot_cap
        hot_values = self._hot_state(table, hot_cap)[0] if hot_cap else None
        multiproc = is_multiprocess()
        uses_rank = getattr(self.model, "uses_rank_offset", False)
        uses_seq = getattr(self.model, "uses_seq_pos", False)
        auc = self.init_auc()
        n_slots = None
        template = None
        groups = _group_batches(dataset.batches(drop_last=drop_last), self.n_local)
        while True:
            group = next(groups, None)
            if multiproc:
                left = host_allgather(
                    np.asarray([0 if group is None else 1], np.int64)
                )
                if int(left.sum()) == 0:
                    break
                if group is None:
                    if template is None:
                        raise RuntimeError(
                            "this process received no batches at all: "
                            "give every process at least one file"
                        )
                    group = [empty_like(template)] * self.n_local
                else:
                    template = group[0]
            elif group is None:
                break
            if n_slots is None:
                n_slots = group[0].n_sparse_slots
            pass_loop.validate_batch(group[0], uses_rank, uses_seq, 1)
            plan = table.plan_group(group)
            feed = _stack_group(group, plan, n_slots)
            feed = global_from_local(self._sharding, feed)
            if hot_cap:
                auc = self._eval_fn(
                    self.params, table.values, auc, feed, hot_values
                )
            else:
                auc = self._eval_fn(self.params, table.values, auc, feed)
        return compute_metrics(merge_device_axis(auc))


def _group_batches(
    batches: Iterator[HostBatch], n: int
) -> Iterator[list[HostBatch]]:
    """Yield n batches at a time; a ragged tail is padded with empty batches
    (ins_mask all zero) so every device always receives a feed."""
    group: list[HostBatch] = []
    for b in batches:
        group.append(b)
        if len(group) == n:
            yield group
            group = []
    if group:
        pad = empty_like(group[0])
        group += [pad] * (n - len(group))
        yield group
