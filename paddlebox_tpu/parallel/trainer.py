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
overlaps the all_to_alls with the dense tower compute where possible.
"""

from __future__ import annotations

import math
import os
import time
from typing import Iterable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
from paddlebox_tpu.data.feed import HostBatch, empty_like, key_classes
from paddlebox_tpu.metrics.auc import (
    AucState,
    compute_metrics,
    compute_metrics_stacked,
    init_auc_state,
    stack_auc_states,
    update_auc_state,
)
from paddlebox_tpu.metrics.variants import MetricGroup
from paddlebox_tpu.parallel.mesh import DATA_AXIS
from paddlebox_tpu.parallel.multiprocess import (
    global_from_local,
    host_allgather,
    local_device_indices,
    local_view,
    read_replicated,
)
from paddlebox_tpu.parallel.sharded_table import ShardedBatchPlan, ShardedSparseTable
from paddlebox_tpu.sparse.optimizer import sparse_adagrad_update
from paddlebox_tpu.sparse.table import merge_occurrences, scatter_add_rows
from paddlebox_tpu.telemetry.compiles import counted_jit, stage_scope
from paddlebox_tpu.utils.profiler import (
    HOST,
    START,
    CompletionWatcher,
    StatsProfiler,
    pass_seconds,
)
from paddlebox_tpu.utils import faults
from paddlebox_tpu.train.slot_policy import (
    normalize_slot_mask,
    resolve_slot_lr_vec,
    slot_participation_vec,
)
from paddlebox_tpu.train.step_loss import (
    add_counts,
    counter_names,
    make_model_loss,
    publish_counters,
)


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
                mstate, finite = step_metrics(
                    mstate, batch, loss, preds, primary, pgrads, row_grads)
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

        def step_metrics(mstate, batch, loss, preds, primary, pgrads,
                         row_grads):
            mstate["auc"] = update_auc_state(
                mstate["auc"], primary, batch["labels"], batch["ins_mask"]
            )
            # grad-norm health stream in the donated metric state (no
            # step-signature change): [sum of squared grad norms,
            # steps] per device; pass end sums the device axis.  With
            # sync_step the psummed pgrads are identical per device —
            # the device-axis mean (sum/steps) stays the step value.
            # "gn" is always present: _init_mstate seeds it and the
            # restore path backfills it.
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
                # globalize: every device (hence every process) sees the same
                # verdict, so a multi-host raise can't strand the other ranks
                # mid-collective
                bad = jax.lax.psum((~finite).astype(jnp.int32), DATA_AXIS)
                finite = bad == 0
            else:
                finite = jnp.array(True)
            return mstate, finite

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
        n_counters = len(counter_names(self.model))
        if isinstance(auc_state, dict):
            # the step donates mstate: copy so the caller's reference (often
            # trainer.last_metric_state itself) is not invalidated by the
            # first step's buffer donation
            out = self._copy_state(auc_state)
            if "gn" not in out:
                out["gn"] = self._stack_local(jnp.zeros((2,), jnp.float32))
            if n_counters and "counters" not in out:
                out["counters"] = self._stack_local(
                    jnp.zeros((n_counters,), jnp.float32))
            return out
        if auc_state is not None and (self.n_tasks > 1 or self.metric_group):
            raise ValueError(
                "pass trainer.last_metric_state (dict) to continue metrics "
                "across passes — a bare AucState would reset the task/group "
                "streams while continuing the primary one"
            )
        mstate = {
            "auc": self._copy_state(auc_state)
            if auc_state is not None
            else self.init_auc(),
            "gn": self._stack_local(jnp.zeros((2,), jnp.float32)),
        }
        if n_counters:
            # the model's per-step sums (step_loss.counter_names)
            mstate["counters"] = self._stack_local(
                jnp.zeros((n_counters,), jnp.float32))
        if self.n_tasks > 1:
            base = stack_auc_states(
                init_auc_state(self.conf.auc_buckets), self.n_tasks
            )
            mstate["task"] = self._stack_local(base)
        if self.metric_group is not None:
            mstate["group"] = self._stack_local(self.metric_group.init_state())
        return mstate

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
        """next_pass_keys: next pass's census (array or zero-arg callable),
        staged via table.prepare_pass once this pass's groups are exhausted
        — the sharded half of pass-boundary pipelining (single-process
        only; multi-host prepare_pass no-ops, see sharded_table.py)."""
        sprof = StatsProfiler()
        # the pass's head: the step for this hot capacity, the telemetry's
        # own set-up, the metric state and its baselines (eager programs
        # and a read-back), the watchdog, the plan channel -- the devices
        # idle under it, so it has a name
        with sprof.stage("open"):
            hot_cap = int(getattr(table, "hot_block_capacity", 0))
            if self._step_fn is None or self._step_hot_cap != hot_cap:
                self._step_fn = self._build_step(hot_cap)
                self._step_hot_cap = hot_cap
            if self._sync_fn is None and self.conf.sync_dense_mode == "kstep":
                self._sync_fn = self._build_sync()
            from paddlebox_tpu.parallel.multiprocess import is_multiprocess

            multiproc = is_multiprocess()
            async_dense = self.conf.sync_dense_mode == "async"
            if async_dense and self.async_dense is None:
                from paddlebox_tpu.parallel.async_dense import AsyncDenseTable

                # every process hosts an identical table fed identical
                # replicated grads, so multi-host needs no extra dense comm
                # (the reference runs one table per node the same way)
                p0 = jax.tree.map(lambda x: local_view(x)[0], self.params)
                self.async_dense = AsyncDenseTable(
                    p0, optimizer=self.conf.dense_optimizer,
                    lr=self.conf.dense_lr,
                )
            # telemetry: exporter/event log are process singletons (first pass
            # starts them); host stage timing always feeds the per-stage
            # latency histograms (plan/feed run on the producer thread;
            # ``step`` is the enqueue, the device's side is the completion
            # watcher's)
            from paddlebox_tpu import telemetry
            from paddlebox_tpu.config import TelemetryConfig

            tele = self.conf.telemetry or TelemetryConfig.from_flags()
            telemetry.ensure_exporter(tele.metrics_port or None)
            event_log = telemetry.ensure_event_log(tele.events_path or None)

            watch = self._watch
            pending_grads: list = []  # device grads fetched one step behind
            pull_every = max(self.conf.sync_weight_step, 1)
            from paddlebox_tpu.parallel.multiprocess import merge_device_axis

            with stage_scope("train.init"):
                mstate = self._init_mstate(auc_state)
                # grad-norm baseline: the accumulator carries across continued
                # passes — snapshot NOW (a lockstep device-axis merge on
                # every rank), the first step donates the buffer
                gn_base = np.asarray(
                    merge_device_axis(mstate["gn"]), dtype=np.float64
                )
                counters_base = np.asarray(
                    merge_device_axis(mstate["counters"]), dtype=np.float64
                ) if "counters" in mstate else None
            vocab_keys = getattr(self.model, "vocab_keys", None)
            pass_t0 = time.monotonic()
            values, g2sum = table.values, table.g2sum
            hot_values = hot_g2sum = None
            if hot_cap:
                with stage_scope("train.init"):
                    hot_values, hot_g2sum = self._hot_state(table, hot_cap)
            losses, counts, n_steps = [], [], 0
            uses_rank = getattr(self.model, "uses_rank_offset", False)
            uses_seq = getattr(self.model, "uses_seq_pos", False)

            # distributed-liveness watchdog: heartbeats through the same KV
            # store the planning plane rides, local + peer stall detection,
            # poison-key coordinated abort.  Namespaced per pass (global_step
            # advances in lockstep across processes) so heartbeat keys from a
            # previous aborted pass can never poison a fresh one.
            from paddlebox_tpu.parallel import watchdog as _wd_mod

            wd = None
            if self.conf.liveness is not None:
                wd = _wd_mod.for_trainer(
                    self.conf.liveness, namespace=f"train-{self.global_step}"
                )
                if wd is not None:
                    wd.start()

            # the producer's collectives must be HOST-side: it runs concurrent
            # with the consumer's device step, and two threads racing device
            # collectives onto the queues in different orders across processes
            # is a cross-process deadlock.  Each pass gets its own KV channel
            # (deterministic name: every process increments in lockstep).
            plan_channel = None
            if multiproc:
                from paddlebox_tpu.parallel.host_plane import KvChannel

                _PLAN_CHANNEL_SEQ[0] += 1
                plan_channel = KvChannel(
                    f"plan-{_PLAN_CHANNEL_SEQ[0]}",
                    timeout_s=(
                        self.conf.liveness.hostplane_timeout_s
                        if self.conf.liveness is not None
                        else self.conf.host_plane_timeout_s
                    ),
                )
                plan_gather = plan_channel.allgather
            else:
                plan_gather = host_allgather  # no-op [1, ...] wrap
            dumper = None
            if self.conf.need_dump_field and self.conf.dump_fields_path:
                from paddlebox_tpu.train.dump import FieldDumper

                # per-process file (the reference's per-node dump discipline):
                # each process dumps exactly its local devices' instances
                suffix = (
                    f"-r{jax.process_index()}" if multiproc else ""
                )
                dumper = FieldDumper(
                    os.path.join(
                        self.conf.dump_fields_path,
                        f"dump-{self.global_step}{suffix}.txt",
                    ),
                    self.conf.dump_fields,
                )

        def produce_feeds():
            """Barrier + host planning + stack + H2D for every group.

            Runs on the prefetch thread so the per-batch want-matrix
            allgather and feed assembly overlap the device step (the
            single-chip _FeedPrefetcher discipline).
            All its cross-process exchanges ride the host-plane KV channel
            above — it never touches the device queues, so it cannot
            deadlock against the consumer's step collectives."""
            groups_it = sprof.iterate("batch", groups)
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
                        group = [empty_like(template)] * self.n_local
                    else:
                        template = group[0]
                elif group is None:
                    return
                if n_slots is None:
                    n_slots = group[0].n_sparse_slots
                if uses_seq and group[0].seq_pos is None:
                    raise RuntimeError(
                        "model consumes an ordered behavior sequence: set "
                        "DataFeedConfig.sequence_slot (and max_seq_len) so "
                        "batches carry seq_pos"
                    )
                if uses_rank and group[0].rank_offset is None:
                    raise RuntimeError(
                        "model requires PV-merged batches with rank_offset: "
                        "set enable_pv_merge and call dataset.preprocess_instance()"
                    )
                if self.n_tasks > 1 and (
                    group[0].task_labels is None
                    or group[0].task_labels.shape[1] != self.n_tasks
                ):
                    got = (
                        0 if group[0].task_labels is None
                        else group[0].task_labels.shape[1]
                    )
                    raise RuntimeError(
                        f"model has {self.n_tasks} tasks but the batch carries "
                        f"{got} task label columns: configure "
                        "DataFeedConfig.task_label_slots with "
                        f"{self.n_tasks - 1} slots (task 0 is the primary label)"
                    )
                with sprof.stage("plan"):
                    plan = table.plan_group(
                        group, gather=plan_gather,
                        slot_lr_vec=self._slot_lr_vec, n_slots=n_slots,
                    )
                with sprof.stage("feed"):
                    feed = _stack_group(
                        group, plan, n_slots, self.metric_group,
                        vocab_keys=vocab_keys,
                    )
                yield (
                    global_from_local(self._sharding, feed),
                    group if dumper is not None else None,
                )

        feed_iter = produce_feeds()
        prefetcher = None
        try:
          with telemetry.span("pass", global_step=self.global_step):
              if self.conf.prefetch_batches > 0:
                  from paddlebox_tpu.train.trainer import _FeedPrefetcher

                  # started inside the pass span: the producer's plan/feed
                  # spans inherit it as their parent
                  prefetcher = _FeedPrefetcher(
                      feed_iter, self.conf.prefetch_batches, sprof
                  )
                  feed_iter = prefetcher
              for feed, dump_group in feed_iter:
                  # chaos site: a hang here simulates a stalled device step
                  # on this process; the watchdog bounds it fleet-wide
                  faults.inject("train.step")
                  t_dispatch = time.perf_counter()
                  with sprof.stage("step"):
                      hot = (hot_values, hot_g2sum) if hot_cap else ()
                      out = self._step_fn(
                          self.params, self.opt_state, values, g2sum, mstate,
                          feed, *hot,
                      )
                  if hot_cap:
                      (self.params, self.opt_state, values, g2sum, hot_values,
                       hot_g2sum, mstate, loss, cnt, finite) = out[:10]
                      n_fixed = 10
                  else:
                      (self.params, self.opt_state, values, g2sum, mstate, loss,
                       cnt, finite) = out[:8]
                      n_fixed = 8
                  watch.dispatched(loss, t_dispatch)
                  if wd is not None:
                      wd.report("step")
                  if dumper is not None:
                      # [L, B] local predictions; pad batches dump nothing
                      preds = local_view(out[-1])
                      for d, b in enumerate(dump_group):
                          dumper.dump_batch(b, np.asarray(preds[d]))
                  if async_dense:
                      # push one step BEHIND: step t's grad is already computed
                      # when step t+1 dispatches, so reading it never stalls
                      # the device pipeline
                      pending_grads.append(out[n_fixed])
                      if len(pending_grads) > 1:
                          self._push_async_grad(pending_grads.pop(0))
                      if (self.global_step + 1) % pull_every == 0:
                          self.params = self._stack_local(self.async_dense.pull())
                  if self.conf.check_nan_inf and not bool(
                      local_view(finite).all()
                  ):
                      raise FloatingPointError(
                          f"non-finite loss/grad at step {self.global_step} "
                          "(FLAGS_check_nan_inf analog)"
                      )
                  losses.append(loss)
                  counts.append(cnt)
                  n_steps += 1
                  self.global_step += 1
                  if (
                      self.conf.sync_dense_mode == "kstep"
                      and self.global_step % max(self.conf.sync_weight_step, 1) == 0
                  ):
                      self.params, self.opt_state = self._sync_fn(
                          self.params, self.opt_state
                      )
              if async_dense:
                  # pass boundary: flush the lagged grad, wait for the master
                  # copy to absorb everything, refresh device params
                  for g in pending_grads:
                      self._push_async_grad(g)
                  pending_grads.clear()
                  self.async_dense.drain()
                  self.params = self._stack_local(self.async_dense.pull())
        except _wd_mod.DistributedStallError:
            # coordinated abort: every process converges on the same
            # structured error (poison key); teardown in the finally below
            # leaves no dangling producer thread.  Recovery is the
            # driver's: restart the job and resume from the newest valid
            # checkpoint (AutoCheckpointer.resume / find_valid_tag) — the
            # aborted pass never reached after_pass, so nothing partial
            # survives the replay.
            from paddlebox_tpu.utils.monitor import stats

            stats.add("train.stall_aborts")
            raise
        finally:
            # the old table buffers were donated to the jitted step: always
            # hand the live ones back so end_pass() can salvage the pass even
            # when check_nan_inf raises mid-loop.  The watchdog retires
            # FIRST so its abort latch cannot fire into the teardown.
            if wd is not None:
                wd.close()
            table.values, table.g2sum = values, g2sum
            if hot_cap and hot_values is not None:
                table.hot_values, table.hot_g2sum = hot_values, hot_g2sum
            if prefetcher is not None:
                prefetcher.close()
            if dumper is not None:
                dumper.close()
        # pre-promotion: groups are exhausted but the device still drains
        # queued steps (the metric merge below blocks on them) — stage the
        # next pass's working set in that window (single-chip Trainer
        # discipline; sharded prepare_pass no-ops multi-host)
        if next_pass_keys is not None:
            prepare = getattr(table, "prepare_pass", None)
            if prepare is not None:
                prepare(next_pass_keys)
        # the devices' tail: the metric merge below waits for the last
        # queued step anyway; waiting here first gives the wait its own
        # name and leaves ``readback`` the merges and eager programs alone
        with sprof.stage("drain"):
            if losses:
                losses[-1].block_until_ready()
            watch.settle()
            # the devices have nothing queued: did the host let the pass's
            # threads run (the feed producer answered before it exited)
            HOST.after_drain(watch)
        with stage_scope("train.readback"), sprof.stage("readback"):
            metrics = self._read_back(mstate, losses, counts, gn_base,
                                      multiproc)
            if counters_base is not None:
                metrics.update(publish_counters(
                    self.model,
                    np.asarray(merge_device_axis(mstate["counters"]),
                               dtype=np.float64),
                    counters_base))
        # the pass's tail is the telemetry's own -- the fleet view, the
        # registry's delta over every series, the health rules, the
        # pass_end record -- with the devices idle: it has a name too
        with sprof.stage("observe"):
            metrics["steps"] = n_steps
            metrics["duration_s"] = time.monotonic() - pass_t0
            pass_seconds().observe(metrics["duration_s"])
            metrics["missing_keys"] = table.missing_key_count
            metrics["overflow_keys"] = table.overflow_key_count  # always 0 now
            metrics["capacity_bumps"] = table.capacity_bumps
            self.last_auc_state = mstate["auc"]
            self.last_metric_state = mstate
            # pass-boundary fleet view: allgather every rank's metric snapshot
            # over the coordination-service KV and log ONE merged view on rank
            # 0 (per-rank stage p99s, counters) — the PrintSyncTimer analog.
            # Telemetry must never kill a healthy pass: failures log and move
            # on.  Every rank participates (lockstep, like the collectives).
            if multiproc and tele.fleet_snapshot:
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
                            prefix=f"fleet pass step={self.global_step}",
                        ), flush=True)
                except Exception:
                    import logging

                    logging.getLogger(__name__).warning(
                        "fleet snapshot gather failed", exc_info=True
                    )
            # run-health plane: evaluate the rule catalog on the SAME window
            # the pass_end record carries, BEFORE the record is written so
            # the window's health_alert events precede its pass_end record
            snap = telemetry.registry.delta_snapshot()
            telemetry.observe_pass(
                self.global_step, metrics=metrics, telemetry=snap, table=table
            )
            if event_log is not None:
                event_log.log_pass(metrics, telemetry=snap,
                                   global_step=self.global_step)
        if plan_channel is not None:
            # every peer has joined the metric collectives above, which it
            # can only do after its producer read ALL of this channel's
            # keys — deleting the final two sequences is now race-free.
            # (Skipped on the exception path: peers may still be blocked on
            # a get; two leaked keys on a dying pass is the lesser evil.)
            plan_channel.close()
        return metrics

    def _read_back(self, mstate: dict, losses: list, counts: list,
                   gn_base, multiproc: bool) -> dict:
        """The pass's metrics from the devices' metric state (eager
        programs and lockstep device-axis merges, tagged ``train.readback``
        by the caller)."""
        from paddlebox_tpu import telemetry
        from paddlebox_tpu.parallel.multiprocess import merge_device_axis

        # cross-device merge: sum each stream's histograms over the device
        # axis (multi-host: jitted replicated sum + local read,
        # collect_data_nccl analog)
        merged = merge_device_axis(mstate["auc"])
        metrics = compute_metrics(merged)
        if self.n_tasks > 1:
            task_merged = merge_device_axis(mstate["task"])
            metrics.update(
                compute_metrics_stacked(
                    task_merged, [f"task{t}" for t in range(self.n_tasks)]
                )
            )
        if self.metric_group is not None:
            group_merged = merge_device_axis(mstate["group"])
            metrics.update(self.metric_group.compute(group_merged))
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
                metrics["loss"] = float((num / den).mean())
            else:
                # psummed loss is replicated across the axis
                metrics["loss"] = float(per_step[:, 0].mean())
            metrics["samples"] = float(cnts.sum())
        else:
            metrics["loss"] = 0.0
            metrics["samples"] = 0.0
        gn_now = np.asarray(merge_device_axis(mstate["gn"]), dtype=np.float64)
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
            float(jnp.sum(jnp.square(read_replicated(leaf).astype(
                jnp.float32))))
            for leaf in jax.tree.leaves(self.params)
        )
        metrics["weight_norm"] = math.sqrt(wsq) if wsq >= 0 else float("nan")
        telemetry.gauge(
            "train.weight_norm", "dense parameter L2 norm at pass end"
        ).set(metrics["weight_norm"])
        return metrics

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
        from paddlebox_tpu.parallel.multiprocess import (
            is_multiprocess,
            merge_device_axis,
        )

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
            if uses_seq and group[0].seq_pos is None:
                raise RuntimeError(
                    "model consumes an ordered behavior sequence: set "
                    "DataFeedConfig.sequence_slot (and max_seq_len) so "
                    "batches carry seq_pos"
                )
            if uses_rank and group[0].rank_offset is None:
                raise RuntimeError(
                    "model requires PV-merged batches with rank_offset: "
                    "set enable_pv_merge and call dataset.preprocess_instance()"
                )
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
