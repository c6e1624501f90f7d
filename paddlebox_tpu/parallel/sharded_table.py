"""Multi-chip sparse table: the working set sharded by ``key % n_shards``.

This is the TPU-native answer to the reference's multi-GPU sparse PS
(reference: per-GPU HBM caches inside ``libbox_ps.so`` behind
``PullSparseGPU/PushSparseGPU``, fleet/box_wrapper_impl.h:24-255 and
SURVEY.md §2.7): every chip owns the embedding rows whose key hashes to it,
a pull becomes all_to_all(row requests) -> local gather -> all_to_all(rows),
and a push is the exact transpose with a scatter-add accumulation before one
fused sparse-adagrad update (see parallel/trainer.py for the device side).

The host half here mirrors the single-chip ``SparseTable`` (same host store,
same pass lifecycle) but materializes the pass working set as one stacked
``[n_shards, cap, W]`` array laid out for a ``NamedSharding(mesh, P('data'))``
placement, and resolves batches into *per-owner bucketed* row indices — the
static-shape plan the all_to_all needs.

Because the host plans every device's batch in one place, it also knows what
every shard will be asked to *serve* — so the device step needs no key
exchange at all (the reference pays a CopyKeys + DedupKeysAndFillIdx round
trip per batch, box_wrapper_impl.h:95-122): just two all_to_alls total, one
returning pulled rows, one delivering pushed gradients.

Multi-host (jax.process_count() > 1): every process plans only its LOCAL
devices' batches — shard ownership stays global (``key % n_global``) — and
two small host collectives glue the plans together: begin_pass allgathers
the local key censuses into one global census (so row numbering agrees
everywhere), and plan_group allgathers the per-device request matrices (so
each local shard knows which rows remote requesters want before the device
all_to_all runs).  Each process materializes, serves, persists and
checkpoints only its own shards; this is the reference's per-node sparse
shard discipline (box_wrapper.h:415 MPI cluster membership) on the JAX
coordination service.

Plan layout over n shards, per-device key capacity K, bucket capacity C,
US = n * C:

    serve_rows [D, n, C] int32  rows shard D must serve: serve_rows[o, d, c]
                                is requester d's c-th row owned by o
                                (dead-row padded).
    occ_flat   [D, K]    int32  o * C + c for each key occurrence of device
                                d's batch (points into its [n, C] pull
                                response); padding occurrences -> n * C,
                                which reads an appended all-zero row.
    serve_map  [D, n, C] int32  dedup: position of (requester, slot) in
                                serve_uniq[D] — the same table row requested
                                by several devices folds into one segment, so
                                the push-side optimizer update touches each
                                row exactly once.
    serve_uniq [D, US]   int32  deduped rows served by shard D (dead padded).
    key_mask   [D, K]    f32    1.0 for real occurrences.

Realized hybrid placement (PR 20, ``SparseTableConfig.placement_realize``):
beside the sharded cold layout above, the placement plan's hot set lives as
a REPLICATED ``[H, W+1]`` block resident on every device ACROSS passes (H =
``placement_hot_capacity``, padded — jit specializes on H once, never on
the live plan).  A hot occurrence routes to ``hot_occ`` (its slot in the
sorted resident hot set; H = sink) instead of the a2a bucket, so hot
lookups are a purely local gather with ZERO host-plane row bytes and zero
all_to_all slots inside a pass; its cold ``occ_flat`` entry points at the
dropped ``n*C`` sink.  Hot gradients reduce with a deterministic
device-order fold (parallel/trainer.py hybrid_hot_update) and the adagrad
apply runs replica-identically, so the replicas never diverge.  Hot⇄cold
promotions/demotions happen only at pass boundaries inside begin_pass
(keycodec-framed like reshard migration, broadcast on the census channel
multi-host, hysteresis-bounded churn); flush() writes the resident hot
rows back to the host store, so every persistence/reshard barrier sees
truth.  The cold census (``_pass_keys``) EXCLUDES resident hot keys — the
HbmCache directories, the staging thread and the FleetCacheMirror all see
only the cold tail.
"""

from __future__ import annotations

import dataclasses
import functools
import struct
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.config import SparseTableConfig
from paddlebox_tpu.data.feed import HostBatch
from paddlebox_tpu.parallel.mesh import DATA_AXIS
from paddlebox_tpu.parallel.multiprocess import (
    global_from_local,
    host_allgather,
    host_allgather_varlen,
    is_multiprocess,
    local_device_indices,
    local_view,
)
from paddlebox_tpu.sparse.table import (
    _PASS,
    SparseTable,
    _count_begin,
    _next_pow2,
    sorted_census,
)
from paddlebox_tpu.telemetry.compiles import stage_scope

# lockstep census-channel naming: every process constructs its sharded
# tables in the same order, so the counter agrees fleet-wide (the same
# discipline as the trainer's plan channels)
_CENSUS_CHANNEL_SEQ = [0]

# lockstep reshard-channel naming: reshard() is a collective (every
# process calls it at the same pass boundary), so the counter agrees
_RESHARD_CHANNEL_SEQ = [0]

# migration payload framing (keycodec-framed, versioned like the host
# plane's PBC1): magic | n_rows | row_width+1 | len(key_stream) |
# delta-compressed sorted keys | int32 rank (hottest-first order rides
# as the permutation beside the compressed sorted copy) | f32 rows
_RESHARD_MAGIC = b"PBR1"
_RESHARD_HEAD = "<4sIII"


def _encode_migration(keys: np.ndarray, rows: np.ndarray) -> bytes:
    """Frame one process's outgoing migration rows.  ``keys`` arrive in
    hottest-first order and that order is preserved on the wire
    (encode_u64_with_perm: compressed sorted stream + permutation)."""
    from paddlebox_tpu.utils.keycodec import encode_u64_with_perm

    kb, rank = encode_u64_with_perm(keys)
    head = struct.pack(
        _RESHARD_HEAD, _RESHARD_MAGIC, keys.shape[0], rows.shape[1], len(kb)
    )
    return (head + kb + rank.astype("<i4").tobytes()
            + np.ascontiguousarray(rows, dtype="<f4").tobytes())


def _decode_migration(buf: bytes):
    """Inverse of :func:`_encode_migration` -> (keys, rows), row order
    preserved.  Raises on any framing mismatch — a migration payload
    that doesn't round-trip must abort the reshard, never half-apply."""
    from paddlebox_tpu.utils.keycodec import decode_u64_with_perm

    head = struct.calcsize(_RESHARD_HEAD)
    magic, n, w1, klen = struct.unpack_from(_RESHARD_HEAD, buf, 0)
    if magic != _RESHARD_MAGIC:
        raise ValueError(f"bad reshard payload magic {magic!r}")
    off = head
    kb = bytes(buf[off:off + klen])
    off += klen
    rank = np.frombuffer(buf, dtype="<i4", count=n, offset=off)
    off += 4 * n
    keys = decode_u64_with_perm(kb, rank)
    rows = np.frombuffer(
        buf, dtype="<f4", count=n * w1, offset=off
    ).reshape(n, w1)
    if off + 4 * n * w1 != len(buf):
        raise ValueError("reshard payload length mismatch")
    return keys.copy(), rows.astype(np.float32)


@dataclasses.dataclass
class ShardedBatchPlan:
    """Stacked host plans for one group of per-device batches.

    Leading axis D == devices this process owns (== n_shards single-process);
    stacked into the mesh-sharded feed by the trainer.
    """

    serve_rows: np.ndarray  # int32 [D, n, C]
    occ_flat: np.ndarray  # int32 [D, K]
    serve_map: np.ndarray  # int32 [D, n, C]
    serve_uniq: np.ndarray  # int32 [D, n*C]
    key_mask: np.ndarray  # f32 [D, K]
    n_missing: int = 0  # keys absent from the pass census
    # structurally 0 since r4: the bucket grows to exact fit instead of
    # dropping keys (kept so callers' metrics plumbing keeps working)
    n_overflow: int = 0
    # f32 [D, n*C] per-served-unique-row learning rates (aligned with
    # serve_uniq), present only when the per-slot LR map is configured —
    # the serve-side half of the BoxPS LR map (box_wrapper.h:631): each
    # requester resolves its keys' slot lrs host-side and they ride the
    # want-matrix allgather, so slot identity survives the serve merge
    serve_lr: Optional[np.ndarray] = None
    # int32 [D, K] hot routing (realized hybrid placement only): each
    # occurrence's slot in the replicated hot block, H for cold/padding
    # occurrences (the appended-zero sink).  Hot occurrences carry the
    # n*C sink in occ_flat and are excluded from the want matrices.
    hot_occ: Optional[np.ndarray] = None
    # f32 [D, H] per-hot-slot learning rates (0.0 where this device has no
    # occurrence — the step pmax-folds them over the device axis so every
    # replica applies the identical lr), present only with the LR map
    hot_lr: Optional[np.ndarray] = None


class ShardedSparseTable(SparseTable):
    """Same host store / persistence / shrink as SparseTable; the pass
    working set lives as one stacked, mesh-sharded array."""

    def __init__(
        self,
        conf: SparseTableConfig,
        mesh: Mesh,
        seed: int = 0,
        bucket_slack: float = 2.0,
    ):
        super().__init__(conf, seed)
        self.mesh = mesh
        # composed (data x inner) meshes shard the table over the DATA
        # axis only; the inner axis replicates it and splits dense work
        self.n_shards = int(mesh.shape[DATA_AXIS])
        # all_to_all bucket capacity multiplier over the uniform-hash
        # expectation K / n_shards.  This sizes the BASE bucket only: a
        # group whose worst shard needs more grows the bucket in
        # power-of-two steps (capacity_bumps) — keys are never dropped, so
        # slack tunes recompile frequency, not correctness.
        self.bucket_slack = float(bucket_slack)
        self._shard_keys: Optional[list[np.ndarray]] = None
        self.overflow_key_count = 0  # kept for API compat: always 0 now
        # groups whose worst per-shard occupancy outgrew the base bucket and
        # forced a power-of-two capacity bump (each distinct capacity
        # recompiles the step once)
        self.capacity_bumps = 0
        # largest serve buffer (n * C) planned so far: sizes the next
        # pass's per-shard scratch region (pass 1 falls back to
        # conf.plan_scratch_rows)
        self._last_serve_n = 0
        # device-resident embedding engine, sharded: one HbmCache per LOCAL
        # shard (conf.hbm_cache_rows split evenly across shards), each on
        # its shard's own device, built lazily by _caches().  Hit fills
        # and admissions are per-shard single-device ops (_assemble_cached
        # / _end_pass_cached_sharded): no computation over the GLOBAL
        # arrays ever depends on which rows are locally cached — per-rank
        # cache state must never shape a collective, and a cache's rows
        # never leave its shard's HBM.
        self._shard_cache_list: list = []
        self._cache_plans = None
        # sparsity-aware placement + census wire (sparse/placement.py,
        # parallel/census.py): "hybrid" classifies replicated-hot keys
        # from observed census skew and rides them as membership bits on
        # the multi-host census exchange; "hash" is the flat baseline;
        # "loopback" additionally exercises the encode->decode wire path
        # single-process.  Lazily built (_census_exchange_obj).
        from paddlebox_tpu.config import flags as _flags

        self._placement_mode = conf.placement or _flags.placement
        if self._placement_mode not in ("hybrid", "hash", "loopback"):
            raise ValueError(
                "placement must be hybrid|hash|loopback, got "
                f"{self._placement_mode!r}"
            )
        # realized hybrid placement (module docstring): the plan's hot set
        # materialized as a replicated [H, W+1] device block.  OFF under
        # "hash" (no planner) and under the config/env kill switches —
        # then the table runs the PR-15 wire-only lifecycle unchanged.
        self._hot_realize = bool(
            conf.placement_realize
            and _flags.placement_realize
            and self._placement_mode in ("hybrid", "loopback")
            and conf.placement_hot_capacity > 0
        )
        # device-RESIDENT hot set (sorted unique; its position is the hot
        # block slot) + the replicated block itself: [n, H, W] values and
        # [n, H] g2sum, one identical copy per device, persistent ACROSS
        # passes (None until the first non-empty plan realizes)
        self._hot_keys = np.empty(0, np.uint64)
        self.hot_values = None
        self.hot_g2sum = None
        # resident hot rows updated by a pass and not yet written back
        self._hot_dirty = False
        self._hot_swap_fn = None  # jitted survivor remap (static [H] shapes)
        self._census = None
        self._census_channel = None
        # frequency evidence carried across a reshard cutover (seeds the
        # rebuilt planner so the hot set survives the shard-map swap)
        self._carry_freq = None
        # mesh positions (== global shard ids) whose devices this process
        # owns; single-process: every position.  The want-matrix allgather in
        # plan_group assumes each process's positions are one contiguous run
        # in process order (JAX's default device order guarantees it).
        self._local_pos = self._checked_local_pos(mesh)

    @staticmethod
    def _checked_local_pos(mesh: Mesh) -> np.ndarray:
        pos = local_device_indices(mesh)
        L = pos.shape[0]
        pid = jax.process_index()
        if not np.array_equal(pos, np.arange(pid * L, pid * L + L)):
            raise RuntimeError(
                f"process {pid} owns non-contiguous mesh positions "
                f"{pos.tolist()}: build the mesh from "
                "jax.devices() default order"
            )
        return pos

    @property
    def n_local(self) -> int:
        """Devices (== shards) owned by this process."""
        return self._local_pos.shape[0]

    # -- device-resident cache (per-shard) -------------------------------- #
    def _get_cache(self):
        """The single-chip cache object is unused here — the sharded
        lifecycle goes through the per-shard list (_caches)."""
        return None

    def _caches(self) -> list:
        """One HbmCache per local shard (lazily built; empty when
        disabled), its rows on that shard's device.  Capacity splits
        evenly across shards.  Composed meshes keep the uncached
        lifecycle (a data shard's inner device group has no single
        owning device)."""
        if not self._cache_tried:
            with self._cache_lock:
                if not self._cache_tried:
                    from paddlebox_tpu.config import flags

                    per_shard = self.conf.hbm_cache_rows // self.n_shards
                    if (
                        per_shard > 0
                        and flags.hbm_cache
                        and self.mesh.devices.ndim == 1
                    ):
                        from paddlebox_tpu.sparse.engine import HbmCache

                        self._shard_cache_list = [
                            HbmCache(
                                per_shard,
                                self.conf.row_width + 1,
                                aging=self.conf.hbm_cache_aging,
                                device=self.mesh.devices[int(o)],
                            )
                            for o in self._local_pos
                        ]
                    self._cache_tried = True
        return self._shard_cache_list

    # -- census wire (placement + compression) ----------------------------- #
    def _census_exchange_obj(self):
        """Lazily built CensusExchange: the placement planner + fleet
        cache mirrors + transport (loopback single-process, a dedicated
        KvChannel byte gather multi-host).  Construction is deterministic
        across ranks — channel naming rides a lockstep counter, planner
        and mirror sizing come from the (identical) table config."""
        if self._census is None:
            from paddlebox_tpu.config import flags
            from paddlebox_tpu.parallel.census import (
                CensusExchange,
                FleetCacheMirror,
                KvGatherTransport,
                LoopbackTransport,
            )

            planner = None
            mirror = None
            if self._placement_mode in ("hybrid", "loopback"):
                from paddlebox_tpu.sparse.placement import PlacementPlanner

                planner = PlacementPlanner(
                    hot_capacity=self.conf.placement_hot_capacity,
                    aging=self.conf.placement_aging,
                    update_interval=self.conf.placement_update_interval,
                )
                # seed from the HBM-cache LFU/aging directories when the
                # caches already hold frequency evidence (warm restart)
                for c in self._caches():
                    used = np.nonzero(c.used)[0]
                    if used.shape[0]:
                        planner.seed(c.keys[used], c.frequency(used))
                # evidence carried across a reshard cutover: the previous
                # planner's full tracker, so the hot set stays warm
                if self._carry_freq is not None:
                    planner.seed(*self._carry_freq)
                    self._carry_freq = None
                per_shard = self.conf.hbm_cache_rows // self.n_shards
                if per_shard > 0 and flags.hbm_cache:
                    mirror = FleetCacheMirror(
                        self.n_shards, per_shard, self.conf.hbm_cache_aging
                    )
            codec = (
                "raw" if flags.hostplane_codec == "raw" else "varint"
            )
            if is_multiprocess():
                from paddlebox_tpu.parallel.host_plane import KvChannel

                _CENSUS_CHANNEL_SEQ[0] += 1
                self._census_channel = KvChannel(
                    f"census-{_CENSUS_CHANNEL_SEQ[0]}"
                )
                transport = KvGatherTransport(self._census_channel)
            else:
                transport = LoopbackTransport()
            self._census = CensusExchange(
                transport, planner=planner, mirror=mirror, codec=codec,
                realize=self._hot_realize,
            )
        return self._census

    def _exchange_census(self, pk: np.ndarray) -> np.ndarray:
        """Local census -> the global census.  Multi-host, the exchange
        runs on the main thread in lockstep across ranks (prepare_pass
        stays gated off multi-process for exactly this reason); the
        legacy codec keeps the pre-codec device-collective union for
        mixed-version fleets."""
        from paddlebox_tpu.config import flags

        if is_multiprocess():
            if flags.hostplane_codec == "legacy":
                return np.unique(host_allgather_varlen(pk))
            return self._census_exchange_obj().exchange(pk)
        if self._placement_mode == "loopback" or self._hot_realize:
            # realization needs the planner even single-process "hybrid"
            # (the hot set it materializes IS the planner's); loopback
            # additionally exercises the wire round-trip
            return self._census_exchange_obj().exchange(pk)
        return pk

    def placement_plan(self):
        """The current PlacementPlan, or None when the planner is off —
        test introspection."""
        if self._census is None or self._census.planner is None:
            return None
        return self._census.planner.plan()

    # -- realized hybrid placement (replicated-hot block) ------------------ #
    @property
    def hot_block_capacity(self) -> int:
        """Padded capacity H of the replicated hot block (0 = realization
        off).  STATIC for the table's lifetime: the trainer specializes
        its step on this, never on the live plan — the zero-retrace-
        under-plan-churn pin."""
        return self.conf.placement_hot_capacity if self._hot_realize else 0

    def hot_resident_keys(self) -> np.ndarray:
        """The device-resident hot set (sorted; slot i of the hot block
        holds key i) — test introspection."""
        return self._hot_keys

    def _drop_hot_residency(self) -> None:
        """Forget the replicated hot block WITHOUT writing it back —
        callers that mutate the store underneath (load_state_dict /
        apply_delta / shrink / reshard cutover) flush() first, and flush
        writes the resident hot rows to the store."""
        self._hot_keys = np.empty(0, np.uint64)
        self.hot_values = None
        self.hot_g2sum = None
        self._hot_dirty = False

    def _invalidate_caches(self) -> None:
        """Store mutated underneath: the hot block is as stale as the
        HBM-cache rows — drop residency along with the cache state (the
        next begin_pass re-realizes from the rewritten store)."""
        super()._invalidate_caches()
        self._drop_hot_residency()

    def flush(self) -> None:
        """Hot rows first: the resident hot block is truth for its keys
        (they are absent from both the cold working set and the HBM
        caches), so every barrier that makes the store authoritative —
        checkpoint, shrink, delta, reshard — must land them before the
        base-class cache drain + merge wait."""
        self._flush_hot()
        super().flush()

    def _flush_hot(self) -> None:
        if (
            self._in_pass
            or not self._hot_dirty
            or self.hot_values is None
            or not self._hot_keys.shape[0]
        ):
            return
        m = self._hot_keys.shape[0]
        lv = np.asarray(local_view(self.hot_values)[0])  # [H, W]
        lg = np.asarray(local_view(self.hot_g2sum)[0])  # [H]
        keys = self._hot_keys
        rows = np.concatenate([lv[:m], lg[:m, None]], axis=1)
        if is_multiprocess():
            # single owner writes back: every replica holds identical rows,
            # but only the process owning a key's shard persists it
            own = self._proc_of(
                (keys % np.uint64(self.n_shards)).astype(np.int64),
                self.n_shards,
            ) == jax.process_index()
            keys, rows = keys[own], rows[own]
        if keys.shape[0]:
            self._write_back(keys, np.ascontiguousarray(rows))
        self._hot_dirty = False

    def _sync_hot_block(self) -> None:
        """Reconcile the device-resident hot block with the just-updated
        placement plan (begin_pass, after the census exchange).  Steady
        state (no plan change) touches nothing — boundary host traffic
        from the hot tier is O(churn), and churn is hysteresis-bounded."""
        from paddlebox_tpu import telemetry
        from paddlebox_tpu.sparse.placement import hot_churn

        plan = self.placement_plan()
        target = (
            plan.hot_keys if plan is not None else np.empty(0, np.uint64)
        )
        if target.shape[0] > self.conf.placement_hot_capacity:
            raise RuntimeError(
                f"plan hot set ({target.shape[0]}) exceeds the realized "
                f"block capacity ({self.conf.placement_hot_capacity})"
            )
        promote, demote = hot_churn(self._hot_keys, target)
        if (
            promote.shape[0]
            or demote.shape[0]
            or (target.shape[0] and self.hot_values is None)
        ):
            self._migrate_hot(target, promote, demote)
        telemetry.gauge(
            "placement.hot_resident_rows",
            "rows resident in the replicated device hot block",
        ).set(float(self._hot_keys.shape[0]))

    def _migrate_hot(self, target, promote, demote) -> None:
        """Commit one hot-set mutation: demoted rows leave the device
        block for the host store (single owner writes back), promoted
        rows are fetched read-through the HBM caches / store and
        broadcast so every device assembles the identical new block, and
        surviving rows remap device-side (a static-[H]-shape jitted
        gather — zero host bytes and zero retraces for survivors)."""
        from paddlebox_tpu import telemetry

        w = self.conf.row_width
        H = self.conf.placement_hot_capacity
        n = self.n_shards
        host_bytes = telemetry.counter(
            "placement.hot_row_host_bytes",
            "hot-tier row bytes crossing the host plane (promotions + "
            "demotions at pass boundaries; structurally zero inside a "
            "pass)",
        )
        old = self._hot_keys
        if demote.shape[0] and self.hot_values is not None:
            slots = np.searchsorted(old, demote)
            lv = np.asarray(local_view(self.hot_values)[0])
            lg = np.asarray(local_view(self.hot_g2sum)[0])
            rows = np.concatenate([lv[slots], lg[slots, None]], axis=1)
            dk = demote
            if is_multiprocess():
                own = self._proc_of(
                    (demote % np.uint64(n)).astype(np.int64), n
                ) == jax.process_index()
                dk, rows = demote[own], rows[own]
            if dk.shape[0]:
                self._write_back(dk, np.ascontiguousarray(rows))
                host_bytes.inc(rows.nbytes)
        promo_rows = self._fetch_hot_rows(promote)
        if promo_rows.shape[0]:
            host_bytes.inc(promo_rows.nbytes)
        # assemble the new block: promoted rows at their slot in the
        # sorted target, survivors gathered from their old slot on device,
        # padding slots ([live, H)) explicitly zero
        promo_v = np.zeros((H, w), np.float32)
        promo_g = np.zeros(H, np.float32)
        if promote.shape[0]:
            ts = np.searchsorted(target, promote)
            promo_v[ts] = promo_rows[:, :w]
            promo_g[ts] = promo_rows[:, w]
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        if self.hot_values is None or not old.shape[0]:
            lv = np.repeat(promo_v[None], self.n_local, axis=0)
            lg = np.repeat(promo_g[None], self.n_local, axis=0)
            self.hot_values = global_from_local(sharding, lv)
            self.hot_g2sum = global_from_local(sharding, lg)
        else:
            src = np.zeros(H, np.int32)
            surv = np.zeros(H, bool)
            if target.shape[0]:
                pos = np.searchsorted(old, target)
                pos_c = np.minimum(pos, old.shape[0] - 1)
                hit = old[pos_c] == target
                src[: target.shape[0]] = pos_c.astype(np.int32)
                surv[: target.shape[0]] = hit
            self.hot_values, self.hot_g2sum = self._hot_swap_jit()(
                self.hot_values,
                self.hot_g2sum,
                promo_v,
                promo_g,
                src,
                surv,
            )
        self._hot_keys = np.asarray(target, np.uint64).copy()

    def _hot_swap_jit(self):
        if self._hot_swap_fn is None:
            from paddlebox_tpu.telemetry.compiles import counted_jit

            def _swap(hv, hg, pv, pg, src, surv):
                # [n, H, W]/[n, H] replicated-per-device blocks; take along
                # the unsharded slot axis keeps the P(DATA_AXIS) layout —
                # no collective, no host round trip for survivors
                sv = jnp.take(hv, src, axis=1)
                sg = jnp.take(hg, src, axis=1)
                nv = jnp.where(surv[None, :, None], sv, pv[None])
                ng = jnp.where(surv[None, :], sg, pg[None])
                return nv, ng

            self._hot_swap_fn = counted_jit(
                _swap, stage="spmd.hot_swap", donate_argnums=(0, 1)
            )
        return self._hot_swap_fn

    def _fetch_owned_hot_rows(self, keys: np.ndarray) -> np.ndarray:
        """Promotion read-through for keys owned by this process's shards:
        HBM-cache hits gather device->host AND leave the directory (the
        hot block becomes their truth), misses resolve from the
        store/overlay, unseen keys init key-deterministically."""
        w = self.conf.row_width
        out = np.zeros((keys.shape[0], w + 1), np.float32)
        if not keys.shape[0]:
            return out
        caches = self._caches()
        owner = keys % np.uint64(self.n_shards)
        for i, o in enumerate(self._local_pos):
            pos = np.nonzero(owner == np.uint64(int(o)))[0]
            if not pos.shape[0]:
                continue
            sk = keys[pos]
            if caches:
                with self._cache_lock:
                    hit, rows = caches[i].take_rows(
                        sk, pad_to=self.conf.placement_hot_capacity
                    )
                if hit.any():
                    out[pos[hit]] = rows
                miss = ~hit
                if miss.any():
                    out[pos[miss]] = self._resolve_or_init(sk[miss])
            else:
                out[pos] = self._resolve_or_init(sk)
        return out

    def broadcast_hot_rows(self, payload: bytes) -> list:
        """Host collective (multi-host begin_pass, lockstep): every rank
        contributes its owned shards' promoted hot rows as one keycodec
        frame on the census channel; every rank receives all frames and
        assembles the identical replicated block."""
        self._census_exchange_obj()
        return self._census_channel.gather_bytes(payload)

    def _fetch_hot_rows(self, promote: np.ndarray) -> np.ndarray:
        """[P, W+1] rows for the sorted promoted keys, identical on every
        rank.  Single-process: a direct owner fetch ("loopback" rides the
        keycodec frame round trip, verified bit-exact — the same wire
        discipline as reshard migration).  Multi-host: owners frame their
        rows, the frames cross the census channel, every rank decodes
        all of them."""
        w = self.conf.row_width
        if not promote.shape[0]:
            return np.zeros((0, w + 1), np.float32)
        n = self.n_shards
        if not is_multiprocess():
            rows = self._fetch_owned_hot_rows(promote)
            if self._placement_mode == "loopback":
                dk, drows = _decode_migration(
                    _encode_migration(promote, rows)
                )
                if not (np.array_equal(dk, promote)
                        and np.array_equal(drows, rows)):
                    raise RuntimeError(
                        "hot-promotion payload failed the loopback "
                        "round-trip verify")
                rows = drows
            return rows
        own = self._proc_of(
            (promote % np.uint64(n)).astype(np.int64), n
        ) == jax.process_index()
        payload = _encode_migration(
            promote[own], self._fetch_owned_hot_rows(promote[own])
        )
        out = np.zeros((promote.shape[0], w + 1), np.float32)
        for buf in self.broadcast_hot_rows(payload):
            k, v = _decode_migration(buf)
            if k.shape[0]:
                out[np.searchsorted(promote, k)] = v
        return out

    def close(self) -> None:
        """Retire the census channel (its keys and peer-read pool) on top
        of the base-table quiesce."""
        ch, self._census_channel = self._census_channel, None
        self._census = None
        if ch is not None:
            ch.close()
        super().close()

    def abort_pass(self) -> None:
        self._cache_plans = None
        super().abort_pass()

    # -- live resharding (PR 16) ------------------------------------------- #
    def reshard(self, new_mesh: Mesh) -> int:
        """Grow/shrink the shard count at a pass boundary (collective:
        every process calls this at the SAME boundary).  Returns the
        number of rows whose owner shard changed.

        The cut point is the same barrier checkpointing rides: flush()
        drains dirty HBM-cache rows and waits out in-flight write-backs,
        so the host store is truth for every key before any row moves.
        Any staged next pass is discarded — it was resolved and laid out
        for the OLD shard split.

        Two phases, both fault sites, with an all-or-nothing contract:
        ``_reshard_migrate`` stages the owner-changed rows through the
        host plane (keycodec-framed, hottest-first by planner frequency
        evidence, round-trip verified) WITHOUT mutating anything;
        ``_reshard_cutover`` then commits — store ownership, mesh, shard
        count, cache/census rebuild.  A failure in either phase aborts
        cleanly back to the old shard map (``_reshard_abort``) and
        re-raises: there is no partial cutover state.

        Bit-exactness: rows are moved verbatim ([show, clk, embed…,
        g2sum] bytes untouched), fresh-key init is key-deterministic
        (_key_uniform is shard-count-independent), and per-shard math
        orders by the same sorted global census — so training after a
        live reshard is bit-identical to a teardown-and-rebuild at the
        new shard count (pinned by tests/test_reshard.py).
        """
        if self._in_pass:
            raise RuntimeError("reshard between passes, never inside one")
        new_n = int(new_mesh.shape[DATA_AXIS])
        if new_n < 1:
            raise ValueError(f"new mesh has no {DATA_AXIS!r} shards")
        # validate the new mesh placement BEFORE any fallible phase: a
        # non-contiguous process->position layout must fail here, while
        # nothing has migrated or mutated (all-or-nothing contract)
        self._checked_local_pos(new_mesh)
        from paddlebox_tpu import telemetry

        self.flush()
        self._discard_stage()
        if new_n == self.n_shards and np.array_equal(
            np.asarray(self.mesh.devices, dtype=object),
            np.asarray(new_mesh.devices, dtype=object),
        ):
            return 0
        old = self._reshard_snapshot()
        t0 = time.perf_counter()
        try:
            with telemetry.span("reshard.migrate", old_shards=self.n_shards,
                                new_shards=new_n):
                staged, moved = self._reshard_migrate(new_mesh)
            with telemetry.span("reshard.cutover", old_shards=self.n_shards,
                                new_shards=new_n):
                self._reshard_cutover(new_mesh, staged)
        except Exception:
            self._reshard_abort(old)
            telemetry.counter(
                "reshard.aborts",
                "reshards rolled back to the old shard map",
            ).inc()
            raise
        telemetry.counter(
            "reshard.migrated_rows",
            "rows whose owner shard changed across reshards",
        ).inc(moved)
        telemetry.histogram(
            "reshard.seconds", "live reshard wall time (migrate + cutover)"
        ).observe(time.perf_counter() - t0)
        return moved

    def _reshard_snapshot(self) -> dict:
        """Everything _reshard_abort needs to restore the old shard map.
        The snapshot is references, not copies: migrate stages rows
        without mutating, and cutover swaps these fields only after its
        own fault site — so on every abort branch the referenced objects
        are still exactly the pre-reshard state."""
        return {
            "mesh": self.mesh,
            "n_shards": self.n_shards,
            "local_pos": self._local_pos,
            "caches": self._shard_cache_list,
            "cache_tried": self._cache_tried,
            "census": self._census,
            "census_channel": self._census_channel,
            "last_serve_n": self._last_serve_n,
            "carry_freq": self._carry_freq,
            "hot_keys": self._hot_keys,
            "hot_values": self.hot_values,
            "hot_g2sum": self.hot_g2sum,
            "hot_dirty": self._hot_dirty,
            "hot_swap_fn": self._hot_swap_fn,
        }

    def _proc_of(self, shard: np.ndarray, n_shards: int) -> np.ndarray:
        """Owning process per shard id under a given shard count (shards
        split into contiguous per-process runs — asserted in __init__)."""
        per = max(n_shards // jax.process_count(), 1)
        return shard // per

    def _reshard_migrate(self, new_mesh: Mesh):
        """Stage the owner-changed rows for the new shard map — NO
        mutation of store/caches/mesh happens here, so an abort after a
        migrate failure has nothing to undo.

        Single-process, ownership never leaves the one host store: the
        moved set still rides the full encode→decode wire round-trip
        (same loopback discipline as the census exchange) and is
        verified bit-exact against the store rows.  Multi-host, each
        process frames its outgoing rows and the payloads cross the host
        plane on a dedicated KvChannel byte gather; the staged result is
        (incoming keys/rows to merge, outgoing keys to drop) committed
        by cutover."""
        from paddlebox_tpu.utils import faults

        faults.inject("reshard.migrate")
        old_n, new_n = self.n_shards, int(new_mesh.shape[DATA_AXIS])
        keys, rows = self._store.materialize()
        old_owner = (keys % np.uint64(old_n)).astype(np.int64)
        new_owner = (keys % np.uint64(new_n)).astype(np.int64)
        moved_mask = old_owner != new_owner
        moved = int(moved_mask.sum())
        mk, mrows = keys[moved_mask], rows[moved_mask]
        # hottest-first: the planner's frequency evidence orders the
        # payload so the keys most likely in the next pass's census land
        # (and can be cache-seeded) first; ties stay in key order
        planner = None if self._census is None else self._census.planner
        if planner is not None and mk.shape[0]:
            order = np.argsort(-planner.frequencies(mk), kind="stable")
            mk, mrows = mk[order], mrows[order]
        multi = is_multiprocess()
        if not multi:
            # loopback wire: what WOULD cross the host plane must survive
            # the codec round trip bit-exactly, or the reshard aborts
            dk, drows = _decode_migration(_encode_migration(mk, mrows))
            if not (np.array_equal(dk, mk)
                    and np.array_equal(drows, mrows)):
                raise RuntimeError(
                    "reshard migration payload failed the loopback "
                    "round-trip verify")
            return {"multi": False}, moved
        # multi-host: ship only the rows LEAVING this process's shards
        from paddlebox_tpu.parallel.host_plane import KvChannel

        pid = jax.process_index()
        mo = self._proc_of((mk % np.uint64(old_n)).astype(np.int64), old_n)
        mn = self._proc_of((mk % np.uint64(new_n)).astype(np.int64), new_n)
        om = (mo == pid) & (mn != pid)
        _RESHARD_CHANNEL_SEQ[0] += 1
        ch = KvChannel(f"reshard-{_RESHARD_CHANNEL_SEQ[0]}")
        try:
            payloads = ch.gather_bytes(_encode_migration(mk[om], mrows[om]))
        finally:
            ch.close()
        in_keys, in_rows = [], []
        for p, buf in enumerate(payloads):
            if p == pid:
                continue
            k, v = _decode_migration(buf)
            mine = self._proc_of(
                (k % np.uint64(new_n)).astype(np.int64), new_n
            ) == pid
            if mine.any():
                in_keys.append(k[mine])
                in_rows.append(v[mine])
        staged = {
            "multi": True,
            "drop_keys": mk[om],
            "in_keys": (np.concatenate(in_keys) if in_keys
                        else np.empty(0, np.uint64)),
            "in_rows": (np.concatenate(in_rows) if in_rows
                        else np.empty((0, rows.shape[1]), np.float32)),
        }
        return staged, moved

    def _reshard_cutover(self, new_mesh: Mesh, staged: dict) -> None:
        """Commit the new shard map.  The fault site fires BEFORE any
        mutation, so an injected cutover failure aborts with the old map
        fully intact (the chaos contract tests pin).  Dirty cache rows
        were drained by the flush() at the cut point and no pass ran
        since, so dropping the per-shard caches here loses nothing; the
        planner's frequency evidence is carried into the rebuilt census
        exchange so the hot set stays warm."""
        from paddlebox_tpu.utils import faults

        faults.inject("reshard.cutover")
        # the last fallible step runs before the first mutation: a bad
        # mesh placement aborts with the store and census fully intact
        new_local_pos = self._checked_local_pos(new_mesh)
        if staged.get("multi"):
            # ownership commit: merge rows that moved to this process,
            # rebuild the store without the rows that left.  The wire
            # payload is hottest-first; the store contract is sorted
            # unique keys, so re-sort before merging (keys are globally
            # unique — each has exactly one old owner process)
            if staged["in_keys"].shape[0]:
                order = np.argsort(staged["in_keys"], kind="stable")
                self._store.update(
                    staged["in_keys"][order], staged["in_rows"][order]
                )
            if staged["drop_keys"].shape[0]:
                keys, rows = self._store.materialize()
                keep = ~np.isin(keys, staged["drop_keys"])
                self._store.clear()
                self._store.load_bulk(keys[keep], rows[keep])
        # carry the planner's evidence before the census objects go
        if self._census is not None and self._census.planner is not None:
            self._carry_freq = self._census.planner.evidence()
        ch, self._census_channel = self._census_channel, None
        self._census = None
        self.mesh = new_mesh
        self.n_shards = int(new_mesh.shape[DATA_AXIS])
        self._local_pos = new_local_pos
        # per-shard caches are keyed to the old split: drop and let
        # _caches() rebuild for the new shard count (re-seeded from the
        # next passes' censuses + the carried frequency evidence)
        self._shard_cache_list = []
        self._cache_tried = False
        self._cache_plans = None
        self._shard_keys = None
        # serve-scratch sizing learned under the old split is stale
        self._last_serve_n = 0
        # the hot block was flushed at the cut point (reshard's flush()
        # writes resident hot rows) and the planner evidence is carried,
        # so dropping residency loses nothing: the next begin_pass
        # re-realizes the warm hot set from the store at the new split.
        # The swap fn is shape-bound to the old device count.
        self._drop_hot_residency()
        self._hot_swap_fn = None
        # close the old census channel LAST: everything above is either
        # pre-mutation validation or infallible assignment, so an abort
        # can never be asked to restore an already-closed channel
        if ch is not None:
            ch.close()

    def _reshard_abort(self, old: dict) -> None:
        """Restore the old shard map on ANY failed branch: every field
        cutover swaps goes back to the snapshot references (which were
        never mutated — migrate stages, cutover commits)."""
        self.mesh = old["mesh"]
        self.n_shards = old["n_shards"]
        self._local_pos = old["local_pos"]
        self._shard_cache_list = old["caches"]
        self._cache_tried = old["cache_tried"]
        self._census = old["census"]
        self._census_channel = old["census_channel"]
        self._last_serve_n = old["last_serve_n"]
        self._carry_freq = old["carry_freq"]
        self._hot_keys = old["hot_keys"]
        self.hot_values = old["hot_values"]
        self.hot_g2sum = old["hot_g2sum"]
        self._hot_dirty = old["hot_dirty"]
        self._hot_swap_fn = old["hot_swap_fn"]
        self._cache_plans = None

    # -- pass lifecycle --------------------------------------------------- #
    def _shard_split(self, pk: np.ndarray):
        """(owner, shard_keys, row_within) for a sorted global census —
        deterministic in pk, so staging and begin_pass always agree."""
        n = self.n_shards
        owner = (pk % np.uint64(n)).astype(np.int64)
        shard_keys = [pk[owner == o] for o in range(n)]  # each stays sorted
        # precomputed key -> (owner, row-within-shard) map aligned with the
        # sorted pass keys, so per-batch planning is one searchsorted
        row_within = np.empty(pk.shape[0], dtype=np.int32)
        for o in range(n):
            m = owner == o
            row_within[m] = np.arange(int(m.sum()), dtype=np.int32)
        return owner, shard_keys, row_within

    def _sharded_cap(self, shard_keys) -> int:
        # shard layout mirrors the single-chip table: [0, live) rows |
        # [live, cap-1) plan scratch (distinct scatter targets for the
        # serve_uniq padding tail -> unique push indices) | cap-1 dead.
        # After the first plan, the observed serve-buffer size is the exact
        # scratch need; pass 1 falls back to the config default.
        scratch = self._last_serve_n or self.conf.plan_scratch_rows
        return _next_pow2(
            max((sk.shape[0] for sk in shard_keys), default=0) + 1 + scratch
        )

    def prepare_pass(self, pass_keys) -> None:
        """Stage the next pass's stacked working set in the background.
        Multi-process runs keep the synchronous begin_pass (the census
        allgather is a collective that must run on the main thread in
        lockstep across ranks); the async end-pass write-back still
        applies there — it is purely local."""
        if is_multiprocess():
            return
        super().prepare_pass(pass_keys)

    def _stage_job(self, pass_keys):
        from paddlebox_tpu import telemetry

        t0 = time.perf_counter()
        if callable(pass_keys):
            pass_keys = pass_keys()
        # single-process only (prepare_pass gates): the local census IS the
        # global census, no allgather needed off-thread
        pk = sorted_census(pass_keys)
        cache_keys, stage_seq, entries = self._stage_snapshot()
        # hot/cold split prediction: the stage resolves only the COLD tail
        # under the CURRENT resident hot set (the plan cannot change
        # mid-pass — only begin_pass's exchange updates it).  begin_pass
        # validates the prediction and discards the stage when the plan
        # churned (pass.stage_discards) — churn passes pay the sync
        # resolve, steady-state passes get the full overlap.
        shot = self._hot_keys if self._hot_realize else None
        cold_pk = (
            np.setdiff1d(pk, shot, assume_unique=True)
            if shot is not None and shot.shape[0] else pk
        )
        owner, shard_keys, row_within = self._shard_split(cold_pk)
        w = self.conf.row_width
        cap = self._sharded_cap(shard_keys)
        lvals = np.zeros((self.n_local, cap, w + 1), dtype=np.float32)
        for i, o in enumerate(self._local_pos):
            sk = shard_keys[o]
            ok = self._stage_resolve(
                sk,
                lvals[i, : sk.shape[0]],
                cache_keys[i] if cache_keys else None,
                entries,
            )
            if not ok:  # fault-injected promotion fetch: stage => discard
                return pk, owner, shard_keys, row_within, None, shot, stage_seq
        telemetry.histogram(
            "pass.promote_seconds",
            "background next-pass census resolve + init + staging wall time",
        ).observe(time.perf_counter() - t0)
        # stage_seq stays LAST: the base _pop_stage reads payload[-1] as
        # the overlay consistency point for patch-log filtering
        return pk, owner, shard_keys, row_within, lvals, shot, stage_seq

    def _cached_sync_resolve(self, caches, shard_keys, lvals, pk):
        """Synchronous per-shard census resolve against the HBM cache:
        fill only each shard's cache misses from the host store.  Returns
        (caches, plans): each local shard's census as resolved against
        its directory, for the device hit-fill to reuse.  A
        fault-injected promotion fetch (site ``cache.fetch``) degrades the
        whole pass to the uncached host resolve — dirty rows drain first,
        census keys leave every cache — and returns ([], None) so the
        caller skips the device hit-fill."""
        from paddlebox_tpu import telemetry
        from paddlebox_tpu.utils import faults

        plans = []
        try:
            for i, o in enumerate(self._local_pos):
                sk = shard_keys[o]
                plans.append(caches[i].lookup(sk))
                miss_pos = np.nonzero(~plans[i].hit_mask)[0]
                if miss_pos.shape[0]:
                    with _PASS.stage("fetch"):
                        lvals[i, miss_pos] = self._cache_fetch_rows(
                            sk[miss_pos])
        except faults.FaultInjected:
            telemetry.counter(
                "cache.fetch_fallbacks",
                "promotion fetches degraded to the full host resolve",
            ).inc()
            self._cache_degrade(pk)
            lvals[:] = 0.0
            with _PASS.stage("fetch"):
                for i, o in enumerate(self._local_pos):
                    sk = shard_keys[o]
                    lvals[i, : sk.shape[0]] = self._resolve_or_init(sk)
            return [], None
        return caches, plans

    @stage_scope("pass.begin")
    def begin_pass(self, pass_keys: np.ndarray) -> None:
        """Promote the pass working set (this process's shards) to device.

        pass_keys: the keys THIS process saw in its dataset shard; the
        global census is the allgather-union (multi-host collective #1).
        With a matching prepare_pass stage, the visible work is one
        per-shard intersection patch + the sharded device_put.
        """
        if self._in_pass:
            raise RuntimeError("end_pass the previous pass first")
        from paddlebox_tpu.utils.monitor import stats

        _count_begin("begin_entry", [c.rows for c in self._caches()])
        with _PASS.stage("census"):
            pk = sorted_census(pass_keys)
            # global census: the shared-dictionary exchange (hot/cached
            # keys ride as membership bits, the cold tail as varint deltas
            # — parallel/census.py) with byte-identical union semantics;
            # the legacy codec keeps the raw device-collective union
            pk = self._exchange_census(pk)
        w = self.conf.row_width
        cold_pk = pk
        if self._hot_realize:
            # reconcile the replicated hot block with the (possibly just
            # updated) plan, THEN split: the cold working set excludes
            # every resident hot key — caches, staging and the mirror all
            # see only the cold tail (module docstring)
            with _PASS.stage("hot_sync"):
                self._sync_hot_block()
                if self._hot_keys.shape[0]:
                    cold_pk = np.setdiff1d(
                        pk, self._hot_keys, assume_unique=True
                    )
        with _PASS.stage("take_stage"):
            payload, patches = self._pop_stage()
        lvals = None
        if payload is not None:
            spk, owner, shard_keys, row_within, svals, shot, _ = payload
            if svals is None:  # fault-injected stage fetch: sync fallback
                stats.add("pass.stage_discards")
            elif (
                np.array_equal(spk, pk)
                and (shot is None or np.array_equal(shot, self._hot_keys))
                and svals.shape[1] == self._sharded_cap(shard_keys)
                and svals.shape[0] == self.n_local
            ):
                lvals = svals
                for i, o in enumerate(self._local_pos):
                    sk = shard_keys[o]
                    if sk.shape[0]:
                        self._patch_rows(
                            sk, lvals[i, : sk.shape[0]], patches
                        )
            else:
                stats.add("pass.stage_discards")
        caches = self._caches()
        plans = None  # the staged path resolves at the fill
        pass_hits = 0  # cache hits filled from device THIS pass
        if lvals is None:
            with _PASS.stage("census"):
                owner, shard_keys, row_within = self._shard_split(cold_pk)
            cap = self._sharded_cap(shard_keys)
            # materialize only the local shards: rows come from this
            # process's host store (each process persists exactly its owned
            # shards), and fresh keys init key-deterministically
            # (_key_uniform), so any process layout produces identical rows.
            # With the HBM cache, the host supplies only the cache MISSES
            # per shard — the hit positions are filled from device below.
            with _PASS.stage("alloc"):
                lvals = np.zeros(
                    (self.n_local, cap, w + 1), dtype=np.float32)
            if caches:
                caches, plans = self._cached_sync_resolve(
                    caches, shard_keys, lvals, cold_pk
                )
            else:
                with _PASS.stage("fetch"):
                    for i, o in enumerate(self._local_pos):
                        sk = shard_keys[o]
                        lvals[i, : sk.shape[0]] = self._resolve_or_init(sk)
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self._cache_plans = None
        # the uploaded and filled per-shard buffers: what begin_exit asks
        # is_ready of (cache off: the pass arrays themselves)
        self._begin_bufs = []
        if caches:
            # cached assembly: strictly per-shard single-device ops, then
            # one process-local global-array construction — a computation
            # over the GLOBAL arrays here would be a collective whose
            # program depends on per-rank cache state (deadlock multi-host)
            self._assemble_cached(
                lvals, shard_keys, caches, cold_pk, sharding, plans)
            pass_hits = self.last_cache_hits
        else:
            with _PASS.stage("upload"):
                self.values = global_from_local(sharding, lvals[:, :, :w])
                self.g2sum = global_from_local(sharding, lvals[:, :, w])
            self._begin_bufs = [self.values, self.g2sum]
        # boundary host traffic: rows that actually crossed host->device
        # (cache misses; everything, cache-off).  With realization on, the
        # hot tier never lands here — test_placement pins the collapse
        # to O(cold)
        from paddlebox_tpu import telemetry as _tm

        owned = sum(int(shard_keys[o].shape[0]) for o in self._local_pos)
        _tm.counter(
            "pass.host_row_bytes_in",
            "embedding-row bytes promoted host->device at begin_pass "
            "(cache misses + cold materialization)",
        ).inc(max(owned - pass_hits, 0) * 4 * (w + 1))
        self._shard_keys = shard_keys
        self._census_index = None  # stale: points at the previous census
        self._shard_live = np.asarray(
            [shard_keys[o].shape[0] for o in self._local_pos], np.int32
        )  # per-LOCAL-shard scratch base
        self._pass_owner = owner.astype(np.int32)
        self._pass_row = row_within
        self._pass_keys = cold_pk
        self._in_pass = True
        if is_multiprocess():
            local_keys = [shard_keys[o] for o in self._local_pos]
            if self._hot_keys.shape[0]:
                # this process's delta also covers the hot rows its shards
                # own (every replica trains them; one owner persists them)
                own = self._proc_of(
                    (self._hot_keys % np.uint64(self.n_shards)).astype(
                        np.int64
                    ),
                    self.n_shards,
                ) == jax.process_index()
                local_keys.append(self._hot_keys[own])
            self._delta_keys.append(np.concatenate(local_keys))
        else:
            self._delta_keys.append(pk)
        self._observe_gap()
        _count_begin("begin_exit", self._begin_bufs + [
            c.rows for c in self._caches()])
        self._begin_bufs = []

    def _assemble_cached(self, lvals, shard_keys, caches, pk,
                         sharding, plans=None) -> None:
        """Cached promotion: per LOCAL shard, put the miss-filled host
        buffer on the shard's own device, overwrite the cache hits with a
        single-device gather out of that shard's persistent cache, and
        assemble the global [n, cap, W] arrays from the per-device buffers
        (make_array_from_single_device_arrays — a pure construction, no
        collective).  Multi-host, the census exchange already agreed pk
        fleet-wide, so shapes match across ranks even though every rank's
        hit pattern differs.  ``plans``: the per-shard resolves of the
        sync miss fetch; without them (the staged path) each shard's
        census is resolved here."""
        from paddlebox_tpu import telemetry

        w = self.conf.row_width
        cap = lvals.shape[1]
        devs = [self.mesh.devices[int(o)] for o in self._local_pos]
        vbufs, gbufs = [], []
        if plans is None:
            plans = [caches[i].lookup(shard_keys[o])
                     for i, o in enumerate(self._local_pos)]
        total_hits = 0
        for i, plan in enumerate(plans):
            with _PASS.stage("upload"):
                lv = jax.device_put(lvals[i], devs[i])  # [cap, W+1]
            if plan.n_hits:
                with _PASS.stage("fill"):
                    hr = caches[i].gather_rows(plan.hit_slots)
                    lv = lv.at[
                        jax.device_put(plan.hit_pos, devs[i])].set(hr)
            caches[i].touch(plan)
            total_hits += plan.n_hits
            vbufs.append(lv[None, :, :w])
            gbufs.append(lv[None, :, w])
            self._begin_bufs.append(lv)
        n = self.n_shards
        with _PASS.stage("upload"):
            self.values = jax.make_array_from_single_device_arrays(
                (n, cap, w), sharding, vbufs
            )
            self.g2sum = jax.make_array_from_single_device_arrays(
                (n, cap), sharding, gbufs
            )
        self._cache_plans = plans
        # local-shard hit accounting (pk is global; the per-process miss
        # count is relative to the keys THIS process's shards own)
        owned = sum(int(shard_keys[o].shape[0]) for o in self._local_pos)
        self.last_cache_hits = total_hits
        self.last_cache_misses = owned - total_hits
        telemetry.gauge(
            "cache.hit_rate",
            "fraction of the pass census served from the HBM cache",
        ).set(total_hits / max(owned, 1))

    def _local_shard_arrays(self, x) -> dict:
        """{global shard position -> [cap, ...] single-device array} for
        this process's shards of a leading-axis-sharded global array —
        the multi-host face of per-shard device math (no computation on
        the global array, hence no accidental collective)."""
        out = {}
        for s in x.addressable_shards:
            start = s.index[0].start or 0
            if start not in out:
                out[start] = s.data[0]
        return out

    def _end_pass_cached_sharded(self, caches, plans) -> None:
        """Cached sharded end-of-pass: per shard, hits + admits update
        their cache slots with a device gather/scatter out of the stacked
        working set, and only cold + evicted rows come D2H into ONE
        globally-sorted write-back.  A fault at ``cache.admit`` degrades
        every shard to the full write-back with the census leaving the
        cache (rows route through the host exactly like cache-off)."""
        from paddlebox_tpu import telemetry
        from paddlebox_tpu.utils import faults

        w = self.conf.row_width
        empty_rows = np.empty((0, w + 1), np.float32)
        upds = None
        try:
            faults.inject("cache.admit")
            upds = [
                caches[i].plan_update(self._shard_keys[o], plans[i])
                for i, o in enumerate(self._local_pos)
            ]
        except faults.FaultInjected:
            telemetry.counter(
                "cache.admit_fallbacks",
                "cache admissions degraded to the full host write-back",
            ).inc()
        if upds is None:
            with _PASS.stage("d2h"):
                vals = local_view(self.values)
                g2 = local_view(self.g2sum)
            ks, vs = [], []
            with self._cache_lock:
                for i, o in enumerate(self._local_pos):
                    sk = self._shard_keys[o]
                    m = sk.shape[0]
                    if m:
                        ks.append(sk)
                        vs.append(np.concatenate(
                            [vals[i, :m], g2[i, :m, None]], axis=1
                        ))
                        caches[i].evict_keys(sk[plans[i].hit_mask])
                self._sorted_write_back(ks, vs)
            return
        # per-shard single-device views: indexing the GLOBAL arrays here
        # would dispatch per-rank-divergent computations on a multi-device
        # global array (each rank's cache plan differs), and would pull a
        # shard's rows off its own device
        vmap = self._local_shard_arrays(self.values)
        gmap = self._local_shard_arrays(self.g2sum)
        ks, vs = [], []
        n_evicted = 0
        for i, o in enumerate(self._local_pos):
            sk = self._shard_keys[o]
            plan, upd = plans[i], upds[i]
            if sk.shape[0] == 0:
                continue
            rows_at = functools.partial(
                _shard_rows_at, vmap[int(o)], gmap[int(o)]
            )
            victim_rows = empty_rows
            upd_pos = np.concatenate([plan.hit_pos, upd.admit_pos])
            if upd_pos.shape[0]:
                if upd.victim_slots.shape[0]:
                    with _PASS.stage("d2h"):
                        victim_rows = np.asarray(
                            caches[i].gather_rows(upd.victim_slots)
                        )
                with _PASS.stage("set_rows"):
                    caches[i].set_rows(
                        np.concatenate([plan.hit_slots, upd.admit_slots]),
                        rows_at(upd_pos),
                    )
            cold = empty_rows
            if upd.cold_pos.shape[0]:
                with _PASS.stage("d2h"):
                    cold = np.asarray(rows_at(upd.cold_pos))
            ks += [sk[upd.cold_pos], upd.victim_keys]
            vs += [cold, victim_rows]
            n_evicted += int(upd.victim_slots.shape[0])
        with self._cache_lock:
            for i in range(len(caches)):
                caches[i].commit_update(plans[i], upds[i])
            self._sorted_write_back(ks, vs)
        if n_evicted:
            telemetry.counter(
                "cache.evicted_rows",
                "rows evicted from the HBM cache (written back to the host)",
            ).inc(n_evicted)

    @_PASS.wrap("write_back")
    def _sorted_write_back(self, ks: list, vs: list) -> None:
        """One globally-sorted write-back from per-shard key/row pieces
        (shards partition the key space, so the concat is unique; the
        overlay's searchsorted reads and the bucketed merge both want
        sorted keys)."""
        ks = [k for k in ks if k.shape[0]]
        vs = [v for v in vs if v.shape[0]]
        if ks:
            from paddlebox_tpu import telemetry

            k = np.concatenate(ks)
            v = np.concatenate(vs)
            order = np.argsort(k, kind="stable")
            telemetry.counter(
                "pass.host_row_bytes_out",
                "embedding-row bytes written back device->host at "
                "end_pass (cold + evicted rows)",
            ).inc(v.nbytes)
            self._write_back(k[order], v[order])
        else:
            self._write_back(
                np.empty(0, np.uint64),
                np.empty((0, self.conf.row_width + 1), np.float32),
            )

    def end_pass(self) -> None:
        if not self._in_pass:
            raise RuntimeError("no pass in flight")
        # drop (never eagerly close) the native index: a prefetch producer
        # may still hold a reference — see SparseTable.end_pass
        self._census_index = None
        caches = self._caches()
        plans, self._cache_plans = self._cache_plans, None
        with stage_scope("pass.end"):
            if caches and plans is not None:
                self._end_pass_cached_sharded(caches, plans)
            else:
                with _PASS.stage("d2h"):
                    vals = local_view(self.values)  # [L, cap, W]
                    g2 = local_view(self.g2sum)  # [L, cap]
                ks, vs = [], []
                for i, o in enumerate(self._local_pos):
                    sk = self._shard_keys[o]
                    m = sk.shape[0]
                    if m:
                        ks.append(sk)
                        vs.append(np.concatenate(
                            [vals[i, :m], g2[i, :m, None]], axis=1))
                self._sorted_write_back(ks, vs)
        self.values = None
        self.g2sum = None
        # the hot block stays device-resident across passes — its rows
        # never transit the host here (that is the whole point); they are
        # now newer than the store until the next flush/demotion
        if self._hot_keys.shape[0]:
            self._hot_dirty = True
        self._shard_keys = None
        self._pass_keys = None
        self._pass_owner = None
        self._pass_row = None
        self._in_pass = False

    def pass_state_dict(self) -> dict:
        """Mid-pass snapshot over the stacked [n_shards, cap, W] layout.

        Multi-host: this process's shards only — checkpoints are per-process
        sharded, the reference's per-node SaveBase discipline."""
        if not self._in_pass:
            return self.state_dict()
        vals = local_view(self.values)
        g2 = local_view(self.g2sum)
        keys, rows = [], []
        for i, o in enumerate(self._local_pos):
            sk = self._shard_keys[o]
            m = sk.shape[0]
            if m:
                keys.append(sk)
                rows.append(np.concatenate([vals[i, :m], g2[i, :m, None]], axis=1))
        if self._hot_keys.shape[0] and self.hot_values is not None:
            # resident hot rows (this process's owned subset): absent from
            # both the cold working set and the store's recent write-backs,
            # so a mid-run snapshot without them would lose the hot tier
            m = self._hot_keys.shape[0]
            lv = np.asarray(local_view(self.hot_values)[0])
            lg = np.asarray(local_view(self.hot_g2sum)[0])
            hk = self._hot_keys
            hr = np.concatenate([lv[:m], lg[:m, None]], axis=1)
            if is_multiprocess():
                own = self._proc_of(
                    (hk % np.uint64(self.n_shards)).astype(np.int64),
                    self.n_shards,
                ) == jax.process_index()
                hk, hr = hk[own], hr[own]
            if hk.shape[0]:
                keys.append(hk)
                rows.append(hr)
        if not keys:
            return {
                "keys": np.empty(0, np.uint64),
                "values": np.empty((0, self.conf.row_width + 1), np.float32),
            }
        k = np.concatenate(keys)
        v = np.concatenate(rows)
        order = np.argsort(k)
        return {"keys": k[order], "values": v[order]}

    # -- planning --------------------------------------------------------- #
    @property
    def shard_capacity(self) -> int:
        return 0 if self.values is None else int(self.values.shape[1])

    @property
    def capacity(self) -> int:
        """Total working-set rows across shards (the inherited property would
        read the stacked leading axis and report n_shards)."""
        return self.shard_capacity * self.n_shards

    @property
    def dead_row(self) -> int:
        """In-shard dead-row index (what planning actually uses)."""
        return self.shard_capacity - 1

    def plan_batch(self, batch):  # pragma: no cover - guard
        raise TypeError(
            "ShardedSparseTable plans whole device groups: use "
            "plan_group([batch_per_device, ...]) with MultiChipTrainer "
            "(the single-chip plan_batch would index the stacked layout wrong)"
        )

    def plan_keys(self, keys, n_real):  # pragma: no cover - guard
        raise TypeError(
            "ShardedSparseTable plans whole device groups: use plan_group()"
        )

    def bucket_capacity(self, key_capacity: int) -> int:
        n = self.n_shards
        c = int(np.ceil(key_capacity * self.bucket_slack / n / 8.0)) * 8
        return min(key_capacity, max(c, 8))

    def plan_group(
        self,
        batches: Sequence[HostBatch],
        bucket_capacity: Optional[int] = None,
        gather=None,
        slot_lr_vec: Optional[np.ndarray] = None,
        n_slots: Optional[int] = None,
    ) -> ShardedBatchPlan:
        """Resolve one batch group (one batch per LOCAL device) into the
        stacked a2a plan.  All plan arrays carry this process's leading axis
        [L, ...]; multi-host, the per-device request matrices are allgathered
        (collective #2) so each local shard knows every requester's rows.

        Bucket capacity is exact-fit, never lossy: each group's worst
        per-shard occupancy is computed first (plus a tiny scalar allgather
        for cross-process shape agreement) and the bucket grows in
        power-of-two steps above the base whenever a skewed group needs it —
        the reference never drops keys, so neither do we (the r3 design
        silently zero-filled overflowing keys).
        A capacity bump changes the feed shape and recompiles the step once
        per distinct capacity — amortized by the quantization.

        ``gather``: the allgather transport for the two planning
        collectives.  Defaults to multiprocess.host_allgather; the
        MultiChipTrainer's prefetch producer passes a host-plane KvChannel
        instead, because planning runs concurrently with the device step
        and must not enqueue device collectives (parallel/host_plane.py).

        ``slot_lr_vec`` + ``n_slots``: the per-slot LR map ([S] float32 from
        resolve_slot_lr_vec).  Each occurrence's slot lr is resolved here on
        the requester, packed bitwise next to the row id in the want matrix
        (so the existing allgather carries it — no extra collective), and
        folded into a per-served-unique-row lr vector (plan.serve_lr) during
        the serve dedup.  A key appearing in several slots takes the last
        assignment, matching the single-chip _host_batch_dict caveat.
        """
        gather = gather or host_allgather
        if not self._in_pass:
            raise RuntimeError("begin_pass before planning batches")
        if slot_lr_vec is not None and not n_slots:
            raise ValueError("slot_lr_vec needs n_slots to resolve "
                             "occurrence slots from key_segments")
        default_lr = float(self.conf.learning_rate)
        L = self.n_local
        if len(batches) != L:
            raise ValueError(
                f"need {L} batches (one per local device), got {len(batches)}"
            )
        K = batches[0].keys.shape[0]
        n = self.n_shards
        dead = self.shard_capacity - 1

        # pass 1 (capacity-independent): resolve per-device unique keys and
        # their worst per-shard occupancy
        per_dev: list = []
        needed = 0
        n_missing = 0
        ix = self._native_index()
        hot_res = self._hot_keys if self._hot_realize else None
        H = self.hot_block_capacity
        for b in batches:
            if b.n_keys == 0:
                per_dev.append(None)
                continue
            real = b.keys[: b.n_keys]
            out = ix.lookup_unique(real, b.n_keys) if ix is not None else None
            if out is not None:
                # native dedup+census lookup (first-seen slot order —
                # self-consistent within the plan, like the single-chip
                # planner; _native/plan_resolve.cpp)
                inv, uk, pos = out
                found = pos >= 0
                if self._pass_row.shape[0]:
                    rows = np.where(
                        found, self._pass_row[np.clip(pos, 0, None)], dead
                    ).astype(np.int32)
                else:  # empty census: nothing can be found
                    rows = np.full(uk.shape[0], dead, np.int32)
                owner = (uk % np.uint64(n)).astype(np.int64)
                miss = int((~found).sum())
            else:
                uk, inv = np.unique(real, return_inverse=True)
                rows, owner, miss = self._resolve_shard_rows(uk)
            if hot_res is not None and hot_res.shape[0]:
                hp = np.searchsorted(hot_res, uk)
                hp_c = np.minimum(hp, hot_res.shape[0] - 1)
                ishot = hot_res[hp_c] == uk
                # resident hot keys are excluded from the cold census by
                # construction, so both resolution branches above counted
                # them as missing — they are device-resident, not missing
                miss -= int(ishot.sum())
                # route hot occurrences into a VIRTUAL group n so they
                # neither consume cold slots nor inflate the bucket need;
                # cold ranks are unchanged (ranks are per-group)
                owner_v = np.where(ishot, np.int64(n), owner)
                slot = _rank_within_group(owner_v, n + 1)
            else:
                ishot = np.zeros(uk.shape[0], dtype=bool)
                hp_c = None
                slot = _rank_within_group(owner, n)
            n_missing += miss
            per_dev.append((b.n_keys, inv, rows, owner, slot, ishot, hp_c))
            cold_slot = slot[~ishot] if hp_c is not None else slot
            if cold_slot.shape[0]:
                needed = max(needed, int(cold_slot.max()) + 1)

        # capacity consensus: every process must build the same [L, n, C]
        # shape for the want allgather below, so agree on the max need first
        # (8 bytes per process — trivial next to the want matrix itself)
        needed = int(
            gather(np.asarray([needed], np.int64)).max()
        )
        # floor of 8: a K=0 local batch would give base 0 and 0*2 == 0
        # could never reach a peer's positive need
        base = max(bucket_capacity or self.bucket_capacity(K), 8)
        C = base
        while C < needed:
            C *= 2
        if C > base:
            self.capacity_bumps += 1

        want = np.full((L, n, C), dead, dtype=np.int32)
        want_lr = (
            None if slot_lr_vec is None
            else np.full((L, n, C), default_lr, dtype=np.float32)
        )
        occ = np.full((L, K), n * C, dtype=np.int32)
        mask = np.zeros((L, K), dtype=np.float32)
        # hybrid realization: every occurrence additionally carries a hot
        # slot (H = padded sink for cold/pad) and each referenced hot slot
        # its lr (0.0 where unreferenced on this device — the device-side
        # pmax fold across replicas recovers the true lr; a slot no device
        # references keeps lr 0.0 AND receives an exactly-zero gradient, so
        # the unconditional adagrad apply is a bitwise no-op for it).
        # Shapes depend only on the padded capacity H, never on the plan.
        hot_occ = hot_lr = None
        if self._hot_realize:
            hot_occ = np.full((L, K), H, dtype=np.int32)
            hot_lr = np.zeros((L, H), dtype=np.float32)
        n_overflow = 0  # structurally zero now; kept for API compatibility
        for d, resolved in enumerate(per_dev):
            if resolved is None:
                continue
            n_keys, inv, rows, owner, slot, ishot, hp_c = resolved
            cold = ~ishot
            want[d, owner[cold], slot[cold]] = rows[cold]
            occ[d, :n_keys] = np.where(
                ishot, n * C, owner * C + slot
            ).astype(np.int32)[inv]
            mask[d, :n_keys] = 1.0
            klr = None
            if want_lr is not None:
                # occurrence slot -> lr, merged per unique key (last wins —
                # keys never span slots in practice, same assumption as the
                # single-chip feed and the reference's slot-keyed pull)
                occ_lr = np.asarray(slot_lr_vec, np.float32)[
                    np.asarray(batches[d].key_segments[:n_keys]) % n_slots
                ]
                klr = np.full(rows.shape[0], default_lr, np.float32)
                klr[inv] = occ_lr
                want_lr[d, owner[cold], slot[cold]] = klr[cold]
            if hot_occ is not None and hp_c is not None:
                hot_occ[d, :n_keys] = np.where(
                    ishot, hp_c, H
                ).astype(np.int32)[inv]
                if ishot.any():
                    if klr is None:
                        klr = np.full(rows.shape[0], default_lr, np.float32)
                    hot_lr[d, hp_c[ishot]] = klr[ishot]
        # every requester's matrix, in mesh order (processes own contiguous
        # runs — asserted in __init__); single-process: want itself.  With an
        # LR map the float lrs travel bit-packed beside the row ids so the
        # multi-host path still pays exactly one want allgather.
        if want_lr is None:
            want_all = gather(want).reshape(n, n, C)
            lr_serve = None
        else:
            packed = np.concatenate(
                [want[..., None], want_lr.view(np.int32)[..., None]], axis=-1
            )  # [L, n, C, 2] int32
            packed_all = gather(packed).reshape(n, n, C, 2)
            want_all = np.ascontiguousarray(packed_all[..., 0])
            lr_all = np.ascontiguousarray(packed_all[..., 1]).view(np.float32)
            lr_serve = np.ascontiguousarray(
                lr_all[:, self._local_pos, :].transpose(1, 0, 2)
            )  # [L, n, C] — aligned with serve_rows
        # the serve side: local shard o serves want_all[:, o, :]; dedup rows
        # so the push-side optimizer touches each row once (dead row shares
        # one segment — it is scrubbed after every push anyway)
        serve_rows = np.ascontiguousarray(
            want_all[:, self._local_pos, :].transpose(1, 0, 2)
        )  # [L, n, C]
        serve_map = np.empty((L, n, C), dtype=np.int32)
        # padding tail: every slot gets its OWN scratch row (live + j), so
        # serve_uniq is unique by construction — uq itself is np.unique
        # output (at most one dead entry for census-missing keys) and the
        # scratch region is disjoint from live rows and dead.  The jitted
        # push claims unique_indices on this.  Slots past the provisioned
        # scratch clamp to the dead row; sharded_push_and_update zeroes
        # every dead-targeted delta before the scatter, so clamped
        # duplicates only write unchanged bytes (and the dead row is
        # scrubbed after every push anyway) — an under-provisioned scratch
        # region degrades, never crashes or corrupts.
        self._last_serve_n = max(self._last_serve_n, n * C)
        serve_uniq = np.minimum(
            self._shard_live[:, None]
            + np.arange(n * C, dtype=np.int32)[None, :],
            dead,
        )
        serve_lr = (
            None if lr_serve is None
            else np.full((L, n * C), default_lr, np.float32)
        )
        for o in range(L):
            out = None
            if ix is not None:  # same flag/availability as the request side
                from paddlebox_tpu._native import dedup_rows_native

                out = dedup_rows_native(serve_rows[o])
            if out is not None:
                inv, uq = out  # first-seen order: self-consistent, like
                # the request side (training-visible results unchanged)
            else:
                uq, inv = np.unique(
                    serve_rows[o].reshape(-1), return_inverse=True
                )
            serve_uniq[o, : uq.shape[0]] = uq
            serve_map[o] = inv.reshape(n, C).astype(np.int32)
            if serve_lr is not None:
                # fold per-request lrs onto the deduped rows: requesters of
                # the same row carry the same key, hence the same slot lr
                # (dead/pad rows may disagree — their deltas are zeroed in
                # sharded_push_and_update, so any value is benign)
                serve_lr[o][inv] = lr_serve[o].reshape(-1)
        self.missing_key_count += n_missing
        self.overflow_key_count += n_overflow
        return ShardedBatchPlan(
            serve_rows, occ, serve_map, serve_uniq, mask, n_missing,
            n_overflow, serve_lr, hot_occ, hot_lr,
        )

    def _resolve_shard_rows(self, uk: np.ndarray):
        """Owner shard + row-within-shard for sorted unique keys (dead row
        when absent from the pass census): one vectorized searchsorted into
        the begin_pass-precomputed (owner, row) map."""
        dead = self.shard_capacity - 1
        owner = (uk % np.uint64(self.n_shards)).astype(np.int64)
        npk = self._pass_keys.shape[0]
        if npk == 0:
            return np.full(uk.shape[0], dead, np.int32), owner, uk.shape[0]
        pos = np.searchsorted(self._pass_keys, uk)
        pos_c = np.minimum(pos, npk - 1)
        found = self._pass_keys[pos_c] == uk
        rows = np.where(found, self._pass_row[pos_c], dead).astype(np.int32)
        return rows, owner, int((~found).sum())


def _shard_rows_at(values, g2sum, pos: np.ndarray) -> jax.Array:
    """[len(pos), W+1] rows (values + g2sum column) of ONE shard's
    single-device arrays, gathered on that shard's device."""
    p = jax.device_put(pos, values.device)
    return jnp.concatenate([values[p], g2sum[p][:, None]], axis=1)


def _rank_within_group(group: np.ndarray, n_groups: int) -> np.ndarray:
    """rank_within_group([2,0,2,1]) -> [0,0,1,0]: occurrence index of each
    element within its group, preserving order."""
    order = np.argsort(group, kind="stable")
    sorted_g = group[order]
    starts = np.searchsorted(sorted_g, np.arange(n_groups))
    ranks = np.empty_like(group)
    ranks[order] = np.arange(group.shape[0]) - starts[sorted_g]
    return ranks
