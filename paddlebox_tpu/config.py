"""Configuration system.

The reference uses three config tiers (SURVEY.md §5.6): env-settable gflags
(paddle/fluid/platform/flags.cc), protobuf descriptors (data_feed.proto,
trainer_desc.proto), and an opaque BoxPS conf file. Here that collapses into
plain dataclasses plus a small env-var flag shim (`flags`).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Optional, Sequence


# --------------------------------------------------------------------------- #
# Flag shim — replaces gflags FLAGS_* (reference: platform/flags.cc).
# Flags are read from the environment as PBOX_<NAME>, with typed defaults.
# --------------------------------------------------------------------------- #
class _Flags:
    _DEFAULTS = {
        # reference: FLAGS_padbox_record_pool_max_size (flags.cc:478)
        "record_pool_max_size": 2_000_000,
        # reference: FLAGS_padbox_dataset_shuffle_thread_num (flags.cc:483)
        "dataset_shuffle_thread_num": 10,
        # reference: FLAGS_padbox_dataset_merge_thread_num
        "dataset_merge_thread_num": 10,
        # NOTE: the reference's FLAGS_enable_pullpush_dedup_keys (flags.cc:603)
        # has no flag here on purpose: batch dedup happens host-side in
        # SparseTable.plan_keys where np.unique is essentially free, so it is
        # unconditionally on — there is no faster no-dedup path to toggle to.
        # reference: FLAGS_check_nan_inf (boxps_worker.cc:575-581)
        "check_nan_inf": False,
        # reference: FLAGS_enable_pull_box_padding_zero (pull_box_sparse_op.h)
        "enable_pull_box_padding_zero": True,
        # use the native (C++/ctypes) slot parser when it builds; falls back
        # to the pure-Python parser automatically
        "use_native_parser": True,
        # use the native (C++/ctypes) batch planner (dedup + census
        # resolve, _native/plan_resolve.cpp) when it builds; numpy fallback
        "use_native_planner": True,
        # reference: FLAGS_padbox_auc_runner_mode (flags.cc:495)
        "auc_runner_mode": False,
        # preferred device compute dtype for dense towers
        "compute_dtype": "float32",
        # unified retry/backoff defaults (utils/retry.py) — every transient-
        # failure site (hadoop commands, publish uploads, data reads) uses
        # these unless the caller passes an explicit RetryPolicy.  The
        # reference hard-codes equivalent knobs per site in fs.cc/fleet_util.
        "retry_max_attempts": 3,
        "retry_base_delay_s": 1.0,
        "retry_max_delay_s": 5.0,
        # fault-injection plan (utils/faults.py): ';'-separated
        # "site=spec" list, e.g. "fs.upload=first:2;data.read=p:0.01";
        # empty = no injection.  Seed makes probabilistic specs replayable.
        "fault_plan": "",
        "fault_seed": 0,
        # distributed-liveness defaults (parallel/watchdog.py): the stall
        # deadline bounds how long ANY stage (feed, step, host-plane
        # collective, shuffle) may go without progress before the watchdog
        # declares a stall; heartbeat/poll pace the per-process heartbeat
        # publisher and the detector loop.  The deadline default matches
        # the host-plane patience (first XLA compile / capacity-bump
        # recompile can legitimately stall a process that long).
        "liveness_deadline_s": 3600.0,
        "liveness_heartbeat_s": 15.0,
        "liveness_poll_s": 1.0,
        # host-plane KV-channel wait bound (KvChannel default timeout);
        # overrides TrainerConfig.host_plane_timeout_s when a LivenessConfig
        # is active
        "hostplane_timeout_s": 3600.0,
        # host-plane wire codec (parallel/host_plane.py + data/shuffle.py):
        # "varint" = framed zigzag-delta/sorted-delta LEB128 compression of
        # key and plan payloads (the default — want matrices and censuses
        # shrink 4x+); "raw" = framed, uncompressed; "legacy" = the
        # pre-codec bare-bytes wire for mixed-version fleets during a
        # rolling upgrade.  Must match on every rank: a framing mismatch
        # fails loudly (HostPlaneCodecError / CensusProtocolError), never
        # silently mis-decodes.
        "hostplane_codec": "varint",
        # sparsity-aware placement (sparse/placement.py +
        # parallel/census.py): "hybrid" = the planner classifies
        # replicated-hot vs hash-sharded cold keys from observed census
        # skew and the multi-host census exchange rides the shared
        # dictionary (hot keys cost one BIT on the wire); "hash" = the
        # flat key%n placement and full-key census wire (the ablation
        # baseline / kill switch); "loopback" = hybrid plus the
        # encode->decode wire path exercised even single-process (tests).
        "placement": "hybrid",
        # hybrid-placement device realization kill switch
        # (parallel/sharded_table.py): PBOX_PLACEMENT_REALIZE=0 keeps the
        # planner + census wire running but pins device row placement back
        # to pure hash-sharding (the PR-15 v1 lifecycle) regardless of
        # SparseTableConfig.placement_realize — the operational escape
        # hatch if the replicated-hot block misbehaves
        "placement_realize": True,
        # shuffle-transport wait bound (TcpShuffler default timeout)
        "shuffle_timeout_s": 120.0,
        # telemetry defaults (telemetry/): a non-zero metrics port starts
        # the per-process Prometheus /metrics listener (launch.py offsets
        # it per rank); trace_dir enables host span tracing (Chrome-trace
        # JSON per pass, Perfetto-viewable) on top of the jax device
        # trace; events_path appends a rank-tagged JSONL metrics/event
        # record per pass.
        "metrics_port": 0,
        "trace_dir": "",
        "events_path": "",
        # JSONL event-file rotation threshold in MB (streaming mode
        # appends forever; past this size the file shift-rotates to
        # .1/.2/... keeping the last few generations; 0 = never rotate)
        "events_max_mb": 64.0,
        # postmortem plane (telemetry/flight.py + tools/pbox_doctor.py):
        # flight_dir is where crash-time flight-recorder dumps land
        # ("" = fall back to the events_path directory, else no dumps;
        # the in-memory ring records regardless); flight_ring bounds the
        # per-process ring (recent spans/events kept for a dump)
        "flight_dir": "",
        "flight_ring": 512,
        # online model delivery (serving_sync/): the publish root a
        # trainer ships base/delta model units to (""= publishing off;
        # launch.py --publish-root sets it fleet-wide), and the serving-
        # side sync agent's donefile poll cadence / artifact cache dir
        "publish_root": "",
        "sync_interval_s": 10.0,
        "sync_cache_dir": "",
        # serving-fleet resilience (serving_fleet/ + inference/server.py).
        # serve_replicas > 0 switches `python -m paddlebox_tpu.serve` into
        # fleet mode: a ReplicaSupervisor spawns that many single-model
        # server processes and a FleetRouter front door spreads /score
        # traffic over them (health-checked, failover on replica death).
        "serve_replicas": 0,
        # port the fleet router binds (fleet mode only; 0 = ephemeral)
        "router_port": 8180,
        # admission control (every ScoringServer): max requests WAITING
        # for a scoring slot before new arrivals shed with 429 — bounds
        # queue memory and tail latency under overload (never unbounded
        # queuing into saturation)
        "serve_max_queue": 64,
        # scoring requests in flight at once (calibrated device batches;
        # >1 buys nothing single-chip — the device lock still serializes)
        "serve_max_concurrency": 1,
        # default per-request deadline (ms): arrivals whose ESTIMATED
        # queue wait exceeds it shed immediately with 429 + Retry-After
        # (clients override per request via X-Request-Deadline-Ms).
        # 0 = no deadline: shedding happens on queue_full only.
        "request_deadline_ms": 0,
        # largest accepted /score request body; beyond it the server
        # answers 413 without reading the payload
        "serve_max_body_bytes": 8 << 20,
        # continuous micro-batching at the admission gate: up to this many
        # queued /score requests coalesce into ONE padded-bucket device
        # call (dispatch cost amortizes across the queue).  1 = the
        # one-at-a-time legacy path and the ablation baseline
        # (PBOX_SERVE_MAX_BATCH=1)
        "serve_max_batch": 8,
        # how long a forming micro-batch may wait for more requests (ms)
        # before it cuts; an idle queue never waits — the linger only
        # spends latency when more traffic is demonstrably in flight
        "serve_batch_linger_ms": 2.0,
        # serving-artifact embedding payload dtype (export_serving_programs
        # / export_model): "fp32" | "int8" | "fp8".  Quantized artifacts
        # ship per-row scales and dequantize INSIDE the serving program's
        # gather, so fp32 rows never materialize host-side
        "embedding_dtype": "fp32",
        # fleet router health/freshness probe cadence per replica
        "fleet_probe_interval_s": 1.0,
        # elastic fleet (serving_fleet/autoscaler.py): autoscaler decision
        # cadence and the cooldown after ANY scale action before the next
        # may fire (hysteresis lives in the tick thresholds; the cooldown
        # is the flap-proofing backstop on top)
        "autoscale_interval_s": 2.0,
        "autoscale_cooldown_s": 30.0,
        # fleet size bounds the autoscaler may never cross in either
        # direction (min also floors the rolling-restart freshness gate:
        # a one-replica fleet can never roll without downtime)
        "autoscale_min_replicas": 1,
        "autoscale_max_replicas": 8,
        # pass-boundary pipelining kill switch (sparse/table.py): 0 forces
        # every table back to the serial end_pass/begin_pass lifecycle
        # regardless of SparseTableConfig.overlap_pass_boundary — the
        # operational escape hatch when an overlap bug is suspected
        "overlap_pass_boundary": True,
        # device-resident embedding engine kill switch (sparse/engine/):
        # PBOX_HBM_CACHE=0 disables the persistent HBM hot-key cache
        # process-wide regardless of SparseTableConfig.hbm_cache_rows —
        # every pass then round-trips its full working set through the
        # host store again (the pre-engine lifecycle, bit-exact by test)
        "hbm_cache": True,
        # streaming online learning (streaming/): the tail-source root a
        # StreamingTrainer follows ("" = streaming off; launch.py
        # --stream-root sets it fleet-wide), the freshness budget that
        # triggers publish_delta on a max-staleness DEADLINE rather than
        # pass cadence, and the mini-pass window size in records
        "stream_root": "",
        "max_staleness_s": 10.0,
        "stream_window_records": 1024,
        # durable cold tier kill switch (sparse/logstore.py):
        # PBOX_DURABLE_STORE=0 disables the crash-consistent log under
        # every table regardless of SparseTableConfig.store_log_dir —
        # the operational escape hatch if the log path misbehaves (the
        # table then runs the pre-durability in-RAM lifecycle)
        "durable_store": True,
        # run-health plane (telemetry/health.py): PBOX_HEALTH_ENABLED=0
        # silences the per-pass rule evaluation entirely (signals still
        # flow; nothing alerts); alpha is the EWMA smoothing factor the
        # z-score baselines use; warmup is how many windows a baseline
        # rule observes before it may fire (steady-state rules like the
        # recompile check wait the same count); max_alerts bounds the
        # in-process recent-alert ring /healthz serves
        "health_enabled": True,
        "health_ewma_alpha": 0.3,
        "health_warmup": 3,
        "health_max_alerts": 256,
    }

    def __getattr__(self, name: str):
        if name not in self._DEFAULTS:
            raise AttributeError(f"unknown flag {name!r}")
        default = self._DEFAULTS[name]
        env = os.environ.get("PBOX_" + name.upper())
        if env is None:
            return default
        if isinstance(default, bool):
            return env.lower() in ("1", "true", "yes", "on")
        return type(default)(env)

    def set(self, name: str, value) -> None:
        if name not in self._DEFAULTS:
            raise AttributeError(f"unknown flag {name!r}")
        os.environ["PBOX_" + name.upper()] = str(value)


flags = _Flags()


# --------------------------------------------------------------------------- #
# Slot / data-feed config — replaces data_feed.proto (reference:
# paddle/fluid/framework/data_feed.proto:17-38: Slot{name,type,is_dense,
# is_used,shape}, pipe_command, batch_size, pv_batch_size, rank_offset).
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class SlotConfig:
    """One feature slot.

    sparse slots hold uint64 feature signs (variable count per instance);
    dense slots hold a fixed-shape float vector.
    """

    name: str
    type: str = "uint64"  # "uint64" (sparse) | "float" (dense)
    is_dense: bool = False
    is_used: bool = True
    shape: Sequence[int] = (1,)

    def __post_init__(self):
        if self.type not in ("uint64", "float"):
            raise ValueError(f"slot {self.name}: bad type {self.type}")
        if self.is_dense and self.type != "float":
            raise ValueError(f"dense slot {self.name} must be float")
        if self.type == "float" and not self.is_dense:
            # variable-count float slots are not supported yet; requiring
            # is_dense keeps config and parser classification identical.
            raise ValueError(
                f"float slot {self.name} must be is_dense=True "
                "(variable-count float slots are unsupported)"
            )


@dataclasses.dataclass
class DataFeedConfig:
    """Reader configuration (DataFeedDesc equivalent)."""

    slots: Sequence[SlotConfig] = ()
    batch_size: int = 64
    pipe_command: str = ""  # optional shell preprocessor, like reference pipe_command
    pv_batch_size: int = 32  # page-view batches (PV merge mode)
    enable_pv_merge: bool = False
    rank_offset: str = ""  # name of the rank-offset tensor for rank_attention
    rank_offset_cols: int = 7  # reference: data_feed.cc max_rank 3 -> 7 cols
    # cmatch codes whose instances participate in PV ranking; None = all.
    # Default matches the reference kernel, which hard-codes ad channels
    # {222, 223} (data_feed.cu:219) — pass None explicitly to rank every
    # cmatch code.
    rank_cmatch_filter: Optional[Sequence[int]] = (222, 223)
    parse_ins_id: bool = False
    parse_logkey: bool = False  # search_id / rank / cmatch packed key
    label_slot: str = "click"  # float slot whose first value is the label
    # extra per-task label slots for multi-task models (reference: each task's
    # label is its own float slot, named per-metric in the MetricMsg config,
    # box_wrapper.cc:1222-1270).  Excluded from the dense feature matrix.
    task_label_slots: Sequence[str] = ()

    # ordered behavior-sequence slot (long-sequence models): this sparse
    # slot's per-instance keys are ALSO exposed as an ordered sequence —
    # HostBatch.seq_pos [B, max_seq_len] holds each instance's key-buffer
    # positions for it (padding = key capacity).  The slot still
    # participates in normal pooled features.  The reference has no
    # long-sequence path (SURVEY §5.7); this feeds the beyond-parity
    # sequence-parallel tower (models/longseq_ctr.py) and, as a token
    # stream, the decoder (models/decoder_lm.py: a model that declares
    # ``vocab_keys`` also gets each occurrence's class, data/feed.py
    # key_classes -- no config key: the vocabulary is the model's).
    sequence_slot: str = ""
    max_seq_len: int = 64

    # malformed-line policy (reference: the MultiSlot parser CHECKs and
    # aborts; production daily logs carry occasional corrupt lines, so the
    # trainer must be able to quarantine instead of dying):
    #   "raise" — any malformed line aborts the read (strict, the default)
    #   "skip"  — drop the line, count it (stats "data.quarantined_lines" /
    #             "data.quarantined_files"), keep parsing
    malformed_policy: str = "raise"
    # with malformed_policy="skip": abort the pass anyway when more than
    # this fraction of input lines was quarantined — pervasive corruption
    # is an upstream incident, not line noise to skip past
    quarantine_abort_frac: float = 0.01

    # fixed device-batch capacities (XLA static shapes): max total feasigns per
    # batch per sparse slot group.  Host feed pads/clips to these.
    max_feasigns_per_ins: int = 256
    # total key capacity of one device batch; None -> batch_size * max_feasigns_per_ins
    batch_key_capacity: Optional[int] = None

    @property
    def max_rank(self) -> int:
        return (self.rank_offset_cols - 1) // 2

    def to_dict(self) -> dict:
        """JSON-ready form (the artifact's feed.json): version-stamped,
        tuples as lists.  from_dict is the exact inverse."""
        d = dataclasses.asdict(self)
        d["slots"] = [
            {**sd, "shape": list(sd["shape"])} for sd in d["slots"]
        ]
        for k, v in list(d.items()):
            if isinstance(v, tuple):
                d[k] = list(v)
        d["feed_format_version"] = 1
        return d

    @staticmethod
    def from_dict(d: dict) -> "DataFeedConfig":
        """Inverse of to_dict.  Unknown keys (a NEWER exporter's fields)
        are dropped with a warning instead of crashing an older serving
        host; tuple-typed fields are restored by inspecting the dataclass
        defaults rather than a hand-maintained name list."""
        import warnings

        d = dict(d)
        ver = d.pop("feed_format_version", 1)
        if ver > 1:
            # a same-named field may have CHANGED meaning in a newer
            # format: unknown-key dropping can't catch that, so be loud
            warnings.warn(
                f"feed.json format version {ver} is newer than this "
                "serving host understands (1): existing fields may have "
                "changed semantics — upgrade before trusting scores",
                RuntimeWarning, stacklevel=2,
            )
        known = {f.name: f for f in dataclasses.fields(DataFeedConfig)}
        unknown = [k for k in d if k not in known]
        for k in unknown:
            warnings.warn(
                f"feed.json key {k!r} unknown to this version — ignored",
                RuntimeWarning, stacklevel=2,
            )
            d.pop(k)
        slot_known = {f.name for f in dataclasses.fields(SlotConfig)}
        slots = []
        for sd in d.get("slots", []):
            extra = [k for k in sd if k not in slot_known]
            for k in extra:
                warnings.warn(
                    f"feed.json slot key {k!r} unknown — ignored",
                    RuntimeWarning, stacklevel=2,
                )
            sd = {k: v for k, v in sd.items() if k in slot_known}
            slots.append(SlotConfig(**{**sd, "shape": tuple(sd["shape"])}))
        d["slots"] = slots
        for name, f in known.items():
            if name == "slots" or name not in d:
                continue
            if isinstance(f.default, tuple) and isinstance(d[name], list):
                d[name] = tuple(d[name])
        return DataFeedConfig(**d)

    def used_slots(self) -> list[SlotConfig]:
        return [s for s in self.slots if s.is_used]

    def sparse_slots(self) -> list[SlotConfig]:
        """Used uint64 slots, in file order.  Single source of truth for the
        sparse slot index used by the parser, batcher and slots_shuffle."""
        return [
            s
            for s in self.slots
            if s.is_used and s.type == "uint64" and s.name != self.label_slot
        ]

    def dense_slots(self) -> list[SlotConfig]:
        """Used dense float slots excluding label/task-label slots, in file
        order.  Matches the RecordBlock dense-matrix column layout exactly."""
        excluded = {self.label_slot, *self.task_label_slots}
        return [
            s
            for s in self.slots
            if s.is_used and s.is_dense and s.name not in excluded
        ]

    def dense_width(self) -> int:
        return sum(int(math.prod(s.shape)) for s in self.dense_slots())

    def __post_init__(self):
        if self.malformed_policy not in ("raise", "skip"):
            raise ValueError(
                f"malformed_policy must be 'raise' or 'skip', "
                f"got {self.malformed_policy!r}"
            )
        if not 0.0 <= self.quarantine_abort_frac <= 1.0:
            raise ValueError(
                "quarantine_abort_frac must be in [0, 1], "
                f"got {self.quarantine_abort_frac}"
            )
        seen = set()
        for s in self.slots:
            if s.name in seen:
                raise ValueError(f"duplicate slot name {s.name!r}")
            seen.add(s.name)
            if s.name == self.label_slot and s.type != "float":
                raise ValueError(
                    f"label slot {s.name!r} must be a float slot, "
                    f"got type={s.type!r}"
                )
        if self.slots and self.label_slot not in seen:
            raise ValueError(
                f"label slot {self.label_slot!r} is not among the configured "
                "slots; every instance must carry a label"
            )
        if len(set(self.task_label_slots)) != len(self.task_label_slots):
            raise ValueError("task_label_slots contains duplicates")
        by_name = {s.name: s for s in self.slots}
        for t in self.task_label_slots:
            if self.slots and t not in seen:
                raise ValueError(f"task label slot {t!r} is not configured")
            if self.slots and by_name[t].type != "float":
                raise ValueError(
                    f"task label slot {t!r} must be a float slot, "
                    f"got type={by_name[t].type!r}"
                )
            if t == self.label_slot:
                raise ValueError(
                    "task_label_slots must not repeat the primary label slot "
                    "(task 0 is the primary label implicitly)"
                )


# --------------------------------------------------------------------------- #
# Sparse table config — replaces the BoxPS side conf + embedding dims dispatch
# (reference: box_wrapper.cc:404-566 compile-time dims; box_wrapper.h:523-534
# feature types; the closed-lib optimizer semantics chosen per SURVEY.md §7).
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class SparseTableConfig:
    embedding_dim: int = 8  # embedx dim (excludes show/clk/embed_w companions)
    expand_dim: int = 0  # extended embedding (pull_box_extended_sparse)

    # sparse optimizer: adagrad with scalar g2sum (Baidu abacus-style)
    learning_rate: float = 0.05
    # per-slot learning-rate overrides: ((slot, lr), ...) — slots not listed
    # use `learning_rate`.  The BoxPS LR map analog (reference: GetLRMap/
    # SetLRMap, box_wrapper.h:631; per-param lr consumed by the PS update).
    # Works on both the single-chip Trainer and the sharded multi-chip path
    # (plan_group resolves slot lrs requester-side; see sharded_table.py).
    slot_learning_rates: Sequence = ()
    initial_g2sum: float = 3.0
    initial_range: float = 0.02  # uniform init range for new features
    # feature admission / eviction (reference: ShrinkTable semantics)
    create_threshold: float = 0.0  # min show count to materialize embedx
    delete_threshold: float = 0.0  # evict rows below this show at shrink
    show_decay_rate: float = 0.98  # per-day show/clk decay at shrink time
    # gradient clip per element
    grad_clip: float = 10.0

    # CVM companions stored per row ahead of the embedding: [show, clk]
    # (3 = conv layout [show, clk, conv]; 4+p = pcoc layout — SURVEY §2.6
    # feature-type dispatch, box_wrapper.h:523-534)
    cvm_offset: int = 2
    # quantized-table descale applied to embed columns at pull time
    # (reference: pull_embedx_scale_ in the FeaturePullValueGpuQuant copy
    # kernels, box_wrapper.cu:1223-1256).  1.0 = no-op (unquantized table).
    pull_embedx_scale: float = 1.0

    # host feature store (the CPU/SSD tier analog — reference: libbox_ps
    # SSD/CPU/HBM tiering, cmake/external/box_ps.cmake:17-63 and the
    # LoadSSD/ShrinkTable surface, box_wrapper.cc:1329-1460).  Keys are
    # hash-partitioned into power-of-two buckets (splitmix64 mix, so skewed
    # integer key spaces balance like hashed feasigns do); a
    # pass-boundary merge updates existing rows in place and rebuilds only
    # buckets that received NEW keys, so steady-state merge cost tracks the
    # pass size, not total features ever seen (sparse/store.py).
    store_buckets: int = 256
    # device-table scratch rows reserved past the pass working set, one per
    # slot of the plan's unique side (uniq_idx: the table's unique-slot
    # bucket, which follows the batches' distinct keys and is at most the
    # key buffer's capacity), so every padding/missing plan slot scatters
    # into its OWN row instead of all duplicating the dead row.  Push
    # indices are then unique by construction and the jitted push claims
    # unique_indices=True, unlocking XLA's parallel scatter lowering (the
    # serial duplicate-safe lowering is the sparse push's worst case on
    # TPU).  Used for PASS 1 only — later passes size the region exactly
    # from the observed plan (unique-slot bucket single-chip, serve buffer
    # sharded), so a mis-set default costs at most one extra pass-boundary
    # recompile, never correctness: slots past the region clamp to the
    # dead row and the push zeroes every dead-targeted delta before the
    # scatter (see plan_keys / push_and_update).
    plan_scratch_rows: int = 1 << 15
    # spill directory for cold buckets ("" = whole store stays in RAM).
    # With a spill dir, at most store_max_resident buckets are resident and
    # the rest live as .npz files — the SSD tier for stores beyond RAM.
    store_spill_dir: str = ""
    store_max_resident: int = 64
    # durable cold tier (sparse/logstore.py): directory of the
    # crash-consistent log-structured store under the warm tier ("" =
    # durability off, the pre-PR-17 in-RAM lifecycle).  Every pass-boundary
    # merge writes through to append-only checksummed segments and commits
    # a manifest generation, so the table recovers its last committed
    # merge after SIGKILL at any byte; census resolve consults per-segment
    # bloom/min-max filters before ever touching disk.  The process-wide
    # kill switch is PBOX_DURABLE_STORE=0.
    store_log_dir: str = ""
    # power-of-two bucket count of the durable log (independent of
    # store_buckets: segments are pass-granular, so fewer, larger buckets
    # keep file counts sane) and the per-bucket segment count beyond which
    # the background compactor folds a bucket to one newest-wins segment
    store_log_buckets: int = 8
    store_compact_threshold: int = 8

    # -- pass-boundary pipelining (sparse/table.py) ----------------------- #
    # Overlap the pass transition with device/host work: end_pass snapshots
    # the working set (D2H only) and merges into the host store on a
    # background thread (a pending-merge overlay keeps lookups
    # read-your-writes; checkpoint/shrink barrier on it), and prepare_pass
    # stages the NEXT pass's resolve + init + host buffer while the current
    # pass still trains (begin_pass then only patches the census
    # intersection from the finished pass and transfers).  The overlapped
    # lifecycle is bit-exact vs the serial one (pinned by
    # tests/test_pass_overlap.py).  False = the serial escape hatch; the
    # PBOX_OVERLAP_PASS_BOUNDARY=0 env flag forces serial process-wide.
    overlap_pass_boundary: bool = True
    # host-store bucket parallelism: lookup/update/decay_evict fan their
    # per-bucket work (independent by construction — hash-partitioned keys)
    # over this many threads with per-bucket locking.  <= 1 = serial.
    store_threads: int = 4

    # -- device-resident embedding engine (sparse/engine/) ---------------- #
    # Capacity (rows) of the persistent HBM hot-key cache that lives ABOVE
    # the per-pass working set: hot rows stay device-resident across
    # passes (LFU-with-aging admission from each census) and census
    # resolve fetches only cache MISSES from the host store, shrinking
    # the begin-pass promotion patch from O(working set) to O(cold keys)
    # — the reference's per-device BoxPS embedding cache (PAPER.md §2.7).
    # 0 disables; PBOX_HBM_CACHE=0 is the process-wide kill switch.  The
    # sharded table splits this capacity evenly across its shards.  The
    # cached lifecycle is bit-exact vs cache-off (tests/test_hbm_cache.py);
    # dirty rows drain to the host store at every checkpoint/shrink/delta
    # barrier, so persistence never sees a stale view.
    hbm_cache_rows: int = 1 << 16
    # per-pass frequency decay of the cache's LFU-with-aging policy: a
    # resident row untouched for k passes keeps freq * aging^k and becomes
    # evictable once that falls below a fresh candidate's 1.0
    hbm_cache_aging: float = 0.8

    # -- sparsity-aware placement (sparse/placement.py) ------------------- #
    # Per-variable placement chosen from observed access skew (Parallax /
    # Parameter Box): the planner classifies the top keys by aged census
    # frequency as replicated-hot, the tail stays hash-sharded.  The plan
    # drives the multi-host census wire (hot keys ride as membership bits
    # — parallel/census.py) AND, with placement_realize on, the device
    # data plane: the hot set is materialized as a replicated [H, W+1]
    # block on every device (parallel/sharded_table.py) so hot lookups are
    # a purely local gather with zero host-plane row bytes inside a pass.
    # "" resolves PBOX_PLACEMENT ("hybrid" default); "hash" disables.
    placement: str = ""
    # max replicated-hot keys the planner may classify (top-k bound); also
    # the padded capacity H of the realized device-resident hot block —
    # jit specializes on it once, never on the live plan (zero retrace
    # under plan churn)
    placement_hot_capacity: int = 4096
    # per-pass aged-frequency decay of the planner's tracker
    placement_aging: float = 0.8
    # hysteresis: the hot set mutates at most once per this many passes
    placement_update_interval: int = 2
    # realize the plan on device (replicated-hot / sharded-cold hybrid
    # layout).  False = the PR-15 v1 wire-only lifecycle: the planner and
    # census dictionary still run but rows stay hash-sharded end to end.
    # PBOX_PLACEMENT_REALIZE=0 is the process-wide kill switch.  The
    # realized lifecycle is bit-exact vs hash placement (pinned by
    # tests/test_placement.py): hot-gradient reduction is a
    # deterministic-order fold over the device axis, matching the cold
    # path's requester-major segment-sum order.
    placement_realize: bool = True

    @property
    def row_width(self) -> int:
        """Width of a pulled value row: [show, clk, embed...(, expand...)]."""
        return self.cvm_offset + self.embedding_dim + self.expand_dim


# --------------------------------------------------------------------------- #
# Distributed liveness — the watchdog/heartbeat/deadline policy
# (parallel/watchdog.py).  One config object bounds every wait in the
# system: local stage progress, peer heartbeats, host-plane KV gathers and
# the shuffle transport.  The reference has no equivalent (its MPI/NCCL
# collectives hang until an operator kills the job); parameter-server
# systems treat inter-worker liveness as first-class, and so does this.
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class LivenessConfig:
    """Deadlines and cadences for the distributed-liveness layer.

    deadline_s: a process (local check) or peer (heartbeat check) with no
    stage progress for this long is declared stalled.  Must comfortably
    exceed the longest legitimate stall (first XLA compile, capacity-bump
    recompile) — the default matches the host-plane patience.
    """

    enabled: bool = True
    deadline_s: float = 3600.0
    heartbeat_interval_s: float = 15.0
    poll_interval_s: float = 1.0
    # host-plane KV-channel wait bound (KvChannel default timeout)
    hostplane_timeout_s: float = 3600.0
    # shuffle-transport wait bound (TcpShuffler default timeout)
    shuffle_timeout_s: float = 120.0
    # on a stall abort, roll the process back to the newest valid
    # checkpoint (PR 1's find_valid_tag / PassRolledBack machinery) so no
    # partially-applied pass survives; requires trainer.checkpointer.
    # Trainer only: MultiChipTrainer ignores it (an abort there is the
    # driver's to recover: restart and resume from the newest valid tag)
    rollback_on_abort: bool = False
    # multi-process only: a thread blocked INSIDE a device collective
    # cannot be unwound from Python, so after an abort the watchdog gives
    # the process this long to exit cleanly and then hard-exits (code
    # 124) — the fleet converges even when one rank is wedged in XLA.
    # <= 0 disables (single-process runs never hard-exit).
    hard_exit_grace_s: float = 60.0

    @staticmethod
    def from_flags() -> "LivenessConfig":
        return LivenessConfig(
            deadline_s=flags.liveness_deadline_s,
            heartbeat_interval_s=flags.liveness_heartbeat_s,
            poll_interval_s=flags.liveness_poll_s,
            hostplane_timeout_s=flags.hostplane_timeout_s,
            shuffle_timeout_s=flags.shuffle_timeout_s,
        )

    def __post_init__(self):
        if self.deadline_s <= 0:
            raise ValueError("deadline_s must be positive")
        if self.heartbeat_interval_s <= 0 or self.poll_interval_s <= 0:
            raise ValueError("heartbeat/poll intervals must be positive")
        if self.heartbeat_interval_s >= self.deadline_s:
            raise ValueError(
                f"heartbeat_interval_s ({self.heartbeat_interval_s}) must be "
                f"< deadline_s ({self.deadline_s}) or every peer always "
                "looks stale"
            )


# --------------------------------------------------------------------------- #
# Telemetry — the observability policy (telemetry/): where metrics are
# served, where span traces and JSONL event records land, whether pass
# boundaries gather a merged cross-rank fleet view.  The reference spreads
# this across gflags (FLAGS_enable_binding_train_cpu etc.), monitor.h and
# per-worker profiler switches; here it is one attachable config with env
# flags (PBOX_METRICS_PORT / PBOX_TRACE_DIR / PBOX_EVENTS_PATH) so the
# launcher can switch a whole fleet on without code changes.
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Observability knobs for one process.

    metrics_port: serve Prometheus text exposition on
    ``127.0.0.1:<port>/metrics`` (0 = off).  Multi-process launches offset
    the port per rank (launch.py ``--metrics-port``).
    trace_dir: write per-pass host span traces (Chrome trace JSON) here
    ("" = off).  The trainers also point the jax device trace at their own
    ``TrainerConfig.trace_dir``; the two are separate captures.
    events_path: append rank-tagged JSONL event/metrics records here
    ("" = off).
    fleet_snapshot: multi-process only — gather every rank's metric
    snapshot at pass boundaries and log ONE merged fleet view on rank 0.
    """

    metrics_port: int = 0
    trace_dir: str = ""
    events_path: str = ""
    fleet_snapshot: bool = True

    @staticmethod
    def from_flags() -> "TelemetryConfig":
        return TelemetryConfig(
            metrics_port=flags.metrics_port,
            trace_dir=flags.trace_dir,
            events_path=flags.events_path,
        )

    def __post_init__(self):
        if self.metrics_port < 0 or self.metrics_port > 65535:
            raise ValueError(
                f"metrics_port must be in [0, 65535], got {self.metrics_port}"
            )


# --------------------------------------------------------------------------- #
# Streaming online learning — the policy object of paddlebox_tpu/streaming/:
# how records arrive (tail root / buffer bound), how mini-pass windows are
# cut (record count and/or wall-clock age), and the freshness budget the
# deadline publisher must honor.  The reference's production loop is
# continuous at PASS cadence (BoxHelper day/pass chains); this config is
# the second-level-freshness contract layered on top of it.
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class StreamingConfig:
    """Knobs for the streaming plane (source → mini-pass → deadline publish).

    max_staleness_s is the end-to-end freshness budget: the deadline
    publisher aims to have every event's effect PUBLISHED (and, with a
    serving confirmation wired, served) within this many seconds of the
    event entering the stream; misses are counted, never hidden
    (``stream.deadline_misses``).
    """

    # tailing file-set source root ("" = the caller supplies a source)
    stream_root: str = ""
    # freshness budget (s): publish_delta fires on this deadline
    max_staleness_s: float = 10.0
    # mini-pass window size in records (the scheduler may widen it under
    # publish backpressure, up to max_window_records)
    window_records: int = 1024
    # additionally cut a non-empty window once its oldest record is this
    # old (s); 0 = cut by record count only
    window_seconds: float = 1.0
    # bounded source buffer: past it the producer blocks (backpressure to
    # the tail poll / socket reader), nothing is dropped
    buffer_records: int = 1 << 16
    # tail-source poll cadence (s)
    tail_poll_interval_s: float = 0.05
    # windows staged ahead of training (census pre-computed); small — the
    # whole point is bounded lag, not deep pipelines
    max_pending_windows: int = 2
    # backpressure: window growth factor when publish lags/fails, and the
    # cap it may never exceed
    widen_factor: float = 2.0
    max_window_records: int = 1 << 20
    # fraction of the staleness budget spent accumulating before the
    # publisher triggers (the rest is headroom for publish + sync)
    trigger_fraction: float = 0.5
    # drain-and-checkpoint shutdown + periodic persistence: write an
    # AutoCheckpointer pass record every N windows (0 = only at shutdown)
    checkpoint_every_windows: int = 0

    @staticmethod
    def from_flags() -> "StreamingConfig":
        return StreamingConfig(
            stream_root=flags.stream_root,
            max_staleness_s=flags.max_staleness_s,
            window_records=flags.stream_window_records,
        )

    def __post_init__(self):
        if self.max_staleness_s <= 0:
            raise ValueError("max_staleness_s must be positive")
        if self.window_records < 1:
            raise ValueError("window_records must be >= 1")
        if self.window_seconds < 0:
            raise ValueError("window_seconds must be >= 0")
        if not 0 < self.trigger_fraction <= 1.0:
            raise ValueError("trigger_fraction must be in (0, 1]")
        if self.widen_factor < 1.0:
            raise ValueError("widen_factor must be >= 1")
        if self.max_window_records < self.window_records:
            raise ValueError(
                "max_window_records must be >= window_records"
            )
        if self.max_pending_windows < 1:
            raise ValueError("max_pending_windows must be >= 1")


# --------------------------------------------------------------------------- #
# Per-scenario serving policy (scenarios/ plane).  One scenario = one
# served model name; each picks its own artifact dtype, micro-batch
# linger, request deadline and freshness budget instead of inheriting
# server-wide knobs (a retrieval surface and a CTR surface have very
# different latency/freshness contracts over the same table).
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class ScenarioServingConfig:
    """Serving knobs for one scenario's model name.

    * ``embedding_dtype`` — publish-side: the artifact/delta transport
      dtype this scenario publishes (Publisher ``embedding_dtype=``);
    * ``batch_linger_ms`` — the coalescer linger for THIS model's
      micro-batches (None = the server-wide default; leaders are
      per-model so the override is exact);
    * ``deadline_ms`` — this model's default request deadline (the
      X-Request-Deadline-Ms header still outranks it; None/0 = server
      default);
    * ``max_staleness_s`` — the scenario's freshness budget when it runs
      through the streaming plane (DeadlinePublishPolicy).

    Attach request-path knobs with ``ScoringServer.set_serving_policy``.
    """

    name: str
    embedding_dtype: str = "fp32"
    batch_linger_ms: Optional[float] = None
    deadline_ms: Optional[float] = None
    max_staleness_s: Optional[float] = None

    def __post_init__(self):
        if self.embedding_dtype not in ("fp32", "int8", "fp8"):
            raise ValueError(
                f"embedding_dtype must be fp32|int8|fp8, got "
                f"{self.embedding_dtype!r}"
            )
        if self.batch_linger_ms is not None and self.batch_linger_ms < 0:
            raise ValueError("batch_linger_ms must be >= 0")
        if self.deadline_ms is not None and self.deadline_ms < 0:
            raise ValueError("deadline_ms must be >= 0")
        if self.max_staleness_s is not None and self.max_staleness_s <= 0:
            raise ValueError("max_staleness_s must be positive")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "ScenarioServingConfig":
        known = {f.name for f in dataclasses.fields(ScenarioServingConfig)}
        return ScenarioServingConfig(
            **{k: v for k, v in d.items() if k in known}
        )


# --------------------------------------------------------------------------- #
# Trainer config — replaces trainer_desc.proto (reference:
# trainer_desc.proto:21-66,100-108 BoxPSWorkerParameter).
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class TrainerConfig:
    # dense sync cadence: psum gradients every step (sync_dense_mode="step"),
    # average params every K steps ("kstep", reference DenseKStepNode), or
    # "async": psummed grads feed a CPU-hosted AsyncDenseTable whose
    # background thread applies the optimizer off the device critical path,
    # with params re-pulled every sync_weight_step steps (reference
    # BoxPSAsynDenseTable, boxps_worker.cc:37-297)
    sync_dense_mode: str = "step"
    sync_weight_step: int = 1
    # dense optimizer
    dense_lr: float = 1e-3
    dense_optimizer: str = "adam"
    # metrics
    auc_buckets: int = 1 << 20  # reference: 1M-bucket BasicAucCalculator
    # dump (reference: trainer dump_fields/dump_param)
    dump_fields: Sequence[str] = ()
    dump_fields_path: str = ""
    dump_param: Sequence[str] = ()
    need_dump_field: bool = False
    # Trainer only: MultiChipTrainer ignores need_dump_param (its params
    # are stacked a device and its table sharded; dump_params takes neither)
    need_dump_param: bool = False
    # task-label columns (indices into the batch's task_labels matrix, whose
    # col 0 is the primary label and cols 1.. are the configured
    # task_label_slots) that feed the extra CVM counters of a cvm_offset > 2
    # table: counter 2+i of each pushed key increments by
    # task_labels[:, counter_label_tasks[i]] of the key's instance.  The conv
    # layout's conversion counter (reference: FeaturePushValueGpuConv,
    # box_wrapper.cu PushCopy conv variants) is counter_label_tasks=(1,)
    # with task-label slot 0 holding the conversion event.
    counter_label_tasks: Sequence[int] = ()
    # dense-tower compute dtype: "" keeps the model's own setting (which
    # defaults to flags.compute_dtype / PBOX_COMPUTE_DTYPE); "bfloat16" is
    # the TPU AMP analog (params/accum stay f32) — reference:
    # meta_optimizers/amp_optimizer.py, SURVEY.md §2.9 "bf16 by default"
    compute_dtype: str = ""
    # nan check after each batch (reference: FLAGS_check_nan_inf)
    check_nan_inf: bool = False
    # what a non-finite loss/grad does to the pass (any value other than
    # "raise" implies the per-batch finiteness check even when
    # check_nan_inf is off):
    #   "raise"      — FloatingPointError aborts the pass (the reference's
    #                  FLAGS_check_nan_inf behavior)
    #   "skip_batch" — the offending batch's updates AND metric
    #                  contributions are discarded on-device (the step
    #                  returns the pre-batch state) and training continues;
    #                  counted to stats as train.nan_skipped_steps /
    #                  train.nan_skipped_ins
    #   "rollback"   — the pass aborts, and if an AutoCheckpointer is
    #                  attached (trainer.checkpointer) the table + dense
    #                  state are restored to the last completed pass;
    #                  train_from_dataset raises PassRolledBack so the
    #                  driver re-runs from there
    # Trainer only: MultiChipTrainer ignores nan_policy (its step has no
    # guarded form) — it knows check_nan_inf alone and raises a bare
    # FloatingPointError, the verdict psummed so every rank raises
    nan_policy: str = "raise"
    # device-feed double buffering: a background thread runs key planning +
    # host->device transfer for the next batches while the current step
    # computes, bounded at this queue depth (the pinned-arena/double-buffered
    # staging analog, SURVEY.md §2.3 — reference data_feed pipelines blocks
    # through SlotObjPool + a CUDA copy stream).  0 = serial feed.  Profiling
    # and tracing never change it: they report the loop that runs.
    prefetch_batches: int = 2
    # multi-host planning-plane patience: how long one host-plane KV
    # gather waits for a straggling peer (covers first-compile and
    # capacity-bump recompile stalls; the device collectives it replaced
    # waited indefinitely).  Superseded by liveness.hostplane_timeout_s
    # when a LivenessConfig is attached.
    host_plane_timeout_s: float = 3600.0
    # distributed-liveness policy (parallel/watchdog.py): None = no
    # watchdog (every wait still bounded by its own timeout, but no
    # heartbeats / stall attribution / coordinated abort).  Attach a
    # LivenessConfig to get per-process heartbeats, local+peer stall
    # detection naming the culprit, and poison-key coordinated abort.
    liveness: Optional["LivenessConfig"] = None
    # telemetry policy (telemetry/): None = flags only (PBOX_METRICS_PORT /
    # PBOX_TRACE_DIR / PBOX_EVENTS_PATH still apply through
    # TelemetryConfig.from_flags()); attach one to pin it in code.
    telemetry: Optional["TelemetryConfig"] = None
    # per-stage report (reference: TrainFilesWithProfiler): the pass's
    # metrics gain "profile" — per-stage seconds, counts, ms per step and
    # quantiles, the pass's delta of the always-on trainer.stage_seconds
    # and trainer.step_complete_seconds — and a [profile] line is printed.
    # The loop is the one every run has: no serial feed, no sync per step.
    profile: bool = False
    # jax.profiler trace dir for one-pass device timeline capture ("" = off):
    # the real loop, with the program's pbox.* stages on the trace's host
    # plane.  Also enables the HOST span trace: each pass additionally
    # writes a Chrome-trace JSON of the stage spans nested under "pass".
    trace_dir: str = ""
