"""Single-chip pass-scoped sparse embedding table.

TPU-native redesign of the BoxPS sparse PS core (reference:
fleet/box_wrapper_impl.h:24-255 PullSparseCase/PushSparseGradCase, pass
lifecycle box_wrapper.cc:609-673, persistence cc:1329-1460 — all backed by
the closed ``libbox_ps.so`` HBM hash table, SURVEY.md §2.7).

Design (SURVEY.md §7): instead of a device-side hash table, exploit the fact
that a pass's key census is known before training starts (the
BeginFeedPass/EndFeedPass trick, §3.4):

  * host store  — all features ever seen: sorted uint64 keys + value rows
    ``[show, clk, embed..., g2sum]`` (float32).  The CPU/SSD tier analog.
  * begin_pass(keys) — promote the pass working set to device: one dense
    ``values [P, W]`` array (P = padded capacity, last row = dead row held
    at zero) + ``g2sum [P]``.  New keys get uniform(-initial_range,
    initial_range) embeddings.  The HBM tier analog.
  * plan_batch(batch) — host-side key->row resolution: ``searchsorted`` into
    the sorted pass keys, plus batch dedup (np.unique) so push merges
    duplicate keys exactly like the reference's ``DedupKeysAndFillIdx`` +
    ``PushMergeCopy`` (box_wrapper.cu:457-1034), but on the host where
    dynamic shapes are free.  Everything handed to the device has a static
    shape: the occurrence side at the table's occurrence bucket
    (``_occ_slots``), which follows the real occurrences of the batches
    seen and not the key buffer's capacity, the unique side at the table's
    unique-slot bucket (``_uniq_slots``), which follows their distinct keys.
  * pull_rows / push_and_update — pure jittable functions: gather, and
    ONE segment-sum over the occurrences (merge_occurrences: the merged
    gradient with the show/clk increments in its counter columns) + sparse
    adagrad + one scatter-add of rows over the distinct keys.
  * end_pass() — write the working set back into the host store.

The dead row (index P-1) serves padding keys and keys missing from the pass
census: pulls read zeros (reference FLAGS_enable_pull_box_padding_zero), and
it is re-zeroed after every push so stray gradients cannot leak into it.
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from concurrent.futures import Future
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config import SparseTableConfig
from paddlebox_tpu.data.feed import HostBatch
from paddlebox_tpu.sparse.optimizer import sparse_adagrad_update
from paddlebox_tpu.telemetry import metrics as _tm
from paddlebox_tpu.telemetry.compiles import stage_scope
from paddlebox_tpu.utils.profiler import START, StatsProfiler

logger = logging.getLogger(__name__)

# the pass boundary by stage (pass.stage_seconds{stage=}, pbox.pass.<stage>
# on a running device trace): begin_pass = census / take_stage / alloc /
# lookup / fetch / upload / fill / touch, end_pass = pack / plan_update /
# d2h / set_rows / commit / write_back.  lookup, touch, plan_update and
# commit are timed inside HbmCache, where both tables call them.
_PASS = StatsProfiler("pass.stage_seconds")

# the plan's unique side (SparseTable._uniq_slots): keys / slots is its fill
_UNIQ_KEYS = _tm.counter(
    "plan.uniq_keys", "distinct keys of the planned batches (found or "
    "census-missing): the live slots of uniq_idx")
_UNIQ_SLOTS = _tm.counter(
    "plan.uniq_slots", "uniq_idx slots of the planned batches: the push's "
    "scatter indices")
_UNIQ_GROWS = _tm.counter(
    "plan.uniq_grows", "times a batch did not fit the table's unique-slot "
    "bucket and moved it (each is a new step shape)")
# the plan's occurrence side (SparseTable._occ_slots): keys / slots is its
# fill, 1.0 where every slot of the key buffer is a real occurrence
_OCC_KEYS = _tm.counter(
    "plan.occ_keys", "real key occurrences of the planned batches "
    "(HostBatch.n_keys)")
_OCC_SLOTS = _tm.counter(
    "plan.occ_slots", "occurrence slots of the planned batches: the length "
    "of idx, inverse, key_mask and of every occurrence-sized feed leaf")
_OCC_GROWS = _tm.counter(
    "plan.occ_grows", "times a batch's occurrences did not fit the table's "
    "occurrence bucket and moved it (each is a new step shape)")


_RESORTED = _tm.counter(
    "pass.census_resorted", "censuses handed to begin_pass / prepare_pass "
    "that were not ascending and distinct and went through np.unique")


def sorted_census(pass_keys) -> np.ndarray:
    """A pass's census as the table holds it: uint64, ascending, distinct.
    A dataset's ``unique_keys()`` already is (it was sorted where the pass
    loaded), so one comparison of neighbours takes it as it is — the array
    itself, not a copy: the caller leaves it alone while the pass is open.
    Any other caller's keys are sorted and deduplicated here, as before."""
    pk = np.asarray(pass_keys, dtype=np.uint64).reshape(-1)
    if (pk[1:] > pk[:-1]).all():
        return pk
    _RESORTED.inc()
    return np.unique(pk)


def _count_begin(at: str, arrays) -> None:
    """Was the device still working when begin_pass got here?  ``is_ready``
    on the arrays the boundary's device work produces (the cache's rows
    after end_pass's set_rows; the pass buffer after upload and fill, before
    it is split into values and g2sum): a question, never a wait."""
    from paddlebox_tpu import telemetry

    if at == "begin_entry":
        telemetry.counter(
            "pass.begins", "begin_pass calls (pass.device_pending's base)"
        ).inc()
    if any(a is not None and not a.is_ready() for a in arrays):
        telemetry.counter(
            "pass.device_pending",
            "begin_pass calls that found (at=begin_entry) or left "
            "(at=begin_exit) boundary device work still running",
        ).inc(at=at)


class _SerialWorker:
    """One lazily-started daemon thread running submitted jobs FIFO.

    The pass-boundary pipeline needs strictly ordered background work
    (store merges must land in pass order), futures for the barrier sites,
    and daemon threads so a hang-injected merge can never wedge interpreter
    exit — a plain queue+thread gives all three where ThreadPoolExecutor
    gives none."""

    def __init__(self, name: str):
        self._name = name
        self._q: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def submit(self, fn, *args) -> Future:
        fut: Future = Future()
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name=self._name, daemon=True
                )
                self._thread.start()
        self._q.put((fut, fn, args))
        return fut

    def _run(self) -> None:
        while True:
            fut, fn, args = self._q.get()
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args))
            except BaseException as e:  # surfaced at the barrier sites
                fut.set_exception(e)


@dataclasses.dataclass
class BatchPlan:
    """Host-resolved device indices for one batch (all static shapes).

    The occurrence side is L long: the table's occurrence bucket
    (SparseTable._occ_slots), which follows the real occurrences of the
    batches planned so far, not the key buffer's capacity K (L <= K; the
    plan covers the buffer's first L slots, which hold every real
    occurrence).

    idx:      int32 [L] — table row per key occurrence (dead row for padding
              or keys absent from the pass census).
    uniq_idx: int32 [U] — scatter target per *unique* batch key.  U is the
              table's unique-slot bucket (SparseTable._uniq_slots): it
              follows the distinct keys of the batches planned so far, not
              the occurrence side (U <= L).  Slots [0, n_uniq) hold the
              batch's distinct keys (live row, or the slot's scratch row
              for a census-missing key); every slot past them aims at its
              own scratch row.
    inverse:  int32 [L] — position of each occurrence in uniq_idx (padding
              occurrences point at slot U-1, which is never a key's unless
              the plan has no padding).
    key_mask: float32 [L] — 1.0 for real key occurrences.
    n_missing: keys that were not in the pass census (observability).
    n_uniq:   distinct keys of the batch, found or missing (the slots in
              use; plan.uniq_keys / plan.uniq_slots is the fill).
    """

    idx: np.ndarray
    uniq_idx: np.ndarray
    inverse: np.ndarray
    key_mask: np.ndarray
    n_missing: int = 0
    n_uniq: int = 0


def _next_pow2(n: int) -> int:
    return 1 << max(10, (n - 1).bit_length())


def _occ_bucket(n_real: int) -> int:
    """The occurrence bucket a batch of ``n_real`` occurrences asks for: a
    sixteenth of headroom, rounded up to a sixteenth of the count's power
    of two (1,024 at least, so tiles stay whole).  A batch's occurrences
    are a sum over its instances and steady to a fraction of a percent, so
    the headroom is far smaller than the unique side's quarter and a power
    of two."""
    step = max(1024, _next_pow2(n_real) >> 4)
    return max(1, -(-(n_real + n_real // 16) // step)) * step


def _key_uniform(keys: np.ndarray, seed: int, n_cols: int, rng_range: float) -> np.ndarray:
    """Deterministic per-(key, seed, column) uniform(-range, range) init via a
    splitmix64 hash.  Independent of table sharding and of the order keys are
    first seen, so single-chip and key-sharded multi-chip tables initialize
    any feature identically (and a rebuilt table reproduces a lost one)."""
    from paddlebox_tpu.sparse.store import _MIX_1, _MIX_2, splitmix64

    with np.errstate(over="ignore"):
        x = (
            keys[:, None].astype(np.uint64)
            + np.uint64(seed + 1) * _MIX_1
            + np.arange(1, n_cols + 1, dtype=np.uint64)[None, :] * _MIX_2
        )
        z = splitmix64(x)
    u = (z >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))  # [0, 1)
    return ((u * 2.0 - 1.0) * rng_range).astype(np.float32)


class SparseTable:
    def __init__(self, conf: SparseTableConfig, seed: int = 0):
        from paddlebox_tpu.config import flags
        from paddlebox_tpu.sparse.store import BucketStore

        self.conf = conf
        self._seed = seed
        w = conf.row_width  # [show, clk, embed...(, expand...)]
        # host tier: bucketed store — pass-boundary merges update existing
        # rows in place and rebuild only buckets that got new keys, instead
        # of re-argsorting all features ever seen
        self._store = BucketStore(
            n_cols=w + 1,  # +g2sum
            n_buckets=conf.store_buckets,
            spill_dir=conf.store_spill_dir,
            max_resident=conf.store_max_resident,
            n_threads=conf.store_threads,
            recover_fn=self._recover_spill_bucket,
        )
        # durable cold tier (sparse/logstore.py): every pass-boundary merge
        # writes through to the crash-consistent log and commits a manifest
        # generation, so a killed process recovers its last committed merge
        # here at construction.  "" / PBOX_DURABLE_STORE=0 = off (the
        # pre-durability in-RAM lifecycle).
        self._log = None
        self._compact_worker: Optional[_SerialWorker] = None
        self._compact_future: Optional[Future] = None
        if conf.store_log_dir and flags.durable_store:
            from paddlebox_tpu.sparse.logstore import LogStore

            self._log = LogStore(
                conf.store_log_dir,
                n_cols=w + 1,
                n_buckets=conf.store_log_buckets,
                compact_threshold=conf.store_compact_threshold,
            )
            self._compact_worker = _SerialWorker("table-compact")
            if self._log.gen > 0:
                rk, rv = self._log.materialize()
                if rk.shape[0]:
                    self._store.load_bulk(rk, rv)
                    logger.info(
                        "durable log %s: recovered %d rows at gen %d",
                        conf.store_log_dir, rk.shape[0], self._log.gen,
                    )
        # pass-scoped device state
        self.values: Optional[jax.Array] = None  # [P, w]
        self.g2sum: Optional[jax.Array] = None  # [P]
        self._pass_keys: Optional[np.ndarray] = None  # sorted
        self._in_pass = False
        # delta tracking for SaveDelta-style incremental checkpoints
        self._delta_keys: list[np.ndarray] = []
        # unique-slot bucket of the plans (uniq_idx's length): a high-water
        # mark over the batches planned so far (_uniq_slots).  It also
        # sizes the next pass's scratch region (pass 1 falls back to
        # conf.plan_scratch_rows)
        self._plan_uniq_slots = 0
        # occurrence bucket of the plans (the length of idx, inverse,
        # key_mask): the same kind of mark over the batches' real
        # occurrences (_occ_slots).  The JOB's, not a dataset's: every
        # dataset a trainer cycles through meets one length
        self._plan_occ_slots = 0
        # native per-pass census hash index (lazily built on first plan;
        # borrows self._pass_keys, so it must drop with the pass)
        self._census_index = None
        # -- pass-boundary pipelining state ------------------------------- #
        # end_pass write-backs merge into the store on a background thread;
        # until a merge lands its (seq, keys, vals) entry sits in _overlay
        # so every read (_lookup_with_overlay) stays read-your-writes.
        # _patch_log additionally retains write-back snapshots while a
        # next-pass stage is pending, independent of merge completion —
        # begin_pass patches the staged buffer's census intersection from
        # them.  Checkpoint/shrink/state_dict barrier via flush().
        self._overlap = bool(
            conf.overlap_pass_boundary and flags.overlap_pass_boundary
        )
        self._overlay: list = []  # [(seq, keys sorted, vals [n, W+1])]
        self._overlay_lock = threading.Lock()
        self._wb_seq = 0
        self._merge_worker = _SerialWorker("table-merge")
        self._merge_futures: list = []
        self._merge_poisoned = False
        self._stage_worker = _SerialWorker("table-stage")
        self._stage_future: Optional[Future] = None
        self._patch_log: list = []  # write-backs newer than a pending stage
        self._last_end_t: Optional[float] = None
        # -- device-resident embedding engine (sparse/engine/) ------------ #
        # A persistent HBM hot-key cache above the per-pass working set:
        # begin_pass fetches only cache misses from the host store and
        # fills hits with a device gather (they never leave HBM); end_pass
        # updates resident rows in place, admits new hot keys (LFU with
        # aging) and writes back only cold + evicted rows.  Dirty rows
        # drain through _write_back at every flush() barrier, so
        # checkpoint/shrink/delta always see a coherent host store.
        # _cache_lock makes (directory, write-back log) mutations atomic
        # against the staging thread's snapshot.
        self._cache = None
        self._cache_tried = False
        self._cache_lock = threading.Lock()
        self._cache_plan = None
        self.last_cache_hits = 0  # of the last begin_pass
        self.last_cache_misses = 0  # == the begin-pass promotion patch rows
        # stats
        self.missing_key_count = 0

    # -- pass-boundary pipelining helpers --------------------------------- #
    @property
    def overlap_enabled(self) -> bool:
        """True when the overlapped pass lifecycle (async write-back +
        pre-promotion) is active on this table."""
        return self._overlap

    def _lookup_with_overlay(self, q: np.ndarray, entries=None):
        """Store lookup with pending write-backs layered on top (newest
        wins).  ``entries`` pins a snapshot of the overlay taken under the
        lock (the staging job's consistency point); None reads the current
        overlay.  An entry whose merge already landed is harmless to
        re-apply — it holds exactly the rows the store received."""
        if entries is None:
            with self._overlay_lock:
                entries = list(self._overlay)
        vals, found = self._store.lookup(q)
        n = q.shape[0]
        for _, ek, ev in entries:  # oldest -> newest: later passes win
            if not ek.shape[0] or not n:
                continue
            pos = np.searchsorted(ek, q)
            pos_c = np.minimum(pos, ek.shape[0] - 1)
            hit = ek[pos_c] == q
            if hit.any():
                vals[hit] = ev[pos_c[hit]]
                found |= hit
        return vals, found

    # -- device-resident cache helpers ------------------------------------ #
    def _get_cache(self):
        """Lazily build the persistent HBM hot-row cache (None when
        disabled via conf.hbm_cache_rows=0 or PBOX_HBM_CACHE=0).  Creation
        is double-checked under the cache lock: the staging thread's
        snapshot may race the first begin_pass here."""
        if not self._cache_tried:
            with self._cache_lock:
                if not self._cache_tried:
                    from paddlebox_tpu.config import flags

                    if self.conf.hbm_cache_rows > 0 and flags.hbm_cache:
                        from paddlebox_tpu.sparse.engine import HbmCache

                        self._cache = HbmCache(
                            self.conf.hbm_cache_rows,
                            self.conf.row_width + 1,
                            aging=self.conf.hbm_cache_aging,
                        )
                    self._cache_tried = True
        return self._cache

    def _caches(self) -> list:
        """Every cache this table owns (the sharded table overrides with
        its per-shard list)."""
        c = self._get_cache()
        return [c] if c is not None else []

    def health_stats(self) -> dict:
        """Cheap per-pass health snapshot for telemetry/health.py: O(1)
        gauges only — never the store's full finiteness scan.  The
        ``cache_hit_rate`` key is present only once the cache has served
        a pass (absent signals make the collapse rule skip, not fire)."""
        hits = int(self.last_cache_hits)
        misses = int(self.last_cache_misses)
        out = {
            "cache_hits": hits,
            "cache_misses": misses,
            # the begin-pass promotion patch is exactly the miss rows
            "promotion_patch_rows": misses,
            "merge_backlog": len(self._merge_futures),
            "overlay_entries": len(self._overlay),
            "missing_keys": int(self.missing_key_count),
            "store_rows": int(self._store.n),
            "store_resident_buckets": int(self._store.resident_buckets),
        }
        if hits + misses > 0:
            out["cache_hit_rate"] = hits / (hits + misses)
        caches = self._caches()
        if caches:
            out["cache_capacity"] = int(sum(c.capacity for c in caches))
            out["cache_resident"] = int(sum(c.resident for c in caches))
        return out

    def _cache_fetch_rows(self, miss: np.ndarray, _entries=None) -> np.ndarray:
        """Host-tier fetch of cache-MISS rows — the begin-pass promotion
        patch, now O(cold keys).  Chaos site ``cache.fetch``: a failure
        here must degrade to the full synchronous host resolve, never
        corrupt rows (the callers catch and call _cache_degrade).  Timed by
        its callers: ``pass.stage_seconds{stage=fetch}`` in ``begin_pass``,
        ``pass.promote_seconds`` on the staging thread."""
        from paddlebox_tpu.utils import faults

        faults.inject("cache.fetch")
        return self._resolve_or_init(miss, _entries=_entries)

    def _cache_degrade(self, pk: np.ndarray) -> None:
        """cache.fetch failed: push every dirty row to the host tier and
        drop the census keys from the cache, so the pass can run fully
        host-resolved (through the overlay) with zero stale rows."""
        self._drain_cache()
        caches = self._caches()
        with self._cache_lock:
            for c in caches:
                c.evict_keys(pk)

    def _drain_cache(self) -> None:
        """Route every dirty cache row through the write-back path (one
        globally-sorted merge across caches) so the host store becomes
        truth for all resident keys.  Part of the flush() barrier."""
        caches = self._caches()
        if not caches:
            return
        with self._cache_lock:
            ks, vs = [], []
            for c in caches:
                k, v = c.drain()
                if k.shape[0]:
                    ks.append(k)
                    vs.append(v)
            if not ks:
                return
            if len(ks) == 1:
                self._write_back(ks[0], vs[0])
            else:
                k = np.concatenate(ks)
                v = np.concatenate(vs)
                order = np.argsort(k, kind="stable")
                self._write_back(k[order], v[order])

    def _invalidate_caches(self) -> None:
        """Drop cache membership (no row movement) — required whenever the
        host store changes underneath: restore, apply_delta, shrink."""
        caches = self._caches()  # before the lock: creation takes it too
        with self._cache_lock:
            for c in caches:
                c.invalidate()

    def _write_back(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """Hand one pass's final rows to the host store: synchronous merge
        on the serial path, overlay + background merge when overlapped."""
        if keys.shape[0] == 0:
            self._last_end_t = time.monotonic()
            return
        if not self._overlap:
            self._merge_into_store(keys, vals)
            self._last_end_t = time.monotonic()
            return
        with self._overlay_lock:
            self._wb_seq += 1
            entry = (self._wb_seq, keys, vals)
            self._overlay.append(entry)
            if self._stage_future is not None:
                # a pending stage resolved BEFORE this write-back existed:
                # keep the snapshot for begin_pass's intersection patch
                self._patch_log.append(entry)
        self._merge_futures.append(
            self._merge_worker.submit(self._merge_job, entry)
        )
        self._last_end_t = time.monotonic()

    def _merge_job(self, entry) -> None:
        from paddlebox_tpu import telemetry
        from paddlebox_tpu.utils import faults

        seq, keys, vals = entry
        t0 = time.perf_counter()
        try:
            if self._merge_poisoned:
                # a previous pass's merge failed: merging THIS pass would
                # skip one in the store's layering and make overlay reads
                # stale-ordered — freeze the store at the last good pass
                # (entries keep accumulating in the overlay, so reads stay
                # correct; flush raises at the next barrier)
                raise RuntimeError(
                    "store merge disabled: an earlier pass write-back "
                    "failed (surfaced at flush)"
                )
            # chaos site: a hang/failure here is a slow or dying merge
            # thread — reads must stay correct via the overlay, barriers
            # must surface it
            faults.inject("store.merge")
            self._merge_into_store(keys, vals)
        except BaseException:
            self._merge_poisoned = True
            raise
        with self._overlay_lock:
            # merges run FIFO on one worker and a failure poisons the rest:
            # the oldest overlay entry is always ours
            head = self._overlay.pop(0)
            assert head[0] == seq, "merge completed out of order"
        telemetry.histogram(
            "store.merge_seconds",
            "background pass write-back merge wall time",
        ).observe(time.perf_counter() - t0)

    def flush(self) -> None:
        """Barrier on the pass-boundary pipeline: drain dirty device-cache
        rows into the write-back path, then wait for every pending
        background merge (re-raising the first failure).  Checkpointing
        (state_dict/delta_state_dict), shrink and load_state_dict call this
        so persisted state never misses an in-flight write-back OR a row
        that only ever lived in the HBM cache."""
        self._drain_cache()
        while self._merge_futures:
            self._merge_futures.pop(0).result()
        if self._log is not None:
            # merges commit per batch; this covers any straggler staging
            self._log.commit()

    def close(self) -> None:
        """Quiesce and retire background resources: barrier the
        write-back pipeline (flush), drop any staged next pass, and shut
        the host store's bucket pool down so its worker threads don't
        outlive the table across respawns.  The table remains usable —
        a later lookup simply respawns the pool — so callers may still
        checkpoint/publish after close()."""
        if self._in_pass:
            raise RuntimeError("end_pass (or abort_pass) before close")
        self._discard_stage()
        self.flush()
        if self._compact_future is not None:
            try:
                self._compact_future.result()
            except Exception:
                logger.warning(
                    "background log compaction failed at close", exc_info=True
                )
            self._compact_future = None
        if self._log is not None:
            self._log.close()
        self._store.close()

    def _discard_stage(self) -> None:
        """Drop any staged next-pass buffer (waiting for the job so no
        staging read can race a store mutation) and trim the patch log."""
        fut, self._stage_future = self._stage_future, None
        if fut is not None:
            try:
                fut.result()
            except Exception:
                # a failed stage has nothing to discard — but the staging
                # thread's failure must not evaporate silently
                logger.debug("discarded a failed background stage",
                             exc_info=True)
        with self._overlay_lock:
            self._patch_log = []

    def prepare_pass(self, pass_keys) -> None:
        """Stage the NEXT pass's working set in the background while the
        current pass still trains (the reference's BeginFeedPass background
        promote, box_wrapper.cc:609-659): census resolve against
        store+overlay, `_key_uniform` init for unseen keys, and the host
        buffer begin_pass will hand to jnp.asarray.  ``pass_keys`` may be
        the key array or a zero-arg callable returning it — a callable is
        evaluated on the staging thread, so a blocking census provider
        (e.g. dataset.wait_preload_done) stays off the critical path.
        No-op on a serial table.  begin_pass with a matching census
        consumes the stage and only patches rows the finishing pass also
        touched; any mismatch falls back to the synchronous resolve."""
        if not self._overlap:
            return
        self._discard_stage()
        self._stage_future = self._stage_worker.submit(
            self._stage_job, pass_keys
        )

    def staged_pass_keys(self) -> Optional[np.ndarray]:
        """Block until a pending stage finishes and return its census (the
        sorted unique keys begin_pass must be called with), or None when
        nothing is staged — drivers that let prepare_pass's callable
        consume a dataset preload read the census back from here."""
        if self._stage_future is None:
            return None
        return self._stage_future.result()[0]

    def _stage_cap(self, n_keys: int) -> int:
        scratch = self._plan_uniq_slots or self.conf.plan_scratch_rows
        return _next_pow2(n_keys + 1 + scratch)

    def _stage_snapshot(self):
        """Atomic (cache directories, overlay, write-back seq) snapshot for
        a staging job.  One lock pair — _cache_lock then _overlay_lock, the
        same order end_pass mutates under — guarantees the stage never
        pairs a pre-eviction directory with a post-eviction overlay (which
        would leave an evicted key's staged row a hole no patch covers)."""
        caches = self._caches()  # before the lock: creation takes it too
        with self._cache_lock:
            cache_keys = [c.snapshot_keys() for c in caches]
            with self._overlay_lock:
                return cache_keys, self._wb_seq, list(self._overlay)

    def _stage_resolve(self, pk: np.ndarray, out: np.ndarray, cache_keys,
                       entries) -> bool:
        """Fill ``out`` [n, W+1] for census ``pk`` on the staging thread:
        with a cache, resolve ONLY the keys absent from the snapshot
        directory (hits are filled from HBM at begin_pass; keys the
        finishing pass evicts are always written back, so the begin_pass
        patch covers the snapshot's staleness).  Returns False when the
        promotion fetch was fault-injected — the stage is then consumed as
        a discard and begin_pass falls back to its synchronous resolve."""
        from paddlebox_tpu.utils import faults

        if cache_keys is None:
            out[:] = self._resolve_or_init(pk, _entries=entries)
            return True
        from paddlebox_tpu.sparse.engine import HbmCache

        hit = HbmCache.hit_mask_in(cache_keys, pk)
        miss_pos = np.nonzero(~hit)[0]
        try:
            if miss_pos.shape[0]:
                out[miss_pos] = self._cache_fetch_rows(
                    pk[miss_pos], _entries=entries
                )
        except faults.FaultInjected:
            return False
        return True

    def _stage_job(self, pass_keys):
        from paddlebox_tpu import telemetry

        t0 = time.perf_counter()
        if callable(pass_keys):
            pass_keys = pass_keys()
        pk = sorted_census(pass_keys)
        cache_keys, stage_seq, entries = self._stage_snapshot()
        w = self.conf.row_width
        cap = self._stage_cap(pk.shape[0])
        vals = np.zeros((cap, w + 1), dtype=np.float32)
        ok = self._stage_resolve(
            pk, vals[: pk.shape[0]],
            cache_keys[0] if cache_keys else None, entries,
        )
        if not ok:
            return pk, None, stage_seq
        telemetry.histogram(
            "pass.promote_seconds",
            "background next-pass census resolve + init + staging wall time",
        ).observe(time.perf_counter() - t0)
        return pk, vals, stage_seq

    def _pop_stage(self):
        """Consume the pending stage: (payload, patches) where payload is
        the `_stage_job` result (payload[0] = staged census, payload[-1] =
        the stage's overlay consistency point) and patches are the
        write-back snapshots that landed after it — or (None, []) when
        nothing is staged."""
        from paddlebox_tpu.utils.monitor import stats

        fut, self._stage_future = self._stage_future, None
        if fut is None:
            return None, []
        try:
            payload = fut.result()
        except Exception:
            stats.add("pass.stage_discards")
            with self._overlay_lock:
                self._patch_log = []
            raise
        with self._overlay_lock:
            stage_seq = payload[-1]
            patches = [e for e in self._patch_log if e[0] > stage_seq]
            self._patch_log = []
        return payload, patches

    @staticmethod
    def _patch_rows(keys: np.ndarray, rows: np.ndarray, patches) -> None:
        """Overwrite ``rows`` (aligned with sorted ``keys``) with every
        patch entry's rows for keys they share — the host-side sorted
        intersect + row copy that makes a staged buffer current."""
        n = keys.shape[0]
        for _, ek, ev in patches:  # oldest -> newest
            if not ek.shape[0] or not n:
                continue
            pos = np.searchsorted(ek, keys)
            pos_c = np.minimum(pos, ek.shape[0] - 1)
            hit = ek[pos_c] == keys
            if hit.any():
                rows[hit] = ev[pos_c[hit]]

    def _take_stage(self, pk: np.ndarray, cap: int):
        """Consume a pending stage if it matches (census AND capacity);
        returns the patched [cap, W+1] host buffer or None.  Patch = for
        every write-back newer than the stage's consistency point, copy the
        rows of its census ∩ ``pk`` (host-side sorted intersect)."""
        from paddlebox_tpu.utils.monitor import stats

        payload, patches = self._pop_stage()
        if payload is None:
            return None
        spk, vals, _ = payload
        if vals is None:
            # the staging thread's promotion fetch was fault-injected
            # (site cache.fetch): consume the stage as a discard and let
            # begin_pass run its synchronous resolve
            stats.add("pass.stage_discards")
            return None
        if vals.shape[0] != cap or not np.array_equal(spk, pk):
            # census changed between staging and begin_pass (or the scratch
            # sizing moved): the stage is stale — resolve synchronously
            stats.add("pass.stage_discards")
            return None
        self._patch_rows(pk, vals[: pk.shape[0]], patches)
        return vals

    def _native_index(self):
        """Lazily built native census index for this pass (None when the
        native planner is off/unavailable).  Shared by the single-chip and
        sharded planners; reset (dropped, never eagerly freed) at every
        pass boundary."""
        from paddlebox_tpu.config import flags

        if not flags.use_native_planner:
            return None
        if self._census_index is None:
            from paddlebox_tpu._native import build_census_index

            self._census_index = build_census_index(self._pass_keys)
        return self._census_index

    # -- introspection --------------------------------------------------- #
    @property
    def n_features(self) -> int:
        self.flush()  # pending merges may still be inserting new keys
        return self._store.n

    @property
    def capacity(self) -> int:
        return 0 if self.values is None else int(self.values.shape[0])

    @property
    def dead_row(self) -> int:
        return self.capacity - 1

    # -- pass lifecycle --------------------------------------------------- #
    def _resolve_or_init(self, pk: np.ndarray, _entries=None) -> np.ndarray:
        """Rows for sorted unique keys ``pk``: fetched from the host store
        (with pending write-backs overlaid) when present, freshly
        initialized otherwise.  Returns [n, W+1]."""
        w = self.conf.row_width
        n = pk.shape[0]
        if not n:
            return np.zeros((0, w + 1), dtype=np.float32)
        vals, found = self._lookup_with_overlay(pk, _entries)
        n_new = int((~found).sum())
        if n_new and self._log is not None:
            # census disk-reject: the per-segment bloom + min-max filters
            # prove most unseen keys are on NO segment without a read —
            # only the maybes (bloom false positives, or rows the warm
            # tier genuinely lost) pay a disk lookup
            from paddlebox_tpu.utils.monitor import stats

            miss_idx = np.nonzero(~found)[0]
            maybe = self._log.might_contain(pk[miss_idx])
            stats.add("store.census_disk_rejects", int((~maybe).sum()))
            if maybe.any():
                lv, lf = self._log.lookup(pk[miss_idx[maybe]])
                if lf.any():
                    hit_idx = miss_idx[maybe][lf]
                    vals[hit_idx] = lv[lf]
                    found[hit_idx] = True
                    n_new -= int(lf.sum())
                    stats.add("store.census_log_hits", int(lf.sum()))
        if n_new:
            init = np.zeros((n_new, w + 1), dtype=np.float32)
            init[:, self.conf.cvm_offset : w] = _key_uniform(
                pk[~found], self._seed, w - self.conf.cvm_offset,
                self.conf.initial_range,
            )
            vals[~found] = init
        return vals

    def _observe_gap(self) -> None:
        """Record one pass-boundary device-idle gap (end_pass return ->
        begin_pass return) — the number the whole pipeline exists to
        shrink."""
        if self._last_end_t is None:
            return
        from paddlebox_tpu import telemetry

        telemetry.histogram(
            "pass.boundary_gap_seconds",
            "device-idle gap from end_pass return to begin_pass return",
        ).observe(time.monotonic() - self._last_end_t)
        self._last_end_t = None

    def _cache_plan_and_fill(self, cache, pk: np.ndarray, v: jax.Array,
                             plan=None):
        """Fill every hit position of the device buffer ``v`` [cap, W+1]
        straight from HBM (hits never touch the host), and record the
        pass's plan + hit-rate telemetry.  ``plan`` is the census as
        begin_pass already resolved it against the directory; the staged
        path has none and resolves here.  Returns (plan, v)."""
        from paddlebox_tpu import telemetry

        if plan is None:
            plan = cache.lookup(pk)
        if plan.n_hits:
            with _PASS.stage("fill"):
                v = v.at[jnp.asarray(plan.hit_pos)].set(
                    cache.gather_rows(plan.hit_slots)
                )
        cache.touch(plan)
        n = pk.shape[0]
        self.last_cache_hits = plan.n_hits
        self.last_cache_misses = n - plan.n_hits
        telemetry.gauge(
            "cache.hit_rate",
            "fraction of the pass census served from the HBM cache",
        ).set(plan.n_hits / max(n, 1))
        return plan, v

    @stage_scope("pass.begin")
    def begin_pass(self, pass_keys: np.ndarray) -> None:
        """Promote the pass working set to device (reference: EndFeedPass
        SSD->CPU->HBM promote + BeginPass, box_wrapper.cc:630-659).  When
        prepare_pass staged this census, the visible work is one
        intersection patch + jnp.asarray; with the HBM cache, the host
        only ever supplies the cache MISSES (the promotion patch) and hit
        rows are filled by a device gather."""
        if self._in_pass:
            raise RuntimeError("end_pass the previous pass first")
        from paddlebox_tpu import telemetry
        from paddlebox_tpu.utils import faults

        cache = self._get_cache()
        _count_begin("begin_entry", [cache.rows] if cache is not None else [])
        with _PASS.stage("census"):
            pk = sorted_census(pass_keys)
        w = self.conf.row_width
        # layout: [0, n) live rows | [n, cap-1) plan scratch | cap-1 dead.
        # Scratch rows give every padding/missing plan slot a distinct
        # scatter target (see SparseTableConfig.plan_scratch_rows).  Once a
        # plan has run, the plans' unique-slot bucket is the exact need;
        # pass 1 uses the config default (over-provisioning only rounds
        # into the same pow2 in the common case, and plan_keys degrades
        # gracefully if a later batch needs more).
        cap = self._stage_cap(pk.shape[0])
        n = pk.shape[0]
        with _PASS.stage("take_stage"):
            staged = self._take_stage(pk, cap)
        vals = staged
        # every census key resident in the row cache and nothing staged:
        # the host has no row to supply, so the pass buffer starts as
        # zeros ON the device -- no [cap, W+1] host buffer allocated and
        # uploaded only to be overwritten by the cache's fill (at rows
        # 2,307 floats wide that buffer is 302 MB a boundary)
        plan = (cache.lookup(pk)
                if vals is None and cache is not None else None)
        all_hit = plan is not None and plan.n_hits == n
        if vals is None and not all_hit:
            with _PASS.stage("alloc"):
                vals = np.zeros((cap, w + 1), dtype=np.float32)
            if cache is None:
                with _PASS.stage("fetch"):
                    vals[:n] = self._resolve_or_init(pk)
            else:
                try:
                    miss_pos = np.nonzero(~plan.hit_mask)[0]
                    if miss_pos.shape[0]:
                        with _PASS.stage("fetch"):
                            vals[miss_pos] = self._cache_fetch_rows(
                                pk[miss_pos])
                except faults.FaultInjected:
                    # degraded pass: dirty rows drain to the host tier,
                    # census keys leave the cache, full host resolve (the
                    # overlay makes the drained rows visible immediately)
                    telemetry.counter(
                        "cache.fetch_fallbacks",
                        "promotion fetches degraded to the full host resolve",
                    ).inc()
                    self._cache_degrade(pk)
                    cache = plan = None  # its directory moved
                    with _PASS.stage("fetch"):
                        vals[:n] = self._resolve_or_init(pk)
        if all_hit:
            with _PASS.stage("alloc"):  # on the device: nothing to upload
                v = jnp.zeros((cap, w + 1), jnp.float32)
        else:
            with _PASS.stage("upload"):
                v = jnp.asarray(vals)
        if cache is not None:
            # staged path included: current-miss positions carry staged
            # rows (+ write-back patches — evictions always write back),
            # current hits are overwritten from HBM here
            plan, v = self._cache_plan_and_fill(cache, pk, v, plan)
        # host-plane promotion volume (same counter both planes —
        # parallel/sharded_table.py): every census row the device could
        # not fill from its own HBM tier crossed host->device here
        n_hits = plan.n_hits if plan is not None else 0
        telemetry.counter(
            "pass.host_row_bytes_in",
            "embedding-row bytes promoted host->device at begin_pass "
            "(cache misses + cold materialization)",
        ).inc(max(n - n_hits, 0) * 4 * (w + 1))
        self._cache_plan = plan
        self.values = v[:, :w]
        self.g2sum = v[:, w]
        self._pass_keys = pk
        self._census_index = None  # stale: points at the previous census
        self._in_pass = True
        self._delta_keys.append(pk)
        self._observe_gap()
        # the uploaded and filled buffer, not the two slices taken of it a
        # moment ago: is the boundary's own device work still running?
        _count_begin("begin_exit", [
            v, cache.rows if cache is not None else None])

    def _cache_update_plan(self, cache, pk: np.ndarray, plan):
        """Admission/eviction decision for the finished pass — chaos site
        ``cache.admit``: a failure returns None and end_pass degrades to
        evicting the census from the cache + a full host write-back (rows
        route through the host tier exactly like the cache-off lifecycle,
        so nothing is lost or stale)."""
        from paddlebox_tpu import telemetry
        from paddlebox_tpu.utils import faults

        try:
            faults.inject("cache.admit")
            return cache.plan_update(pk, plan)
        except faults.FaultInjected:
            telemetry.counter(
                "cache.admit_fallbacks",
                "cache admissions degraded to the full host write-back",
            ).inc()
            return None

    def _end_pass_cached(self, cache, plan, pk: np.ndarray, n: int) -> None:
        """Cached end-of-pass: hits update their HBM slots in place, the
        hottest misses are admitted (evicting aged-out residents), and
        ONLY cold + evicted rows travel D2H into the host write-back.
        Evicted rows are written back even when clean so a pre-staged next
        pass can always be patched current from the write-back log."""
        from paddlebox_tpu import telemetry

        with _PASS.stage("pack"):
            full = jnp.concatenate(
                [self.values, self.g2sum[:, None]], axis=1)
        upd = self._cache_update_plan(cache, pk, plan)
        if upd is None:
            with _PASS.stage("d2h"):
                vals = np.asarray(full[:n])
            telemetry.counter(
                "pass.host_row_bytes_out",
                "embedding-row bytes written back device->host at "
                "end_pass (cold + evicted rows)",
            ).inc(vals.nbytes)
            with self._cache_lock:
                cache.evict_keys(pk[plan.hit_mask])
                with _PASS.stage("write_back"):
                    self._write_back(pk, vals)
            return
        upd_pos = np.concatenate([plan.hit_pos, upd.admit_pos])
        upd_slots = np.concatenate([plan.hit_slots, upd.admit_slots])
        with _PASS.stage("d2h"):
            victim_rows = (
                np.asarray(cache.gather_rows(upd.victim_slots))
                if upd.victim_slots.shape[0]
                else np.empty((0, cache.n_cols), np.float32)
            )
            cold_rows = (
                np.asarray(full[jnp.asarray(upd.cold_pos)])
                if upd.cold_pos.shape[0]
                else np.empty((0, cache.n_cols), np.float32)
            )
        if upd_slots.shape[0]:
            with _PASS.stage("set_rows"):
                cache.set_rows(upd_slots, full[jnp.asarray(upd_pos)])
        wb_keys = np.concatenate([pk[upd.cold_pos], upd.victim_keys])
        order = np.argsort(wb_keys, kind="stable")
        telemetry.counter(
            "pass.host_row_bytes_out",
            "embedding-row bytes written back device->host at "
            "end_pass (cold + evicted rows)",
        ).inc(cold_rows.nbytes + victim_rows.nbytes)
        with self._cache_lock:
            cache.commit_update(plan, upd)
            with _PASS.stage("write_back"):
                self._write_back(
                    wb_keys[order],
                    np.concatenate([cold_rows, victim_rows])[order],
                )
        if upd.victim_slots.shape[0]:
            telemetry.counter(
                "cache.evicted_rows",
                "rows evicted from the HBM cache (written back to the host)",
            ).inc(int(upd.victim_slots.shape[0]))

    def end_pass(self) -> None:
        """Write the working set back to the host store (reference: EndPass
        HBM->CPU/SSD write-back, box_wrapper.cc:660-673).  Overlapped
        tables only pay the D2H snapshot here; the store merge runs on the
        background thread (flush() is the barrier).  With the HBM cache,
        only cold + evicted rows come down — hits never leave the device
        (_end_pass_cached)."""
        if not self._in_pass:
            raise RuntimeError("no pass in flight")
        pk = self._pass_keys
        n = pk.shape[0]
        cache = self._get_cache()
        plan, self._cache_plan = self._cache_plan, None
        with stage_scope("pass.end"):
            if cache is not None and plan is not None and n:
                self._end_pass_cached(cache, plan, pk, n)
            else:
                from paddlebox_tpu import telemetry

                with _PASS.stage("d2h"):
                    vals = np.concatenate(
                        [np.asarray(self.values),
                         np.asarray(self.g2sum)[:, None]],
                        axis=1,
                    )[:n]
                telemetry.counter(
                    "pass.host_row_bytes_out",
                    "embedding-row bytes written back device->host at "
                    "end_pass (cold + evicted rows)",
                ).inc(vals.nbytes)
                with _PASS.stage("write_back"):
                    self._write_back(pk, vals)
        self.values = None
        self.g2sum = None
        # DROP the native index reference rather than eagerly closing it: a
        # feed-prefetch producer that outlived its 5s close() join may still
        # be inside resolve() holding its own reference — refcounting frees
        # the handle (CensusIndex.__del__) only after the last user is done,
        # where an eager close here would be a native use-after-free
        self._census_index = None
        self._pass_keys = None
        self._in_pass = False

    def abort_pass(self) -> None:
        """Discard the in-flight working set WITHOUT merging it back — the
        rollback path for a pass poisoned by non-finite updates
        (TrainerConfig.nan_policy="rollback").  The host store keeps the
        last completed pass's state; the aborted pass's delta-tracker entry
        (appended by begin_pass) is removed since nothing of it persisted.
        No-op when no pass is open."""
        if not self._in_pass:
            return
        self.values = None
        self.g2sum = None
        self._census_index = None  # dropped, not closed — see end_pass
        self._pass_keys = None
        self._in_pass = False
        # cache rows were never written by this pass (updates land only at
        # end_pass); begin_pass's frequency credit is metadata-only noise
        self._cache_plan = None
        if self._delta_keys:
            self._delta_keys.pop()

    def _merge_into_store(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """Write back rows for sorted unique ``keys`` (existing rows update
        in place; buckets with new keys rebuild — see sparse/store.py).
        With a durable log, the batch lands there FIRST and commits a
        manifest generation: a failure aborts before the warm tier sees the
        rows (clean abort), and a kill after commit replays them from the
        log at the next construction."""
        vals32 = np.asarray(vals, dtype=np.float32)
        if self._log is not None:
            self._log.append(keys, vals32)
            self._log.commit()
            self._maybe_compact_log()
        self._store.update(keys, vals32)

    def _recover_spill_bucket(self, b: int):
        """BucketStore corrupt-spill recovery source: rebuild bucket ``b``
        from the durable log's committed state (raises in the store when
        no log is configured)."""
        if self._log is None:
            raise RuntimeError(
                f"spill bucket {b} corrupt and no durable log configured"
            )
        lk, lv = self._log.materialize()
        mask = self._store._bucket_of(lk) == b
        return lk[mask], lv[mask]

    def _maybe_compact_log(self) -> None:
        """Kick background compaction (PR-5 _SerialWorker pattern) when any
        log bucket crossed the segment threshold.  One compaction in flight
        at a time; a failure is counted + logged, never fatal — the log
        stays correct uncompacted, only longer."""
        if self._log is None or not self._log.buckets_over_threshold():
            return
        fut = self._compact_future
        if fut is not None and not fut.done():
            return
        if fut is not None:
            exc = fut.exception()
            if exc is not None:
                from paddlebox_tpu.utils.monitor import stats

                stats.add("store.compact_failures")
                logger.warning("background log compaction failed: %s", exc)
        self._compact_future = self._compact_worker.submit(self._log.compact)

    # -- batch planning (host) ------------------------------------------- #
    def plan_batch(self, batch: HostBatch) -> BatchPlan:
        return self.plan_keys(batch.keys, batch.n_keys)

    def _occ_slots(self, n_real: int, K: int) -> int:
        """L, the length of a plan's occurrence side, for a batch of
        ``n_real`` real occurrences in a ``K``-slot key buffer.

        Gather, pooling and merge cost per occurrence slot, padding or not,
        so L follows the occurrences the table has seen in a batch and not
        the buffer's capacity.  Like U (_uniq_slots) it is the TABLE's
        high-water mark: every plan of a settled stream has one length
        (one compiled step).  A batch that does not fit moves the mark to
        ``_occ_bucket`` of its count, never past K, where every batch fits
        by construction (a buffer that is all real occurrences — a
        decoder's token buffer — plans at K as before)."""
        L = min(self._plan_occ_slots, K)
        if L < K and (n_real > L or L == 0):
            self._plan_occ_slots = max(
                self._plan_occ_slots, min(K, _occ_bucket(n_real)))
            L = min(self._plan_occ_slots, K)
            _OCC_GROWS.inc()
        return L

    def _uniq_slots(self, n_uniq: int, K: int) -> int:
        """U, the length of a plan's unique side, for a batch of ``n_uniq``
        distinct keys on a ``K``-slot occurrence side.

        The push costs per scatter index, not per byte, so U follows the
        distinct keys the table has seen in a batch and not the buffer's
        capacity.  It is the TABLE's high-water mark, never the batch's:
        every plan of a settled stream has one length (one compiled
        step).  A batch fits while its keys leave slot U-1
        to the padding occurrences; one that does not moves the mark to a
        power of two with a quarter of headroom — a count that sits on a
        power of two must not flip between two step shapes — and never
        past K, where every batch fits by construction."""
        U = min(self._plan_uniq_slots, K)
        if U < K and n_uniq > U - 1:
            self._plan_uniq_slots = max(
                self._plan_uniq_slots,
                min(K, _next_pow2(n_uniq + n_uniq // 4 + 1)))
            U = min(self._plan_uniq_slots, K)
            _UNIQ_GROWS.inc()
        return U

    def plan_keys(self, keys: np.ndarray, n_real: int) -> BatchPlan:
        """Resolve a padded key buffer to device row indices + dedup maps.

        The plan covers the buffer's first L slots (L = _occ_slots: every
        real occurrence, then padding up to the table's occurrence bucket,
        L <= K).  ``idx`` (the pull side, [L]) maps missing/padding
        occurrences to the dead row (reads zeros).  ``uniq_idx`` (the push
        side, [U] with U = _uniq_slots: the batch's distinct keys first,
        U <= L) maps every non-live slot to its OWN scratch row
        (scratch_base + slot), so push indices are unique by construction
        — push_and_update scatters with unique_indices=True and XLA never
        pays the duplicate-safe serial lowering.  Scratch rows are never
        pulled and never merged back."""
        if not self._in_pass:
            raise RuntimeError("begin_pass before planning batches")
        # both planners size the occurrence side by the buffer they get
        keys = keys[:self._occ_slots(n_real, keys.shape[0])]
        dead = self.dead_row
        scratch_base = self._pass_keys.shape[0]
        plan = self._plan_native(keys, n_real, dead, scratch_base)
        if plan is None:
            plan = self._plan_numpy(keys, n_real, dead, scratch_base)
        self.missing_key_count += plan.n_missing
        _OCC_KEYS.inc(n_real)
        _OCC_SLOTS.inc(keys.shape[0])
        _UNIQ_KEYS.inc(plan.n_uniq)
        _UNIQ_SLOTS.inc(plan.uniq_idx.shape[0])
        return plan

    def _plan_native(self, keys, n_real, dead, scratch_base):
        """C++ planner (_native/plan_resolve.cpp): a per-pass census hash
        index + one sort-free O(K) batch walk (first-seen slot order).
        Training results are BIT-identical to the numpy path — idx is
        order-free and the push permutes inverse/uniq_idx consistently —
        pinned by test_native_planner's e2e equality.  The walk is what
        counts the batch's distinct keys, so a batch that moves the mark
        is resolved a second time at the new length."""
        ix = self._native_index()
        if ix is None:
            return None
        K = keys.shape[0]
        U = min(self._plan_uniq_slots, K)
        out = ix.resolve(keys, n_real, dead, scratch_base, U)
        if out is not None:
            n_uniq = out[-1]
            fit = self._uniq_slots(n_uniq, K)
            if fit != U:
                out = ix.resolve(keys, n_real, dead, scratch_base, fit)
        return None if out is None else BatchPlan(*out)

    def _plan_numpy(self, keys, n_real, dead, scratch_base):
        K = keys.shape[0]
        idx = np.full(K, dead, dtype=np.int32)
        mask = np.zeros(K, dtype=np.float32)
        nu = n_missing = 0
        if n_real:
            uk, inv = np.unique(keys[:n_real], return_inverse=True)
            nu = uk.shape[0]
            npk = self._pass_keys.shape[0]
            pos_c = np.minimum(np.searchsorted(self._pass_keys, uk),
                               max(npk - 1, 0))
            found = (self._pass_keys[pos_c] == uk) if npk else np.zeros(nu, bool)
            n_missing = int((~found).sum())
        U = self._uniq_slots(nu, K)
        # slots beyond the provisioned scratch clamp to the dead row:
        # push_and_update zeroes every dead-targeted delta, so the clamped
        # duplicates only ever write unchanged bytes (real unique slots sit
        # at the front and win scratch rows first; clamped missing-key
        # grads were headed for the post-push dead-row scrub regardless)
        uniq_idx = np.minimum(
            scratch_base + np.arange(U, dtype=np.int32), dead
        )
        inverse = np.full(K, U - 1, dtype=np.int32)
        if n_real:
            # push target: live row when found, the slot's scratch row else
            uniq_idx[:nu] = np.where(found, pos_c, uniq_idx[:nu])
            idx[:n_real] = np.where(found, pos_c, dead).astype(np.int32)[inv]
            inverse[:n_real] = inv
            mask[:n_real] = 1.0
        return BatchPlan(idx, uniq_idx, inverse, mask, n_missing, nu)

    # -- maintenance (day boundary) --------------------------------------- #
    def shrink(self) -> int:
        """Decay show/clk and evict cold features (reference: ShrinkTable +
        per-day decay, box_wrapper.cc:496-499; semantics per SURVEY.md §7).
        Returns the number of evicted rows."""
        if self._in_pass:
            raise RuntimeError("shrink between passes, not inside one")
        # barrier + stage invalidation: the decay/evict must see every
        # pending write-back, and a staged next pass resolved pre-shrink
        # would resurrect undecayed rows
        self._discard_stage()
        if self.n_features == 0:  # n_features flushes merges + cache drain
            return 0
        evicted = self._store.decay_evict(
            decay_cols=2,  # show + clk
            decay=self.conf.show_decay_rate,
            threshold=self.conf.delete_threshold,
        )
        # cached rows pre-date the decay (they were drained, then the
        # store decayed/evicted): membership must drop so the next pass
        # re-reads the decayed rows from the store
        self._invalidate_caches()
        if self._log is not None:
            # the log must not resurrect decayed/evicted rows at recovery:
            # one rewrite generation replaces the chain with the shrunk
            # state (also the compaction that bounds recovery cost)
            lk, lv = self._store.materialize()
            self._log.rewrite(lk, lv)
        return evicted

    # -- persistence ------------------------------------------------------ #
    def state_dict(self) -> dict:
        """Materialized copy of the host store, globally key-sorted (a full
        copy: the bucketed store has no single contiguous array to view)."""
        if self._in_pass:
            raise RuntimeError("end_pass before checkpointing")
        self.flush()  # checkpoint barrier: no write-back may be in flight
        keys, vals = self._store.materialize()
        return {"keys": keys, "values": vals}

    @stage_scope("table.load")
    @START.wrap("table_load")
    def load_state_dict(self, state: dict) -> None:
        """Restore the host store from a checkpoint's rows
        (``start.table_load``; inside it ``store_sort`` and ``store_split``
        (``HostStore.load_bulk``), ``invalidate``, and ``log_rewrite``
        where a log is attached)."""
        self.flush()  # pending merges must not land on top of the restore
        self._discard_stage()  # a staged pass resolved pre-restore is stale
        self._store.load_bulk(
            np.asarray(state["keys"], dtype=np.uint64),
            np.asarray(state["values"], dtype=np.float32),
        )
        # every cached row is now stale relative to the restored store
        with START.stage("invalidate"):
            self._invalidate_caches()
        if self._log is not None:
            # re-sync the durable chain: recovery must reproduce the
            # restored state, not the pre-restore one
            with START.stage("log_rewrite"):
                lk, lv = self._store.materialize()
                self._log.rewrite(lk, lv)

    def pass_state_dict(self) -> dict:
        """Snapshot usable mid-pass: the live working set when a pass is
        open (for in-pass dump_param), the host store otherwise."""
        if not self._in_pass:
            return self.state_dict()
        n = self._pass_keys.shape[0]
        vals = np.concatenate(
            [np.asarray(self.values), np.asarray(self.g2sum)[:, None]], axis=1
        )[:n]
        return {"keys": self._pass_keys, "values": vals}

    def delta_state_dict(self) -> dict:
        """Rows touched since the last pop — SaveDelta's xbox-delta analog
        (reference: box_wrapper.cc:1411-1460)."""
        if self._in_pass:
            raise RuntimeError("end_pass before checkpointing")
        self.flush()  # checkpoint barrier (see state_dict)
        if not self._delta_keys:
            return {
                "keys": np.empty(0, np.uint64),
                "values": np.empty((0, self.conf.row_width + 1), np.float32),
            }
        dk = np.unique(np.concatenate(self._delta_keys))
        vals, found = self._store.lookup(dk)
        # evicted-since keys drop out of the delta
        return {"keys": dk[found], "values": vals[found]}

    def pop_delta(self) -> dict:
        state = self.delta_state_dict()
        self._delta_keys = []
        return state

    def clear_delta(self) -> None:
        """Reset the delta tracker (call only after a successful save)."""
        self._delta_keys = []

    def apply_delta(self, state: dict) -> None:
        keys = np.asarray(state["keys"], dtype=np.uint64)
        if keys.shape[0]:
            # order against in-flight write-backs, and drop any staged pass
            # that resolved before these rows existed
            self.flush()
            self._discard_stage()
            self._merge_into_store(keys, np.asarray(state["values"], np.float32))
            # delta rows may overwrite keys the cache holds — drop membership
            self._invalidate_caches()


# ------------------------------------------------------------------------- #
# Pure device functions (jit these, or call them inside a larger train_step)
# ------------------------------------------------------------------------- #
def scatter_add_rows(values: jax.Array, idx: jax.Array,
                     delta: jax.Array) -> jax.Array:
    """Row scatter-add that promises XLA the indices are distinct (the
    plan's scratch-row construction), which unlocks its parallel scatter
    lowering.

    Caveat on the promise (ADVICE r4): plan index vectors can still repeat
    DEAD-ROW entries (scratch-clamped pad slots and the census-missing
    sink).  Callers zero every dead-targeted delta before the scatter, so
    any lowering that races duplicate writes only ever writes identical
    (unchanged) bytes — the claim relies on that add-of-zero idempotence,
    which XLA's semantics leave formally undefined for non-unique
    indices."""
    return values.at[idx].add(delta, unique_indices=True)


def pull_rows(
    values: jax.Array,
    idx: jax.Array,
    create_threshold: float = 0.0,
    cvm_offset: int = 2,
    pull_embedx_scale: float = 1.0,
) -> jax.Array:
    """Gather pulled value rows [K, W] (reference: PullSparseCase +
    PullCopy kernels).  With create_threshold > 0, embeddings of rows whose
    show count is below it read as zero (feature admission: embedx is not
    materialized until the feature is frequent enough).
    pull_embedx_scale != 1 descales the embedx columns of a quantized table
    — but NOT the first embed column (embed_w), which the reference stores
    unquantized (pulled layout [show, click, embed_w, embedx...],
    SURVEY.md §2.6; FeaturePullValueGpuQuant, box_wrapper.cu:1223-1256)."""
    rows = jnp.take(values, idx, axis=0)
    if create_threshold > 0.0 or pull_embedx_scale != 1.0:
        embed = rows[..., cvm_offset:]
        if pull_embedx_scale != 1.0:
            embed = jnp.concatenate(
                [embed[..., :1], embed[..., 1:] * pull_embedx_scale], axis=-1
            )
        if create_threshold > 0.0:
            visible = (rows[..., 0:1] >= create_threshold).astype(rows.dtype)
            embed = embed * visible
        rows = jnp.concatenate([rows[..., :cvm_offset], embed], axis=-1)
    return rows


def merge_occurrences(
    row_grads: jax.Array,
    key_mask: jax.Array,
    key_clicks: jax.Array,
    key_extras: Optional[jax.Array],
    segment_ids: jax.Array,
    num_segments: int,
    cvm_offset: int,
    dtype,
) -> jax.Array:
    """The push's ONE reduction over a batch's occurrences: [K, W] ->
    [num_segments, W] with the counter increments (show, click, extras) in
    columns ``[:cvm_offset]`` and the merged gradient in ``[cvm_offset:]``.

    The first ``cvm_offset`` columns of ``row_grads`` are REPLACED by
    ``key_mask``, ``key_clicks`` and ``key_extras`` (zeros when absent), not
    added to: the counters ignore whatever gradient a loss gives show and
    click.  A scatter-add on the TPU is paid per index, so the counters ride
    the merge's index pass for free where a segment-sum of their own costs
    as much as the merge.  ``dtype`` is the table's: a narrower
    ``row_grads`` is widened to it, the counters are never cast down (whole
    numbers far below 2^24: their float32 sums are exact in any order).

    The replacement is a select by column, not a concatenate: XLA fuses
    the select into whatever produces ``row_grads`` and the operand costs
    no pass of its own, where slice + concatenate materialise a [K, 1]
    column in a 128-lane tile for each counter and the operand after them.
    """
    counters = [key_mask, key_clicks] + [
        jnp.zeros_like(key_mask) if key_extras is None else key_extras[:, j]
        for j in range(cvm_offset - 2)
    ]
    col = jax.lax.broadcasted_iota(jnp.int32, row_grads.shape, 1)
    occ = row_grads.astype(dtype)
    for j, counter in enumerate(counters):
        occ = jnp.where(col == j, counter.astype(dtype)[:, None], occ)
    return jax.ops.segment_sum(occ, segment_ids, num_segments=num_segments)


def push_and_update(
    values: jax.Array,
    g2sum: jax.Array,
    row_grads: jax.Array,
    plan_idx: jax.Array,
    plan_uniq_idx: jax.Array,
    plan_inverse: jax.Array,
    key_mask: jax.Array,
    key_clicks: jax.Array,
    conf: SparseTableConfig,
    key_extras: Optional[jax.Array] = None,
    uniq_lr: Optional[jax.Array] = None,
):
    """Merge per-occurrence gradients by unique key and apply the sparse
    optimizer + show/clk counter update (reference: PushSparseGradCase,
    box_wrapper_impl.h:165-255 — CopyForPush merge of duplicate keys +
    closed-lib optimizer; semantics per sparse/optimizer.py).

    row_grads: [K, W] cotangent of the pulled rows; its show/clk columns
        are replaced by the counters in the merge (merge_occurrences), so
        whatever a loss puts there never reaches the table.
    key_clicks: [K] click/label of each occurrence's instance (masked).
    key_extras: [K, cvm_offset - 2] extra counter increments per occurrence
        (e.g. conversion events for the conv layout's third counter,
        reference FeaturePushValueGpuConv); zeros when absent.
    uniq_lr: optional [U] per-unique-key learning rates (the BoxPS LR-map
        analog: the Trainer resolves each key's slot-group lr host-side,
        reference box_wrapper.h:631 GetLRMap).  None = conf.learning_rate.
    Returns (values, g2sum) updated.  Both scatters claim the plan's
    targets are distinct (the plan_keys scratch-row construction
    guarantees it; see scatter_add_rows for the dead-row caveat).
    """
    del plan_idx  # pull-side only; kept in the signature for symmetry
    U = plan_uniq_idx.shape[0]
    co = conf.cvm_offset
    # merge duplicate keys, counters in the first co columns: [K, W] -> [U, W]
    merged = merge_occurrences(
        row_grads, key_mask, key_clicks, key_extras, plan_inverse, U, co,
        values.dtype,
    )
    # sparse adagrad on the embedding columns
    g2_rows = jnp.take(g2sum, plan_uniq_idx)
    lr = conf.learning_rate if uniq_lr is None else uniq_lr
    w_delta, g2_delta = sparse_adagrad_update(
        g2_rows, merged[:, co:], lr, conf.initial_g2sum, conf.grad_clip,
    )
    delta = jnp.concatenate([merged[:, :co], w_delta], axis=1)
    # plan_uniq_idx targets are unique EXCEPT possibly repeated dead-row
    # entries (slots the plan clamped when the scratch region was
    # under-provisioned — plan_keys).  Zero every dead-targeted delta so
    # duplicates only ever write unchanged bytes: dead-row gradients were
    # always discarded (the scrub below), so this changes no observable
    # state while keeping the unique_indices claim's duplicates benign
    # under any scatter lowering.
    dead = values.shape[0] - 1
    ok = (plan_uniq_idx != dead).astype(delta.dtype)
    values = scatter_add_rows(values, plan_uniq_idx, delta * ok[:, None])
    g2sum = g2sum.at[plan_uniq_idx].add(g2_delta * ok, unique_indices=True)
    # the dead row must stay zero (pulls read it as the zero row)
    values = values.at[dead].set(0.0)
    g2sum = g2sum.at[dead].set(0.0)
    return values, g2sum
