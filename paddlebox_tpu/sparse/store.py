"""Bucketed host feature store — the CPU/SSD tier of the sparse table.

TPU-native replacement for the closed ``libbox_ps`` host store (reference:
cmake/external/box_ps.cmake:17-63 tiers 1e11 features over SSD/CPU/HBM;
LoadSSD / ShrinkTable surface, box_wrapper.cc:1329-1460).  The device tier
(per-pass HBM working set) lives in sparse/table.py; this class owns
everything below it.

Design: keys (uint64 feature signs) are partitioned into ``n_buckets``
(power of two) by a splitmix64 mix of the key — NOT raw high bits, so the
store balances for ANY key distribution (real feasigns are hashes, but
small integer ids must not collapse into one bucket).  Each bucket holds a
sorted key array + a row matrix.  The pass-boundary merge then has two
cost regimes:

  * keys already in the store (the steady state of CTR training) update
    their rows IN PLACE — O(u log b) searchsorted, no allocation;
  * buckets that received genuinely new keys are rebuilt with one sorted
    ``np.insert`` each — O(bucket), touching only those buckets.

This replaces the round-3 monolithic store whose every merge concatenated
and re-argsorted ALL features ever seen: O(N log N) host time and 2x peak
RAM per pass boundary at any store size.

Optional disk tier: with ``spill_dir`` set, at most ``max_resident``
buckets stay in RAM (LRU); the rest live as ``.npz`` files and reload on
access.  That bounds resident memory at ~max_resident/n_buckets of the
store, the SSD-tier analog for stores beyond RAM.

Parallelism: buckets are independent by construction (hash-partitioned key
spaces), so with ``n_threads > 1`` the per-bucket work of ``lookup`` /
``update`` / ``decay_evict`` fans out over a thread pool.  A per-bucket
lock serializes access to each bucket's arrays (the pass-boundary merge
thread, the next-pass staging thread and the caller may all touch the
store concurrently — sparse/table.py); the LRU/spill bookkeeping holds its
own lock and only ever *tries* a bucket lock (non-blocking) when evicting,
so the two lock orders cannot deadlock.
"""

from __future__ import annotations

import logging
import os
import threading
import zlib
from collections import OrderedDict
from typing import Callable, Iterator, Optional, Tuple

import numpy as np

from paddlebox_tpu.utils.monitor import stats
from paddlebox_tpu.utils.profiler import START

logger = logging.getLogger(__name__)

_EMPTY_KEYS = np.empty(0, dtype=np.uint64)


class StoreCorrupt(RuntimeError):
    """A spill file failed its integrity check and no recovery source is
    wired — raised loud instead of deserializing garbage rows."""


def _spill_crc(keys: np.ndarray, vals: np.ndarray) -> int:
    return zlib.crc32(
        np.ascontiguousarray(vals).tobytes(),
        zlib.crc32(np.ascontiguousarray(keys).tobytes()),
    )

# splitmix64 finalizer constants (public-domain mixing function)
_MIX_1 = np.uint64(0x9E3779B97F4A7C15)
_MIX_2 = np.uint64(0xBF58476D1CE4E5B9)
_MIX_3 = np.uint64(0x94D049BB133111EB)


def splitmix64(x: np.ndarray) -> np.ndarray:
    """The splitmix64 finalizer over a uint64 array — the single mixing
    function shared by bucket assignment (``_bucket_of``) and
    key-deterministic embedding init (sparse/table.py ``_key_uniform``)."""
    with np.errstate(over="ignore"):
        z = x + _MIX_1
        z = (z ^ (z >> np.uint64(30))) * _MIX_2
        z = (z ^ (z >> np.uint64(27))) * _MIX_3
        return z ^ (z >> np.uint64(31))


class BucketStore:
    def __init__(
        self,
        n_cols: int,
        n_buckets: int = 256,
        spill_dir: str = "",
        max_resident: int = 64,
        n_threads: int = 0,
        recover_fn: Optional[Callable[[int], Tuple[np.ndarray, np.ndarray]]] = None,
    ):
        if n_buckets & (n_buckets - 1) or n_buckets <= 0:
            raise ValueError(f"n_buckets must be a power of two, got {n_buckets}")
        self.n_cols = n_cols
        self.n_buckets = n_buckets
        self._shift = np.uint64(64 - (n_buckets.bit_length() - 1))
        self._keys: list[Optional[np.ndarray]] = [None] * n_buckets
        self._vals: list[Optional[np.ndarray]] = [None] * n_buckets
        self._counts = np.zeros(n_buckets, dtype=np.int64)
        self._spilled = np.zeros(n_buckets, dtype=bool)
        self.spill_dir = spill_dir
        self.max_resident = max(1, max_resident)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # corrupt-spill recovery source: called with the bucket id, returns
        # (keys, vals) rebuilt from a durable tier (the table wires this to
        # its logstore).  None = a corrupt spill raises StoreCorrupt.
        self._recover_fn = recover_fn
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)
        # bucket parallelism: per-bucket content locks + one LRU/spill lock
        # + one counter lock (see module docstring for the lock discipline)
        self.n_threads = max(int(n_threads), 0)
        self._locks = [threading.Lock() for _ in range(n_buckets)]
        self._lru_lock = threading.Lock()
        self._ctr_lock = threading.Lock()
        self._pool = None
        self._pool_lock = threading.Lock()
        # observability: pass-boundary merge behavior
        self.updated_in_place = 0  # keys whose rows were overwritten in place
        self.inserted = 0  # genuinely new keys
        self.buckets_rebuilt = 0  # buckets that had to reallocate
        self.spill_writes = 0
        self.spill_reads = 0

    # -- size -------------------------------------------------------------- #
    @property
    def n(self) -> int:
        return int(self._counts.sum())

    @property
    def resident_buckets(self) -> int:
        return sum(k is not None for k in self._keys)

    # -- bucket residency --------------------------------------------------- #
    def _path(self, b: int) -> str:
        return os.path.join(self.spill_dir, f"bucket_{b:05d}.npz")

    def _touch(self, b: int) -> None:
        if not self.spill_dir:
            return
        with self._lru_lock:
            self._lru[b] = None
            self._lru.move_to_end(b)
            while len(self._lru) > self.max_resident:
                old, _ = self._lru.popitem(last=False)
                if old == b:
                    # never evict the bucket being touched: the caller
                    # holds its lock and is mid-operation on its arrays
                    self._lru[old] = None
                    self._lru.move_to_end(old)
                    if len(self._lru) <= 1:
                        break
                    continue
                # bucket-lock -> lru-lock is the normal order; the evictor
                # holds lru-lock, so it may only TRY the victim's bucket
                # lock — a busy victim counts as recently used (deadlock-
                # free; residency becomes best-effort under contention)
                lk = self._locks[old]
                if lk.acquire(blocking=False):
                    try:
                        self._spill(old)
                    finally:
                        lk.release()
                else:
                    self._lru[old] = None
                    self._lru.move_to_end(old)
                    break

    def _spill(self, b: int) -> None:
        k = self._keys[b]
        if k is None:
            return
        if k.shape[0]:
            # checksum rides the file: _get verifies before trusting a row
            # (an unchecked spill deserializes disk corruption straight
            # into training state)
            np.savez(
                self._path(b), keys=k, vals=self._vals[b],
                crc=np.uint32(_spill_crc(k, self._vals[b])),
            )
            self._spilled[b] = True
            self.spill_writes += 1
        elif self._spilled[b]:
            # the bucket emptied (decay_evict) after an earlier spill: the
            # stale file would resurrect evicted rows at the next _get
            try:
                os.remove(self._path(b))
            except OSError:
                pass
            self._spilled[b] = False
        self._keys[b] = None
        self._vals[b] = None

    def _get(self, b: int) -> Tuple[np.ndarray, np.ndarray]:
        """Bucket arrays (loading from disk if spilled); marks MRU."""
        k = self._keys[b]
        if k is None:
            if self._spilled[b]:
                try:
                    with np.load(self._path(b)) as z:
                        sk = np.ascontiguousarray(z["keys"], dtype=np.uint64)
                        sv = np.ascontiguousarray(z["vals"], dtype=np.float32)
                        crc = int(z["crc"]) if "crc" in z.files else None
                    if crc is None:
                        # pre-checksum spill format: loadable, just
                        # unverifiable — warn instead of treating a valid
                        # legacy file as corruption (the next spill of
                        # this bucket rewrites it with a crc)
                        logger.warning(
                            "spill bucket %d: legacy file without "
                            "checksum, loaded unverified", b,
                        )
                    elif _spill_crc(sk, sv) != crc:
                        raise StoreCorrupt(
                            f"spill bucket {b}: checksum mismatch"
                        )
                except Exception as e:  # torn/garbled npz raises zoo-wide
                    stats.add("store.spill_corrupt")
                    logger.error("spill bucket %d failed verification: %s", b, e)
                    if self._recover_fn is None:
                        raise StoreCorrupt(
                            f"spill bucket {b} corrupt and no durable tier "
                            f"to recover from: {e}"
                        ) from e
                    sk, sv = self._recover_fn(b)
                    sk = np.ascontiguousarray(sk, dtype=np.uint64)
                    sv = np.ascontiguousarray(sv, dtype=np.float32)
                    stats.add("store.spill_recovered", int(sk.shape[0]))
                    self._counts[b] = sk.shape[0]
                self._keys[b] = sk
                self._vals[b] = sv
                self.spill_reads += 1
            else:
                self._keys[b] = _EMPTY_KEYS
                self._vals[b] = np.empty((0, self.n_cols), dtype=np.float32)
        self._touch(b)
        return self._keys[b], self._vals[b]

    def _set(self, b: int, keys: np.ndarray, vals: np.ndarray) -> None:
        self._keys[b] = keys
        self._vals[b] = vals
        self._counts[b] = keys.shape[0]
        self._touch(b)

    # -- query splitting ---------------------------------------------------- #
    def _bucket_of(self, q: np.ndarray) -> np.ndarray:
        """Bucket id per key: top bits of the splitmix64 mix, so skewed key
        spaces (small sequential ids) spread as evenly as hash feasigns."""
        if self.n_buckets == 1:
            # shift-by-64 is undefined for uint64 (x86 leaves the value
            # unchanged): one bucket means every key maps to bucket 0
            return np.zeros(q.shape[0], dtype=np.int64)
        return (splitmix64(q) >> self._shift).astype(np.int64)

    def _split(self, q: np.ndarray) -> Iterator[Tuple[int, np.ndarray]]:
        """Yield (bucket, positions-into-q) groups for sorted key array
        ``q``.  Positions are ascending within each group (stable sort), so
        ``q[idx]`` stays key-sorted per bucket."""
        if q.shape[0] == 0:
            return
        bids = self._bucket_of(q)
        order = np.argsort(bids, kind="stable")
        sb = bids[order]
        ub, starts = np.unique(sb, return_index=True)
        bounds = np.append(starts, q.shape[0])
        for j in range(ub.shape[0]):
            yield int(ub[j]), order[starts[j] : bounds[j + 1]]

    # -- parallel bucket dispatch ------------------------------------------- #
    def _run_buckets(self, tasks: list) -> list:
        """Run ``(bucket, thunk)`` tasks, each under its bucket's lock —
        thread-pooled when parallelism is on and there is more than one
        bucket to touch, serial otherwise.  Returns the thunk results in
        task order.  numpy releases the GIL inside the searchsorted/copy
        kernels, so independent buckets genuinely overlap."""

        def one(b, fn):
            with self._locks[b]:
                return fn()

        if self.n_threads > 1 and len(tasks) > 1:
            pool = self._pool
            if pool is None:
                from concurrent.futures import ThreadPoolExecutor

                with self._pool_lock:
                    if self._pool is None:
                        self._pool = ThreadPoolExecutor(
                            max_workers=self.n_threads,
                            thread_name_prefix="bucket-store",
                        )
                    pool = self._pool
            futs = [pool.submit(one, b, fn) for b, fn in tasks]
            return [f.result() for f in futs]
        return [one(b, fn) for b, fn in tasks]

    # -- core API ----------------------------------------------------------- #
    def lookup(self, q: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Rows for sorted unique uint64 keys ``q``.

        Returns (vals [n, n_cols] float32 — zero rows where missing,
        found bool [n])."""
        n = q.shape[0]
        out = np.zeros((n, self.n_cols), dtype=np.float32)
        found = np.zeros(n, dtype=bool)

        def work(b, idx):
            # each bucket's idx rows are disjoint: concurrent writes into
            # out/found never overlap
            bk, bv = self._get(b)
            if bk.shape[0] == 0:
                return
            sub = q[idx]
            pos = np.searchsorted(bk, sub)
            pos_c = np.minimum(pos, bk.shape[0] - 1)
            hit = bk[pos_c] == sub
            out[idx[hit]] = bv[pos_c[hit]]
            found[idx] = hit

        self._run_buckets(
            [(b, lambda b=b, idx=idx: work(b, idx)) for b, idx in self._split(q)]
        )
        return out, found

    def update(self, q: np.ndarray, vals: np.ndarray) -> None:
        """Overwrite/insert rows for sorted unique keys ``q`` (end-of-pass
        write-back).  Existing keys update in place; buckets receiving new
        keys are rebuilt with one sorted insert each."""
        # the sorted-insert merge below silently builds unsorted buckets
        # (= keys lost to every later searchsorted) on unsorted input, so
        # the contract is enforced loudly, not assumed
        if q.shape[0] > 1 and not bool(np.all(q[:-1] < q[1:])):
            raise ValueError(
                "BucketStore.update requires sorted unique keys"
            )

        def work(b, idx):
            bk, bv = self._get(b)
            sub, subv = q[idx], vals[idx]
            if bk.shape[0] == 0:
                self._set(b, sub.copy(), subv.astype(np.float32, copy=True))
                with self._ctr_lock:
                    self.inserted += sub.shape[0]
                    self.buckets_rebuilt += 1
                return
            pos = np.searchsorted(bk, sub)
            pos_c = np.minimum(pos, bk.shape[0] - 1)
            hit = bk[pos_c] == sub
            if hit.any():
                bv[pos_c[hit]] = subv[hit]
                with self._ctr_lock:
                    self.updated_in_place += int(hit.sum())
            miss = ~hit
            if miss.any():
                nk = sub[miss]
                nv = subv[miss]
                self._set(
                    b,
                    np.insert(bk, pos[miss], nk),
                    np.insert(bv, pos[miss], nv, axis=0),
                )
                with self._ctr_lock:
                    self.inserted += nk.shape[0]
                    self.buckets_rebuilt += 1

        self._run_buckets(
            [(b, lambda b=b, idx=idx: work(b, idx)) for b, idx in self._split(q)]
        )

    # -- maintenance -------------------------------------------------------- #
    def decay_evict(self, decay_cols: int, decay: float, threshold: float) -> int:
        """Decay the first ``decay_cols`` columns of every row and evict rows
        whose column 0 falls below ``threshold``.  Returns evicted count.
        (ShrinkTable semantics — touches every bucket, once per day, not per
        pass.)"""

        def work(b):
            bk, bv = self._get(b)
            bv[:, :decay_cols] *= decay
            if threshold <= 0.0:
                return 0
            keep = bv[:, 0] >= threshold
            ne = int((~keep).sum())
            if ne:
                self._set(b, bk[keep], bv[keep])
            return ne

        return sum(self._run_buckets(
            [(b, lambda b=b: work(b))
             for b in range(self.n_buckets) if self._counts[b]]
        ))

    # -- bulk / serialization ------------------------------------------------ #
    def close(self) -> None:
        """Retire the bucket-parallelism pool (its worker threads
        otherwise outlive the store across table respawns).  Safe to
        call at any quiesced point: ``_run_buckets`` lazily recreates
        the pool if the store is used again afterwards."""
        with self._pool_lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def clear(self) -> None:
        for b in range(self.n_buckets):
            if self._spilled[b]:
                try:
                    os.remove(self._path(b))
                except OSError:
                    pass
        self._keys = [None] * self.n_buckets
        self._vals = [None] * self.n_buckets
        self._counts[:] = 0
        self._spilled[:] = False
        self._lru.clear()

    def load_bulk(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """Replace the store content (checkpoint restore).  ``keys`` need not
        be sorted; duplicates keep the LAST occurrence.  Two stages of the
        ``start`` family: ``store_sort`` (the argsort, the gather of keys
        and rows, the duplicate test) and ``store_split`` (the buckets)."""
        self.clear()
        keys = np.asarray(keys, dtype=np.uint64)
        vals = np.asarray(vals, dtype=np.float32)
        with START.stage("store_sort"):
            if keys.shape[0]:
                order = np.argsort(keys, kind="stable")
                keys, vals = keys[order], vals[order]
                uniq, last_idx = np.unique(keys[::-1], return_index=True)
                if uniq.shape[0] != keys.shape[0]:
                    take = keys.shape[0] - 1 - last_idx  # last one wins
                    keys, vals = uniq, vals[take]
        with START.stage("store_split"):
            for b, idx in self._split(keys):
                self._set(b, keys[idx], vals[idx])

    def stats(self) -> dict:
        """Bucket-by-bucket size/finiteness report WITHOUT materializing a
        global copy (the pre-publish check must not be the thing that OOMs
        the day-loop host at 1e8+ features).  ``spilled_buckets`` /
        ``resident_rows`` report host-tier pressure (captured BEFORE the
        scan below faults spilled buckets back in): how much of the warm
        tier has fallen to disk and how many rows are actually RAM-held —
        the inputs to HBM-cache sizing."""
        spilled_buckets = int(self._spilled.sum())
        resident_rows = int(
            sum(
                int(self._counts[b])
                for b in range(self.n_buckets)
                if self._keys[b] is not None
            )
        )
        n_bytes = 0
        finite = True
        for b in range(self.n_buckets):
            if self._counts[b] == 0:
                continue
            with self._locks[b]:
                bk, bv = self._get(b)
                n_bytes += int(bk.nbytes + bv.nbytes)
                if finite:
                    finite = bool(np.isfinite(bv).all())
        return {
            "n": self.n,
            "bytes": n_bytes,
            "finite": finite,
            "spilled_buckets": spilled_buckets,
            "resident_rows": resident_rows,
        }

    def materialize(self) -> Tuple[np.ndarray, np.ndarray]:
        """Whole store as (keys, vals), globally key-sorted.  Hash bucketing
        interleaves key ranges across buckets, so this pays one full argsort
        — checkpoint-time cost only, never on the per-pass merge path."""
        ks, vs = [], []
        for b in range(self.n_buckets):
            if self._counts[b] == 0:
                continue
            with self._locks[b]:
                bk, bv = self._get(b)
                ks.append(bk)  # concatenate + argsort below already copy;
                vs.append(bv)  # result never aliases live buckets
        if not ks:
            return _EMPTY_KEYS, np.empty((0, self.n_cols), dtype=np.float32)
        keys = np.concatenate(ks)
        vals = np.concatenate(vs)
        order = np.argsort(keys, kind="stable")
        return keys[order], vals[order]
