"""HBM-resident hot-key row cache: the persistent device tier.

The reference's BoxPS core keeps each device's hot sparse working set in an
HBM hash table across passes (``pull_box_sparse``/``push_box_sparse``
against a per-device embedding cache, PAPER.md §2.7); this is the
TPU-native analog over the census-driven pass lifecycle: a fixed-capacity
slot table whose ROWS (``[capacity, W+1]`` — value columns + g2sum) live as
one JAX device array, with a host-side directory (keys, frequency/recency
metadata, dirty flags) deciding membership once per pass from the census.

Why the directory is host-side numpy while the rows are device-side JAX:
every key decision in this system (census resolve, batch planning, shard
routing) already happens on the host where dynamic shapes are free — the
directory is ~tens of bytes per slot and mutates once per pass, while the
rows are the multi-KB-per-slot payload whose round trip the cache exists to
eliminate.

Policy: LFU with aging.  Every pass ages all resident frequencies by
``aging`` and adds 1 to this census's hits; admission (at end_pass, from
the pass census) fills free slots first, then evicts the
lowest-(frequency, recency) resident slots not touched by the current pass
whose aged frequency has fallen below a fresh candidate's (1.0).  The
ageing is applied on READ: a boundary's directory work follows its census,
not the capacity.  The directory stores every frequency in units of
``aging ** ticks`` (``_freq`` = frequency × ``_unit``, where ``_unit`` grows
by 1/``aging`` a pass), so one scalar division ages every slot at once and
``touch`` writes only the census's hits; ``frequency()`` reads a slot's
value back, and the eviction test (< 1.0) compares the stored form with
``_unit`` itself.
Once in some hundreds of passes, before ``_unit`` leaves float64's range,
the whole array is rescaled by an exact power of two.  Eviction
and admission move only directory state here — the owning table moves the
rows (device scatter for admits, D2H + host write-back for evictions: an
evicted row is ALWAYS written back, dirty or not, so a pre-staged next
pass that believed the key was cache-resident can be patched from the
write-back log instead of reading a hole).

Coherence contract (enforced by sparse/table.py): rows newer than the host
store are marked ``dirty`` and must be drained (``drain()`` →
``_write_back``) before anything reads the store as truth — checkpoint
``state_dict``/``delta_state_dict``, ``n_features``, shrink, publish.
``invalidate()`` drops membership without moving rows and is required
whenever the store changes underneath the cache (restore, apply_delta,
shrink's decay).  Thread-safety is the caller's: the owning table wraps
directory mutation and its census-staging snapshot in one lock so a
background stage never sees a half-updated (directory, write-back log)
pair.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu import telemetry
from paddlebox_tpu._native import (
    DIRECTORY_STRIDE,
    cache_lookup_native,
    cache_touch_native,
)
from paddlebox_tpu.utils.profiler import StatsProfiler

# the directory's share of a pass boundary, by stage (lookup / touch /
# plan_update / commit): pass.stage_seconds, pbox.pass.<stage> on a trace
_PASS = StatsProfiler("pass.stage_seconds")

_LOOKUPS = telemetry.counter(
    "cache.lookups",
    "HbmCache.lookup calls by what resolved the census: form=native, one "
    "merge over the two sorted arrays in the planner's library; form=numpy, "
    "a binary search a key where that library is off or did not build")

_EMPTY_U64 = np.empty(0, dtype=np.uint64)
_EMPTY_I32 = np.empty(0, dtype=np.int32)

# ``_unit`` past this is folded back into the stored frequencies: far from
# float64's 2**1024 even after the 1/(1-aging) a slot hit every pass sums to
_RESCALE_AT = 2.0 ** 512


@dataclasses.dataclass
class CachePlan:
    """One census resolved against the cache directory.

    hit_mask:  bool [n] aligned with the sorted unique census keys.
    hit_pos:   int32 [H] census positions of the hits (ascending).
    hit_slots: int32 [H] cache slot per hit, aligned with hit_pos.
    """

    hit_mask: np.ndarray
    hit_pos: np.ndarray
    hit_slots: np.ndarray

    @property
    def n_hits(self) -> int:
        return int(self.hit_slots.shape[0])


@dataclasses.dataclass
class UpdatePlan:
    """End-of-pass admission/eviction decision (directory-only; the owning
    table moves the rows).  admit_* are parallel; victim_* are parallel;
    every victim slot is reused by exactly one admit."""

    admit_pos: np.ndarray  # int32 — census positions being admitted
    admit_keys: np.ndarray  # uint64 — keys at those positions
    admit_slots: np.ndarray  # int32 — slots they land in
    victim_slots: np.ndarray  # int32 — evicted slots (⊆ admit_slots)
    victim_keys: np.ndarray  # uint64 — keys leaving the cache
    cold_pos: np.ndarray  # int32 — census misses NOT admitted (host-bound)


class HbmCache:
    def __init__(self, capacity: int, n_cols: int, aging: float = 0.8,
                 device=None, materialize_rows: bool = True):
        """``materialize_rows=False`` builds a METADATA-ONLY twin: the full
        directory/policy state machine (lookup/touch/plan_update/commit)
        with no device row array — what the multi-host census plane uses to
        mirror every remote shard's membership decisions from the shared
        census stream (parallel/census.py FleetCacheMirror).  Row movement
        (gather/set/drain) raises on a twin."""
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        if not 0.0 < aging < 1.0:
            raise ValueError(f"aging must be in (0, 1), got {aging}")
        self.capacity = int(capacity)
        self.n_cols = int(n_cols)
        self.aging = float(aging)
        # the device the rows live on (None = the default device); slot
        # indices are placed there too, so gathers and scatters are
        # single-device ops wherever the caller's other arrays live
        self.device = device
        self.rows: Optional[jax.Array] = (
            jnp.zeros((self.capacity, self.n_cols), jnp.float32,
                      device=device)
            if materialize_rows else None
        )
        # directory (slot-indexed)
        self.keys = np.zeros(self.capacity, dtype=np.uint64)
        self.used = np.zeros(self.capacity, dtype=bool)
        # frequency × _unit (module docstring): read through frequency()
        self._freq = np.zeros(self.capacity, dtype=np.float64)
        self._unit = 1.0
        self.last_seen = np.full(self.capacity, -1, dtype=np.int64)
        self.dirty = np.zeros(self.capacity, dtype=bool)
        self.tick = 0
        # sorted view for the key→slot resolve (rebuilt on membership
        # change); the sample, every DIRECTORY_STRIDE-th sorted key, is what
        # the native resolve walks to name a census key's block of the view
        self._sorted_keys = _EMPTY_U64
        self._sorted_slots = _EMPTY_I32
        self._sorted_sample = _EMPTY_U64

    # -- introspection ---------------------------------------------------- #
    @property
    def resident(self) -> int:
        return int(self.used.sum())

    @property
    def dirty_rows(self) -> int:
        return int(self.dirty.sum())

    def snapshot_keys(self) -> np.ndarray:
        """The sorted resident-key array, safe to hand to another thread:
        rebuilds REPLACE the array, they never mutate it in place (the
        owning table still takes its cache lock around the grab so the
        (keys, write-back seq) pair it snapshots is consistent)."""
        return self._sorted_keys

    @staticmethod
    def hit_mask_in(sorted_keys: np.ndarray, pk: np.ndarray) -> np.ndarray:
        """bool [n]: which of sorted unique ``pk`` are in ``sorted_keys``
        — the snapshot-based membership test the staging thread uses."""
        n = pk.shape[0]
        if sorted_keys.shape[0] == 0 or n == 0:
            return np.zeros(n, dtype=bool)
        pos = np.searchsorted(sorted_keys, pk)
        pos_c = np.minimum(pos, sorted_keys.shape[0] - 1)
        return sorted_keys[pos_c] == pk

    # -- resolve ---------------------------------------------------------- #
    def _rebuild_index(self) -> None:
        slots = np.nonzero(self.used)[0].astype(np.int32)
        if slots.shape[0]:
            order = np.argsort(self.keys[slots], kind="stable")
            self._sorted_keys = self.keys[slots][order]
            self._sorted_slots = slots[order]
        else:
            self._sorted_keys = _EMPTY_U64
            self._sorted_slots = _EMPTY_I32
        self._sorted_sample = np.ascontiguousarray(
            self._sorted_keys[::DIRECTORY_STRIDE])

    @_PASS.wrap("lookup")
    def lookup(self, pk: np.ndarray) -> CachePlan:
        """Resolve a sorted unique census against the directory: one native
        merge over the two sorted arrays, or the numpy form below (the same
        arrays to the element) where the planner's library is off or did
        not build; ``cache.lookups{form=}`` says which."""
        sk = self._sorted_keys
        resolved = cache_lookup_native(
            sk, self._sorted_slots, self._sorted_sample, pk)
        _LOOKUPS.inc(form="numpy" if resolved is None else "native")
        if resolved is not None:
            return CachePlan(*resolved)
        n = pk.shape[0]
        if n == 0 or sk.shape[0] == 0:
            return CachePlan(np.zeros(n, dtype=bool), _EMPTY_I32, _EMPTY_I32)
        pos = np.searchsorted(sk, pk)
        pos = np.minimum(pos, sk.shape[0] - 1)
        hit = sk[pos] == pk
        hit_pos = np.nonzero(hit)[0].astype(np.int32)
        return CachePlan(hit, hit_pos, self._sorted_slots[pos[hit]])

    # -- policy ----------------------------------------------------------- #
    def frequency(self, slots: np.ndarray) -> np.ndarray:
        """The aged frequency of ``slots`` as of this pass."""
        return self._freq[slots] / self._unit

    @_PASS.wrap("touch")
    def touch(self, plan: CachePlan) -> None:
        """One pass observed: age every resident frequency (one step of
        ``_unit``), credit this census's hits (metadata only — membership
        is untouched, so the staging snapshot stays valid without the
        table lock)."""
        if self._unit > _RESCALE_AT:
            # the only pass over the capacity: an exact power of two, so
            # no frequency is rounded and no order or tie changes
            down = 0.5 ** math.frexp(self._unit)[1]
            self._freq *= down
            self._unit *= down
        self._unit /= self.aging
        if plan.n_hits and not cache_touch_native(
                self._freq, self.last_seen, plan.hit_slots, self._unit,
                self.tick):
            self._freq[plan.hit_slots] += self._unit
            self.last_seen[plan.hit_slots] = self.tick
        telemetry.counter(
            "cache.aged_slots",
            "cache slots whose frequency a pass's touch aged and credited "
            "(the census's hits, not the resident rows)",
        ).inc(plan.n_hits)
        self.tick += 1

    @_PASS.wrap("plan_update")
    def plan_update(self, pk: np.ndarray, plan: CachePlan) -> UpdatePlan:
        """Admission/eviction for the finished pass's census: misses fill
        free slots first, then evict the coldest non-census residents whose
        aged frequency dropped below a fresh candidate's (1.0).  Pure
        decision — ``commit_update`` applies it."""
        miss_pos = np.nonzero(~plan.hit_mask)[0].astype(np.int32)
        n_cand = miss_pos.shape[0]
        if not n_cand:  # nothing to admit: no scan of the capacity
            return UpdatePlan(
                admit_pos=_EMPTY_I32, admit_keys=_EMPTY_U64,
                admit_slots=_EMPTY_I32, victim_slots=_EMPTY_I32,
                victim_keys=_EMPTY_U64, cold_pos=_EMPTY_I32,
            )
        free = np.nonzero(~self.used)[0].astype(np.int32)
        n_free = min(n_cand, free.shape[0])
        victim_slots = _EMPTY_I32
        if n_cand > n_free:
            # aged frequency < 1.0, on the stored form: no division of
            # the whole capacity
            evictable = self.used & (self._freq < self._unit)
            evictable[plan.hit_slots] = False  # never evict a current hit
            cand_slots = np.nonzero(evictable)[0]
            if cand_slots.shape[0]:
                order = np.lexsort(
                    (cand_slots, self.last_seen[cand_slots],
                     self.frequency(cand_slots))
                )
                n_evict = min(n_cand - n_free, cand_slots.shape[0])
                victim_slots = cand_slots[order[:n_evict]].astype(np.int32)
        n_admit = n_free + victim_slots.shape[0]
        admit_pos = miss_pos[:n_admit]
        admit_slots = np.concatenate([free[:n_free], victim_slots])
        return UpdatePlan(
            admit_pos=admit_pos,
            admit_keys=pk[admit_pos],
            admit_slots=admit_slots,
            victim_slots=victim_slots,
            victim_keys=self.keys[victim_slots],
            cold_pos=miss_pos[n_admit:],
        )

    @_PASS.wrap("commit")
    def commit_update(self, plan: CachePlan, upd: UpdatePlan) -> None:
        """Apply an UpdatePlan to the directory: victims leave, admits
        enter (fresh frequency 1.0), and every row the pass touched —
        surviving hits and admits — is now newer than the host store."""
        if upd.victim_slots.shape[0]:
            self.used[upd.victim_slots] = False
            self.dirty[upd.victim_slots] = False
        if upd.admit_slots.shape[0]:
            self.keys[upd.admit_slots] = upd.admit_keys
            self.used[upd.admit_slots] = True
            self._freq[upd.admit_slots] = self._unit
            self.last_seen[upd.admit_slots] = self.tick
            self.dirty[upd.admit_slots] = True
        if plan.n_hits:
            self.dirty[plan.hit_slots] = True
        if upd.admit_slots.shape[0] or upd.victim_slots.shape[0]:
            self._rebuild_index()

    def evict_keys(self, keys: np.ndarray) -> int:
        """Drop ``keys`` from the directory WITHOUT moving rows — the
        degraded paths (cache.fetch / cache.admit faults) use this after
        routing the same keys' current rows to the host tier.  Unknown
        keys are ignored; returns the number actually evicted."""
        mask = self.hit_mask_in(self._sorted_keys, np.asarray(keys))
        if not mask.any():
            return 0
        pos = np.searchsorted(self._sorted_keys, np.asarray(keys)[mask])
        slots = self._sorted_slots[pos]
        self.used[slots] = False
        self.dirty[slots] = False
        self._rebuild_index()
        return int(slots.shape[0])

    def take_rows(
        self, keys: np.ndarray, pad_to: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Read-and-evict for hot promotion (realized hybrid placement):
        ``keys`` leaving for the replicated device block must not stay
        resident here too, or the next census would double-home them.
        Returns ``(hit_mask bool [n], rows [hits, n_cols])`` — rows
        aligned with the hit subset of ``keys`` in order; the evicted
        slots are dropped clean (the caller now owns the freshest copy).
        Misses are the caller's to resolve against the host store.
        ``pad_to`` pads the device gather to a static length so repeated
        promotions with varying hit counts reuse one compiled gather."""
        keys = np.asarray(keys, dtype=np.uint64)
        mask = self.hit_mask_in(self._sorted_keys, keys)
        if not mask.any():
            return mask, np.empty((0, self.n_cols), dtype=np.float32)
        pos = np.searchsorted(self._sorted_keys, keys[mask])
        slots = self._sorted_slots[pos]
        k = int(slots.shape[0])
        if pad_to is not None and pad_to >= k:
            padded = np.zeros(pad_to, dtype=np.int64)
            padded[:k] = slots
            rows = np.asarray(self.gather_rows(padded))[:k]
        else:
            rows = np.asarray(self.gather_rows(slots))
        self.used[slots] = False
        self.dirty[slots] = False
        self._rebuild_index()
        return mask, rows

    # -- row movement ------------------------------------------------------ #
    def _slot_index(self, slots: np.ndarray) -> jax.Array:
        return jax.device_put(np.asarray(slots, dtype=np.int32), self.device)

    def gather_rows(self, slots: np.ndarray) -> jax.Array:
        """Device gather of ``slots`` rows."""
        if self.rows is None:
            raise RuntimeError(
                "metadata-only cache twin has no rows to gather "
                "(materialize_rows=False)"
            )
        return jnp.take(self.rows, self._slot_index(slots), axis=0)

    def set_rows(self, slots: np.ndarray, rows: jax.Array) -> None:
        """Device scatter-replace of ``rows`` into ``slots``."""
        if np.asarray(slots).shape[0] == 0:
            return
        if self.rows is None:
            raise RuntimeError(
                "metadata-only cache twin has no rows to set "
                "(materialize_rows=False)"
            )
        self.rows = self.rows.at[self._slot_index(slots)].set(rows)

    # -- coherence --------------------------------------------------------- #
    def drain(self) -> Tuple[np.ndarray, np.ndarray]:
        """(keys sorted, rows [n, n_cols]) of every DIRTY slot, marking
        them clean — the barrier half of the coherence contract: after a
        drain lands through the table's write-back path, the host store is
        truth again for every resident key."""
        d = np.nonzero(self.dirty)[0]
        if d.shape[0] == 0:
            return _EMPTY_U64, np.empty((0, self.n_cols), dtype=np.float32)
        keys = self.keys[d]
        order = np.argsort(keys, kind="stable")
        rows = np.asarray(self.gather_rows(d[order].astype(np.int32)))
        self.dirty[d] = False
        return keys[order], rows

    def invalidate(self) -> None:
        """Forget every resident key without moving rows — required when
        the host store changed underneath (restore, apply_delta, shrink's
        decay/evict).  Callers needing the rows preserved drain() first."""
        self.used[:] = False
        self.dirty[:] = False
        self._freq[:] = 0.0
        self._unit = 1.0
        self.last_seen[:] = -1
        self._rebuild_index()
