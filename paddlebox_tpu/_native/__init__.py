"""Native (C++) host-pipeline components, loaded via ctypes.

The reference keeps its whole data layer in C++ because host feed was the
production bottleneck (SURVEY.md §2.4); here the parser, the batch
builder's key pack, the batch planner and the row cache's directory work
at a pass boundary (the census resolve and the touch of its hits) are
native and the rest of the pipeline stays numpy (already vectorized).  The
shared library builds on demand with g++ (no pybind11 in the image — plain
C ABI + ctypes) into a file named by a hash of its source and build flags,
so a binary is only ever loaded if it was built from exactly this source
with exactly these flags; the flags target the baseline ISA, so a tree
copied to another machine carries nothing host-specific.  Anything failing
(no compiler, build error) falls back to the pure-Python parser; entry
points that must not run degraded call :func:`require_native`.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "slot_parser.cpp")
_CXX = ("g++", "-O3", "-std=c++17", "-shared", "-fPIC")

_lock = threading.Lock()
_lib = None
_tried = False


def _build_so(src: str) -> Optional[str]:
    """Path of the library built from ``src`` (``_<name>.<hash>.so`` beside
    it), building it first unless that exact file exists; None on ANY
    failure (missing source, no compiler, build error)."""
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(
                " ".join(_CXX).encode() + b"\0" + f.read()
            ).hexdigest()[:16]
    except OSError:
        return None
    stem = os.path.join(
        os.path.dirname(src),
        "_" + os.path.splitext(os.path.basename(src))[0],
    )
    so = f"{stem}.{digest}.so"
    if os.path.exists(so):
        return so
    tmp = so + f".tmp-{os.getpid()}"
    try:
        subprocess.run([*_CXX, "-o", tmp, src], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning(
            "native build of %s failed: %s", src,
            (getattr(e, "stderr", None) or b"").decode(errors="replace") or e,
        )
        if os.path.exists(tmp):
            os.remove(tmp)
        return None
    for stale in glob.glob(stem + "*.so"):  # builds of older sources
        if stale != so:
            with contextlib.suppress(FileNotFoundError):  # a racing build
                os.remove(stale)
    return so


def _build() -> Optional[str]:
    return _build_so(_SRC)


def get_lib():
    """The loaded native library, or None (build unavailable/failed)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        # pbox-lint: ignore[lock-held-blocking] build-once: holding the
        # lock through the compile is the point — every caller must wait
        # for the single build instead of racing their own
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        lib.pbx_parse_buffer.restype = ctypes.c_void_p
        lib.pbx_parse_buffer.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int8), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int64,
        ]
        for name in ("pbx_n_ins", "pbx_n_keys", "pbx_ins_id_bytes"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int64
            fn.argtypes = [ctypes.c_void_p]
        lib.pbx_fill.restype = None
        lib.pbx_fill.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 10
        lib.pbx_free.restype = None
        lib.pbx_free.argtypes = [ctypes.c_void_p]
        lib.pbx_hash_ids.restype = None
        lib.pbx_hash_ids.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.pbx_pack_batch.restype = ctypes.c_int64
        lib.pbx_pack_batch.argtypes = (
            [ctypes.c_void_p] * 3 + [ctypes.c_int64] * 3 + [ctypes.c_int32]
            + [ctypes.c_void_p] * 3 + [ctypes.POINTER(ctypes.c_int64)]
        )
        _lib = lib
        return _lib


def hash_ids_native(ins_ids) -> Optional[np.ndarray]:
    """Batch FNV-1a 64 via the native lib; None when it is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    enc = [s.encode() for s in ins_ids]
    buf = b"".join(enc)
    offs = np.zeros(len(enc) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in enc], out=offs[1:])
    out = np.empty(len(enc), dtype=np.uint64)
    lib.pbx_hash_ids(
        buf,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(enc),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return out


def pack_batch_native(block_keys: np.ndarray, key_offsets: np.ndarray,
                      ids: np.ndarray, S: int, K: int, pad_seg: int):
    """The batch builder's key pack in one native pass (GIL released):
    (keys uint64 [K], key_segments int32 [K], lens int64 [len(ids)*S],
    n_keys, dropped), every array fresh; None when the library is
    unavailable or the block's arrays are not the contiguous uint64 /
    int64 the parser makes.  The caller has checked ``ids`` against the
    block (``BatchBuilder.build``)."""
    lib = get_lib()
    if lib is None or not (
        block_keys.dtype == np.uint64 and block_keys.flags.c_contiguous
        and key_offsets.dtype == np.int64 and key_offsets.flags.c_contiguous
    ):
        return None
    ids = np.ascontiguousarray(ids, dtype=np.int64)
    keys = np.empty(K, dtype=np.uint64)
    segs = np.empty(K, dtype=np.int32)
    lens = np.empty(ids.shape[0] * S, dtype=np.int64)
    dropped = ctypes.c_int64(0)
    n_keys = lib.pbx_pack_batch(
        block_keys.ctypes.data, key_offsets.ctypes.data, ids.ctypes.data,
        ids.shape[0], S, K, pad_seg, keys.ctypes.data, segs.ctypes.data,
        lens.ctypes.data, ctypes.byref(dropped),
    )
    return keys, segs, lens, int(n_keys), int(dropped.value)


_KIND_CODE = {"skip": 0, "label": 1, "task": 2, "dense": 3, "sparse": 4}


class NativeParser:
    """ctypes front-end bound to one walk layout (shared per SlotParser)."""

    def __init__(self, walk, n_sparse: int, dense_width: int, n_tasks: int,
                 parse_ins_id: bool, parse_logkey: bool):
        self.lib = get_lib()
        if self.lib is None:
            raise RuntimeError("native parser unavailable")
        kinds, widths, cols = [], [], []
        for kind, width, col, _typ in walk:
            kinds.append(_KIND_CODE[kind])
            widths.append(max(width, 0))
            cols.append(max(col, 0))
        self._kinds = np.asarray(kinds, dtype=np.int8)
        self._widths = np.asarray(widths, dtype=np.int32)
        self._cols = np.asarray(cols, dtype=np.int32)
        self.n_sparse = n_sparse
        self.dense_width = dense_width
        self.n_tasks = n_tasks
        self.parse_ins_id = parse_ins_id
        self.parse_logkey = parse_logkey

    def parse_bytes(self, data: bytes, path: str = "<buffer>"):
        from paddlebox_tpu.data.record import RecordBlock

        lib = self.lib
        err = ctypes.create_string_buffer(256)
        handle = lib.pbx_parse_buffer(
            data, len(data),
            self._kinds.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            self._widths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            self._cols.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            len(self._kinds), self.n_sparse, self.dense_width, self.n_tasks,
            int(self.parse_ins_id), int(self.parse_logkey), err, 256,
        )
        if not handle:
            raise ValueError(
                f"{path}: malformed instance ({err.value.decode()})"
            )
        try:
            n = lib.pbx_n_ins(handle)
            nk = lib.pbx_n_keys(handle)
            keys = np.empty(nk, dtype=np.uint64)
            offsets = np.empty(n * self.n_sparse + 1, dtype=np.int64)
            dense = np.zeros((n, self.dense_width), dtype=np.float32)
            labels = np.empty(n, dtype=np.float32)
            tasks = (
                np.empty((n, self.n_tasks), dtype=np.float32)
                if self.n_tasks
                else None
            )
            sids = ranks = cmatches = None
            if self.parse_logkey:
                sids = np.empty(n, dtype=np.uint64)
                ranks = np.empty(n, dtype=np.int32)
                cmatches = np.empty(n, dtype=np.int32)
            insid_buf = insid_offs = None
            if self.parse_ins_id:
                insid_buf = np.empty(lib.pbx_ins_id_bytes(handle), dtype=np.uint8)
                insid_offs = np.empty(n + 1, dtype=np.int64)
            ptr = lambda a: (
                a.ctypes.data_as(ctypes.c_void_p) if a is not None else None
            )
            lib.pbx_fill(
                handle, ptr(keys), ptr(offsets), ptr(dense), ptr(labels),
                ptr(tasks), ptr(sids), ptr(ranks), ptr(cmatches),
                ptr(insid_buf), ptr(insid_offs),
            )
        finally:
            lib.pbx_free(handle)
        ins_ids = None
        if self.parse_ins_id:
            raw = insid_buf.tobytes()
            ins_ids = [
                raw[insid_offs[i]:insid_offs[i + 1]].decode()
                for i in range(n)
            ]
        return RecordBlock(
            n_ins=int(n),
            n_sparse_slots=self.n_sparse,
            keys=keys,
            key_offsets=offsets,
            dense=dense,
            labels=labels,
            ins_ids=ins_ids,
            search_ids=sids,
            ranks=ranks,
            cmatches=cmatches,
            task_labels=tasks,
        )


# --------------------------------------------------------------------------- #
# Native batch planner (plan_resolve.cpp) — own .so, same build discipline
# --------------------------------------------------------------------------- #
_PLAN_SRC = os.path.join(_DIR, "plan_resolve.cpp")
_plan_lock = threading.Lock()
_plan_lib = None
_plan_tried = False


def _build_plan() -> Optional[str]:
    return _build_so(_PLAN_SRC)


def get_plan_lib():
    """The loaded planner library, or None (build unavailable/failed)."""
    global _plan_lib, _plan_tried
    with _plan_lock:
        if _plan_tried:
            return _plan_lib
        _plan_tried = True
        # pbox-lint: ignore[lock-held-blocking] build-once under the lock
        # (see get_lib): waiters NEED the build to finish
        so = _build_plan()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        try:
            _bind_plan_symbols(lib)
        except AttributeError as e:  # not the library of this source
            logger.warning("native planner %s lacks a symbol: %s", so, e)
            return None
        _plan_lib = lib
        return _plan_lib


def require_native() -> dict:
    """{"parser": bool, "planner": bool} — which native libraries the
    flags ask for AND are loaded ("parser" is the data layer's library:
    the parser and the batch builder's key pack; "planner" the batch
    planner's, which also holds the row cache's directory resolve and
    touch).  Raises when a flag
    asks for one that did not build: entry points that measure or prove
    the system must not run on the 4-5x slower Python fallback unnoticed."""
    from paddlebox_tpu.config import flags

    wanted = {"parser": (flags.use_native_parser, get_lib),
              "planner": (flags.use_native_planner, get_plan_lib)}
    loaded = {name: bool(flag and load() is not None)
              for name, (flag, load) in wanted.items()}
    missing = [name for name, (flag, _) in wanted.items()
               if flag and not loaded[name]]
    if missing:
        raise RuntimeError(
            f"native {' and '.join(missing)} did not build (is g++ "
            "installed?) — set PBOX_USE_NATIVE_PARSER=0 / "
            "PBOX_USE_NATIVE_PLANNER=0 to accept the Python fallback"
        )
    return loaded


def _bind_plan_symbols(lib) -> None:
    lib.pbx_census_index_build.restype = ctypes.c_void_p
    lib.pbx_census_index_build.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64,
    ]
    lib.pbx_census_index_free.restype = None
    lib.pbx_census_index_free.argtypes = [ctypes.c_void_p]
    lib.pbx_plan_resolve.restype = ctypes.c_int64
    lib.pbx_plan_resolve.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, ctypes.c_int, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.pbx_dedup_rows.restype = ctypes.c_int64
    lib.pbx_dedup_rows.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.pbx_census_lookup_unique.restype = ctypes.c_int64
    lib.pbx_census_lookup_unique.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.pbx_cache_lookup.restype = ctypes.c_int64
    lib.pbx_cache_lookup.argtypes = (
        [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
         ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
        + [ctypes.c_void_p] * 3
    )
    lib.pbx_cache_touch.restype = None
    lib.pbx_cache_touch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_double, ctypes.c_int64,
    ]


class CensusIndex:
    """Per-pass census hash index (native).  Holds a REFERENCE to the
    census array — the caller must keep it alive for the index lifetime
    (SparseTable owns its sorted pass keys for the whole pass)."""

    def __init__(self, lib, census: np.ndarray):
        self._lib = lib
        self._census = np.ascontiguousarray(census, dtype=np.uint64)
        self._lock = threading.Lock()  # close vs concurrent resolve
        self._handle = lib.pbx_census_index_build(
            self._census.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            self._census.shape[0],
        )

    def close(self) -> None:
        with self._lock:
            if self._handle:
                self._lib.pbx_census_index_free(self._handle)
                self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            # interpreter-teardown finalizer: the lib/lock may be half
            # collected — record it, never raise out of __del__
            logger.debug("census index close failed in __del__",
                         exc_info=True)

    def lookup_unique(self, keys: np.ndarray, n_real: int):
        """(inverse[:n_real], uniq_key[:n_uniq], uniq_pos[:n_uniq]) with
        first-seen slot order and census position -1 for absent keys, or
        None.  The sharded planner's per-device dedup+resolve."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        K = keys.shape[0]
        inverse = np.empty(K, dtype=np.int32)
        uniq_key = np.empty(K, dtype=np.uint64)
        uniq_pos = np.empty(K, dtype=np.int64)
        with self._lock:
            if not self._handle:
                return None
            n_uniq = self._lib.pbx_census_lookup_unique(
                self._handle,
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                K, int(n_real),
                inverse.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                uniq_key.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                uniq_pos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            )
        if n_uniq < 0:
            return None
        return (inverse[:n_real], uniq_key[:n_uniq], uniq_pos[:n_uniq])

    def resolve(self, keys: np.ndarray, n_real: int, dead: int,
                scratch_base: int, n_slots: int):
        """(idx, uniq_idx, inverse, key_mask, n_missing, n_uniq) or None,
        with ``uniq_idx`` of ``n_slots`` slots.  ``n_uniq`` (the batch's
        distinct keys) is exact whatever ``n_slots``; the arrays are a
        plan only when the keys fit, n_uniq <= n_slots - 1 or n_slots ==
        len(keys) — the caller sizes ``n_slots`` and asks again."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        K = keys.shape[0]
        idx = np.empty(K, dtype=np.int32)
        uniq_idx = np.empty(n_slots, dtype=np.int32)
        inverse = np.empty(K, dtype=np.int32)
        key_mask = np.empty(K, dtype=np.float32)
        n_uniq = ctypes.c_int64(0)
        i32p = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        with self._lock:
            if not self._handle:
                return None
            n_missing = self._lib.pbx_plan_resolve(
                self._handle,
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                K, int(n_real), int(dead), int(scratch_base), int(n_slots),
                i32p(idx), i32p(uniq_idx), i32p(inverse),
                key_mask.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                ctypes.byref(n_uniq),
            )
        if n_missing < 0:
            return None
        return (idx, uniq_idx, inverse, key_mask, int(n_missing),
                int(n_uniq.value))


def build_census_index(census: np.ndarray):
    """A CensusIndex over the sorted pass keys, or None (no native lib)."""
    lib = get_plan_lib()
    if lib is None:
        return None
    return CensusIndex(lib, census)


def dedup_rows_native(rows: np.ndarray):
    """First-seen-order unique of an int32 id buffer: (inverse, uniq) or
    None when the native lib is unavailable.  The sharded serve-side
    np.unique replacement (no census involved; stateless)."""
    lib = get_plan_lib()
    if lib is None:
        return None
    rows = np.ascontiguousarray(rows, dtype=np.int32).reshape(-1)
    n = rows.shape[0]
    inverse = np.empty(n, dtype=np.int32)
    uniq = np.empty(max(n, 1), dtype=np.int32)
    i32p = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    n_uniq = lib.pbx_dedup_rows(i32p(rows), n, i32p(inverse), i32p(uniq))
    return inverse, uniq[:n_uniq]


# --------------------------------------------------------------------------- #
# The row cache's directory at a pass boundary (HbmCache.lookup / touch):
# same library as the planner, same flag
# --------------------------------------------------------------------------- #
# plan_resolve.cpp kDirStride: the directory's sample holds every 16th of
# its sorted keys (17 MB at the CTR cells' 33.8 M rows)
DIRECTORY_STRIDE = 16


def _directory_lib():
    from paddlebox_tpu.config import flags

    return get_plan_lib() if flags.use_native_planner else None


def cache_lookup_native(sorted_keys: np.ndarray, sorted_slots: np.ndarray,
                        sample: np.ndarray, pk: np.ndarray):
    """Sorted unique ``pk`` against a cache directory's sorted view in one
    native merge (GIL released): (hit_mask bool [n], hit_pos int32 [H]
    ascending, hit_slots int32 [H]), equal to ``HbmCache.lookup``'s numpy
    form to the element; None when the planner's library is off or did not
    build.  ``sample`` is ``sorted_keys[::DIRECTORY_STRIDE]``; the three
    directory arrays are the cache's own (contiguous uint64 / int32)."""
    lib = _directory_lib()
    if lib is None:
        return None
    pk = np.ascontiguousarray(pk, dtype=np.uint64)
    n = pk.shape[0]
    hit_mask = np.empty(n, dtype=bool)
    hit_pos = np.empty(n, dtype=np.int32)
    hit_slots = np.empty(n, dtype=np.int32)
    n_hits = lib.pbx_cache_lookup(
        sorted_keys.ctypes.data, sorted_slots.ctypes.data,
        sorted_keys.shape[0], sample.ctypes.data, sample.shape[0],
        pk.ctypes.data, n, hit_mask.ctypes.data, hit_pos.ctypes.data,
        hit_slots.ctypes.data,
    )
    if n_hits < 0:
        raise ValueError(
            f"a sample of {sample.shape[0]} keys is not every "
            f"{DIRECTORY_STRIDE}th of a directory of {sorted_keys.shape[0]}")
    return hit_mask, hit_pos[:n_hits], hit_slots[:n_hits]


def cache_touch_native(freq: np.ndarray, last_seen: np.ndarray,
                       slots: np.ndarray, unit: float, tick: int) -> bool:
    """``freq[slots] += unit; last_seen[slots] = tick`` over DISTINCT
    int32 ``slots`` in one native pass; False (nothing written) when the
    planner's library is off or did not build."""
    lib = _directory_lib()
    if lib is None:
        return False
    slots = np.ascontiguousarray(slots, dtype=np.int32)
    lib.pbx_cache_touch(freq.ctypes.data, last_seen.ctypes.data,
                        slots.ctypes.data, slots.shape[0], unit, tick)
    return True
