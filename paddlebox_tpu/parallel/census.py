"""Shared-dictionary census exchange: O(cold keys + hot-set deltas) wire.

The multi-host pass census (``ShardedSparseTable.begin_pass``) used to
allgather every process's FULL local census as raw 8-byte keys — O(working
set) bytes per pass, the host-plane analog of the promotion traffic PR 6
collapsed per-process.  This module applies the same collapse to the wire:

  * every process independently derives an IDENTICAL **shared dictionary**
    from the global census stream — the placement planner's replicated-hot
    set (sparse/placement.py) unioned with metadata-only mirrors of every
    shard's HBM-cache directory (:class:`FleetCacheMirror`, replaying the
    deterministic LFU-with-aging admission from the same censuses the real
    caches see).  No collective builds the dictionary; determinism does.
  * a census message is then ``(membership bitmap over the dictionary,
    varint sorted-delta of the cold tail)``: a dictionary key costs ONE
    BIT, a cold key ~1-2 bytes (utils/keycodec.py) instead of 8 raw + 4/3x
    base64.
  * correctness never depends on the dictionary matching any REAL cache:
    the dictionary is a compression codebook, owners still resolve their
    own shards against their own caches/stores.  What MUST hold is that
    all ranks hold the same codebook — every message carries its size and
    a 64-bit digest, and any divergence (or a mixed-version peer speaking
    a different wire format) raises the structured
    :class:`CensusProtocolError` instead of silently mis-decoding.

Transports: :class:`LoopbackTransport` (single process — lets tests
drive the full encode->decode path in vivo), a ``KvChannel.gather_bytes``
bound method (real multi-host, host-side KV store, main-thread begin_pass
per the spmd-collective-on-thread contract), and
:class:`InProcessCensusGroup` (N simulated ranks on threads — the
CPU-admissible fleet harness, same discipline as
``data/shuffle.InProcessShuffleGroup``).
"""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import numpy as np

from paddlebox_tpu import telemetry
from paddlebox_tpu.utils import keycodec

_MAGIC = b"PBCX1"
_CODEC_RAW = 0
_CODEC_VARINT = 1

_EMPTY_U64 = np.empty(0, dtype=np.uint64)

# byte-scale histogram edges: one wire message spans ~100B (bitmap-only)
# to tens of MB (a cold full census at production scale)
BYTE_BUCKETS = (
    64.0, 256.0, 1024.0, 4096.0, 16384.0, 65536.0, 262144.0,
    float(1 << 20), float(4 << 20), float(16 << 20), float(64 << 20),
)


def _gather_bytes_hist():
    return telemetry.histogram(
        "hostplane.gather_bytes",
        "host-plane gather payload bytes by channel base and kind "
        "(raw = pre-codec equivalent, encoded = on-wire)",
        buckets=BYTE_BUCKETS,
    )


class CensusProtocolError(RuntimeError):
    """A census message failed negotiation: a peer speaks a different
    wire format/codec, or its shared dictionary diverged from ours.
    Mixed-version fleets must fail HERE, loudly, naming the peer — never
    decode a bitmap against the wrong codebook."""

    def __init__(self, channel: str, sender: int, reason: str):
        self.channel = channel
        self.sender = sender
        self.reason = reason
        super().__init__(
            f"census exchange on channel {channel!r}: message from rank "
            f"{sender} {reason} (mixed-version peer or dictionary "
            "divergence — set PBOX_PLACEMENT=hash and "
            "PBOX_HOSTPLANE_CODEC=legacy fleet-wide, or upgrade all ranks)"
        )


def _dict_digest(keys: np.ndarray) -> int:
    """Order-free 64-bit digest of a key set (xor of splitmix64 hashes):
    the cheap cross-rank dictionary-agreement check."""
    if not keys.shape[0]:
        return 0
    from paddlebox_tpu.sparse.store import splitmix64

    return int(np.bitwise_xor.reduce(splitmix64(keys)))


def _read_varint(buf: memoryview, off: int) -> tuple:
    """One scalar LEB128 read -> (value, next offset); loud on damage."""
    shift = 0
    val = 0
    for i in range(10):
        if off >= len(buf):
            raise keycodec.KeyCodecError("truncated",
                                         "header varint runs off the buffer")
        b = buf[off]
        off += 1
        val |= (b & 0x7F) << shift
        if not (b & 0x80):
            if val >= 1 << 64:
                raise keycodec.KeyCodecError("overlong",
                                             "header varint exceeds 2^64")
            return val, off
        shift += 7
    raise keycodec.KeyCodecError("overlong", "header varint spans > 10 bytes")


# --------------------------------------------------------------------------- #
# transports
# --------------------------------------------------------------------------- #
class LoopbackTransport:
    """World of one: gather returns this process's own payload.  Used
    single-process so the encode->decode wire path still executes (and is
    measured) without a fleet — ``PBOX_PLACEMENT=loopback``."""

    world = 1

    def gather(self, payload: bytes) -> List[bytes]:
        return [payload]


class InProcessCensusGroup:
    """N simulated ranks (threads) exchanging census payloads through a
    barrier-coordinated mailbox — the CPU-admissible fleet harness for
    tests (real multi-process JAX collectives cannot execute on the CPU
    backend; the wire logic is identical)."""

    def __init__(self, n_ranks: int):
        if n_ranks < 1:
            raise ValueError("n_ranks must be >= 1")
        self.n_ranks = n_ranks
        self._box: List[Optional[bytes]] = [None] * n_ranks
        self._lock = threading.Lock()
        self._barrier = threading.Barrier(n_ranks)
        self.bytes_per_round: List[int] = []  # wire bytes, appended by rank 0

    def transport(self, rank: int) -> "_GroupTransport":
        if not 0 <= rank < self.n_ranks:
            raise ValueError(f"bad rank {rank}")
        return _GroupTransport(self, rank)

    def _gather(self, rank: int, payload: bytes) -> List[bytes]:
        with self._lock:
            self._box[rank] = payload
        self._barrier.wait()  # all deposits visible
        msgs = list(self._box)
        if rank == 0:
            self.bytes_per_round.append(sum(len(m) for m in msgs))
        # second barrier: nobody starts the next round (overwriting the
        # mailbox) until every rank has copied this round's messages
        self._barrier.wait()
        return msgs


class _GroupTransport:
    def __init__(self, group: InProcessCensusGroup, rank: int):
        self.group = group
        self.rank = rank
        self.world = group.n_ranks

    def gather(self, payload: bytes) -> List[bytes]:
        return self.group._gather(self.rank, payload)


class KvGatherTransport:
    """Real multi-host transport: one ``KvChannel.gather_bytes`` per
    exchange (host-side KV store — begin_pass stays on the main thread,
    and the channel is exempt from the collective-on-thread rule by
    design)."""

    def __init__(self, channel):
        self.channel = channel
        self.world = channel._world

    def gather(self, payload: bytes) -> List[bytes]:
        return self.channel.gather_bytes(payload)


# --------------------------------------------------------------------------- #
# cache mirrors
# --------------------------------------------------------------------------- #
class FleetCacheMirror:
    """Metadata-only twins of EVERY shard's HbmCache directory.

    Cache admission (sparse/engine/hbm_cache.py) is a deterministic
    function of the per-shard census sequence, and every rank holds the
    same global census — so every rank can replay every shard's
    lookup->touch->plan_update->commit sequence on a rows-free twin and
    predict remote residency without a single extra byte on the wire.
    Resident keys join the shared dictionary: a key resident anywhere
    rides the census as one bit.

    A REAL cache can diverge from its twin (fault-injected degrade paths
    evict out-of-band); that only costs compression — the dictionary is a
    codebook, not a coherence protocol — and the twins themselves stay
    identical across ranks because they never see local-only events.
    """

    def __init__(self, n_shards: int, per_shard_rows: int, aging: float):
        from paddlebox_tpu.sparse.engine import HbmCache

        self.n_shards = int(n_shards)
        self._dirs = [
            HbmCache(per_shard_rows, 1, aging=aging, materialize_rows=False)
            for _ in range(self.n_shards)
        ]

    def shard_resident(self, shard: int) -> np.ndarray:
        """Sorted resident keys of one shard's twin (test introspection)."""
        return self._dirs[shard].snapshot_keys()

    def resident_keys(self) -> np.ndarray:
        """All residents, globally sorted (shards partition the key space,
        so the concat is duplicate-free)."""
        parts = [d.snapshot_keys() for d in self._dirs]
        parts = [p for p in parts if p.shape[0]]
        if not parts:
            return _EMPTY_U64.copy()
        return np.sort(np.concatenate(parts))

    def step(self, pk: np.ndarray) -> None:
        """Replay one pass's directory evolution from the global census."""
        n = np.uint64(self.n_shards)
        owner = pk % n
        for o, d in enumerate(self._dirs):
            sk = pk[owner == np.uint64(o)]
            plan = d.lookup(sk)
            d.touch(plan)
            upd = d.plan_update(sk, plan)
            d.commit_update(plan, upd)

    def evict(self, keys: np.ndarray) -> None:
        """Mirror the realized hot promotion: keys promoted into the
        replicated device block leave the REAL per-shard caches (the
        owner read them out via ``take_rows``), so their twins must drop
        them too — same keys on every rank, so the twins stay lockstep."""
        keys = np.asarray(keys, dtype=np.uint64)
        if not keys.shape[0]:
            return
        owner = keys % np.uint64(self.n_shards)
        for o, d in enumerate(self._dirs):
            sk = keys[owner == np.uint64(o)]
            if sk.shape[0]:
                d.evict_keys(sk)


# --------------------------------------------------------------------------- #
# the exchange
# --------------------------------------------------------------------------- #
class CensusExchange:
    """One rank's half of the census collective.

    Every rank must construct this with the SAME planner/mirror
    configuration and feed it the same call sequence — the dictionary is
    derived state, and the digest in every message verifies the derivation
    stayed in lockstep.  ``exchange(local_census)`` returns the global
    census (identical on every rank, byte-for-byte equal to the legacy
    allgather-union).
    """

    def __init__(
        self,
        transport,
        planner=None,
        mirror: Optional[FleetCacheMirror] = None,
        codec: str = "varint",
        channel: str = "census",
        realize: bool = False,
    ):
        """``realize=True`` when the owning table MATERIALIZES the plan's
        hot set on device (realized hybrid placement): hot keys then never
        reach the real per-shard caches — they are promoted out at plan
        realization and served from the replicated block — so the mirror
        twins must replay the same split (evict promoted keys, see only
        the cold census) or residency prediction drifts from reality."""
        if codec not in ("varint", "raw"):
            raise ValueError(f"codec must be varint|raw, got {codec!r}")
        self.transport = transport
        self.planner = planner
        self.mirror = mirror
        self.codec = codec
        self.channel = channel
        self.realize = bool(realize)
        self._known: np.ndarray = _EMPTY_U64.copy()
        self.last_wire_bytes = 0  # this rank's encoded payload size
        self.last_raw_bytes = 0  # what the legacy wire would have shipped
        self.last_cold_keys = 0

    # -- wire format ------------------------------------------------------ #
    def _encode(self, local_pk: np.ndarray, known: np.ndarray) -> bytes:
        if known.shape[0] and local_pk.shape[0]:
            pos = np.searchsorted(known, local_pk)
            pos_c = np.minimum(pos, known.shape[0] - 1)
            hit = known[pos_c] == local_pk
            seen = np.zeros(known.shape[0], dtype=bool)
            seen[pos_c[hit]] = True
            cold = local_pk[~hit]
        else:
            seen = np.zeros(known.shape[0], dtype=bool)
            cold = local_pk
        bitmap = np.packbits(seen).tobytes() if known.shape[0] else b""
        if self.codec == "varint":
            cold_payload = keycodec.encode_sorted_u64(cold)
            codec_byte = _CODEC_VARINT
        else:
            cold_payload = np.ascontiguousarray(cold, np.uint64).tobytes()
            codec_byte = _CODEC_RAW
        header = keycodec.encode_varints(
            np.asarray(
                [known.shape[0], _dict_digest(known), cold.shape[0]],
                dtype=np.uint64,
            )
        )
        self.last_cold_keys = int(cold.shape[0])
        return (
            _MAGIC + bytes([codec_byte]) + header + bitmap + cold_payload
        )

    def _decode(self, msg: bytes, sender: int, known: np.ndarray):
        """-> (seen bool [n_known], cold keys sorted)."""
        if not msg.startswith(_MAGIC):
            raise CensusProtocolError(
                self.channel, sender,
                "does not carry the PBCX1 census wire magic",
            )
        codec_byte = msg[len(_MAGIC)]
        if codec_byte not in (_CODEC_RAW, _CODEC_VARINT):
            raise CensusProtocolError(
                self.channel, sender, f"declares unknown codec {codec_byte}"
            )
        view = memoryview(msg)
        off = len(_MAGIC) + 1
        try:
            n_known, off = _read_varint(view, off)
            digest, off = _read_varint(view, off)
            n_cold, off = _read_varint(view, off)
        except keycodec.KeyCodecError as e:
            raise CensusProtocolError(
                self.channel, sender, f"has a damaged header ({e})"
            ) from e
        if n_known != known.shape[0] or digest != _dict_digest(known):
            raise CensusProtocolError(
                self.channel, sender,
                f"was encoded against a different dictionary "
                f"({n_known} keys, digest {digest:#x}; ours "
                f"{known.shape[0]} keys, digest {_dict_digest(known):#x})",
            )
        n_bitmap = (n_known + 7) // 8
        if len(msg) < off + n_bitmap:
            raise CensusProtocolError(
                self.channel, sender, "is truncated inside the bitmap"
            )
        if n_known:
            seen = np.unpackbits(
                np.frombuffer(view[off:off + n_bitmap], dtype=np.uint8)
            )[:n_known].astype(bool)
        else:
            seen = np.zeros(0, dtype=bool)
        off += n_bitmap
        body = view[off:]
        try:
            if codec_byte == _CODEC_VARINT:
                cold = keycodec.decode_sorted_u64(body)
                if cold.shape[0] != n_cold:
                    raise keycodec.KeyCodecError(
                        "count-mismatch",
                        f"header says {n_cold} cold keys, "
                        f"stream holds {cold.shape[0]}",
                    )
            else:
                if len(body) != n_cold * 8:
                    raise keycodec.KeyCodecError(
                        "truncated",
                        f"raw cold payload is {len(body)} bytes, "
                        f"expected {n_cold * 8}",
                    )
                cold = np.frombuffer(body, dtype=np.uint64).copy()
        except keycodec.KeyCodecError as e:
            raise CensusProtocolError(
                self.channel, sender, f"has a damaged cold payload ({e})"
            ) from e
        return seen, cold

    # -- the collective --------------------------------------------------- #
    def exchange(self, local_census: np.ndarray) -> np.ndarray:
        """Gather every rank's census -> the global census (sorted unique),
        advancing the planner/mirror dictionary for the NEXT pass."""
        local_pk = np.unique(np.asarray(local_census, dtype=np.uint64))
        known = self._known
        payload = self._encode(local_pk, known)
        self.last_wire_bytes = len(payload)
        self.last_raw_bytes = int(local_pk.nbytes)
        hist = _gather_bytes_hist()
        hist.observe(float(self.last_raw_bytes),
                     channel=self.channel, kind="raw")
        hist.observe(float(self.last_wire_bytes),
                     channel=self.channel, kind="encoded")
        telemetry.histogram(
            "census.cold_keys",
            "keys per census message that missed the shared dictionary "
            "and rode the wire as key payloads",
        ).observe(float(self.last_cold_keys))
        msgs = self.transport.gather(payload)
        seen_any = np.zeros(known.shape[0], dtype=bool)
        colds = []
        for sender, msg in enumerate(msgs):
            seen, cold = self._decode(msg, sender, known)
            seen_any |= seen
            if cold.shape[0]:
                colds.append(cold)
        parts = [known[seen_any]] if known.shape[0] else []
        parts += colds
        if parts:
            pk = np.unique(np.concatenate(parts))
        else:
            pk = _EMPTY_U64.copy()
        self._advance(pk)
        return pk

    def _advance(self, pk: np.ndarray) -> None:
        """Evolve the shared dictionary from the agreed global census —
        pure function of ``pk``, so every rank stays in lockstep."""
        parts = []
        hot = _EMPTY_U64
        if self.planner is not None:
            self.planner.observe(pk)
            plan = self.planner.update_plan()
            if plan.n_hot:
                parts.append(plan.hot_keys)
                hot = plan.hot_keys
        if self.mirror is not None:
            if self.realize and hot.shape[0]:
                # realized placement: hot keys live in the replicated
                # device block, not the per-shard caches — evict their
                # twins and feed the directories the COLD census only,
                # exactly what the real caches will observe
                self.mirror.evict(hot)
                self.mirror.step(np.setdiff1d(pk, hot, assume_unique=True))
            else:
                self.mirror.step(pk)
            res = self.mirror.resident_keys()
            if res.shape[0]:
                parts.append(res)
        if not parts:
            self._known = _EMPTY_U64.copy()
        elif len(parts) == 1:
            self._known = parts[0]
        else:
            self._known = np.unique(np.concatenate(parts))


def legacy_union(censuses: Sequence[np.ndarray]) -> np.ndarray:
    """The pre-codec semantics in one place: allgather-union of raw local
    censuses.  Tests pin ``CensusExchange`` output equal to this."""
    parts = [np.asarray(c, dtype=np.uint64) for c in censuses]
    if not parts:
        return _EMPTY_U64.copy()
    return np.unique(np.concatenate(parts))
