"""The checked-in SPMD collective catalog: what counts as a collective,
which ones are host-side (thread-tolerant) vs device-entangled, and what
seeds rank taint.

Every entry is a contract the multi-host plane documents in prose and a
hang family a review round has chased by hand:

  * ``KvChannel.allgather`` — "every process must ... call ``allgather``
    the same number of times in the same logical order"
    (parallel/host_plane.py:110).  Host-side by design: it exists so the
    feed-producer THREAD can run planning collectives concurrently with
    the consumer's device step.
  * ``host_allgather`` / ``host_allgather_varlen`` /
    ``multihost_utils.process_allgather`` — device collectives behind a
    host-call surface; "the census allgather is a collective that must
    run on the main thread" (parallel/sharded_table.py:228), because two
    threads enqueueing device collectives in racing order across
    processes deadlocks the per-device queues (host_plane.py module
    docstring).
  * ``TcpShuffler.exchange`` — the pass-scoped shuffle is a collective
    over workers (every worker must exchange every round); socket
    transport, thread-tolerant (datasets load on reader threads).
  * ``ShardedSparseTable.flush`` — on the multi-host path the write-back
    barrier sits between lockstep pass collectives; only resolved
    receivers count (``SparseTable.flush`` alone is process-local).
  * ``gather_fleet_snapshot`` — the pass-boundary metric allgather over
    the coordination KV ("Every rank participates (lockstep, like the
    collectives)", parallel/trainer.py _GroupPass.observe, entered from
    the observe stage of train/pass_loop.py run_pass).
  * ``ShardedSparseTable.broadcast_hot_rows`` — hot-promotion rows ride
    the census channel as keycodec frames; every rank contributes and
    receives in lockstep inside ``begin_pass`` (main thread, between the
    census gather and the device step).  The device half of hot realize —
    the hot-gradient ``all_gather``+fold and the ``pmax`` lr fold in
    ``trainer.hybrid_hot_update`` — are plain ``lax.*`` entries below.
  * ``lax.psum``/``pmean``/``ppermute``/``all_gather``/``all_to_all`` —
    device collectives inside ``shard_map`` bodies; they participate in
    sequence/divergence analysis and in the mesh-axis binding check.

Rank-taint seeding: ``jax.process_index()`` / ``lax.axis_index()``
calls, parameters and attributes conventionally named for a rank, and
env reads of rank-shaped variables.  ``process_count()``/``world`` are
deliberately NOT divergence seeds: the world size is the same value on
every rank, so ``if is_multiprocess(): gather()`` is the rank-UNIFORM
gate the whole codebase is built on, not a divergence.
"""

from __future__ import annotations

import re
from dataclasses import dataclass


@dataclass(frozen=True)
class CollectiveSpec:
    """One collective operation the SPMD passes recognize."""

    op: str                      # method/function base name
    kind: str = "host"           # host | device
    classes: frozenset = frozenset()  # project classes owning the method
    require_class: bool = False  # only fire on a RESOLVED receiver class
    thread_safe: bool = False    # legal on Thread/executor paths
    why: str = ""                # one-line rationale for messages


#: ``recv.op(...)`` method-call collectives.  When the receiver's class
#: resolves through the call graph it must be one of ``classes`` (or a
#: subclass); an unresolvable receiver matches by name unless
#: ``require_class`` — the names are unique to the collective surface, so
#: fixtures and new call sites are covered without annotations.
METHOD_COLLECTIVES = {
    "allgather": CollectiveSpec(
        op="allgather", classes=frozenset({"KvChannel"}), thread_safe=True,
        why="ordered KV-channel gather (host_plane.py:110 lockstep contract)",
    ),
    "exchange": CollectiveSpec(
        op="exchange",
        classes=frozenset({
            "TcpShuffler", "_InProcessShuffler", "InProcessShuffleGroup",
            "CensusExchange",
        }),
        thread_safe=True,
        why="pass-scoped shuffle round / census gather: every worker must "
            "exchange",
    ),
    "gather_bytes": CollectiveSpec(
        op="gather_bytes", classes=frozenset({"KvChannel"}),
        thread_safe=True,
        why="ordered KV-channel byte gather (same lockstep contract as "
            "allgather; the census wire's transport face)",
    ),
    "flush": CollectiveSpec(
        op="flush", classes=frozenset({"ShardedSparseTable"}),
        require_class=True,
        why="multi-host write-back barrier between lockstep collectives",
    ),
    "broadcast_hot_rows": CollectiveSpec(
        op="broadcast_hot_rows", classes=frozenset({"ShardedSparseTable"}),
        why="hot-promotion row broadcast on the census channel: every "
            "rank contributes its owned shards' frames and every rank "
            "receives all of them (begin_pass lockstep, main thread)",
    ),
}

#: bare / dotted function-call collectives, matched on the last dotted
#: segment (``host_allgather(...)``, ``multiprocess.host_allgather(...)``).
FUNCTION_COLLECTIVES = {
    "host_allgather": CollectiveSpec(
        op="host_allgather",
        why="device collective (process_allgather) behind a host call",
    ),
    "host_allgather_varlen": CollectiveSpec(
        op="host_allgather_varlen",
        why="two chained device collectives (sizes, then payload)",
    ),
    "process_allgather": CollectiveSpec(
        op="process_allgather",
        why="multihost_utils.process_allgather IS a device collective "
            "(host_plane.py module docstring)",
    ),
    "gather_fleet_snapshot": CollectiveSpec(
        op="gather_fleet_snapshot", thread_safe=True,
        why="pass-boundary metric gather: every rank participates in "
            "lockstep (parallel/trainer.py fleet snapshot)",
    ),
}

#: ``lax.*`` device collectives — events inside shard_map bodies; their
#: axis arguments feed the spmd-mesh-axis check.
DEVICE_COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "psum_scatter",
})

#: ops whose axis argument spmd-mesh-axis validates, mapped to the
#: positional index of that argument (kw ``axis_name``/``axis_names``
#: always wins).
AXIS_CONSUMERS = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "ppermute": 1,
    "all_gather": 1, "all_to_all": 1, "psum_scatter": 1,
    "axis_index": 0, "axis_size": 0, "pcast": 1,
}

#: parameter names treated as carrying THIS process's rank.
RANK_PARAMS = frozenset({
    "rank", "pid", "worker_id", "process_id", "proc_id", "rank_id",
    "process_index",
})

#: attribute names (leading underscores stripped) treated as rank reads:
#: ``self._rank``, ``table.worker_id``, ``device.process_index`` ...
RANK_ATTRS = frozenset({
    "rank", "worker_id", "process_id", "proc_id", "rank_id",
    "process_index",
})

#: call base names whose RESULT is this process's rank.
RANK_CALLS = frozenset({"process_index", "axis_index", "getpid"})

#: env keys whose value is rank-shaped (flight._default_rank reads
#: PBOX_PROCESS_ID; launchers export *_RANK variables).
RANK_ENV_RE = re.compile(r"RANK|PROCESS_ID|WORKER_ID", re.IGNORECASE)
