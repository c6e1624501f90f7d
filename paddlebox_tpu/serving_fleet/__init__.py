"""Serving-fleet resilience: replica routing, supervision, failover.

The fleet layer over the packaged scoring stack (ROADMAP item 2(c)):

  * :mod:`router` — :class:`FleetRouter`, the health-checked front door:
    per-replica healthy/degraded/ejected state machine fed by periodic
    ``/healthz`` + freshness probes, round-robin routing with
    per-request failover, degraded replicas deprioritized-but-kept;
  * :mod:`supervisor` — :class:`ReplicaSupervisor`: spawns/monitors the
    replica processes and restarts crashes with jittered backoff; grows
    (``spawn_replica``, fresh bind-probed port) and shrinks
    (``retire_replica``, never resurrected) the fleet on demand;
  * :mod:`autoscaler` — :class:`FleetAutoscaler` (PR 16): turns the
    fleet's own telemetry (queue depth, admission-wait EWMA, shed rate)
    into spawn/drain-retire decisions with hysteresis + cooldown, and
    runs freshness-gated rolling restarts one replica at a time;
  * admission control itself lives in the server
    (:mod:`paddlebox_tpu.inference.admission`): bounded queue,
    deadline-aware 429 shedding — the fleet never queues into
    saturation, it sheds at the edge.

``python -m paddlebox_tpu.serve --replicas N --router-port P`` wires all
three together.
"""

from paddlebox_tpu.serving_fleet.router import (  # noqa: F401
    DEGRADED,
    EJECTED,
    HEALTHY,
    FleetRouter,
    ReplicaHandle,
)
from paddlebox_tpu.serving_fleet.supervisor import (  # noqa: F401
    ReplicaProc,
    ReplicaSupervisor,
)
from paddlebox_tpu.serving_fleet.autoscaler import (  # noqa: F401
    AutoscalerConfig,
    FleetAutoscaler,
)
