"""Gateway subsystem (ISSUE 15): the fault-tolerant replicated serving
tier — ROADMAP direction 2's "heavy traffic from millions of users"
availability layer.

- **registry.py** — heartbeating `pio_query_replica` records on the
  shared lifecycle record layer (the fleet worker-record mechanism),
- **identity.py** — durable per-replica identity, which also scopes
  each replica's online fold-in cursor (no shared-cursor double-fold),
- **ring.py** — consistent-hash ring with bounded-load overflow,
- **replica.py** — `ReplicaMember`: registration + heartbeats +
  zero-drop graceful drain for one QueryServer,
- **server.py** — `GatewayServer`: the L7 router (health/SLO-aware
  routing, hedged queries at the rolling p95 mark, failover, drain),
- **autoscale.py** — the closed-loop `Autoscaler` policy + the
  subprocess ReplicaManager for tests,
- **replica_main.py** — the replica subprocess entry.

Import discipline: the gateway runs as a data-plane process — this
package must never import jax (CI guards it).
"""

from predictionio_tpu.gateway.autoscale import (
    Autoscaler,
    AutoscalerConfig,
    ReplicaManager,
    ScaleDecision,
    SubprocessReplicaManager,
)
from predictionio_tpu.gateway.identity import replica_identity
from predictionio_tpu.gateway.registry import (
    REPLICA_ENTITY,
    ReplicaInfo,
    ReplicaRegistry,
)
from predictionio_tpu.gateway.replica import ReplicaConfig, ReplicaMember
from predictionio_tpu.gateway.ring import HashRing
from predictionio_tpu.gateway.server import GatewayConfig, GatewayServer

__all__ = [
    "Autoscaler",
    "AutoscalerConfig",
    "GatewayConfig",
    "GatewayServer",
    "HashRing",
    "REPLICA_ENTITY",
    "ReplicaConfig",
    "ReplicaInfo",
    "ReplicaManager",
    "ReplicaMember",
    "ReplicaRegistry",
    "ScaleDecision",
    "SubprocessReplicaManager",
    "replica_identity",
]
