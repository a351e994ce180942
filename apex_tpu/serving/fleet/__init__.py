"""apex_tpu.serving.fleet — horizontally scaled serving.

The fleet layer turns one supervised engine into a serving TIER:
:class:`ReplicaFleet` runs N :class:`~apex_tpu.serving.EngineSupervisor`
replicas behind a single ``submit()`` front door with least-loaded
dispatch (:class:`Router`), fleet-wide admission control (an open
breaker removes a replica from the dispatch set;
:class:`FleetUnavailableError` only when none remain), and draining
restarts that migrate in-flight work token-exact to peers so a rebuild
never drops capacity below N−1. :class:`ShardedEngine` is the
scale-up counterpart: the same engine with its decode/prefill programs
tensor-parallel over the device mesh and the KV page pools sharded
on the heads axis. See docs/serving.md#fleet.

On top of the fleet sit the two halves of the train->serve loop
(PR 16): :class:`Autoscaler` grows and shrinks the fleet between
``min_replicas``/``max_replicas`` under live SLO pressure
(docs/serving.md#autoscaling), and :class:`Deployment` rolls freshly
trained checkpoints or LoRA adapters through canary-scored draining
restarts with automatic rollback
(docs/serving.md#continuous-deployment).
"""

from apex_tpu.serving.fleet.autoscale import AutoscaleConfig, Autoscaler
from apex_tpu.serving.fleet.brownout import (
    BROWNOUT_RUNGS,
    BrownoutConfig,
    BrownoutController,
)
from apex_tpu.serving.fleet.deploy import (
    DEPLOY_CANARY,
    DEPLOY_COMPLETE,
    DEPLOY_DRAINING,
    DEPLOY_REJECTED,
    DEPLOY_ROLLED_BACK,
    DEPLOY_ROLLING,
    DEPLOY_ROLLING_BACK,
    DEPLOY_UNLOADING,
    CanaryConfig,
    Deployment,
)
from apex_tpu.serving.fleet.router import (
    REPLICA_ACTIVE,
    REPLICA_DRAINING,
    REPLICA_FAILED,
    REPLICA_PROBING,
    FleetConfig,
    FleetUnavailableError,
    ReplicaFleet,
    Router,
)
from apex_tpu.serving.fleet.quota import (
    QuotaConfig,
    QuotaExceededError,
    QuotaLedger,
    TenantQuota,
)
from apex_tpu.serving.fleet.sharded import ShardedEngine

__all__ = [
    "ReplicaFleet",
    "Router",
    "FleetConfig",
    "FleetUnavailableError",
    "ShardedEngine",
    "AutoscaleConfig",
    "Autoscaler",
    "CanaryConfig",
    "Deployment",
    "REPLICA_ACTIVE",
    "REPLICA_DRAINING",
    "REPLICA_PROBING",
    "REPLICA_FAILED",
    "DEPLOY_ROLLING",
    "DEPLOY_DRAINING",
    "DEPLOY_CANARY",
    "DEPLOY_ROLLING_BACK",
    "DEPLOY_UNLOADING",
    "DEPLOY_COMPLETE",
    "DEPLOY_ROLLED_BACK",
    "DEPLOY_REJECTED",
    "TenantQuota",
    "QuotaConfig",
    "QuotaLedger",
    "QuotaExceededError",
    "BrownoutConfig",
    "BrownoutController",
    "BROWNOUT_RUNGS",
]
