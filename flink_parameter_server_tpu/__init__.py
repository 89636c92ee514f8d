"""flink_parameter_server_tpu — a TPU-native parameter-server framework.

A from-scratch re-founding of FlinkML/flink-parameter-server (Scala/Flink)
on JAX/XLA for TPU: the ``transform(data, worker_logic, server_logic)``
abstraction with ``pull(id)`` / ``push(id, delta)`` worker hooks, where the
server-side keyed store is a pjit-sharded HBM array and pull/push compile to
sharded gather / scatter-add over ICI collectives inside one jitted step.

See SURVEY.md at the repo root for the reference structural analysis this
build follows, and README.md for the architecture overview.
"""

from .core.api import (
    ParameterServer,
    ParameterServerClient,
    ParameterServerLogic,
    SimplePSLogic,
    WorkerLogic,
    add_pull_limiter,
)
from .core.batched import BatchedWorkerLogic, PushRequest
from .core.dense import DenseParameterServer, transform_dense
from .core.entities import Pull, PullAnswer, Push, PSToWorker, WorkerToPS
from .core.hybrid import transform_hybrid
from .core.store import GroupSpec, ShardedParamStore, StoreGroup, StoreSpec
from .core.transform import (
    TransformResult,
    transform,
    transform_batched,
    transform_with_model_load,
)
from .cluster import (
    ClusterClient,
    ClusterConfig,
    ClusterDriver,
    ConsistentHashPartitioner,
    ParamShard,
    RangePartitioner,
    ShardServer,
    StalenessClock,
)
from .parallel.mesh import DP_AXIS, PS_AXIS, make_mesh
from .resilience import (
    FaultPlan,
    HealthMonitor,
    RecoveringDriver,
    RestartPolicy,
    StallWatchdog,
    UpdateWAL,
)
from .serving import (
    QueryEngine,
    ServingClient,
    ServingServer,
    ServingService,
    SnapshotManager,
)
from .telemetry import (
    MetricsRegistry,
    SpanTracer,
    TelemetryServer,
    build_run_report,
    get_registry,
    get_tracer,
    prometheus_text,
    write_run_report,
)
from .hotcache import (
    CachedLookupService,
    HotRowCache,
    LeasePolicy,
)
from .training.driver import DriverConfig, StreamingDriver

__version__ = "0.1.0"

__all__ = [
    "ParameterServer",
    "ParameterServerClient",
    "ParameterServerLogic",
    "SimplePSLogic",
    "WorkerLogic",
    "add_pull_limiter",
    "BatchedWorkerLogic",
    "PushRequest",
    "Pull",
    "Push",
    "PullAnswer",
    "WorkerToPS",
    "PSToWorker",
    "ShardedParamStore",
    "StoreGroup",
    "StoreSpec",
    "GroupSpec",
    "TransformResult",
    "transform",
    "transform_batched",
    "transform_with_model_load",
    "transform_hybrid",
    "make_mesh",
    "DP_AXIS",
    "PS_AXIS",
    "DenseParameterServer",
    "transform_dense",
    "DriverConfig",
    "StreamingDriver",
    "QueryEngine",
    "ServingClient",
    "ServingServer",
    "ServingService",
    "SnapshotManager",
    "CachedLookupService",
    "HotRowCache",
    "LeasePolicy",
    "UpdateWAL",
    "RecoveringDriver",
    "RestartPolicy",
    "FaultPlan",
    "HealthMonitor",
    "StallWatchdog",
    "MetricsRegistry",
    "SpanTracer",
    "TelemetryServer",
    "get_registry",
    "get_tracer",
    "prometheus_text",
    "build_run_report",
    "write_run_report",
]
