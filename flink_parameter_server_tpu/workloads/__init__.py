"""workloads/ — the workload-generic runtime (ROADMAP item 5).

Heterogeneous learners (MF, the PA classifier, streaming sketches) as
first-class citizens of the full cluster stack: one contract
(:class:`~.base.Workload`), one registry (drive any workload by name
from the nemesis runner, the soak harness, the examples and
psctl), per-workload serving verbs, and per-workload parity oracles —
bitwise for PA, integer-exact for sketches.  See docs/workloads.md.
"""
from .base import (
    DenseCombineLogic,
    Workload,
    WorkloadParams,
)
from .registry import (
    WorkloadRegistry,
    create_workload,
    get_workload_registry,
    workload_names,
)
from .runtime import (
    build_cluster_driver,
    resolve_workload,
    run_streaming,
    serve_workload,
    workload_table,
)
from .serving import WorkloadServingClient, WorkloadServingServer

__all__ = [
    "DenseCombineLogic",
    "Workload",
    "WorkloadParams",
    "WorkloadRegistry",
    "WorkloadServingClient",
    "WorkloadServingServer",
    "build_cluster_driver",
    "create_workload",
    "get_workload_registry",
    "resolve_workload",
    "run_streaming",
    "serve_workload",
    "workload_names",
    "workload_table",
]
