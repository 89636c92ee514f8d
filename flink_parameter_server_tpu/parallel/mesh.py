"""Device-mesh construction for the PS framework.

The reference's two parallelism knobs (SURVEY.md §2 "Parallelism
strategies") map onto named mesh axes:

  * ``workerParallelism``  → the ``dp`` axis: data batches are sharded
    across it, worker-local state is partitioned along it.
  * ``psParallelism``      → the ``ps`` axis: the parameter table is
    row-sharded across it.

A Flink job picks the two independently; here they share one physical mesh
(``dp × ps``) so pull/push collectives ride ICI.  Multi-host scale-out: the
same named axes span hosts via ``jax.distributed`` — shardings are laid out
so the ``ps`` axis stays within a slice (ICI) and only the data-ingestion
edge crosses DCN.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


DP_AXIS = "dp"
PS_AXIS = "ps"


def make_mesh(
    worker_parallelism: Optional[int] = None,
    ps_parallelism: Optional[int] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_names: Tuple[str, str] = (DP_AXIS, PS_AXIS),
) -> Mesh:
    """Build a ``dp × ps`` mesh over the available devices.

    Defaults: use every device; if only one of the two parallelism degrees
    is given the other absorbs the remaining devices; if neither is given
    all devices go to ``dp`` (pure data parallelism, params replicated).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if worker_parallelism is None and ps_parallelism is None:
        worker_parallelism, ps_parallelism = n, 1
    elif worker_parallelism is None:
        assert n % ps_parallelism == 0, (n, ps_parallelism)
        worker_parallelism = n // ps_parallelism
    elif ps_parallelism is None:
        assert n % worker_parallelism == 0, (n, worker_parallelism)
        ps_parallelism = n // worker_parallelism
    assert worker_parallelism * ps_parallelism == n, (
        f"worker_parallelism({worker_parallelism}) * ps_parallelism"
        f"({ps_parallelism}) != device count ({n})"
    )
    arr = np.array(devices).reshape(worker_parallelism, ps_parallelism)
    return Mesh(arr, axis_names)


def worker_count(mesh: Optional[Mesh], dp_axis: str = DP_AXIS) -> int:
    """Workers a mesh holds: the size of its ``dp`` axis; 1 without a mesh
    or without that axis."""
    if mesh is None or dp_axis not in mesh.axis_names:
        return 1
    return mesh.shape[dp_axis]


def single_device_mesh(axis_names: Tuple[str, str] = (DP_AXIS, PS_AXIS)) -> Mesh:
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), axis_names)


__all__ = [
    "DP_AXIS", "PS_AXIS", "make_mesh", "single_device_mesh", "worker_count",
]
