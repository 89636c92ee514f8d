"""shmem/: zero-copy shared-memory transport for co-located shards.

PR 13's binary framing collapsed the codec share; what remained of a
pull round (~78%, p50 0.32 ms on this host) was TCP loopback's
scheduler-wakeup + kernel-copy floor — the wrong substrate between
processes on ONE host.  This package swaps the substrate and nothing
else: per-(client, shard-proc) SPSC ring pairs in
``multiprocessing.shared_memory`` carrying the SAME versioned frame
layout as ``utils/frames.py`` byte for byte, negotiated per
connection (``hello shm v=1`` → binary TCP → lines) with automatic
fallback for non-co-located peers.  See docs/shmem.md; not measured
on the chip (no cell).

Layering: ``ring`` and ``doorbell`` are dependency-free substrate;
``pump`` is the server half (imported lazily by ``utils/net.py`` on
the first shm hello); ``channel`` is the client half (imported lazily
by ``cluster/client.py`` on an shm dial).  Import THIS package freely
— it pulls in the cluster client, so the server-side never imports it
at module scope.
"""
from .channel import (
    DEFAULT_CAPACITY,
    ShmShardConnection,
    available,
    hello_shm_line,
    shm_usable,
)
from .doorbell import Doorbell
from .pump import ShmServerPump
from .ring import (
    K_FRAME,
    K_LINE,
    K_WRAP,
    RingClosed,
    RingCorruption,
    RingTimeout,
    ShmRing,
)

__all__ = [
    "DEFAULT_CAPACITY",
    "Doorbell",
    "K_FRAME",
    "K_LINE",
    "K_WRAP",
    "RingClosed",
    "RingCorruption",
    "RingTimeout",
    "ShmRing",
    "ShmServerPump",
    "ShmShardConnection",
    "available",
    "hello_shm_line",
    "shm_usable",
]
