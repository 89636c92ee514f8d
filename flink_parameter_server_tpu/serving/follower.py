"""Follower lookups — the serving plane's read path over replica chains.

The in-process serving stack (snapshot.py/engine.py) reads versioned
snapshots inside the TRAINING process; this module is the other
serving topology: a lookup service that reads the live cluster table
**through the replica chains** (replication/, docs/elastic.md), so
serving traffic keeps flowing while a primary is dead and being failed
over — the "millions of users read from followers" story.

It is a thin façade over a read-routed
:class:`~..cluster.client.ClusterClient`: lookups load-balance across
each shard's chain, honor the follower staleness contract (a lagging
follower's ``err lagging`` falls back to the primary inside the
client), and survive a promotion as a membership refresh — latency,
never an error.  The chaos failover e2e test
(tests/test_replication.py) drives its "zero serving errors
during failover" window through this service.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class ChainLookupResult:
    """One answered lookup batch + its routing provenance."""

    values: np.ndarray  # (B, *value_shape) float32
    epoch: Optional[int]  # membership epoch the routing used


class FollowerLookupService:
    """Serving lookups against a replica-chained cluster.

    Built from a ``membership`` view (the usual case — promotions and
    resizes are then just refreshes) or handed an existing read-routed
    client.  Timeouts default TIGHT: a serving read is latency-bound,
    and the chain gives it somewhere else to go.
    """

    def __init__(
        self,
        membership=None,
        value_shape: Sequence[int] = (),
        *,
        client=None,
        registry=None,
        timeout: float = 5.0,
        connect_timeout: float = 2.0,
        retry_timeout: float = 10.0,
    ):
        if client is None:
            if membership is None:
                raise ValueError(
                    "FollowerLookupService needs membership= (or a "
                    "pre-built read-routed client=)"
                )
            from ..cluster.client import ClusterClient

            client = ClusterClient(
                value_shape=value_shape,
                membership=membership,
                read_replicas=True,
                timeout=timeout,
                connect_timeout=connect_timeout,
                retry_timeout=retry_timeout,
                registry=registry if registry is not None else None,
                worker="serving",
            )
            self._owns_client = True
        else:
            self._owns_client = False
        self._client = client
        self.lookups_served = 0
        self.lookup_errors = 0
        if registry is not False:
            from ..telemetry.registry import get_registry

            reg = registry if registry is not None else get_registry()
            self._c_lookups = reg.counter(
                "replication_serving_lookups_total",
                component="replication",
            )
        else:
            self._c_lookups = None

    def lookup(self, ids) -> ChainLookupResult:
        """Pull the rows for ``ids`` through the chain-routed client;
        every retry/fallback/refresh happens inside — a raised error
        here means the whole chain (followers AND primary) was
        unreachable past the retry budget."""
        ids = np.asarray(ids, np.int64)
        try:
            values = self._client.pull_batch(ids)
        except Exception:
            self.lookup_errors += 1
            raise
        self.lookups_served += 1
        if self._c_lookups is not None:
            self._c_lookups.inc()
        return ChainLookupResult(
            values=values, epoch=self._client._epoch
        )

    def close(self) -> None:
        if self._owns_client:
            self._client.close()


__all__ = ["ChainLookupResult", "FollowerLookupService"]
