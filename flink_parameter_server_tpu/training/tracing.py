"""Tracing / profiling hooks.

Reference parity (SURVEY.md §5 "Tracing / profiling"): the reference
inherits Flink's web-UI operator metrics; nothing in-repo.  The rebuild's
equivalents are the JAX profiler (Perfetto/XPlane traces of the jitted
step, DMA and collective timelines) plus named scopes so pull/compute/push
phases are attributable inside one fused step.
"""
from __future__ import annotations

import contextlib
import logging
import os
from typing import Iterator

import jax

logger = logging.getLogger(__name__)


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Capture a JAX profiler trace (view in Perfetto / TensorBoard).

    Wrap a handful of steady-state steps, not the whole run — the first
    call inside includes compilation."""
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def scope(name: str):
    """Named scope for phase attribution inside a jitted step: metadata
    on the ``op_name`` of every op traced under it, so a device trace
    can be reduced by phase whatever XLA names its fusions.  The step's
    scopes are ``ps.pull`` / ``ps.compute`` / ``ps.push``
    (``core/transform.make_train_step``) and, inside ``ps.compute``, a
    logic's own (MF: ``ps.state_pull`` / ``ps.state_push``).

    Usage::

        with tracing.scope("ps.pull"):
            pulled = store_mod.pull(spec, table, ids)
    """
    return jax.named_scope(name)


# devices whose memory_stats() raised an UNEXPECTED type — warned once
# per device, not once per poll (device_memory_stats is on gauge-scrape
# cadence) and never swallowed silently
_mem_stats_warned: set = set()


def device_memory_stats() -> dict:
    """Best-effort per-device memory stats (HBM live bytes).

    Uniform contract: every returned device entry carries exactly the
    keys ``bytes_in_use`` and ``peak_bytes`` (ints; 0 when the backend
    reports no value — a consumer never key-checks per platform).
    Backends without the API (CPU raises AttributeError / runtime
    errors) are omitted; anything ELSE raising is logged once per
    device and omitted — an unknown failure must be visible, not
    silently absorbed into an empty dict."""
    out = {}
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except (AttributeError, NotImplementedError,
                jax.errors.JaxRuntimeError):
            stats = None  # backend simply has no memory_stats
        except Exception as e:  # noqa: BLE001 — log once, keep polling
            key = str(d)
            if key not in _mem_stats_warned:
                _mem_stats_warned.add(key)
                logger.warning(
                    "device_memory_stats: %s raised %s: %s "
                    "(suppressing further warnings for this device)",
                    key, type(e).__name__, e,
                )
            stats = None
        if stats:
            out[str(d)] = {
                "bytes_in_use": int(stats.get("bytes_in_use") or 0),
                "peak_bytes": int(
                    stats.get("peak_bytes_in_use")
                    or stats.get("peak_bytes")
                    or 0
                ),
            }
    return out


def register_device_memory_gauges(registry=None) -> int:
    """Register live probe gauges ``device_bytes_in_use{device=...}`` /
    ``device_peak_bytes{device=...}`` (component=train) on the unified
    plane for every device currently reporting stats; returns how many
    devices were wired.  Values resolve at scrape time — the endpoint
    sees CURRENT HBM pressure, not enrollment-time numbers."""
    from ..telemetry import get_registry

    reg = registry if registry is not None else get_registry()
    wired = 0
    for name in device_memory_stats():
        def _probe(key, field):
            return lambda: device_memory_stats().get(key, {}).get(field)

        reg.gauge("device_bytes_in_use", component="train", device=name,
                  fn=_probe(name, "bytes_in_use"))
        reg.gauge("device_peak_bytes", component="train", device=name,
                  fn=_probe(name, "peak_bytes"))
        wired += 1
    return wired


__all__ = [
    "profile_trace",
    "scope",
    "device_memory_stats",
    "register_device_memory_gauges",
]
