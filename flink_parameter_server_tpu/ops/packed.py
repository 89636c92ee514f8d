"""Lane-packed table layout: k narrow rows per 128-lane physical row.

Reference parity: the reference's stores hold *narrow* values — MF item
factors (dim 64), FM rows (dim 17), PA scalar weights — as JVM objects
where row width is free (SURVEY.md §2 #3, #7, #9).  On TPU, width is NOT
free: the VPU/MXU lane width is 128 and real Mosaic requires 128-aligned
minor dims for dynamic-offset DMA.
A (capacity, 17) table either wastes 7/8 of every vector register or is
ineligible for the pallas scatter kernel entirely.

The TPU-native answer is a *packed physical layout*: ``k = 128 // d``
logical rows live side-by-side in one ``(phys_capacity, 128)`` physical
row.  Logical row ``r`` maps to physical row ``r // k``, lane offset
``(r % k) * d``:

  * **pull** = one physical-row gather + one ``take_along_axis`` lane
    slice (both vectorized XLA gathers, batch-sized),
  * **push** = lane-shift each delta row to its offset (one batch-sized
    gather), then scatter-add at PHYSICAL row granularity — which is
    exactly the shape the pallas sorted-window kernel wants (width 128).
    Two logical rows sharing a physical row collide in different lanes,
    so the add semantics are unchanged, and Zipf-hot neighbours now
    share windows (fewer HBM round trips, fuller DMAs).

Everything here is pure XLA; the pallas kernel consumes the packed form
unmodified.  ``ShardedParamStore(layout="packed")`` wires it in.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

LANES = 128


def pack_k(row_width: int) -> int:
    """Logical rows per 128-lane physical row (1 when width >= 128)."""
    if row_width <= 0:
        raise ValueError(f"row width must be positive, got {row_width}")
    return max(1, LANES // row_width)


def phys_width(row_width: int) -> int:
    """Physical lane width: 128 for narrow rows, else the padded width."""
    if row_width >= LANES:
        return ((row_width + LANES - 1) // LANES) * LANES
    return LANES


def phys_rows(capacity: int, row_width: int) -> int:
    """Physical rows needed for ``capacity`` logical rows."""
    k = pack_k(row_width)
    return (capacity + k - 1) // k


def pack_table(values: Array, capacity_phys: Optional[int] = None) -> Array:
    """(capacity, d) logical values -> (capacity_phys, phys_width) packed."""
    capacity, d = values.shape
    k = pack_k(d)
    w = phys_width(d)
    if capacity_phys is None:
        capacity_phys = phys_rows(capacity, d)
    pad_rows = capacity_phys * k - capacity
    v = jnp.pad(values, ((0, pad_rows), (0, 0)))
    v = v.reshape(capacity_phys, k * d)
    return jnp.pad(v, ((0, 0), (0, w - k * d)))


def unpack_table(packed: Array, capacity: int, row_width: int) -> Array:
    """(capacity_phys, phys_width) packed -> (capacity, d) logical values."""
    capacity_phys, w = packed.shape
    k = pack_k(row_width)
    v = packed[:, : k * row_width].reshape(capacity_phys * k, row_width)
    return v[:capacity]


def packed_pull(packed: Array, ids: Array, row_width: int) -> Array:
    """Gather logical rows ``ids`` (pre-clipped) from the packed table."""
    k = pack_k(row_width)
    ids = ids.astype(jnp.int32)
    phys_vals = jnp.take(packed, ids // k, axis=0)  # (n, phys_width)
    if k == 1:
        return phys_vals[:, :row_width]
    cols = (ids % k)[:, None] * row_width + jnp.arange(row_width)[None, :]
    return jnp.take_along_axis(phys_vals, cols, axis=1)


def lane_shift_deltas(deltas: Array, ids: Array, row_width: int) -> Array:
    """(n, d) deltas -> (n, phys_width) rows shifted to their lane offset.

    Row ``i`` carries ``deltas[i]`` at lanes ``[(ids[i] % k) * d, ... + d)``
    and zeros elsewhere — ready to scatter-add at physical-row granularity.
    """
    n, d = deltas.shape
    assert d == row_width, (d, row_width)
    k = pack_k(d)
    w = phys_width(d)
    if k == 1:
        return jnp.pad(deltas, ((0, 0), (0, w - d)))
    t = (ids.astype(jnp.int32) % k)[:, None]  # (n, 1) sub-row index
    lane = jnp.arange(w)[None, :]  # (1, w)
    src = lane - t * d  # source column per output lane
    valid = (src >= 0) & (src < d)
    padded = jnp.pad(deltas, ((0, 0), (0, w - d)))
    out = jnp.take_along_axis(padded, jnp.clip(src, 0, w - 1), axis=1)
    return jnp.where(valid, out, jnp.zeros_like(out))


def lane_unshift(rows: Array, ids: Array, row_width: int) -> Array:
    """Inverse of :func:`lane_shift_deltas`: slice each (phys_width,)
    row back down to the (row_width,) slice at its id's lane offset."""
    k = pack_k(row_width)
    if k == 1:
        return rows[:, :row_width]
    cols = (
        (ids.astype(jnp.int32) % k)[:, None] * row_width
        + jnp.arange(row_width)[None, :]
    )
    return jnp.take_along_axis(rows, cols, axis=1)


def packed_phys_ids(ids: Array, row_width: int) -> Array:
    """Logical ids -> physical row ids (sorting by these keeps id order)."""
    return ids.astype(jnp.int32) // pack_k(row_width)


__all__ = [
    "LANES",
    "pack_k",
    "phys_width",
    "phys_rows",
    "pack_table",
    "unpack_table",
    "packed_pull",
    "lane_shift_deltas",
    "lane_unshift",
    "packed_phys_ids",
]
