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

  * **pull** = one gather of whole physical rows + the lane slice down to
    the logical row: ``k`` static slices and a ``select`` on ``r % k``
    (one pass over a batch-sized buffer, no per-element gather), in XLA
    (:func:`_sub_row_slice`) or, for a batch of a block or more on a TPU,
    in a Pallas kernel that writes the rows feature-major
    (:func:`sub_row_slice_kernel`),
  * **push** = lane-shift each delta row to its offset (``k`` static pads
    and the same ``select``: :func:`lane_shift_deltas`; for a batch of a
    block or more on a TPU the slice's mirror kernel, which reads the
    deltas feature-major: :func:`lane_shift_kernel`), then scatter-add at
    PHYSICAL row granularity — which is exactly the shape the pallas
    kernels want (width 128).
    Two logical rows sharing a physical row collide in different lanes,
    so the add semantics are unchanged, and Zipf-hot neighbours now
    share windows (fewer HBM round trips, fuller DMAs).

The scatter kernels consume the packed form unmodified; this module's two
kernels are the pull's lane slice and the push's lane shift, each the
other's mirror.  ``ShardedParamStore(layout="packed")`` wires it in.  A
store whose update is a RULE packs too (rule rows of 9 to 64 lanes by
default, DiFacto's 36 three to a row): its push reads whole physical rows,
slices each touched logical row out, runs the rule and writes each touched
physical row back once, the new rows shifted to their windows and merged by
selects (``core/store._rewrite_packed``).

**The lane slice as a kernel** (``core/store.arms``' ``pull``).  XLA
compiles :func:`_sub_row_slice` row-major: ``k`` lane rotates and selects
over every 128-lane register of the gathered rows, then a copy of the
``(n, d)`` result to the feature-major form ``{0,1}`` that whatever reads
it wants (FM at 1,277,952 ids of 17 lanes: 7.3 + 1.1 ms a step on the v5e
for 654 MB read and 87 kept, and no ``jnp`` form of the slice steers it:
PERF.md section 6, PR 42).  :func:`sub_row_slice_kernel` reads a block of
gathered rows once, transposes it in VMEM to ``(128, block)``, where the
``k`` windows are ``d`` SUBLANES each and a block of 128 ids is
``ceil(d / 8)`` registers a window and not 16, selects among them by
``ids % k`` broadcast along sublanes and writes a ``(d, block)`` block of a
``(d, n)`` output, whose transpose, a bitcast, is the ``(n, d)`` result
held feature-major.  Selects only, so the same bits as
:func:`_sub_row_slice`, NaN, infinities and -0.0 included.

**The lane shift as a kernel** (``core/store.arms``' ``shift``).  XLA
compiles :func:`lane_shift_deltas` column-major over the ``(n, 128)``
buffer and then relays it to the row-major rows the scatter-add reads (FM:
2.23 + 1.99 ms a step on the v5e for 87 MB in and 654 MB out, and 0.38 for
the mask in front; PERF.md section 6, PR 51).  :func:`lane_shift_kernel` is
the slice run backwards: it reads a ``(d, block)`` block of the deltas
FEATURE-major, where the step's logic leaves them, lays ``k`` copies of it
down the 128 sublanes (pad sublanes zeros), keeps in every lane the one
window ``ids % k`` names (none for a masked lane, which rides in as -1),
transposes in VMEM and writes a ``(block, 128)`` block of the row-major
``(n, 128)`` rows.  Selects against zeros only, never a 0/1 product.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

LANES = 128
# Gathered rows a grid step of `sub_row_slice_kernel`: 2 MB in, a (d, block)
# block out, each double-buffered by the pipeline.  On the v5e, 1,277,952
# rows of 17 lanes: 1.57 / 1.22 / 1.10 / 1.08 / 1.07 ms at 512 / 1,024 /
# 2,048 / 4,096 / 8,192 (PERF.md section 6, PR 42).
SLICE_BLOCK = 4096


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


def _sub_row_slice(
    rows: Array, ids: Array, row_width: int, width: Optional[int] = None
) -> Array:
    """``rows[i, t*d:(t+1)*d]`` with ``t = ids[i] % k``: ``k`` STATIC lane
    slices and a ``select`` on the sub-row index, one pass over the
    batch-sized buffer.  Never a ``take_along_axis``: that is a gather of
    scalars (~10 ns an element on the TPU, PERF.md section 6, PR 29), and
    never a 0/1 matmul or a masked sum: ``0 * NaN`` would spread one
    non-finite element over its physical row.  ``width``: only the first
    ``width`` lanes of each logical row (the worker's part of a rule
    store's row, ``StoreSpec.worker_width``), cut by the same slices."""
    k, d = pack_k(row_width), row_width
    w = d if width is None else width
    if k == 1:
        return rows[:, :w]
    t = (ids.astype(jnp.int32) % k)[:, None]
    out = rows[:, :w]
    for j in range(1, k):
        out = jnp.where(t == j, rows[:, j * d:j * d + w], out)
    return out


def slice_refusal(n: int, dtype, row_width: int) -> Optional[str]:
    """Why :func:`sub_row_slice_kernel` cannot take ``n`` gathered rows of
    this dtype and logical width (None: it can)."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"rows are {jnp.dtype(dtype).name}, the kernel moves float32"
    if pack_k(row_width) == 1:
        return f"rows of {row_width} lanes lie one to a physical row"
    if n < SLICE_BLOCK:
        return f"{n} rows are under one block of {SLICE_BLOCK}"
    return None


def _slice_kernel(t_ref, rows_ref, out_ref, *, k: int, d: int, w: int):
    # (block, 128) -> (128, block): a window is now d sublanes of every lane,
    # of which the first w are wanted
    by_lane = rows_ref[...].T
    t = jnp.broadcast_to(t_ref[...], (w, t_ref.shape[1]))
    out = by_lane[:w]
    for j in range(1, k):
        out = jnp.where(t == j, by_lane[j * d:j * d + w], out)
    out_ref[...] = out


def sub_row_slice_kernel(
    rows: Array, ids: Array, row_width: int, width: Optional[int] = None,
    *, block: Optional[int] = None, interpret: Optional[bool] = None,
) -> Array:
    """:func:`_sub_row_slice` as one Pallas kernel (the module docstring
    says how), bit for bit; the ``(n, d)`` result is the transpose of the
    kernel's ``(d, n)`` output, so on the TPU it is held feature-major with
    no copy (``(n, width)`` of ``(width, n)`` where only a row's first
    ``width`` lanes are asked for: the kernel writes no other).  ``n`` need
    not be whole blocks: the pipeline cuts the last
    one.  Off the TPU the kernel is interpreted (``interpret=None``: by the
    default backend)."""
    from .row_update import _pallas

    pl, pltpu = _pallas()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d, k = rows.shape[0], row_width, pack_k(row_width)
    w = d if width is None else width
    block = SLICE_BLOCK if block is None else block
    t = (ids.astype(jnp.int32) % k).reshape(1, n)
    out = pl.pallas_call(
        functools.partial(_slice_kernel, k=k, d=d, w=w),
        out_shape=jax.ShapeDtypeStruct((w, n), rows.dtype),
        grid=(pl.cdiv(n, block),),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((block, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((w, block), lambda i: (0, i)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
        name="packed_lane_slice",
    )(t, rows)
    return out.T


def sub_row_slice(
    rows: Array, ids: Array, row_width: int, kernel: bool = False,
    width: Optional[int] = None,
) -> Array:
    """The lane slice of gathered physical rows in the arm the caller read
    (``core/store.arms``' ``pull``), down to a row's first ``width`` lanes
    where the caller wants no more."""
    if kernel:
        return sub_row_slice_kernel(rows, ids, row_width, width)
    return _sub_row_slice(rows, ids, row_width, width)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def packed_pull(
    packed: Array, ids: Array, row_width: int, kernel: bool = False,
    width: Optional[int] = None,
) -> Array:
    """Gather logical rows ``ids`` (pre-clipped) from the packed table:
    one gather of whole 128-lane physical rows, then the lane slice
    (``kernel``: :func:`sub_row_slice`), cut to ``width`` lanes of each row
    where given.  The gather moves whole physical rows whatever ``width``:
    the TPU's gather takes whole rows of its operand, a slice of the table
    in front of it (the leading three of GloVe's five registers) is a COPY
    of those registers (6.7 GB beside cell 13's 11.24 GB table), and a
    gather window narrower than the row (``slice_sizes={1,384}``) is
    expanded to a loop over the ids (both compiled for a v5e: PERF.md
    section 6, PR 59).
    Jitted, so that a pull outside a jitted step (``store.pull(ids)`` by
    hand, a checkpoint's spot check) is one program and not ``3 k`` eager
    ones, each compiled on its first use."""
    ids = ids.astype(jnp.int32)
    # ids are in range, so no pass to fill rows that are not
    phys_vals = jnp.take(
        packed, ids // pack_k(row_width), axis=0, mode="clip"
    )
    return sub_row_slice(phys_vals, ids, row_width, kernel, width)


def lane_shift_deltas(deltas: Array, ids: Array, row_width: int) -> Array:
    """(n, d) deltas -> (n, phys_width) rows shifted to their lane offset.

    Row ``i`` carries ``deltas[i]`` at lanes ``[(ids[i] % k) * d, ... + d)``
    and zeros elsewhere — ready to scatter-add at physical-row granularity.
    ``k`` static pads chosen by a ``select`` on the sub-row index (see
    :func:`_sub_row_slice` for what it must not be).

    At ``k`` = 1 (a row of ``w`` lanes flat in ``W``: 600 in 640) there is
    nothing to shift and this is the pad to whole registers alone, a pass
    over the batch's rows in HBM that only a consumer of ``W``-lane rows
    needs: XLA's ``table.at[].add`` / ``set``.  ``ops/row_update``'s tile
    kernel takes rows of ``w`` <= ``W`` lanes as they are, and the callers
    that go to it skip this function (``core/store._phys_scatter_args``,
    ``core/store._rewrite_packed``).
    """
    n, d = deltas.shape
    assert d == row_width, (d, row_width)
    k = pack_k(d)
    w = phys_width(d)
    out = jnp.pad(deltas, ((0, 0), (0, w - d)))
    if k == 1:
        return out
    t = (ids.astype(jnp.int32) % k)[:, None]
    for j in range(1, k):
        shifted = jnp.pad(deltas, ((0, 0), (j * d, w - (j + 1) * d)))
        out = jnp.where(t == j, shifted, out)
    return out


def _shift_kernel(t_ref, deltas_ref, out_ref, *, k: int, d: int):
    by_lane = deltas_ref[...]  # (d, block): a row's lanes are d sublanes
    block = by_lane.shape[1]
    # every window holds the row, the pad lanes zeros ...
    pad = [jnp.zeros((LANES - k * d, block), by_lane.dtype)] * (k * d < LANES)
    windows = jnp.concatenate([by_lane] * k + pad, axis=0)
    # ... and a lane keeps the window its id names: sublanes [t d, (t + 1) d),
    # none for t = -1
    first = t_ref[...] * d
    sublane = jax.lax.broadcasted_iota(jnp.int32, (LANES, block), 0)
    keep = (sublane >= first) & (sublane < first + d)
    out_ref[...] = jnp.where(keep, windows, jnp.zeros_like(windows)).T


def lane_shift_kernel(
    by_lane: Array, ids: Array, row_width: int, mask: Optional[Array] = None,
    *, block: Optional[int] = None, interpret: Optional[bool] = None,
) -> Array:
    """:func:`lane_shift_deltas` as one Pallas kernel (the module docstring
    says how), bit for bit, for rows packed several to a physical row.  It
    takes the deltas FEATURE-major, ``by_lane`` ``(d, n)``: the transpose
    of the ``(n, d)`` deltas, a bitcast where XLA holds them so.  A lane
    that ``mask`` (``(n,)`` bool) leaves out comes back a row of +0.0: the
    bits of ``lane_shift_deltas(jnp.where(mask[:, None], deltas, 0), ...)``,
    with no pass over the deltas for it.  ``n`` need not be whole blocks;
    off the TPU the kernel is interpreted, as :func:`sub_row_slice_kernel`
    is."""
    from .row_update import _pallas

    pl, pltpu = _pallas()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (d, n), k = by_lane.shape, pack_k(row_width)
    assert d == row_width and k > 1, (d, row_width)
    block = SLICE_BLOCK if block is None else block
    t = ids.astype(jnp.int32) % k
    if mask is not None:
        t = jnp.where(mask, t, -1)
    return pl.pallas_call(
        functools.partial(_shift_kernel, k=k, d=d),
        out_shape=jax.ShapeDtypeStruct((n, LANES), by_lane.dtype),
        grid=(pl.cdiv(n, block),),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((d, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
        name="packed_lane_shift",
    )(t.reshape(1, n), by_lane)


def lane_unshift(rows: Array, ids: Array, row_width: int) -> Array:
    """Inverse of :func:`lane_shift_deltas`: slice each (phys_width,)
    row back down to the (row_width,) slice at its id's lane offset."""
    return _sub_row_slice(rows, ids, row_width)


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
    "slice_refusal",
    "sub_row_slice",
    "sub_row_slice_kernel",
    "lane_shift_deltas",
    "lane_shift_kernel",
    "lane_unshift",
    "packed_phys_ids",
]
