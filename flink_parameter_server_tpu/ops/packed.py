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

**Both kernels a field at a time** (:func:`by_field`; PERF.md section 6, PR
63).  A logic whose batch is ``(B, K)``, ``B`` examples of ``K`` keys, and
that sums over the ``K`` wants ``B`` as the MINOR axis of its buffers: on the
TPU XLA then holds ``(K, B, d)`` as ``f32[d, K, B]``, ``K`` on the sublanes of
a tile, lane-dense.  That is no bitcast of the flat kernels' ``f32[d, K B]``
(a row's LANES on the sublanes; with ``d`` > 8 no three-axis layout is those
bytes), and the gather wants the rows in the batch's own order, example-major
(neighbours that name one row cost it a quarter more).  So each kernel has a
form whose grid step moves a block of examples of all ``K`` fields, the
flat body once a field: :func:`turned_slice_kernel` takes field ``f``'s rows
out of the example-major block with a strided load and writes sublane ``f``
of a ``(d, K, B)`` output; :func:`lane_shift_kernel` handed ``(d, K, B)``
reads sublane ``f`` and writes field ``f``'s ``(block, 128)`` rows.  The same
bits as the flat forms.  Their loop over the fields is a ``fori_loop`` of
``TURN_GROUP`` fields a trip (:func:`_each_field`): with 39 static bodies a
warm set-up pays 1.4 s a kernel of tracing and lowering on the chip's host.
At FM's size on the v5e the two relayouts between the flat kernels and the
logic took 6.4 ms a step; in the step these take 0.87 ms each where the
flat ones take 1.08 and 1.21.
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
# Examples a grid step of the two kernels that move a batch a field at a time
# (`by_field`).  `turned_slice_kernel`: `TURN_BLOCK x K` gathered rows in, a
# (d, K, TURN_BLOCK) block out; `lane_shift_kernel` of three axes the reverse,
# at `FIELDED_BLOCK`.  Their loop over the fields is bound by a trip's latency,
# not by bytes, so what pays is more work a trip: in cell 2's step the slice
# takes 2.11 / 1.08 / 0.87 ms at 128 x 1 / 256 x 1 / 256 x 3 (examples x
# fields a trip) and the shift 2.04 / 1.72 / 1.33, and 0.87 at 128 x 3, the
# 39 static bodies' time; 13 fields a trip cost 0.2 s more of tracing
# (PERF.md section 6, PR 63, calls H and J).
TURN_BLOCK = 256
FIELDED_BLOCK = 128
# Fields a trip of the kernels' loop over the fields (`_each_field`).
TURN_GROUP = 3
# The most fields a block has: 40 fields of 256 examples are 5.2 MB of rows,
# twice in VMEM (`tests/test_tpu_compile.py` compiles both kernels at 40 for a
# described v5e; Criteo's 39 ran on the chip).
TURN_FIELDS = 40


def by_field(fields: int, batch: int) -> bool:
    """Whether the two kernels take a batch of ``fields x batch`` lanes a
    FIELD at a time (:func:`turned_slice_kernel`; :func:`lane_shift_kernel`
    of three axes; ``core/store.arms`` asks, of a batch whose logic says it
    is such a block): the batch in whole blocks of both kernels
    (:data:`TURN_BLOCK`, :data:`FIELDED_BLOCK` lanes), and no more than
    :data:`TURN_FIELDS` fields, whose rows a block holds."""
    whole = batch % TURN_BLOCK == 0 and batch % FIELDED_BLOCK == 0
    return whole and 0 < fields <= TURN_FIELDS


def _each_field(fields: int, one_field) -> None:
    """``one_field(f)`` for every field of a kernel's block, :data:`TURN_GROUP`
    fields a trip of a ``fori_loop`` and the odd ones after it: a trip's
    fields are independent chains the scheduler interleaves.  A loop, not
    ``fields`` copies of the body: a warm set-up traces and lowers the kernel
    anew, 1.4 s a kernel unrolled 39 times on the chip's host (cell 2's
    ``setup_s`` 16.2 -> 19.0: PERF.md section 6, PR 63)."""
    trips = fields // TURN_GROUP

    def trip(g, carry):
        for j in range(TURN_GROUP):
            one_field(g * TURN_GROUP + j)
        return carry

    if trips:
        jax.lax.fori_loop(0, trips, trip, 0)
    for f in range(trips * TURN_GROUP, fields):
        one_field(f)


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
    if d == 1 and rows.dtype.itemsize == 4:
        # SCALAR rows, 128 to a physical row (a graph's neighbour ids): the
        # one lane `t` names, every other lane a zero WORD, summed as words,
        # so the scalar's bits whatever they are (one word and 127 zeros: no
        # float add ever sees a NaN or a -0.0).  One pass over the gathered
        # rows where the selects below would be 127
        lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        words = jax.lax.bitcast_convert_type(rows, jnp.int32)
        picked = jnp.where(lane == t, words, 0).sum(axis=1, keepdims=True)
        return jax.lax.bitcast_convert_type(picked, rows.dtype)
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


def _turned_slice_kernel(t_ref, rows_ref, out_ref, *, fields: int, **widths):
    from .row_update import _pallas

    pl, examples = _pallas()[0], t_ref.shape[1]

    def one_field(f):
        # every `fields`-th row from row f on is field f of the block's
        # examples; its slice is sublane f of the (w, fields, examples) block
        _slice_kernel(
            t_ref.at[pl.ds(f, 1), :],
            rows_ref.at[pl.ds(f, examples, stride=fields), :],
            out_ref.at[:, f, :], **widths)

    _each_field(fields, one_field)


def turned_slice_kernel(
    rows: Array, ids: Array, row_width: int, width: Optional[int] = None,
    *, block: Optional[int] = None, interpret: Optional[bool] = None,
) -> Array:
    """The lane slice of the rows gathered for a TWO-axis key block ``ids``
    ``(B, K)``, ``rows`` ``(B K, 128)`` in the block's C order, handed back
    TURNED, ``(K, B, w)``: ``out[f, b]`` is the slice of row ``b K + f``.
    :func:`sub_row_slice_kernel`'s body a field: a grid step reads the
    gathered rows of ``block`` examples, takes field ``f``'s with a strided
    load (every ``K``-th row), transposes, selects the window and writes
    sublane ``f`` of a ``(w, K, block)`` block of a ``(w, K, B)`` output,
    whose transpose to ``(K, B, w)`` is a bitcast on the TPU: the form XLA
    gives a logic's buffers whose minor axis is the batch.  What it is for:
    the gather runs in the ORDER of the key block, and the TPU's gather
    pays for neighbours that name one row (cell 2's, example-major 12.98 ms
    a step, field-major 16.49: a field's few rows 32,768 lanes on end;
    PERF.md section 6, PR 63), while a logic that sums over its fields
    wants them on a leading axis.  ``B`` in whole blocks of ``block``
    (:data:`TURN_BLOCK`) examples; bit for bit
    ``_sub_row_slice(rows, ids.reshape(-1), ...)`` turned."""
    from .row_update import _pallas

    pl, pltpu = _pallas()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (b, fields), d, k = ids.shape, row_width, pack_k(row_width)
    w = d if width is None else width
    block = TURN_BLOCK if block is None else block
    assert rows.shape[0] == b * fields and b % block == 0, (rows.shape, b)
    out = pl.pallas_call(
        functools.partial(_turned_slice_kernel, k=k, d=d, w=w, fields=fields),
        out_shape=jax.ShapeDtypeStruct((w, fields, b), rows.dtype),
        grid=(b // block,),
        in_specs=[
            pl.BlockSpec((fields, block), lambda i: (0, i)),
            pl.BlockSpec((block * fields, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((w, fields, block), lambda i: (0, 0, i)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
        name="packed_lane_slice_turned",
    )((ids.astype(jnp.int32) % k).T, rows)
    return jnp.transpose(out, (1, 2, 0))


def sub_row_slice(
    rows: Array, ids: Array, row_width: int, kernel: bool = False,
    width: Optional[int] = None, turned: bool = False,
) -> Array:
    """The lane slice of gathered physical rows in the arm the caller read
    (``core/store.arms``' ``pull``), down to a row's first ``width`` lanes
    where the caller wants no more.  ``turned`` (the arm
    ``packed_kernel_by_field``): ``ids`` a key block of two axes ``(B, K)``,
    ``rows`` in its C order, the slices handed back ``(K, B, width)``
    (:func:`turned_slice_kernel`)."""
    if turned:
        return turned_slice_kernel(rows, ids, row_width, width)
    if kernel:
        return sub_row_slice_kernel(rows, ids, row_width, width)
    return _sub_row_slice(rows, ids, row_width, width)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def packed_pull(
    packed: Array, ids: Array, row_width: int, kernel: bool = False,
    width: Optional[int] = None, turned: bool = False,
) -> Array:
    """Gather logical rows ``ids`` (pre-clipped) from the packed table:
    one gather of whole 128-lane physical rows, then the lane slice
    (``kernel``: :func:`sub_row_slice`), cut to ``width`` lanes of each row
    where given.  ``ids`` flat give ``(n, width)``; ``turned``, a key block
    of two axes ``(B, K)`` is gathered in its C order and handed back ``(K,
    B, width)`` (:func:`sub_row_slice`).  The gather moves whole physical
    rows whatever ``width``:
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
        packed, ids.reshape(-1) // pack_k(row_width), axis=0, mode="clip"
    )
    return sub_row_slice(phys_vals, ids, row_width, kernel, width, turned)


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


def _fielded_shift_kernel(t_ref, deltas_ref, out_ref, *, k: int, d: int):
    from .row_update import _pallas

    pl = _pallas()[0]

    def one_field(f):
        # sublane f of every one of the d planes: field f's (d, block)
        _shift_kernel(
            t_ref.at[pl.ds(f, 1), :], deltas_ref.at[:, f, :], out_ref.at[f],
            k=k, d=d)

    _each_field(t_ref.shape[0], one_field)


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
    is.

    ``by_lane`` of THREE axes ``(d, K, B)`` (``ids`` and ``mask`` ``(K,
    B)``) is the same batch as its flattening to ``(d, K B)``, and gives the
    same ``(K B, 128)`` rows, read where a logic whose minor axis is the
    batch leaves its deltas: on the TPU XLA holds ``(K, B, d)`` deltas as
    ``f32[d, K, B]``, ``K`` on the sublanes, which no bitcast makes the
    ``(d, K B)`` operand (that has a row's LANES on the sublanes: a pad and
    a copy of the batch, 0.55 ms a step in cell 2; PERF.md section 6, PR
    63).  The kernel's body a field, ``B`` in whole blocks of
    :data:`FIELDED_BLOCK` lanes."""
    from .row_update import _pallas

    pl, pltpu = _pallas()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d, k = by_lane.shape[0], pack_k(row_width)
    assert d == row_width and k > 1, (d, row_width)
    t = ids.astype(jnp.int32) % k
    if mask is not None:
        t = jnp.where(mask, t, -1)
    if by_lane.ndim == 3:
        fields, b = ids.shape
        block = FIELDED_BLOCK if block is None else block
        assert b % block == 0, (b, block)
        return pl.pallas_call(
            functools.partial(_fielded_shift_kernel, k=k, d=d),
            out_shape=jax.ShapeDtypeStruct((fields, b, LANES), by_lane.dtype),
            grid=(b // block,),
            in_specs=[
                pl.BlockSpec((fields, block), lambda i: (0, i)),
                pl.BlockSpec((d, fields, block), lambda i: (0, 0, i)),
            ],
            out_specs=pl.BlockSpec((fields, block, LANES), lambda i: (0, i, 0)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=interpret,
            name="packed_lane_shift_fielded",
        )(t, by_lane).reshape(fields * b, LANES)
    n = by_lane.shape[1]
    block = SLICE_BLOCK if block is None else block
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
    "turned_slice_kernel",
    "by_field",
    "lane_shift_deltas",
    "lane_shift_kernel",
    "lane_unshift",
    "packed_phys_ids",
]
