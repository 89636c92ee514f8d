"""Intra-batch duplicate-id handling — the "combination sender" layer.

Reference parity (SURVEY.md §2 #6, §7 step 4): the reference's batching
("combination") senders buffer pull/push messages and flush them combined
on count/timer triggers.  In the batched TPU model the *microbatch itself*
is the combination buffer; what remains of the concern is how duplicate
ids inside one microbatch combine.

By default deltas for the same id SUM (exact minibatch SGD — every
gradient was computed at the same pulled snapshot).  Under Zipf-hot id
distributions (word2vec, Criteo) a hot id can appear hundreds of times per
batch, making its effective step ~count × lr and destabilising training at
learning rates that are fine sequentially.  ``occurrence_scale`` gives the
mean-combining alternative: scale each lane's delta by 1/count(id) so a
hot id takes one averaged step per batch — bounded regardless of skew.
The counts come from a sort of the batch's own keys (``occurrence_counts``),
as the rule store's per-row sums do (``combine_runs``): on the device
nothing here is as long as the table.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


_INT32_MAX = np.int32(np.iinfo(np.int32).max)


def occurrence_counts(
    ids: Array, capacity: int, mask: Optional[Array] = None
) -> Array:
    """Per-lane occurrence count of each lane's id within the batch.

    ``ids``: any-shape int array; returns same-shape float32 counts, each
    lane's the number of COUNTING lanes that hold its id.  A lane counts if
    its id names a row (``0 <= id < capacity``) and ``mask`` (same shape),
    if given, is true there; every other lane (a dead lane's -1, an id past
    the table, a masked lane whatever it holds) counts nothing and reads 1.

    Work and memory go with the batch, never with ``capacity`` (2^30 hash
    spaces are fine): one sort of (key, stream position) with the lanes
    that do not count keyed last, the length of each run of one key from
    the run's first and last index (two prefix scans over the run
    boundaries), and a second sort on the carried positions that brings the
    lengths back to stream order.  On the TPU a sort of a batch's keys is
    about 1 ns a lane where one random access to a counter is 7-9, whatever
    the counter's length (PERF.md section 6, PR 39).
    """
    flat = ids.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    if n == 0:
        return jnp.ones(ids.shape, jnp.float32)
    counts_lane = (flat >= 0) & (flat < capacity)
    if mask is not None:
        counts_lane = counts_lane & mask.reshape(-1).astype(bool)
    lane = jnp.arange(n, dtype=jnp.int32)
    key, pos = jax.lax.sort(
        (jnp.where(counts_lane, flat, _INT32_MAX), lane), num_keys=1
    )
    edge = key[1:] != key[:-1]
    true = jnp.ones((1,), bool)
    first = jnp.where(jnp.concatenate([true, edge]), lane, 0)
    last = jnp.where(jnp.concatenate([edge, true]), lane, n - 1)
    # prefix max of the run starts, suffix min of the run ends, as log2 n
    # shifted max / min (alone on the v5e at 233,472 lanes 0.02 ms and a
    # second to compile; ``lax.cummax`` / ``cummin`` lower to reduce-windows
    # that take 0.11 ms and 47 s: PERF.md section 6, PR 39)
    d = 1
    while d < n:
        first = jnp.maximum(first, jnp.pad(first[:-d], (d, 0)))
        last = jnp.minimum(
            last, jnp.pad(last[d:], (0, d), constant_values=n - 1)
        )
        d *= 2
    length = jnp.where(key == _INT32_MAX, 1, last - first + 1)
    # (the positions are distinct: nothing for a stable sort to keep)
    _, counts = jax.lax.sort((pos, length), num_keys=1, is_stable=False)
    return counts.astype(jnp.float32).reshape(ids.shape)


def occurrence_scale(
    ids: Array, capacity: int, mask: Optional[Array] = None
) -> Array:
    """1/count(id) per lane: turns duplicate-id delta *sums* into *means*."""
    return 1.0 / occurrence_counts(ids, capacity, mask)


# Rows up to this many lanes wide ride through a sort as operands of their
# own, and their runs are summed along the sorted lanes (`combine_runs`' arm
# "sort"; `core/store.arms` reads this, nothing here does).  Alone on the v5e,
# 1,277,952 lanes (PERF.md section 6, PR 34): carried, rows of 1 / 3 / 4
# lanes sort in 2.8 / 3.5 / 4.0 ms; at 8 lanes it is 6.8, and the carrying
# sort takes 96 s to compile (40 s at 3 lanes).  Wider rows are neither
# sorted nor permuted: `_wide_runs` scatter-adds them where they lie.
SORT_CARRIES_LANES = 4


def _sorted_by_id(ids: Array, rows: Array) -> Tuple[Array, Array]:
    """``ids`` ascending and the ``(n, w)`` ``rows`` in that order, the
    rows' lanes carried through the sort as operands."""
    out = jax.lax.sort((ids,) + tuple(rows.T), num_keys=1)
    return out[0], jnp.stack(out[1:], axis=1)


def _shifted(cols: Array, d: int) -> Array:
    """``cols`` (w, n) moved ``d`` lanes to the right, zeros coming in."""
    return jnp.concatenate(
        [jnp.zeros((cols.shape[0], d), cols.dtype), cols[:, :-d]], axis=1
    )


def _ranked(ids: Array, is_stable: bool):
    """One sort of ``(ids, lane)`` and the runs it shows: ``(sorted_ids,
    order, starts, rank)``, ``order`` each sorted lane's place in the
    stream, ``starts`` true where a run of one id begins, ``rank`` the
    prefix sum of the starts (by doubling, on one int32 vector): a lane's
    run number, from 1."""
    n = ids.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    sorted_ids, order = jax.lax.sort(
        (ids, lane), num_keys=1, is_stable=is_stable)
    starts = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    rank = starts.astype(jnp.int32)
    d = 1
    while d < n:
        rank = rank + jnp.pad(rank[:-d], (d, 0))
        d *= 2
    return sorted_ids, order, starts, rank


def combine_runs(
    ids: Array, vals: Array, sentinel: int, arm: str,
    *, interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Optional[Array]]:
    """Sum the rows of ``vals`` (n, w) that share an id: ``(row_ids, sums,
    writes)``, the first two of the batch's static length.  The distinct
    ids come first, in
    ascending order, each with its run's total; the rest of ``row_ids`` is
    ``sentinel`` (an id no row has, and larger than any: lanes to drop carry
    it coming in).  A run of any length costs what the batch does.  ``arm``
    names the form that runs; which one a store's push gets is
    ``core/store.arms``' to say (its ``combine`` field), nothing here tests
    a width:

    - ``"sort"`` (rows a sort carries): one sort
      that carries the values, ``log2 n`` shifted adds (a segmented prefix
      sum by doubling, which sums each run as a balanced tree, the batch's
      lanes along the minor axis), and a second sort that moves the lanes
      ending a run to the front;
    - ``"scatter_add"`` (wider rows off a TPU, under ``dp`` > 1, bfloat16):
      :func:`_wide_runs`' ONE scatter-add in the order of the stream,
      ``np.add.at``'s own additions, into a zeroed ``(n, w)`` block: the
      sums come back ``w`` lanes wide;
    - ``"tile_kernel"`` (float32 rows of more than 128 lanes: a
      rule store's flat wide row, at the width the push holds it, GloVe's
      602): the rows permuted once into sorted order and added, ``w`` lanes
      a row, into a zeroed block of whole registers by the TILE kernel, in
      the order of the stream (:func:`_tile_sums`; PERF.md section 6, PRs
      55 and 57).  The sums come back ``(n, W)``, ``W`` = ``w`` rounded up
      to 128 lanes (640), zeros past ``w``: :func:`kernel_refusal` is
      asked about ``W``, and ``core/store._rewrite_packed`` slices these
      sums down to the row's own ``w`` lanes (the arm above hands it ``w``);
    - ``"row_kernel"`` (float32 rows of at most 128 lanes): the
      rows permuted ONCE into sorted order at 128 lanes and their runs summed
      by the row kernel of ``ops/row_update`` (:func:`_kernel_sums`): on
      the v5e a serial scatter-add is 146 ns a 36-lane row, the permute of
      a whole-register row 8-10 and the kernel ~0.6 us a block of 256
      lanes, whatever the block holds: its slots are the dense ranks made
      here, so a block's sums are neighbours and leave as ONE copy (cell
      9's ``ps.combine`` 188.8 -> 29.9 ms: PERF.md section 6, PR 46; 25.2
      once the walk paid by the row it wrote and not by the lane: PR 54;
      by the block since PR 62).

    The third value, for every arm but ``"sort"``: the DMAs the kernel
    started over the stretches it walked, an int32 scalar on the device
    (``"row_kernel"``: a copy a block of 256 sorted lanes in which a run
    ends; ``"tile_kernel"``: the tile rows it read and wrote; 0 where the
    scatter-add summed the rows); ``None``
    for ``"sort"``, which has no such arm.  ``interpret`` is the kernel's
    (None: by the default backend)."""
    n, w = vals.shape
    if arm != "sort":
        sums = {"scatter_add": None, "row_kernel": _kernel_sums,
                "tile_kernel": _tile_sums}[arm]
        return _wide_runs(
            ids.astype(jnp.int32), vals, sentinel, sums, interpret)
    ids, rows = _sorted_by_id(ids.astype(jnp.int32), vals)
    cols = rows.T
    d = 1
    while d < n:
        same = jnp.concatenate([jnp.zeros((d,), bool), ids[d:] == ids[:-d]])
        before = _shifted(cols, d)
        cols = cols + jnp.where(same[None], before, jnp.zeros_like(before))
        d *= 2
    ends = jnp.concatenate([ids[1:] != ids[:-1], jnp.ones((1,), bool)])
    return _sorted_by_id(jnp.where(ends, ids, sentinel), cols.T) + (None,)


def sorted_runs(
    ids: Array, sentinel: int
) -> Tuple[Array, Array, Array, Array]:
    """The runs of one id among ``ids`` (n,), for a pull that reads each
    DISTINCT row once (``core/store._distinct_pull``): ``(row_ids, count,
    place, behind)``.  ONE sort of ``(ids, lane)``: ``place`` is each sorted
    lane's place in the stream.  ``row_ids`` are the distinct ids first, in
    ascending order, ``count`` of them, the rest ``sentinel`` (an id larger
    than any here): what :func:`combine_runs` makes of the same live ids,
    by :func:`_wide_runs`' prefix sum of the run starts and its sort of the
    starts' ids.  ``behind[k] = k - run(k)`` is how far to the LEFT of
    sorted lane ``k`` its run's number lies (:func:`spread_runs` reads it);
    it never falls and rises by at most one a lane."""
    # (any order within a run will do: every lane of it gets the same row)
    sorted_ids, place, starts, rank = _ranked(ids.astype(jnp.int32), False)
    lane = jnp.arange(ids.shape[0], dtype=jnp.int32)
    # (keys alone: nothing for a stable sort to keep, and it would carry an
    # iota to keep it)
    row_ids = jax.lax.sort(
        jnp.where(starts, sorted_ids, sentinel), is_stable=False)
    return row_ids, rank[-1], place, lane - (rank - 1)


def spread_runs(rows: Array, place: Array, behind: Array) -> Array:
    """The way back from a batch's distinct rows to its lanes: ``rows`` (n,
    w), row ``j`` the ``j``-th distinct id's (what lies past the last
    distinct id is never read), ``place`` and ``behind`` as
    :func:`sorted_runs` made them; ``(n, w)`` in the order of the STREAM,
    every lane its id's row, copied bit for bit.

    In sorted order lane ``k`` wants ``rows[k - behind[k]]``, and
    ``behind`` never falls and rises by at most one a lane: so the read is
    ``log2 n`` shifted SELECTS, ``behind``'s highest bit first, each lane
    deciding by its own ``behind`` alone (the hop for bit ``b`` lands on a
    lane ``q`` with ``behind[q]`` in ``[u, u + 2^b)``, ``u`` what is left of
    the lane's own from bit ``b`` up: the same bits from ``b`` up), which
    places a run's row and spreads it along the run in ONE loop of the
    shape :func:`combine_runs`' shifted adds have, the lanes along the
    minor axis.  Then one sort on ``place`` that carries the ``w`` lanes
    (``w`` <= ``SORT_CARRIES_LANES``).  No gather: alone on the v5e, at
    1,277,952 lanes of three-lane rows, the selects take 0.63 ms and the
    sort 2.75 (1.71 with one lane carried, which is what a step whose logic
    reads one lane compiles to), where a gather of that many rows from the
    table is 18.8 ms and XLA's gather of them from the compact block 5.6
    alone and ~17 inside a larger program; placing the rows at their runs'
    starts by a sort and filling the runs forward costs a second carrying
    sort, 2.7 ms more (PERF.md section 6, PR 70)."""
    n = rows.shape[0]
    cols = rows.T
    for b in reversed(range((n - 1).bit_length())):
        hops = ((behind >> b) & 1) == 1
        cols = jnp.where(hops[None], _shifted(cols, 1 << b), cols)
    # (the places are distinct: nothing for a stable sort to keep)
    out = jax.lax.sort((place,) + tuple(cols), num_keys=1, is_stable=False)
    return jnp.stack(out[1:], axis=1)


def kernel_refusal(width: int, dtype) -> Optional[str]:
    """Why :func:`_kernel_sums` cannot sum rows of ``width`` lanes of this
    dtype (None: it can)."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"rows are {jnp.dtype(dtype).name}, the kernel sums float32"
    if width > 128:
        # rows of whole registers are summed tile by tile (`_tile_sums`)
        from .row_update import tile_refusal

        return tile_refusal((8, width), dtype)
    return None


def _wide_runs(
    ids: Array, vals: Array, sentinel: int, kernel_sums,
    interpret: Optional[bool],
) -> Tuple[Array, Array, Array]:
    """:func:`combine_runs` for rows wider than a sort carries.  One sort of
    (id, stream position); each sorted lane's SLOT is the rank of its id
    among the distinct ids (a prefix sum of the run starts, by doubling, on
    one int32 vector); a sort of the run starts' ids moves the distinct ids
    to the front, where their slots are.  Then the sums, in one of two forms.

    ``kernel_sums`` None: the rows are never permuted.  A second sort on the
    carried positions brings the slots back to stream order, and ONE
    scatter-add of the rows into a zeroed ``(n, w)`` block sums every run in
    the order of the stream, float32 addition by addition what
    ``np.add.at`` does.  Else the rows go to the slots, not the slots to the
    rows (``kernel_sums``: :func:`_kernel_sums` or :func:`_tile_sums`).

    The first form here sorted the ids, permuted the rows by one gather,
    summed the runs by the shifted adds above on ``(w, n)`` and permuted
    them again.  Inside a 36-lane store's push on the v5e those adds read
    a shifted operand wrongly across every block of 16,000 lanes the
    compiler cut the fusion into: 23 of 352,388 rows of DiFacto's first
    batch got another run's share (``correct`` false at 8,500 x the
    allowance; alone, jitted by itself, the same function was right:
    PERF.md section 6, PR 45)."""
    n, w = vals.shape
    # (stable: a run's lanes stay in the order of the stream)
    sorted_ids, order, starts, rank = _ranked(ids, True)
    if kernel_sums is not None:
        # the lanes to drop sort last: the kernel writes no row for them
        slot = jnp.where(sorted_ids < sentinel, rank - 1, _INT32_MAX)
        sums, issued = kernel_sums(order, slot, vals, interpret)
    else:
        # (the positions are distinct: nothing for a stable sort to keep)
        _, slot = jax.lax.sort((order, rank - 1), num_keys=1, is_stable=False)
        sums = jnp.zeros((n, w), vals.dtype).at[slot].add(vals)
        issued = jnp.zeros((), jnp.int32)
    row_ids = jax.lax.sort(jnp.where(starts, sorted_ids, sentinel))
    return row_ids, sums, issued


def _kernel_sums(
    order: Array, slot: Array, vals: Array, interpret: Optional[bool]
) -> Tuple[Array, Array]:
    """``sums[slot[k]] += vals[order[k]]`` over the sorted lanes ``k``
    (``vals`` float32, at most 128 lanes: :func:`kernel_refusal`; ``slot``
    ascending, the lanes to drop last with a slot past ``n``), as a
    segment sum through ``ops/row_update.sorted_run_sums``, the MF cells'
    row kernel under the plan for ids that are DENSE RANKS: a zeroed block
    of 128-lane rows is its state, a run's total is the one row it writes.
    Beside the sums, the DMAs the calls started.

    The ``(n, w)`` rows are padded to ``(n, 128)``, row-major: whole
    registers, the only width at which a row gathers and DMAs in one piece
    (a 36-lane row of a rows-minor array is a strided column: 44 ns a row
    to permute on the v5e where a 128-lane row is 8-10).  Then, a stretch of
    at most ``MAX_LANES`` sorted lanes a trip of ONE loop (equal shapes, so
    the kernel is traced and lowered once; a stretch is also what the one
    permute holds beside the block): the stretch's rows gathered in sorted
    order, the kernel's call into the block, which is carried and aliased
    from trip to trip.  A run that lies across two stretches is written by
    both: the second reads what the first wrote as ITS old row, as
    ``row_update.row_add`` does it.

    The walk pays by the BLOCK (PERF.md section 6, PR 62).  ``slot`` is
    :func:`_wide_runs`' rank of each id among the distinct ones, so the
    runs that end in a block of 256 sorted lanes write NEIGHBOURING rows:
    the kernel sums each run straight into its place among them and sends
    the block's rows as one copy, where it issued a DMA descriptor a lane
    (PR 46) and then a row written (PR 54: 72 % of cell 9's lanes end no
    run, but 99.5 % of cell 14's do, and a descriptor is 13 ns of the one
    scalar core).  A copy is a whole kernel block of rows from the block's
    first slot on, zeros behind its sums; a lane's slot is at most the
    lane's place among the sorted lanes, so the zeroed block has a row a
    lane of the padded stretches and no copy leaves it (cells 9 and 12: the
    batch's own 1,277,952 rows, nothing sliced after).  The loop ends with the stretch that holds the last
    LIVE lane, as ``core/store._push_rule``'s ends with the last distinct
    id: the dead lanes sort last, the block starts zeroed and a dead lane
    writes nothing, so the stretches left out change no bit (a shard of
    cell 12 that owns 3.7 % of the keys walks one stretch of thirteen).  A
    stretch that is partly dead is walked whole.

    A run is summed block by block of 256 lanes on the MXU, from three
    exact bfloat16 pieces accumulated in float32, a carry between blocks:
    NOT in the order of the stream (``np.add.at``), and to float32's
    rounding of a blocked sum; bit for bit what the kernel summed under
    its other plans.  A non-finite value stays in its row."""
    from .row_update import BLOCK, MAX_LANES, sorted_run_sums

    n, w = vals.shape
    trips = -(-n // MAX_LANES)
    size = -(-n // (trips * BLOCK)) * BLOCK
    tail = trips * size - n  # lanes that drop, reading row 0
    order = jnp.pad(order, (0, tail))
    slot = jnp.pad(slot, (0, tail), constant_values=_INT32_MAX)
    padded = jnp.pad(vals, ((0, 0), (0, 128 - w)))

    def stretch(i, carry):
        block, issued = carry
        lo = i * size
        # (a permutation: nothing to clip, and no fill to select after)
        rows = jnp.take(
            padded, jax.lax.dynamic_slice_in_dim(order, lo, size), axis=0,
            mode="clip",
        )
        # the slots are dense ranks: a block's sums leave as neighbours
        block, sent = sorted_run_sums(
            block, jax.lax.dynamic_slice_in_dim(slot, lo, size), rows,
            interpret=interpret,
        )
        return block, issued + sent

    # the lanes to drop sort last: the live ones are a prefix, and the loop
    # ends with the stretch that holds the last of them
    live = jnp.sum(slot < _INT32_MAX, dtype=jnp.int32)
    block, issued = jax.lax.fori_loop(
        0, (live + size - 1) // size, stretch,
        # (a block's copy is a kernel block of rows from its first slot on,
        # and a slot is at most its lane's place: a row a padded lane)
        (jnp.zeros((trips * size, 128), jnp.float32),
         jnp.zeros((), jnp.int32)),
    )
    return block[:n, :w], issued


def _tile_sums(
    order: Array, slot: Array, vals: Array, interpret: Optional[bool]
) -> Tuple[Array, Array]:
    """:func:`_kernel_sums` for rows wider than a register (``vals``
    float32 ``(n, w)``, as the push holds them: GloVe's 602 lanes), which
    the row kernel cannot write alone: the rows permuted once into sorted
    order and added into a zeroed ``(n, W)`` block of whole registers
    (``W`` = ``w`` rounded up to 128: :func:`kernel_refusal` is asked about
    ``W``; the sums' lanes past ``w`` are zeros)
    by ``ops/row_update``'s TILE kernel (``sorted_tile_add``, the wide
    add push's: every touched tile of eight slots read, added to lane by
    lane and written once).  The slots are the ranks ``0 .. distinct - 1``,
    so the tiles are full and the walk opens an eighth as many as there are
    distinct rows.  A run's lanes are added one by one in the order of the
    stream (the sort that ranked them is on (id, position)): float32
    addition by addition what ``_wide_runs``' scatter-add and ``np.add.at``
    do, bit for bit.  Beside the sums, the tile rows the calls read and
    wrote (the kernel's DMA descriptors: two a tile row)."""
    from .row_update import _tile_add_calls

    n, w = vals.shape
    block = jnp.zeros((-(-n // 8) * 8, -(-w // 128) * 128), jnp.float32)
    block, _, opened = _tile_add_calls(block, slot, order, vals, interpret)
    return block[:n], opened


# -- host-side coalescing (the cluster client's request combiner) -----------
# The wire-protocol analogue of the combination senders: before a
# microbatch's pulls/pushes go to the network, duplicate ids collapse to
# ONE request per id (a Zipf-hot item can appear hundreds of times per
# batch — sending it hundreds of times would pay the line protocol per
# lane).  These run on the HOST (numpy): the cluster client formats
# text frames from the result, so there is no device round trip to save.


def coalesce_ids(
    ids: np.ndarray, mask: Optional[np.ndarray] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """``(unique_ids, inverse)``: each valid lane's id appears once in
    ``unique_ids`` (sorted ascending); ``inverse`` maps every input
    lane to its unique slot so pulled values scatter back with
    ``values[inverse]``.  Masked-out lanes map to slot 0 — callers must
    treat those lanes as padding (the store contract already does)."""
    flat = np.asarray(ids).reshape(-1).astype(np.int64)
    if mask is not None:
        m = np.asarray(mask).reshape(-1).astype(bool)
        # padding lanes piggyback on the first valid id (or id 0 for an
        # all-padding batch) so unique_ids never carries a pad-only id
        fill = flat[m][0] if m.any() else np.int64(0)
        flat = np.where(m, flat, fill)
    unique, inverse = np.unique(flat, return_inverse=True)
    return unique.astype(np.int64), inverse.reshape(np.asarray(ids).shape)


def aggregate_deltas(
    ids: np.ndarray,
    deltas: np.ndarray,
    mask: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """``(unique_ids, summed)``: duplicate-id deltas SUMMED per id —
    exactly the store's duplicate semantics (intra-batch duplicates
    combine additively), applied before the bytes hit the wire.  Masked
    lanes contribute nothing.  ``deltas`` is ``(n, *value_shape)`` (or
    ``(n,)`` for scalar stores); the result rows align with
    ``unique_ids``."""
    ids_arr = np.asarray(ids)
    flat_ids = ids_arr.reshape(-1).astype(np.int64)
    d = np.asarray(deltas)
    flat_d = d.reshape((ids_arr.size,) + d.shape[ids_arr.ndim:])
    if mask is not None:
        m = np.asarray(mask).reshape(-1).astype(bool)
        flat_ids = flat_ids[m]
        flat_d = flat_d[m]
    unique, inverse = np.unique(flat_ids, return_inverse=True)
    out = np.zeros((unique.shape[0],) + flat_d.shape[1:], np.float64)
    np.add.at(out, inverse, flat_d.astype(np.float64))
    return unique.astype(np.int64), out.astype(flat_d.dtype)


def aggregate_delta_batches(batches) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`aggregate_deltas` across SEVERAL workers' batches — the
    aggregation tree's combine step (compression/aggregator.py): each
    element of ``batches`` is ``(ids, deltas)`` or ``(ids, deltas,
    mask)``; the result is one ``(unique_ids, summed)`` pair equal to
    aggregating the concatenation (per-id sums are associative — the
    f64 accumulator below makes the combine order immaterial).  Empty
    or ``None`` entries are skipped, so a worker with nothing to push
    this round costs nothing."""
    flat_ids = []
    flat_deltas = []
    for entry in batches:
        if entry is None:
            continue
        ids, deltas = entry[0], entry[1]
        mask = entry[2] if len(entry) > 2 else None
        ids_arr = np.asarray(ids).reshape(-1).astype(np.int64)
        if ids_arr.size == 0:
            continue
        d = np.asarray(deltas)
        d = d.reshape((ids_arr.size,) + d.shape[np.asarray(ids).ndim:])
        if mask is not None:
            m = np.asarray(mask).reshape(-1).astype(bool)
            ids_arr, d = ids_arr[m], d[m]
            if ids_arr.size == 0:
                continue
        flat_ids.append(ids_arr)
        flat_deltas.append(d)
    if not flat_ids:
        return np.empty(0, np.int64), np.empty(0, np.float32)
    all_ids = np.concatenate(flat_ids)
    all_deltas = np.concatenate(flat_deltas)
    return aggregate_deltas(all_ids, all_deltas)


__all__ = [
    "occurrence_counts",
    "occurrence_scale",
    "coalesce_ids",
    "aggregate_deltas",
    "aggregate_delta_batches",
]
