"""Pallas TPU kernel: one pipelined row WRITE per unique id of a sorted batch.

Reference parity (SURVEY.md §2 #7, §7 "Hard parts"): the reference's MF
worker keeps its user vectors in a JVM hash map and updates one vector per
rating.  A microbatch of ratings is here one step, whose worker-state
update ``state.at[users].add(deltas)`` XLA lowers, on the TPU, to one
read-modify-write per LANE that may not overlap the next (any two lanes
may hit one row): 75 ns a row on the v5e against the 8 ns a row at which
the same chip gathers the same rows — and 125 ns a row once the scatter is
promised unique and sorted (PERF.md section 6, PR 27).

The step has already gathered every row it is about to update, so a row's
new value is ``old + (sum of its lanes' deltas)`` and the update needs no
second read.  With ids, old rows and deltas brought into row order
(:func:`row_add`: one sort, two permutes of batch-sized buffers), lanes
sharing a row are adjacent, and this kernel

  1. walks the sorted lanes in blocks of ``block`` rows (a sequential TPU
     grid; old rows and deltas arrive as pipelined VMEM blocks),
  2. sums each run of equal ids INSIDE the block on the MXU — an inclusive
     segmented prefix sum as one 0/1-mask matmul, so any run length costs
     the same — and carries the open run's sum into the next block,
  3. writes, for the LAST lane of every run, ``old + prefix`` (= the row's
     new value) to its row with a single-row DMA.  Rows written are unique,
     so no DMA waits for another: a whole block's writes are in flight
     while the next block is summed, and are awaited, all at once, one
     block later.  Issuing the descriptors is what the kernel's time is:
     13 ns a lane on the v5e, 16 ns a lane all told (PERF.md section 6).

The state array stays in HBM and is aliased to the output; rows no lane
names are never touched.  Lanes to drop carry an id >= the row count (they
sort to the end) and write nothing.

What the compiled kernel takes (:func:`refusal`): float32 rows whose width
is a multiple of 128 lanes, and at most ``MAX_LANES`` lanes a call (two
int32 a lane are prefetched into SMEM).  The row count is free (single-row
DMAs need no 8-row alignment).  A non-finite delta stays in its row: it is
taken out of the mask matmul (0 x NaN would spread it over its block) and
its row's element is made NaN by select afterwards (a lone inf reads NaN
too, where the XLA scatter leaves inf).  A dropped lane's NaN reaches only
its own run, which writes nothing.
"""
from __future__ import annotations

import functools
import importlib
import threading
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

BLOCK = 256  # sorted lanes per grid step
# the kernel prefetches two int32 a lane into SMEM, 1 MiB on the v5e with
# Mosaic's own share in it: 98,304 lanes compile there and 131,072 run out
# of it (tests/test_tpu_compile.py)
MAX_LANES = 98_304
_INT32_MAX = jnp.iinfo(jnp.int32).max


def refusal(width: int, dtype, lanes: int) -> Optional[str]:
    """Why the compiled kernel cannot take ``lanes`` rows of this width and
    dtype in one call (None: it can)."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"rows are {jnp.dtype(dtype).name}, the kernel sums float32"
    if width % 128 != 0:
        return f"row width {width} is not a multiple of 128 lanes"
    if lanes > MAX_LANES:
        return (
            f"a batch of {lanes} lanes is over the {MAX_LANES} whose row "
            f"ids fit the kernel's scalar memory"
        )
    return None


# How often a caller on the kernel's own ground (a TPU, no mesh) was refused
# and kept its XLA scatter-add instead (`note_refusal`).
_REFUSALS = 0


def refusal_count() -> int:
    return _REFUSALS


def note_refusal(what: str, why: str) -> None:
    """Count and warn of a :func:`refusal` the caller is about to act on.
    The choice is static per compiled step, so callers note it once a
    logic: a step that could have had the kernel never silently lacks it."""
    global _REFUSALS
    _REFUSALS += 1
    warnings.warn(
        f"ops/row_update falling back to XLA scatter: {what}: {why}",
        RuntimeWarning,
        stacklevel=3,
    )


def preload() -> None:
    """Import Pallas on a thread, for a caller that knows a step of its is
    going to trace the kernel.  The import is about a second (most of it
    Mosaic GPU, which JAX loads for its own interpreter) that would else
    fall into that first trace; begun where a job builds its logic it runs
    beside the staging of tables and batches (0.6 s of a warm set-up on
    the v5e's machine: PERF.md section 6, PR 27)."""
    threading.Thread(
        target=importlib.import_module, args=("jax.experimental.pallas.tpu",),
        name="pallas-import", daemon=True,
    ).start()


def sort_by_row(ids: Array, keep: Optional[Array], rows: int):
    """Sort a batch by row id: ``(sorted_ids, order)``.

    Lanes to drop (``keep`` false, id < 0, id >= ``rows``) get one key above
    every row, so they sort to the END as one run that writes nothing.
    ``order[k]`` is the stream position of sorted lane ``k``.
    """
    ids = ids.astype(jnp.int32)
    ok = (ids >= 0) & (ids < rows)
    if keep is not None:
        ok = ok & keep
    key = jnp.where(ok, ids, _INT32_MAX)
    iota = jnp.arange(ids.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, iota), num_keys=1)


def _kernel(tgt_ref, src_ref, writes_ref, aux_ref, old_ref, dl_ref, state_ref,
            out_ref, buf_ref, carry_ref, sem, *, block: int):
    """One grid step = ``block`` sorted lanes.

    tgt_ref / src_ref: (N,) int32 SMEM (scalar prefetch) — per lane, the
      row its DMA writes and the block-local lane whose new row it sends.
      A lane that is the last of its run (and kept) sends its own; every
      other lane REPEATS its block's first such write, same source and
      same row, so a block issues exactly ``block`` DMAs with no branch a
      lane, and identical bytes landing twice on one row harm nothing.
    writes_ref: (N / block,) int32 SMEM — how many lanes of a block write;
      a block where none does issues and awaits nothing.
    aux_ref: (8, block) int32 VMEM — row 0: per lane, the block-local index
      of the last lane of its run (clipped to the block); row 1: whether
      the block's first lane continues the previous block's run.
    old_ref / dl_ref: (block, W) f32 VMEM — gathered rows, deltas (sorted).
    state_ref / out_ref: the aliased (rows, W) state in HBM.
    buf_ref: (2, block, W) f32 VMEM — new rows staged for their DMAs; a
      block's writes stay in flight while the next block fills the other.
    carry_ref: (1, W) f32 VMEM — prefix sum of the run open at a block's end.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del state_ref  # aliased to out_ref: untouched rows keep their values
    b = pl.program_id(0)
    slot = b % 2
    base = b * block

    @pl.when(b == 0)
    def _init():
        carry_ref[:] = jnp.zeros_like(carry_ref)

    end = aux_ref[0:1, :]  # (1, block)
    ii = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    # lane j adds into lane i iff j <= i <= end[j]: runs are contiguous
    same_run = ((jj <= ii) & (ii <= end)).astype(jnp.bfloat16)
    # three bfloat16 pieces hold a float32 exactly and the mask is 0/1, so
    # every product is exact and the MXU's float32 accumulation is the only
    # rounding: a float32 segmented prefix sum in three passes.  0 x NaN is
    # NaN, so a non-finite delta is summed as 0 and a fourth pass says which
    # elements of its run it reaches: those become NaN by select
    deltas = dl_ref[:]
    finite = jnp.abs(deltas) <= jnp.finfo(jnp.float32).max
    deltas = jnp.where(finite, deltas, 0.0)
    hi = deltas.astype(jnp.bfloat16)
    rest = deltas - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    bad = jnp.where(finite, 0.0, 1.0).astype(jnp.bfloat16)

    def run_sum(*pieces):
        return sum(
            jnp.dot(same_run, piece, preferred_element_type=jnp.float32)
            for piece in pieces
        )

    prefix = jnp.where(run_sum(bad) > 0, jnp.nan, run_sum(lo, mid, hi))
    col = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    continues = (col <= aux_ref[0:1, 0:1]) & (aux_ref[1:2, 0:1] > 0)
    prefix = prefix + jnp.where(continues, carry_ref[:], 0.0)
    carry_ref[:] = prefix[block - 1:block, :]
    buf_ref[slot] = old_ref[:] + prefix

    @pl.when(writes_ref[b] > 0)
    def _start():
        # eight lanes a trip: Mosaic unrolls a loop wholly or not at all
        def group(g, _):
            for k in range(8):
                lane = base + g * 8 + k
                pltpu.make_async_copy(
                    buf_ref.at[slot, pl.ds(src_ref[lane], 1)],
                    out_ref.at[pl.ds(tgt_ref[lane], 1)],
                    sem.at[slot],
                ).start()
            return 0

        jax.lax.fori_loop(0, block // 8, group, 0)

    def wait_for(blk, s):
        # a DMA semaphore counts bytes: one wait the size of the staging
        # slot answers the block's ``block`` single-row copies together
        @pl.when(writes_ref[blk] > 0)
        def _():
            pltpu.make_async_copy(
                buf_ref.at[s], buf_ref.at[s], sem.at[s]
            ).wait()

    @pl.when(b > 0)
    def _previous():
        wait_for(b - 1, 1 - slot)

    @pl.when(b == pl.num_programs(0) - 1)
    def _own():
        wait_for(b, slot)


def _plan(sorted_ids: Array, rows: int, block: int):
    """The kernel's per-lane scalars and vectors from the sorted ids."""
    n = sorted_ids.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    differs = sorted_ids[1:] != sorted_ids[:-1]
    is_last = jnp.concatenate([differs, jnp.ones((1,), bool)])
    is_first = jnp.concatenate([jnp.ones((1,), bool), differs])
    run_end = jax.lax.cummin(
        jnp.where(is_last, iota, n - 1), axis=0, reverse=True
    )

    def by_block(x):
        return x.reshape(n // block, block)

    local = by_block(iota % block)
    end_local = jnp.minimum(by_block(run_end - (iota // block) * block),
                            block - 1)
    continues = jnp.broadcast_to(
        by_block(~is_first).astype(jnp.int32)[:, :1], local.shape
    )
    aux = jnp.concatenate(
        [end_local[:, None], continues[:, None],
         jnp.zeros((n // block, 6, block), jnp.int32)], axis=1,
    )
    writes = by_block(is_last & (sorted_ids < rows))
    first_write = jnp.argmax(writes, axis=1).astype(jnp.int32)[:, None]
    src = jnp.where(writes, local, first_write)
    # (a block-sized take_along_axis by ``src`` would be a gather of
    # scalars, 10 ns each on the TPU: take one row a block and select)
    ids = by_block(sorted_ids)
    tgt = jnp.where(
        writes, ids,
        jnp.minimum(jnp.take_along_axis(ids, first_write, axis=1), rows - 1),
    )
    count = jnp.sum(writes, axis=1, dtype=jnp.int32)
    return tgt.reshape(-1), src.reshape(-1), count, aux


def sorted_row_update(
    state: Array,
    sorted_ids: Array,
    old_rows: Array,
    deltas: Array,
    *,
    interpret: Optional[bool] = None,
) -> Array:
    """``state[r] = old_rows[k] + sum(deltas[lanes of r])`` for every row
    ``r`` some kept lane names; every other row is left as it is.

    ``sorted_ids``: (n,) int32 ASCENDING, lanes to drop at the end with an
    id >= the row count (:func:`sort_by_row` makes them).  ``old_rows``:
    (n, W) — ``state[sorted_ids]`` as the step gathered it (lanes of one
    row carry equal values).  ``deltas``: (n, W).  A dropped lane's old row
    and delta may be anything, NaN included: its run writes nothing.

    The state is updated in place when the enclosing jit donates it; an
    eager call copies it first.  Off the TPU the kernel is interpreted
    (``interpret=None``: by the default backend).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, width = state.shape
    n = sorted_ids.shape[0]
    why = refusal(width, state.dtype, n)
    if why is not None and not interpret:
        raise ValueError(f"sorted_row_update: {why}")
    block = BLOCK
    sorted_ids = sorted_ids.astype(jnp.int32)
    deltas = deltas.astype(jnp.float32)
    old_rows = old_rows.astype(jnp.float32)
    pad = -n % block
    if pad:
        sorted_ids = jnp.concatenate(
            [sorted_ids, jnp.full((pad,), _INT32_MAX, jnp.int32)]
        )
        deltas = jnp.pad(deltas, ((0, pad), (0, 0)))
        old_rows = jnp.pad(old_rows, ((0, pad), (0, 0)))
    tgt, src, count, aux = _plan(sorted_ids, rows, block)
    if not isinstance(state, jax.core.Tracer):
        state = jnp.copy(state)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=((n + pad) // block,),
        in_specs=[
            pl.BlockSpec((None, 8, block), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((block, width), lambda b, *_: (b, 0)),
            pl.BlockSpec((block, width), lambda b, *_: (b, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the state stays in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, block, width), jnp.float32),
            pltpu.VMEM((1, width), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        grid_spec=grid_spec,
        input_output_aliases={6: 0},  # (tgt, src, count, aux, old, deltas, state)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="sorted_row_update",
    )(tgt, src, count, aux, old_rows, deltas, state)


def row_add(
    state: Array,
    ids: Array,
    old_rows: Array,
    deltas: Array,
    mask: Optional[Array] = None,
    *,
    interpret: Optional[bool] = None,
) -> Array:
    """``state.at[ids].add(deltas)`` through the kernel (masked lanes, ids
    < 0 and ids >= rows dropped): sort the ids, bring old rows and deltas
    into that order, one write per unique row.

    ``old_rows``: ``state[ids]`` in the order of ``ids``, as the caller has
    gathered it (the MF step has: a kept lane's row must be the state's, a
    dropped lane's may be anything); the kernel's old rows are a permute of
    it, out of fast memory, instead of a second gather out of the state.
    """
    keep = None if mask is None else mask.reshape(-1)
    sid, order = sort_by_row(ids.reshape(-1), keep, state.shape[0])
    return sorted_row_update(
        state, sid, jnp.take(old_rows, order, axis=0),
        jnp.take(deltas, order, axis=0), interpret=interpret,
    )


__all__ = [
    "BLOCK", "MAX_LANES", "note_refusal", "preload", "refusal",
    "refusal_count", "row_add", "sort_by_row", "sorted_row_update",
]
