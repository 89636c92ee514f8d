"""Pallas TPU kernels: one pipelined row WRITE per unique id of a sorted batch,
and, for rows wider than a register or far narrower, one read-modify-write
per touched TILE.

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
     while the next block is summed, and are awaited one block later.
     Issuing the descriptors is what the kernel's time is: on the v5e a
     block costs ~0.6 us whatever it holds (its two pipelined input blocks,
     the four mask products) and 10-13 ns for every descriptor it issues
     on the one scalar core (PERF.md section 6, PRs 27, 52 and 54).

What a block issues is the PLAN's (:func:`_plan`), and the plan is the
call site's, from what it knows of its ids by construction.  A keyed stream
(MF's users: 99.3 % of the lanes write) takes ``"lane"``: every writing
lane stays where it lies, a lane that writes nothing repeats its block's
first write, so a block that writes issues exactly ``block`` DMAs in a loop
of static length with no branch a lane, and one wait the size of the
staging slot answers them.  A write-back that dropped lanes itself
(:func:`sorted_row_set`: its targets are scattered table rows) takes
``"compact"``: one more sort of the block-shaped scalars brings a block's
writes to the front of its stretch, and the walk issues a descriptor a row
it WRITES, eight a trip, at most seven over (cell 9's write-back's eleven
calls 4.93 -> 4.22 ms a step: PERF.md section 6, PR 54).  Same rows, same
bytes, fewer copies of them.  At MF's shape the compact plan and its waits
cost 0.023 ms a call more than they save (1.107 -> 1.130 ms at 65,536
lanes): that caller keeps its plan.  A rule store's combine
(``ops/dedup._kernel_sums``) hands over ids that are DENSE RANKS, so the
rows a block writes are neighbours in its zeroed block, and takes
``"dense"`` (:func:`sorted_run_sums`, a kernel body of its own): each run
is summed straight into its place among the block's runs, by the same
products, and the block's rows leave as ONE copy, no descriptor a row, no
scalar a lane, whatever share of the lanes write (72 % of cell 9's end no
run, 0.5 % of cell 14's: thirteen calls 7.89 -> 2.64 ms a step and two 2.61
-> 0.44; PERF.md section 6, PR 62).

The state array stays in HBM and is aliased to the output; rows no lane
names are never touched.  Lanes to drop carry an id >= the row count (they
sort to the end) and write nothing.

What the compiled kernel takes (:func:`refusal`): float32 rows of exactly
128 lanes.  The TPU tiles a 2-D float32 array (8, 128): at 128 lanes a row
is 512 contiguous bytes; a wider row is 512-byte pieces 4 KB apart, eight
rows to a tile, and Mosaic takes no DMA of ONE row of it ("slice shape must
be aligned to tiling (8)"; held as ``(rows, 1, W)`` a row is contiguous and
its DMA compiles, but XLA then copies the whole table to ``(rows, W)`` for
every gather and scatter: PERF.md section 6, PR 33).  The row count is free
(single-row DMAs need no 8-row alignment).  One call takes at most
``MAX_LANES`` lanes (two int32 a lane are prefetched into SMEM);
:func:`row_add` gives a larger batch to several calls.  A non-finite delta
stays in its row: it is taken out of the mask matmul (0 x NaN would spread
it over its block) and its row's element is made NaN by select afterwards
(a lone inf reads NaN too, where the XLA scatter leaves inf).  A dropped
lane's NaN reaches only its own run, which writes nothing.

**Rows of several registers** (``core/store.push`` on a table of wide rows,
word2vec's 640 lanes: :func:`scatter_add`) go through the second kernel,
:func:`sorted_tile_add`.  What can be moved alone there is a TILE ROW: eight
rows, ``8 x W x 4`` contiguous bytes.  Block by block of the sorted lanes,
the kernel reads the tile rows its lanes touch into VMEM (one DMA each),
adds every lane's delta to its row's sublane, one float32 add a lane in the
order of the batch (the sort is stable), and writes the tile rows back: a
read-modify-write per touched tile row instead of XLA's serial one per lane
(124 ns a 640-lane row on the v5e), with XLA's roundings, bit for bit.  It
reads the table itself, so it takes no old rows, and it takes the deltas at
the width ``w`` <= ``W`` their caller holds them (word2vec's 600 lanes,
fastText's 300 in 384, GloVe's 602): a lane's add touches lanes ``[0, w)``
of its row of the tile, so nothing pads a batch to ``W`` in HBM in front of
it (PR 57).  Since PR 41 it walks as
the third kernel does: descriptors and adds eight to a loop trip
(:func:`_each`), a block's copies answered sixteen tile rows a wait, and
three tile buffers, so that the next block's reads are in flight under this
block's adds and the block before's writes.  A hot row's run spans many
blocks, so one tile row may be open in a long stretch of them; but ids
ascend, so only a block's FIRST tile row can be the block before's LAST,
and that one is carried from slot to slot in VMEM: not written by the block
before, not read by this one, written by the block that closes it.  Every
touched tile row is then read once and written once a call and no two
copies meet.  Its time is what one scalar core issues, far more than the
bytes: on the v5e a DMA descriptor is 17 ns eight to a trip (22 issued one
a trip), two a tile row, ~20 with its share of the waits and of what the
copies still expose; a wait is 6.5 ns (one a tile row until they were
folded); an add is two loads, an add and a store a register of the row,
~6 ns a lane at three registers and 11.6 at five (20.8 one a trip).  Cell
5's 114,688 lanes on 32.2 k tile rows take 2.81 ms where they took 4.20
(its cold call's 1.30 GB of tile rows 2.15 ms, 1.6 at HBM speed), cell 7's
126.4 k live lanes on 56.4 k tile rows 3.17 where they took 5.03 (PERF.md
section 6, PR 41).  Since PR 49 the same walk takes rows of ONE register
where the table is eight batches long or longer (the TPU compiler leaves
XLA's scatter-add serial there, 74.7 ns a lane: ``core/store.
_tile_kernel_takes``): cell 10's 851,968 lanes on 228 k tile rows of
``f32[24563152,128]`` are nine calls, 14.2 ms, 9.4 ns of adds a lane and
~22-27 a tile row.

**Narrow rows under a rule** (``core/store._push_rule``'s write-back of
FTRL's ``(w, z, n)``: :func:`sorted_tile_set`) go through the third.  A
float32 table of rows of 1, 2, 4 or 8 lanes lies rows-minor on the TPU,
``{0,1:T(L,128)}``: byte for byte the row-major ``(rows / 128, L, 128)``, a
TILE of it 128 consecutive rows, ``L x 512`` contiguous bytes, the tiles
side by side, and what XLA's row ``set`` writes at 83 ns a row is one column
of such a tile.  The kernel reads every tile the sorted distinct ids touch
into VMEM, sets each row's column by selects on iotas (its values scalars
from SMEM) and writes the tiles back.  A tile belongs to the block of lanes
that holds its first lane, whose sets reach into the next blocks' lanes, so
no two grid steps touch one tile: the next block's reads are in flight
under this block's sets and the block before's writes, three tile buffers.
Its time was the issue of two DMA descriptors a tile (17 ns each on the
v5e) and 7 ns a lane of sets (PERF.md section 6, PR 35).  Since PR 72 a
copy takes a SPAN where the touched tiles lie close: the tiles stand in
aligned groups of ``W`` (:func:`set_span`: from the table's tiles and the
push's lanes, 32 in cell 17, 8 in cell 6, 1 where a push is short against
its table), and a whole group that the ids touch in two tiles or more is
read and written by ONE descriptor each way, the untouched tiles inside it
as they are; any other touched tile is copied alone (the table's last,
ragged group; a lone tile).  Cell 17's 187.6 k touched tiles a step go in
6.6 k copies and its write-back took 13.1 ms alone and takes 7.5 (11.8 ->
6.4 in the step: the 685 k lane sets are what is left), cell 6's 180.6 k
in 110.4 k, 9.7 -> 7.8 (PERF.md section 6, PR 72).

**A packed rule store's write-back** (``core/store._rewrite_packed``: whole
128-lane physical rows, distinct, sorted, lanes to drop between them:
:func:`sorted_row_set`) is the first kernel's walk with a COPY for its body:
a block's new rows are staged as they are and sent, one single-row DMA a
lane, nothing summed, so every bit of a row arrives (-0.0, NaN payloads,
infinities).  XLA's row ``set`` of 32,768 such rows is 72 ns a lane on the
v5e, kept or dropped, and 764 once promised sorted; the walk was 13.7 ns a
lane inside cell 9's step (PERF.md section 6, PR 47) and pays by the row
written since PR 54 (the compact plan: the store's dropped lanes, 18-20 %
of a chunk, lie BETWEEN its writes).
"""
from __future__ import annotations

import functools
import importlib
import math
import sys
import threading
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

BLOCK = 256  # sorted lanes per grid step
# a call of either kernel prefetches two int32 a lane into SMEM, 1 MiB on the
# v5e with Mosaic's own share in it: 98,304 lanes compile there and 131,072
# run out of it (tests/test_tpu_compile.py)
MAX_LANES = 98_304
# the tile kernel keeps three blocks' tile rows in VMEM (3 x block x 8 x W x 4
# bytes: 15.7 MB at 640 lanes, 9.4 at 384) beside its pipelined deltas (two
# blocks of them): at 640 lanes that is over the 16 MiB Mosaic allows a
# kernel by default, and well under the v5e's 128
_TILE_VMEM_BYTES = 64 * 2**20
_INT32_MAX = jnp.iinfo(jnp.int32).max
# the tile ADD kernel holds a tile row in registers in the blocks where at
# least one in so many trips of eight sorted lanes lies in one tile row
_RUN_TRIPS_SHARE = 3
# and at the rows' widths, in 128-lane registers, at which the v5e has priced
# that and it won (`_tile_kernel`): one (cell 10) and five (cell 5).  At three
# lane by lane is ~6 ns a lane and the held row lost (cell 13 -0.64 %, cell 7
# nothing); no cell adds rows of another width
_HELD_ROW_REGISTERS = (1, 5)


def refusal(row: Tuple[int, ...], dtype) -> Optional[str]:
    """Why the compiled row-write kernel cannot take a state whose rows have
    this shape (``state.shape[1:]``) and dtype (None: it can)."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"rows are {jnp.dtype(dtype).name}, the kernel sums float32"
    if tuple(row) != (128,):
        return (
            f"rows of shape {tuple(row)}: only a row of 128 lanes lies in "
            f"one piece in the TPU's (8, 128) tiles and can be written alone"
        )
    return None


def tile_refusal(table_shape: Tuple[int, ...], dtype) -> Optional[str]:
    """Why :func:`sorted_tile_add` cannot take this table (None: it can)."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"rows are {jnp.dtype(dtype).name}, the kernel sums float32"
    if len(table_shape) != 2 or table_shape[1] % 128 != 0:
        return (
            f"rows of shape {tuple(table_shape[1:])}, the kernel takes flat "
            f"rows of whole 128-lane registers"
        )
    if table_shape[0] % 8 != 0:
        return f"{table_shape[0]} rows are not whole tiles of 8"
    held = (3 * 8 + 2) * BLOCK * table_shape[1] * 4
    if held > _TILE_VMEM_BYTES:
        return (
            f"rows of {table_shape[1]} lanes: three blocks' tile rows and "
            f"two blocks' deltas are {held} bytes of VMEM, over the "
            f"{_TILE_VMEM_BYTES} the kernel asks for"
        )
    return None


def _too_many(lanes: int) -> Optional[str]:
    if lanes > MAX_LANES:
        return (
            f"{lanes} lanes in one call are over the {MAX_LANES} whose "
            f"scalars fit the kernel's SMEM (row_add and scatter_add split "
            f"a batch)"
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
        f"ops/row_update falling back to XLA: {what}: {why}",
        RuntimeWarning,
        stacklevel=3,
    )


_PRELOAD = threading.Lock()  # held from the first `preload` on
_PALLAS = None  # (pl, pltpu) once a kernel has asked for them


# What of ``jax.experimental.pallas`` no TPU kernel runs: the interpreter of
# Mosaic GPU kernels and, behind it, Mosaic GPU's own dialects, 0.7 s of the
# import's 1.1.  JAX's own ``pallas_call`` module imports it under ``except
# ImportError``.
_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"


def _import_pallas() -> None:
    """Import Pallas for the TPU.  On a TPU the import leaves the Mosaic GPU
    interpreter out (a ``None`` in ``sys.modules`` makes ITS import raise
    ``ImportError``, which Pallas takes as "not installed"; the entry is
    taken away again, so a later import of Mosaic GPU itself finds it): a
    process that runs TPU kernels interprets no GPU kernel, and every warm
    set-up paid for loading it (PERF.md section 6, PR 67).  Where Pallas is
    imported already, or JAX has moved the module, nothing is left out."""
    from ..telemetry.compile_ledger import setup_span

    with setup_span("kernel_import"):
        leave_out = (jax.default_backend() == "tpu"
                     and _GPU_INTERPRETER not in sys.modules)
        if leave_out:
            sys.modules[_GPU_INTERPRETER] = None
        try:
            importlib.import_module("jax.experimental.pallas.tpu")
        finally:
            if leave_out:
                del sys.modules[_GPU_INTERPRETER]


def preload() -> None:
    """Import Pallas on a thread, for a caller that knows a step of its is
    going to trace the kernel.  The import is about a second (most of it
    Mosaic GPU, which JAX loads for its own interpreter) that would else
    fall into that first trace; begun where a job builds its logic it runs
    beside the staging of tables and batches (0.6 s of a warm set-up on
    the v5e's machine: PERF.md section 6, PR 27).  The first call starts
    the thread, later ones do nothing; the import is the ledger's event
    ``setup.kernel_import`` (a span for an export: on a thread beside the
    staging its length is no cost of set-up's)."""
    if _PRELOAD.acquire(blocking=False):
        threading.Thread(
            target=_import_pallas, name="pallas-import", daemon=True
        ).start()


def _pallas():
    """``(pl, pltpu)``.  The first kernel to ask waits here for the import
    :func:`preload` began, or pays for all of it: ``setup.kernel_import_wait``,
    and the seconds of ``setup_kernel_import_seconds_total``, which is what
    of the import lands in set-up's time (trace time only; a dispatch never
    comes here)."""
    global _PALLAS
    if _PALLAS is None:
        from ..telemetry.compile_ledger import setup_span
        from ..telemetry.registry import get_registry

        with setup_span("kernel_import_wait", get_registry().counter(
            "setup_kernel_import_seconds_total", component="setup"
        )):
            import jax.experimental.pallas as pl
            from jax.experimental.pallas import tpu as pltpu
        _PALLAS = (pl, pltpu)
    return _PALLAS


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


def _exact_run_sums(mask, deltas):
    """``mask @ deltas`` in float32 for a 0/1 bfloat16 ``mask``: three
    bfloat16 pieces hold a float32 exactly and the mask is 0/1, so every
    product is exact and the MXU's float32 accumulation is the only
    rounding, three passes.  0 x NaN is NaN, so a non-finite delta is
    summed as 0 and a fourth pass says which rows of the product it
    reaches: those elements become NaN by select."""
    finite = jnp.abs(deltas) <= jnp.finfo(jnp.float32).max
    deltas = jnp.where(finite, deltas, 0.0)
    hi = deltas.astype(jnp.bfloat16)
    rest = deltas - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    bad = jnp.where(finite, 0.0, 1.0).astype(jnp.bfloat16)

    def run_sum(*pieces):
        return sum(
            jnp.dot(mask, piece, preferred_element_type=jnp.float32)
            for piece in pieces
        )

    return jnp.where(run_sum(bad) > 0, jnp.nan, run_sum(lo, mid, hi))


def _kernel(tgt_ref, src_ref, writes_ref, aux_ref, old_ref, dl_ref, state_ref,
            out_ref, buf_ref, carry_ref, sem, *, block: int, plan: str):
    """One grid step = ``block`` sorted lanes.

    tgt_ref / src_ref: (N,) int32 SMEM (scalar prefetch) — per DMA of a
      block's stretch, the row it writes and the block-local lane whose new
      row it sends (:func:`_plan`).  A lane that is the last of its run
      (and kept) sends its own; every other entry REPEATS its block's
      first such write, same source and same row: identical bytes landing
      twice on one row harm nothing, and the loop has no branch a lane.
    writes_ref: (N / block,) int32 SMEM — how many lanes of a block write;
      a block where none does issues and awaits nothing.  What a block that
      writes issues is :func:`_send_and_await`'s: all ``block`` entries
      (``plan`` ``"lane"``), or (``"compact"``: the plan has moved the
      writes to the front) as many trips of eight as hold them.
    aux_ref: (8, block) int32 VMEM — row 0: per lane, the block-local index
      of the last lane of its run (clipped to the block); row 1: whether
      the block's first lane continues the previous block's run.
    old_ref / dl_ref: (block, W) f32 VMEM — gathered rows, deltas (sorted).
    state_ref / out_ref: the aliased (rows, W) state in HBM.
    buf_ref: (2, block, W) f32 VMEM — new rows staged for their DMAs; a
      block's writes stay in flight while the next block fills the other.
    carry_ref: (1, W) f32 VMEM — prefix sum of the run open at a block's end.
    """
    pl, pltpu = _pallas()

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
    # lane j adds into lane i iff j <= i <= end[j]: runs are contiguous; an
    # inclusive segmented prefix sum in float32
    same_run = ((jj <= ii) & (ii <= end)).astype(jnp.bfloat16)
    prefix = _exact_run_sums(same_run, dl_ref[:])
    col = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    continues = (col <= aux_ref[0:1, 0:1]) & (aux_ref[1:2, 0:1] > 0)
    prefix = prefix + jnp.where(continues, carry_ref[:], 0.0)
    carry_ref[:] = prefix[block - 1:block, :]
    buf_ref[slot] = old_ref[:] + prefix

    _send_and_await(tgt_ref, src_ref, writes_ref, buf_ref, out_ref, sem,
                    slot, base, block=block, plan=plan)


def _send_and_await(tgt_ref, src_ref, writes_ref, buf_ref, out_ref, sem, slot,
                    base, *, block: int, plan: str):
    """The walk both row kernels end a grid step with: send block ``b``'s
    staged rows (``buf_ref[slot]``, ``slot = b % 2``), one single-row DMA an
    entry of its stretch of ``tgt_ref`` / ``src_ref`` (which begins at
    ``base = b x block``), and await the block before's, the last block its
    own too: a block's writes are in flight while the next is staged.

    What a block issues is the plan's (:func:`_plan`).  ``"lane"`` (a
    keyed stream: nearly every lane writes): all ``block`` entries,
    eight a trip of a loop of static length, answered by ONE wait the size
    of the staging slot (a DMA semaphore counts bytes, and exactly
    ``block`` rows were sent).  ``"compact"`` (a write-back that dropped
    lanes itself; the combine's until it got a plan of its own): the
    block's ``writes_ref[b]`` writing entries lie first, and the loop runs
    ``ceil(writes / 8)`` trips, the last trip's spare entries repeating the
    block's first write as every entry past the writes does; the waits
    answer exactly those bytes, four trips (32 rows) a wait and the rest a
    trip a wait (a wait for more bytes than were sent hangs the chip, and
    the interpreter cannot see it).  On the v5e the one scalar core issues
    a descriptor in ~10 ns and a wait in 6.5; sixteen or thirty-two entries
    a trip, and waits folded by two, eight or not at all, read within 0.1
    ms of this form over cell 9's thirteen calls, 11.70-11.99 ms (PERF.md
    section 6, PR 54)."""
    pl, pltpu = _pallas()
    lax = jax.lax

    compact = plan == "compact"
    b = pl.program_id(0)

    def trips(blk):  # compact: the trips of eight that hold a block's writes
        return lax.shift_right_logical(lax.add(writes_ref[blk], 7), 3)

    # eight entries a trip: Mosaic unrolls a loop wholly or not at all
    def group(g, _):
        for k in range(8):
            at = base + g * 8 + k
            pltpu.make_async_copy(
                buf_ref.at[slot, pl.ds(src_ref[at], 1)],
                out_ref.at[pl.ds(tgt_ref[at], 1)],
                sem.at[slot],
            ).start()
        return 0

    if compact:
        lax.fori_loop(0, trips(b), group, 0)
    else:
        @pl.when(writes_ref[b] > 0)
        def _start():
            lax.fori_loop(0, block // 8, group, 0)

    def wait_for(blk, s):
        def rows(count):  # a wait that answers ``count`` single-row copies
            def wait(j, _):
                part = buf_ref.at[s, pl.ds(0, count)]
                pltpu.make_async_copy(part, part, sem.at[s]).wait()
                return 0

            return wait

        if compact:
            sent = trips(blk)
            lax.fori_loop(0, lax.shift_right_logical(sent, 2), rows(32), 0)
            lax.fori_loop(0, lax.bitwise_and(sent, 3), rows(8), 0)
        else:
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


def _plan(sorted_ids: Array, rows: int, block: int, plan: str = "lane"):
    """The kernel's per-lane scalars and vectors from the sorted ids:
    ``(tgt, src, count, aux)`` as :func:`_kernel` reads them.  A lane WRITES
    if it is the last of its run and its id names a row; ``count`` is a
    block's writing lanes.  ``plan`` is what the CALLER knows of its ids by
    construction:

    ``"lane"``: a writing lane's entry of ``tgt`` / ``src`` lies at the
    lane, and every other entry repeats its block's first write: a block
    that writes issues all ``block`` of them.  Nothing is moved, which is
    what a keyed stream wants (MF's users: 99.3 % of the lanes write).

    ``"compact"``: a block's writing entries lie FIRST in its stretch, in
    lane order (single-row DMAs to distinct rows may go in any order), the
    rest repeat the first: a block issues ``ceil(count / 8)`` trips of
    eight (:func:`descriptors`), at most seven entries over what it writes.
    One sort along the block axis of ``(n / block, block)`` carries the
    rows along, as :func:`_tile_plan` moves its tile rows (a
    ``take_along_axis`` would be a gather of scalars, 10 ns each on the
    TPU).  That sort is what a caller pays to drop its repeats: on the v5e
    10.5 us a call of 98,304 lanes (cell 9's write-back's eleven ``sort
    s32[128,256]`` 0.041 ms a step: PERF.md section 6, PR 54).

    ``"dense"`` (:func:`_dense_kernel` reads it): the kept ids are DENSE
    RANKS, each the one before it or that plus one (a combine's slots), so
    the rows a block writes are the ``count`` neighbours from its first
    kept id on and no lane needs an entry: ``tgt`` is that first row A
    BLOCK, ``src`` is None, and ``aux`` carries per lane the place of its
    run among the block's (its id less the block's first; ``block`` for a
    lane to drop: no place), whether the block's first lane continues the
    block before's run (row 1, as above) and whether its first run is the
    CALL's first (row 2).  Differences and two reductions a block: no
    sort, no scan."""
    n = sorted_ids.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    differs = sorted_ids[1:] != sorted_ids[:-1]
    is_last = jnp.concatenate([differs, jnp.ones((1,), bool)])
    is_first = jnp.concatenate([jnp.ones((1,), bool), differs])

    def by_block(x):
        return x.reshape(n // block, block)

    def aux_rows(*given):  # eight sublanes of int32 a block
        given = [x[:, None] for x in given]
        spare = jnp.zeros((n // block, 8 - len(given), block), jnp.int32)
        return jnp.concatenate(given + [spare], axis=1)

    if plan == "dense":
        ids = by_block(sorted_ids)
        place = jnp.where(ids < rows, ids - ids[:, :1], block)
        flags = [
            jnp.broadcast_to(flag.astype(jnp.int32)[:, :1], place.shape)
            for flag in (by_block(~is_first), ids == sorted_ids[0])
        ]
        count = jnp.sum(
            by_block(is_last & (sorted_ids < rows)), axis=1, dtype=jnp.int32)
        tgt = jnp.minimum(ids[:, 0], rows - 1)
        return tgt, None, count, aux_rows(place, *flags)
    run_end = jax.lax.cummin(
        jnp.where(is_last, iota, n - 1), axis=0, reverse=True
    )
    local = by_block(iota % block)
    end_local = jnp.minimum(by_block(run_end - (iota // block) * block),
                            block - 1)
    continues = jnp.broadcast_to(
        by_block(~is_first).astype(jnp.int32)[:, :1], local.shape
    )
    aux = aux_rows(end_local, continues)
    writes = by_block(is_last & (sorted_ids < rows))
    if plan == "compact":
        key, tgt = jax.lax.sort(
            (jnp.where(writes, local, local + block), by_block(sorted_ids)),
            dimension=1, num_keys=1,
        )
        count = jnp.sum(writes, axis=1, dtype=jnp.int32)
        spare = local >= count[:, None]
        # (a block that writes nothing issues nothing: its entries only
        # have to be in range)
        src = jnp.where(spare, key[:, :1], key) % block
        tgt = jnp.where(spare, jnp.minimum(tgt[:, :1], rows - 1), tgt)
        return tgt.reshape(-1), src.reshape(-1), count, aux
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


def descriptors(count: Array, plan: str, block: int = BLOCK) -> Array:
    """The DMAs the walk starts for blocks whose writing lanes number
    ``count`` (int32, a block an entry), summed: under ``"lane"`` a block
    that writes sends all ``block`` single-row entries of its stretch,
    under ``"compact"`` the trips of eight that hold its writes
    (:func:`_send_and_await`), under ``"dense"`` ONE copy of its whole
    staging slot (:func:`_dense_kernel`)."""
    if plan == "dense":
        sent = count > 0
    elif plan == "compact":
        sent = jax.lax.shift_right_logical(count + 7, 3) * 8
    else:
        sent = jnp.where(count > 0, block, 0)
    return jnp.sum(sent, dtype=jnp.int32)


def sorted_row_update(
    state: Array,
    sorted_ids: Array,
    old_rows: Array,
    deltas: Array,
    *,
    plan: str = "lane",
    interpret: Optional[bool] = None,
) -> Array:
    """``state[r] = old_rows[k] + sum(deltas[lanes of r])`` for every row
    ``r`` some kept lane names; every other row is left as it is.

    ``sorted_ids``: (n,) int32 ASCENDING, lanes to drop at the end with an
    id >= the row count (:func:`sort_by_row` makes them).  ``old_rows``:
    (n, W) — ``state[sorted_ids]`` as the step gathered it (lanes of one
    row carry equal values).  ``deltas``: (n, W).  A dropped lane's old row
    and delta may be anything, NaN included: its run writes nothing.

    ``plan`` is what the CALLER knows of its ids by construction
    (:func:`_plan`): ``"lane"`` for a keyed stream, whose lanes nearly all
    write (the walk then issues a DMA a lane and the plan moves nothing);
    ``"compact"`` for a batch whose ids repeat, which pays one more sort of
    its block-shaped scalars and issues a DMA a row it WRITES.  The same
    rows get the same bytes either way.  (Ids that are dense ranks go to
    :func:`sorted_run_sums`, which sends a block's rows as neighbours.)

    The state is updated in place when the enclosing jit donates it; an
    eager call copies it first.  Off the TPU the kernel is interpreted
    (``interpret=None``: by the default backend).
    """
    return sorted_row_update_counted(
        state, sorted_ids, old_rows, deltas, plan=plan, interpret=interpret,
    )[0]


def _padded_to_blocks(sorted_ids: Array, *rows: Array):
    """Ids and rows of lanes to drop appended up to whole blocks."""
    pad = -sorted_ids.shape[0] % BLOCK
    if not pad:
        return (sorted_ids,) + rows
    sorted_ids = jnp.concatenate(
        [sorted_ids, jnp.full((pad,), _INT32_MAX, jnp.int32)]
    )
    return (sorted_ids,) + tuple(jnp.pad(r, ((0, pad), (0, 0))) for r in rows)


def sorted_row_update_counted(
    state: Array,
    sorted_ids: Array,
    old_rows: Array,
    deltas: Array,
    *,
    plan: str = "lane",
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """:func:`sorted_row_update`'s state and, as an int32 scalar on the
    device, the single-row DMAs the call issued (:func:`descriptors`)."""
    pl, pltpu = _pallas()

    if plan not in ("lane", "compact"):
        raise ValueError(f"sorted_row_update: no plan {plan!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, width = state.shape
    why = refusal(state.shape[1:], state.dtype) or _too_many(
        sorted_ids.shape[0])
    if why is not None and not interpret:
        raise ValueError(f"sorted_row_update: {why}")
    block = BLOCK
    sorted_ids, deltas, old_rows = _padded_to_blocks(
        sorted_ids.astype(jnp.int32), deltas.astype(jnp.float32),
        old_rows.astype(jnp.float32),
    )
    tgt, src, count, aux = _plan(sorted_ids, rows, block, plan)
    if not isinstance(state, jax.core.Tracer):
        state = jnp.copy(state)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(sorted_ids.shape[0] // block,),
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
    state = pl.pallas_call(
        functools.partial(_kernel, block=block, plan=plan),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        grid_spec=grid_spec,
        input_output_aliases={6: 0},  # (tgt, src, count, aux, old, deltas, state)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="sorted_row_update",
    )(tgt, src, count, aux, old_rows, deltas, state)
    return state, descriptors(count, plan, block)


# -- the same sums for ids that are DENSE RANKS: a rule store's combine -------
def _dense_kernel(tgt_ref, writes_ref, aux_ref, head_ref, dl_ref, state_ref,
                  out_ref, buf_ref, carry_ref, sem, *, block: int):
    """:func:`_kernel` for ids that are dense ranks (:func:`_plan`,
    ``"dense"``): one grid step = ``block`` sorted lanes, whose runs'
    totals are summed STRAIGHT INTO THE ORDER THEY ARE WRITTEN IN and sent
    as neighbours.

    tgt_ref / writes_ref: (N / block,) int32 SMEM (scalar prefetch) — the
      first row a block writes and how many: rows ``[tgt, tgt + writes)``.
    aux_ref: (8, block) int32 VMEM — row 0: per lane, its run's place among
      the block's runs (``block``: a lane to drop, no place); row 1: whether
      the block's first lane continues the block before's run; row 2:
      whether the block's first run is the call's first.
    head_ref: (1, W) f32 VMEM — the row the state holds for the call's
      first run (what an earlier call summed of it; zeros else): its old
      row.  Every other row a call writes is new.
    dl_ref: (block, W) f32 VMEM — the deltas, sorted.
    buf_ref / carry_ref / sem: as :func:`_kernel`'s.

    Lane ``j`` adds into row ``place[j]`` of the product, so row ``r`` is
    the sum of the block's ``r``-th run: the same 0/1 mask row, the same
    three exact pieces and the same float32 accumulation as
    :func:`_kernel`'s inclusive prefix sum reads at that run's LAST lane,
    bit for bit, the carry of an open run and the non-finite rule with
    them; it is only born where it is sent from, and no product is added
    for it.  The ``writes`` rows then lie at the front of the staging slot,
    zeros behind them, and the slot goes out WHOLE: one copy of ``block``
    rows to ``tgt`` on, where :func:`_send_and_await` issues a descriptor a
    row.  The zeros behind a block's writes land on rows the NEXT blocks
    write (and, past the last slot, on rows that hold zeros), so a block
    starts its copy only when the block before's has landed: awaited after
    this block's sums are staged, so the copy flies under the next block's
    matmuls, and the state needs ``block`` rows from ``tgt`` on.  On the
    v5e that wait is never on the path (cell 9's 4,992 blocks a step, 72
    writes each: 23.94 ms the whole combine alone, against 23.97 / 24.15 in
    copies of 64 / 32 rows, 25.62 for two overlapping copies of a power of
    two that need no order but nine branches a block, and 29.38 under the
    compact plan; cell 14's 626 blocks of 254 writes 1.75 against 4.13:
    PERF.md section 6, PR 62)."""
    pl, pltpu = _pallas()

    del state_ref  # aliased to out_ref: untouched rows keep their values
    b = pl.program_id(0)
    slot = b % 2

    @pl.when(b == 0)
    def _init():
        carry_ref[:] = jnp.zeros_like(carry_ref)

    place = aux_ref[0:1, :]  # (1, block)
    ii = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    sums = _exact_run_sums((ii == place).astype(jnp.bfloat16), dl_ref[:])
    row = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    first = row == 0
    sums = sums + jnp.where(
        first & (aux_ref[1:2, 0:1] > 0), carry_ref[:], 0.0)
    # the run open at the block's end is the last lane's (all but one row
    # of the sum are zeros: it is exact)
    carry_ref[:] = jnp.sum(
        jnp.where(row == aux_ref[0:1, block - 1:block], sums, 0.0),
        axis=0, keepdims=True,
    )
    head = jnp.where(first & (aux_ref[2:3, 0:1] > 0), head_ref[:], 0.0)
    buf_ref[slot] = jnp.where(row < writes_ref[b], head + sums, 0.0)

    def whole(blk, s):  # a block that writes sends its slot whole
        return pltpu.make_async_copy(
            buf_ref.at[s], out_ref.at[pl.ds(tgt_ref[blk], block)], sem.at[s]
        )

    before = jnp.maximum(b - 1, 0)

    @pl.when((b > 0) & (writes_ref[before] > 0))
    def _previous():
        whole(before, 1 - slot).wait()

    @pl.when(writes_ref[b] > 0)
    def _start():
        whole(b, slot).start()

    @pl.when((b == pl.num_programs(0) - 1) & (writes_ref[b] > 0))
    def _own():
        whole(b, slot).wait()


def sorted_run_sums(
    state: Array,
    slots: Array,
    rows: Array,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """``state[s] = state[s] + sum(rows[lanes of s])`` for every slot ``s``
    some kept lane names, where the kept slots are DENSE RANKS — ascending,
    each the one before it or that plus one — and ``state`` holds zeros
    from the first slot's row on, that row itself aside (a run that began in
    the call before: its old row is read here, as :func:`_open_run_reread`
    reads it for :func:`row_add`): a rule store's combine
    (``ops/dedup._kernel_sums``), one call a stretch of its sorted lanes.
    Returns the state and the DMAs the call started
    (:func:`descriptors`, ``"dense"``), an int32 scalar on the device.

    ``state``: (n, 128) float32 (:func:`refusal`) with ``BLOCK`` rows from
    the first slot of every block of ``BLOCK`` lanes on: a block's copy
    writes zeros over those of them it has no sum for.  (Ranks from 0 are
    at most their lane's place: a row a lane of the batch, padded to whole
    blocks, is enough.)
    ``slots``: (n,) int32, lanes to drop at the end with a slot >= the row
    count.  ``rows``: (n, 128), a dropped lane's anything.  The sums are
    :func:`sorted_row_update`'s bit for bit (:func:`_dense_kernel`); what
    differs is how they leave the kernel: a block's totals are neighbours
    in the state, so they go as ONE copy and the plan needs no scalar a
    lane (two a BLOCK in SMEM).  At most ``MAX_LANES`` lanes a call all the
    same: the caller's stretches are its one permute's too.  In place when
    the enclosing jit donates the state; an eager call copies it first.
    Off the TPU the kernel is interpreted."""
    pl, pltpu = _pallas()

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    height, width = state.shape
    why = refusal(state.shape[1:], state.dtype) or _too_many(slots.shape[0])
    if why is not None and not interpret:
        raise ValueError(f"sorted_run_sums: {why}")
    block = BLOCK
    slots, rows = _padded_to_blocks(
        slots.astype(jnp.int32), rows.astype(jnp.float32))
    tgt, _, count, aux = _plan(slots, height, block, "dense")
    head = _open_run_row(state, slots[0])
    if not isinstance(state, jax.core.Tracer):
        state = jnp.copy(state)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots.shape[0] // block,),
        in_specs=[
            pl.BlockSpec((None, 8, block), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, width), lambda b, *_: (0, 0)),
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
    state = pl.pallas_call(
        functools.partial(_dense_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        grid_spec=grid_spec,
        input_output_aliases={5: 0},  # (tgt, count, aux, head, rows, state)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="sorted_run_sums",
    )(tgt, count, aux, head, rows, state)
    return state, descriptors(count, "dense", block)


def _calls(sorted_ids: Array, order: Array):
    """``(first lane, sorted ids, order)`` of each kernel call a sorted
    batch takes: as few calls as hold ``MAX_LANES`` lanes each, of equal
    size in whole blocks.  One call takes the arrays as they are (nothing
    is sliced)."""
    lanes = sorted_ids.shape[0]
    calls = max(1, -(-lanes // MAX_LANES))
    size = max(1, -(-lanes // (calls * BLOCK))) * BLOCK
    if size >= lanes:
        return [(0, sorted_ids, order)]
    return [
        (lo, sorted_ids[lo:lo + size], order[lo:lo + size])
        for lo in range(0, lanes, size)
    ]


def _pad_for_calls(lanes: int) -> int:
    """Lanes to append so that the calls :func:`_calls` cuts a sorted batch
    into have ONE shape: every process that runs the step traces and lowers
    the kernel once a shape, warm cache or not (cell 10's 851,968 lanes were
    eight calls of 94,720 and one of 94,208).  Nothing for one call."""
    calls = -(-lanes // MAX_LANES)
    return -lanes % (calls * BLOCK) if calls > 1 else 0


def _open_run_row(state: Array, first: Array) -> Array:
    """``(1, W)``: the state's row for ``first``, a call's first id (row 0's
    for a lane to drop, which writes nothing)."""
    return jax.lax.dynamic_slice_in_dim(
        state, jnp.minimum(first, state.shape[0] - 1), 1, axis=0
    )


def _open_run_reread(state: Array, sorted_ids: Array, old: Array) -> Array:
    """``old`` for a call that is not a batch's first: the run at its head
    may have begun in the call before, which then wrote that row (its old
    value + the sum of the lanes it held).  The one old row the kernel
    reads of a run, its last lane's, is read again from the state; a run
    that begins here reads what it had."""
    first = sorted_ids[0]
    last = jnp.sum(sorted_ids == first, dtype=jnp.int32) - 1
    row = _open_run_row(state, first)
    return jax.lax.dynamic_update_slice_in_dim(
        old, row.astype(old.dtype), last, axis=0
    )


def row_add(
    state: Array,
    ids: Array,
    old_rows: Array,
    deltas: Array,
    mask: Optional[Array] = None,
    *,
    interpret: Optional[bool] = None,
) -> Array:
    """``state.at[ids].add(deltas)`` through the row-write kernel (masked
    lanes, ids < 0 and ids >= rows dropped): sort the ids, bring old rows
    and deltas into that order, one write per unique row.

    ``old_rows``: ``state[ids]`` in the order of ``ids``, as the caller has
    gathered it (the MF step has: a kept lane's row must be the state's, a
    dropped lane's may be anything); the kernel's old rows are a permute of
    it, out of fast memory, instead of a second gather out of the state.

    A batch over ``MAX_LANES`` lanes takes several calls, each on its own
    stretch of the sorted lanes, permuted on its own (no batch-sized buffer
    of rows is sliced).  A row whose run lies across two calls is written
    by both, the second time with the whole sum.
    """
    keep = None if mask is None else mask.reshape(-1)
    sid, order = sort_by_row(ids.reshape(-1), keep, state.shape[0])
    for lo, s, o in _calls(sid, order):
        # (a permutation: nothing to clip, and no fill to select after)
        old = jnp.take(old_rows, o, axis=0, mode="clip")
        if lo:
            old = _open_run_reread(state, s, old)
        new = jnp.take(deltas, o, axis=0, mode="clip")
        state = sorted_row_update(state, s, old, new, interpret=interpret)
    return state


# -- the same walk with a SET for its body: a packed rule store's write-back ---
def _row_set_kernel(tgt_ref, src_ref, writes_ref, new_ref, state_ref, out_ref,
                    buf_ref, sem, *, block: int):
    """:func:`_kernel`'s walk with nothing to sum: one grid step = ``block``
    lanes whose new rows (``new_ref``, (block, W) VMEM) are staged as they
    are and sent, one single-row DMA a lane that WRITES, to the rows
    ``tgt_ref`` names (``src_ref`` / ``writes_ref`` as there, the compact
    plan's: the dropped lanes lie between the writes, and the block issues
    as many trips of eight as hold its writes).  A block's writes stay in
    flight while the next is staged and are awaited one block later."""
    pl, _ = _pallas()

    del state_ref  # aliased to out_ref: untouched rows keep their values
    b = pl.program_id(0)
    slot = b % 2
    buf_ref[slot] = new_ref[:]
    _send_and_await(tgt_ref, src_ref, writes_ref, buf_ref, out_ref, sem,
                    slot, b * block, block=block, plan="compact")


def sorted_row_set(
    state: Array,
    ids: Array,
    new_rows: Array,
    *,
    interpret: Optional[bool] = None,
) -> Array:
    """``state[r] = new_rows[k]`` for every row ``r`` a kept lane ``k``
    names, bit for bit (a copy, no arithmetic); every other row is left as
    it is.  The row kernel's walk (:func:`sorted_row_update`) with a set for
    its body, under the compact plan (a DMA a row WRITTEN: the dropped lanes
    cost their block's base and no descriptor): on the v5e XLA's ``set`` of
    32,768 sorted distinct 128-lane rows is 72 ns a lane, kept or dropped,
    serial; a single-row DMA out of a staged block is what the row kernel
    issues at 10-13 (PERF.md section 6, PRs 47 and 54).

    ``state``: (rows, 128) float32 (:func:`refusal`).  ``ids``: (n,) int32,
    the kept lanes' DISTINCT (two lanes may not name one row: their DMAs
    would race); a lane to drop carries an id >= the row count and may lie
    anywhere (a packed rule store's lie between the lanes that write:
    ``core/store._rewrite_packed``).  ``new_rows``: (n, 128).  At most
    ``MAX_LANES`` lanes a call.  In place when the enclosing jit donates the
    state; an eager call copies it first.  Off the TPU the kernel is
    interpreted."""
    pl, pltpu = _pallas()

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, width = state.shape
    n = ids.shape[0]
    why = refusal(state.shape[1:], state.dtype) or _too_many(n)
    if why is not None and not interpret:
        raise ValueError(f"sorted_row_set: {why}")
    block = BLOCK
    ids, new_rows = _padded_to_blocks(
        ids.astype(jnp.int32), new_rows.astype(state.dtype))
    # distinct ids: every kept lane is the last of its run, and writes; the
    # caller's dropped lanes lie between them, so the plan compacts
    tgt, src, count, _ = _plan(ids, rows, block, "compact")
    if not isinstance(state, jax.core.Tracer):
        state = jnp.copy(state)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ids.shape[0] // block,),
        in_specs=[
            pl.BlockSpec((block, width), lambda b, *_: (b, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the state stays in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, block, width), state.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_row_set_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        grid_spec=grid_spec,
        input_output_aliases={4: 0},  # (tgt, src, count, new rows, state)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="sorted_row_set",
    )(tgt, src, count, new_rows, state)


# -- rows of several registers: a read-modify-write per touched tile row ------
def _tile_kernel(tiles_ref, words_ref, counts_ref, dl_ref, table_ref, out_ref,
                 tile_buf, sem, *, block: int, assign: bool = False):
    """One grid step = the adds of ``block`` sorted lanes (the kept ones
    first), under the reads of the next block's tile rows and the writes of
    the block before's.  Step ``g`` awaits the writes of block ``g - 3``
    (block ``g`` reads into their slot), starts the reads of block ``g``,
    and then works block ``g - 1``: awaits its reads, adds its lanes,
    starts its writes.  Every step is that and no more: the grid is three
    steps longer than the blocks, and three blocks of zeros before the
    first block's counts and three after the last's make the steps at
    either end do nothing where there is no block.

    Ids ASCEND, so of a block's tile rows only the FIRST can be one an
    earlier block has open (a hot row's run spans many blocks: one tile row
    is then first and last of each); every other is strictly greater than
    all an earlier block reads or writes.  Such a shared first tile row is
    CARRIED: the block before does not write it, this block does not read
    it, it is copied from that block's slot to this one's in VMEM after
    that block's adds, and written by the block that closes it.  So within
    a call every touched tile row is read once and written once, no two
    copies touch the same bytes, and nothing orders them but the slots.

    tiles_ref: (N,) int32 SMEM (scalar prefetch) — at the head of each
      block's stretch, the tile rows (row // 8) its kept lanes touch,
      ascending, each once.
    words_ref: (N,) int32 SMEM — per kept lane, its tile row's place in
      the block's list (bits 0-7) and its row's sublane in the tile (8-10).
    counts_ref: (5 N / block + 30,) int32 SMEM — per block, how many tile
      rows, how many kept lanes, 1 where its first tile row is carried over
      from the block before, and two words of a bit a trip of eight lanes:
      where the trip's eight lie in ONE tile row, and where the trip before
      does too and in the same one; blocks -3 to -1 and the three after the
      last are zeros.
    dl_ref: (block, w) f32 VMEM — the deltas of block ``g - 1``, sorted, at
      the width ``w`` <= ``W`` their caller holds them (a logical row of 600
      lanes in a table of 640: nothing pads it to ``W`` in HBM for the
      kernel's sake).
    table_ref / out_ref: the aliased (rows, W) table in HBM.
    tile_buf: (3, block, 8, W) f32 VMEM — three blocks' tile rows: one
      being read, one being added to, one being written back.
    sem: (2, 3) DMA semaphores — reads and writes of each slot.

    The adds (PR 74), where rows are ADDED and are as wide as
    :func:`_holds_tile_rows` takes (one register or five: any other width,
    and ``assign``, keep the walk lane by lane of the last paragraph but
    one).  Ids ascend, so the lanes of one tile row lie side by
    side, and a block's lanes are walked eight at a time.  Where eight lie
    in ONE tile row (the plan says which trips do: ``counts_ref``), that
    tile row is LOADED once into registers, the eight lanes are added to
    that value, each to its sublane by a select against a sublane iota (a
    lane's delta, broadcast to the eight sublanes, is added where the iota
    names the lane's row: the same float32 add on the same operands as
    before, in the same order, so every row's sum keeps its bits), the
    following trips too for as long as they lie in that tile row, and it is
    STORED once.  Before, every lane loaded its row of the tile, added and
    stored it, and the next lane's load, at an address the compiler cannot
    tell from the store's, waited for that store: a hot row's run was one
    chain of load, add and store from end to end.  Any other eight lanes
    are added one by one as they were, and a block with no such trip, or
    with fewer than one in ``_RUN_TRIPS_SHARE`` (the plan clears its bits),
    is walked by the old loop alone and pays no branch a trip.

    What its time is made of on the v5e (PERF.md section 6, PRs 41 and 74),
    all of it issued by the one scalar core, one thing after the other: a
    DMA descriptor 13-17 ns eight a trip (22 one a trip), two a tile row:
    22-27 ns a tile row at one register, 65 at five (19 bundles of the
    compiled kernel a descriptor, its bounds checks among them: the scalar
    core's two slots a bundle are what is full); an add one lane at a time
    9.1-9.4 ns a lane at one register a row (seven bundles, and as long
    again waiting for the store before it), ~6 at three, 11.6 at five
    (20.8 one a trip); **an add to a tile row held in registers 5.0 ns a
    lane at one register** (39 bundles a trip of eight: 851,968 lanes in
    runs of 64 read 4.62 ms where lane by lane read 8.12), ~6.7 at five
    (cell 5's ids: 2.857 -> 2.548 ms for the 55 % of its lanes that lie in
    such trips), and at three DEARER than lane by lane, which is cheapest
    there (cell 7's ids, 22 % of the lanes: 3.16 -> 3.15-3.19 ms; cell 13,
    whose combine adds dense ranks, nearly every lane: -0.64 % end to end),
    so three registers keep the old loop; a wait 6.5 ns, one for sixteen
    tile rows.
    The copies themselves run under the adds: the three slots hide their
    latency, not their issue.  The walk by TILE ROW that ISSUE 74 set out
    (a loop a tile row over its lanes, the tile row held from its first
    lane to its last) read 18.6-22.2 ms on cell 10's ids where this reads
    11.78 and the old loop 14.10: five tile rows in six hold ONE lane, and
    a loop a tile row is 40 bundles and two taken branches before its
    first add.  The body is short on purpose (every index a ``lax``
    equation or two and nothing computed twice, the eight lanes of a trip
    a loop unrolled where the kernel is lowered): every process that runs
    the step traces it and lowers it, each unrolled copy by itself, warm
    cache or not (cell 10's nine calls: 0.18 -> 0.25 s in this sandbox).

    ``assign``: a kept lane's row REPLACES its row of the tile instead of
    being added to it (:func:`sorted_tile_assign`, a rule store's wide
    write-back): the same copies, the walk lane by lane, one store a lane
    and no load (distinct ids: a lane a row, 1.05-1.2 a tile row in cells
    13 and 15, nothing to hold); the tile's other rows go back as they
    were read.

    Either body touches lanes ``[0, w)`` of a lane's row of the tile and
    no other: lanes ``[w, W)`` of every row, an assigned one's too, are
    written back as they were read, bit for bit (the table's pad lanes,
    whatever they hold).  At ``w`` = ``W`` the slice is the whole row.
    """
    pl, pltpu = _pallas()
    lax = jax.lax  # not jnp: an operator on a tracer is a jitted call to trace
    w = dl_ref.shape[1]
    lanes = slice(None) if w == tile_buf.shape[3] else pl.ds(0, w)

    del table_ref  # aliased to out_ref
    g = pl.program_id(0)
    reads, writes = 0, 1
    counts = lax.mul(g, 5)  # where block g - 3's five counts lie

    def count(offset):  # blocks g - 3 (offsets 0-4) to g (offsets 15-19)
        return counts_ref[lax.add(counts, offset)]

    ahead = lax.rem(g, 3)  # the slot of block g, and of block g - 3
    slot = lax.rem(lax.add(g, 2), 3)  # the slot of block g - 1
    opened, kept = count(10), count(11)  # of block g - 1
    skip = count(17)  # 1 where block g's first tile row is carried over
    stretch = lax.mul(g, block)  # block g's stretch of tiles_ref
    base = lax.sub(stretch, block)  # block g - 1's, and of words_ref

    def tile_row(i):
        row = pl.multiple_of(lax.mul(tiles_ref[i], 8), 8)
        return out_ref.at[pl.ds(row, 8)]

    def await_copies(copies, which, of):
        # a DMA semaphore counts bytes: a block's copies are answered by a
        # wait the size of sixteen tile rows for every sixteen of them and
        # one the size of a tile row for each of the rest
        def wait_for(tiles):
            def wait(j, _):
                part = tile_buf.at[0, pl.ds(0, tiles)]
                pltpu.make_async_copy(part, part, sem.at[which, of]).wait()
                return 0

            return wait

        lax.fori_loop(0, lax.shift_right_logical(copies, 4), wait_for(16), 0)
        lax.fori_loop(0, lax.bitwise_and(copies, 15), wait_for(1), 0)

    # block g reads into the slot the third block before wrote from: every
    # tile row of that block but a last one that block g - 2 carried on
    await_copies(lax.sub(count(0), count(7)), writes, ahead)
    first = lax.add(stretch, skip)

    def read(j):  # every tile row but a carried first one
        pltpu.make_async_copy(
            tile_row(lax.add(first, j)), tile_buf.at[ahead, lax.add(skip, j)],
            sem.at[reads, ahead],
        ).start()

    _each(lax.sub(count(15), skip), read)
    await_copies(lax.sub(opened, count(12)), reads, slot)

    def add(lane):
        # one float32 add a lane, in the order of the batch: what XLA's
        # scatter-add and a plain ``np.add.at`` do, rounding for rounding
        word = words_ref[lax.add(base, lane)]
        at = (slot, lax.bitwise_and(word, 255),
              pl.ds(lax.shift_right_logical(word, 8), 1), lanes)
        if assign:
            tile_buf[at] = dl_ref[pl.ds(lane, 1), :]
        else:
            tile_buf[at] = lax.add(tile_buf[at], dl_ref[pl.ds(lane, 1), :])

    holds = _holds_tile_rows(tile_buf.shape[3], assign)
    sublanes = lax.broadcasted_iota(jnp.int32, (8, w), 0)
    whole, goes_on = count(13), count(14)  # a bit a trip of eight lanes

    def bit(word, trip):
        return lax.bitwise_and(lax.shift_right_logical(word, trip), 1)

    def eight(trip):
        first = lax.mul(trip, 8)

        def in_registers():
            # all eight lie in ONE tile row: it is loaded once, the lanes
            # are added to that VALUE, each to its sublane, trip after trip
            # for as long as the trips lie in it, and it is stored once: no
            # lane's load waits behind another lane's store
            at = (slot, lax.bitwise_and(words_ref[lax.add(base, first)], 255),
                  slice(None), lanes)

            def add_eight(carry):
                lane0 = lax.mul(carry[0], 8)

                def add_to(k, held):
                    lane = lax.add(lane0, k)
                    delta = lax.broadcast_in_dim(
                        dl_ref[pl.ds(lane, 1), :], (8, w), (0, 1))
                    mine = lax.eq(sublanes, lax.shift_right_logical(
                        words_ref[lax.add(base, lane)], 8))
                    return lax.select(mine, lax.add(held, delta), held)

                after = lax.add(carry[0], 1)
                # (a shift by 32 is no shift: nothing follows the last trip)
                more = lax.bitwise_and(
                    lax.convert_element_type(lax.lt(after, 32), jnp.int32),
                    bit(goes_on, after))
                return after, lax.fori_loop(
                    0, 8, add_to, carry[1], unroll=True), more

            after, held, _ = lax.while_loop(
                lambda carry: lax.ne(carry[2], 0), add_eight,
                (trip, tile_buf[at], jnp.int32(1)))
            tile_buf[at] = held
            return after

        def one_by_one():
            lax.fori_loop(
                0, 8, lambda k, c: (add(lax.add(first, k)), c)[1], 0,
                unroll=True)
            return lax.add(trip, 1)

        return lax.cond(lax.ne(bit(whole, trip), 0), in_registers, one_by_one)

    if not holds:  # lane by lane, as every block was walked until PR 74
        _each(kept, add)
    else:
        @pl.when(lax.eq(whole, 0))
        def _apart():  # no eight lanes of the block lie in one tile row
            _each(kept, add)

        @pl.when(lax.ne(whole, 0))
        def _runs():
            trips = lax.shift_right_logical(kept, 3)
            lax.while_loop(
                lambda trip: lax.lt(trip, trips), eight, jnp.int32(0))
            lax.fori_loop(
                lax.mul(trips, 8), kept, lambda i, c: (add(i), c)[1], 0)

    @pl.when(lax.gt(skip, 0))
    def _carry():  # the open tile row, as the adds left it, to the next slot
        tile_buf[ahead, 0] = tile_buf[slot, lax.sub(opened, 1)]

    def write(j):  # every tile row but a last one that block g carries on
        pltpu.make_async_copy(
            tile_buf.at[slot, j], tile_row(lax.add(base, j)),
            sem.at[writes, slot],
        ).start()

    _each(lax.sub(opened, skip), write)


def _holds_tile_rows(width: int, assign: bool) -> bool:
    """Whether the tile kernel adds the trips of eight lanes that lie in one
    tile row to that tile row held in registers (``_tile_kernel``): an ADD
    of rows as wide as the chip has priced it and it won."""
    return not assign and width // 128 in _HELD_ROW_REGISTERS


def _bits(flags: Array) -> Array:
    """``(n, k)`` booleans, ``k`` <= 32, as ``(n,)`` int32: bit ``i`` of a
    word is ``flags[:, i]``."""
    weights = jnp.left_shift(
        jnp.uint32(1), jnp.arange(flags.shape[1], dtype=jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.sum(jnp.where(flags, weights, jnp.uint32(0)), axis=1,
                dtype=jnp.uint32), jnp.int32)


def _tile_plan(sorted_ids: Array, rows: int, block: int, holds: bool = True):
    """The tile kernel's scalars from the sorted ids (``holds``:
    :func:`_holds_tile_rows`; a kernel that does not is told of no trip that
    lies in one tile row)."""
    assert block <= 256, "a bit a trip of eight lanes in one int32"
    ids = sorted_ids.reshape(-1, block)
    local = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)
    kept = ids < rows  # the dropped lanes sort to the end
    tile = ids // 8
    # a kept lane opens a tile row of its block unless the lane before it
    # lies in the same (ids ascend)
    before = jnp.concatenate(
        [jnp.full_like(tile[:, :1], -1), tile[:, :-1]], axis=1
    )
    opens = kept & (tile != before)
    place = jnp.cumsum(opens, axis=1, dtype=jnp.int32) - 1
    words = place | ((ids % 8) << 8)
    # the opened tile rows to the front of their block (a take_along_axis
    # would be a gather of scalars, 10 ns each on the TPU: a sort of the
    # block that carries them along)
    tiles = jax.lax.sort(
        (jnp.where(opens, local, local + block), tile), dimension=1,
        num_keys=1,
    )[1]
    # a block's first tile row is carried over where the last lane of the
    # block before lies in it (kept, as every lane before a kept one is)
    last = jnp.concatenate([jnp.full((1,), -1, tile.dtype), tile[:-1, -1]])
    carried = kept[:, 0] & (tile[:, 0] == last)
    # eight lanes side by side (a trip of the kernel's walk) lie in ONE tile
    # row where the first and the last do: a bit a trip of the block, and a
    # second where the trip before lies in the same tile row (a run goes on)
    eights = place.reshape(ids.shape[0], -1, 8)
    whole = kept.reshape(eights.shape)[:, :, 7] & (
        eights[:, :, 0] == eights[:, :, 7])
    # (a block with few such trips is walked lane by lane: the look at a
    # trip's bit costs every OTHER trip of the block a branch)
    whole &= _RUN_TRIPS_SHARE * jnp.sum(whole, axis=1, keepdims=True) >= (
        jnp.sum(kept, axis=1, keepdims=True) // 8)
    if not holds:
        whole = jnp.zeros_like(whole)
    goes_on = whole[:, 1:] & whole[:, :-1] & (
        eights[:, 1:, 0] == eights[:, :-1, 0])
    goes_on = jnp.concatenate([jnp.zeros_like(whole[:, :1]), goes_on], axis=1)
    counts = jnp.stack(
        [jnp.sum(opens, axis=1, dtype=jnp.int32),
         jnp.sum(kept, axis=1, dtype=jnp.int32),
         carried.astype(jnp.int32), _bits(whole), _bits(goes_on)], axis=1,
    )
    # the grid's first and last three steps work blocks that are not there
    edge = jnp.zeros((3, 5), jnp.int32)
    counts = jnp.concatenate([edge, counts, edge])
    return tiles.reshape(-1), words.reshape(-1), counts.reshape(-1)


def sorted_tile_add(
    table: Array,
    sorted_ids: Array,
    deltas: Array,
    *,
    interpret: Optional[bool] = None,
) -> Array:
    """``table[r] += sum(deltas[lanes of r])`` for every row ``r`` some kept
    lane names, a read-modify-write of each touched tile row (8 rows);
    every other tile row is left as it is.

    ``table``: (rows, W) float32, whole tiles (:func:`tile_refusal`).
    ``sorted_ids``: (n,) int32 ASCENDING, lanes to drop at the end with an
    id >= the row count (:func:`sort_by_row`).  ``deltas``: (n, w) in that
    order, ``w`` <= ``W``: a lane is added into lanes ``[0, w)`` of its row
    and lanes ``[w, W)`` of every row are left as they are, so no caller
    pads a batch's rows to ``W`` for the kernel (a pad of 57,344 x 640 f32
    in HBM was 0.45 ms on the v5e: PERF.md section 6, PR 57); a dropped
    lane's row may be anything.  In place when the enclosing
    jit donates the table; an eager call copies it first.  Off the TPU the
    kernel is interpreted.

    The plan and the kernel are one jitted function, inlined where it is
    called: the calls a batch takes have one shape, so a step traces the
    kernel once and lowers it once (equal equations share a lowering), not
    once a call, and its text stays what separate calls give.  Every
    process that runs the step pays both, warm cache or not: traced a call,
    this walk cost cell 5 a second of set-up.
    """
    return _sorted_tile_add_counted(table, sorted_ids, deltas, interpret)[0]


def _sorted_tile_add_counted(table, sorted_ids, deltas, interpret,
                             assign=False, first=None):
    """:func:`sorted_tile_add`'s ``(table, kept lanes, tile rows read and
    written)``, the last two from the plan's own counts (``assign``:
    :func:`sorted_tile_assign`'s; ``first``: :func:`_tile_add`'s)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    name = "sorted_tile_assign" if assign else "sorted_tile_add"
    if deltas.ndim != 2 or deltas.shape[1] > table.shape[-1]:
        raise ValueError(
            f"{name}: rows of shape {tuple(deltas.shape[1:])} for a table "
            f"of rows of shape {tuple(table.shape[1:])}")
    why = tile_refusal(table.shape, table.dtype) or _too_many(
        sorted_ids.shape[0])
    if why is not None and not interpret:
        raise ValueError(f"{name}: {why}")
    return _tile_add(table, sorted_ids, deltas, first, block=BLOCK,
                     interpret=interpret, assign=assign)


def sorted_tile_assign(
    table: Array,
    sorted_ids: Array,
    new_rows: Array,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """``table.at[sorted_ids].set(new_rows, mode="drop")`` for DISTINCT
    ascending ids and rows of several 128-lane registers, and the tile rows
    it read and wrote: :func:`sorted_tile_add`'s walk with a store for its
    body (a Pallas DMA cannot write ONE row wider than 128 lanes: eight rows
    to a tile).  Every touched tile row of 8 rows is read, the kept lanes'
    rows replaced, and the tile row written back once; its other rows, NaN
    and -0.0 included, come back bit for bit.  ``new_rows``: (n, w), ``w``
    <= the table's ``W``: lanes ``[0, w)`` of an assigned row are replaced
    and its lanes ``[w, W)`` (the table's pad lanes, whatever they hold)
    come back as they were read, so the caller neither pads the new rows
    nor merges them into the gathered ones.  ``sorted_ids``: (n,) int32
    ascending and distinct, lanes to drop at the end with an id >= the row
    count; a batch over ``MAX_LANES`` lanes goes in several calls of equal
    size.  The write-back of a rule store whose row is wider than a
    register (``core/store._rewrite_packed``)."""
    pad = _pad_for_calls(sorted_ids.shape[0])
    if pad:
        sorted_ids = jnp.concatenate(
            [sorted_ids, jnp.full((pad,), _INT32_MAX, jnp.int32)])
        new_rows = jnp.pad(new_rows, ((0, pad), (0, 0)))
    opened = jnp.zeros((), jnp.int32)
    for lo, ids, _ in _calls(sorted_ids, sorted_ids):
        table, _, moved = _sorted_tile_add_counted(
            table, ids, new_rows[lo:lo + ids.shape[0]], interpret, True)
        opened = opened + moved
    return table, opened


@functools.partial(
    jax.jit, static_argnames=("block", "interpret", "assign"), inline=True)
def _tile_add(table, sorted_ids, deltas, first=None, *, block: int,
              interpret: bool, assign: bool = False):
    """One call of the tile kernel.  ``deltas``: the ``n`` sorted lanes'
    rows, or, with ``first`` (an int32 scalar), the rows of a longer sorted
    batch of which this call's lanes begin at block ``first``: the kernel's
    pipeline reads its blocks from there, nothing is sliced
    (:func:`_tile_add_calls`)."""
    pl, pltpu = _pallas()

    rows, width = table.shape
    n = sorted_ids.shape[0]
    if n == 0:
        zero = jnp.zeros((), jnp.int32)
        return table, zero, zero
    sorted_ids = sorted_ids.astype(jnp.int32)
    deltas = deltas.astype(jnp.float32)
    pad = -n % block
    if pad:
        sorted_ids = jnp.concatenate(
            [sorted_ids, jnp.full((pad,), _INT32_MAX, jnp.int32)]
        )
        assert first is None, "a stretch of a longer batch is whole blocks"
        deltas = jnp.pad(deltas, ((0, pad), (0, 0)))
    tiles, words, counts = _tile_plan(
        sorted_ids, rows, block, _holds_tile_rows(width, assign))

    blocks = (n + pad) // block
    if first is not None:
        # one scalar more at the end of the counts, which no step reads
        counts = jnp.concatenate([counts, first.reshape(1)])

    def delta_block(g, tiles_ref, words_ref, counts_ref):
        at = jax.lax.min(jax.lax.max(g - 1, 0), blocks - 1)
        if first is None:
            return at, 0
        return at + counts_ref[5 * blocks + 30], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(blocks + 3,),  # step g works block g - 1: `_tile_kernel`
        in_specs=[
            # the deltas' own width: w <= W
            pl.BlockSpec((block, deltas.shape[1]), delta_block),
            pl.BlockSpec(memory_space=pl.ANY),  # the table stays in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((3, block, 8, width), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
    )
    table = pl.pallas_call(
        functools.partial(_tile_kernel, block=block, assign=assign),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        grid_spec=grid_spec,
        input_output_aliases={4: 0},  # (tiles, words, counts, deltas, table)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_TILE_VMEM_BYTES,
        ),
        interpret=interpret,
        name="sorted_row_assign_tiles" if assign else "sorted_row_update_tiles",
    )(tiles, words, counts, deltas, table)
    # a block's (opened, kept, carried): a carried tile row is opened by
    # both blocks that share it and moved once
    opened, kept, carried = jnp.sum(
        counts[:5 * blocks + 30].reshape(-1, 5)[:, :3], axis=0)
    return table, kept, opened - carried


# -- narrow rows under a rule: a read-modify-write per touched tile of 128 rows
# lanes of a narrow physical row the set kernel takes: the sublane tiles the
# TPU gives a float32 table of rows that narrow anyway
SET_ROW_LANES = (1, 2, 4, 8)
_TILE_ROWS = 128  # rows to a tile of the transposed (lanes, rows) table
# a call of the set kernel prefetches, a lane, its tile, its word and the
# row's values into SMEM: as many words as MAX_LANES lanes of the other two
# kernels take
_SET_SMEM_WORDS = 2 * MAX_LANES


def _each(count, body) -> None:
    """``body(i)`` for ``i`` in ``[0, count)``, eight to a trip of the loop
    and the rest one by one: Mosaic unrolls a loop wholly or not at all,
    and a trip's scalar work packs the better the more of it there is (on
    the v5e a DMA descriptor issued one a trip is 22 ns, eight a trip 17;
    a lane set 9.4 ns and 6.8: PERF.md section 6, PR 35; a lane's
    load-add-store in the tile ADD kernel 9.1-9.4 ns eight a trip at one
    register, and 5.0 where the eight are added to a tile row held in
    registers: PR 74).  The eight are a
    loop of their own, unrolled where the kernel is lowered: ``body`` is
    traced twice, not nine times, and the arithmetic here and in the
    kernel is written in ``lax`` (``count // 8`` is a dozen equations and
    a nested function to lower where a shift is one): every process that
    runs the step traces and lowers the kernel, warm cache or not, and the
    first form of this one cost cell 6 four seconds of set-up."""
    whole = jax.lax.shift_right_logical(count, 3)

    def group(g, _):
        jax.lax.fori_loop(
            0, 8, lambda k, c: (body(g * 8 + k), c)[1], 0, unroll=True
        )
        return 0

    def single(i, _):
        body(i)
        return 0

    jax.lax.fori_loop(0, whole, group, 0)
    jax.lax.fori_loop(whole * 8, count, single, 0)


def set_refusal(table_shape: Tuple[int, ...], dtype) -> Optional[str]:
    """Why :func:`sorted_tile_set` cannot take this table (None: it can)."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"rows are {jnp.dtype(dtype).name}, the kernel moves float32"
    if len(table_shape) != 2 or table_shape[1] not in SET_ROW_LANES:
        return (
            f"rows of shape {tuple(table_shape[1:])}, the kernel takes flat "
            f"rows of {SET_ROW_LANES} lanes (a whole sublane tile)"
        )
    if table_shape[0] % _TILE_ROWS != 0:
        return f"{table_shape[0]} rows are not whole tiles of {_TILE_ROWS}"
    return None


def _tile_set_kernel(tiles_ref, words_ref, counts_ref, vals_ref, table_ref,
                     out_ref, tile_buf, sem, *, block: int, width: int,
                     lanes: int, span: int):
    """One grid step = the copies that ``block`` sorted lanes OPEN, and every
    lane of the tiles they move (the last may reach into later blocks'
    lanes, so no two steps touch one tile and nothing orders their copies).
    A copy moves ONE touched tile or, where ``span`` > 1, a whole span: the
    ``span`` tiles of an aligned group that the ids touch in several places
    (:func:`_tile_set_plan`), one descriptor each way for all of them.

    tiles_ref: (N,) int32 SMEM (scalar prefetch) — at the head of each
      block's stretch, the first tile (row // 128) of each span whose first
      lane lies in the block, ascending, then the single tiles it opens.
    words_ref: (N,) int32 SMEM — per kept lane, its tile's place in its
      OWNER block's buffer (bits 0-15) and its row's lane in the tile
      (16-22).  The spans' tiles lie first there, side by side in the
      list's order, then the single tiles.
    counts_ref: (4 N / block,) int32 SMEM — per block, how many single
      tiles and how many spans it opens, the first lane it owns and how
      many lanes it owns.
    vals_ref: (width x N,) float32 SMEM — the new rows, lane-major.
    table_ref / out_ref: the aliased (tiles, L, 128) view of the table in
      HBM.
    tile_buf: (3, P, L, 128) f32 VMEM — three blocks' tiles: one being
      read, one being set, one being written back.
    sem: (2, 3) DMA semaphores — reads and writes of each slot.
    """
    pl, pltpu = _pallas()

    del table_ref  # aliased to out_ref
    b, blocks = pl.program_id(0), pl.num_programs(0)
    reads, writes = 0, 1

    def start_copies(blk, which):
        """Block ``blk``'s tiles from the table into its slot (``reads``)
        or back (``writes``)."""
        slot = jax.lax.rem(blk, 3)
        spans = counts_ref[4 * blk + 1] if span > 1 else 0

        def start(table, held):
            pair = (table, held) if which == reads else (held, table)
            pltpu.make_async_copy(*pair, sem.at[which, slot]).start()

        def whole_span(j):
            start(out_ref.at[pl.ds(tiles_ref[blk * block + j], span)],
                  tile_buf.at[slot, pl.ds(j * span, span)])

        def single(j):
            start(out_ref.at[tiles_ref[blk * block + spans + j]],
                  tile_buf.at[slot, spans * span + j])

        if span > 1:
            _each(spans, whole_span)
        _each(counts_ref[4 * blk], single)

    def await_copies(blk, which):
        # a DMA semaphore counts bytes: a block's copies are answered by a
        # wait the size of sixteen tiles for every sixteen tiles of them and
        # one the size of a tile for each of the rest (a wait a tile is 6.5
        # ns on the v5e, 2.3 ms a step of cell 6)
        slot, count = jax.lax.rem(blk, 3), counts_ref[4 * blk]
        if span > 1:
            count = count + span * counts_ref[4 * blk + 1]

        def wait_for(tiles):
            def wait(j, _):
                part = tile_buf.at[0, pl.ds(0, tiles)]
                pltpu.make_async_copy(part, part, sem.at[which, slot]).wait()
                return 0

            return wait

        jax.lax.fori_loop(
            0, jax.lax.shift_right_logical(count, 4), wait_for(16), 0)
        jax.lax.fori_loop(0, count & 15, wait_for(1), 0)

    # reads run a block ahead: block 0's and block 1's start in step 0.  The
    # slot a block reads into is the one the third block before wrote from
    def ahead(i, _):
        blk = b + i

        @pl.when(blk >= 3)
        def _free_slot():
            await_copies(blk - 3, writes)

        start_copies(blk, reads)
        return 0

    jax.lax.fori_loop(
        (b != 0).astype(jnp.int32), 1 + (b + 1 < blocks).astype(jnp.int32),
        ahead, 0,
    )
    await_copies(b, reads)
    slot = jax.lax.rem(b, 3)
    shape = tile_buf.shape[2:]  # (L, 128)
    sublane = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    column = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    first = counts_ref[4 * b + 2]

    def set_row(k):
        lane = first + k
        word = words_ref[lane]
        at = (slot, word & 0xFFFF)
        # the row as a column of the tile: its values down the sublanes,
        # zeros on the pad ones
        new = jnp.zeros(shape, jnp.float32)
        for s in reversed(range(width)):
            value = jax.lax.broadcast(vals_ref[s * lanes + lane], shape)
            new = jax.lax.select(sublane == s, value, new)
        tile_buf[at] = jax.lax.select(
            column == (word >> 16), new, tile_buf[at])

    _each(counts_ref[4 * b + 3], set_row)
    start_copies(b, writes)

    @pl.when(b == blocks - 1)
    def _drain():  # the last three blocks' writes
        jax.lax.fori_loop(
            jax.lax.max(blocks - 3, 0), blocks,
            lambda blk, _: (await_copies(blk, writes), 0)[1], 0,
        )


def _span_places(block: int, span: int) -> int:
    """The tiles a block of lanes may hold in VMEM at once: every lane a
    tile of its own, or, dearer, every two lanes a whole span (a span is
    touched in two tiles or more; the last copy a block opens may own one
    lane of it alone), and whole sixteens (a wait's size)."""
    return block if span == 1 else span * (block // 2) + 16


class TileSetCounts(NamedTuple):
    """What :func:`sorted_tile_set` counted on the device, int32 scalars
    summed over its calls: the tiles the ids ``touched``, the ``copies`` it
    made of them each way (a DMA descriptor in and one out for each) and
    the tiles those copies ``moved`` each way, the untouched tiles inside a
    span among them.  With one-tile copies the three are one number."""

    touched: Array
    copies: Array
    moved: Array


def _running_max(x: Array, reverse: bool = False) -> Array:
    """The running maximum of ``x`` (blocks, block) in the FLAT order of its
    lanes, block after block (``reverse``: from the last lane back).  A scan
    along each block and the blocks before it by a triangle of their maxima:
    on the v5e a scan of 32,768 lanes in one row is 8.5 us and this is under
    two, and a plan has three."""
    local = jax.lax.cummax(x, axis=1, reverse=reverse)
    blk = jnp.arange(x.shape[0])
    earlier = (blk[None, :] > blk[:, None] if reverse
               else blk[None, :] < blk[:, None])
    before = jnp.max(
        jnp.where(earlier, local[None, :, 0 if reverse else -1],
                  jnp.iinfo(x.dtype).min), axis=1)
    return jnp.maximum(local, before[:, None])


def _tile_set_plan(sorted_ids: Array, rows: int, block: int, span: int = 1):
    """The set kernel's scalars from the sorted DISTINCT ids: ``(tiles,
    words, counts, counted)``, ``counted`` a :class:`TileSetCounts`.

    The table's tiles stand in aligned groups of ``span``.  A group that is
    whole (the table's last few tiles may not be) and in which the ids touch
    two tiles or more is copied as ONE span, the tiles
    between them read and written back as they are; every other touched
    tile is copied alone.  A copy belongs to the block of lanes that holds
    its first lane and owns every lane of its tiles, in whichever block they
    lie.  Scans, sorts and selects of the call's lanes only (a gather of
    scalars is 75 ns a lane on the TPU)."""
    n = sorted_ids.shape[0]
    by_block = (n // block, block)
    ids = sorted_ids.reshape(by_block)
    kept = ids < rows  # the dropped lanes sort to the end
    tile = ids // _TILE_ROWS
    local = jax.lax.broadcasted_iota(jnp.int32, by_block, 1)
    blk = jax.lax.broadcasted_iota(jnp.int32, by_block, 0)

    def shifted(x, fill, by=1):  # the lane before's (after's: by = -1)
        pad, flat = jnp.full((1,), fill, x.dtype), x.reshape(-1)
        parts = [pad, flat[:-1]] if by > 0 else [flat[1:], pad]
        return jnp.concatenate(parts).reshape(by_block)

    new_tile = kept & (tile != shifted(tile, -1))  # (ids ascend)
    touched = jnp.sum(new_tile, dtype=jnp.int32)
    # a copy's place in its block's buffer, the spans first and then the
    # single tiles, is carried from its first lane to the lanes that follow
    # it, over as many blocks as they run, as a running maximum
    places = _span_places(block, span)
    high = 1 << (places - 1).bit_length()
    spanned = jnp.zeros(by_block, bool)
    if span > 1:
        group = tile // span
        new_group = kept & (group != shifted(group, -1))
        # the last tile its group's lanes touch, at every lane: the tile at
        # the group's last lane, carried back (tiles ascend: a minimum)
        ends = kept & ~(shifted(kept, False, -1) & ~shifted(new_group, True, -1))
        last = -_running_max(-jnp.where(ends, tile, _INT32_MAX), reverse=True)
        spanned = (new_group & (group < rows // (_TILE_ROWS * span))
                   & (last != tile))
        # every group's first lane says whether it is a span (bit 0) and
        # where its tiles lie (above it), under the lane's own number, which
        # makes the newest say the maximum (65,536 lanes x 2 x 8,192 places
        # fill an int32)
        base = span * (jnp.cumsum(spanned, axis=1, dtype=jnp.int32) - 1)
        said = _running_max(jnp.where(
            new_group, (blk * block + local) * (2 * high)
            + jnp.where(spanned, 2 * base + 1, 0), -1))
        wide = kept & (said & 1 == 1)
        new_tile = new_tile & ~wide
    spans = jnp.sum(spanned, axis=1, dtype=jnp.int32)
    singles = jnp.sum(new_tile, axis=1, dtype=jnp.int32)
    place = _running_max(jnp.where(
        new_tile, blk * high + (span * spans)[:, None]
        + jnp.cumsum(new_tile, axis=1, dtype=jnp.int32) - 1, -1)) & (high - 1)
    if span > 1:
        place = jnp.where(
            wide, (said >> 1 & (high - 1)) + tile % span, place)
    words = place | ((ids % _TILE_ROWS) << 16)
    # a block owns the lanes from the first copy it opens to the next
    # block's that opens one (or the last kept lane)
    opens = new_tile | spanned
    head = jnp.where(
        singles + spans > 0,
        blk[:, 0] * block + jnp.argmax(opens, axis=1).astype(jnp.int32), n)
    later = blk[:, :1] < jnp.arange(by_block[0])[None, :]
    end = jnp.min(jnp.where(later, head[None, :], n), axis=1)
    owned = jnp.where(
        head < n, jnp.minimum(end, jnp.sum(kept, dtype=jnp.int32)) - head, 0)
    # the opened copies to the front of their block, the spans first (as
    # `_tile_plan` moves its tiles); a span is named by its first tile
    order = jnp.where(
        spanned, local, jnp.where(new_tile, local + block, local + 2 * block))
    first_tile = jnp.where(spanned, tile // span * span, tile)
    tiles = jax.lax.sort((order, first_tile), dimension=1, num_keys=1)[1]
    counts = jnp.stack([singles, spans, jnp.minimum(head, n - 1), owned],
                       axis=1)
    return (tiles.reshape(-1), words.reshape(-1), counts.reshape(-1),
            TileSetCounts(touched, jnp.sum(singles + spans),
                          jnp.sum(singles + span * spans)))


# the widest span: 32 tiles a copy are 64 KB of a 4-lane table each way
_SPAN_MOST_TILES = 32


def set_span(tiles: int, lanes: int) -> int:
    """How many side-by-side tiles one copy of :func:`sorted_tile_set` may
    take, from what a trace can see: the table's ``tiles`` and the ``lanes``
    of the push whose distinct rows are being written.  Eight times the
    lanes to a tile, to the nearest power of two, at most
    ``_SPAN_MOST_TILES``: where the touched tiles lie close a wide copy
    saves descriptors and moves little it need not (cell 17, four lanes a
    tile: 32), where they lie apart it bridges tiles for nothing (cell 6,
    0.87 lanes a tile: 8), and under an eighth of a lane a tile a span is
    the tile itself, the plan and kernel of one-tile copies."""
    if lanes <= 0:
        return 1
    span = 1 << max(0, round(math.log2(8 * lanes / tiles)))
    while span > min(tiles, _SPAN_MOST_TILES):  # (a table of a few tiles)
        span //= 2
    return span


def sorted_tile_set(
    table: Array,
    sorted_ids: Array,
    new: Array,
    *,
    of: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Array, TileSetCounts]:
    """``table[r, :w] = new[k]`` (and zeros on the row's other lanes) for
    every row ``r`` a kept lane ``k`` names, a read-modify-write of each
    touched tile of 128 rows; every other tile is left as it is, or read
    and written back as it is (inside a span: :func:`_tile_set_plan`).
    Returns the table and what the calls counted (:class:`TileSetCounts`).

    ``table``: (rows, L) float32 with L one of ``SET_ROW_LANES`` and whole
    tiles of 128 rows (:func:`set_refusal`): on the TPU such a table lies
    rows-minor, ``{0,1:T(L,128)}``, which is byte for byte the row-major
    ``(rows / 128, L, 128)`` the kernel addresses, 128 consecutive rows to
    a contiguous tile and the tiles side by side, so the reshapes and
    transposes here are bitcasts.
    ``sorted_ids``: (n,) int32 ASCENDING and DISTINCT, lanes to drop at the
    end with an id >= the row count.  ``new``: (n, w) in that order, ``w <=
    L``; a dropped lane's may be anything.  ``of``: the lanes of the whole
    push these ids are a chunk of (``n`` where it is not said), which with
    the table's size says how close the touched tiles lie
    (:func:`set_span`).  In place when the enclosing jit
    donates the table; an eager call copies it first.  Off the TPU the
    kernel is interpreted.
    """
    pl, pltpu = _pallas()

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    why = set_refusal(table.shape, table.dtype)
    if why is None and new.shape[1] > table.shape[1]:
        why = f"new rows of {new.shape[1]} lanes, the table's have fewer"
    if why is not None:
        raise ValueError(f"sorted_tile_set: {why}")
    rows, lanes_row = table.shape
    n, width = new.shape
    block = BLOCK
    zero = jnp.zeros((), jnp.int32)
    counted = TileSetCounts(zero, zero, zero)
    if n == 0:
        return table, counted
    span = set_span(rows // _TILE_ROWS, n if of is None else of)
    # as few calls as hold their scalars in SMEM, of equal size in blocks
    most = _SET_SMEM_WORDS // (2 + width) // block * block
    calls = -(-n // most)
    size = -(-n // (calls * block)) * block
    pad = calls * size - n
    sorted_ids = jnp.concatenate([
        sorted_ids.astype(jnp.int32), jnp.full((pad,), _INT32_MAX, jnp.int32)
    ])
    new = jnp.pad(new.astype(jnp.float32), ((0, pad), (0, 0)))
    if not isinstance(table, jax.core.Tracer):
        table = jnp.copy(table)

    tile = (lanes_row, _TILE_ROWS)
    held = (3, _span_places(block, span)) + tile
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(size // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # the table stays in HBM
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM(held, jnp.float32),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
    )
    call = pl.pallas_call(
        functools.partial(
            _tile_set_kernel, block=block, width=width, lanes=size, span=span
        ),
        out_shape=jax.ShapeDtypeStruct(
            (rows // _TILE_ROWS,) + tile, table.dtype),
        grid_spec=grid_spec,
        input_output_aliases={4: 0},  # (tiles, words, counts, vals, table)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_TILE_VMEM_BYTES,
        ),
        interpret=interpret,
        name="sorted_row_set_tiles",
    )
    # (tiles, L, 128): the tiles of the transposed table, one after another
    view = table.reshape(-1, _TILE_ROWS, lanes_row).transpose(0, 2, 1)
    for lo in range(0, calls * size, size):
        tiles, words, counts, moved = _tile_set_plan(
            sorted_ids[lo:lo + size], rows, block, span
        )
        view = call(
            tiles, words, counts, new[lo:lo + size].T.reshape(-1), view
        )
        counted = jax.tree.map(jnp.add, counted, moved)
    return view.transpose(0, 2, 1).reshape(rows, lanes_row), counted


def scatter_add_counted(
    table: Array,
    ids: Array,
    deltas: Array,
    *,
    interpret: Optional[bool] = None,
    rolled: bool = False,
) -> Tuple[Array, Array, Array]:
    """``table.at[ids].add(deltas, mode="drop")`` through the tile kernel
    (``deltas`` ``(n, w)``, ``w`` <= the table's ``W``: lanes ``[0, w)`` of
    the rows): sort the ids, bring the deltas into that order (a batch over
    ``MAX_LANES`` lanes in several calls: :func:`_tile_add_calls`), and one
    read-modify-write per touched tile row.  Lanes of one row are added in
    the order the batch holds them (the sort is stable).  An eager call is
    one jitted program (one copy of the table, not one a kernel call).
    Beside the table, as int32 scalars on the device: the lanes the kernel
    kept and the tile rows it read and wrote for them, summed over its calls
    (a tile row open across two calls is moved by both).  ``rolled``: the
    calls of a batch over ``MAX_LANES`` lanes as ONE call in a loop that
    ends with the last call that has a live lane (:func:`_tile_add_calls`);
    the table and the counts are the same either way.
    """
    if not isinstance(table, jax.core.Tracer):
        return _scatter_add_jitted(
            table, ids, deltas, interpret=interpret, rolled=rolled)
    sid, order = sort_by_row(ids.reshape(-1), None, table.shape[0])
    return _tile_add_calls(table, sid, order, deltas, interpret, rolled)


def _tile_add_calls(table, sorted_ids, order, deltas, interpret,
                    rolled=False):
    """``(table, kept lanes, tile rows read and written)`` of the tile
    kernel's calls over a sorted batch (``sorted_ids`` ascending, the lanes
    to drop last; ``order[k]`` the row of ``deltas`` that sorted lane ``k``
    adds): as few calls as hold ``MAX_LANES`` lanes each, of ONE shape (the
    ids and ``order`` are padded to it, never the rows).

    ``deltas`` ``(n, w)`` with ``w`` <= the table's ``W``.  Rows narrower
    than the table's are permuted ONCE for all the calls, each of which
    reads its blocks out of that one buffer (``_tile_add``'s ``first``).
    Such a buffer has pad lanes in its ``(8, 128)`` tiles, and where one
    lies idle across a kernel call the TPU compiler, once it counts the
    step's memory as tight, "compresses" it (``remat_compressed``: a copy
    to ``{0,1}`` before the call and a copy back after, the pad this width
    was rid of come back twice); a permute a call leaves the later calls'
    stretches, which nothing orders behind the earlier calls, lying across
    them.  Compiled both ways for a described v5e (PERF.md section 6, PR
    57): a permute a call draws four such copies round cell 7's three calls
    (300 lanes, a 10.81 GB table; two from 6.1 GB on) and none at cell 5's
    7.68 GB (600 lanes) but two from 8.45 GB on, a table a tenth larger;
    how tight is tight is the compiler's and no shape of ours tells it.
    The one buffer is an operand of every call, has no stretch of idleness
    and drew no copy at any size tried (to 12.8 GB).  Rows of whole
    registers (``w`` = ``W``) have no pad lane to shed: every call permutes
    its own stretch, as it did before the kernel took narrower rows (cell
    10: nine calls on a 12.58 GB table, no such copy, and its step stays
    what it was).

    ``rolled``: the calls, which have one shape, as ONE kernel call in the
    body of a ``while`` that carries the table (aliased, as in each call)
    and ends with the last call that holds a live lane: the lanes to drop
    sort last, so a call whose first lane is dead has none to add, and
    neither its rows are permuted nor its blocks walked.  The calls that
    run are the unrolled form's, in its order, so the table and the counts
    are its own bit for bit.  It is what a push on the shards of a mesh
    takes (``core/store._push_add_on_shards``): every shard sorts ALL the
    batch's lanes and owns a part of them (cell 16: 5.5-38.9 %, one to four
    calls of nine), and every process that runs the step traces and lowers
    one kernel call where it traced nine (PERF.md section 6, PR 67).  The
    unrolled form stays what a store in one place traces, whose lanes are
    nearly all live and whose step text is pinned (cells 5, 7, 10)."""
    pad = _pad_for_calls(sorted_ids.shape[0])
    if pad:
        sorted_ids = jnp.concatenate(
            [sorted_ids, jnp.full((pad,), _INT32_MAX, jnp.int32)])
        order = jnp.concatenate([order, jnp.zeros((pad,), jnp.int32)])
    calls = _calls(sorted_ids, order)
    whole = None
    if len(calls) > 1 and deltas.shape[1] < table.shape[1]:
        whole = jnp.take(deltas, order, axis=0, mode="clip")
    lanes = tile_rows = jnp.zeros((), jnp.int32)
    if rolled and len(calls) > 1:
        size, rows = calls[0][1].shape[0], table.shape[0]

        def some_live(carry):
            # (past the last call the index is clamped and the count says no)
            first = jax.lax.dynamic_index_in_dim(
                sorted_ids, carry[0] * size, keepdims=False)
            return (carry[0] < len(calls)) & (first < rows)

        def one_call(carry):
            i, table, lanes, tile_rows = carry
            s = jax.lax.dynamic_slice_in_dim(sorted_ids, i * size, size)
            if whole is None:
                o = jax.lax.dynamic_slice_in_dim(order, i * size, size)
                stretch, first = jnp.take(deltas, o, axis=0, mode="clip"), None
            else:
                stretch, first = whole, i * (size // BLOCK)
            table, kept, moved = _sorted_tile_add_counted(
                table, s, stretch, interpret, False, first)
            return i + 1, table, lanes + kept, tile_rows + moved

        return jax.lax.while_loop(
            some_live, one_call,
            (jnp.zeros((), jnp.int32), table, lanes, tile_rows))[1:]
    for lo, s, o in calls:
        if whole is None:
            rows, first = jnp.take(deltas, o, axis=0, mode="clip"), None
        else:
            rows, first = whole, jnp.asarray(lo // BLOCK, jnp.int32)
        table, kept, moved = _sorted_tile_add_counted(
            table, s, rows, interpret, False, first)
        lanes, tile_rows = lanes + kept, tile_rows + moved
    return table, lanes, tile_rows


_scatter_add_jitted = jax.jit(
    scatter_add_counted, static_argnames=("interpret", "rolled"))


def scatter_add(
    table: Array,
    ids: Array,
    deltas: Array,
    *,
    interpret: Optional[bool] = None,
) -> Array:
    """:func:`scatter_add_counted`'s table."""
    return scatter_add_counted(table, ids, deltas, interpret=interpret)[0]


__all__ = [
    "BLOCK", "MAX_LANES", "descriptors", "note_refusal", "preload", "refusal",
    "refusal_count", "row_add", "scatter_add", "scatter_add_counted",
    "set_refusal", "sort_by_row", "sorted_row_set", "sorted_row_update",
    "sorted_row_update_counted", "sorted_tile_add", "sorted_tile_assign",
    "sorted_tile_set",
    "tile_refusal",
]
