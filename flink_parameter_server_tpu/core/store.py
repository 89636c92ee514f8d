"""ShardedParamStore — the TPU-native server-side keyed parameter store.

Reference parity: replaces the reference server's per-subtask
``HashMap[Int, P]`` with ``getOrElseUpdate(id, init(id))`` semantics
(``SimplePSLogic`` — SURVEY.md §2 #3) and its ``hash(paramId) % psParallelism``
routing (SURVEY.md §2 "Model parallelism").

TPU-first design
----------------
The store is a dense ``(capacity, *value_shape)`` ``jax.Array`` living in HBM,
row-sharded over a named mesh axis (``"ps"``).  The reference's message-level
protocol maps onto array ops *inside* a jitted step:

  * ``pull(ids)``  → sharded gather (``jnp.take``); XLA lowers the
    cross-shard reads to ICI collectives (or we do it explicitly with
    ``shard_map`` — see :mod:`..parallel.collectives`).
  * ``push(ids, deltas)`` → sharded scatter-add (``table.at[ids].add``).

"Lazy init on first pull" in the reference uses a *deterministic per-id*
initializer (``RangedRandomFactorInitializerDescriptor``), so eager
whole-table initialisation at create time is observationally equivalent and
far more TPU-friendly (one fused init kernel instead of per-row branches).

Duplicate ids within one microbatch: the reference applies each push
sequentially; with the default commutative ``add`` update, combining
duplicates with a segment-sum is exactly equivalent.  For *non-commutative*
custom ``update`` functions, intra-batch duplicate deltas are summed first
and ``update`` is then applied once per touched id — the documented
semantic delta vs. the reference (bounded staleness ≤ one microbatch;
SURVEY.md §7 "Guiding translation").  That arm costs what the batch does
and nothing table-sized: a sort of the batch's ids, the sums of each run,
one read and one write of every distinct row (:func:`_push_rule`).  Under a
mesh with one worker it runs where the reference runs ``paramUpdate``: on
the server shard that owns the row (:func:`_push_rule_on_shards`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import DP_AXIS, worker_count

Array = jax.Array
InitFn = Callable[[Array], Array]  # ids (n,) int32 -> values (n, *value_shape)
UpdateFn = Callable[[Array, Array], Array]  # (current, combined_delta) -> new

def _resolve_layout(
    layout: str,
    update: Union[str, UpdateFn],
    value_shape: Tuple[int, ...],
) -> str:
    """Resolve the table layout, validating packed-layout constraints.

    ``"auto"`` reads the row's shape and the update rule, nothing else (which
    FORM a pull or a push of the layout then takes is :func:`arms`' to say):
    dense for an add-store whose rows are ONE axis of a whole number of 128
    lanes (a row is then a whole number of vector registers as it is),
    packed for every other add-store.  A store whose ``update`` is a rule
    lies by its row's width:

    - one axis of 9 to 64 lanes: PACKED, ``k = 128 // width`` logical rows
      to a 128-lane physical row (DiFacto's 36 lanes three to a row:
      ``f32[16375440,128]{1,0:T(8,128)}``, 8.384 GB where the dense table
      is 7.860).  Dense, such a table lies rows-minor on the TPU, a row a
      strided column of scalars across its tiles, and every op that names
      a row pays a serial price for it: on the v5e the pull's gather 44.3
      ns a 36-lane row, the rule's read 48.0, XLA's row ``set`` 136
      (PERF.md section 6, PRs 46, 47).
    - one axis of 1 to 8 lanes: dense, and where it is float32 and in one
      place its PHYSICAL row is its sublane tile, 1, 2, 4 or 8 lanes, the
      rest zeros, and its table whole tiles of 128 rows
      (``StoreSpec.tile_lanes``: FTRL's ``(w, z, n)`` lies as
      ``f32[187767424,4]{0,1:T(4,128)}``, 3.00 GB at 187.8 M rows, the
      bytes the TPU pads a dense 3-lane row to anyway): a table whose
      tiles a Pallas kernel can move (a 3-lane table has the same tiles
      and Mosaic refuses a slice of them: PERF.md section 6, PR 35).
      Packed 42 to a physical row, its step asked 13.5 GB for its 42
      static lane slices (PR 34).
    - one axis of more than 128 lanes: PACKED with ``k`` = 1, the flat
      whole-register row an add-store of that width has (GloVe's 602 lanes
      of weights, bias and AdaGrad's accumulators in five registers:
      ``f32[4392040,640]``, 11.24 GB; PERF.md section 6, PR 55).  Dense,
      the TPU holds ``(capacity, 602)`` capacity-minor and every step
      copies the whole table for its gather and back (below).
    - one axis of 65 to 127 lanes: PACKED with ``k`` = 1, the row alone in
      ONE 128-lane register, zeros past its width (PBG's 101 lanes, an
      embedding and row-wise AdaGrad's one accumulator: ``f32[15152096,128]
      {1,0:T(8,128)}``, 7.76 GB).  Dense, the TPU pads such a row to 128
      lanes all the same and hands the step its table ``(capacity, 101)``
      capacity-minor: the step copies the WHOLE table to a row-major one for
      its gathers and back after its row ``set`` (compiled for a v5e at
      15,152,092 rows: 7.9 GB of temporaries beside the table, which do not
      fit; packed, 0.44 GB and neither copy; PERF.md section 6, PR 61).
    - every other row (exactly 128 lanes, a row that fills its register as
      it is; two axes; none): dense: no cell stands there.

    ``layout="packed"`` may be pinned for any rule store; the rule's push
    then takes the packed arm at whatever ``k`` the width gives.

    A narrow dense row of an add-store is the same column of scalars
    across the table's tiles, and its gather and scatter-add walk that
    column (36 and 121 ns a row for FM's 17 lanes on the v5e, against 10
    and 22 for the 128-lane physical row that holds seven of them;
    PERF.md section 6, PR 29).  A row of 128 lanes or more that has two
    axes or is no multiple of 128 is packed ONE to a physical row, flat
    and zero-padded to whole registers (``ops/packed.py``: ``pack_k`` 1,
    ``phys_width`` the padded width): left as it is, the TPU holds
    ``(capacity, 2, 300)`` and ``(capacity, 600)`` capacity-minor (no
    padding) and the step copies the WHOLE table to a row-major one for
    its gather and back after its scatter-add (compiled for a v5e at
    3,000,000 rows: 7.7-9.2 GB of temporaries; padded flat, 0.65 GB and
    neither copy; PERF.md section 6, PR 32).  The shard count is not asked:
    under a mesh every shard holds its own packed block, so a store and its
    reload from a checkpoint (``from_values``) resolve to one layout."""
    if layout not in ("dense", "packed", "auto"):
        raise ValueError(
            f"layout must be 'dense', 'packed' or 'auto', got {layout!r}"
        )
    if layout != "auto":
        return layout
    width = 1
    for s in value_shape:
        width *= int(s)
    one_axis = len(value_shape) == 1
    if update == "add":
        return "dense" if one_axis and width % 128 == 0 else "packed"
    return "packed" if one_axis and width > 8 and width != 128 else "dense"


@dataclasses.dataclass(frozen=True)
class StoreSpec:
    """Static configuration of a parameter store (not a pytree leaf)."""

    capacity: int
    value_shape: Tuple[int, ...] = ()
    dtype: Any = jnp.float32
    # "add" is one scatter-add of the batch; any other callable is a
    # row-wise rule `(current, combined) -> new`, vectorised over a leading
    # axis, applied once to every row the batch touches (intra-batch
    # duplicate deltas are summed first): a sort of the batch, a read and
    # a write of its distinct rows, nothing table-sized (`_push_rule`).
    update: Union[str, UpdateFn] = "add"
    mesh: Optional[Mesh] = None
    ps_axis: str = "ps"
    # "dense": one logical row per physical row (the trivial layout).
    # "packed": k = 128 // row_width logical rows per 128-lane physical
    #   row (ops/packed.py) — the TPU-native layout for narrow values
    #   (MF dim 64, FM dim 17): full vector lanes on every pull/push.  A
    #   row of 128 lanes or more lies alone (k = 1), flat and zero-padded
    #   to whole 128-lane registers (word2vec's (2, 300): 640 lanes).
    #   A rule store's push then reads and writes whole physical rows, the
    #   touched logical rows of each merged by selects (`_push_rule`).
    layout: str = "dense"
    # The WORKER'S PART of a rule store's row: how many leading lanes of
    # its flat row a worker reads and its gradients touch, the rest being
    # the server's own (an optimiser's accumulators: DiFacto's `S`, GloVe's
    # `gradsq`).  A step then pulls these lanes alone and pushes deltas
    # that wide (`make_train_step`, `pull(worker_part=True)`,
    # `push_counted`); the rule is still `update(whole current row,
    # combined)`, `combined` that wide, and writes whole rows.  None: the
    # whole row crosses, as every add store's does.  The MODEL's fact, set
    # by whoever lays the row out (`models/difacto.make_store`: 20 of 36,
    # `models/glove.make_store`: 301 of 602); nothing tunes it.
    worker_width: Optional[int] = None

    def __post_init__(self) -> None:
        if self.layout not in ("dense", "packed"):
            raise ValueError(
                f"layout={self.layout!r} is not one of ('dense', 'packed')"
            )
        if self.worker_width is not None and (
                self.update == "add" or len(self.value_shape) != 1
                or not 0 < self.worker_width <= self.row_width):
            raise ValueError(
                f"worker_width={self.worker_width!r}: the worker's part is "
                f"1 to {self.row_width} leading lanes of a RULE store's "
                f"one-axis row (update={self.update!r}, value shape "
                f"{self.value_shape})"
            )

    @property
    def num_shards(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.ps_axis]

    @property
    def row_width(self) -> int:
        w = 1
        for s in self.value_shape:
            w *= int(s)
        return w

    @property
    def pack(self) -> int:
        """Logical rows per physical row (1 for the dense layout)."""
        if self.layout != "packed":
            return 1
        from ..ops.packed import pack_k

        return pack_k(self.row_width)

    @property
    def narrow_rule(self) -> bool:
        """A dense table in one place whose ``update`` is a rule and whose
        rows hold at most 8 elements: the stores whose write-back the set
        kernel is for (:func:`arms`)."""
        return (self.update != "add" and self.layout == "dense"
                and self.mesh is None and self.row_width <= 8)

    @property
    def tile_lanes(self) -> int:
        """Lanes of a PHYSICAL row where the table is that of a narrow rule
        store the set kernel can move, and 0 for every other store: a
        ``narrow_rule`` store of float32 rows of one axis holds them at 1,
        2, 4 or 8 lanes, the sublane tile the TPU
        pads a table of rows that narrow to anyway (FTRL's ``(w, z, n)`` at
        four: 3.00 GB at 187.8 M rows either way).  The table then lies
        rows-minor on the chip, a tile of it is 128 whole rows, and the
        rule's write-back can move whole tiles
        (``ops/row_update.sorted_tile_set``; :func:`arms`).
        The lanes past ``row_width`` are zero, the rule never reads them,
        and ``pull`` and ``values()`` strip them."""
        if (not self.narrow_rule or len(self.value_shape) != 1
                or jnp.dtype(self.dtype) != jnp.float32):
            return 0
        return 1 << (self.row_width - 1).bit_length()

    @property
    def rows_per_shard(self) -> int:
        """Per-shard PHYSICAL row count, aligned to 8 rows: the sublane
        tile of a float32 table on the TPU, so every shard's block starts
        and ends on a tile.  A narrow rule store's (``tile_lanes``) is
        aligned to the 128 rows of ITS tiles."""
        n = self.num_shards
        logical = (self.capacity + self.pack - 1) // self.pack
        per = (logical + n - 1) // n
        align = 128 if self.tile_lanes else 8
        return -(-per // align) * align

    @property
    def padded_capacity(self) -> int:
        """LOGICAL capacity including padding rows (init'd, addressable)."""
        return self.rows_per_shard * self.num_shards * self.pack

    def table_shape(self) -> Tuple[int, ...]:
        """Shape of the physical table array."""
        if self.layout == "packed":
            from ..ops.packed import phys_width

            return (
                self.rows_per_shard * self.num_shards,
                phys_width(self.row_width),
            )
        if self.tile_lanes:
            return (self.padded_capacity, self.tile_lanes)
        return (self.padded_capacity,) + self.value_shape

    def sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        if self.layout == "packed":
            return NamedSharding(self.mesh, P(self.ps_axis, None))
        return NamedSharding(
            self.mesh, P(self.ps_axis, *([None] * len(self.value_shape)))
        )


def zeros_init(spec: StoreSpec) -> InitFn:
    def init(ids: Array) -> Array:
        return jnp.zeros(ids.shape + spec.value_shape, spec.dtype)

    return init


def create_table(spec: StoreSpec, init_fn: Optional[InitFn] = None) -> Array:
    """Materialise the full table, eagerly initialised via ``init_fn``.

    ``init_fn`` must be deterministic per id (vectorised over an id array) —
    the analogue of the reference's ranged-random factor initializer
    descriptors, which exist precisely so that init is reproducible per key.
    """
    init_fn = init_fn or zeros_init(spec)
    _preload_tile_kernel(spec)
    if spec.layout == "packed":
        return _create_packed(spec, init_fn)()
    if spec.mesh is None and spec.padded_capacity > _INIT_BLOCK:
        # a long dense table in one place is initialised block by block, in
        # place: no temporary as long as the table (PERF.md section 6, PR 45)
        return _create_dense_in_blocks(spec, init_fn)()
    ids = jnp.arange(spec.padded_capacity, dtype=jnp.int32)
    out_sharding = spec.sharding()

    def build(ids):
        return _physical_rows(spec, init_fn(ids))

    return jax.jit(build, out_shardings=out_sharding)(ids)


def _create_packed(spec: StoreSpec, init_fn: InitFn) -> Callable[[], Array]:
    """``() -> table`` for a packed spec, as one jitted program: the rows
    are initialised and packed ``_PACK_CHUNK`` physical rows a ``fori_loop``
    step, so a chip holds its table and one chunk of logical rows, never
    all of them (initialised at once, FM's rows are first laid 128 lanes
    wide: 25 GB asked of a 16 GB chip on one chip, 24 GB on each of four;
    PERF.md section 6, PR 31).  Under a mesh every shard does so for its
    own block, inside a ``shard_map`` over ``ps``."""
    from ..ops.packed import pack_table

    k, d = spec.pack, spec.row_width
    # a shard's block under a mesh, the whole table without one
    rows = spec.rows_per_shard
    chunk = min(rows, _PACK_CHUNK)

    def block(first: Any) -> Array:
        """``rows`` physical rows whose first logical row is ``first``."""

        def init_chunk(i, table):
            # the last chunk starts early and packs some rows a second time
            at = jnp.minimum(i * chunk, rows - chunk)
            ids = first + at * k + jnp.arange(chunk * k, dtype=jnp.int32)
            part = init_fn(ids).reshape(-1, d).astype(spec.dtype)
            return jax.lax.dynamic_update_slice(
                table, pack_table(part, chunk), (at, 0)
            )

        table = jnp.zeros((rows, spec.table_shape()[1]), spec.dtype)
        return jax.lax.fori_loop(0, -(-rows // chunk), init_chunk, table)

    if spec.mesh is None:
        return jax.jit(lambda: block(0))
    ps = spec.ps_axis
    # the loop's carry starts as zeros on every shard and ends as its block
    return jax.jit(jax.shard_map(
        lambda: block(jax.lax.axis_index(ps) * (rows * k)),
        mesh=spec.mesh, in_specs=(), out_specs=P(ps, None), check_vma=False,
    ), out_shardings=spec.sharding())


def pull(
    spec: StoreSpec, table: Array, ids: Array, *, worker_part: bool = False,
    turned: bool = False,
) -> Array:
    """Batched pull: ``values[i] = table[ids[i]]`` (sharded gather).

    Out-of-range ids are clipped (callers use a validity mask alongside):
    a DEAD lane, which by convention carries id -1 (the padding of a ragged
    key bag: ``models/fasttext.py``), reads row 0, and its logic masks what
    it reads.
    Packed layout: one gather of whole 128-lane physical rows, then the
    lane slice down to sub-row ``id % k``, by selects and never a gather of
    elements (ops/packed.py); under a mesh each shard slices what it
    gathered before the one all-reduce (:func:`_packed_pull_on_shards`).
    Which form: :func:`arms`.

    ``worker_part`` (a step's pull: ``make_train_step``): of a store whose
    spec names a worker's part (``StoreSpec.worker_width``) those leading
    lanes of each row alone, ``ids.shape + (worker_width,)``, cut where the
    arm cuts anyway and by no pass of its own (the gather's window; the
    lane slice of a packed row, under a mesh in front of the all-reduce);
    of every other store, and by default, whole rows.

    ``turned`` (``make_train_step`` for a logic that ``pulls_turned``), of
    a key block of two axes ``(B, K)``: the rows with the block's axes
    swapped, ``values[f, b] = table[ids[b, f]]``, ``(K, B) + row``.  The
    rows are GATHERED in the block's own order whatever the answer's (the
    TPU's gather pays for neighbours that name one row:
    ``ops/packed.turned_slice_kernel``).  The arm ``packed_kernel_by_field``
    writes them turned (:func:`arms`' ``fields``); every other arm's answer
    is turned as it stands (what a CPU runs; no cell).

    Whole rows of a dense table over ``ps`` > 1 servers under ONE worker
    group (the arm ``take`` there: cell 16) are taken ON the shards and
    summed (:func:`_take_on_shards`); GSPMD's gather keeps ``dp`` > 1."""
    if turned and ids.ndim != 2:
        raise ValueError(
            f"a turned pull takes a key block of two axes, (B, K): got "
            f"{tuple(ids.shape)}")
    ids = jnp.clip(ids.astype(jnp.int32), 0, spec.padded_capacity - 1)
    arm = arms(spec, pull_lanes=ids.size,
               fields=ids.shape[1] if turned else None).pull
    by_field = turned and arm == "packed_kernel_by_field"
    if turned and not by_field:
        return jnp.swapaxes(
            pull(spec, table, ids, worker_part=worker_part), 0, 1)
    part = spec.worker_width if worker_part else None
    if arm == "take":
        if spec.num_shards > 1 and worker_count(spec.mesh) == 1:
            rows = _take_on_shards(spec, table, ids)
        else:
            rows = jnp.take(table, ids, axis=0)
        return rows if part is None else rows[..., :part]
    if arm == "narrow":
        return _narrow_pull(table, ids, part or spec.row_width)
    from ..ops.packed import packed_pull
    block, kernel = ids if by_field else ids.reshape(-1), arm != "packed_selects"
    if spec.num_shards > 1:
        vals = _packed_pull_on_shards(
            spec, table, block, kernel, part, by_field)
    else:
        vals = packed_pull(
            table, block, spec.row_width, kernel, part, by_field)
    return vals.reshape(
        (ids.T if by_field else ids).shape
        + (spec.value_shape if part is None else (part,)))


@functools.partial(jax.jit, static_argnums=(2,))
def _narrow_pull(table: Array, ids: Array, width: int) -> Array:
    """Rows ``ids`` of a narrow rule store's table without the zero lanes
    that end them: the slice is the gather's own window (``slice_sizes``
    ``(1, width)``), no op of its own.  Jitted so that an eager ``pull``
    is that gather too: op by op the slice comes first and copies the
    table (3.0 GB at cell 6's size: PERF.md section 6, PR 35)."""
    return jnp.take(table[:, :width], ids, axis=0)


def _phys_scatter_args(
    spec: StoreSpec, table: Array, flat_ids: Array, flat_deltas: Array,
    flat_mask: Optional[Array], arm: Arms, lead: Tuple[int, ...] = (),
):
    """(ids, deltas) at PHYSICAL granularity for the scatter-add, the
    masked lanes' deltas zeros.

    Dense: passthrough.  Packed: lane-shift each delta row to its
    sub-row offset and divide ids down to physical rows (the sentinel
    ``padded_capacity`` divides to the out-of-range physical row, so
    ``mode="drop"`` semantics are preserved).  The shift (``arm.shift``) is
    ``k`` pads under a select or ONE
    Pallas call that reads the deltas feature-major and leaves a masked
    lane out itself (``ops/packed.lane_shift_kernel``; under a mesh
    :func:`_packed_shift_on_mesh`): the same rows in the same order, bit
    for bit.  A row that lies ONE to a physical row (``k`` = 1: 600 lanes in
    640) has nothing to shift, only its pad to whole registers: XLA's
    scatter-add needs it, the tile kernel (``arm.push``) adds a row of
    ``w`` <= ``W`` lanes into lanes ``[0, w)`` and is handed the deltas as
    they are.  ``lead`` is the batch's shape in front of a row: the shift
    ``kernel_by_field`` (:func:`arms`' ``fields``) is handed the deltas of
    a block ``(K, B)`` as ``(d, K, B)``, the same lanes in the same order,
    read where XLA holds them on the TPU (``lane_shift_kernel``)."""
    kernel, tiles = arm.shift.startswith("kernel"), arm.push == "tile_add"
    if not kernel:
        flat_deltas = _zero_masked(flat_deltas, flat_mask)
    if spec.layout != "packed":
        return flat_ids, flat_deltas
    from ..ops.packed import (
        lane_shift_deltas, lane_shift_kernel, packed_phys_ids)

    d = spec.row_width
    deltas = flat_deltas.reshape(-1, d).astype(table.dtype)
    if tiles and spec.pack == 1:
        shifted = deltas  # the tile kernel takes a row at its own width
    elif not kernel:
        shifted = lane_shift_deltas(deltas, flat_ids, d)
    else:
        # feature-major, which is how XLA holds a step's narrow rows on
        # the TPU: the transpose is a bitcast there
        ids, mask, by_lane = flat_ids, flat_mask, deltas.T
        if arm.shift == "kernel_by_field":
            ids = ids.reshape(lead)
            mask = None if mask is None else mask.reshape(lead)
            # (the row axis moved in front of the block as it was handed
            # in: XLA folds two reshapes, not a reshape round a transpose)
            by_lane = jnp.moveaxis(deltas.reshape(lead + (d,)), -1, 0)
        if spec.mesh is not None:
            shifted = _packed_shift_on_mesh(spec, by_lane, ids, mask)
        else:
            shifted = lane_shift_kernel(by_lane, ids, d, mask)
    return packed_phys_ids(flat_ids, d), shifted


def _zero_masked(flat_deltas: Array, flat_mask: Optional[Array]) -> Array:
    """Masked-out lanes keep their id but carry a zero delta: for the add
    path zero deltas are a no-op (a rule store's push sends a masked lane to
    the sentinel instead and zeroes nothing: :func:`_push_rule`)."""
    if flat_mask is None:
        return flat_deltas
    return jnp.where(
        flat_mask.reshape((-1,) + (1,) * (flat_deltas.ndim - 1)),
        flat_deltas,
        jnp.zeros_like(flat_deltas),
    )


def push(
    spec: StoreSpec,
    table: Array,
    ids: Array,
    deltas: Array,
    mask: Optional[Array] = None,
) -> Array:
    """Batched push: fold ``deltas`` into rows ``ids`` (sharded scatter).

    ``mask`` (same leading shape as ``ids``) zeroes out padding lanes — the
    jit-friendly replacement for the reference's variable-length message
    batches (SURVEY.md §7 "Dynamic shapes").  Out-of-range ids are dropped
    (``mode="drop"``), and that is what a DEAD lane is: by convention it
    carries id -1, every negative id is routed to the sentinel one past the
    table, and both arms drop it there whatever its delta holds (XLA's
    ``mode="drop"``; the tile kernel sorts it to the end of the batch and
    neither reads its delta nor opens a tile for it), so it moves nothing
    and a rule store's counts pass it by.  A masked lane that keeps a live
    id is added as a zero.  ``update="add"`` is one scatter-add of the batch,
    duplicates and all: ONE XLA scatter-add, which sums the deltas of a row
    in the order the batch holds them (part of what the benchmark's
    reference checks), or ``ops/row_update.scatter_add``: the batch sorted
    by row (stably: a row's deltas stay in the order of the batch and are
    added one by one, XLA's roundings bit for bit) and every touched tile of
    eight rows read, added to and written back once a block of lanes.  A
    store whose ``update`` is a rule goes through :func:`_push_rule`.  Under
    a mesh with one worker that push, and the tile kernel's, run on the
    shards that own the rows (:func:`_push_rule_on_shards`,
    :func:`_push_add_on_shards`).  Which form, and what each cost on the
    chip: :func:`arms`; :func:`push_counted` hands out what the push counted.
    """
    return push_counted(spec, table, ids, deltas, mask)[0]


def push_counted(
    spec: StoreSpec,
    table: Array,
    ids: Array,
    deltas: Array,
    mask: Optional[Array] = None,
    *,
    lanes_over_workers: bool = False,
    turned: bool = False,
) -> Tuple[Array, Optional[dict]]:
    """:func:`push`, and beside the table what the push counted on the
    device (``None`` for an ``update="add"`` batch that XLA's scatter-add
    took, which counts nothing), by the arm :func:`arms` read.
    ``tile_add``: ``ps_push_kernel_lanes``, the lanes the kernel
    kept, and ``ps_push_tile_rows``, the tile rows of eight rows it read
    and wrote for them, summed over the kernel's calls (what its time is
    made of: two DMA descriptors a tile row).  ``rule``:
    ``ps_rule_keys``, the live lanes of the batch, ``ps_rule_rows``, the
    distinct rows the rule rewrote, and ``ps_rule_tiles``, the tiles of 128
    rows the write-back read and wrote to do so (0 where XLA's ``set``
    wrote the rows); where ``combine`` is not ``sort`` also
    ``ps_combine_kernel_lanes``, the lanes
    whose rows the row kernel summed (the live lanes; 0 where XLA's
    scatter-add summed them), and
    ``ps_combine_kernel_writes``, the single-row DMAs the kernel issued to
    do so over the stretches it walked (``ops/row_update.descriptors``:
    writes over lanes is the share of the walk that writes, ~28 % on Criteo
    records); a PACKED rule
    store carries ``ps_rule_packed_rows``, the physical rows its write-back
    wrote (:func:`_rewrite_packed`; a dense rule store has no such count).
    ``on_shards`` (a rule's push or ``tile_add``): each of these is the SUM
    over the shards of what each counted on its own block (ownership is
    disjoint, so keys, rows and kernel lanes are the one-place push's
    numbers; tiles, tile rows, physical rows and the kernel's writes are
    counted block by block and call by call), and two more say how even the
    partition is, the FULLEST shard's, the one a step waits for:
    ``ps_rule_keys_max_shard`` and ``ps_rule_rows_max_shard``, of an add
    store ``ps_push_lanes_max_shard`` and ``ps_push_tile_rows_max_shard``.
    ``make_train_step`` puts them among the step's outputs
    (:func:`publish_counts`) if the logic's outputs are a dict (every logic
    of ``models/``); outputs of another type leave without the counts.

    ``lanes_over_workers`` is what the caller knows of where the batch lies
    and a bare ``push`` cannot see: its lanes are split over the mesh's
    ``dp`` workers (``make_train_step`` under such a mesh says so).  An
    ``add`` batch may then be summed worker by worker
    (``worker_reduce``); a bare ``push`` keeps ONE scatter-add in
    the batch's order, whatever the mesh.

    A store whose spec names a worker's part (``StoreSpec.worker_width``)
    takes deltas of that trailing width too, which is what its step
    pushes: lanes ``[0, worker_width)`` of each row's sum, the server's own
    lanes carrying nothing (they only ever carried ``+0.0`` that no rule
    reads).  The combine then runs at that width (:func:`arms`'
    ``push_width``) and the rule is handed ``combined`` that wide; whole-row
    deltas stay legal, any other width raises.  ``ps_push_row_lanes``, for
    such a store alone, is the width the push was handed.

    ``turned`` (``make_train_step`` for a logic that ``pulls_turned``, the
    declaration :func:`pull` is handed): the request is a block of two axes
    ``(K, B)``, the ``K`` keys of an example down its leading axis.  The
    same lanes in the same order as its flattening, and the same table;
    :func:`arms` is told the ``fields`` and may shift them a field at a
    time."""
    vr = len(spec.value_shape)
    lead = tuple(deltas.shape[: deltas.ndim - vr])
    row = tuple(deltas.shape[deltas.ndim - vr:])
    part = row == (spec.worker_width,)  # (no row is `(None,)` wide)
    if (vr and row != spec.value_shape and not part) or (
        lead != tuple(ids.shape)
    ):
        raise ValueError(
            f"push deltas shape {tuple(deltas.shape)} does not match ids "
            f"shape {tuple(ids.shape)} + store value shape "
            f"{spec.value_shape}" + (
                "" if spec.worker_width is None else
                f" (or its worker's part, ({spec.worker_width},))")
        )
    if turned and len(lead) != 2:
        raise ValueError(
            f"a turned push takes a block of two axes, (K, B): got ids of "
            f"shape {lead}")
    if mask is not None and tuple(mask.shape) != tuple(ids.shape):
        # a length-1 mask would silently broadcast across every lane
        raise ValueError(
            f"push mask shape {tuple(mask.shape)} does not match ids shape "
            f"{tuple(ids.shape)}"
        )
    ids = ids.astype(jnp.int32)
    flat_ids = ids.reshape(-1)
    # Negative ids would wrap (numpy semantics) before mode="drop" applies;
    # route them to an always-out-of-bounds sentinel so they drop too.
    flat_ids = jnp.where(flat_ids < 0, spec.padded_capacity, flat_ids)
    flat_deltas = deltas.reshape((-1,) + row)
    flat_mask = None if mask is None else mask.reshape(-1)

    width = row[0] if part else spec.row_width  # lanes of a row handed in
    arm = arms(spec, push_lanes=flat_ids.shape[0],
               lanes_over_workers=lanes_over_workers, push_width=width,
               fields=lead[0] if turned else None)
    if arm.push == "rule":
        # (a masked lane's delta goes as it is: `_push_rule` sends the lane
        # to the sentinel, and no combine arm lets a dropped lane's value
        # reach a kept row)
        table, counted = (
            _push_rule_on_shards if arm.on_shards else _push_rule
        )(spec, table, flat_ids, flat_deltas, flat_mask, arm)
        if spec.worker_width is not None:
            counted["ps_push_row_lanes"] = jnp.asarray(width, jnp.int32)
        return table, counted
    s_ids, s_deltas = _phys_scatter_args(
        spec, table, flat_ids, flat_deltas, flat_mask, arm, lead
    )
    if arm.on_shards:
        return _push_add_on_shards(spec, table, s_ids, s_deltas)
    if arm.push == "tile_add":
        from ..ops.row_update import scatter_add_counted

        table, lanes, tile_rows = scatter_add_counted(
            table, s_ids, s_deltas.astype(table.dtype))
        return table, {
            "ps_push_kernel_lanes": lanes, "ps_push_tile_rows": tile_rows,
        }
    if arm.push == "worker_reduce":
        return _push_add_over_workers(
            spec, table, s_ids, s_deltas.astype(table.dtype)
        ), None
    return (
        table.at[s_ids].add(s_deltas.astype(table.dtype), mode="drop"),
        None,
    )


# Lanes a step of `_push_rule`'s loop over the batch's distinct rows.
_RULE_CHUNK = 32_768


def _push_rule(
    spec: StoreSpec,
    table: Array,
    flat_ids: Array,
    flat_deltas: Array,
    live: Optional[Array],
    arm: Arms,
    block: Optional[int] = None,
) -> Tuple[Array, dict]:
    """The push of a store whose ``update`` is a rule and not ``"add"``:
    ``(table, counted)``, as :func:`push_counted` hands them out, in the
    forms ``arm`` names (:func:`arms`).  ``table``
    is the whole table in one place (or GSPMD's to partition), or, with
    ``block`` its count of LOGICAL rows, the block of one shard with the
    ids relative to its first row and every lane the shard does not own
    carrying ``block``, dead as a dropped lane is
    (:func:`_push_rule_on_shards`).

    Work and memory go with the batch, never with the table.  Under
    ``ps.combine`` the batch's ids are sorted with their deltas, every run
    of one id summed and the distinct ids moved to the front
    (:func:`..ops.dedup.combine_runs`, ``arm.combine``; masked, negative and
    out-of-range lanes sort last and are dropped).  Then
    ``_RULE_CHUNK`` lanes a step of
    a loop that ends with the last distinct id (on the TPU a dropped lane
    of a gather or a scatter costs what a kept one does, and a batch of
    Criteo records names a row 3.6 times on average): under ``ps.rule``
    the CURRENT rows of the chunk's ids are read (:func:`pull`) and
    ``update(current, combined)`` run on those alone; what is left under
    ``ps.push`` writes the new rows back, each distinct id once
    (``arm.write_back``): XLA's row ``set``, or, for a narrow row held at
    its sublane tile
    (``StoreSpec.tile_lanes``), ``ops/row_update.sorted_tile_set``: every
    touched tile of 128 rows read, set and written back once, the same
    bits.  A PACKED store's chunk goes through
    :func:`_rewrite_packed`, which reads and writes whole physical rows,
    and ``counted`` then carries ``ps_rule_packed_rows``.  A rule row wider
    than a register lies flat in several (packed at
    ``k`` = 1): nothing here pads its deltas.  The ``tile_kernel`` combine
    sums
    them, ``w`` lanes wide as they come, into a
    zeroed block of whole registers, in the order of the stream
    (``ops/dedup._tile_sums``: the sums come out ``W`` wide, zeros past
    ``w``; the ``scatter_add`` arm sums at ``w``), and its ``tile_assign``
    write-back reads, sets and writes every touched tile of
    eight rows once (``ps_rule_tiles`` counts those
    tile rows, ``ps_combine_kernel_writes`` the combine's).

    ``flat_deltas`` are ``(n, row...)`` whole rows or ``(n, worker_width)``,
    the worker's part of them (:func:`push_counted`): the combine sums at
    the width it is handed (``arm.combine`` was read for that width: 20 of
    DiFacto's 36 lanes through the row kernel as 36 are, 301 of GloVe's 602
    through the tile kernel into a zeroed block of three registers, not
    five), every chunk of sums is cut out that wide, and the rule is called
    as ``update(whole current rows, combined)`` with ``combined`` that wide
    (a rule reads it by lane number from the front); the write-back writes
    whole rows either way.

    ``flat_deltas`` come as the logic made them, a masked lane's too
    (``live`` false): every such lane, like every id past the table, is
    sent to the sentinel here, each combine arm drops a sentinel's lanes,
    and what they hold (NaN, Inf) reaches no kept row
    (``tests/test_store.py`` holds the four arms to that), so no pass
    zeroes them first."""
    from ..ops.dedup import combine_runs
    from ..ops.row_update import sorted_tile_set

    n = flat_ids.shape[0]
    sentinel = spec.padded_capacity if block is None else block
    update_fn: UpdateFn = spec.update  # type: ignore[assignment]
    packed = spec.layout == "packed"
    # what the push hands out, an empty batch's too: by the arms alone
    names = ["ps_rule_keys", "ps_rule_rows", "ps_rule_tiles"]
    if arm.combine != "sort":
        names += ["ps_combine_kernel_lanes", "ps_combine_kernel_writes"]
    if packed:
        names.append("ps_rule_packed_rows")
    if n == 0:  # an empty batch rewrites nothing
        return table, dict.fromkeys(names, jnp.zeros((), jnp.int32))
    chunk = min(n, _RULE_CHUNK)
    with jax.named_scope("ps.combine"):
        dead = flat_ids >= sentinel
        if live is not None:
            dead = dead | ~live
        vals = flat_deltas.reshape(n, -1).astype(table.dtype)
        width = vals.shape[1]  # the whole row's lanes, or the worker's part
        row_ids, combined, issued = combine_runs(
            jnp.where(dead, sentinel, flat_ids), vals, sentinel, arm.combine,
        )
        counted = {
            "ps_rule_keys": n - jnp.sum(dead, dtype=jnp.int32),
            "ps_rule_rows": jnp.sum(row_ids < sentinel, dtype=jnp.int32),
        }
        if arm.combine != "sort":
            counted["ps_combine_kernel_lanes"] = (
                jnp.zeros((), jnp.int32) if arm.combine == "scatter_add"
                else counted["ps_rule_keys"]
            )
            counted["ps_combine_kernel_writes"] = issued
        # whole chunks: a chunk that started early would run the rule on
        # rows the chunk before it has already rewritten
        pad = -n % chunk
        row_ids = jnp.pad(row_ids, (0, pad), constant_values=sentinel)
        combined = jnp.pad(combined, ((0, pad), (0, 0)))

    def rewrite(i, carry):
        table, moved = carry  # tiles, or a packed store's physical rows
        ids = jax.lax.dynamic_slice(row_ids, (i * chunk,), (chunk,))
        # (the tile kernel's sums come in whole registers, zeros past
        # `width`: the chunk is cut out at the width the rule is handed)
        sums = jax.lax.dynamic_slice(
            combined, (i * chunk, 0), (chunk, width)
        )
        if packed:
            table, wrote = _rewrite_packed(
                spec, table, ids, sums, arm.write_back)
            return table, moved + wrote
        with jax.named_scope("ps.rule"):
            # a shard's block is a plain dense array (no tile under a mesh)
            current = pull(spec, table, ids) if block is None else jnp.take(
                table, ids, axis=0, mode="clip")
            new = update_fn(
                current, sums.reshape((chunk,) + _rule_takes(spec, width)),
            ).astype(table.dtype)
        if arm.write_back == "tile_set":
            table, opened = sorted_tile_set(table, ids, new)
            return table, moved + opened
        return table.at[ids].set(_physical_rows(spec, new), mode="drop"), moved

    chunks = -(-counted["ps_rule_rows"] // chunk)
    zero = jnp.zeros((), jnp.int32)
    table, moved = jax.lax.fori_loop(0, chunks, rewrite, (table, zero))
    # a flat wide row's write-back counts its tile rows where the tile
    # kernel wrote them; its physical rows are its distinct rows (k = 1)
    wide_tiles = arm.write_back == "tile_assign"
    counted["ps_rule_tiles"] = moved if wide_tiles or not packed else zero
    if packed:
        counted["ps_rule_packed_rows"] = (
            counted["ps_rule_rows"] if wide_tiles else moved)
    return table, {name: counted[name] for name in names}


def _rewrite_packed(
    spec: StoreSpec, table: Array, ids: Array, sums: Array, write_back: str,
) -> Tuple[Array, Array]:
    """One chunk of :func:`_push_rule` for a PACKED table: ``(table,
    physical rows written)``, or, where the tile kernel wrote a flat wide
    row's (below), ``(table, tile rows read and written)``.  ``ids`` are
    sorted and distinct, the sentinel last; ``sums`` ``(chunk, row_width)``,
    or ``(chunk, worker_width)`` where the push carried the worker's part
    of the rows (:func:`_push_rule` cuts a chunk out at the width pushed,
    whatever registers the combine summed it in).

    Under ``ps.rule`` ONE gather of the chunk's physical rows (``ids //
    k``, whole 128-lane registers), the lane slice down to each id's
    logical row (``ops/packed._sub_row_slice``) and the rule on those.
    What follows (``ps.push``, the write-back) places each new row at its
    window (``ops/packed.lane_shift_deltas``) and MERGES the logical rows
    of one physical row: sorted and distinct, the ids that share one are
    neighbours, at most ``k`` of them, so the merged row is the gathered
    physical row with every touched window replaced, ``k - 1`` shifted
    passes over the chunk, and the FIRST lane of each such run writes it,
    the others go to the sentinel: ONE ``set`` of whole physical rows,
    each touched physical row once, in the form ``write_back`` names
    (:func:`arms`): ``xla_set``, XLA's row ``set``; ``row_set``, for
    float32 physical rows of one register, ``ops/row_update.
    sorted_row_set``, the row kernel's walk with a copy for its body; and
    ``tile_assign``, for float32 physical rows of SEVERAL
    registers (``k`` = 1, a flat wide row: GloVe's 640 lanes),
    ``ops/row_update.sorted_tile_assign``, the wide add push's tile walk
    with a store for its body: a Pallas DMA cannot write one such row
    alone, so every touched tile of eight rows is read, set and written
    once.  That arm is handed the NEW rows as the
    rule made them, ``(chunk, row_width)``: at ``k`` = 1 a row has one
    window and no neighbour, and the kernel stores lanes ``[0, row_width)``
    of a row and keeps its pad lanes as it read them, so nothing shifts,
    pads or merges on its way (the other two arms write whole
    physical rows and merge first).  Selects and copies only, never an add or a
    masked sum: an untouched logical row inside a touched physical row,
    the pad lanes and a row's NaN or -0.0 come back bit for bit.  A
    physical row whose touched rows fall on two chunks is written by
    both: the second reads what the first wrote."""
    from ..ops.packed import _sub_row_slice, lane_shift_deltas
    from ..ops.row_update import sorted_row_set, sorted_tile_assign

    k, d = spec.pack, spec.row_width
    chunk, (phys_rows, lanes) = ids.shape[0], table.shape
    update_fn: UpdateFn = spec.update  # type: ignore[assignment]
    phys, sub = ids // k, ids % k  # the sentinel: one past the last row
    with jax.named_scope("ps.rule"):
        rows = jnp.take(table, phys, axis=0, mode="clip")
        new = update_fn(
            _sub_row_slice(rows, ids, d).reshape(
                (chunk,) + spec.value_shape),
            sums.reshape((chunk,) + _rule_takes(spec, sums.shape[1])),
        ).astype(table.dtype).reshape(chunk, d)
    writes = jnp.concatenate(
        [jnp.ones((1,), bool), phys[1:] != phys[:-1]]
    ) & (phys < phys_rows)
    at = jnp.where(writes, phys, phys_rows)
    if write_back == "tile_assign":
        # k = 1: every live lane writes, the sentinels close the chunk; a
        # new row has one window and no neighbour to merge with, and the
        # kernel keeps the pad lanes of the rows it sets as it read them
        return sorted_tile_assign(table, at, new)
    placed = lane_shift_deltas(new, ids, d)
    # the window a lane lies in (the pad lanes: one no id has)
    window = (jnp.arange(lanes, dtype=jnp.int32) // d)[None]
    merged = rows
    for s in range(min(k, chunk)):
        # the lane itself, then the lane `s` further on where it names the
        # same physical row: each replaces its own window
        near = (
            jnp.pad(phys[s:], (0, s), constant_values=-1) == phys
        )[:, None] & (window == jnp.pad(sub[s:], (0, s))[:, None])
        merged = jnp.where(
            near, jnp.pad(placed[s:], ((0, s), (0, 0))), merged)
    if write_back == "row_set":
        table = sorted_row_set(table, at, merged)
    else:
        table = table.at[at].set(merged, mode="drop")
    return table, jnp.sum(writes, dtype=jnp.int32)


def _rule_takes(spec: StoreSpec, width: int) -> Tuple[int, ...]:
    """The trailing shape of the ``combined`` a rule is handed for sums
    ``width`` lanes wide: the row's own shape, or, for the sums of a push
    that carried the worker's part alone, that many lanes."""
    return spec.value_shape if width == spec.row_width else (width,)


def _physical_rows(spec: StoreSpec, rows: Array) -> Array:
    """Logical rows ``(n, *value_shape)`` as rows of a dense table: a narrow
    rule store's get the zero lanes that fill its sublane tile
    (``StoreSpec.tile_lanes``), every other store's are as they are."""
    pad = spec.tile_lanes - spec.row_width
    return jnp.pad(rows, ((0, 0), (0, pad))) if pad > 0 else rows


# Rows a step of the loop that initialises a long dense table in place.
_INIT_BLOCK = 1 << 20


def _create_dense_in_blocks(
    spec: StoreSpec, init_fn: InitFn
) -> Callable[[], Array]:
    """``() -> table`` for a dense spec in one place, as one jitted program
    that initialises ``_INIT_BLOCK`` rows a ``fori_loop`` step and writes
    them into the table where it lies, as :func:`_create_packed` does for a
    packed one: the chip holds its table and one block's temporaries.  All
    rows at once, a seeded init of DiFacto's 49,126,310 x 36 float32 rows
    asked 11.8 GB of temporaries beside the 7.86 GB table (compiled for a
    v5e: PERF.md section 6, PR 45).  ``init_fn`` is deterministic per id,
    so the rows are what one call over all ids gives."""
    rows = spec.padded_capacity
    origin = (0,) * (len(spec.table_shape()) - 1)

    def init_block(i, table):
        # the last block starts early and writes some rows a second time
        at = jnp.minimum(i * _INIT_BLOCK, rows - _INIT_BLOCK)
        ids = at + jnp.arange(_INIT_BLOCK, dtype=jnp.int32)
        part = _physical_rows(spec, init_fn(ids)).astype(spec.dtype)
        return jax.lax.dynamic_update_slice(table, part, (at,) + origin)

    def build() -> Array:
        table = jnp.zeros(spec.table_shape(), spec.dtype)
        return jax.lax.fori_loop(0, -(-rows // _INIT_BLOCK), init_block, table)

    return jax.jit(build)


# The three constants below mirror the TPU compiler, as measured on the v5e.
# Physical row widths, in 128-lane registers, from which an add push goes
# through ops/row_update's tile kernel WHATEVER the batch: XLA's scatter-add
# is one serial read-modify-write a lane (~65 ns + ~12 ns a 128-lane piece of
# the row); the kernel pays a sort, a permute of the deltas, ~20 ns of adds a
# lane and a read and a write of every touched tile of 8 rows at HBM speed
# (cell 5's ids at 2 / 3 / 5 registers a row: XLA 9.95 / 11.71 / 14.28 ms, the
# kernel path 4.46 / 5.18 / 6.28; PERF.md section 6, PR 33).
_TILE_KERNEL_MIN_REGISTERS = 2
# A row of ONE register goes where the compiler's own form of the scatter-add
# sends it: it sorts the batch inside the scatter (13-22 ns a lane, cells 1
# and 2) exactly when the batch has MORE than an eighth as many lanes as the
# operand has rows, and leaves the serial form (74.7 ns a lane) at or under it
# (`tests/test_tpu_compile.py` pins that cut on the plain op for a described
# v5e).  The kernel takes the push whose lanes times this do not exceed the
# table's rows, XLA keeps every other.
_SERIAL_SCATTER_ROWS_A_LANE = 8
# ... and whose lanes are no fewer than this: under it the kernel's fixed
# costs (its sorts, its plan, a grid of at least four steps) lose to 75 ns a
# lane, and an eager push of a few rows would trace and lower a kernel for
# them, 0.4 s (the sweep: PERF.md section 6, PR 49).
_ONE_REGISTER_MIN_LANES = 1024
# (arm, physical row shape, dtype) already warned of (`arms`)
_REFUSALS_NOTED: set = set()


@dataclasses.dataclass(frozen=True)
class Arms:
    """The forms a store's pull and push take, as :func:`arms` read them."""

    # "take" | "narrow" | "packed_selects" | "packed_kernel[_by_field]"
    pull: str
    push: str  # "xla_add" | "tile_add" | "worker_reduce" | "rule"
    # a packed add push's lane shift: "" | "selects" | "kernel[_by_field]"
    shift: str
    # a rule push: "" | "sort" | "scatter_add" | "row_kernel" | "tile_kernel"
    combine: str
    # a rule push: "" | "xla_set" | "tile_set" | "row_set" | "tile_assign"
    write_back: str
    # a rule push or a `tile_add` runs in a shard_map, on the owning shard
    on_shards: bool


def arms(
    spec: StoreSpec, *, pull_lanes: Optional[int] = None,
    push_lanes: Optional[int] = None, lanes_over_workers: bool = False,
    push_width: Optional[int] = None, fields: Optional[int] = None,
) -> Arms:
    """THE one reader of which form a pull of ``pull_lanes`` ids and a push
    of ``push_lanes`` lanes take, from what the spec and the batch hold: the
    backend, the mesh and its workers, the update, the layout, the physical
    row, the dtype, the lanes against the rows, and the kernels' own
    refusals.  All of it is shapes: static per compiled step.  A lane count
    of None asks whether ANY pull / push of the store may take a kernel
    (:func:`_preload_tile_kernel`).  ``lanes_over_workers``: the caller
    knows the batch's lanes lie split over ``dp`` (:func:`push_counted`).
    ``push_width``: the lanes of a row the push is handed, None for what a
    STEP pushes (the worker's part where the spec names one,
    ``StoreSpec.worker_width``, else the whole row); a rule's sums are made
    at that width, so it chooses the ``combine``.  ``fields``: the batch is
    a block of two axes, ``fields`` keys an example, whose logic takes its
    rows TURNED and pushes them so (:func:`pull` and :func:`push_counted`'s
    ``turned``, ONE declaration, ``make_train_step``'s of a logic that
    ``pulls_turned``); where the lane kernels take the batch and
    ``ops/packed.by_field`` says its shape is one they were compiled for,
    they move it a field at a time, ``packed_kernel_by_field`` and
    ``kernel_by_field``: the same bits, XLA's own layout of the logic's
    buffers at the other end.  A
    store a kernel REFUSES (bfloat16, a pinned layout Mosaic cannot tile, a
    batch under one block) keeps XLA's arm and is counted and warned of once
    a physical row shape, dtype and arm (``ops/row_update.refusal_count``).

    THE CASE TABLE, on a TPU, float32 (``-`` is ``""``; the PRs are PERF.md
    section 6's, where the arm was priced;
    ``tests/test_store.py::test_the_arms_table`` holds a case a row).  Off a
    TPU a pull reads ``take`` / ``narrow`` / ``packed_selects``, an add push
    ``xla_add`` (``worker_reduce`` as below) and ``selects`` (``on_shards``
    follows ``tile_add``: no), a rule push ``sort`` / ``scatter_add`` and
    ``xla_set``, ``on_shards`` as below.
    The worker's part of a row (the last rows of the second table) changes
    no pull arm, only the width the arm cuts (:func:`pull`), and no
    write-back: the rule writes whole rows.

    An ``add`` store (``combine`` and ``write_back`` ``""``):

    =================================  ======================  =============  ===============  =========  ======  ========
    spec and batch                     pull                    push           shift            on_shards  cell    PR
    =================================  ======================  =============  ===============  =========  ======  ========
    dense 1 reg, lanes x 8 > rows      take                    xla_add        -                no         1 3 11  27 30
    dense 1 reg, 1,024+ <= rows / 8    take                    tile_add       -                no         none    49
    1 reg over ps 4, shard's rows / 8  take                    tile_add       -                yes        16      49 67 68
    dense 1 reg, dp 4, shard <= lanes  take                    worker_reduce  -                no         8       40
    packed k 7, lanes x 8 > rows       packed_kernel           xla_add        kernel           no         none    29 42 51
    the same over ps 4, dp 1           packed_kernel           xla_add        kernel           no         none    31 42 51
    packed k 7, fields 39              packed_kernel_by_field  xla_add        kernel_by_field  no         2       63
    fields 39 over ps 4, dp 1          packed_kernel_by_field  xla_add        kernel_by_field  no         4       63
    fields 39, the batch no blocks     packed_kernel           xla_add        kernel           no         none    63
    packed k 2, 1,024+ <= rows / 8     packed_kernel           tile_add       kernel           no         none    49 51
    k 2, fields 26, 1,024+ <= rows/8   packed_kernel_by_field  tile_add       kernel_by_field  no         10      65
    packed k 7, under a block of ids   packed_selects          xla_add        selects          no         none    42
    packed k 1, 5 regs (3: cell 7)     packed_selects          tile_add       selects          no         5 7     32 33 57
    5 regs over ps 4, dp 1             take                    tile_add       -                yes        none    33 67
    5 regs over ps 2, dp 2             take                    xla_add        -                no         none    33 67
    =================================  ======================  =============  ===============  =========  ======  ========

    A store whose ``update`` is a rule (``push`` ``"rule"``, ``shift``
    ``""``):

    ================================  ======================  ===========  ===========  =========  ====  ========
    spec                              pull                    combine      write_back   on_shards  cell  PR
    ================================  ======================  ===========  ===========  =========  ====  ========
    3 lanes, held at its tile of 4    narrow                  sort         tile_set     no         6     34 35
    6 lanes, held at its tile of 8    narrow                  row_kernel   tile_set     no         none  35 46
    (2, 2) lanes: rank 2, no tile     take                    sort         xla_set      no         none  35
    packed k 3 (36 lanes)             packed_kernel           row_kernel   row_set      no         none  46 47 54
    the same over ps 4, dp 1          packed_kernel           row_kernel   row_set      yes        none  52
    the same over ps 2, dp 2          packed_kernel           scatter_add  xla_set      no         none  52
    packed k 1, 1 reg (100 lanes)     packed_selects          row_kernel   row_set      no         none  47 61
    packed k 1, 5 regs (602 lanes)    packed_selects          tile_kernel  tile_assign  no         none  55 57
    dense 1 reg (pinned, 100)         take                    row_kernel   xla_set      no         none  46 61
    packed k 3, the worker's 20 / 36  packed_kernel           row_kernel   row_set      no         none  59 62
    the worker's 20 / 36 over ps 4    packed_kernel           row_kernel   row_set      yes        none  59 62
    the worker's 20 / 36, fields 39   packed_kernel_by_field  row_kernel   row_set      no         9     63
    20 / 36, fields 39, over ps 4     packed_kernel_by_field  row_kernel   row_set      yes        12    63
    5 regs, the worker's 301 / 602    packed_selects          tile_kernel  tile_assign  no         13    59
    5 regs, the worker's 100 / 602    packed_selects          row_kernel   tile_assign  no         none  59
    5 regs, the worker's 3 / 602      packed_selects          sort         tile_assign  no         none  59
    1 reg, the worker's 100 / 101     packed_selects          row_kernel   row_set      no         14    61 62
    1 reg, 100 / 101 over ps 4        packed_selects          row_kernel   row_set      yes        none  61
    2 regs, the worker's 128 / 256    packed_selects          row_kernel   tile_assign  no         15    64
    2 regs, 128 / 256 over ps 4       packed_selects          row_kernel   tile_assign  yes        none  64
    ================================  ======================  ===========  ===========  =========  ====  ========

    Reasons the code does not show.  GSPMD partitions XLA's scatter-add and
    cannot partition Mosaic's call: the lane kernels, a rule's kernels and
    the add push's tile kernel run under a mesh inside a ``shard_map``,
    where they see a plain array; a push's need ONE worker for that: ``dp``
    > 1 holds the batch's lanes split, a shard would first have to be sent
    the other workers' keys, and no code here does, so such a store keeps
    the one-place push under GSPMD (an add store ``xla_add`` or
    ``worker_reduce``), noted.  On the shards an add push is judged as a
    store of ONE shard's block in one place is (its rows against the lanes:
    every shard walks all the batch's lanes), and where that reads
    ``xla_add`` the scatter stays GSPMD's, the program it was (cell 4: its
    lanes x 8 exceed a shard's 6.7 M physical rows, the compiler's sorted
    form).  A mesh with one shard leaves the packed pull to GSPMD.  ``take``
    over ``ps`` under one worker group gathers on the shards too
    (:func:`_take_on_shards`: cell 16 since PR 68); over ``dp`` > 1 it is
    GSPMD's (a gather a shard and an all-reduce; no cell).
    ``worker_reduce`` wants a shard no longer than the batch, so that the
    per-worker sums it moves are no larger than the deltas.  A rule's write-back shifts a chunk at a time
    (:func:`_rewrite_packed`), so only an add push has a ``shift``.  A rule
    row no wider than a sort carries rides through the sort whatever the
    backend.  A narrow rule row NOT held at its tile (bfloat16, rank 0 or
    2) keeps XLA's ``set``, noted.  The ``row_kernel`` combine pays by the
    BLOCK of 256 sorted lanes, whatever the batch's duplicates (its slots
    are dense ranks, so a block's sums leave as ONE copy:
    ``ops/row_update.sorted_run_sums``, PR 62; a descriptor a row written
    until then), which is why ``arms`` need not know them: XLA's
    scatter-add, which won cell 14's all-distinct batch by 1.15 ms against
    the walk that paid by the row (PR 61), loses it by ~1.2 now, and lost
    cell 9's (a row named 3.6 times) all along."""
    from ..ops import dedup, packed, row_update

    tpu = jax.default_backend() == "tpu"
    shape, rule = spec.table_shape(), spec.update != "add"
    phys = 1  # lanes of a physical row
    for s in shape[1:]:
        phys *= int(s)
    workers = worker_count(spec.mesh)
    one_block = workers == 1  # a rule's push sees a plain array

    def taken(what: str, why: Optional[str]) -> bool:
        if why is not None:
            key = (what, shape[1:], jnp.dtype(spec.dtype).name)
            if key not in _REFUSALS_NOTED:
                _REFUSALS_NOTED.add(key)
                row_update.note_refusal(what, why)
        return why is None

    def lane_kernel(n: Optional[int], what: str) -> bool:
        # ops/packed's two kernels move the same rows, one way each
        if not tpu or spec.pack == 1 or (
                spec.mesh is not None and spec.num_shards == 1):
            return False
        why = packed.slice_refusal(
            packed.SLICE_BLOCK if n is None else n, spec.dtype, spec.row_width)
        return why is None if n is None else taken(what, why)

    def by_field(n: Optional[int]) -> str:
        whole = fields and n is not None and n % fields == 0
        return "_by_field" if whole and packed.by_field(
            fields, n // fields) else ""

    if spec.layout != "packed":
        pull = "narrow" if spec.tile_lanes > spec.row_width else "take"
    else:
        n = pull_lanes
        if n is not None and spec.mesh is not None:
            # a shard slices the lanes of its worker
            over = spec.mesh.size // spec.num_shards
            n = n // over if n % over == 0 else n
        kernel = lane_kernel(n, "the lane slice of a packed pull")
        pull = "packed_kernel" + by_field(n) if kernel else "packed_selects"

    if not rule:
        # the table the kernel sees: the whole one, or a shard's block
        block = (spec.rows_per_shard,) + shape[1:]
        tiles, what = tpu, "wide rows"
        if tiles and phys < _TILE_KERNEL_MIN_REGISTERS * 128:
            n = _ONE_REGISTER_MIN_LANES if push_lanes is None else push_lanes
            tiles = (phys == 128 and n >= _ONE_REGISTER_MIN_LANES
                     and n * _SERIAL_SCATTER_ROWS_A_LANE <= block[0])
            what = "one-register rows eight batches long"
        if tiles and spec.mesh is not None and not one_block:
            if push_lanes is not None:  # noted where a batch is judged
                taken("an add store's push on the shards that own its rows",
                      f"the batch lies split over dp = {workers} workers")
            tiles = False
        if tiles and taken(f"push into a table of {what}",
                           row_update.tile_refusal(block, spec.dtype)):
            push = "tile_add"
        elif (lanes_over_workers and push_lanes is not None and workers > 1
              and push_lanes % workers == 0
              and spec.rows_per_shard <= push_lanes):
            push = "worker_reduce"
        else:
            push = "xla_add"
        shift = ""
        if spec.layout == "packed":
            kernel = lane_kernel(push_lanes, "the lane shift of a packed push")
            shift = "kernel" + by_field(push_lanes) if kernel else "selects"
        on_shards = push == "tile_add" and spec.mesh is not None
        return Arms(pull, push, shift, "", "", on_shards)

    on_shards = spec.mesh is not None and taken(
        "a rule store's push on the shards that own its rows",
        None if one_block else
        f"the batch lies split over dp = {workers} workers")
    flat_wide = spec.layout == "packed" and spec.pack == 1 and phys > 128
    write_back = "xla_set"
    if tpu and spec.tile_lanes:
        write_back = "tile_set"
    elif tpu and spec.layout == "packed":
        if one_block and taken(
                "write-back of a packed rule store's rows",
                row_update.tile_refusal(shape, spec.dtype) if flat_wide
                else row_update.refusal(shape[1:], spec.dtype)):
            write_back = "tile_assign" if flat_wide else "row_set"
    elif tpu and spec.narrow_rule:
        taken("write-back of a rule's narrow rows",
              row_update.set_refusal(shape, spec.dtype))
    # the sums are as wide as the rows the push is handed: the whole row
    # (as it lies: flat in several registers or not), or the worker's part,
    # which is several registers wide where it is over 128 lanes
    width = push_width or spec.worker_width or spec.row_width
    wide = flat_wide if width == spec.row_width else width > 128
    if width <= dedup.SORT_CARRIES_LANES:
        combine = "sort"
    elif one_block and tpu and taken(
            "the sum of a rule's wide rows", dedup.kernel_refusal(
                packed.phys_width(width) if wide else width, spec.dtype)):
        combine = "tile_kernel" if wide else "row_kernel"
    else:
        combine = "scatter_add"
    return Arms(pull, "rule", "", combine, write_back, on_shards)


def _preload_tile_kernel(spec: StoreSpec) -> None:
    """Where a store is made whose pushes or pulls will trace a kernel: have
    Pallas imported by then, beside the table's staging (the import is ~1 s
    that the first trace of the step else pays)."""
    a = arms(spec)
    if (a.pull.startswith("packed_kernel") or a.push == "tile_add"
            or a.combine.endswith("_kernel")
            or a.write_back not in ("", "xla_set")):
        from ..ops.row_update import preload

        preload()


def step_counts(
    spec: StoreSpec, counted: Optional[dict], *, pull_lanes: int,
    push_lanes: int, fields: Optional[int] = None, compute_parts: int = 1,
) -> dict:
    """What a step hands out of its pull and its push beside the logic's
    outputs: what :func:`push_counted` counted and, for a store packed
    several rows to a physical row, which arm sliced the pulled rows
    (``ps_slice_kernel``) and, where ``update`` is ``"add"``, which shifted
    the pushed deltas (``ps_shift_kernel``), 1 for the kernel in either of
    its forms, as this trace read them (:func:`arms`, told the step's
    ``fields``); and ``ps_lanes_by_field``, 1 where every lane kernel the
    step has (the pull's and, for an ``add`` store, the shift's) moves the
    batch a FIELD at a time, the logic's buffers batch-minor at the other
    end (``packed_kernel_by_field`` / ``kernel_by_field``), else 0.  A store
    whose spec names a worker's
    part (``StoreSpec.worker_width``) says how many lanes of a row crossed,
    a key: ``ps_pull_row_lanes`` here (a step pulls the worker's part) beside
    :func:`push_counted`'s ``ps_push_row_lanes``; every other store's whole
    row crosses and its step hands out neither.  ``compute_parts`` is the
    caller's own reading (``make_train_step``): into how many parts over
    ``ps`` it split the minibatch's compute, ``ps_compute_parts`` where that
    is more than one (a step that computes the whole minibatch in every
    place hands out no such output and keeps its text)."""
    out = dict(counted or {})
    if compute_parts > 1:
        out["ps_compute_parts"] = jnp.asarray(compute_parts, jnp.int32)
    if spec.worker_width is not None:
        out["ps_pull_row_lanes"] = jnp.asarray(spec.worker_width, jnp.int32)
    if spec.pack > 1:
        arm = arms(spec, pull_lanes=pull_lanes, push_lanes=push_lanes,
                   fields=fields)
        out["ps_slice_kernel"] = jnp.asarray(
            arm.pull.startswith("packed_kernel"), jnp.int32)
        if arm.shift:
            out["ps_shift_kernel"] = jnp.asarray(
                arm.shift.startswith("kernel"), jnp.int32)
        out["ps_lanes_by_field"] = jnp.asarray(
            arm.pull.endswith("_by_field")
            and arm.shift in ("", "kernel_by_field"), jnp.int32)
    return out


def publish_counts(outs: dict, registry: Any, total, peak) -> None:
    """The store's own outputs of a dispatch (:func:`step_counts`: every
    ``ps_*`` key of ``outs``) as its ``store_*`` gauges, ``component=train``
    (docs/observability.md is their catalog).  ``total`` and ``peak`` are
    the caller's readings of one output over the steps a dispatch stacked:
    a count's sum, a constant's maximum.  Whoever fetches a step's outputs
    calls this where it fetches them anyway
    (``StreamingDriver._publish_step_counts``).  The names are literal:
    ``tools/fpsanalyze`` matches them to the catalog."""
    # (1 where the step says nothing: the whole minibatch in every place)
    registry.gauge("store_compute_parts", component="train").set(
        peak(outs["ps_compute_parts"]) if "ps_compute_parts" in outs else 1)
    if "ps_slice_kernel" in outs:
        registry.gauge(
            "store_packed_slice_kernel", component="train"
        ).set(peak(outs["ps_slice_kernel"]))
    if "ps_shift_kernel" in outs:
        registry.gauge(
            "store_packed_shift_kernel", component="train"
        ).set(peak(outs["ps_shift_kernel"]))
    if "ps_lanes_by_field" in outs:
        registry.gauge(
            "store_packed_by_field", component="train"
        ).set(peak(outs["ps_lanes_by_field"]))
    if "ps_push_tile_rows" in outs:
        registry.gauge(
            "store_push_kernel_lanes", component="train"
        ).set(total(outs["ps_push_kernel_lanes"]))
        registry.gauge(
            "store_push_tile_rows", component="train"
        ).set(total(outs["ps_push_tile_rows"]))
    if "ps_push_lanes_max_shard" in outs:
        registry.gauge(
            "store_push_lanes_max_shard", component="train"
        ).set(total(outs["ps_push_lanes_max_shard"]))
        registry.gauge(
            "store_push_tile_rows_max_shard", component="train"
        ).set(total(outs["ps_push_tile_rows_max_shard"]))
    if "ps_rule_rows" not in outs:
        return
    if "ps_pull_row_lanes" in outs:
        registry.gauge("store_pull_row_lanes", component="train").set(
            peak(outs["ps_pull_row_lanes"]))
        registry.gauge("store_push_row_lanes", component="train").set(
            peak(outs["ps_push_row_lanes"]))
    registry.gauge("store_rule_keys", component="train").set(
        total(outs["ps_rule_keys"]))
    registry.gauge("store_rule_rows", component="train").set(
        total(outs["ps_rule_rows"]))
    registry.gauge("store_rule_tiles", component="train").set(
        total(outs["ps_rule_tiles"]))
    if "ps_rule_rows_max_shard" in outs:
        registry.gauge(
            "store_rule_keys_max_shard", component="train"
        ).set(total(outs["ps_rule_keys_max_shard"]))
        registry.gauge(
            "store_rule_rows_max_shard", component="train"
        ).set(total(outs["ps_rule_rows_max_shard"]))
    if "ps_combine_kernel_lanes" in outs:
        registry.gauge(
            "store_combine_kernel_lanes", component="train"
        ).set(total(outs["ps_combine_kernel_lanes"]))
        registry.gauge(
            "store_combine_kernel_writes", component="train"
        ).set(total(outs["ps_combine_kernel_writes"]))
    if "ps_rule_packed_rows" in outs:
        registry.gauge(
            "store_rule_packed_rows", component="train"
        ).set(total(outs["ps_rule_packed_rows"]))


def _push_rule_on_shards(
    spec: StoreSpec,
    table: Array,
    flat_ids: Array,
    flat_deltas: Array,
    live: Optional[Array],
    arm: Arms,
) -> Tuple[Array, dict]:
    """:func:`_push_rule` for a table sharded over ``ps`` under one worker:
    the reference's servers, each running ``paramUpdate`` on the rows it
    owns.  ONE ``shard_map`` over the mesh, the table ``P(ps, ...)``, the
    batch's ids, deltas and mask replicated (every chip holds them: ``dp``
    = 1).  A shard takes the ids that fall in its block, relative to its
    first row; every other lane is dead to it, as a masked or dropped lane
    is to the one-place push, and it runs that push on its own block: its
    own keys sorted and summed, its own distinct rows read, ruled and
    written, in the arms a store of that block in one place would get
    (``arm``: Mosaic's calls cannot be partitioned, and inside the
    ``shard_map`` they see a plain array).  No key is sent anywhere and no row leaves its chip; every
    chip still walks all the batch's lanes in its sort (routing a key to
    its owner alone: ROADMAP S9 (2)).

    Ownership is disjoint, so the counts summed over ``ps`` are the
    one-place push's; ``ps_rule_keys_max_shard`` and
    ``ps_rule_rows_max_shard`` are the fullest shard's (how far the
    partition is from even: a step waits for that shard).  One gather of a
    few scalars across ``ps`` carries them, the push's only collective."""
    mesh, ps = spec.mesh, spec.ps_axis
    block = spec.rows_per_shard * spec.pack  # a shard's LOGICAL rows
    masks = () if live is None else (live,)

    def on_shard(table: Array, ids: Array, deltas: Array, *mask: Array):
        rel = ids - jax.lax.axis_index(ps) * block
        # another shard's row, or the sentinel of a dropped lane
        rel = jnp.where((rel >= 0) & (rel < block), rel, block)
        table, counted = _push_rule(
            spec, table, rel, deltas, mask[0] if mask else None, arm, block)
        names = sorted(counted)
        by_shard = jax.lax.all_gather(
            jnp.stack([counted[k] for k in names]), ps)
        total = dict(zip(names, by_shard.sum(axis=0)))
        for k in ("ps_rule_keys", "ps_rule_rows"):
            total[k + "_max_shard"] = by_shard[:, names.index(k)].max()
        return table, total

    rows = spec.sharding().spec
    return jax.shard_map(
        on_shard,
        mesh=mesh,
        in_specs=(rows,) + (P(),) * (2 + len(masks)),
        out_specs=(rows, P()),
        check_vma=False,  # a Pallas call states no varying axes
    )(table, flat_ids, flat_deltas, *masks)


def _push_add_on_shards(
    spec: StoreSpec, table: Array, ids: Array, deltas: Array
) -> Tuple[Array, dict]:
    """The ``tile_add`` push (physical ids, flat, and their rows as
    :func:`_phys_scatter_args` leaves them) into a table sharded over ``ps``
    under one worker, shaped as :func:`_push_rule_on_shards` is: ONE
    ``shard_map`` over the mesh, the table ``P(ps, ...)``, ids and rows
    replicated.  A shard takes the ids that fall in its block, relative to
    its first row; every other lane is dead to it (it sorts to the end of
    the shard's batch, and the kernel neither reads its row nor opens a
    tile for it), and the shard runs the one-place push on its own block:
    ``ops/row_update.scatter_add_counted``, which inside the ``shard_map``
    sees a plain array.  No key is sent anywhere, no row leaves its chip,
    and every chip still sorts and permutes all the batch's lanes.

    The table is the one-place push's bit for bit: ownership is disjoint,
    the sort is stable, so a row's deltas are added to it one by one in the
    order of the batch, by the shard that owns it.  The counts are sums over
    ``ps`` (the kept lanes are the one-place push's; a shard's tile rows are
    counted over ITS sorted batch's calls, and blocks start on a tile row,
    so the sum is the one-place push's wherever a batch is one call long);
    ``ps_push_lanes_max_shard`` and ``ps_push_tile_rows_max_shard`` are the
    fullest shard's.  One gather of two scalars across ``ps`` carries them,
    the push's only collective."""
    from ..ops.row_update import scatter_add_counted

    ps, rows = spec.ps_axis, spec.rows_per_shard

    def on_shard(table: Array, ids: Array, deltas: Array):
        rel = ids - jax.lax.axis_index(ps) * rows
        # another shard's row, or the sentinel of a dropped lane
        rel = jnp.where((rel >= 0) & (rel < rows), rel, rows)
        table, lanes, tile_rows = scatter_add_counted(
            table, rel, deltas, rolled=True)
        by_shard = jax.lax.all_gather(jnp.stack([lanes, tile_rows]), ps)
        total, most = by_shard.sum(axis=0), by_shard.max(axis=0)
        return table, {
            "ps_push_kernel_lanes": total[0], "ps_push_tile_rows": total[1],
            "ps_push_lanes_max_shard": most[0],
            "ps_push_tile_rows_max_shard": most[1],
        }

    block = spec.sharding().spec
    return jax.shard_map(
        on_shard,
        mesh=spec.mesh,
        in_specs=(block, P(), P()),
        out_specs=(block, P()),
        check_vma=False,  # a Pallas call states no varying axes
    )(table, ids, deltas.astype(table.dtype))


def _push_add_over_workers(
    spec: StoreSpec, table: Array, ids: Array, deltas: Array
) -> Array:
    """``table.at[ids].add(deltas, mode="drop")`` (physical ids, flat) for a
    batch whose lanes lie split over the mesh's ``dp`` workers: every
    worker scatter-adds ITS lanes, in their order, into a zeroed copy of
    the shard of the table its chip holds, and the workers' sums are added
    up across ``dp`` (scope ``ps.delta_reduce``: the step's one collective
    for the push) and onto the table.  Bulk-synchronous over the whole
    microbatch, as one scatter-add is; what differs is the order in which a
    row's deltas are summed (worker by worker, then across workers).  Left to
    GSPMD the scatter's operand is a table that every worker holds whole,
    and the partitioner is free to gather the batch onto every chip and
    have each walk all of it.  The sum is written outside the ``shard_map``
    for the partitioner to name and place as an ``all-reduce``
    (:func:`_packed_pull_on_shards` says why)."""
    mesh, ps = spec.mesh, spec.ps_axis
    rows = spec.rows_per_shard

    def on_worker(ids: Array, deltas: Array) -> Array:
        rel = ids - jax.lax.axis_index(ps) * rows
        # other shards' rows, and the sentinel of a dropped lane, fall out
        rel = jnp.where((rel >= 0) & (rel < rows), rel, rows)
        zeros = jnp.zeros((rows,) + deltas.shape[1:], deltas.dtype)
        return zeros.at[rel].add(deltas, mode="drop")[None]

    wide = (None,) * (deltas.ndim - 1)
    sums = jax.shard_map(
        on_worker,
        mesh=mesh,
        in_specs=(P(DP_AXIS), P(DP_AXIS, *wide)),
        out_specs=P(DP_AXIS, ps, *wide),
        check_vma=False,
    )(ids, deltas)
    with jax.named_scope("ps.delta_reduce"):
        total = sums.sum(axis=0)
    return table + total


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _packed_pull_on_shards(
    spec: StoreSpec, table: Array, ids: Array, kernel: bool = False,
    width: Optional[int] = None, turned: bool = False,
) -> Array:
    """The packed pull of ``ids`` (flat, or ``turned`` a key block of two
    axes whose rows come back as ``ops/packed.packed_pull``'s do;
    pre-clipped) from a table sharded
    over ``ps``: each shard gathers physical rows of its own block, slices
    them down to the logical row and zeroes the rows it does not own; the
    sum over the shards of those ``(n, row_width)`` answers is the step's
    ONE all-reduce.  Left to GSPMD the ``jnp.take`` is all-reduced where
    the gather ends, before the lane slice: 128 lanes a row where
    ``row_width`` are wanted (FM: ``f32[1277952,128]``, 654 MB a step, for
    87).  The sum is written outside the ``shard_map``, for the partitioner
    to place: a ``psum`` inside it reduces the rows as the slice leaves
    them, row-major and padded to 128 lanes again on the TPU (16.7 ms a
    step against 5-8; PERF.md section 6, PR 31).  The ids stay split over
    the mesh's other axes (``dp``) where the batch is.  ``kernel``: the
    slice's arm (:func:`arms`).  ``width``: a row's first ``width`` lanes
    alone (the worker's part, :func:`pull`), cut by that slice, so the
    all-reduce moves no lane a worker does not read (DiFacto on four
    chips: ``f32[32768,39,20]`` for ``[..., 36]``).  Jitted for the reason
    ``packed_pull`` is."""
    from ..ops.packed import sub_row_slice

    mesh, ps, k = spec.mesh, spec.ps_axis, spec.pack
    others = tuple(a for a in mesh.axis_names if a != ps)
    if ids.shape[0] % (mesh.size // spec.num_shards):
        others = ()
    # the axis the batch is split on: the block's leading one, which a
    # turned answer has second
    lanes = (None,) if turned else ()
    out = (others or None,) + lanes
    if turned:
        out = out[::-1]

    def on_shard(block: Array, ids: Array) -> Array:
        vals, mine = _own_rows(spec, block, ids // k)
        vals = sub_row_slice(
            vals, ids, spec.row_width, kernel, width, turned)
        mine = mine.T if turned else mine
        return jnp.where(mine[..., None], vals, jnp.zeros_like(vals))[None]

    return jax.shard_map(
        on_shard,
        mesh=mesh,
        in_specs=(P(ps, None), P(others or None, *lanes)),
        out_specs=P(ps, *out, None),
        check_vma=not kernel,  # a Pallas call states no varying axes
    )(table, ids).sum(axis=0)


def _own_rows(spec: StoreSpec, block: Array, phys: Array):
    """Inside a ``shard_map`` over ``ps``: ``(rows, mine)``, the rows of
    this shard's ``block`` that the physical rows ``phys`` of the whole
    table name, flat, and which of ``phys`` lie in it.  Rows of other
    shards wrap round to rows spread over this block: clipped, they would
    all be its first or its last row, and a gather that keeps hitting one
    row takes longer a row (13.6 ns a 128-lane row against 9.9 at cell
    16's size: PERF.md section 6, PR 68)."""
    rows = spec.rows_per_shard
    rel = phys - jax.lax.axis_index(spec.ps_axis) * rows
    mine = (rel >= 0) & (rel < rows)
    return jnp.take(block, rel.reshape(-1), axis=0, mode="wrap"), mine


@functools.partial(jax.jit, static_argnums=(0,))
def _take_on_shards(spec: StoreSpec, table: Array, ids: Array) -> Array:
    """``jnp.take(table, ids, axis=0)`` (``ids`` pre-clipped, whole on every
    chip) of a dense table sharded over ``ps`` under one worker group: each
    shard gathers every lane from its own block and zeroes the rows it does
    not own, as :func:`_packed_pull_on_shards` does, and the sum over the
    shards is written OUTSIDE the ``shard_map`` and FLAT, ``(lanes, row)``,
    for the partitioner to name and place: an all-reduce, or, where the
    caller constrains the flat answer split over ``ps`` (``make_train_step``
    for a logic whose compute it splits), a reduce-scatter that moves the
    block as it lies.  Jitted for the reason ``packed_pull`` is."""

    def on_shard(block: Array, lanes: Array) -> Array:
        vals, mine = _own_rows(spec, block, lanes)
        mine = mine.reshape(mine.shape + (1,) * (vals.ndim - 1))
        return jnp.where(mine, vals, jnp.zeros_like(vals))[None]

    flat = jax.shard_map(
        on_shard,
        mesh=spec.mesh,
        in_specs=(spec.sharding().spec, P()),
        out_specs=P(spec.ps_axis),
    )(table, ids.reshape(-1)).sum(axis=0)
    return flat.reshape(ids.shape + flat.shape[1:])


def _packed_shift_on_mesh(
    spec: StoreSpec, by_lane: Array, ids: Array, live: Optional[Array]
) -> Array:
    """``ops/packed.lane_shift_kernel`` of a batch pushed into a table
    sharded over ``ps``: Mosaic's call cannot be partitioned, so it runs
    inside a ``shard_map`` over the whole mesh with lanes, ids and mask
    replicated, every chip shifting ALL the lanes, as GSPMD has each chip do
    with the selects.  The scatter-add that takes the rows stays the
    partitioner's, on the operand it had: rows split over ``dp`` here would
    have it sum a row's deltas worker by worker, in another order."""
    from ..ops.packed import lane_shift_kernel

    masks = () if live is None else (live,)
    return jax.shard_map(
        lambda d, i, *m: lane_shift_kernel(d, i, spec.row_width, *m),
        mesh=spec.mesh,
        in_specs=(P(),) * (2 + len(masks)),
        out_specs=P(),
        check_vma=False,  # a Pallas call states no varying axes
    )(by_lane, ids, *masks)


def _pad_rows(spec: StoreSpec, pad: int) -> Callable[[Array], Array]:
    """``values -> values`` with ``pad`` zero rows appended, for ``values``
    that already live on the spec's mesh, row-sharded or not.  One jitted
    call with the zeros made INSIDE it: the partitioner then pads each shard
    in place and passes a few halo rows to its neighbour.  An eager
    ``jnp.concatenate`` of a ``ps``-sharded array with a zeros ARGUMENT
    gathers the whole table onto every chip instead, which a table larger
    than one chip's memory cannot survive (187.8 M x 17 f32 over four v5e
    chips: RESOURCE_EXHAUSTED, 24 GB asked of 16; PERF.md section 6, PR 28)."""

    def padded(values: Array) -> Array:
        return jnp.concatenate(
            [values, jnp.zeros((pad,) + spec.value_shape, spec.dtype)]
        )

    return jax.jit(padded, out_shardings=spec.sharding())


# Physical rows a step of `_pack_block`: 131,072 x 7 of FM's rows are 0.47 GB
# once the compiler has laid them 128 lanes wide.
_PACK_CHUNK = 131_072


def _pack_block(
    spec: StoreSpec, rows: int, values: Array, skip: Any = 0,
    reach: int = 0, beyond: Optional[Array] = None,
) -> Array:
    """``rows`` packed physical rows whose first logical row is
    ``values[skip]`` (``skip <= reach``, and only ``reach`` is static);
    past the end of ``values`` the rows are ``beyond``'s, then zeros.
    Packs ``_PACK_CHUNK`` physical rows a ``fori_loop`` step into a zeroed
    table, so the chip holds the values, the table and one chunk.
    To reshape ``(n, 17)`` into ``(n / 7, 119)`` the TPU compiler first
    lays the rows 128 lanes wide: for FM's 49.1 M rows all at once that is
    25 GB asked of a 16 GB chip, whether the ops are jitted together or
    run eagerly one by one (the fault PR 28 met under a mesh; PERF.md
    section 6, PR 29)."""
    from ..ops.packed import pack_table

    k, d = spec.pack, spec.row_width
    n = values.shape[0]
    # physical rows that `values` holds whole wherever the block starts
    whole = (n - reach) // k
    table = jnp.zeros((rows, spec.table_shape()[1]), spec.dtype)
    chunk = min(whole, _PACK_CHUNK)

    def pack_chunk(i, table):
        # the last chunk starts early and packs some rows a second time
        at = jnp.minimum(i * chunk, whole - chunk)
        part = jax.lax.dynamic_slice(
            values, (skip + at * k, 0), (chunk * k, d)
        )
        return jax.lax.dynamic_update_slice(
            table, pack_table(part, chunk), (at, 0)
        )

    if whole:
        table = jax.lax.fori_loop(0, -(-whole // chunk), pack_chunk, table)
    if rows > whole:
        # the block's end: a few rows of `values`, of `beyond`, and zeros
        end = (rows - whole) * k
        edge = [values[whole * k:]] + ([] if beyond is None else [beyond])
        short = reach + end - sum(e.shape[0] for e in edge)
        if short > 0:
            edge.append(jnp.zeros((short, d), spec.dtype))
        edge = jax.lax.dynamic_slice(
            jnp.concatenate(edge), (skip, 0), (end, d)
        )
        table = jax.lax.dynamic_update_slice(
            table, pack_table(edge, rows - whole), (whole, 0)
        )
    return table


def _pack_rows(spec: StoreSpec) -> Callable[[Array], Array]:
    """``values -> table`` for a packed table, as one jitted program
    (:func:`_pack_block`).  The table comes out where the values lie,
    whole; under a mesh ``_place`` then hands it to the shards, which is
    right for values that fit one place (numpy, one device).  The values
    are not donated: no output has their shape to take their place."""

    def packed(values: Array) -> Array:
        return _pack_block(
            spec, spec.table_shape()[0], values.reshape(-1, spec.row_width)
        )

    return jax.jit(packed)


def _next_shard_in_reach(spec: StoreSpec, n: int) -> bool:
    """Whether ``n`` rows split evenly over ``ps`` put every shard's packed
    block inside that shard's rows and its right neighbour's, with room.
    A packed shard holds ``rows_per_shard * pack`` logical rows, ``ahead``
    more than a shard of the values (FM on four chips: 46,941,888 against
    46,941,853), so shard ``s``'s block starts ``s * ahead`` rows into its
    own values and ends ``(s + 1) * ahead`` rows into the next shard's.
    False only for tables of a few rows a shard (twice the rows a block
    may share with a neighbour, and one physical row, do not fit one)."""
    shards = spec.num_shards
    reach = (shards - 1) * (spec.rows_per_shard * spec.pack - n // shards)
    return n % shards == 0 and 2 * reach + spec.pack <= n // shards


def _pack_rows_on_mesh(spec: StoreSpec) -> Callable[[Array], Array]:
    """``values -> table`` for a packed table whose values already lie on
    the spec's mesh (``_next_shard_in_reach``): each shard packs its own
    block from its own rows (:func:`_pack_block`) and the few rows at the
    block's end that its right neighbour holds (one ``ppermute`` of at
    most ``(shards - 1) * ahead`` rows; the last shard's end is zeros).
    Neither the values nor the table are ever whole anywhere: a table
    sharded because it outgrew one chip packs as it lies (FM's 187.8 M
    rows: 4.51 GB of values, 3.43 GB of table and one chunk a chip)."""
    shards, ps = spec.num_shards, spec.ps_axis

    def on_shard(values: Array) -> Array:
        values = values.reshape(-1, spec.row_width)
        ahead = spec.rows_per_shard * spec.pack - values.shape[0]
        reach = (shards - 1) * ahead
        beyond = None
        if reach:
            beyond = jax.lax.ppermute(
                values[:reach], ps, [(s + 1, s) for s in range(shards - 1)]
            )
        return _pack_block(
            spec, spec.rows_per_shard, values,
            jax.lax.axis_index(ps) * ahead, reach, beyond,
        )

    # the loop's carry starts as zeros on every shard and ends as its block
    return jax.jit(jax.shard_map(
        on_shard, mesh=spec.mesh, in_specs=P(ps), out_specs=P(ps, None),
        check_vma=False,
    ))


def _unpack_block(
    spec: StoreSpec, n: int, block: Array, skip: Any = 0, reach: int = 0,
    before: Optional[Array] = None,
) -> Array:
    """:func:`_pack_block` backwards: ``n`` logical rows out of a packed
    ``block``, the first of them ``skip`` rows BEFORE the block's first
    (``skip <= reach <= n / 2``, and only ``reach`` is static): those are
    the last of ``before``'s ``reach`` rows.  ``_PACK_CHUNK`` physical rows
    a ``fori_loop`` step, for the reason given there."""
    from ..ops.packed import unpack_table

    k, d = spec.pack, spec.row_width

    def logical(phys: Array) -> Array:
        return unpack_table(phys, phys.shape[0] * k, d)

    # physical rows whose logical rows all land inside, wherever it starts
    whole = (n - reach) // k
    out = jnp.zeros((n, d), spec.dtype)
    chunk = min(whole, _PACK_CHUNK)

    def unpack_chunk(i, out):
        # the last chunk starts early and unpacks some rows a second time
        at = jnp.minimum(i * chunk, whole - chunk)
        part = jax.lax.dynamic_slice(block, (at, 0), (chunk, block.shape[1]))
        return jax.lax.dynamic_update_slice(
            out, logical(part), (skip + at * k, 0)
        )

    if whole:
        out = jax.lax.fori_loop(0, -(-whole // chunk), unpack_chunk, out)
    if reach:
        # the first rows: the end of `before`, then the block's first
        head = jnp.concatenate(
            [before, logical(block[: -(-reach // k)])[:reach]]
        )
        head = jax.lax.dynamic_slice(head, (reach - skip, 0), (reach, d))
        out = jax.lax.dynamic_update_slice(out, head, (0, 0))
    last = n - whole * k
    if last:
        # the last rows: past the last whole chunk, wherever that ended
        first = (whole * k - reach) // k
        tail = jax.lax.dynamic_slice(
            logical(block[first: -(-n // k)]),
            (whole * k - first * k - skip, 0), (last, d),
        )
        out = jax.lax.dynamic_update_slice(out, tail, (n - last, 0))
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _unpack_rows(spec: StoreSpec, table: Array) -> Array:
    """The values (unpadded, logical) of a packed table, as one jitted
    program (:func:`_unpack_block`); they come out where the partitioner
    puts them, which is right for a table in one place."""
    vals = _unpack_block(spec, spec.capacity, table)
    return vals.reshape((spec.capacity,) + spec.value_shape)


@functools.partial(jax.jit, static_argnums=(0,))
def _unpack_rows_on_mesh(spec: StoreSpec, table: Array) -> Array:
    """The values of a packed table sharded over ``ps``
    (``_next_shard_in_reach``): they come out row-sharded over ``ps`` as a
    dense table's do, each shard unpacking its own block and the few rows
    before it that end its left neighbour's (:func:`_pack_rows_on_mesh`
    backwards); never gathered onto one chip."""
    from ..ops.packed import unpack_table

    shards, ps, k = spec.num_shards, spec.ps_axis, spec.pack
    n = spec.capacity // shards

    def on_shard(block: Array) -> Array:
        ahead = block.shape[0] * k - n
        reach = (shards - 1) * ahead
        before = None
        if reach:
            end = block[reach // -k:]  # the physical rows that hold them
            before = jax.lax.ppermute(
                unpack_table(end, end.shape[0] * k, spec.row_width)[-reach:],
                ps, [(s, s + 1) for s in range(shards - 1)],
            )
        vals = _unpack_block(
            spec, n, block, jax.lax.axis_index(ps) * ahead, reach, before
        )
        return vals.reshape((n,) + spec.value_shape)

    # as in the pack: the loop's carry starts as zeros on every shard
    return jax.shard_map(
        on_shard, mesh=spec.mesh, in_specs=P(ps, None), out_specs=P(ps),
        check_vma=False,
    )(table)


def _lives_on_mesh(spec: StoreSpec, values: Any) -> bool:
    """Whether ``values`` is a concrete array on exactly the mesh's devices:
    only then can a jitted program of that mesh take it as it lies."""
    return (
        spec.mesh is not None
        and isinstance(values, jax.Array)
        and not isinstance(values, jax.core.Tracer)
        and values.sharding.device_set == set(spec.mesh.devices.flat)
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _pad_to_tiles(values: Array, rows: int, lanes: int) -> Array:
    """``values`` (n, w) as a ``(rows, lanes)`` table, zeros past them."""
    n, w = values.shape
    return jnp.pad(values, ((0, rows - n), (0, lanes - w)))


def _placing():
    """A store's construction on the device as ``setup.store_place`` on the
    process's ledger (host time inside the call: the jitted placements are
    enqueued, not waited for), its seconds kept in
    ``setup_store_place_seconds_total`` (``telemetry/compile_ledger.setup_span``;
    the benchmark's ``setup.store_place_s``).  A ``create`` under
    ``jax.eval_shape`` or ``jax.jit`` counts the time it took to trace."""
    from ..telemetry.compile_ledger import setup_span
    from ..telemetry.registry import get_registry

    return setup_span("store_place", get_registry().counter(
        "setup_store_place_seconds_total", component="setup"
    ))


@jax.tree_util.register_pytree_node_class
class ShardedParamStore:
    """Functional bundle of (spec, table).  All mutators return new stores.

    The TPU-side equivalent of one *logical* parameter server spanning
    ``spec.num_shards`` shards (the reference's ``psParallelism``).
    """

    def __init__(self, spec: StoreSpec, table: Array):
        self.spec = spec
        self.table = table

    # -- construction -----------------------------------------------------
    @classmethod
    def create(
        cls,
        capacity: int,
        value_shape: Tuple[int, ...] = (),
        *,
        dtype: Any = jnp.float32,
        init_fn: Optional[InitFn] = None,
        update: Union[str, UpdateFn] = "add",
        mesh: Optional[Mesh] = None,
        ps_axis: str = "ps",
        layout: str = "dense",
        worker_width: Optional[int] = None,
    ) -> "ShardedParamStore":
        spec = StoreSpec(
            capacity=capacity,
            value_shape=tuple(value_shape),
            dtype=dtype,
            update=update,
            mesh=mesh,
            ps_axis=ps_axis,
            layout=_resolve_layout(layout, update, tuple(value_shape)),
            worker_width=worker_width,
        )
        with _placing():
            return cls(spec, create_table(spec, init_fn))

    @classmethod
    def from_values(
        cls,
        values: Array,
        *,
        update: Union[str, UpdateFn] = "add",
        mesh: Optional[Mesh] = None,
        ps_axis: str = "ps",
        layout: str = "dense",
    ) -> "ShardedParamStore":
        """Seed the store from an existing ``(capacity, *value_shape)``
        array — the reference's ``transformWithModelLoad`` analogue
        (SURVEY.md §5 "Checkpoint / resume")."""
        spec = StoreSpec(
            capacity=values.shape[0],
            value_shape=tuple(values.shape[1:]),
            dtype=values.dtype,
            update=update,
            mesh=mesh,
            ps_axis=ps_axis,
            layout=_resolve_layout(layout, update, tuple(values.shape[1:])),
        )
        with _placing():
            return cls(spec, cls._place(spec, values))

    @classmethod
    def from_spec_values(
        cls, spec: StoreSpec, values: Array
    ) -> "ShardedParamStore":
        """Seed a store carrying the *full* target ``spec`` (update rule,
        mesh, layout) from an unpadded ``(capacity, ...)``
        value array — the checkpoint-restore path, which must not drop
        spec fields the way a shape-inferred rebuild would."""
        with _placing():
            return cls(spec, cls._place(spec, values.astype(spec.dtype)))

    @staticmethod
    def _place(spec: StoreSpec, values: Array) -> Array:
        _preload_tile_kernel(spec)
        if spec.layout == "packed":  # either way pads its own rows
            if _lives_on_mesh(spec, values) and _next_shard_in_reach(
                spec, values.shape[0]
            ):
                return _pack_rows_on_mesh(spec)(values)
            values = _pack_rows(spec)(values)
        elif spec.tile_lanes and values.shape != spec.table_shape():
            # rows and zero lanes in one pass, the table its only output
            values = _pad_to_tiles(values, *spec.table_shape())
        else:
            pad = spec.padded_capacity - values.shape[0]
            if pad and _lives_on_mesh(spec, values):
                values = _pad_rows(spec, pad)(values)
            elif pad:
                # numpy, uncommitted, or committed elsewhere (one device,
                # another mesh): pad where the values are; ``device_put``
                # below moves them
                values = jnp.concatenate(
                    [values, jnp.zeros((pad,) + spec.value_shape, spec.dtype)]
                )
        sharding = spec.sharding()
        if sharding is not None:
            values = jax.device_put(values, sharding)
        return values

    # -- protocol ---------------------------------------------------------
    def pull(self, ids: Array) -> Array:
        return pull(self.spec, self.table, ids)

    def push(
        self, ids: Array, deltas: Array, mask: Optional[Array] = None
    ) -> "ShardedParamStore":
        return ShardedParamStore(
            self.spec, push(self.spec, self.table, ids, deltas, mask)
        )

    def values(self) -> Array:
        """Final model dump (unpadded, LOGICAL layout) — the reference's
        close()-time parameter flush (SURVEY.md §3.5)."""
        spec = self.spec
        if spec.layout == "packed":
            if spec.num_shards > 1 and _next_shard_in_reach(
                spec, spec.capacity
            ):
                return _unpack_rows_on_mesh(spec, self.table)
            return _unpack_rows(spec, self.table)
        if spec.tile_lanes > spec.row_width:
            return self.table[: spec.capacity, : spec.row_width]
        return self.table[: spec.capacity]

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.table,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, leaves):
        return cls(spec, leaves[0])


__all__ = [
    "StoreSpec",
    "ShardedParamStore",
    "create_table",
    "pull",
    "push",
    "push_counted",
    "Arms",
    "arms",
    "step_counts",
    "publish_counts",
    "zeros_init",
]
