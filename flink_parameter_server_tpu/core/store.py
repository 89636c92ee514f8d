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
SURVEY.md §7 "Guiding translation").
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array
InitFn = Callable[[Array], Array]  # ids (n,) int32 -> values (n, *value_shape)
UpdateFn = Callable[[Array, Array], Array]  # (current, combined_delta) -> new

# How often layout="auto" wanted the packed layout and a mesh kept the
# table dense (one per store built so; `_resolve_layout` says why).
_PACKED_REFUSALS = 0


def packed_refusal_count() -> int:
    return _PACKED_REFUSALS


def _resolve_layout(
    layout: str,
    update: Union[str, UpdateFn],
    value_shape: Tuple[int, ...],
    num_shards: int = 1,
) -> str:
    """Resolve the table layout, validating packed-layout constraints.

    ``"auto"`` reads the row width, the update rule and the shard count:
    packed for an add-store whose rows are narrower than 128 lanes and
    whose table lies on ONE shard; dense otherwise.  A narrow dense row is
    a column of scalars across the table's tiles on the TPU, and its
    gather and scatter-add walk that column (36 and 121 ns a row for FM's
    17 lanes on the v5e, against 10 and 22 for the 128-lane physical row
    that holds seven of them; PERF.md section 6, PR 29).

    Under ``ps > 1`` a narrow add-store stays dense, warned of and counted
    (:func:`packed_refusal_count`): ``_place`` packs a table whole, where
    its values lie, before the shards get their parts (``_pack_rows``), so
    a table sharded because it is larger than one chip cannot be packed
    from values yet (ROADMAP S9).  ``create`` builds its table under the
    mesh's ``out_shardings`` and could pack there; the rule is the same
    for it on purpose, so that a store and its reload from a checkpoint
    (``from_values``) resolve to one layout."""
    if layout not in ("dense", "packed", "auto"):
        raise ValueError(
            f"layout must be 'dense', 'packed' or 'auto', got {layout!r}"
        )
    width = 1
    for s in value_shape:
        width *= int(s)
    if layout == "auto":
        if update != "add" or width >= 128:
            return "dense"
        if num_shards > 1:
            global _PACKED_REFUSALS
            _PACKED_REFUSALS += 1
            warnings.warn(
                f"layout='auto': rows of {width} lanes would be packed "
                f"{128 // width} to a 128-lane row, but the table is "
                f"sharded over ps={num_shards}; it stays dense",
                RuntimeWarning,
                stacklevel=3,
            )
            return "dense"
        return "packed"
    if layout == "packed" and update != "add":
        # the generic update path applies `update` per logical row on a
        # dense combined table — packing it would need an unpack per push
        raise ValueError(
            "layout='packed' requires update='add' (custom update "
            "functions take the dense per-row path)"
        )
    return layout


@dataclasses.dataclass(frozen=True)
class StoreSpec:
    """Static configuration of a parameter store (not a pytree leaf)."""

    capacity: int
    value_shape: Tuple[int, ...] = ()
    dtype: Any = jnp.float32
    # "add" uses the fast scatter-add path; any other callable takes the
    # generic dense-update path (see module docstring; intra-batch
    # duplicate deltas are always summed before `update` is applied).
    update: Union[str, UpdateFn] = "add"
    mesh: Optional[Mesh] = None
    ps_axis: str = "ps"
    # "dense": one logical row per physical row (the trivial layout).
    # "packed": k = 128 // row_width logical rows per 128-lane physical
    #   row (ops/packed.py) — the TPU-native layout for narrow values
    #   (MF dim 64, FM dim 17): full vector lanes on every pull/push.
    #   Requires update="add".
    layout: str = "dense"

    def __post_init__(self) -> None:
        if self.layout not in ("dense", "packed"):
            raise ValueError(
                f"layout={self.layout!r} is not one of ('dense', 'packed')"
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
    def rows_per_shard(self) -> int:
        """Per-shard PHYSICAL row count, aligned to 8 rows: the sublane
        tile of a float32 table on the TPU, so every shard's block starts
        and ends on a tile."""
        n = self.num_shards
        logical = (self.capacity + self.pack - 1) // self.pack
        per = (logical + n - 1) // n
        return ((per + 7) // 8) * 8

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
    ids = jnp.arange(spec.padded_capacity, dtype=jnp.int32)
    out_sharding = spec.sharding()

    def build(ids):
        values = init_fn(ids)
        if spec.layout == "packed":
            from ..ops.packed import pack_table

            values = pack_table(
                values.reshape(-1, spec.row_width),
                spec.rows_per_shard * spec.num_shards,
            )
        return values

    if out_sharding is not None:
        build = jax.jit(build, out_shardings=out_sharding)
    else:
        build = jax.jit(build)
    return build(ids)


def pull(spec: StoreSpec, table: Array, ids: Array) -> Array:
    """Batched pull: ``values[i] = table[ids[i]]`` (sharded gather).

    Out-of-range ids are clipped (callers use a validity mask alongside).
    Packed layout: one gather of whole 128-lane physical rows, then the
    lane slice as ``k`` static slices chosen by a ``select`` on
    ``id % k`` (no per-element gather — see ops/packed.py)."""
    ids = jnp.clip(ids.astype(jnp.int32), 0, spec.padded_capacity - 1)
    if spec.layout == "packed":
        from ..ops.packed import packed_pull

        vals = packed_pull(table, ids.reshape(-1), spec.row_width)
        return vals.reshape(ids.shape + spec.value_shape)
    return jnp.take(table, ids, axis=0)


def _phys_scatter_args(
    spec: StoreSpec, table: Array, flat_ids: Array, flat_deltas: Array
):
    """(ids, deltas) at PHYSICAL granularity for the scatter-add.

    Dense: passthrough.  Packed: lane-shift each delta row to its
    sub-row offset and divide ids down to physical rows (the sentinel
    ``padded_capacity`` divides to the out-of-range physical row, so
    ``mode="drop"`` semantics are preserved)."""
    if spec.layout != "packed":
        return flat_ids, flat_deltas
    from ..ops.packed import lane_shift_deltas, packed_phys_ids

    shifted = lane_shift_deltas(
        flat_deltas.reshape(-1, spec.row_width).astype(table.dtype),
        flat_ids,
        spec.row_width,
    )
    return packed_phys_ids(flat_ids, spec.row_width), shifted


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
    (``mode="drop"``).  ``update="add"`` is ONE XLA scatter-add, which sums
    the deltas of a row in the order the batch holds them (part of what the
    benchmark's reference checks; PERF.md section 6, PR 27 and PR 30).
    """
    vr = len(spec.value_shape)
    lead = tuple(deltas.shape[: deltas.ndim - vr])
    if (vr and tuple(deltas.shape[deltas.ndim - vr:]) != spec.value_shape) or (
        lead != tuple(ids.shape)
    ):
        raise ValueError(
            f"push deltas shape {tuple(deltas.shape)} does not match ids "
            f"shape {tuple(ids.shape)} + store value shape "
            f"{spec.value_shape}"
        )
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
    flat_deltas = deltas.reshape((-1,) + spec.value_shape)
    if mask is not None:
        flat_mask = mask.reshape(-1)
        # Masked-out lanes keep their id but carry a zero delta: for the
        # fast add path zero deltas are a no-op; for the generic path the
        # count is also masked.
        flat_deltas = jnp.where(
            flat_mask.reshape((-1,) + (1,) * len(spec.value_shape)),
            flat_deltas,
            jnp.zeros_like(flat_deltas),
        )

    if spec.update == "add":
        s_ids, s_deltas = _phys_scatter_args(
            spec, table, flat_ids, flat_deltas
        )
        return table.at[s_ids].add(s_deltas.astype(table.dtype), mode="drop")

    # Generic path: combine duplicates densely, then apply `update` once per
    # touched row.  O(capacity) per step — documented slow path; the add
    # fast path is the perf path.
    combined = jnp.zeros_like(table).at[flat_ids].add(
        flat_deltas.astype(table.dtype), mode="drop"
    )
    ones = jnp.ones(flat_ids.shape, jnp.int32)
    if mask is not None:
        ones = jnp.where(flat_mask, ones, 0)
    counts = (
        jnp.zeros((spec.padded_capacity,), jnp.int32)
        .at[flat_ids]
        .add(ones, mode="drop")
    )
    update_fn: UpdateFn = spec.update  # type: ignore[assignment]
    updated = update_fn(table, combined)
    touched = (counts > 0).reshape((-1,) + (1,) * len(spec.value_shape))
    return jnp.where(touched, updated, table)


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


# Physical rows a step of `_pack_rows`: 131,072 x 7 of FM's rows are 0.47 GB
# once the compiler has laid them 128 lanes wide.
_PACK_CHUNK = 131_072


def _pack_rows(spec: StoreSpec) -> Callable[[Array], Array]:
    """``values -> table`` for a packed table, as one jitted program that
    packs ``_PACK_CHUNK`` physical rows at a time into a zeroed table, so
    the chip holds the values, the table and one chunk.  The table comes
    out where the values lie, whole; under a mesh ``_place`` then hands it
    to the shards, so a packed table still has to fit one chip.
    To reshape ``(n, 17)`` into ``(n / 7, 119)`` the TPU compiler first
    lays the rows 128 lanes wide: for FM's 49.1 M rows all at once that is
    25 GB asked of a 16 GB chip, whether the ops are jitted together or
    run eagerly one by one (the fault PR 28 met under a mesh; PERF.md
    section 6, PR 29).  The values are not donated: no output has their
    shape to take their place."""
    from ..ops.packed import pack_table

    k, d = spec.pack, spec.row_width

    def packed(values: Array) -> Array:
        values = values.reshape(-1, d)
        whole = values.shape[0] // k  # physical rows with all k logical rows
        table = jnp.zeros(spec.table_shape(), spec.dtype)
        chunk = min(whole, _PACK_CHUNK)

        def pack_chunk(i, table):
            # the last chunk starts early and packs some rows a second time
            at = jnp.minimum(i * chunk, whole - chunk)
            rows = jax.lax.dynamic_slice(values, (at * k, 0), (chunk * k, d))
            return jax.lax.dynamic_update_slice(
                table, pack_table(rows, chunk), (at, 0)
            )

        if whole:
            table = jax.lax.fori_loop(
                0, -(-whole // chunk), pack_chunk, table
            )
        if values.shape[0] > whole * k:
            table = jax.lax.dynamic_update_slice(
                table, pack_table(values[whole * k:], 1), (whole, 0)
            )
        return table

    return jax.jit(packed)


def _lives_on_mesh(spec: StoreSpec, values: Any) -> bool:
    """Whether ``values`` is a concrete array on exactly the mesh's devices:
    only then can a jitted program of that mesh take it as it lies."""
    return (
        spec.mesh is not None
        and isinstance(values, jax.Array)
        and not isinstance(values, jax.core.Tracer)
        and values.sharding.device_set == set(spec.mesh.devices.flat)
    )


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
    ) -> "ShardedParamStore":
        spec = StoreSpec(
            capacity=capacity,
            value_shape=tuple(value_shape),
            dtype=dtype,
            update=update,
            mesh=mesh,
            ps_axis=ps_axis,
            layout=_resolve_layout(
                layout, update, tuple(value_shape),
                1 if mesh is None else mesh.shape[ps_axis],
            ),
        )
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
            layout=_resolve_layout(
                layout, update, tuple(values.shape[1:]),
                1 if mesh is None else mesh.shape[ps_axis],
            ),
        )
        return cls(spec, cls._place(spec, values))

    @classmethod
    def from_spec_values(
        cls, spec: StoreSpec, values: Array
    ) -> "ShardedParamStore":
        """Seed a store carrying the *full* target ``spec`` (update rule,
        mesh, layout) from an unpadded ``(capacity, ...)``
        value array — the checkpoint-restore path, which must not drop
        spec fields the way a shape-inferred rebuild would."""
        return cls(spec, cls._place(spec, values.astype(spec.dtype)))

    @staticmethod
    def _place(spec: StoreSpec, values: Array) -> Array:
        if spec.layout == "packed":
            values = _pack_rows(spec)(values)  # pads its own rows
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
        if self.spec.layout == "packed":
            from ..ops.packed import unpack_table

            vals = unpack_table(
                self.table, self.spec.capacity, self.spec.row_width
            )
            return vals.reshape((self.spec.capacity,) + self.spec.value_shape)
        return self.table[: self.spec.capacity]

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
    "zeros_init",
    "packed_refusal_count",
]
