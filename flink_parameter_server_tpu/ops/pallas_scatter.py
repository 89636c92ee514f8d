"""Pallas TPU kernel: sorted window scatter-add ("the native component").

Reference parity: SURVEY.md §7 "Hard parts" names sparse scatter-add under
skewed id distributions (Criteo, word2vec) as the rebuild's native-kernel
obligation — the role CUDA kernels would play in a GPU framework.

Algorithm (duplicate-compressing windowed read-modify-write):

  1. XLA-side, sort the (ids, deltas) batch by id — hot ids become
     contiguous *runs*.
  2. The kernel walks the sorted lanes in GROUPS OF 8 with a sequential
     TPU grid; per-lane ids sit in SMEM via scalar prefetch.  Table rows
     are read and written in aligned 8-row WINDOWS (row ``r`` lives in
     window ``r // 8`` at slot ``r % 8``): the current window's deltas
     accumulate into an (8, d) f32 register, and each unique window gets
     ONE HBM read-modify-write (async 8-row DMA in, add, DMA out).  A
     Zipf-hot id touches HBM once per microbatch instead of once per
     occurrence, and adjacent hot ids share a window — HBM traffic is
     O(unique windows) · 8 rows instead of O(batch) serialized rows.
  3. Lane placement never slices a VMEM ref at a per-lane offset (real
     Mosaic rejects sub-8-row dynamic slices).  A group's 8
     delta rows are loaded as one aligned (8, d) tile and placed into
     window slots with an 8×8 one-hot select matmul; groups that sit in
     a single window (the common case for sorted Zipf ids) take one
     matmul for all 8 lanes.
  4. Run carry state (current window + partial sums) lives in scratch
     that persists across grid steps (TPU grids execute sequentially),
     so windows spanning chunk boundaries are handled for free.

Mosaic-measured shape requirements for the compiled path (the store and
the collective plane fall back to XLA scatter — with a warning — when
they are not met; see :func:`supports_shape`):

  - flattened row width ``d`` must be a multiple of 128 (lane width:
    dynamic-offset HBM DMAs require 128-aligned minor extents),
  - table capacity must be a multiple of 8 (windows must not overrun).

``scatter_add(...)`` is the public wrapper: turns OOB/masked lanes into
zero-deltas on the last row, sorts, and invokes the kernel with
``input_output_aliases`` (the table is updated in place when the caller's
jit donates it; on an eager call the wrapper copies the table first so the
functional all-mutators-return-new-stores contract holds).  On non-TPU
backends it runs in interpreter mode (slow but exact) so the unit tests
cover the kernel logic on the CPU mesh; ``use_pallas="auto"`` in callers
picks the XLA path off-TPU instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

WINDOW = 8  # table rows per DMA window (Mosaic sublane tile)

# In-kernel lane shifting costs sub_k unrolled roll+select ops per 8-lane
# group; past this point (e.g. scalar rows, sub_k=128) the XLA-side
# pre-shift (ops.packed.lane_shift_deltas + physical ids) is cheaper
# despite its phys-width delta buffer.
MAX_INKERNEL_SUB_K = 16


def supports_shape(capacity: int, dim: int) -> bool:
    """True if the compiled kernel supports a (capacity, dim) table."""
    return dim % 128 == 0 and capacity % WINDOW == 0


def _kernel(ids_ref, deltas_ref, table_ref, out_ref,
            acc_ref, win_ref, carry_ref, sem_in, sem_out, *, chunk: int,
            sub_k: int = 1, sub_width: int = 0):
    """One grid step = one chunk of sorted lanes (chunk % 8 == 0).

    ids_ref: (N,) int32 in SMEM (scalar-prefetched, whole batch).
      With ``sub_k > 1`` (lane-packed table, ops/packed.py) these are
      sorted LOGICAL ids; id ``i`` lives in physical row ``i // sub_k``
      at lane offset ``(i % sub_k) * sub_width``.
    deltas_ref: (chunk, d) VMEM block for this grid step (table dtype).
      Packed: d is the LOGICAL width — the kernel lane-shifts each
      group's rows in-register (``sub_k`` static rolls), so the HBM
      delta buffer never pays the phys-width expansion.
    table_ref/out_ref: aliased (capacity, W) HBM table (dropped lanes
      arrive as zero-deltas on the last row, so no sentinel is needed).
    acc_ref: (8, W) VMEM — the current window's accumulated deltas
      (f32 for float tables; table dtype for integer tables, where an
      f32 round trip would drop increments past 2**24).
    win_ref: (8, W) VMEM staging window for the HBM read-modify-write.
    carry_ref: (1,) int32 SMEM — the current window index (-1 = none).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = pl.program_id(0)
    num_chunks = pl.num_programs(0)
    base = c * chunk
    table_w = win_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        carry_ref[0] = -1
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def flush(w):
        """table[w*8 : w*8+8] += acc (one 8-row RMW round trip)."""
        dma_in = pltpu.make_async_copy(
            table_ref.at[pl.ds(w * WINDOW, WINDOW)], win_ref, sem_in
        )
        dma_in.start()
        dma_in.wait()
        win_ref[:] = (
            win_ref[:].astype(acc_ref.dtype) + acc_ref[:]
        ).astype(win_ref.dtype)
        dma_out = pltpu.make_async_copy(
            win_ref, out_ref.at[pl.ds(w * WINDOW, WINDOW)], sem_out
        )
        dma_out.start()
        dma_out.wait()

    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (WINDOW, 1), 0)

    def place(G, j, s_j):
        """acc[s_j, :] += G[j, :] — static row slice + iota-mask
        broadcast (exact VPU ops; no per-lane VMEM slicing)."""
        row = G[j:j + 1, :]  # static slice of a loaded value
        sel = (slot_iota == s_j).astype(acc_ref.dtype)  # (8, 1) one-hot
        acc_ref[:] = acc_ref[:] + sel * row

    def shift_group(G, gbase):
        """Lane-shift a packed group's (8, d) logical rows to their
        (8, W) physical-lane positions: ``sub_k`` STATIC rolls selected
        by each lane's sub-row index (no dynamic lane indexing)."""
        lane8 = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
        t_col = jnp.zeros((8, 1), jnp.int32)
        for j in range(8):
            t_j = ids_ref[gbase + j] % sub_k
            t_col = t_col + jnp.where(lane8 == j, t_j, 0)
        G_pad = jnp.pad(G, ((0, 0), (0, table_w - sub_width)))
        # native lane rotate: jnp.roll lowers to lane slices, which
        # Mosaic refuses at widths under 128 (and at size 0 for tt == 0)
        out = (t_col == 0).astype(G_pad.dtype) * G_pad
        for tt in range(1, sub_k):
            sel_t = (t_col == tt).astype(G_pad.dtype)
            out = out + sel_t * pltpu.roll(G_pad, tt * sub_width, axis=1)
        return out

    def group(g, _):
        gbase = base + g * 8
        G = deltas_ref[pl.ds(g * 8, 8), :].astype(acc_ref.dtype)
        if sub_k > 1:
            G = shift_group(G, gbase)
            w_first = (ids_ref[gbase] // sub_k) // WINDOW
            w_last = (ids_ref[gbase + 7] // sub_k) // WINDOW
        else:
            w_first = ids_ref[gbase] // WINDOW
            w_last = ids_ref[gbase + 7] // WINDOW

        @pl.when(w_first == w_last)
        def _one_window():
            # the whole group lands in one window (sorted ids): one
            # flush check for all 8 lanes
            @pl.when(w_first != carry_ref[0])
            def _switch():
                @pl.when(carry_ref[0] >= 0)
                def _():
                    flush(carry_ref[0])
                acc_ref[:] = jnp.zeros_like(acc_ref)
                carry_ref[0] = w_first

            for j in range(8):
                place(G, j, (ids_ref[gbase + j] // sub_k) % WINDOW)

        @pl.when(w_first != w_last)
        def _boundary_group():
            # window boundary inside the group: place lanes one at a
            # time with flush checks (rare — at most once per window)
            for j in range(8):
                phys_j = ids_ref[gbase + j] // sub_k
                w_j = phys_j // WINDOW

                @pl.when(w_j != carry_ref[0])
                def _switch(w_j=w_j):
                    @pl.when(carry_ref[0] >= 0)
                    def _():
                        flush(carry_ref[0])
                    acc_ref[:] = jnp.zeros_like(acc_ref)
                    carry_ref[0] = w_j

                place(G, j, phys_j % WINDOW)

        return 0

    jax.lax.fori_loop(0, chunk // 8, group, 0)

    @pl.when(c == num_chunks - 1)
    def _final():
        @pl.when(carry_ref[0] >= 0)
        def _():
            flush(carry_ref[0])


def sorted_scatter_add_pallas(
    table: Array, sorted_ids: Array, sorted_deltas: Array, *,
    chunk: int = 512, interpret: bool = False,
    sub_k: int = 1, sub_width: int = 0,
) -> Array:
    """Core kernel call: ids MUST be sorted ascending and in-range;
    dropped lanes must carry zero deltas (they may alias any row).

    ``sub_k > 1``: the table is lane-PACKED (ops/packed.py) — ids are
    LOGICAL, ``sorted_deltas`` stay at the logical ``sub_width``, and
    the kernel shifts them to their lane slice in-register (the HBM
    delta buffer never pays the 128-lane expansion).

    ``input_output_aliases`` makes the kernel update the table buffer in
    place.  Under an enclosing jit that is donation-aware and safe; on an
    *eager* call the caller's concrete buffer would be invalidated, so we
    copy it first (eager pushes are the cold path — tests, notebooks)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = sorted_deltas.shape
    capacity = table.shape[0]
    if sub_k > 1:
        if sub_width != dim:
            raise ValueError(
                f"packed deltas width {dim} != sub_width {sub_width}"
            )
        if sub_k * sub_width > table.shape[1]:
            raise ValueError(
                f"sub_k {sub_k} x sub_width {sub_width} exceeds table "
                f"width {table.shape[1]}"
            )
        if sub_k > MAX_INKERNEL_SUB_K:
            raise ValueError(
                f"sub_k {sub_k} > {MAX_INKERNEL_SUB_K}: the in-kernel "
                f"shift unrolls sub_k rolls per group — pre-shift with "
                f"ops.packed.lane_shift_deltas and scatter at physical "
                f"ids instead (ShardedParamStore.push does this "
                f"automatically)"
            )
    if capacity % WINDOW != 0:
        # structural for the windowed DMA in EVERY mode: the last window
        # would overrun (interpret clamps the slice => silent corruption)
        raise ValueError(
            f"pallas scatter kernel needs capacity % {WINDOW} == 0 (the "
            f"table is read/written in {WINDOW}-row windows); got "
            f"{capacity}. Use scatter_add(), which pads, or align the "
            f"table (ShardedParamStore does)."
        )
    # The Mosaic lane constraint applies to the PHYSICAL table width (the
    # HBM DMA extent) — with sub_k > 1 the deltas stay at the narrow
    # logical width by design (shifted in-register), so gate on the table.
    hbm_width = table.shape[1] if sub_k > 1 else dim
    if not interpret and not supports_shape(capacity, hbm_width):
        raise ValueError(
            f"pallas scatter kernel needs the physical row width to be a "
            f"multiple of 128 on real Mosaic (lane alignment); got table "
            f"({capacity}, {table.shape[1]}), deltas width {dim}. Callers "
            f"should gate on supports_shape() and use the XLA scatter "
            f"path instead."
        )
    if chunk % 8 != 0:
        raise ValueError(f"chunk must be a multiple of 8, got {chunk}")

    if not isinstance(table, jax.core.Tracer):
        table = jnp.copy(table)

    n_pad = ((n + chunk - 1) // chunk) * chunk
    if n_pad != n:
        # pad with zero-deltas onto the last (logical) row (largest id
        # keeps the lanes sorted; zero delta makes them no-ops)
        last_id = capacity * sub_k - 1 if sub_k > 1 else capacity - 1
        sorted_ids = jnp.concatenate(
            [sorted_ids, jnp.full((n_pad - n,), last_id, jnp.int32)]
        )
        sorted_deltas = jnp.concatenate(
            [sorted_deltas, jnp.zeros((n_pad - n, dim), sorted_deltas.dtype)]
        )

    grid = (n_pad // chunk,)
    kernel = functools.partial(
        _kernel, chunk=chunk, sub_k=sub_k, sub_width=sub_width
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (chunk, dim), lambda c, ids: (c, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),  # table stays in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM(
                (WINDOW, table.shape[1]),
                jnp.float32
                if jnp.issubdtype(table.dtype, jnp.floating)
                else table.dtype,
            ),  # acc
            pltpu.VMEM((WINDOW, table.shape[1]), table.dtype),  # RMW window
            pltpu.SMEM((1,), jnp.int32),  # carry window index
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        grid_spec=grid_spec,
        input_output_aliases={2: 0},  # (ids, deltas, table) -> table
        interpret=interpret,
    )(sorted_ids, sorted_deltas.astype(table.dtype), table)


def scatter_add(
    table: Array,
    ids: Array,
    deltas: Array,
    mask: Optional[Array] = None,
    *,
    chunk: int = 512,
    interpret: Optional[bool] = None,
    sub_k: int = 1,
    sub_width: int = 0,
) -> Array:
    """Duplicate-compressing scatter-add: ``table[ids] += deltas``.

    Drop-in replacement for the XLA ``.at[].add`` path in
    :func:`..core.store.push` (OOB/masked lanes dropped).  Sorts by id,
    then one 8-row-window HBM read-modify-write per unique window.

    ``sub_k > 1``: ``table`` is lane-PACKED physical rows (ops/packed.py),
    ``ids`` are LOGICAL and ``deltas`` are (n, sub_width) logical rows —
    the kernel lane-shifts them in-register.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if sub_k > 1:
        capacity, dim = table.shape[0], sub_width
        logical_cap = capacity * sub_k
    else:
        capacity, dim = table.shape[0], int(np.prod(table.shape[1:]))
        logical_cap = capacity
    cap8 = ((capacity + WINDOW - 1) // WINDOW) * WINDOW
    if cap8 != capacity:
        # window-align with a pad copy (correctness path for direct
        # callers; ShardedParamStore aligns capacity at create time so
        # the store's perf path never takes this)
        padded = jnp.pad(
            table.reshape(capacity, -1), ((0, cap8 - capacity), (0, 0))
        )
        out = scatter_add(
            padded, ids, deltas, mask, chunk=chunk, interpret=interpret,
            sub_k=sub_k, sub_width=sub_width,
        )
        return out[:capacity].reshape(table.shape)
    flat_ids = ids.reshape(-1).astype(jnp.int32)
    flat_deltas = deltas.reshape(-1, dim)
    oob = (flat_ids < 0) | (flat_ids >= logical_cap)
    if mask is not None:
        oob = oob | ~mask.reshape(-1)
    # Dropped lanes become zero-deltas on the last row (no sentinel row —
    # avoiding a full-table concatenate+slice copy per push).
    work_ids = jnp.where(oob, logical_cap - 1, flat_ids)
    flat_deltas = jnp.where(
        oob[:, None], jnp.zeros_like(flat_deltas), flat_deltas
    )
    order = jnp.argsort(work_ids)
    sorted_ids = jnp.take(work_ids, order)
    sorted_deltas = jnp.take(flat_deltas, order, axis=0)
    out = sorted_scatter_add_pallas(
        table.reshape(capacity, -1), sorted_ids, sorted_deltas,
        chunk=chunk, interpret=interpret, sub_k=sub_k, sub_width=sub_width,
    )
    return out.reshape(table.shape)


__all__ = ["scatter_add", "sorted_scatter_add_pallas", "supports_shape",
           "WINDOW", "MAX_INKERNEL_SUB_K"]
