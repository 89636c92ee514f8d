"""Fused MF-SGD Pallas kernel: pull + SGD + push in one pass (item side).

The compiled MF step (core/transform.make_train_step) is three XLA ops on
the item table: gather ``pulled = table[items]`` (B rows of HBM read),
SGD math, scatter-add of ``item_deltas`` (B-row read-modify-write) — plus
the ``pulled``/``deltas`` (B, d) intermediates living in HBM between
them.  For the gather/scatter-bound MF workload (SURVEY.md §6-§7: the
headline metric is bandwidth-limited), that is ~4 B-row traversals plus
2 B-row intermediates per step.

This kernel fuses the item side into ONE sorted pass (the same
sorted-run structure as ops/pallas_scatter.py): lanes arrive sorted by
item id; each *unique* item row is DMA'd in once, every lane of its run
computes ``err = r - p·q`` against that pulled snapshot and accumulates
the item delta in VMEM, and the updated row is DMA'd out once.  Per-lane
user rows stay OUTSIDE the kernel as a pre-gathered VMEM-blocked input
and the per-lane user deltas as a blocked output (XLA's vectorized
gather/scatter is the right tool for the unsorted user side — fusing it
would serialize on per-row DMA latency).  Item-side HBM traffic drops
from O(B) reads + O(B) RMW + 2 intermediates to **O(unique) RMW, no
intermediates** — under Zipf skew unique << B.

Semantics match the batched step's (same pulled snapshot per microbatch,
duplicate deltas summed, masked lanes contribute nothing, masked-lane
predictions computed against the real item row) — verified lane-for-lane
against make_train_step in tests.  Two documented divergences, both on
*invalid* lanes only: an out-of-range item id yields a prediction against
the last table row (the unfused path predicts against a clipped row), and
its lane updates no user row (the unfused path still applies the user
delta from the clipped pull).

Real-Mosaic layout (sub-8-row dynamic VMEM slices and non-128-multiple
minor dims are rejected by the hardware compiler, which interpreter mode
cannot see):
lanes are processed in GROUPS OF 8 at 8-aligned offsets, the item table
is read/written in aligned 8-row WINDOWS (item row ``r`` = window
``r // 8``, slot ``r % 8``), per-lane rows are extracted/placed with
iota masks and static value slices (never per-lane ref slicing), and
each group's outputs are written as one aligned (8, d) store.  The
compiled path requires ``d % 128 == 0`` and ``capacity % 8 == 0``
(:func:`supports_shape`); callers fall back to the unfused XLA step
otherwise.  A unique window costs ONE 8-row DMA round trip per
microbatch, so item-side HBM traffic is O(unique windows) — under Zipf
skew far below the O(batch) row traversals of the unfused step.

Status: compiled and checked against the XLA reference on a v5e by
``chip_smoke.py`` (dense d128 and packed d64); whether it wins on the
chip is not measured (ROADMAP S4).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# One measured Mosaic rule, one home: the scatter kernel module owns the
# window size and shape gate; this kernel shares them.
from .pallas_scatter import WINDOW, supports_shape  # noqa: E402


def _kernel(ids_ref, p_ref, r_ref, m_ref, table_ref,
            out_table_ref, udelta_ref, pred_ref,
            win_ref, acc_ref, carry_ref, sem_in, sem_out,
            *, chunk: int, lr: float, reg: float,
            sub_k: int = 1, sub_width: int = 0):
    """One grid step = one chunk of lanes sorted by item id (chunk % 8 == 0).

    ids_ref: (N,) int32 SMEM (scalar-prefetched) — sorted LOGICAL item
      ids.  With the packed layout (``sub_k`` > 1, ops/packed.py), item
      ``i`` lives in physical row ``i // sub_k`` at lane offset
      ``(i % sub_k) * sub_width``; the kernel windows over PHYSICAL rows
      and masks per-lane math to the item's lane slice.  ``sub_k == 1``
      is the dense layout (slice == the whole row).
    p_ref: (chunk, d) VMEM — pre-gathered user rows (f32; lane-SHIFTED
      to the item's slice when packed).
    r_ref / m_ref: (chunk, 1) VMEM — ratings / mask (f32).
    table_ref/out_table_ref: aliased (phys_capacity, d) HBM item table.
    udelta_ref: (chunk, d) VMEM out — per-lane user deltas (f32;
      lane-shifted when packed — caller unshifts).
    pred_ref: (chunk, 1) VMEM out — per-lane predictions (f32).
    win_ref: (8, d) VMEM — the current window's PULLED snapshot (table
      dtype; all lanes of a window compute against it).
    acc_ref: (8, d) f32 VMEM — the current window's item-delta sums.
    carry_ref: (1,) int32 SMEM — current window index (-1 = none).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = pl.program_id(0)
    num_chunks = pl.num_programs(0)
    base = c * chunk

    @pl.when(c == 0)
    def _init():
        carry_ref[0] = -1
        acc_ref[:] = jnp.zeros_like(acc_ref)
        win_ref[:] = jnp.zeros_like(win_ref)

    def flush(w):
        """item_table[w*8 : w*8+8] = win + acc (one RMW per window)."""
        win_ref[:] = (
            win_ref[:].astype(jnp.float32) + acc_ref[:]
        ).astype(win_ref.dtype)
        dma = pltpu.make_async_copy(
            win_ref, out_table_ref.at[pl.ds(w * WINDOW, WINDOW)], sem_out
        )
        dma.start()
        dma.wait()

    def load(w):
        """Pull window w's snapshot (before any of this batch's deltas)."""
        dma = pltpu.make_async_copy(
            table_ref.at[pl.ds(w * WINDOW, WINDOW)], win_ref, sem_in
        )
        dma.start()
        dma.wait()

    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (WINDOW, 1), 0)
    if sub_k > 1:
        lane128 = jax.lax.broadcasted_iota(
            jnp.int32, (1, win_ref.shape[1]), 1
        )

    def switch_window(w):
        @pl.when(w != carry_ref[0])
        def _():
            @pl.when(carry_ref[0] >= 0)
            def _():
                flush(carry_ref[0])
            load(w)
            acc_ref[:] = jnp.zeros_like(acc_ref)
            carry_ref[0] = w

    def lane_math(W, P, j, id_j, r_j, m_j):
        """SGD math for one lane against window snapshot W.

        Returns (pred_row, udelta_row) as (1, 1)/(1, d) values; the item
        delta is accumulated into acc at the lane's physical slot (and,
        when packed, only within its lane slice — the other sub-rows of
        the slot belong to other items).
        """
        phys = id_j // sub_k
        sel = (slot_iota == phys % WINDOW).astype(jnp.float32)  # (8, 1)
        q = jnp.sum(sel * W, axis=0, keepdims=True)   # (1, d) win[slot]
        p = P[j:j + 1, :]                             # static value slice
        # packed: p is lane-shifted to the item's slice (zero elsewhere),
        # so the dot never sees other sub-rows' lanes
        pred = jnp.sum(p * q, axis=1, keepdims=True)  # (1, 1)
        e = (m_j * lr) * (r_j - pred)                 # (1, 1)
        ud = e * q - (m_j * lr * reg) * p             # (1, d)
        idlt = e * p - (m_j * lr * reg) * q           # (1, d)
        if sub_k > 1:
            # e*q / reg*q leak outside the item's slice — mask them off
            sl = (lane128 // sub_width == id_j % sub_k).astype(jnp.float32)
            ud = sl * ud
            idlt = sl * idlt
        acc_ref[:] = acc_ref[:] + sel * idlt
        return pred, ud

    def group(g, _):
        gbase = base + g * 8
        P = p_ref[pl.ds(g * 8, 8), :]
        r_col = r_ref[pl.ds(g * 8, 8), :]
        m_col = m_ref[pl.ds(g * 8, 8), :]
        w_first = (ids_ref[gbase] // sub_k) // WINDOW
        w_last = (ids_ref[gbase + 7] // sub_k) // WINDOW
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)

        @pl.when(w_first == w_last)
        def _one_window():
            # whole group in one window (sorted ids): one flush check,
            # then all 8 lanes against the same snapshot
            switch_window(w_first)
            W = win_ref[:].astype(jnp.float32)
            UD = jnp.zeros_like(acc_ref[:])
            PRED = jnp.zeros((8, 1), jnp.float32)
            for j in range(8):
                lane_sel = (lane_iota == j).astype(jnp.float32)
                pred, ud = lane_math(
                    W, P, j, ids_ref[gbase + j],
                    r_col[j:j + 1, :], m_col[j:j + 1, :],
                )
                UD = UD + lane_sel * ud
                PRED = PRED + lane_sel * pred
            udelta_ref[pl.ds(g * 8, 8), :] = UD
            pred_ref[pl.ds(g * 8, 8), :] = PRED

        @pl.when(w_first != w_last)
        def _boundary_group():
            # window boundary inside the group: per-lane flush checks;
            # W re-read per lane because the window can change under us
            UD = jnp.zeros_like(acc_ref[:])
            PRED = jnp.zeros((8, 1), jnp.float32)
            for j in range(8):
                id_j = ids_ref[gbase + j]
                switch_window((id_j // sub_k) // WINDOW)
                lane_sel = (lane_iota == j).astype(jnp.float32)
                pred, ud = lane_math(
                    win_ref[:].astype(jnp.float32), P, j, id_j,
                    r_col[j:j + 1, :], m_col[j:j + 1, :],
                )
                UD = UD + lane_sel * ud
                PRED = PRED + lane_sel * pred
            udelta_ref[pl.ds(g * 8, 8), :] = UD
            pred_ref[pl.ds(g * 8, 8), :] = PRED

        return 0

    jax.lax.fori_loop(0, chunk // 8, group, 0)

    @pl.when(c == num_chunks - 1)
    def _final():
        @pl.when(carry_ref[0] >= 0)
        def _():
            flush(carry_ref[0])


def _sorted_fused_call(
    item_table: Array,
    s_items: Array,
    s_p: Array,
    s_r: Array,
    s_m: Array,
    *,
    learning_rate: float,
    regularization: float,
    chunk: int,
    interpret: bool,
    sub_k: int = 1,
    sub_width: int = 0,
) -> Tuple[Array, Array, Array]:
    """Kernel invocation on pre-sorted, chunk-padded lanes.

    Returns ``(new_item_table, udeltas, preds)`` in sorted lane order —
    the composable core shared by the single-shard wrapper and the
    ps-sharded shard_map wrapper."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    capacity, dim = item_table.shape
    n_pad = s_items.shape[0]
    if capacity % WINDOW != 0:
        # structural for the windowed DMA in EVERY mode: the last window
        # would overrun (interpret clamps the slice => silent corruption)
        raise ValueError(
            f"fused MF pallas kernel needs capacity % {WINDOW} == 0 (the "
            f"item table is read/written in {WINDOW}-row windows); got "
            f"{capacity}. Use fused_mf_sgd(), which pads, or align the "
            f"table (ShardedParamStore does)."
        )
    if not interpret and not supports_shape(capacity, dim):
        raise ValueError(
            f"fused MF pallas kernel needs dim % 128 == 0 on real Mosaic "
            f"(lane alignment); got item table ({capacity}, {dim}). "
            f"Callers should gate on supports_shape() and use the unfused "
            f"XLA step instead."
        )
    if chunk % 8 != 0:
        raise ValueError(f"chunk must be a multiple of 8, got {chunk}")

    if not isinstance(item_table, jax.core.Tracer):
        # eager call: aliasing would invalidate the caller's buffer
        item_table = jnp.copy(item_table)

    grid = (n_pad // chunk,)
    kernel = functools.partial(
        _kernel, chunk=chunk,
        lr=float(learning_rate), reg=float(regularization),
        sub_k=sub_k, sub_width=sub_width,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, dim), lambda c, ids: (c, 0),
                         memory_space=pltpu.VMEM),  # p
            pl.BlockSpec((chunk, 1), lambda c, ids: (c, 0),
                         memory_space=pltpu.VMEM),  # r
            pl.BlockSpec((chunk, 1), lambda c, ids: (c, 0),
                         memory_space=pltpu.VMEM),  # m
            pl.BlockSpec(memory_space=pl.ANY),  # item table (HBM)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # item table out (aliased)
            pl.BlockSpec((chunk, dim), lambda c, ids: (c, 0),
                         memory_space=pltpu.VMEM),  # user deltas
            pl.BlockSpec((chunk, 1), lambda c, ids: (c, 0),
                         memory_space=pltpu.VMEM),  # predictions
        ],
        scratch_shapes=[
            pltpu.VMEM((8, dim), item_table.dtype),  # window snapshot
            pltpu.VMEM((8, dim), jnp.float32),  # acc (window deltas)
            pltpu.SMEM((1,), jnp.int32),  # carry window index
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    s_r2 = s_r.reshape(-1, 1)
    s_m2 = s_m.reshape(-1, 1)
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(item_table.shape, item_table.dtype),
            jax.ShapeDtypeStruct((n_pad, dim), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        grid_spec=grid_spec,
        input_output_aliases={4: 0},  # (ids, p, r, m, table) -> table
        interpret=interpret,
    )(s_items, s_p, s_r2, s_m2, item_table)


def _sort_pad_lanes(
    capacity: int,
    user_table: Array,
    users: Array,
    items: Array,
    ratings: Array,
    mask: Optional[Array],
    chunk: int,
):
    """Sort lanes by item id and pad to a chunk multiple.

    Only lanes with INVALID ids are routed to the last row (they have no
    real row to read); masked-but-valid lanes keep their id so their
    returned prediction is computed against the real item row, exactly
    like the unfused path.  Deltas are zeroed via the mask either way."""
    n = items.shape[0]
    dim = user_table.shape[1]
    items = items.astype(jnp.int32)
    users = users.astype(jnp.int32)
    valid = (items >= 0) & (items < capacity)
    m = valid if mask is None else (mask & valid)
    work_items = jnp.where(valid, items, capacity - 1)

    order = jnp.argsort(work_items)
    s_items = jnp.take(work_items, order)
    s_users = jnp.take(users, order)
    s_r = jnp.take(ratings.astype(jnp.float32), order)
    s_m = jnp.take(m, order).astype(jnp.float32)
    # vectorized XLA gather for the unsorted user side (f32 compute)
    s_p = jnp.take(
        user_table, jnp.clip(s_users, 0, user_table.shape[0] - 1), axis=0
    ).astype(jnp.float32)

    n_pad = ((n + chunk - 1) // chunk) * chunk
    if n_pad != n:
        pad = n_pad - n
        s_items = jnp.concatenate(
            [s_items, jnp.full((pad,), capacity - 1, jnp.int32)]
        )
        s_users = jnp.concatenate([s_users, jnp.zeros((pad,), jnp.int32)])
        s_r = jnp.concatenate([s_r, jnp.zeros((pad,), jnp.float32)])
        s_m = jnp.concatenate([s_m, jnp.zeros((pad,), jnp.float32)])
        s_p = jnp.concatenate([s_p, jnp.zeros((pad, dim), jnp.float32)])
    return order, s_items, s_users, s_r, s_m, s_p


def fused_mf_sgd(
    user_table: Array,
    item_table: Array,
    users: Array,
    items: Array,
    ratings: Array,
    mask: Optional[Array] = None,
    *,
    learning_rate: float = 0.01,
    regularization: float = 0.0,
    chunk: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """One fused MF-SGD microbatch step (single shard).

    Returns ``(new_user_table, new_item_table, predictions)`` with
    predictions in the original lane order — semantically identical to
    the unfused gather→SGD→scatter step (same snapshot, sum-combined
    duplicates, masked lanes inert; see module docstring for the two
    invalid-lane divergences).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = items.shape[0]
    capacity = item_table.shape[0]
    cap8 = ((capacity + WINDOW - 1) // WINDOW) * WINDOW
    if cap8 != capacity:
        # window-align with a pad copy (correctness path for direct
        # callers; stores align capacity at create time).  Invalid lanes
        # are routed against the REAL last row before padding, so the
        # documented invalid-lane prediction semantics are unchanged.
        valid = (items >= 0) & (items < capacity)
        routed = jnp.where(valid, items, capacity - 1)
        padded = jnp.pad(item_table, ((0, cap8 - capacity), (0, 0)))
        new_users, new_items, pred = fused_mf_sgd(
            user_table, padded, users, routed, ratings,
            valid if mask is None else (mask & valid),
            learning_rate=learning_rate, regularization=regularization,
            chunk=chunk, interpret=interpret,
        )
        return new_users, new_items[:capacity], pred
    order, s_items, s_users, s_r, s_m, s_p = _sort_pad_lanes(
        capacity, user_table, users, items, ratings, mask, chunk
    )
    new_item_table, udeltas, preds = _sorted_fused_call(
        item_table, s_items, s_p, s_r, s_m,
        learning_rate=learning_rate, regularization=regularization,
        chunk=chunk, interpret=interpret,
    )
    # user side: vectorized XLA scatter-add of the per-lane deltas
    # (padding lanes carry zero deltas onto user row 0 — inert)
    new_user_table = user_table.at[s_users].add(
        udeltas.astype(user_table.dtype), mode="drop"
    )
    # un-permute predictions to the original lane order (scatter-based
    # inverse permutation — no second argsort)
    pred = jnp.zeros((n,), jnp.float32).at[order[:n]].set(preds[:n, 0])
    return new_user_table, new_item_table, pred


def fused_mf_sgd_packed(
    user_table: Array,
    packed_item_table: Array,
    users: Array,
    items: Array,
    ratings: Array,
    mask: Optional[Array] = None,
    *,
    capacity: int,
    dim: int,
    learning_rate: float = 0.01,
    regularization: float = 0.0,
    chunk: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """The fused step over a lane-PACKED item table (ops/packed.py) —
    the reference's native narrow dims (MF 64, FM 17) on the compiled
    kernel, which needs 128-wide rows on real Mosaic.

    ``packed_item_table``: (phys_capacity, 128·m) as built by
    ``ShardedParamStore(layout="packed")`` / ``ops.packed.pack_table``.
    ``capacity``/``dim``: the LOGICAL item count and row width.

    XLA side does the lane plumbing (both batch-sized gathers): user
    rows are pre-shifted to their item's lane slice, and the kernel's
    lane-shifted user deltas are unshifted before the user scatter.  The
    kernel itself windows over physical rows and masks its math to the
    item's slice — semantics identical to :func:`fused_mf_sgd` on the
    equivalent dense table (asserted by tests/test_pallas_mf.py).

    Returns ``(new_user_table, new_packed_item_table, predictions)``.
    """
    from .packed import lane_shift_deltas, lane_unshift, pack_k

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k = pack_k(dim)
    nphys = packed_item_table.shape[0]
    if capacity > nphys * k:
        # a mismatched capacity would route lanes past the physical
        # table — interpret mode clamps the window DMA and silently
        # corrupts, so fail loudly here, and BEFORE window-align padding
        # (padding grows the table, which would let an over-capacity
        # claim slip past this guard into the zero-filled pad rows)
        raise ValueError(
            f"capacity {capacity} exceeds the packed table's "
            f"{nphys} physical rows x k={k} = {nphys * k} logical rows"
        )
    nphys8 = ((nphys + WINDOW - 1) // WINDOW) * WINDOW
    if nphys8 != nphys:
        # window-align with a pad copy, like fused_mf_sgd does for dense
        # tables (pack_table's default phys row count is NOT 8-aligned;
        # stores align at create time)
        padded = jnp.pad(packed_item_table, ((0, nphys8 - nphys), (0, 0)))
        new_users, new_packed, pred = fused_mf_sgd_packed(
            user_table, padded, users, items, ratings, mask,
            capacity=capacity, dim=dim,
            learning_rate=learning_rate, regularization=regularization,
            chunk=chunk, interpret=interpret,
        )
        return new_users, new_packed[:nphys], pred
    n = items.shape[0]
    order, s_items, s_users, s_r, s_m, s_p = _sort_pad_lanes(
        capacity, user_table, users, items, ratings, mask, chunk
    )
    s_p_shifted = lane_shift_deltas(s_p, s_items, dim)
    new_packed, udeltas, preds = _sorted_fused_call(
        packed_item_table, s_items, s_p_shifted, s_r, s_m,
        learning_rate=learning_rate, regularization=regularization,
        chunk=chunk, interpret=interpret, sub_k=k, sub_width=dim,
    )
    # unshift the lane-shifted user deltas back to logical width
    ud = lane_unshift(udeltas, s_items, dim)
    new_user_table = user_table.at[s_users].add(
        ud.astype(user_table.dtype), mode="drop"
    )
    pred = jnp.zeros((n,), jnp.float32).at[order[:n]].set(preds[:n, 0])
    return new_user_table, new_packed, pred


def fused_mf_sgd_sharded(
    user_table: Array,
    item_table: Array,
    users: Array,
    items: Array,
    ratings: Array,
    mask: Optional[Array] = None,
    *,
    mesh,
    ps_axis: str = "ps",
    learning_rate: float = 0.01,
    regularization: float = 0.0,
    chunk: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """The fused step over a ps-sharded item table (the giant-table
    layout: table row-blocked over ``ps``, batch + user table replicated).

    Each ps shard runs the fused kernel on its local block with lanes
    outside its row range masked off; since a lane's item row lives on
    exactly one shard, per-lane user deltas and predictions are disjoint
    across shards and ONE ``psum`` over ``ps`` assembles them — there is
    no separate pull round-trip at all.  The reference's whole
    pull/push message plane for this step becomes that single collective
    (SURVEY.md §2 "TPU-native equivalent").

    dp-sharding the batch is NOT supported here: item blocks would be
    replicated over dp and the in-kernel writes would diverge across dp
    rows (the unfused/locality paths handle that case).

    Divergence from the single-shard fused step, on *invalid* lanes
    only: a globally out-of-range item id yields prediction 0.0 (no
    shard owns it), where the single-shard step predicts against the
    routed last row.  Valid lanes — masked included — are identical.
    """
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ps = mesh.shape[ps_axis]
    for ax, sz in mesh.shape.items():
        if ax != ps_axis and sz != 1:
            raise ValueError(
                f"fused sharded step supports ps-only meshes (item blocks "
                f"would be replicated over axis {ax!r} (size {sz}) and the "
                f"in-kernel writes would diverge)"
            )
    capacity, dim = item_table.shape
    if capacity % ps != 0:
        raise ValueError(
            f"item table capacity {capacity} must divide evenly over "
            f"ps={ps} shards (pad the table — ShardedParamStore does "
            f"this automatically)"
        )
    rows = capacity // ps
    n = items.shape[0]
    lr, reg = learning_rate, regularization

    def body(local_table, u_table, b_users, b_items, b_ratings, b_mask):
        ps_idx = jax.lax.axis_index(ps_axis)
        lo = ps_idx * rows
        rel = b_items.astype(jnp.int32) - lo
        hit = (rel >= 0) & (rel < rows)
        m = hit if b_mask is None else (hit & b_mask)
        order, s_items, s_users, s_r, s_m, s_p = _sort_pad_lanes(
            rows, u_table, b_users, jnp.where(hit, rel, -1), b_ratings,
            m, chunk,
        )
        rows8 = ((rows + WINDOW - 1) // WINDOW) * WINDOW
        block = (
            local_table if rows8 == rows
            else jnp.pad(local_table, ((0, rows8 - rows), (0, 0)))
        )
        new_block, udeltas, preds = _sorted_fused_call(
            block, s_items, s_p, s_r, s_m,
            learning_rate=lr, regularization=reg,
            chunk=chunk, interpret=interpret,
        )
        new_block = new_block[:rows]
        # un-permute to lane order, then assemble across shards: each
        # lane was computed on exactly its item's owning shard (zero
        # elsewhere), so one psum yields the full per-lane values
        lane_udelta = (
            jnp.zeros((n, udeltas.shape[1]), jnp.float32)
            .at[order[:n]]
            .set(udeltas[:n])
        )
        lane_pred = (
            jnp.zeros((n,), jnp.float32).at[order[:n]].set(preds[:n, 0])
        )
        # a non-owning shard computed its (routed-row) pred for foreign
        # lanes — only the owner contributes (udeltas are already zeroed
        # by the kernel mask, which includes ``hit``)
        lane_pred = jnp.where(hit, lane_pred, 0.0)
        lane_udelta = jax.lax.psum(lane_udelta, ps_axis)
        lane_pred = jax.lax.psum(lane_pred, ps_axis)
        # user table is replicated over ps; every shard applies the same
        # psum'd deltas, so it stays replicated
        new_users = u_table.at[b_users.astype(jnp.int32)].add(
            lane_udelta.astype(u_table.dtype), mode="drop"
        )
        return new_block, new_users, lane_pred

    rep = P()
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(ps_axis, None), rep, rep, rep, rep, rep),
        out_specs=(P(ps_axis, None), rep, rep),
        check_vma=False,
    )
    mask_in = (
        jnp.ones(n, bool) if mask is None else mask
    )
    new_item_table, new_user_table, pred = fn(
        item_table, user_table, users, items, ratings, mask_in
    )
    return new_user_table, new_item_table, pred


def make_fused_mf_train_step(
    *,
    learning_rate: float = 0.01,
    regularization: float = 0.0,
    chunk: int = 1024,
    interpret: Optional[bool] = None,
    layout: str = "dense",
    capacity: Optional[int] = None,
    dim: Optional[int] = None,
):
    """A drop-in alternative to ``make_train_step(OnlineMatrixFactorization,
    spec)`` for the MF flagship: same ``(table, state, batch) -> (table,
    state, out)`` signature (state = user factor table), fused item side.

    ``layout="packed"`` (with the LOGICAL ``capacity`` and ``dim``) runs
    the fused kernel on a lane-packed item table — pass the table from a
    ``ShardedParamStore(layout="packed")``."""
    if layout not in ("dense", "packed"):
        # 'auto' is a STORE-construction convenience; here the layout
        # must match the concrete table being passed — silently treating
        # an unknown value as dense would read a packed table as dense
        # rows and train garbage
        raise ValueError(
            f"layout must be 'dense' or 'packed' (matching the item "
            f"table's actual layout), got {layout!r}"
        )
    if layout == "packed" and (capacity is None or dim is None):
        raise ValueError("layout='packed' needs capacity= and dim=")

    if layout == "packed":
        fused_fn = fused_mf_sgd_packed
        layout_kwargs = {"capacity": capacity, "dim": dim}
    else:
        fused_fn = fused_mf_sgd
        layout_kwargs = {}

    def step(item_table, user_table, batch):
        mask = batch.get("mask")
        new_users, new_items, pred = fused_fn(
            user_table,
            item_table,
            batch["user"],
            batch["item"],
            batch["rating"],
            mask,
            learning_rate=learning_rate,
            regularization=regularization,
            chunk=chunk,
            interpret=interpret,
            **layout_kwargs,
        )
        m = (
            jnp.ones_like(pred)
            if mask is None
            else mask.astype(jnp.float32)
        )
        out = {
            "prediction": pred,
            "error": (batch["rating"].astype(jnp.float32) - pred) * m,
        }
        return new_items, new_users, out

    return step


__all__ = [
    "fused_mf_sgd",
    "fused_mf_sgd_packed",
    "fused_mf_sgd_sharded",
    "make_fused_mf_train_step",
    "supports_shape",
    "WINDOW",
]
