"""ops/row_update: one row write per unique id of a sorted batch, and one
read-modify-write per touched tile of eight wide rows, against a plain numpy
scatter-add (kernels interpreted on the CPU); the MF step that uses the
first against the XLA arm in stream order; who takes which arm."""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu import ShardedParamStore
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import matrix_factorization as mfm
from flink_parameter_server_tpu.ops import row_update

WIDTH = 128
CONFIG = os.path.join(
    os.path.dirname(__file__), "..", "chipbench", "configs",
    "mf-hugewiki-k128.json",
)


def numpy_scatter_add(state, ids, deltas, mask):
    out = np.array(state, np.float64)
    for k, (i, d) in enumerate(zip(ids, deltas)):
        if (mask is None or mask[k]) and 0 <= i < state.shape[0]:
            out[i] += d
    return out


def _case(name):
    """(rows, ids, mask, poison): the batch of one named case; ``poison``
    lanes carry NaN deltas (they are masked or out of range)."""
    rng = np.random.default_rng(11)
    rows, n = 1024, 1536
    ids = rng.integers(0, rows, n).astype(np.int32)
    mask = None
    poison = np.zeros(n, bool)
    if name == "hit_1_2_1000_times":
        ids = np.arange(n, dtype=np.int32) % 400 + 20  # every row once..
        ids[5] = ids[900] = 3  # ..row 3 twice
        ids[200:1200] = 7  # ..row 7 a thousand times, across blocks
        ids = rng.permutation(ids)
    elif name == "masked_lanes_carry_nan":
        mask = rng.random(n) > 0.3
        poison = ~mask
    elif name == "ids_out_of_range_dropped":
        ids[::5] = -1 - ids[::5]
        ids[1::7] = rows + ids[1::7]
        ids[3] = np.iinfo(np.int32).max
        ids[4] = np.iinfo(np.int32).min
        poison = (ids < 0) | (ids >= rows)
    elif name == "rows_not_a_multiple_of_8":
        rows = 1028
        ids = rng.integers(0, rows, n).astype(np.int32)
        ids[:4] = (1024, 1025, 1026, 1027)
    elif name == "all_masked":
        mask = np.zeros(n, bool)
        poison = ~mask
    elif name == "every_id_equal":
        ids[:] = 513
    elif name == "most_lanes_dead":
        # a ragged batch's padding: id -1 on most lanes, whole blocks and
        # (at 512 lanes a call) whole calls of nothing but dropped lanes
        ids[rng.random(n) < 0.6] = -1
        poison = ids < 0
    elif name == "batch_not_a_multiple_of_block":
        ids = ids[:1000]
        poison = poison[:1000]
    elif name == "every_lane_distinct":
        rows = 2048
        ids = rng.permutation(rows)[:n].astype(np.int32)
    elif name == "blocks_that_write_1_7_8_9_and_256":
        # sorted, six blocks of 256 lanes: one row; 7, 8 and 9 rows (the
        # last of each filling its block); a row a lane; nothing but
        # dropped lanes
        ids = np.concatenate([
            np.zeros(256), 10 + np.minimum(np.arange(256), 6),
            20 + np.minimum(np.arange(256), 7),
            30 + np.minimum(np.arange(256), 8), 100 + np.arange(256),
            np.full(256, -1),
        ]).astype(np.int32)
        poison = ids < 0
        keep = rng.permutation(n)
        ids, poison = ids[keep], poison[keep]
    elif name != "uniform_few_duplicates":
        raise AssertionError(name)
    return rows, ids, mask, poison


CASES = [
    "uniform_few_duplicates", "hit_1_2_1000_times", "masked_lanes_carry_nan",
    "ids_out_of_range_dropped", "rows_not_a_multiple_of_8", "all_masked",
    "every_id_equal", "batch_not_a_multiple_of_block", "most_lanes_dead",
]
# the cases PR 54 brought with the compacting plan (the row kernel's tests
# run them under both plans; the tile kernel's keep the list above)
ROW_CASES = CASES + [
    "every_lane_distinct", "blocks_that_write_1_7_8_9_and_256",
]


def _compact_plan_is_numpys(ids, rows, block):
    """One call's compacting plan against numpy: a block's writing lanes
    (the last of each run of a row's id) lie first in its stretch, in lane
    order, every other entry repeats the first, and the descriptors it
    issues are ``ceil(count / 8) x 8`` a block.  Returns them."""
    ids = np.asarray(ids, np.int32)
    ids = np.concatenate(
        [ids, np.full(-len(ids) % block, np.iinfo(np.int32).max, np.int32)])
    tgt, src, count, _ = (np.asarray(x) for x in row_update._plan(
        jnp.asarray(ids), rows, block, "compact"))
    last = np.concatenate([ids[1:] != ids[:-1], [True]]) & (ids < rows)
    sent = 0
    for b in range(len(ids) // block):
        at = slice(b * block, (b + 1) * block)
        lanes = np.flatnonzero(last[at])
        c = len(lanes)
        assert count[b] == c
        assert np.array_equal(src[at][:c], lanes)
        assert np.array_equal(tgt[at][:c], ids[at][lanes])
        if c:
            assert (src[at][c:] == lanes[0]).all()
            assert (tgt[at][c:] == ids[at][lanes[0]]).all()
        assert ((src[at] >= 0) & (src[at] < block)).all()
        assert ((tgt[at] >= 0) & (tgt[at] < rows)).all()
        sent += -(-c // 8) * 8
    assert int(
        row_update.descriptors(jnp.asarray(count), "compact", block)) == sent
    return sent


def _row_add_under_the_compact_plan(monkeypatch):
    """``row_add`` with its kernel under the plan for ids that repeat;
    ``issued`` collects the descriptors each call counted."""
    issued = []

    def update(*args, **kw):
        state, sent = row_update.sorted_row_update_counted(
            *args, plan="compact", **kw)
        issued.append(sent)
        return state

    monkeypatch.setattr(row_update, "sorted_row_update", update)
    return issued


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("compact", [False, True])
@pytest.mark.parametrize("name", ROW_CASES)
def test_row_add_matches_numpy_scatter_add(name, compact, block, monkeypatch):
    """The caller hands over the rows it has gathered, as the MF step does;
    a dropped lane's row and delta are garbage.  Under the compacting plan
    (a combine's: ``ops/dedup._kernel_sums``) the same table bit for bit,
    and a DMA descriptor a row written, the spare lanes of a trip of eight
    aside."""
    monkeypatch.setattr(row_update, "BLOCK", block)
    rows, ids, mask, poison = _case(name)
    rng = np.random.default_rng(5)
    state = rng.normal(size=(rows, WIDTH)).astype(np.float32)
    deltas = rng.normal(size=(ids.shape[0], WIDTH)).astype(np.float32)
    deltas[poison] = np.nan
    old = state[np.clip(ids, 0, rows - 1)]
    old[poison] = np.nan

    def run(issued=()):
        def add(s, i, o, d, m):
            table = row_update.row_add(s, i, o, d, m, interpret=True)
            return table, sum(issued, jnp.zeros((), jnp.int32))

        return jax.jit(add)(state, ids, old, deltas, mask)

    got = np.asarray(run()[0])
    if compact:
        table, sent = run(_row_add_under_the_compact_plan(monkeypatch))
        keyed, got = got, np.asarray(table)
        assert got.tobytes() == keyed.tobytes()
        sid, _ = row_update.sort_by_row(
            jnp.asarray(ids), None if mask is None else jnp.asarray(mask),
            rows)
        want_sent = _compact_plan_is_numpys(sid, rows, block)
        assert int(sent) == want_sent
        if name == "blocks_that_write_1_7_8_9_and_256" and block == 256:
            assert want_sent == 8 + 8 + 8 + 16 + 256
        live = np.ones(ids.shape, bool) if mask is None else mask
        touched = len(np.unique(ids[live & (ids >= 0) & (ids < rows)]))
        # a row whose run crosses a block is written once a call all the
        # same: only the LAST lane of a run writes
        assert touched <= want_sent <= touched + 7 * -(-len(ids) // block)
    want = numpy_scatter_add(state, ids, deltas, mask)
    assert np.isfinite(got).all()
    # float32 sums of up to 1,000 deltas against float64
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    kept = np.ones(ids.shape, bool) if mask is None else mask
    touched = np.unique(ids[kept & (ids >= 0) & (ids < rows)])
    untouched = np.setdiff1d(np.arange(rows), touched)
    assert np.array_equal(got[untouched], state[untouched])  # bit for bit


def test_eager_call_leaves_the_callers_state_alone():
    state = jnp.ones((16, WIDTH), jnp.float32)
    out = row_update.row_add(
        state, jnp.array([3, 3, 5]), jnp.ones((3, WIDTH)),
        jnp.ones((3, WIDTH)), interpret=True,
    )
    assert float(state[3, 0]) == 1.0 and float(out[3, 0]) == 3.0
    assert float(out[5, 0]) == 2.0


@pytest.mark.parametrize("compact", [False, True])
@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_a_non_finite_delta_in_a_kept_lane_stays_in_its_row(
        bad, compact, monkeypatch):
    """The XLA scatter confines a bad record to its row; so does the mask
    matmul (0 x NaN would poison the block): the row's element reads
    non-finite, its other elements and every other row are summed as ever,
    across a block boundary too (row 7 fills lanes of two blocks).  Under
    either plan."""
    if compact:
        _row_add_under_the_compact_plan(monkeypatch)
    rng = np.random.default_rng(3)
    rows, n = 64, 512
    ids = np.sort(rng.integers(0, rows, n)).astype(np.int32)
    ids[200:300] = 7
    ids = rng.permutation(ids)
    state = rng.normal(size=(rows, WIDTH)).astype(np.float32)
    deltas = rng.normal(size=(n, WIDTH)).astype(np.float32)
    lane = int(np.flatnonzero(ids == 7)[40])
    deltas[lane, 5] = bad
    got = np.array(row_update.row_add(
        state, ids, state[ids], deltas, interpret=True))
    assert not np.isfinite(got[7, 5])
    clean = deltas.copy()
    clean[lane, 5] = 0.0
    want = numpy_scatter_add(state, ids, clean, None)
    got[7, 5] = want[7, 5]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)


@pytest.mark.parametrize("row,dtype,lanes,refused", [
    ((128,), jnp.float32, 256, False),
    ((128,), jnp.float32, row_update.MAX_LANES, False),
    # a 2-D state of wider rows lies eight rows to a tile: Mosaic takes no
    # DMA of one row of it (tests/test_tpu_compile.py compiles the proof)
    ((256,), jnp.float32, 256, True), ((1, 256), jnp.float32, 256, True),
    ((64,), jnp.float32, 256, True), ((128,), jnp.bfloat16, 256, True),
    # one CALL holds MAX_LANES lanes; row_add splits a larger batch
    ((128,), jnp.float32, row_update.MAX_LANES + 256, True),
])
def test_refusal_names_what_the_kernel_cannot_take(row, dtype, lanes, refused):
    why = row_update.refusal(row, dtype)
    assert (why is not None) == (refused and lanes <= row_update.MAX_LANES)
    shape = jax.ShapeDtypeStruct  # traced only: nothing this size is made
    width = row[-1]
    args = (
        shape((8,) + row, dtype), shape((lanes,), jnp.int32),
        shape((lanes, width), dtype), shape((lanes, width), dtype),
    )
    if refused and len(row) == 1:
        with pytest.raises(ValueError, match="sorted_row_update"):
            jax.eval_shape(
                lambda *a: row_update.sorted_row_update(*a, interpret=False),
                *args)
    if lanes > row_update.MAX_LANES:
        # the same batch through row_add: two calls, no refusal
        out = jax.eval_shape(
            lambda st, i, o, d: row_update.row_add(st, i, o, d,
                                                   interpret=False), *args)
        assert out.shape == (8,) + row


@pytest.mark.parametrize("shape,dtype,refused", [
    ((64, 256), jnp.float32, False), ((64, 640), jnp.float32, False),
    ((64, 128), jnp.float32, False),
    ((64, 600), jnp.float32, True), ((64, 2, 384), jnp.float32, True),
    ((60, 256), jnp.float32, True), ((64, 256), jnp.bfloat16, True),
])
def test_tile_refusal_names_what_the_tile_kernel_cannot_take(
        shape, dtype, refused):
    why = row_update.tile_refusal(shape, dtype)
    assert (why is not None) == refused
    if refused and len(shape) == 2:
        with pytest.raises(ValueError, match="sorted_tile_add"):
            jax.eval_shape(
                lambda *a: row_update.sorted_tile_add(*a, interpret=False),
                jax.ShapeDtypeStruct(shape, dtype),
                jax.ShapeDtypeStruct((256,), jnp.int32),
                jax.ShapeDtypeStruct((256, shape[1]), dtype),
            )


# -- a batch over one call's lanes -------------------------------------------
@pytest.mark.parametrize("compact", [False, True])
@pytest.mark.parametrize("limit", [256, 512, 1024])
def test_row_add_over_the_lane_limit_equals_one_call_and_xla(
        limit, compact, monkeypatch):
    """``hit_1_2_1000_times`` has a run of a thousand lanes: at a limit of
    256 lanes a call it lies across five calls, each of which writes the
    row; the later ones read it again from the state.  Under either plan."""
    if compact:
        _row_add_under_the_compact_plan(monkeypatch)
    rows, ids, mask, _ = _case("hit_1_2_1000_times")
    rng = np.random.default_rng(7)
    state = rng.normal(size=(rows, WIDTH)).astype(np.float32)
    deltas = rng.normal(size=(ids.shape[0], WIDTH)).astype(np.float32)
    run = jax.jit(lambda s, i, d: row_update.row_add(
        s, i, jnp.take(s, i, axis=0), d, interpret=True))
    one = np.asarray(run(state, ids, deltas))
    monkeypatch.setattr(row_update, "MAX_LANES", limit)
    assert len(row_update._calls(ids, ids)) == -(-ids.shape[0] // limit)
    many = np.asarray(jax.jit(lambda s, i, d: row_update.row_add(
        s, i, jnp.take(s, i, axis=0), d, interpret=True))(state, ids, deltas))
    xla = np.asarray(jnp.asarray(state).at[ids].add(deltas))
    np.testing.assert_allclose(many, one, rtol=0, atol=2e-4)
    np.testing.assert_allclose(many, xla, rtol=0, atol=2e-4)
    np.testing.assert_allclose(
        many, numpy_scatter_add(state, ids, deltas, mask), rtol=0, atol=2e-4)


# -- the tile kernel ----------------------------------------------------------
@pytest.mark.parametrize("limit", [None, 512])
@pytest.mark.parametrize("width", [256, 384, 640])
@pytest.mark.parametrize("name", CASES)
def test_scatter_add_matches_numpy_scatter_add(
        name, width, limit, monkeypatch):
    """``table.at[ids].add(deltas, mode="drop")`` through the tile kernel,
    in one call and in calls of 512 lanes (a run of a thousand lanes and a
    tile of eight rows then lie across calls)."""
    if limit:
        monkeypatch.setattr(row_update, "MAX_LANES", limit)
    rows, ids, mask, poison = _case(name)
    rows = -(-rows // 8) * 8  # whole tiles, as a store's table is
    if mask is not None:  # the store zeroes masked lanes; so does this
        poison = poison & mask
    rng = np.random.default_rng(5)
    table = rng.normal(size=(rows, width)).astype(np.float32)
    deltas = rng.normal(size=(ids.shape[0], width)).astype(np.float32)
    if mask is not None:
        deltas[~mask] = 0.0
    deltas[poison] = np.nan  # out of range only: those lanes are dropped
    got = np.asarray(jax.jit(
        lambda t, i, d: row_update.scatter_add(t, i, d, interpret=True)
    )(table, ids, deltas))
    want = numpy_scatter_add(table, ids, deltas, None)
    assert np.isfinite(got).all()
    # one float32 rounding a lane: 1,536 of them on one row, against float64
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-4)
    touched = np.unique(ids[(ids >= 0) & (ids < rows)])
    untouched = np.setdiff1d(np.arange(rows), touched)
    assert np.array_equal(got[untouched], table[untouched])  # bit for bit
    xla = np.asarray(jnp.asarray(table).at[
        jnp.where(ids < 0, rows, ids)].add(
            jnp.where(np.isnan(deltas), 0.0, deltas), mode="drop"))
    # a row's lanes are added one by one in the order of the batch, as XLA
    # adds them: the same roundings, bit for bit
    np.testing.assert_array_equal(got, xla)


def test_eager_scatter_add_leaves_the_callers_table_alone():
    table = jnp.ones((16, 256), jnp.float32)
    out = row_update.scatter_add(
        table, jnp.array([3, 3, 5]), jnp.ones((3, 256)), interpret=True)
    assert float(table[3, 0]) == 1.0 and float(out[3, 0]) == 3.0
    assert float(out[5, 0]) == 2.0 and float(out[4, 0]) == 1.0


# The tile add kernel's walk: three blocks' tile rows in VMEM, reads a block
# ahead, a tile row that consecutive blocks share carried from slot to slot.
ADD_ROWS = 8 * 96  # 96 tile rows
ADD_CASES = [
    "a_hot_row_over_three_blocks", "a_tile_row_two_blocks_share",
    "a_tile_row_four_blocks_share", "a_block_that_opens_no_tile_row",
    "first_and_last_tile_row", "every_lane_dropped", "one_lane",
    "several_calls",
    # eight lanes side by side in one tile row are added to it as a value
    # held in registers (PR 74)
    "every_row_of_a_tile_row", "duplicates_inside_one_tile_row",
    "a_tile_row_of_a_whole_block", "dropped_lanes_at_the_end",
]


def _add_case(name, rng):
    """The kept ids of one named case in SORTED order (the batch holds them
    shuffled) and how many dropped lanes the batch carries beside them."""
    rows = ADD_ROWS
    if name == "a_hot_row_over_three_blocks":
        # sorted lanes 40-1039 are row 77: blocks 0-4 hold it, blocks 1, 2
        # and 3 nothing else (they read no tile row and write none)
        return np.concatenate([
            np.arange(40), np.full(1000, 77), np.arange(300, 500)]), 9
    if name == "a_tile_row_two_blocks_share":
        # lanes 250-261 lie in tile row 40 (rows 320-327), six a side of
        # the first block's end
        return np.concatenate([
            np.arange(250), np.repeat(np.arange(320, 326), 2),
            np.arange(400, 500)]), 0
    if name == "a_tile_row_four_blocks_share":
        # 900 lanes over the eight rows of tile row 12, from sorted lane 48
        # on: blocks 0, 1, 2 and 3
        return np.concatenate([
            np.arange(0, 400, 2), np.sort(rng.integers(96, 104, 900)),
            np.arange(500, 620)]), 5
    if name == "a_block_that_opens_no_tile_row":
        # 300 kept lanes, then a block and a half of dropped ones
        return np.sort(rng.choice(rows, 300, replace=False)), 468
    if name == "first_and_last_tile_row":
        return np.array([0, 0, 3, 7, rows - 8, rows - 1, rows - 1]), 2
    if name == "every_lane_dropped":
        return np.zeros((0,), np.int64), 300
    if name == "one_lane":
        return np.array([333]), 0
    if name == "several_calls":
        # 512 lanes a call: row 301's run and tile row 37 lie across the
        # first boundary, tile row 60 across the second
        return np.concatenate([
            np.arange(0, 500), np.full(40, 301), np.arange(302, 480),
            np.repeat(np.arange(480, 488), 60)]), 30
    if name == "every_row_of_a_tile_row":
        # tile rows 5 and 6 with each of their eight rows once, tile row 9
        # with each of its rows three times over
        return np.concatenate([
            np.arange(40, 56), np.repeat(np.arange(72, 80), 3)]), 0
    if name == "duplicates_inside_one_tile_row":
        # the batch holds rows 163, 160 and 165 of tile row 20 interleaved
        # (the caller shuffles them): each row's adds in the batch's order
        return np.concatenate([
            np.arange(0, 100, 3), np.tile([163, 160, 165, 163, 163], 9),
            np.arange(200, 260)]), 3
    if name == "a_tile_row_of_a_whole_block":
        # sorted lanes 256-511 (block 1, all of it) lie in tile row 50, and
        # so do the two lanes either side: a run of a block's every lane
        return np.concatenate([
            np.arange(0, 254), np.sort(rng.integers(400, 408, 260)),
            np.arange(500, 600)]), 0
    if name == "dropped_lanes_at_the_end":
        # 100 kept lanes and 1,180 dropped: blocks 1-4 hold nothing to add
        return np.sort(rng.integers(0, rows, 100)), 1180
    raise KeyError(name)


@pytest.mark.parametrize("width,w,name", [
    (256, 256, name) for name in ADD_CASES
] + [
    (width, width, name)
    for width in (384, 640)
    for name in ("a_hot_row_over_three_blocks", "a_tile_row_four_blocks_share",
                 "several_calls")
] + [
    # rows NARROWER than the table's (cells 5, 7 and 13: PR 57)
    (width, w, name)
    for width, w in ((640, 600), (384, 300), (640, 602))
    for name in ("a_hot_row_over_three_blocks", "a_tile_row_two_blocks_share",
                 "several_calls")
] + [
    # ONE register a row (cell 10), and the held tile row at five (PR 74)
    (width, w, name)
    for width, w in ((128, 128), (640, 600))
    for name in ("a_hot_row_over_three_blocks", "every_row_of_a_tile_row",
                 "duplicates_inside_one_tile_row",
                 "a_tile_row_of_a_whole_block", "dropped_lanes_at_the_end",
                 "one_lane")
    if (w, name) != (600, "a_hot_row_over_three_blocks")  # held above
])
def test_sorted_tile_add_is_numpys_add_at_in_batch_order_bit_for_bit(
        width, w, name, monkeypatch):
    """One float32 add a lane onto its row, in the order the batch holds
    the lanes: every bit of the table against ``np.add.at``.  Rows of ``w``
    < ``width`` lanes are added into lanes ``[0, w)``; the table's lanes
    ``[w, width)`` hold NaN and -0.0, in touched rows and untouched, and
    come back as they were."""
    rng = np.random.default_rng([width, ADD_CASES.index(name)])
    kept, dropped = _add_case(name, rng)
    ids = rng.permutation(np.concatenate([
        kept, np.full(dropped // 2, -1), np.full(dropped - dropped // 2,
                                                 ADD_ROWS + 5),
    ])).astype(np.int32)
    table = rng.normal(size=(ADD_ROWS, width)).astype(np.float32)
    table[:, w:] = np.nan  # the pad lanes, whatever they hold
    table[::3, w + 1:] = -0.0
    deltas = rng.normal(size=(len(ids), w)).astype(np.float32)
    live = (ids >= 0) & (ids < ADD_ROWS)
    deltas[~live] = np.nan  # a dropped lane's delta is never read
    if name == "several_calls":
        monkeypatch.setattr(row_update, "MAX_LANES", 512)
    sid, order = row_update.sort_by_row(jnp.asarray(ids), None, ADD_ROWS)
    calls = row_update._calls(sid, order)
    assert len(calls) == (3 if name == "several_calls" else 1)
    got = jnp.asarray(table)
    for _, s, o in calls:
        got = row_update.sorted_tile_add(
            got, s, jnp.asarray(deltas)[o], interpret=True)
    want = table.copy()
    np.add.at(want[:, :w], ids[live], deltas[live])
    assert np.asarray(got).tobytes() == want.tobytes()
    if len(kept):  # the case is what its name says
        flat = np.asarray(sid)
        blocks = [
            set(flat[lo:lo + 256][flat[lo:lo + 256] < ADD_ROWS] // 8)
            for lo in range(0, len(flat), 256)
        ]
        shared = max(
            sum(t in b for b in blocks) for t in set().union(*blocks)
        )
        assert shared >= {
            "a_hot_row_over_three_blocks": 5, "a_tile_row_two_blocks_share": 2,
            "a_tile_row_four_blocks_share": 4, "several_calls": 2,
        }.get(name, 1)
        if name == "a_block_that_opens_no_tile_row":
            assert blocks[0] and not blocks[-1]


@pytest.mark.parametrize("name", [
    "a_hot_row_over_three_blocks", "every_row_of_a_tile_row",
    "a_tile_row_of_a_whole_block", "first_and_last_tile_row",
    "a_block_that_opens_no_tile_row", "every_lane_dropped",
])
def test_the_tile_plan_counts_the_trips_that_lie_in_one_tile_row(name):
    """The plan's fourth and fifth word a block (PR 74), a bit a trip of
    eight sorted lanes: where all eight are kept and lie in ONE tile row
    (the trips whose lanes the kernel adds to a tile row held in registers;
    a block that has none is walked lane by lane as before), and where the
    trip before is such a trip too, in the same tile row (the held tile row
    stays held)."""
    rng = np.random.default_rng(ADD_CASES.index(name))
    kept, dropped = _add_case(name, rng)
    ids = np.sort(np.concatenate(
        [kept, np.full(dropped, ADD_ROWS + 5)])).astype(np.int32)
    ids = np.concatenate(
        [ids, np.full(-len(ids) % 256, np.iinfo(np.int32).max, np.int32)])
    counts = np.asarray(row_update._tile_plan(
        jnp.asarray(ids), ADD_ROWS, 256)[2]).reshape(-1, 5)
    assert not counts[:3].any() and not counts[-3:].any()  # the grid's ends
    trips = ids.reshape(-1, 32, 8)
    whole = (trips[:, :, 7] < ADD_ROWS) & (
        trips[:, :, 0] // 8 == trips[:, :, 7] // 8)
    # (in a block where at least one in `share` of the whole trips is one)
    share = row_update._RUN_TRIPS_SHARE
    whole &= share * whole.sum(axis=1, keepdims=True) >= (
        (trips < ADD_ROWS).sum(axis=(1, 2)) // 8)[:, None]
    goes_on = np.zeros_like(whole)
    goes_on[:, 1:] = whole[:, 1:] & whole[:, :-1] & (
        trips[:, 1:, 0] // 8 == trips[:, :-1, 0] // 8)

    def bits(words):
        return (words.astype(np.uint32)[:, None] >> np.arange(32)) & 1

    assert np.array_equal(bits(counts[3:-3, 3]), whole)
    assert np.array_equal(bits(counts[3:-3, 4]), goes_on)
    assert counts[3:-3, 1].tolist() == (
        ids.reshape(-1, 256) < ADD_ROWS).sum(axis=1).tolist()
    if name in ("a_hot_row_over_three_blocks", "a_tile_row_of_a_whole_block"):
        # a whole block of one tile row: every trip, the last (bit 31) too
        assert whole.sum(axis=1).max() == 32 == goes_on.sum(axis=1).max() + 1
    if name in ("first_and_last_tile_row", "a_block_that_opens_no_tile_row"):
        assert not whole.any()  # the walk by lane alone


# An add push NARROWER than its table (word2vec's 600 lanes in 640, fastText's
# 300 in 384, GloVe's 602 in 640) that takes several kernel calls permutes its
# rows once, and every call reads its blocks out of that one buffer.
LOGICAL_WIDTHS = [(640, 600), (384, 300), (640, 602), (256, 256), (128, 128)]


@pytest.mark.parametrize("W,w", LOGICAL_WIDTHS)
def test_scatter_add_takes_rows_at_their_own_width_over_several_calls(
        W, w, monkeypatch):
    """``scatter_add`` against ``np.add.at`` in the order of the stream,
    every bit of the table: lanes ``[0, w)`` of the rows a kept lane names,
    nothing else.  NaN and -0.0 planted in the table's lanes ``[w, W)`` (of
    touched rows too) and in untouched rows of touched tiles come back as
    they were, a dropped lane's row holds NaN and is never read, the batch
    takes three calls (``MAX_LANES`` cut to 512: below ``W`` the rows are
    permuted once and every call reads its blocks out of that buffer), row
    301's run lies across two blocks and across two calls."""
    monkeypatch.setattr(row_update, "MAX_LANES", 512)
    rng = np.random.default_rng([W, w])
    rows = ADD_ROWS
    table = rng.normal(size=(rows, W)).astype(np.float32)
    table[:, w:] = np.nan  # the pad lanes, whatever they hold
    table[::5, w + 1:] = -0.0
    table[7], table[9, ::3] = np.nan, -0.0  # rows no lane names
    kept = np.concatenate([
        np.arange(0, 500, 2), np.full(600, 301), np.arange(302, 480),
        np.repeat(np.arange(480, 488), 30)])
    kept = kept[(kept != 7) & (kept != 9)]
    ids = rng.permutation(np.concatenate([
        kept, np.full(17, -1), np.full(20, rows + 5)])).astype(np.int32)
    deltas = rng.normal(size=(len(ids), w)).astype(np.float32)
    live = (ids >= 0) & (ids < rows)
    deltas[~live] = np.nan
    assert -(-len(ids) // 512) == 3
    got = np.asarray(jax.jit(
        lambda t, i, d: row_update.scatter_add(t, i, d, interpret=True)
    )(table, ids, deltas))
    want = table.copy()
    np.add.at(want[:, :w], ids[live], deltas[live])
    flat = np.sort(ids[live])
    assert flat[255] == flat[256] == 301 == flat[511] == flat[512]
    assert got.tobytes() == want.tobytes()
    # the pad lanes of every row, written or not, are the table's own
    assert got[:, w:].tobytes() == table[:, w:].tobytes()


ROLLED_LIVE = {  # what of the batch a table (a shard's block) owns
    "every_lane": 1.0, "two_fifths": 0.389, "a_twentieth": 0.055,
    "under_one_call": 0.2, "no_lane": 0.0,
}


@pytest.mark.parametrize("share", list(ROLLED_LIVE))
@pytest.mark.parametrize("W,w", [(128, 128), (128, 64), (384, 300), (640, 640)])
def test_the_rolled_calls_are_the_unrolled_calls_bit_for_bit(
        W, w, share, monkeypatch):
    """``scatter_add_counted(rolled=True)``: the calls of a batch over
    ``MAX_LANES`` lanes as one kernel call in a ``while`` that ends with the
    last call holding a live lane (PR 67: what a push on the shards of a
    mesh takes, where a shard owns a part of the lanes it sorts).  Against
    the unrolled calls: every bit of the table (a run and a tile row lie
    across two calls, NaN rides in the dead lanes' rows and in the table's
    pad lanes) and both counts, whatever share of the lanes is live; the
    traced program holds ONE kernel call where the unrolled form holds one
    a call."""
    monkeypatch.setattr(row_update, "MAX_LANES", 512)
    rng = np.random.default_rng([W, w, list(ROLLED_LIVE).index(share)])
    rows, n = ADD_ROWS, 2_304  # five calls of 512 lanes (the last padded)
    live_n = int(round(ROLLED_LIVE[share] * n))
    table = rng.normal(size=(rows, W)).astype(np.float32)
    table[:, w:] = np.nan
    kept = rng.integers(0, rows, live_n)
    kept[: live_n // 3] = 301  # a run across blocks, and across calls
    dead = rng.choice([-1, rows, rows + 9, 2**31 - 1], n - live_n)
    ids = rng.permutation(np.concatenate([kept, dead])).astype(np.int32)
    deltas = rng.normal(size=(n, w)).astype(np.float32)
    live = (ids >= 0) & (ids < rows)
    deltas[~live] = np.nan
    assert len(row_update._calls(jnp.zeros(n + 256, jnp.int32), ids)) == 5

    def push(rolled):
        return jax.jit(lambda t, i, d: row_update.scatter_add_counted(
            t, i, d, interpret=True, rolled=rolled))

    one, lanes, tile_rows = push(True)(table, ids, deltas)
    want, want_lanes, want_tile_rows = push(False)(table, ids, deltas)
    assert np.asarray(one).tobytes() == np.asarray(want).tobytes()
    assert int(lanes) == int(want_lanes) == live.sum()
    assert int(tile_rows) == int(want_tile_rows)
    by_numpy = table.copy()
    np.add.at(by_numpy[:, :w], ids[live], deltas[live])
    assert np.asarray(one).tobytes() == by_numpy.tobytes()
    if share == "no_lane":  # the loop's body never ran
        assert int(tile_rows) == 0
    text = {r: str(jax.make_jaxpr(lambda t, i, d: row_update.scatter_add_counted(
        t, i, d, interpret=False, rolled=r))(table, ids, deltas))
        for r in (True, False)}
    assert text[True].count("pallas_call[") == 1
    assert text[False].count("pallas_call[") == 5
    # eager, the rolled form is one jitted program like the other
    eager = row_update.scatter_add_counted(
        jnp.asarray(table), ids, deltas, interpret=True, rolled=True)
    assert np.asarray(eager[0]).tobytes() == by_numpy.tobytes()


def test_a_batch_of_one_call_is_not_rolled():
    args = (jnp.zeros((64, 128)), jnp.arange(40, dtype=jnp.int32),
            jnp.ones((40, 128)))
    text = [str(jax.make_jaxpr(lambda t, i, d: row_update.scatter_add_counted(
        t, i, d, interpret=False, rolled=r))(*args)) for r in (True, False)]
    assert text[0] == text[1] and text[0].count("pallas_call[") == 1


def test_the_tile_kernel_refuses_rows_wider_than_the_table():
    for fn in (row_update.sorted_tile_add, row_update.sorted_tile_assign):
        with pytest.raises(ValueError, match=r"rows of shape \(384,\) for a table"):
            fn(jnp.zeros((64, 256), jnp.float32), jnp.zeros((8,), jnp.int32),
               jnp.zeros((8, 384), jnp.float32), interpret=True)


# -- the MF step --------------------------------------------------------------
def _mf_step_run(arm, cfg, batches):
    dry = cfg["dry_run"]
    logic = mfm.OnlineMatrixFactorization(
        dry["num_users"], cfg["dim"],
        updater=mfm.SGDUpdater(float(cfg["learning_rate"])),
        init_low=-cfg["init_scale"], init_high=cfg["init_scale"],
        state_scatter=arm,
    )
    store = ShardedParamStore.from_values(
        jnp.asarray(np.random.default_rng(1).normal(
            size=(dry["num_items"], cfg["dim"])) * cfg["init_scale"],
            jnp.float32)
    )
    step = jax.jit(make_train_step(logic, store.spec))
    table, state = store.table, logic.init_state(jax.random.PRNGKey(0))
    first = (np.asarray(table), np.asarray(state))
    outs = []
    for batch in batches:
        table, state, out = step(table, state, batch)
        outs.append({k: np.asarray(v) for k, v in out.items()})
    return first, (np.asarray(table), np.asarray(state)), outs


def test_mf_step_sorted_rows_matches_xla_in_stream_order():
    """The new arm against ``state_scatter="xla"`` through make_train_step at
    the configuration's dry-run sizes: table, state and both per-record
    outputs (stream order), within the configuration's own allowance."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    dry, ref = cfg["dry_run"], cfg["reference"]
    rng = np.random.default_rng(2)
    batches = []
    for _ in range(ref["batches"]):
        users = rng.integers(0, dry["num_users"], dry["batch"])
        users[:40] = users[40]  # a user hit 41 times
        mask = np.ones(dry["batch"], bool)
        mask[-17:] = False
        batches.append({
            "user": jnp.asarray(users, jnp.int32),
            "item": jnp.asarray(
                (rng.zipf(1.2, dry["batch"]) - 1) % dry["num_items"], jnp.int32),
            "rating": jnp.asarray(rng.normal(size=dry["batch"]), jnp.float32),
            "mask": jnp.asarray(mask),
        })
    first, (t_x, s_x), o_x = _mf_step_run("xla", cfg, batches)
    _, (t_r, s_r), o_r = _mf_step_run("sorted_rows", cfg, batches)
    assert np.abs(s_x - first[1]).max() > 0  # the batches did change rows
    for got, want, was in ((t_r, t_x, first[0]), (s_r, s_x, first[1])):
        # a bound on the reference's summed |delta| an element: its net
        # change is no larger, so this allowance is no looser than the
        # configuration's
        allowed = (
            ref["delta_rtol"] * np.abs(want - was) + ref["delta_atol"]
            + ref["row_ulps"] * 2.0 ** -23 * np.abs(want)
        )
        assert (np.abs(got - want) <= allowed).all()
    for a, b in zip(o_r, o_x):
        np.testing.assert_allclose(a["prediction"], b["prediction"],
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(a["error"], b["error"], rtol=1e-5, atol=1e-6)
        assert (a["error"][-17:] == 0).all()


def _arm(monkeypatch, backend, dim, dtype=jnp.float32, mesh=None, **kw):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    logic = mfm.OnlineMatrixFactorization(64, dim, dtype=dtype, mesh=mesh, **kw)
    return logic, logic.state_update_arm(
        jax.ShapeDtypeStruct((64, dim), dtype))


@pytest.mark.parametrize("backend,dim,dtype,pinned,want", [
    ("tpu", 128, jnp.float32, None, "sorted_rows"),
    ("cpu", 256, jnp.float32, None, "xla"),
    ("cpu", 128, jnp.float32, None, "xla"),
    ("tpu", 128, jnp.float32, "xla", "xla"),
    ("cpu", 64, jnp.float32, "sorted_rows", "sorted_rows"),
])
def test_state_update_arm_is_read_from_what_the_step_sees(
        monkeypatch, backend, dim, dtype, pinned, want):
    n0 = row_update.refusal_count()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _, arm = _arm(monkeypatch, backend, dim, dtype, state_scatter=pinned)
    assert arm == want
    assert row_update.refusal_count() == n0


@pytest.mark.parametrize("dim,dtype,reason", [
    (64, jnp.float32, "(64,)"),
    (128, jnp.bfloat16, "bfloat16"),
    # it chose the kernel before PR 33, which Mosaic refuses at 256 lanes
    (256, jnp.float32, "(256,)"),
])
def test_refused_state_shape_on_tpu_warns_once_and_counts(
        monkeypatch, dim, dtype, reason):
    n0 = row_update.refusal_count()
    with pytest.warns(RuntimeWarning, match="falling back") as caught:
        logic, arm = _arm(monkeypatch, "tpu", dim, dtype)
    assert arm == "xla" and reason in str(caught[0].message)
    assert row_update.refusal_count() == n0 + 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the second trace is silent
        assert logic.state_update_arm(
            jax.ShapeDtypeStruct((64, dim), dtype)) == "xla"
    assert row_update.refusal_count() == n0 + 1


@pytest.mark.parametrize("shape,want", [
    ((1, 4), "xla"),          # one worker: GSPMD partitions the XLA scatter
    ((2, 2), "sorted_rows"),  # keyed workers: each its own block, the kernel
    ((4, 1), "sorted_rows"),
])
def test_under_a_mesh_keyed_workers_take_the_kernel_one_worker_keeps_xla(
        monkeypatch, shape, want):
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(worker_parallelism=shape[0], ps_parallelism=shape[1],
                     devices=jax.devices()[:4])
    n0 = row_update.refusal_count()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _, arm = _arm(monkeypatch, "tpu", 128, mesh=mesh)
    assert arm == want and row_update.refusal_count() == n0


@pytest.mark.parametrize("backend,dim,pinned,started", [
    ("tpu", 128, None, 1), ("cpu", 128, None, 0), ("tpu", 64, None, 0),
    ("tpu", 128, "xla", 0),
])
def test_a_logic_that_will_trace_the_kernel_starts_the_pallas_import(
        monkeypatch, backend, dim, pinned, started):
    calls = []
    monkeypatch.setattr(row_update, "preload", lambda: calls.append(1))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mfm.OnlineMatrixFactorization(64, dim, state_scatter=pinned)
    assert len(calls) == started


# -- the store's arm -----------------------------------------------------------
def _spec(shape, dtype=jnp.float32, update="add", mesh=None, layout="auto",
          capacity=61):
    from flink_parameter_server_tpu.core import store as store_mod

    return store_mod.StoreSpec(
        capacity=capacity, value_shape=shape, dtype=dtype, update=update,
        mesh=mesh, layout=store_mod._resolve_layout(layout, update, shape),
    )


# a one-register table of 40,000 physical rows: 5,000 lanes times 8 are its
# rows, the TPU compiler's cut (``core/store._SERIAL_SCATTER_ROWS_A_LANE``)
LONG = 40_000


@pytest.mark.parametrize("backend,meshed,shape,update,capacity,lanes,want", [
    ("tpu", False, (640,), "add", 61, None, True),
    ("tpu", False, (2, 300), "add", 61, None, True),  # held flat in 640 lanes
    ("tpu", False, (600,), "add", 61, None, True),
    ("tpu", False, (256,), "add", 61, None, True),
    ("tpu", False, (256,), "add", 61, 7, True),  # wide rows: whatever the batch
    ("tpu", False, (256,), "add", 61, 10 ** 6, True),
    # one register: the kernel where the TPU compiler's scatter-add is serial
    ("tpu", False, (128,), "add", 61, None, False),  # no batch is under the cut
    ("tpu", False, (128,), "add", 61, 96, False),
    ("tpu", False, (128,), "add", LONG, None, True),  # some batch may be
    ("tpu", False, (128,), "add", LONG, 5000, True),  # lanes x 8 = rows
    ("tpu", False, (128,), "add", LONG, 4999, True),
    ("tpu", False, (128,), "add", LONG, 5001, False),  # XLA sorts: 13-22 ns
    ("tpu", False, (128,), "add", LONG, 65_536, False),
    ("tpu", False, (128,), "add", 2 * LONG, 1024, True),  # the floor in lanes
    ("tpu", False, (128,), "add", 2 * LONG, 1023, False),
    ("tpu", False, (128,), "add", 2 * LONG, 8, False),  # an eager push of a few
    ("tpu", False, (64,), "add", 2 * LONG, 5000, True),  # two to a register
    ("tpu", False, (64,), "add", 2 * LONG - 1, 5000, True),  # odd capacity
    ("tpu", False, (64,), "add", 2 * LONG, 5001, False),
    ("tpu", False, (17,), "add", 7 * LONG, 5000, True),  # seven to a register
    ("tpu", False, (17,), "add", 7 * LONG, 5001, False),
    ("cpu", False, (128,), "add", LONG, 5000, False),
    ("tpu", True, (128,), "add", LONG, 5000, False),  # GSPMD's scatter
    ("tpu", False, (128,), lambda cur, new: new, LONG, 5000, False),
    ("tpu", False, (100,), "add", 61, None, False),
    ("tpu", False, (17,), "add", 61, None, False),  # seven to a 128-lane row
    ("tpu", False, (), "add", 61, None, False),
    ("tpu", False, (), "add", 128 * LONG, 5000, True),  # 128 scalars to one
    ("tpu", False, (), "add", 128 * LONG, 5001, False),
    ("cpu", False, (640,), "add", 61, None, False),
    ("tpu", True, (640,), "add", 61, None, False),
    ("tpu", False, (640,), lambda cur, new: new, 61, None, False),
])
def test_push_takes_the_tile_kernel_from_what_the_spec_and_the_batch_hold(
        monkeypatch, backend, meshed, shape, update, capacity, lanes, want):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(worker_parallelism=2, ps_parallelism=2,
                     devices=jax.devices()[:4]) if meshed else None
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    n0 = row_update.refusal_count()
    spec = _spec(shape, update=update, mesh=mesh, capacity=capacity)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        arm = store_mod.arms(spec, push_lanes=lanes)
    assert (arm.push == "tile_add") == want
    assert arm.push == ("rule" if update != "add" else arm.push)
    assert row_update.refusal_count() == n0


@pytest.mark.parametrize("shape,dtype,layout,capacity,lanes,reason", [
    ((640,), jnp.bfloat16, "auto", 61, None, "bfloat16"),
    ((2, 384), jnp.float32, "dense", 61, None, "(2, 384)"),
    ((600,), jnp.float32, "dense", 61, None, "(600,)"),
    # one register under the compiler's cut, which the kernel would take
    ((128,), jnp.bfloat16, "auto", LONG, 5000, "bfloat16"),
    ((64,), jnp.bfloat16, "auto", 2 * LONG, 5000, "bfloat16"),
])
def test_a_store_the_tile_kernel_refuses_warns_once_and_counts(
        monkeypatch, shape, dtype, layout, capacity, lanes, reason):
    from flink_parameter_server_tpu.core import store as store_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    spec = _spec(shape, dtype, layout=layout, capacity=capacity)
    # (the record is read whole: two bfloat16 rows to a register are also
    # refused the lane shift of their push, noted once as the tile add is)
    noted = 1 + (spec.pack > 1)
    n0 = row_update.refusal_count()
    with pytest.warns(RuntimeWarning, match="falling back") as caught:
        assert store_mod.arms(spec, push_lanes=lanes).push == "xla_add"
    assert len(caught) == noted and reason in str(caught[0].message)
    assert "push into a table of" in str(caught[0].message)
    assert row_update.refusal_count() == n0 + noted
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # every later trace is silent
        assert store_mod.arms(spec, push_lanes=lanes).push == "xla_add"
        if lanes is not None:
            # above the cut XLA's own sorted form takes it: nothing refused
            assert store_mod.arms(
                spec, push_lanes=lanes + 1).push == "xla_add"
    assert row_update.refusal_count() == n0 + noted


@pytest.mark.parametrize("backend,shape,rows,started", [
    ("tpu", (2, 300), 16, 1), ("tpu", (128,), 16, 0), ("cpu", (2, 300), 16, 0),
    # a one-register table long enough for some batch to lie under the cut
    ("tpu", (128,), 8 * 1024, 1), ("tpu", (128,), 8 * 1024 - 8, 0),
    ("cpu", (128,), 8 * 1024, 0),
])
def test_a_store_whose_pushes_will_trace_the_kernel_starts_the_pallas_import(
        monkeypatch, backend, shape, rows, started):
    calls = []
    monkeypatch.setattr(row_update, "preload", lambda: calls.append(1))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    ShardedParamStore.create(rows, shape, layout="auto")
    assert len(calls) == started
    ShardedParamStore.from_values(jnp.zeros((rows,) + shape), layout="auto")
    assert len(calls) == 2 * started


# -- a rule's narrow rows: the set kernel --------------------------------------
SET_ROWS = 128 * 24  # 24 tiles
SET_CASES = [
    "sparse", "dense", "a_tile_two_blocks_share", "first_and_last_tile",
    "every_lane_of_a_tile", "all_dropped", "empty", "several_calls",
]


def _set_case(name, rng):
    """The kept ids of one named case (ascending, distinct) and how many
    dropped lanes follow them."""
    rows = SET_ROWS
    if name == "sparse":  # a lane or two a tile
        return np.sort(rng.choice(rows, 40, replace=False)), 7
    if name == "dense":  # 700 of 3,072 rows: three blocks of lanes
        return np.sort(rng.choice(rows, 700, replace=False)), 30
    if name == "a_tile_two_blocks_share":
        # lanes 0-255 are rows 64-319: the tile of rows 256-383 begins in
        # the first block of lanes and ends in the second
        return np.arange(64, 64 + 600), 0
    if name == "first_and_last_tile":
        return np.array([0, 5, 127, rows - 128, rows - 2, rows - 1]), 3
    if name == "every_lane_of_a_tile":
        return np.concatenate([[3], np.arange(256, 384), [900, 901]]), 1
    if name == "all_dropped":
        return np.zeros((0,), np.int64), 300
    if name == "empty":
        return np.zeros((0,), np.int64), 0
    if name == "several_calls":  # and a tile that two calls share
        return np.arange(100, 100 + 1500), 36
    raise KeyError(name)


@pytest.mark.parametrize("lanes,width,name", [
    (4, 3, name) for name in SET_CASES
] + [
    (lanes, width, name)
    for lanes, width in [(1, 1), (2, 2), (4, 4), (8, 5), (8, 8)]
    for name in ("dense", "a_tile_two_blocks_share")
])
def test_sorted_tile_set_is_xlas_row_set_bit_for_bit(
        lanes, width, name, monkeypatch):
    """``table.at[ids].set(new, mode="drop")`` with the pad lanes zeroed,
    every bit of the table, and the tiles it moved."""
    rng = np.random.default_rng([lanes, width, SET_CASES.index(name)])
    kept, dropped = _set_case(name, rng)
    ids = np.concatenate([kept, np.full(dropped, SET_ROWS)]).astype(np.int32)
    table = rng.normal(size=(SET_ROWS, lanes)).astype(np.float32)
    table[:, width:] = 0
    new = rng.normal(size=(len(ids), width)).astype(np.float32)
    new[len(kept):] = np.nan  # a dropped lane's values are never read
    calls = []
    if name == "several_calls":
        import jax.experimental.pallas as pl

        # 512 lanes' scalars a call: three calls of two blocks
        monkeypatch.setattr(
            row_update, "_SET_SMEM_WORDS", 512 * (2 + width))
        real = pl.pallas_call
        monkeypatch.setattr(
            pl, "pallas_call",
            lambda *a, **kw: calls.append(kw["grid_spec"].grid) or real(*a, **kw),
        )
    # (a span of one tile: the one-tile copies of PR 35)
    monkeypatch.setattr(row_update, "set_span", lambda tiles, lanes: 1)
    got, counted = row_update.sorted_tile_set(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(new)
    )
    want = jnp.asarray(table).at[ids].set(
        jnp.pad(jnp.asarray(new), ((0, 0), (0, lanes - width))), mode="drop"
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    tiles = int(counted.touched)
    assert tiles == int(counted.copies) == int(counted.moved)
    if name == "several_calls":
        # 1,536 lanes: one kernel of two blocks, called on three stretches
        assert calls == [(2,)], calls
        size = 512
        assert tiles == sum(
            len(np.unique(kept[lo:lo + size] // 128))
            for lo in range(0, len(kept), size)
        )
    else:
        assert tiles == len(np.unique(kept // 128))


# -- spans: side-by-side tiles in one copy (PR 72) ------------------------------
SPAN_TILES = 37  # two whole groups of 16 and five tiles over: ragged at 4, 8, 16
SPAN_CASES = [
    "every_tile_touched", "every_row", "alternating_tiles", "one_tile",
    "one_row", "the_last_ragged_tiles", "both_ends_of_a_span",
    "padded_lanes", "a_span_many_blocks_share", "sparse",
    "a_tile_apart_from_a_touched_group", "all_dropped", "several_calls",
]


def _span_case(name, span, rng):
    """The kept ids of one named case (ascending, distinct) and how many
    dropped lanes follow them."""
    rows, group = SPAN_TILES * 128, span * 128
    if name == "every_tile_touched":  # two rows a tile
        return np.sort(np.concatenate(
            [np.arange(SPAN_TILES) * 128 + r for r in (3, 77)])), 2
    if name == "every_row":  # a group's lanes over many blocks of 256
        return np.arange(rows), 0
    if name == "alternating_tiles":  # a span bridges the tiles between
        return np.sort(np.concatenate(
            [np.arange(0, SPAN_TILES, 2) * 128 + r for r in (0, 64, 127)])), 5
    if name == "one_tile":
        return 128 * 9 + np.array([0, 1, 17, 126, 127]), 3
    if name == "one_row":
        return np.array([128 * 20 + 5]), 0
    if name == "the_last_ragged_tiles":
        # the last whole group and the tiles after it, which no span holds
        whole = SPAN_TILES // span * span
        return np.sort(rng.choice(
            np.arange((whole - span) * 128, rows), 500, replace=False)), 12
    if name == "both_ends_of_a_span":  # a group's first row and its last
        return np.sort(np.concatenate(
            [[g * group, (g + 1) * group - 1]
             for g in range(SPAN_TILES // span)])), 1
    if name == "padded_lanes":  # a few kept lanes in front of many dropped
        return np.sort(rng.choice(rows, 90, replace=False)), 600
    if name == "a_span_many_blocks_share":
        return np.arange(100, 100 + group + group // 2), 36
    if name == "sparse":
        return np.sort(rng.choice(rows, 40, replace=False)), 7
    if name == "a_tile_apart_from_a_touched_group":
        # one touched tile in the first group, two in the second
        return np.array([5, group + 1, group + 128 * (span - 1) + 9]), 0
    if name == "all_dropped":
        return np.zeros((0,), np.int64), 300
    if name == "several_calls":  # and a group that two calls share
        return np.arange(100, 100 + 1500), 36
    raise KeyError(name)


def _copies_of(kept, span, tiles):
    """What the plan must count for one call's kept ids: the touched tiles,
    the copies (a whole group the ids touch in two tiles or more is one, any
    other touched tile one) and the tiles those move."""
    touched = np.unique(kept // 128)
    groups, counts = np.unique(touched // span, return_counts=True)
    spanned = (counts >= 2) & (groups < tiles // span)
    copies = int(spanned.sum() + counts[~spanned].sum())
    return len(touched), copies, int(span * spanned.sum() + counts[~spanned].sum())


@pytest.mark.parametrize("lanes,width,span,name", [
    (4, 3, span, name) for span in (4, 8, 16) for name in SPAN_CASES
] + [
    (lanes, width, 8, name)
    for lanes, width in [(1, 1), (2, 2), (8, 5), (8, 8)]
    for name in ("every_row", "alternating_tiles")
] + [(4, 3, 32, "every_row"), (4, 3, 2, "alternating_tiles")])
def test_sorted_tile_set_with_spans_is_the_one_tile_plan_bit_for_bit(
        lanes, width, span, name, monkeypatch):
    """Aligned groups of ``span`` tiles copied whole where the ids touch two
    of their tiles or more: every bit of the table is the one-tile plan's
    and ``table.at[ids].set(new)``'s, the untouched tiles inside a span come
    back as they were, and the counts are what the plan issued."""
    rng = np.random.default_rng([lanes, width, span, SPAN_CASES.index(name)])
    rows = SPAN_TILES * 128
    kept, dropped = _span_case(name, span, rng)
    ids = np.concatenate([kept, np.full(dropped, rows)]).astype(np.int32)
    table = rng.normal(size=(rows, lanes)).astype(np.float32)
    table[:, width:] = 0
    table[7, 0], table[rows - 1, 0] = np.nan, -0.0  # carried as they are
    new = rng.normal(size=(len(ids), width)).astype(np.float32)
    new[len(kept):] = np.nan  # a dropped lane's values are never read
    size = len(ids)
    if name == "several_calls":  # 512 lanes' scalars a call
        monkeypatch.setattr(row_update, "_SET_SMEM_WORDS", 512 * (2 + width))
        size = 512
    args = jnp.asarray(table), jnp.asarray(ids), jnp.asarray(new)
    monkeypatch.setattr(row_update, "set_span", lambda tiles, lanes: span)
    got, counted = row_update.sorted_tile_set(*args)
    monkeypatch.setattr(row_update, "set_span", lambda tiles, lanes: 1)
    one_tile, _ = row_update.sorted_tile_set(*args)
    want = args[0].at[ids].set(
        jnp.pad(args[2], ((0, 0), (0, lanes - width))), mode="drop")
    assert np.asarray(got).tobytes() == np.asarray(one_tile).tobytes()
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    by_call = [_copies_of(kept[lo:lo + size], span, SPAN_TILES)
               for lo in range(0, max(len(kept), 1), size)]
    assert tuple(int(c) for c in counted) == tuple(
        int(x) for x in np.sum(by_call, axis=0))
    if name in ("every_tile_touched", "every_row"):
        # one descriptor each way for a whole group, the ragged tiles alone
        whole = SPAN_TILES // span
        assert int(counted.copies) == whole + SPAN_TILES - whole * span
    if name == "alternating_tiles":  # the tiles between are moved too
        # (at a span of two no group holds two even tiles)
        assert (int(counted.moved) > int(counted.touched)) == (span > 2)


@pytest.mark.parametrize("tiles,lanes,want", [
    (212_992, 851_968, 32),    # cell 17: four lanes a tile
    (1_466_933, 1_277_952, 8),  # cell 6: 0.87
    (1_466_933, 32_768, 1),    # a short push into a long table
    (8, 100_000, 8),           # no wider than the table
    (1000, 125, 1), (1000, 180, 2), (1000, 0, 1),
])
def test_the_span_is_read_from_the_tables_tiles_and_the_pushs_lanes(
        tiles, lanes, want):
    assert row_update.set_span(tiles, lanes) == want


def test_a_call_says_how_many_lanes_its_push_has(monkeypatch):
    """``of``: a chunk of a push is judged by the push's lanes, a bare call
    by its own."""
    seen = []
    monkeypatch.setattr(
        row_update, "set_span",
        lambda tiles, lanes: seen.append((tiles, lanes)) or 1)
    table = jnp.zeros((1024, 4), jnp.float32)
    ids, new = jnp.arange(40, dtype=jnp.int32), jnp.ones((40, 3))
    row_update.sorted_tile_set(table, ids, new)
    row_update.sorted_tile_set(table, ids, new, of=5000)
    assert seen == [(8, 40), (8, 5000)]


ROW_SET_CASES = [
    "sorted_distinct", "dropped_between_the_kept", "every_lane_dropped",
    "a_block_that_writes_nothing", "not_whole_blocks", "one_lane",
    "nan_inf_and_minus_zero", "unsorted_distinct",
    "dropped_at_the_end", "blocks_that_write_1_7_8_9_and_256",
]


@pytest.mark.parametrize("name", ROW_SET_CASES)
def test_sorted_row_set_is_xlas_row_set_bit_for_bit(name):
    """``state.at[ids].set(rows, mode="drop")`` for distinct kept ids, the
    lanes to drop wherever they lie: every bit of the state (the kernel
    copies, it does no arithmetic)."""
    rng = np.random.default_rng(ROW_SET_CASES.index(name))
    rows_n, n = 1000, 700
    ids = np.sort(rng.choice(rows_n, n, replace=False)).astype(np.int32)
    if name == "dropped_between_the_kept":
        ids[rng.random(n) < 0.3] = rows_n  # as a packed rule store's are
    elif name == "every_lane_dropped":
        ids[:] = rows_n + 5
    elif name == "a_block_that_writes_nothing":
        ids[256:512] = rows_n
    elif name == "not_whole_blocks":
        ids, n = ids[:300], 300
    elif name == "one_lane":
        ids, n = ids[:1], 1
    elif name == "unsorted_distinct":
        ids = rng.permutation(ids)
    elif name == "dropped_at_the_end":
        ids[500:] = rows_n
    elif name == "blocks_that_write_1_7_8_9_and_256":
        # five blocks of 256 lanes, the kept ones between dropped ones
        rows_n, n = 2000, 1280
        ids = np.sort(rng.choice(rows_n, n, replace=False)).astype(np.int32)
        kept = np.zeros(n, bool)
        for b, c in enumerate((1, 7, 8, 9, 256)):
            kept[b * 256 + rng.choice(256, c, replace=False)] = True
        ids[~kept] = rows_n
    state = rng.normal(size=(rows_n, WIDTH)).astype(np.float32)
    new = rng.normal(size=(n, WIDTH)).astype(np.float32)
    if name == "nan_inf_and_minus_zero":
        new[0], new[1, ::2], new[2, 5] = -0.0, np.inf, np.nan
        new[3] = np.frombuffer(  # a signalling NaN's payload
            np.uint32(0x7FA00001).tobytes() * WIDTH, np.float32)
    new[ids >= rows_n] = np.nan  # a dropped lane's row is never written
    got = row_update.sorted_row_set(
        jnp.asarray(state), jnp.asarray(ids), jnp.asarray(new))
    want = np.array(state)
    want[ids[ids < rows_n]] = new[ids < rows_n]
    assert np.asarray(got).tobytes() == want.tobytes()
    jitted = jax.jit(row_update.sorted_row_set, donate_argnums=0)(
        jnp.asarray(state), jnp.asarray(ids), jnp.asarray(new))
    assert np.asarray(jitted).tobytes() == want.tobytes()
    # a descriptor a row written, the spare lanes of a trip of eight aside
    sent = _compact_plan_is_numpys(ids, rows_n, row_update.BLOCK)
    written = int((ids < rows_n).sum())
    assert written <= sent <= written + 7 * -(-n // row_update.BLOCK)
    if name == "blocks_that_write_1_7_8_9_and_256":
        assert sent == 8 + 8 + 8 + 16 + 256


def test_sorted_row_set_refuses_what_the_row_kernel_refuses():
    state = jnp.zeros((64, 256), jnp.float32)
    with pytest.raises(ValueError, match="sorted_row_set: rows of shape"):
        row_update.sorted_row_set(
            state, jnp.zeros((8,), jnp.int32), jnp.zeros((8, 256)),
            interpret=False)
    with pytest.raises(ValueError, match="lanes in one call"):
        row_update.sorted_row_set(
            jnp.zeros((64, 128), jnp.float32),
            jnp.zeros((row_update.MAX_LANES + 256,), jnp.int32),
            jnp.zeros((row_update.MAX_LANES + 256, 128)), interpret=False)


def test_eager_tile_set_leaves_the_callers_table_alone():
    table = jnp.ones((256, 4), jnp.float32)
    out, _ = row_update.sorted_tile_set(
        table, jnp.array([3, 200], jnp.int32), jnp.full((2, 3), 7.0)
    )  # (two tiles of one group: a span)
    assert float(table.sum()) == 1024.0
    assert np.asarray(out)[3].tolist() == [7.0, 7.0, 7.0, 0.0]


@pytest.mark.parametrize("shape,dtype,refused", [
    ((1024, 4), jnp.float32, None), ((1024, 1), jnp.float32, None),
    ((1024, 8), jnp.float32, None), ((1024, 4), jnp.bfloat16, "bfloat16"),
    ((1024, 3), jnp.float32, "(3,)"), ((1024, 16), jnp.float32, "(16,)"),
    ((1024, 2, 2), jnp.float32, "(2, 2)"), ((1024,), jnp.float32, "()"),
    ((1000, 4), jnp.float32, "whole tiles"),
])
def test_set_refusal_names_what_the_set_kernel_cannot_take(
        shape, dtype, refused):
    why = row_update.set_refusal(shape, dtype)
    assert (why is None) if refused is None else (refused in why)
    if refused is not None:
        with pytest.raises(ValueError, match="sorted_tile_set"):
            row_update.sorted_tile_set(
                jnp.zeros(shape, dtype), jnp.zeros((4,), jnp.int32),
                jnp.zeros((4,) + shape[1:], dtype).reshape(4, -1),
            )


def _rule(current, combined):
    return 0.5 * current + combined


@pytest.mark.parametrize("backend,meshed,shape,update,dtype,want", [
    ("tpu", False, (3,), _rule, jnp.float32, True),  # FTRL's (w, z, n)
    ("tpu", False, (1,), _rule, jnp.float32, True),
    ("tpu", False, (8,), _rule, jnp.float32, True),
    ("tpu", False, (5,), _rule, jnp.float32, True),
    ("tpu", False, (9,), _rule, jnp.float32, True),   # packed: the row set
    ("tpu", False, (36,), _rule, jnp.float32, True),  # DiFacto's row, k = 3
    ("tpu", False, (64,), _rule, jnp.float32, True),
    ("tpu", False, (65,), _rule, jnp.float32, True),  # one to a register
    ("tpu", False, (101,), _rule, jnp.float32, True),  # (PR 61: PBG's row)
    ("tpu", False, (128,), _rule, jnp.float32, False),  # dense, XLA's set
    ("tpu", False, (3,), "add", jnp.float32, False),
    ("cpu", False, (3,), _rule, jnp.float32, False),
    ("cpu", False, (36,), _rule, jnp.float32, False),
    ("tpu", True, (3,), _rule, jnp.float32, False),
    ("tpu", True, (36,), _rule, jnp.float32, False),  # dp > 1: GSPMD's set
    # one worker over ps = 4: the push runs on the shards, where a packed
    # store's block gets the row set; a narrow row is not tiled under a mesh
    ("tpu", "ps4", (36,), _rule, jnp.float32, True),
    ("tpu", "ps4", (9,), _rule, jnp.float32, True),
    ("tpu", "ps4", (3,), _rule, jnp.float32, False),
    ("tpu", "ps4", (65,), _rule, jnp.float32, True),
    ("tpu", "ps4", (128,), _rule, jnp.float32, False),
    ("cpu", "ps4", (36,), _rule, jnp.float32, False),
])
def test_the_write_back_takes_the_set_kernel_from_what_the_spec_holds(
        monkeypatch, backend, meshed, shape, update, dtype, want):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = None
    if meshed:
        dp = 1 if meshed == "ps4" else 2
        mesh = make_mesh(worker_parallelism=dp, ps_parallelism=4 // dp,
                         devices=jax.devices()[:4])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    n0 = row_update.refusal_count()
    spec = _spec(shape, dtype, update=update, mesh=mesh)
    # (the record is read whole: under dp = 2 a rule store is refused the
    # push on its shards, noted once; the write-back itself notes nothing)
    noted = int(meshed is True and update != "add")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        arm = store_mod.arms(spec)
    assert (arm.write_back not in ("", "xla_set")) == want
    if want:
        assert arm.write_back == (
            "tile_set" if shape[0] <= 8 else "row_set")
    assert len(caught) == noted and all(
        "dp = 2 workers" in str(w.message) for w in caught)
    assert row_update.refusal_count() == n0 + noted
    if update != "add" and 8 < shape[0] < 128:
        # several rows to a 128-lane physical row (from 65 lanes one),
        # whatever the backend
        assert spec.layout == "packed" and spec.tile_lanes == 0
        assert spec.table_shape()[1] == 128 and spec.pack == 128 // shape[0]
    elif want:  # the physical row is the sublane tile, whole tiles of rows
        lanes = {1: 1, 3: 4, 5: 8, 8: 8}[shape[0]]
        assert spec.tile_lanes == lanes and spec.table_shape() == (128, lanes)
    elif backend == "cpu":  # the physical row is the spec's, not the backend's
        assert spec.tile_lanes == 4


@pytest.mark.parametrize("shape,dtype,reason", [
    ((3,), jnp.bfloat16, "bfloat16"),
    ((2, 2), jnp.float32, "(2, 2)"),
    ((), jnp.float32, "()"),
    ((36,), jnp.bfloat16, "bfloat16"),  # packed: the row set moves float32
])
def test_a_narrow_rule_store_the_kernel_refuses_warns_once_and_counts(
        monkeypatch, shape, dtype, reason):
    from flink_parameter_server_tpu.core import store as store_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    spec = _spec(shape, dtype, update=_rule)
    assert spec.tile_lanes == 0  # held as it is, XLA's row set
    # (the record is read whole: bfloat16 rows too wide for a sort are also
    # refused the row kernel's sums, noted once as the write-back is)
    noted = 1 + (shape == (36,))
    n0 = row_update.refusal_count()
    with pytest.warns(RuntimeWarning, match="falling back") as caught:
        assert store_mod.arms(spec).write_back == "xla_set"
    assert len(caught) == noted and reason in str(caught[0].message)
    assert "write-back of a" in str(caught[0].message)
    assert row_update.refusal_count() == n0 + noted
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # every later trace is silent
        assert store_mod.arms(spec).write_back == "xla_set"
    assert row_update.refusal_count() == n0 + noted


@pytest.mark.parametrize("backend,shape,started", [
    ("tpu", (3,), 1), ("tpu", (9,), 1), ("tpu", (200,), 0), ("cpu", (3,), 0),
    ("cpu", (9,), 0),
])
def test_a_rule_store_whose_pushes_will_trace_the_kernel_starts_the_pallas_import(
        monkeypatch, backend, shape, started):
    """Three lanes: the set kernel writes the rows back.  Nine: the row
    kernel sums them (``core/store.arms``' ``combine``, PR 46; no kernel
    took such a store before).  Two hundred, held DENSE as ``create`` holds a
    store by default: neither, and a warning says so (``layout="auto"`` lays
    such a row flat in two registers since PR 55, and the tile kernel takes
    both: tests/test_store.py)."""
    from flink_parameter_server_tpu.core import store as store_mod

    calls = []
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", {
        ("the sum of a rule's wide rows", (200,), "float32")})  # keep it quiet
    monkeypatch.setattr(row_update, "preload", lambda: calls.append(1))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    ShardedParamStore.create(16, shape, update=_rule)
    assert len(calls) == started
    ShardedParamStore.from_values(jnp.zeros((16,) + shape), update=_rule)
    assert len(calls) == 2 * started


def test_preload_imports_pallas_off_the_calling_thread():
    import sys
    import threading

    row_update.preload()
    for t in threading.enumerate():
        if t.name == "pallas-import":
            t.join(60)
    assert "jax.experimental.pallas.tpu" in sys.modules


def test_unknown_state_scatter_is_refused():
    with pytest.raises(ValueError, match="state_scatter"):
        mfm.OnlineMatrixFactorization(8, 8, state_scatter="pallas")


# -- rows of several registers under a rule: the tile walk with a store -------
TILE_ASSIGN_CASES = [
    "sorted_distinct", "neighbours_in_one_tile", "every_lane_dropped",
    "dropped_at_the_end", "one_lane", "nan_inf_and_minus_zero",
    "a_whole_tile", "several_calls", "a_tile_row_two_blocks_share",
]


@pytest.mark.parametrize("width,w,name", [
    (width, width, name) for width in (256, 640) for name in TILE_ASSIGN_CASES
] + [
    # rows NARROWER than the table's (cell 13's write-back: PR 57)
    (width, w, name)
    for width, w in ((640, 600), (384, 300), (640, 602))
    for name in ("sorted_distinct", "dropped_at_the_end",
                 "nan_inf_and_minus_zero", "several_calls")
] + [
    # one register a row, and a held tile row under a store at five (PR 74)
    (width, w, name)
    for width, w in ((128, 128), (640, 600))
    for name in ("neighbours_in_one_tile", "a_whole_tile", "one_lane",
                 "a_tile_row_two_blocks_share")
])
def test_sorted_tile_assign_is_xlas_row_set_bit_for_bit(
        width, w, name, monkeypatch):
    """``table.at[ids].set(rows, mode="drop")`` for distinct ascending ids
    and rows of several registers: every bit of the table, the rows that
    share a touched tile with a written one included (the kernel copies, it
    does no arithmetic).  New rows of ``w`` < ``width`` lanes replace lanes
    ``[0, w)`` (XLA's ``set`` of the rows padded with what the table holds
    past them): the table's lanes ``[w, width)``, NaN and -0.0 in written
    rows and unwritten, come back as they were."""
    rng = np.random.default_rng(TILE_ASSIGN_CASES.index(name))
    rows_n, n = 1000, 300
    ids = np.sort(rng.choice(rows_n, n, replace=False)).astype(np.int32)
    if name == "neighbours_in_one_tile":
        ids = np.arange(40, 40 + n).astype(np.int32)
    elif name == "every_lane_dropped":
        ids[:] = rows_n
    elif name == "dropped_at_the_end":
        ids[200:] = rows_n + 3
    elif name == "one_lane":
        ids, n = ids[:1], 1
    elif name == "a_whole_tile":
        ids, n = np.arange(16, 24).astype(np.int32), 8
    elif name == "a_tile_row_two_blocks_share":
        # sorted lanes 253-258 are rows 400-405: three a side of block 0's
        # end: the carried tile row, its lanes stored one by one
        ids = np.concatenate(
            [np.arange(0, 253), np.arange(400, 447)]).astype(np.int32)
    elif name == "several_calls":
        monkeypatch.setattr(row_update, "MAX_LANES", 256)
        rows_n, n = 2000, 700
        ids = np.sort(rng.choice(rows_n, n, replace=False)).astype(np.int32)
    table = rng.normal(size=(rows_n, width)).astype(np.float32)
    table[:, w:] = np.nan  # the pad lanes, whatever they hold
    table[::3, w + 1:] = -0.0
    new = rng.normal(size=(n, w)).astype(np.float32)
    if name == "nan_inf_and_minus_zero":
        ids = (2 * np.arange(n)).astype(np.int32)  # the odd rows stay
        new[0], new[1, ::2], new[2, 5] = -0.0, np.inf, np.nan
        table[7] = np.nan  # untouched rows of touched tiles
        table[9, ::3] = -0.0
    new[ids >= rows_n] = np.nan  # a dropped lane's row is never written
    got, opened = row_update.sorted_tile_assign(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(new))
    padded = np.concatenate(  # the new rows, then what their rows hold
        [new, table[np.minimum(ids, rows_n - 1), w:]], axis=1)
    want = np.asarray(
        jnp.asarray(table).at[jnp.asarray(ids)].set(jnp.asarray(padded), mode="drop"))
    assert np.asarray(got).tobytes() == want.tobytes()
    assert want[:, w:].tobytes() == table[:, w:].tobytes()
    jitted, _ = jax.jit(row_update.sorted_tile_assign, donate_argnums=0)(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(new))
    assert np.asarray(jitted).tobytes() == want.tobytes()
    kept = ids[ids < rows_n]
    if name == "several_calls":
        size = 256  # three calls of 256 lanes: a tile open across two is moved by both
        assert int(opened) == sum(
            len(np.unique(kept[lo:lo + size] // 8)) for lo in range(0, 768, size))
    else:
        assert int(opened) == len(np.unique(kept // 8))


def test_sorted_tile_assign_refuses_what_the_tile_kernel_refuses():
    with pytest.raises(ValueError, match="sorted_tile_assign: .*whole tiles"):
        row_update.sorted_tile_assign(
            jnp.zeros((63, 256), jnp.float32), jnp.zeros((8,), jnp.int32),
            jnp.zeros((8, 256)), interpret=False)
    with pytest.raises(ValueError, match="sorted_tile_assign: rows are bfloat16"):
        row_update.sorted_tile_assign(
            jnp.zeros((64, 256), jnp.bfloat16), jnp.zeros((8,), jnp.int32),
            jnp.zeros((8, 256)), interpret=False)


@pytest.mark.parametrize("width, n", [
    (256, 300), (640, 1000),
    (602, 1000), (300, 300), (600, 700),  # no whole registers: summed as they come
])
def test_wide_rows_are_summed_by_the_tile_kernel_in_stream_order_bit_for_bit(
        width, n):
    """``ops/dedup.combine_runs`` for rows wider than a register, kernel
    arm: the same float32 additions in the same order as the scatter-add arm
    (and ``np.add.at``), the descriptors counted by tile rows.  The kernel's
    sums come out in whole registers, zeros past the rows' own width."""
    from flink_parameter_server_tpu.ops import dedup

    rng = np.random.default_rng(width)
    sentinel = 500
    ids = rng.integers(0, 60, n).astype(np.int32)
    ids[rng.random(n) < 0.1] = sentinel
    ids[: n // 4] = 7
    vals = (rng.normal(size=(n, width)) * 10.0 ** rng.integers(-3, 4, (n, 1))
            ).astype(np.float32)
    want_ids, want, zero = jax.jit(dedup.combine_runs, static_argnums=(2, 3))(
        ids, vals, sentinel, "scatter_add")
    got_ids, got, opened = jax.jit(
        lambda i, v: dedup.combine_runs(
            i, v, sentinel, "tile_kernel", interpret=True)
    )(ids, vals)
    assert np.array_equal(np.asarray(got_ids), np.asarray(want_ids))
    distinct = len(np.unique(ids[ids < sentinel]))
    # (past the distinct ids the scatter-add arm holds the dropped lanes' sum,
    # which no caller reads: their ids are the sentinel)
    whole = -(-width // 128) * 128
    assert got.shape == (n, whole) and want.shape == (n, width)
    assert np.asarray(got)[:distinct, :width].tobytes() == (
        np.asarray(want)[:distinct].tobytes())
    assert not np.asarray(got)[distinct:].any()
    assert not np.asarray(got)[:, width:].any()
    assert int(zero) == 0 and int(opened) == -(-distinct // 8)
    # the refusal is asked about the block the sums land in
    assert dedup.kernel_refusal(whole, jnp.float32) is None
    assert "whole 128-lane" in dedup.kernel_refusal(whole + 1, jnp.float32)
    assert "bfloat16" in dedup.kernel_refusal(whole, jnp.bfloat16)


# -- the dense plan: a combine's sums leave the kernel as neighbours ----------
DENSE_CASES = [
    "uniform_few_duplicates", "every_lane_distinct", "one_run_over_the_batch",
    "runs_across_blocks_and_calls", "dropped_lanes_at_the_end",
    "whole_dead_blocks", "every_lane_dropped", "nan_inf_minus_zero_kept",
    "nan_inf_in_dropped_lanes", "blocks_that_write_1_7_8_9_and_256",
    "batch_not_a_multiple_of_block", "every_lane_distinct_no_whole_blocks",
]
DEAD = np.iinfo(np.int32).max


def _dense_case(name):
    """(slots, rows): one batch in sorted order, its slots the dense ranks
    of its ids (ascending, each the one before or that plus one), the lanes
    to drop last with the slot ``DEAD``."""
    rng = np.random.default_rng(DENSE_CASES.index(name))
    n = 1536
    ids = np.sort(rng.integers(0, 700, n))
    dead = np.zeros(n, bool)
    if name == "every_lane_distinct":
        ids = np.arange(n)
    elif name == "one_run_over_the_batch":
        ids[:] = 3
    elif name == "runs_across_blocks_and_calls":
        # runs of 700 and 300 lanes across the blocks of 256 and, at 512
        # lanes a call, across calls; one ends with its block, one with
        # its call
        ids = np.sort(np.concatenate([
            np.full(700, 5), np.full(68, 6), rng.integers(7, 90, 212),
            np.full(300, 90), rng.integers(91, 200, n - 1280)]))
    elif name == "dropped_lanes_at_the_end":
        dead[1000:] = True
    elif name == "whole_dead_blocks":
        dead[300:] = True  # at 512 lanes a call, two calls of nothing else
    elif name == "every_lane_dropped":
        dead[:] = True
    elif name == "blocks_that_write_1_7_8_9_and_256":
        ids = np.concatenate([
            np.zeros(256), 10 + np.minimum(np.arange(256), 6),
            20 + np.minimum(np.arange(256), 7),
            30 + np.minimum(np.arange(256), 8), 100 + np.arange(256),
            np.full(256, 500),
        ])
        dead[1280:] = True
    elif name == "batch_not_a_multiple_of_block":
        ids, dead = ids[:1000], dead[:1000]
    elif name == "every_lane_distinct_no_whole_blocks":
        ids, dead = np.arange(1000), dead[:1000]  # the last copy ends the state
    slots = np.unique(ids, return_inverse=True)[1].astype(np.int32)
    slots[dead] = DEAD
    n = len(slots)
    rows = (rng.normal(size=(n, WIDTH)) * 10.0 ** rng.integers(-3, 4, (n, 1))
            ).astype(np.float32)
    rows[dead] = 7.0
    if name == "nan_inf_minus_zero_kept":
        lanes = np.flatnonzero(slots == slots[400])
        rows[lanes[0], 1], rows[lanes[-1], 2] = np.nan, np.inf
        rows[lanes[0], 3], rows[lanes[-1], 3] = np.inf, -np.inf
        rows[700, 4] = -np.inf
        rows[slots == slots[900], 5] = -0.0
        rows[:, 6] = -0.0
        # finite values whose float32 sum is not
        rows[slots == slots[1200], 7] = 3e38
    elif name == "nan_inf_in_dropped_lanes" or dead.any():
        rows[dead, ::3] = np.nan
        rows[dead, 1::3] = np.inf
    if name == "nan_inf_in_dropped_lanes":
        slots[1100:] = DEAD
        rows[1100:, ::2], rows[1100:, 1::2] = np.nan, -np.inf
    return slots, rows


def _sums_under_the_compact_plan(slots, rows, size):
    """The PARENT's combine (PR 54 to PR 61): a stretch of ``size`` sorted
    lanes a call of the row kernel under the compact plan into a zeroed
    block, a run across two calls read again by the second."""
    n = len(slots)
    pad = -n % size
    slots = jnp.asarray(np.concatenate([slots, np.full(pad, DEAD, np.int32)]))
    rows = jnp.asarray(np.concatenate([rows, np.zeros((pad, WIDTH), "f4")]))
    zeros = jnp.zeros((size, WIDTH), jnp.float32)
    block, sent = jnp.zeros((n, WIDTH), jnp.float32), 0
    for lo in range(0, n + pad, size):
        at = slots[lo:lo + size]
        block, issued = row_update.sorted_row_update_counted(
            block, at, row_update._open_run_reread(block, at, zeros),
            rows[lo:lo + size], plan="compact", interpret=True)
        sent += int(issued)
    return np.asarray(block), sent


def _dense_copies(slots, size, block):
    """numpy: the copies the dense plan starts over calls of ``size``
    lanes: one a block that writes (:func:`row_update.descriptors`)."""
    slots = np.concatenate(
        [slots, np.full(-len(slots) % size, DEAD, np.int32)])
    sent = 0
    for lo in range(0, len(slots), size):
        call = slots[lo:lo + size]
        call = np.concatenate([call, np.full(-len(call) % block, DEAD)])
        last = np.concatenate([call[1:] != call[:-1], [True]]) & (call < DEAD)
        sent += int((last.reshape(-1, block).sum(axis=1) > 0).sum())
    return sent


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("size", [512, 2048])
@pytest.mark.parametrize("name", DENSE_CASES)
def test_the_dense_plans_sums_are_the_compact_plans_bit_for_bit(
        name, size, block, monkeypatch):
    """``sorted_run_sums`` (a rule store's combine: its slots are dense
    ranks) against the row kernel under the compact plan on the same
    slots, one call and calls of 512 lanes: every kept row the same BITS
    (the same mask rows, pieces and accumulation; a NaN or an Inf stays in
    its run, -0.0 sums as it did, finite values that overflow read Inf in
    both), zeros past the last distinct rank whatever the dropped lanes
    hold, and ONE copy a block that writes where the compact plan issues a
    descriptor a row."""
    monkeypatch.setattr(row_update, "BLOCK", block)
    slots, rows = _dense_case(name)
    n = len(slots)
    want, sent_rows = _sums_under_the_compact_plan(slots, rows, size)

    def combine(slots, rows):
        # (a copy is a whole block of rows from the block's first slot on,
        # and a slot is at most its lane's place: a row a padded lane will
        # do, with every lane distinct too)
        state, sent = jnp.zeros((-(-n // block) * block, WIDTH), "f4"), 0
        for lo in range(0, n, size):
            state, issued = row_update.sorted_run_sums(
                state, slots[lo:lo + size], rows[lo:lo + size],
                interpret=True)
            sent = sent + issued
        return state, sent

    got, sent = jax.jit(combine)(jnp.asarray(slots), jnp.asarray(rows))
    got = np.asarray(got)
    assert got[:n].tobytes() == want.tobytes()
    distinct = int(slots[slots < DEAD].max()) + 1 if (slots < DEAD).any() else 0
    assert not got[distinct:].any()
    if name not in ("nan_inf_minus_zero_kept",):
        clean = np.where(np.isfinite(rows), rows, 0.0).astype(np.float64)
        ref = np.zeros(got.shape)
        np.add.at(ref, slots[slots < DEAD], clean[slots < DEAD])
        np.testing.assert_allclose(got, ref, rtol=0, atol=2e-3 * np.abs(
            clean).max())
    assert int(sent) == _dense_copies(slots, size, block) <= -(-n // block)
    assert int(sent) * 8 <= sent_rows  # the compact plan's trips of eight
    if name == "blocks_that_write_1_7_8_9_and_256" and (size, block) == (
            2048, 256):
        assert sent_rows == 8 + 8 + 8 + 16 + 256 and int(sent) == 5
    if name == "nan_inf_minus_zero_kept":
        at = slots[400]
        assert np.isnan(got[at, 1]) and np.isnan(got[at, 3])
        assert np.isnan(got[at, 2]) and np.isnan(got[slots[700], 4])
        assert np.isinf(got[slots[1200], 7]) or (
            slots == slots[1200]).sum() == 1
        assert np.isfinite(np.delete(got, [at, slots[700]], axis=0)[:, :7]).all()


def test_the_dense_plan_is_numpys():
    """``_plan(..., "dense")``: a block's first row, its writes, and per lane
    the place of its run among the block's runs."""
    slots, _ = _dense_case("runs_across_blocks_and_calls")
    slots[1400:] = DEAD
    block, rows = 256, 1536
    tgt, src, count, aux = row_update._plan(
        jnp.asarray(slots), rows, block, "dense")
    assert src is None
    tgt, count, aux = np.asarray(tgt), np.asarray(count), np.asarray(aux)
    last = np.concatenate([slots[1:] != slots[:-1], [True]]) & (slots < rows)
    for b in range(len(slots) // block):
        at = slice(b * block, (b + 1) * block)
        mine = slots[at]
        assert count[b] == last[at].sum()
        assert tgt[b] == min(mine[0], rows - 1)
        kept = mine < rows
        assert np.array_equal(aux[b, 0][kept], mine[kept] - mine[0])
        assert (aux[b, 0][~kept] == block).all()
        # the rows a block writes are neighbours from its first on
        assert np.array_equal(
            mine[last[at]], tgt[b] + np.arange(count[b]))
        assert (aux[b, 1] == (b > 0 and slots[b * block - 1] == mine[0])).all()
        assert (aux[b, 2] == (mine[0] == slots[0])).all()
        assert not aux[b, 3:].any()


@pytest.mark.parametrize("head", ["zeros", "an_open_run", "nan_in_it"])
def test_a_run_across_two_calls_reads_the_first_calls_row_as_its_old_row(
        head):
    """One call of ``sorted_run_sums`` whose first run began in a call
    before: the state's row for it is its old row, added ONCE, where the run
    ends (three blocks in), and no other row reads the state."""
    rng = np.random.default_rng(2)
    slots = np.sort(np.concatenate(
        [np.full(600, 40), rng.integers(41, 300, 424)]))
    slots = (40 + np.unique(slots, return_inverse=True)[1]).astype(np.int32)
    rows = rng.normal(size=(1024, WIDTH)).astype(np.float32)
    state = np.zeros((1100, WIDTH), np.float32)
    state[:40] = rng.normal(size=(40, WIDTH))  # earlier calls' sums
    if head != "zeros":
        state[40] = rng.normal(size=WIDTH)
    if head == "nan_in_it":
        state[40, 3] = np.nan
    got, _ = row_update.sorted_run_sums(
        jnp.asarray(state), jnp.asarray(slots), jnp.asarray(rows),
        interpret=True)
    zeros = jnp.zeros((1024, WIDTH), jnp.float32)
    want, _ = row_update.sorted_row_update_counted(
        jnp.asarray(state), jnp.asarray(slots),
        row_update._open_run_reread(
            jnp.asarray(state), jnp.asarray(slots), zeros),
        jnp.asarray(rows), plan="compact", interpret=True)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert np.asarray(got)[:40].tobytes() == state[:40].tobytes()


def test_sorted_run_sums_refuses_what_the_row_kernel_refuses():
    with pytest.raises(ValueError, match="sorted_run_sums: rows of shape"):
        row_update.sorted_run_sums(
            jnp.zeros((64, 256), jnp.float32), jnp.zeros((8,), jnp.int32),
            jnp.zeros((8, 256)), interpret=False)
    with pytest.raises(ValueError, match="lanes in one call"):
        jax.eval_shape(
            lambda *a: row_update.sorted_run_sums(*a, interpret=False),
            jax.ShapeDtypeStruct((64, 128), jnp.float32),
            jax.ShapeDtypeStruct((row_update.MAX_LANES + 256,), jnp.int32),
            jax.ShapeDtypeStruct((row_update.MAX_LANES + 256, 128), "f4"))
    with pytest.raises(ValueError, match="no plan 'dense'"):
        row_update.sorted_row_update(
            jnp.zeros((64, 128), jnp.float32), jnp.zeros((8,), jnp.int32),
            jnp.zeros((8, 128)), jnp.zeros((8, 128)), plan="dense")
