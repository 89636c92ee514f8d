"""Compile the main path's kernels for a DESCRIBED TPU v5e, at the benchmark's
real widths, with the TPU compiler installed here — no chip, nothing runs
(the ``on-chip-measurement`` guide, section 2).  What Mosaic or the TPU
compiler refuses costs a test failure instead of chip time.

The file's two rules, for the next PR's author:

1. ALL compiles for a described chip live in THIS file: the worker that runs
   it loads the TPU library, inside a fixture, and keeps it until it exits.
2. A cell's FULL-SIZE step is lowered and compiled in a MODULE fixture, never
   in a test body or in a helper a test calls: ``CellStep`` below, one a
   (cell, arm), built under ``_compiling_for_described_chips`` (the run's
   compile cache off: a described chip's executable cannot be read back).  A
   test reads what the fixture hands out: the lowered text, the compiled
   text, the memory analysis, the refusals noted, the outputs' shapes.  A new
   reader of a cell's step is a new test that takes the cell's fixture, at
   no new compile; keep it NEXT to the other readers of that fixture (under
   the driver's ``--dist load`` a module fixture is built once a WORKER that
   is handed one of its tests, and neighbours travel together).  A step is
   compiled when a test first reads ``text`` or ``memory``, so a test that
   reads the lowered text alone (``-k step_text``: every cell's hash) costs a
   lowering.  The helpers that check a refusal or ONE kernel (1-17 s) compile
   where they stand.

``pytest tests/test_tpu_compile.py -k step_text`` is the per-PR proof that a
change left every cell's traced program alone: run it on the parent's tree
and on the change's."""
import collections
import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from flink_parameter_server_tpu import ShardedParamStore
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import matrix_factorization as mfm
from flink_parameter_server_tpu.ops import row_update
from flink_parameter_server_tpu.training import tracing

# mf-hugewiki-k128 (chipbench/configs): the MF cells' shapes
USERS, ITEMS, DIM, BATCH = 5_008_260, 39_780, 128, 65_536
# fm-criteo-ps4: cell 4's table (as values, 17 f32 lanes pad to 24 on the chip:
# 16.8 GiB, 4.51 GB a shard; packed 7 rows to a 128-lane physical row,
# 6,705,984 x 128 f32 = 3.43 GB a shard) and its one global batch
FM_ROWS, FM_FIELDS, FM_BATCH = 187_767_412, 39, 32_768
FM_SHARD_PHYS_ROWS = 6_705_984
# fm-criteo: cell 2's table, on one chip (7 rows of 17 lanes to a 128-lane
# physical row: 7,018,048 x 128 f32, 3.59 GB)
FM1_ROWS, FM1_PHYS_ROWS = 49_126_310, 7_018_048
# the released GoogleNews word2vec table: 3,000,000 words x (2, 300) f32, and a
# batch of 16,384 pairs with 5 negatives = 114,688 keys
W2V_VOCAB, W2V_DIM, W2V_BATCH, W2V_NEG = 3_000_000, 300, 16_384, 5
GB = 1e9
# an HLO line that APPLIES a collective (a use of its result is `%all-reduce,`)
COLLECTIVE_OP = re.compile(
    r" (all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)"
    r"(-start)?\("
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ps4(topo):
    """``fm-criteo-ps4`` (chipbench/configs) on the four described chips:
    its mesh, its store's spec (nothing allocated) and its logic."""
    from flink_parameter_server_tpu.models import factorization_machine as fmm
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(1, 4, devices=topo.devices)
    config = fmm.FMConfig(
        num_features=FM_ROWS, dim=16, learning_rate=1e-5, loss="logistic"
    )
    # narrow rows: packed on every shard, as on one chip
    spec = jax.eval_shape(
        lambda: fmm.make_store(config, mesh=mesh, dtype=jnp.float32)
    ).spec
    assert spec.layout == "packed"
    assert spec.table_shape() == (4 * FM_SHARD_PHYS_ROWS, 128)
    return mesh, spec, fmm.FactorizationMachine(config)


@pytest.fixture(scope="module")
def fm1():
    """``fm-criteo`` (chipbench/configs) as ``chipbench/families/fm.py``
    builds it: ``make_store``'s own layout, nothing allocated."""
    from flink_parameter_server_tpu.models import factorization_machine as fmm

    config = fmm.FMConfig(
        num_features=FM1_ROWS, dim=16, learning_rate=1e-5, loss="logistic"
    )
    spec = jax.eval_shape(
        lambda: fmm.make_store(config, dtype=jnp.float32)
    ).spec
    assert spec.layout == "packed"
    assert spec.table_shape() == (FM1_PHYS_ROWS, 128)
    return spec, fmm.FactorizationMachine(config)


def _fm_batch(sharding):
    return {
        "ids": _shape(sharding, (FM_BATCH, FM_FIELDS), jnp.int32),
        "values": _shape(sharding, (FM_BATCH, FM_FIELDS), jnp.float32),
        "feat_mask": _shape(sharding, (FM_BATCH, FM_FIELDS), jnp.bool_),
        "label": _shape(sharding, (FM_BATCH,), jnp.float32),
        "mask": _shape(sharding, (FM_BATCH,), jnp.bool_),
    }


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# the backend a step's arms are read for, by the name the tests give the arm:
# as the chip runs it, or what a CPU reads
BACKEND = {"kernels": "tpu", "xla": None}


@contextlib.contextmanager
def _compiling_for_described_chips(backend=None):
    """What ``no_compile_cache`` (``conftest.py``: a described device's
    executable cannot be read back from the run's cache) and a
    ``monkeypatch`` of ``jax.default_backend`` do for one test, for a
    fixture of the module's scope (a cell's step is compiled ONCE for every
    test that reads it)."""
    from jax.experimental.compilation_cache import compilation_cache

    was, cached = jax.default_backend, jax.config.jax_enable_compilation_cache
    if backend is not None:
        jax.default_backend = lambda: backend
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.default_backend = was
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


class CellStep:
    """A cell's step ``make_train_step`` gives, at full size for described
    chips (``backend`` ``"tpu"``: as the chip runs it, code that asks for the
    backend steered; ``None``: in the arms a CPU reads).  Lowered where it is
    built: ``lowered`` (the text), ``noted`` (refusals counted on the way)
    and ``crossings`` (what the trace's ``mesh.*`` sites said they move,
    ``tracing.mesh_tally``: name -> bytes a transfer) are there at once.
    Compiled ONCE, when a test first reads ``text`` (the compiled module's)
    or ``memory`` (its memory analysis); ``outs`` (the shapes of the step's
    outputs) is a second trace, made when first read.  No test sees the
    ``Compiled`` object.  ``spec`` and ``arms`` are what the fixture read on
    its way (the store's spec, its arms as the backend reads them), for the
    tests that hold them too."""

    def __init__(self, step, *args, backend=None, spec=None, arms=None):
        self._step, self._args, self._backend = step, args, backend
        self.spec, self.arms = spec, arms
        with _compiling_for_described_chips(backend):
            n0 = row_update.refusal_count()
            with tracing.mesh_tally() as crossings:
                self._lowered = jax.jit(
                    step, donate_argnums=(0, 1)).lower(*args)
            self.noted = row_update.refusal_count() - n0
        self.crossings = dict(crossings)
        self.lowered = self._lowered.as_text()

    @functools.cached_property
    def _compiled(self):
        with _compiling_for_described_chips():
            compiled = self._lowered.compile()
        del self._lowered
        return compiled.as_text(), compiled.memory_analysis()

    @functools.cached_property
    def outs(self):
        with _compiling_for_described_chips(self._backend):
            return jax.eval_shape(self._step, *jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self._args))[2]

    @property
    def text(self):
        return self._compiled[0]

    @property
    def memory(self):
        return self._compiled[1]


# -- the third naming rule, held where it can break: in the compiled step -----
TRANSFER = (r"(all-reduce|all-gather|all-to-all|reduce-scatter|"
            r"collective-permute)(-start|-done)?")
MESH_NAME = re.compile(
    r'op_name="[^"]*?/(mesh\.[A-Za-z0-9_]+|ps\.delta_reduce)/[^"/]*"')
# THE list of the transfers that carry no name of the program's in a compiled
# step: (cell, instruction pattern, the named transfer it is a part of, why).
# A new collective that matches none of these and has no `mesh.*` component
# in its `op_name` fails the test below until its site names it.
UNNAMED_TRANSFERS = [
    ("cell_16", r"collective-permute-(start|done)[.\d]* = .*f32\[1056,128\]",
     "mesh.pull_rows_sum",
     "the halo of the reduce-scatter: the partitioner lays the flat sum out "
     "in four blocks of 213,344 rows (352 more than a chip's quarter of the "
     "lanes) and sends 3 x 352 rows to the right neighbour by a permute of "
     "its own making, under no op_name"),
    ("cell_16", r"all-reduce[.\d]* = f32\[4\]",
     "mesh.block_sums_gather",
     "the last bias' four block sums: an all-gather of 4 bytes a chip, which "
     "the TPU compiler rewrites as an all-reduce of a placed vector and "
     "whose op_name it drops (16 bytes; `core/store._gather_counts` pads its "
     "counts to a whole tile a shard for that reason)"),
]
# ... and of the named transfers that the partitioner lays out LARGER than the
# result the program counts (`core/store.step_counts`: a transfer's bytes are
# its result's on one chip, as the traced program would have them): (cell,
# name, bytes counted, bytes of the compiled instructions under it, why)
LAID_OUT_LARGER = [
    ("cell_16", "mesh.pull_rows_sum",
     212_992 * 128 * 4, (213_344 + 1_056) * 128 * 4,
     "the reduce-scatter's blocks are 352 rows a chip longer than the "
     "quarter of the lanes a chip keeps, and the halo permute above (listed "
     "under this name) moves 1,056 rows beside them"),
]


def _computations(text):
    """``{computation: [instruction lines]}`` of a compiled module, and the
    computations some ``fusion`` calls."""
    bodies, fused, current = {}, set(), None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%\S+) \(.*\{$", line)
        if head:
            current = bodies.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None and " = " in line:
            current.append(line)
            call = re.search(r" fusion\(.*calls=(%[^ ,}]+)", line)
            if call:
                fused.add(call.group(1))
    return bodies, fused


def _bytes(shape):
    dtype, dims = re.match(r"([a-z]+\d+)\[([\d,]*)\]", shape).groups()
    size = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
    return size * int(re.search(r"\d+", dtype).group(0)) // 8


def _transfers(text):
    """Every instruction a chip EXECUTES as a transfer: a collective (or the
    start or done half of one) and a fusion whose computation holds one, in
    any computation that is not itself fused: ``[(instruction, result shapes,
    mesh name or None, line)]``.  The start half of an async pair carries no
    metadata on the TPU; it takes its done half's name, the pairing
    ``chipbench/mesh_trace.py`` makes by the same rule."""
    bodies, fused = _computations(text)
    holds = {name for name, lines in bodies.items()
             if any(re.search(rf" {TRANSFER}\(", line) for line in lines)}
    found = []
    for name, lines in bodies.items():
        if name in fused:
            continue
        for line in lines:
            call = re.search(r" fusion\(.*calls=(%[^ ,}]+)", line)
            if call and call.group(1).startswith("%async_collective_fusion"):
                # the middle of an async pair run inside a COMPUTE fusion
                # (a dense product of cell 16): the overlap, the compute's
                # name and time; the pair's start and done halves are judged
                continue
            if not (re.search(rf" {TRANSFER}\(", line)
                    or (call and call.group(1) in holds)):
                continue
            instruction, result = line.strip().split(" = ", 1)
            instruction = instruction.replace("ROOT ", "")
            shapes = re.findall(
                r"[a-z]+\d+\[[\d,]*\]", re.split(r" [a-z\-]+\(", result)[0])
            named = MESH_NAME.search(line)
            found.append(
                [instruction, shapes, named and named.group(1), line])
    by_name = {f[0]: f for f in found}
    for f in found:
        done = by_name.get(f[0].replace("-start", "-done"))
        if f[2] is None and "-start" in f[0] and done:
            f[2] = done[2]
    return found


def _every_transfer_carries_the_programs_name(cell, step):
    """Held by every test of this file that compiles a four-chip step, on
    the program it compiled (a test of its own would compile each a second
    time: under the driver's six workers a slow file's tests go to whichever
    worker is free, and a module's fixture is built once a WORKER): every
    instruction that is a collective, a half of one, or a fusion whose
    computation holds one, carries a ``mesh.*`` component in its ``op_name``
    (cell 8's: ``ps.delta_reduce``) or stands in ``UNNAMED_TRANSFERS`` with
    its reason; and the bytes of the results, name by name, are what the
    program counted where it wrote the transfer
    (``core/store.step_counts``: a transfer's bytes are its result's on one
    chip; ``LAID_OUT_LARGER`` has what the partitioner pads), their sum, in
    KiB, the constant the step hands out as ``ps_mesh_kib``.
    ROADMAP S9 (2)'s all-to-all fails here until it has a name."""
    found = _transfers(step.text)
    assert found
    sized = collections.defaultdict(int)
    for instruction, shapes, name, line in found:
        if name is None:
            listed = [u for u in UNNAMED_TRANSFERS
                      if u[0] == cell and re.search(u[1], line)]
            assert listed, (
                f"{instruction} moves data between chips under no mesh.* "
                f"name: {line.strip()[:300]}")
            name = listed[0][2]
        if "-start" in instruction:
            continue  # (its done half has the result)
        sized[name] += _bytes(shapes[-1])
    said = {("" if k == "delta_reduce" else "mesh.") + k.replace(
        "delta_reduce", "ps.delta_reduce"): sum(v)
        for k, v in step.crossings.items()}
    for at, name, counted, compiled, _ in LAID_OUT_LARGER:
        if at == cell:
            assert (said[name], sized[name]) == (counted, compiled)
            sized[name] = counted
    assert dict(sized) == said
    kib = -(-sum(said.values()) // 1024)
    assert [k for k in step.outs if "mesh" in k] == ["ps_mesh_kib"]
    assert re.search(rf"s32\[\]\S* constant\({kib}\)", step.text), kib


@pytest.fixture(scope="module")
def fm_step(fm1, ps4, one_chip):
    """``(cell, arm) -> CellStep``: cell 2's step for one described chip and
    cell 4's for four, at full size on the layout ``make_store`` resolves by
    itself, compiled once a cell and arm (``kernels``: as the chips run it,
    asked for the backend; ``xla``: in the arms a CPU reads)."""

    @functools.cache
    def built(cell, arm):
        if cell == "cell_2":
            spec, logic = fm1
            table = _shape(one_chip, spec.table_shape(), jnp.float32)
            batch = _fm_batch(one_chip)
        else:
            mesh, spec, logic = ps4
            table = _shape(spec.sharding(), spec.table_shape(), jnp.float32)
            batch = _fm_batch(NamedSharding(mesh, PartitionSpec()))
        return CellStep(
            make_train_step(logic, spec), table, (), batch,
            backend=BACKEND[arm])

    return built


@pytest.mark.parametrize("plan", ["lane", "compact"])
@pytest.mark.parametrize("lanes", [BATCH, row_update.MAX_LANES])
def test_row_update_kernel_compiles_at_the_mf_cells_shapes(
        one_chip, no_compile_cache, lanes, plan):
    """Single-row dynamic-offset DMAs into 5,008,260 rows (no multiple of
    8) of 128 f32 lanes, 65,536 sorted lanes: Mosaic takes it, and as many
    lanes as ``refusal`` lets through (their row ids fit SMEM).  Under the
    compacting plan too (PR 54: a loop of as many trips as a block's writes
    fill, waits that answer those bytes; SMEM still holds two int32 a lane
    and one a block)."""
    compiled = jax.jit(
        lambda st, ids, old, dl: row_update.sorted_row_update(
            st, ids, old, dl, plan=plan, interpret=False),
        donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (USERS, DIM), jnp.float32),
        _shape(one_chip, (lanes,), jnp.int32),
        _shape(one_chip, (lanes, DIM), jnp.float32),
        _shape(one_chip, (lanes, DIM), jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    # in place: the 2.56 GB state is aliased, not copied
    assert mem.alias_size_in_bytes >= USERS * DIM * 4
    assert mem.temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("rows, lanes", [
    (1_277_952, row_update.MAX_LANES),  # cell 9 (and 12): 13 stretches
    (160_256, 80_128),                  # cell 14: two
    (1_024, 1_000),                     # no whole blocks
])
def test_the_dense_plans_kernel_compiles_at_the_combines_shapes(
        one_chip, no_compile_cache, rows, lanes):
    """``sorted_run_sums`` (PR 62) for a described v5e: a copy of a WHOLE
    staging slot, 256 rows, to a dynamic row of the zeroed block that is no
    multiple of 8, one a block of sorted lanes, where the compact plan sent a
    row a descriptor; in place, nothing block-sized beside it, two int32 a
    BLOCK in SMEM."""
    compiled = jax.jit(
        lambda st, slots, new: row_update.sorted_run_sums(
            st, slots, new, interpret=False),
        donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (rows, DIM), jnp.float32),
        _shape(one_chip, (lanes,), jnp.int32),
        _shape(one_chip, (lanes, DIM), jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r" custom-call\([^\n]*sorted_run_sums", text)) == 1
    assert " sort(" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= rows * DIM * 4
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


@pytest.mark.parametrize("lanes", [32_768, row_update.MAX_LANES])
def test_row_set_kernel_compiles_at_cell_9_s_table(
        one_chip, no_compile_cache, lanes):
    """``sorted_row_set`` under the compacting plan (its only one) into
    cell 9's 16,375,440 physical rows, at a chunk of the rule's loop and at
    as many lanes as a call takes: in place, nothing table-sized beside it."""
    compiled = jax.jit(
        lambda st, ids, new: row_update.sorted_row_set(
            st, ids, new, interpret=False),
        donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (16_375_440, DIM), jnp.float32),
        _shape(one_chip, (lanes,), jnp.int32),
        _shape(one_chip, (lanes, DIM), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 16_375_440 * DIM * 4
    assert mem.temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("state", [(4096, 256), (4096, 640)])
def test_mosaic_takes_no_write_of_one_row_wider_than_a_register(
        one_chip, no_compile_cache, monkeypatch, state):
    """Why ``refusal`` lets only 128-lane rows through: a 2-D float32 array
    is tiled (8, 128), a wider row is eight to a tile, and a DMA of one row
    of it is no slice Mosaic takes.  (ISSUE 33 read the kernel as
    width-general; nothing had compiled it over 128 lanes.)"""
    monkeypatch.setattr(row_update, "refusal", lambda row, dtype: None)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(
            lambda st, ids, old, dl: row_update.sorted_row_update(
                st, ids, old, dl, interpret=False),
            donate_argnums=(0,),
        ).lower(
            _shape(one_chip, state, jnp.float32),
            _shape(one_chip, (512,), jnp.int32),
            _shape(one_chip, (512, state[1]), jnp.float32),
            _shape(one_chip, (512, state[1]), jnp.float32),
        ).compile()


@pytest.mark.parametrize("width,lanes,calls", [
    (640, W2V_BATCH * (W2V_NEG + 2), 2), (256, W2V_BATCH * (W2V_NEG + 2), 2),
    (640, row_update.MAX_LANES, 1),
])
def test_tile_kernel_compiles_at_the_w2v_cells_shapes(
        one_chip, no_compile_cache, width, lanes, calls):
    """``scatter_add`` of 114,688 lanes into 3,000,000 rows of 640 (and
    256) f32 lanes: two calls of the tile kernel (57,344 lanes each, their
    scalars in SMEM, three blocks' 256 tile rows of 8 x 640 f32 in VMEM), the
    table aliased through both, no table-sized temporary; and one call of
    as many lanes as one call takes."""
    compiled = jax.jit(
        lambda t, ids, dl: row_update.scatter_add(t, ids, dl, interpret=False),
        donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (W2V_VOCAB, width), jnp.float32),
        _shape(one_chip, (lanes,), jnp.int32),
        _shape(one_chip, (lanes, width), jnp.float32),
    ).compile()
    text = compiled.as_text()
    found = re.findall(r" custom-call\([^\n]*sorted_row_update_tiles", text)
    assert len(found) == calls, len(found)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= W2V_VOCAB * width * 4
    assert mem.temp_size_in_bytes < 512 * 2 ** 20  # the sorted deltas


@pytest.mark.parametrize("width,rows", [
    (384, 7_038_744), (640, W2V_VOCAB), (2432, 1_000_000),
])
def test_tile_kernel_holds_three_blocks_of_tile_rows_at_the_lane_cap(
        one_chip, no_compile_cache, width, rows):
    """One call of ``MAX_LANES`` sorted lanes at cell 7's and cell 5's row
    widths: three blocks' tile rows (``f32[3,256,8,W]``: 9.4 and 15.7 MB)
    beside the pipelined deltas in VMEM, two int32 a lane and three a block
    in SMEM; and at the widest row ``tile_refusal`` lets through (19
    registers: 59.8 MB of tile rows, 64.7 MB with the deltas)."""
    lanes = row_update.MAX_LANES
    assert row_update.tile_refusal((rows, width), jnp.float32) is None
    compiled = jax.jit(
        lambda t, ids, dl: row_update.sorted_tile_add(
            t, ids, dl, interpret=False),
        donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (rows, width), jnp.float32),
        _shape(one_chip, (lanes,), jnp.int32),
        _shape(one_chip, (lanes, width), jnp.float32),
    ).compile()
    found = re.findall(
        r" custom-call\([^\n]*sorted_row_update_tiles", compiled.as_text())
    assert len(found) == 1, len(found)


def test_tile_refusal_names_a_width_whose_three_buffers_do_not_fit(one_chip):
    """20 registers a row: three blocks' tile rows and two of deltas are
    68.2 MB, over the 64 MiB the kernel asks Mosaic for; the refusal says
    so, ``sorted_tile_add`` raises it, and a store of such rows keeps XLA's
    scatter-add (``core/store.arms`` reads the refusal)."""
    why = row_update.tile_refusal((1024, 2560), jnp.float32)
    assert why is not None and "VMEM" in why and "2560" in why
    with pytest.raises(ValueError, match="VMEM"):
        jax.jit(lambda t, ids, dl: row_update.sorted_tile_add(
            t, ids, dl, interpret=False)).lower(
            _shape(one_chip, (1024, 2560), jnp.float32),
            _shape(one_chip, (256,), jnp.int32),
            _shape(one_chip, (256, 2560), jnp.float32),
        )


MOSAIC_BODY = re.compile(r'(?<=\\22body\\22: \\22)[A-Za-z0-9+/=]+(?=\\22)')


def _body_sha(body):
    """sha256 of a serialized Mosaic body printed without locations (file
    paths and lines are in the serialized kernel)."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    context = jax_mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    with context:
        asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()[:16]


def _mosaic_body_sha(lowered_text):
    """... of the one Mosaic body in a lowered text."""
    body, = MOSAIC_BODY.findall(lowered_text)
    return _body_sha(body)


@pytest.mark.parametrize("rows,width,w,lanes,assign", [
    (W2V_VOCAB, 640, 600, 57_344, False),   # cell 5: (2, 300) flat in 640
    (7_038_744, 384, 300, 77_824, False),   # cell 7
    (65_536, 640, 602, 65_536, False),      # cell 13's sums, a zeroed block
    (W2V_VOCAB, 256, 256, 57_344, False),
    (24_563_152, 128, 128, 94_720, False),  # cell 10: w = W
    (4_392_040, 640, 602, 32_768, True),    # cell 13's write-back
])
def test_mosaic_takes_the_tile_kernel_with_rows_at_their_own_width(
        one_chip, no_compile_cache, rows, width, w, lanes, assign):
    """Mosaic takes the tile kernel with a block of the deltas ``(256, w)``
    for ``w`` <= ``W``, a lane's add (or store) on lanes ``[0, w)`` of its
    row of the tile, at every width a cell pushes: 600 and 602 in 640 (four
    whole registers and one of 88 / 90 lanes under a mask), 300 in 384, and
    ``w`` = ``W``; in place, no pad of the rows in front of it.  At ``w`` =
    ``W`` = 128, cell 10's shape, the Mosaic body is pinned (a change that
    means to move that kernel brings its own hash: PR 57's parent's was
    ``8f20c8758b8c3858``; PR 74 moved it and meant to: eight lanes that lie
    in one tile row are added to the tile row held in registers)."""
    fn = row_update.sorted_tile_assign if assign else row_update.sorted_tile_add
    lowered = jax.jit(
        lambda t, ids, dl: fn(t, ids, dl, interpret=False), donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (rows, width), jnp.float32),
        _shape(one_chip, (lanes,), jnp.int32),
        _shape(one_chip, (lanes, w), jnp.float32),
    )
    if (width, w) == (128, 128):
        assert _mosaic_body_sha(lowered.as_text()) == "3466e093493e333b"
    compiled = lowered.compile()
    text = compiled.as_text()
    name = "sorted_row_assign_tiles" if assign else "sorted_row_update_tiles"
    assert len(re.findall(rf" custom-call\([^\n]*{name}", text)) == 1
    assert not re.search(rf"= f32\[{lanes},{width}\]\S* pad\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes >= rows * width * 4


@pytest.fixture(scope="module")
def mf_tpu_step(one_chip):
    """``batch_size -> CellStep``: the step the MF cells run (cells 1, 3 and
    11: ``OnlineMatrixFactorization`` as ``chipbench/families/mf.py`` builds
    it, no ``state_scatter``) as the chip runs it (code that asks for the
    backend still sees the CPU here: it is steered), at the cells' tables,
    compiled once a batch size."""

    @functools.cache
    def built(batch_size):
        logic = mfm.OnlineMatrixFactorization(
            USERS, DIM, updater=mfm.SGDUpdater(2e-4))
        spec = jax.eval_shape(
            lambda: ShardedParamStore.create(ITEMS, (DIM,), dtype=jnp.float32)
        ).spec
        batch = {
            "user": _shape(one_chip, (batch_size,), jnp.int32),
            "item": _shape(one_chip, (batch_size,), jnp.int32),
            "rating": _shape(one_chip, (batch_size,), jnp.float32),
            "mask": _shape(one_chip, (batch_size,), jnp.bool_),
        }
        return CellStep(
            make_train_step(logic, spec),
            _shape(one_chip, (spec.padded_capacity, DIM), jnp.float32),
            _shape(one_chip, (USERS, DIM), jnp.float32),
            batch, backend="tpu")

    return built


def test_mf_step_with_a_batch_over_one_calls_lanes_takes_two_calls(
        mf_tpu_step):
    """131,072 row ids do not fit one call's SMEM (Mosaic: RESOURCE_
    EXHAUSTED; the step kept the XLA arm for them until PR 33):
    ``row_add`` gives them to two calls of 65,536, nothing is refused and
    the 2.56 GB state is still updated in place."""
    step = mf_tpu_step(131_072)
    assert step.noted == 0
    text = step.text
    calls = re.findall(r" custom-call\([^\n]*sorted_row_update", text)
    assert len(calls) == 2, len(calls)
    assert not re.findall(rf"= f32\[{USERS},{DIM}\][^ ]* copy\(", text)
    assert step.memory.temp_size_in_bytes < 512 * 2 ** 20


def test_the_keyed_streams_row_kernel_is_the_parents_op_for_op(one_chip):
    """``row_add`` (MF's user state: cells 1, 3, 8, 11) keeps the plan that
    issues a DMA a lane and a loop of static length: its Mosaic body,
    printed without locations, is what PR 54's parent lowered (that PR gave
    the combine and the write-back a compacting plan and left this caller
    alone).  A change that means to move the MF cells' kernel brings its own
    hash."""
    text = jax.jit(
        lambda st, ids, old, dl, m: row_update.row_add(
            st, ids, old, dl, m, interpret=False),
        donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (USERS, DIM), jnp.float32),
        _shape(one_chip, (BATCH,), jnp.int32),
        _shape(one_chip, (BATCH, DIM), jnp.float32),
        _shape(one_chip, (BATCH, DIM), jnp.float32),
        _shape(one_chip, (BATCH,), jnp.bool_),
    ).as_text()
    assert _mosaic_body_sha(text) == "3f54df8566796c53"


def test_mf_step_default_arm_on_tpu_is_the_row_kernel(mf_tpu_step):
    """At the cells' batch the user state goes through the kernel, and no
    scatter over the state array is left under ``ps.state_push``."""
    step = mf_tpu_step(BATCH)
    assert step.noted == 0
    text = step.text
    assert "tpu_custom_call" in text and "sorted_row_update" in text
    # what is left under ps.state_push that yields the whole state array:
    # the kernel's call and nothing else (no XLA scatter fusion)
    state_ops = [
        line.strip().split(" ")[0] for line in text.splitlines()
        if f"= f32[{USERS},{DIM}]" in line and "ps.state_push" in line
    ]
    assert state_ops and all("sorted_row_update" in op for op in state_ops), (
        state_ops
    )


def _no_fill_pass_stands_behind_a_bounded_gather(step, rows, temporaries):
    """``jnp.take``'s default mode compares every id with the table's bounds
    after the gather and selects, over all it fetched, between the row and a
    NaN: behind ids the caller has clipped (``core/store._pull``) or a
    sort's permutation (``ops/row_update.row_add``) that is a read and a
    write of the whole block for the gather's own output, which
    ``mode="clip"`` drops (PR 76).  Held in a compiled ``step`` whose batch
    gathers blocks of ``rows`` 128-lane rows: no instruction the chip
    executes on its own (one outside every fused computation) selects such a
    block for a ``jnp.take`` (a select that rides in its consumer costs no
    pass: the MF logic's own gather of user ids it has NOT bounded keeps
    one, under ``ps.state_pull``), the pull and the state push trace no fill
    at all, no synchronous copy of such a block came in the pass's place,
    and the temporaries stay under ``temporaries`` bytes."""
    text = step.text
    block = rf"f32\[{rows},128\]"
    bodies, fused = _computations(text)
    fills = [
        line.strip()[:200] for name, lines in bodies.items()
        if name not in fused for line in lines
        if re.search(r'op_name="[^"]*jit\(_take\)/select_n"', line)
        and re.search(block, line.split("(%", 1)[0])]  # (what it yields)
    assert not fills, fills
    assert not re.findall(
        r'op_name="[^"]*ps\.(?:pull|state_push)/[^"]*jit\(_take\)/'
        r'(?:select_n|and)"', text)
    assert not re.findall(rf" = {block}\S* copy\(", text)
    assert step.memory.temp_size_in_bytes < temporaries


def test_the_mf_steps_pull_and_permutes_fill_nothing_behind_their_gathers(
        mf_tpu_step):
    """Cells 1, 3 and 11: the two-output ``broadcast_select_fusion
    f32[65536,128]`` behind ``row_add``'s two permutes (the pass ROADMAP S4
    counted round the kernel) is gone and the item pull traces no fill.  One
    of that fusion's outputs lay in the chip's fast memory (``S(1)``); the
    compiler now brings that block there by an asynchronous copy
    (``copy-start`` / ``copy-done f32[65536,128]``, in flight across the
    second permute and the item push), so the temporaries hold one block of
    the batch more (71.5 -> 104.7 MB)."""
    step = mf_tpu_step(BATCH)
    _no_fill_pass_stands_behind_a_bounded_gather(step, BATCH, 0.105 * GB)
    assert len(re.findall(
        rf" = f32\[{BATCH},{DIM}\]\S* copy-done\(", step.text)) <= 1


@pytest.fixture(scope="module")
def mf_dp4_step(topo):
    """``staged -> CellStep``: cell 8's step at full size for four
    described chips, compiled once a staging of the batch, as the chips run
    it (asked for the backend)."""
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    users, lanes = 50_082_603, 262_144

    @functools.cache
    def built(staged):
        mesh = make_mesh(4, 1, devices=topo.devices)
        with _compiling_for_described_chips("tpu"):
            logic = mfm.OnlineMatrixFactorization(
                users, DIM, updater=mfm.SGDUpdater(5e-5), mesh=mesh)
            assert (logic.rows_per_worker, logic.state_rows) == (
                12_520_656, 50_082_624)
            spec = jax.eval_shape(lambda: ShardedParamStore.create(
                ITEMS, (DIM,), dtype=jnp.float32, mesh=mesh)).spec

        def on(shape, dtype, *axes):
            return _shape(
                NamedSharding(mesh, PartitionSpec(*axes)), shape, dtype)

        by = ("dp",) if staged == "a_block_a_worker" else ()
        step = CellStep(
            make_train_step(logic, spec),
            on((spec.padded_capacity, DIM), jnp.float32, "ps", None),
            on((logic.state_rows, DIM), jnp.float32, "dp", None),
            {"user": on((lanes,), jnp.int32, *by),
             "item": on((lanes,), jnp.int32, *by),
             "rating": on((lanes,), jnp.float32, *by),
             "mask": on((lanes,), jnp.bool_, *by)}, backend="tpu")
        assert step.noted == 0
        return step

    return built


@pytest.mark.parametrize("staged", ["a_block_a_worker", "whole_on_every_chip"])
def test_keyed_mf_step_at_hugewiki_uncut_on_four_described_chips(
        mf_dp4_step, staged):
    """``mf-hugewiki-k128-dp4`` (chipbench/configs): 50,082,603 x 128 f32
    user factors over ``dp = 4`` keyed workers.  Every chip updates its own
    12,520,656-row block (6.41 GB, aliased) through the row kernel and
    walks its own 65,536 records, never the 262,144 of the microbatch; the
    step's one collective is the all-reduce of the workers' item deltas,
    which carries the scope its reader finds it by.  So too where the batch
    lies whole on every chip, as the benchmark stages its pool: the step
    slices it where it lies, and only its four columns are that long."""
    lanes, by = 262_144, staged == "a_block_a_worker"
    step = mf_dp4_step(staged)
    text, mem = step.text, step.memory
    _every_transfer_carries_the_programs_name("cell_8", step)
    kernels = re.findall(r" = f32\[(\d+),128\][^ ]* custom-call\([^\n]*sorted_row_update", text)
    assert kernels == ["12520656"], kernels
    collectives = [
        line for line in text.splitlines() if re.search(
            r" (all-reduce|all-gather|all-to-all|reduce-scatter|"
            r"collective-permute)(-start)?\(", line)
    ]
    assert len(collectives) == 1, collectives
    assert "f32[39784,128]" in collectives[0] and "all-reduce" in collectives[0]
    assert "ps.push/ps.delta_reduce" in collectives[0]
    # no chip holds rows for the whole microbatch
    assert not re.findall(rf"= \w+\[{lanes},", text)
    assert bool(re.findall(rf"= \w+\[{lanes}\]", text)) == (not by)
    assert 6.41 * GB < mem.alias_size_in_bytes < 6.45 * GB
    assert mem.temp_size_in_bytes < 0.25 * GB


def test_padding_a_sharded_table_larger_than_a_chip_stays_on_its_shards(
        ps4, no_compile_cache):
    """``ShardedParamStore._place`` of a table pinned ``"dense"`` (cell 4's
    before PR 31) appends its 12 padding rows to a 187.8 M-row array that
    is already sharded over ``ps``: each chip pads its own 4.51 GB block
    and hands a few halo rows on.  The eager concatenate it used before
    gathered the table on every chip (RESOURCE_EXHAUSTED on the v5e, my
    chip run, PR 28; the TPU compiler says the same here)."""
    mesh, spec, _ = ps4
    spec = dataclasses.replace(spec, layout="dense")
    pad = spec.padded_capacity - FM_ROWS
    assert (spec.rows_per_shard, pad) == (46_941_856, 12)
    values = _shape(
        NamedSharding(mesh, PartitionSpec("ps", None)), (FM_ROWS, 17),
        jnp.float32,
    )
    compiled = store_mod._pad_rows(spec, pad).lower(values).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "collective-permute" in text and "all-gather" not in text
    assert 4.5 * GB < mem.output_size_in_bytes < 4.6 * GB
    assert mem.temp_size_in_bytes < 0.1 * GB
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        jax.jit(lambda v, z: jnp.concatenate([v, z])).lower(
            values,
            _shape(NamedSharding(mesh, PartitionSpec()), (pad, 17), jnp.float32),
        ).compile()


def test_packing_cell_4_s_table_stays_on_its_shards(ps4, no_compile_cache):
    """``ShardedParamStore._place`` packs the 187.8 M x 17 rows that lie
    sharded over ``ps`` (4.51 GB a chip) into the 3.43 GB block of each
    chip, there (``core/store._pack_rows_on_mesh``): the values, the block
    and one chunk, under the 9.416 GB the dense cell peaked at (ledger,
    PR 30).  A packed shard holds 35 logical rows more than a shard of the
    values, so at most 3 x 35 rows come from the right neighbour: the one
    collective.  Nothing is gathered."""
    mesh, spec, _ = ps4
    assert store_mod._next_shard_in_reach(spec, FM_ROWS)
    assert spec.rows_per_shard * spec.pack - FM_ROWS // 4 == 35
    values = _shape(
        NamedSharding(mesh, PartitionSpec("ps", None)), (FM_ROWS, 17),
        jnp.float32,
    )
    compiled = store_mod._pack_rows_on_mesh(spec).lower(values).compile()
    collectives = [
        line for line in compiled.as_text().splitlines()
        if COLLECTIVE_OP.search(line)
    ]
    assert len(collectives) == 1, collectives
    assert "collective-permute" in collectives[0]
    assert "f32[105,17]" in collectives[0]
    mem = compiled.memory_analysis()
    assert 4.5 * GB < mem.argument_size_in_bytes < 4.6 * GB
    assert 3.43 * GB < mem.output_size_in_bytes < 3.44 * GB
    assert mem.temp_size_in_bytes < 0.5 * GB
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    ) < 8.5 * GB


def test_unpacking_cell_4_s_table_stays_on_its_shards(ps4, no_compile_cache):
    """``values()`` of cell 4's packed table (what a checkpoint of the
    deployment writes): each chip unpacks its own 3.43 GB block into its
    4.51 GB of logical rows chunk by chunk (``core/store._unpack_block``),
    taking at most 105 rows from its left neighbour; ``unpack_table`` on
    the whole block first lays its rows 128 lanes wide, 24 GB a chip."""
    from flink_parameter_server_tpu.ops.packed import unpack_table

    mesh, spec, _ = ps4
    table = _shape(spec.sharding(), spec.table_shape(), jnp.float32)
    compiled = store_mod._unpack_rows_on_mesh.lower(spec, table).compile()
    collectives = [
        line for line in compiled.as_text().splitlines()
        if COLLECTIVE_OP.search(line)
    ]
    assert len(collectives) == 1, collectives
    assert "collective-permute" in collectives[0]
    assert "f32[105,17]" in collectives[0]
    mem = compiled.memory_analysis()
    assert 4.5 * GB < mem.output_size_in_bytes < 4.6 * GB
    assert mem.temp_size_in_bytes < 0.5 * GB
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    ) < 8.5 * GB
    rows_over_ps = NamedSharding(mesh, PartitionSpec("ps"))
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        jax.jit(
            lambda t: unpack_table(t, FM_ROWS, 17), out_shardings=rows_over_ps
        ).lower(table).compile()


def test_fm_step_on_four_chips_keeps_its_collective_under_ps_pull(fm_step):
    """Cell 4's step on the layout ``make_store`` resolves by itself: the
    donated 3.43 GB shard (``f32[6705984,128]``) is updated in place,
    every gather moves whole 128-lane rows, and the ONE collective is the
    all-reduce of the pulled rows AFTER their lane slice, 17 lanes wide
    (left to GSPMD it is ``f32[1277952,128]``, 7.5 x the bytes) and, since
    PR 63, TURNED as the FM logic takes them, ``f32[39,32768,17]``, whose
    ``op_name`` carries ``ps.pull``: a device trace reads it under
    ``store.pull_device_ms`` (docs/observability.md).  The scatter-add is
    GSPMD's, into the chip's own block, with no collective."""
    step = fm_step("cell_4", "xla")
    text, mem = step.text, step.memory
    _every_transfer_carries_the_programs_name("cell_4", step)
    assert mem.alias_size_in_bytes > 3.43 * GB  # in place
    # 1.350 GB here (the dense step's: under 1.0): two ``f32[1277952,128]``
    # of 654 MB live at once, the gathered physical rows (in the push the
    # lane-shifted deltas) and their relayout, as on one chip (1.352)
    assert mem.temp_size_in_bytes < 1.4 * GB
    collectives = [
        line for line in text.splitlines() if COLLECTIVE_OP.search(line)
    ]
    assert len(collectives) == 1, collectives
    # placed by the partitioner behind the pulled rows' relayout, as the
    # dense step's was: XLA's own name, which the benchmark's reader knows
    assert f"%all-reduce = f32[{FM_FIELDS},{FM_BATCH},17]" in collectives[0]
    assert "all-reduce(" in collectives[0]
    assert 'op_name="jit(step)/ps.pull/' in collectives[0]
    assert f"f32[{FM_SHARD_PHYS_ROWS},128]" in text
    gathers = [
        line for line in text.splitlines()
        if " gather(" in line and re.search(r"ps\.(pull|push)", line)
    ]
    assert gathers and all(
        "slice_sizes={1,128}" in line for line in gathers
    ), gathers


def test_fm_step_on_one_chip_moves_whole_128_lane_rows(fm_step):
    """Cell 2's step on the layout ``make_store`` resolves by itself: the
    donated 3.59 GB table is updated in place, and every gather under
    ``ps.pull`` / ``ps.push`` takes a whole 128-lane physical row.  A gather
    with 1-element slices there (``take_along_axis`` for the lane slice or
    the lane shift) is 10 ns an ELEMENT on the v5e: 460 ms and 3.4 s a
    step (my chip run, PR 29)."""
    step = fm_step("cell_2", "xla")
    mem = step.memory
    assert mem.alias_size_in_bytes > 3.59 * GB  # in place
    assert mem.temp_size_in_bytes < 1.4 * GB  # 1.352: two f32[1277952,128]
    gathers = [
        line for line in step.text.splitlines()
        if " gather(" in line and re.search(r"ps\.(pull|push)", line)
    ]
    assert gathers and all(
        "slice_sizes={1,128}" in line for line in gathers
    ), gathers


def _ops(text):
    """``{name: line}`` of a compiled module's ops."""
    found = {}
    for line in text.splitlines():
        named = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if named:
            found[named[1]] = line
    return found


def _is_a_view(line):
    """An op that moves no byte: a bitcast or a tuple's element."""
    return bool(re.search(r" (bitcast|get-tuple-element)\(", line))


def _reaches_the_logic_through_bitcasts(text, call, width):
    """Every op of the entry computation that reads the slice kernel's
    result ``call``, directly or through views, is the logic's own compute
    (or, under a mesh, the ownership mask's select in front of the
    all-reduce): a fusion under ``ps.compute`` / ``ps.pull`` (XLA leaves a
    fusion it cut out of one without a name: such a one yields a plane of
    the batch, ``(K, B)``, never ``width`` of them), never a ``copy``, a
    ``reshape``, a ``while`` or a fusion that only moves the bytes."""
    entry = text[text.index("ENTRY"):]
    ops = _ops(entry)
    seen, front, readers = set(), [re.match(r"\s*%([\w.\-]+) = ", call)[1]], []
    while front:
        name = front.pop()
        for other, line in ops.items():
            if other in seen or not re.search(rf"%{re.escape(name)}[,)]", line):
                continue
            seen.add(other)
            (front if _is_a_view(line) else readers).append(other)
    assert readers
    for name in readers:
        line = ops[name]
        assert re.search(r" (fusion|all-reduce)\(", line), line
        assert not re.search(r" (copy|reshape|while|transpose)\(", line), line
        if "op_name=" in line:
            assert re.search(r'op_name="jit\(step\)/ps\.(compute|pull)', line), line
        else:
            yields = re.search(r" = f32\[([\d,]+)\]", line)[1].split(",")
            assert sorted(int(d) for d in yields if d != "1") == [
                FM_FIELDS, FM_BATCH], line
    return [ops[name] for name in readers]


def _no_block_of_the_batch_has_its_fields_minor(text):
    """No array of the step holds the batch's 1,277,952 lanes with the 39
    fields as its MINOR axis (39 of 128 lanes), whatever its rows' width:
    ``f32[B,39,d]{1,0,2}`` / ``f32[d,B,39]{2,1,0}`` were the parent's."""
    for shape, layout in re.findall(r"f32\[([\d,]+)\]\{([\d,]+)", text):
        dims = [int(d) for d in shape.split(",")]
        minor = dims[int(layout.split(",")[0])]
        if len(dims) == 3 and sorted(dims)[1:] == [FM_FIELDS, FM_BATCH]:
            assert minor == FM_BATCH, (shape, layout)


@pytest.mark.parametrize("d, width, fields", [
    (17, None, 40), (36, 20, 40), (64, None, 40), (64, None, 26)], ids=str)
def test_the_by_field_kernels_compile_at_the_most_fields_they_take(
        one_chip, no_compile_cache, d, width, fields):
    """``ops/packed.by_field`` takes no more than ``TURN_FIELDS`` fields a
    block: both kernels are lowered by Mosaic and compiled for a described
    v5e at exactly that many (Criteo's 39 ran on the chip), at FM's and at
    DiFacto's widths and at the widest row that packs (64 lanes, two to a
    physical row): a block of ``TURN_BLOCK`` examples of every field, twice
    in the 16 MB of scoped VMEM beside its ``(d, fields, block)`` mirror.
    And at DLRM's own shape, 26 fields of 64 lanes (cell 10 since PR 65:
    eight trips of ``TURN_GROUP`` fields and two odd ones)."""
    from flink_parameter_server_tpu.ops import packed

    batch = 8 * packed.TURN_BLOCK
    assert fields <= packed.TURN_FIELDS == 40 and packed.by_field(fields, batch)
    assert not packed.by_field(packed.TURN_FIELDS + 1, batch)
    sliced = jax.jit(lambda rows, ids: packed.turned_slice_kernel(
        rows, ids, d, width, interpret=False)).lower(
            _shape(one_chip, (batch * fields, 128), jnp.float32),
            _shape(one_chip, (batch, fields), jnp.int32)).compile()
    call, = [line for line in sliced.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert "%packed_lane_slice_turned" in call
    assert f" = f32[{width or d},{fields},{batch}]" in call, call
    shifted = jax.jit(lambda deltas, ids, mask: packed.lane_shift_kernel(
        jnp.moveaxis(deltas, -1, 0), ids, d, mask, interpret=False)).lower(
            _shape(one_chip, (fields, batch, d), jnp.float32),
            _shape(one_chip, (fields, batch), jnp.int32),
            _shape(one_chip, (fields, batch), jnp.bool_)).compile()
    call, = [line for line in shifted.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert "%packed_lane_shift_fielded" in call
    assert f" = f32[{fields},{batch},128]" in call, call


@pytest.mark.parametrize("cell", ["cell_2", "cell_4"])
def test_fm_step_on_a_tpu_slices_its_pulled_rows_in_the_kernel(
        cell, fm1, ps4, fm_step, monkeypatch):
    """What cells 2 and 4 run on the chip: asked for the backend, ``pull``
    hands the 1,277,952 gathered rows to ``ops/packed``'s kernel (on four
    chips inside ``_packed_pull_on_shards``' ``shard_map``, a call a
    shard), nothing is refused, and no op of the step yields a
    ``f32[1277952,17]`` ROW-major any more: the parent's 7-way select fusion
    did, and a ``copy`` relaid it (7.34 + 1.08 ms a step on the v5e, PERF.md
    section 6, PR 42).  The kernel's ``f32[17,1277952]`` reaches the flatten
    through a bitcast (cell 4: through the ownership mask's select, in that
    form); the one all-reduce keeps its operand, the table is updated in
    place and the temporaries stay where the select arm's were.

    Since PR 51 ``push`` hands the deltas to the kernel's mirror
    (``ops/packed.lane_shift_kernel``; on four chips inside
    ``_packed_shift_on_mesh``'s ``shard_map``): exactly one call under
    ``ps.push``, its ``f32[1277952,128]`` rows the scatter-add fusion's own
    operand, and no select fusion or ``copy`` of ``f32[1277952,128]`` left:
    the parent's ``select_select_fusion`` and ``copy.34`` (2.23 + 1.99 ms a
    step), and the mask's ``broadcast_select_fusion f32[1277952,17]`` with
    them: the mask rides in as a select on the ids.  The shifted rows and
    their relayout were the step's two largest temporaries.

    Since PR 63 the logic computes FIELD-major
    (``models/factorization_machine.FieldLanes``) and both kernels move the
    batch a field at a time: ``packed_lane_slice_turned`` reads the rows as
    they were gathered, example-major, and writes ``f32[17,39,32768]``, which
    is XLA's own layout of the logic's ``(39, 32768, 17)`` buffers: its
    result reaches the logic's fusions through BITCASTS alone (cell 4:
    through the ownership mask's select and the all-reduce, in that form,
    ``f32[39,32768,17]`` where the parent's was ``f32[32768,39,17]``: 89 MB
    as tiled for 285); ``packed_lane_shift_fielded`` takes the
    ``concatenate``'s ``f32[17,39,32768]`` as the logic's fusion leaves it,
    no op between.  No ``while(`` is left in the step (the parent's two
    flattens were loops of 17 trips through a flat ``f32[21725184]``, 6.0 ms
    of cell 2's 52.9: PERF.md section 6, PR 63), no op yields
    ``f32[17,32768,39]`` / ``f32[32768,39,.]``, and the temporaries fall to
    0.66 GB (0.778 / 0.789 until then)."""
    # code that asks for the backend still sees the CPU here: steer it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = fm1[0] if cell == "cell_2" else ps4[1]
    lanes = FM_BATCH * FM_FIELDS
    assert store_mod.arms(spec, pull_lanes=lanes, push_lanes=lanes) == (
        store_mod.Arms("packed_kernel", "xla_add", "kernel", "", "", False))
    # ... and of the block the step's logic declares, a field at a time
    assert store_mod.arms(
        spec, pull_lanes=lanes, push_lanes=lanes, fields=FM_FIELDS
    ) == store_mod.Arms(
        "packed_kernel_by_field", "xla_add", "kernel_by_field", "", "", False)
    step = fm_step(cell, "kernels")
    assert step.noted == 0
    mem = step.memory
    assert mem.alias_size_in_bytes > 3.43 * GB  # in place
    # 0.660 / 0.660 here (0.778 / 0.789 with the flattens' loops)
    assert mem.temp_size_in_bytes < 0.7 * GB
    text = step.text
    entry = text[text.index("ENTRY"):]
    ops = _ops(entry)
    n, lanes_3d = FM_BATCH * FM_FIELDS, f"{FM_FIELDS},{FM_BATCH}"
    kernels = [
        line for line in text.splitlines() if "tpu_custom_call" in line
    ]
    assert len(kernels) == 2, kernels
    assert not re.findall(r" while\(", text)
    _no_block_of_the_batch_has_its_fields_minor(text)
    shifts = [line for line in kernels if "packed_lane_shift" in line]
    assert len(shifts) == 1 and shifts[0] in entry, shifts
    assert "%packed_lane_shift_fielded" in shifts[0]
    assert f" = f32[{lanes_3d},128]{{2,1,0:" in shifts[0], shifts[0]
    assert 'op_name="jit(step)/ps.push/' in shifts[0]
    # its deltas: what the logic's last fusion wrote, and no op between
    fed = re.search(r"custom-call\(%[\w.\-]+, %([\w.\-]+)\)", shifts[0])[1]
    while _is_a_view(ops[fed]):
        fed = re.search(r"\(%([\w.\-]+)", ops[fed])[1]
    feeder = ops[fed]
    assert f" = f32[17,{lanes_3d}]{{2,1,0:" in feeder, feeder
    assert " fusion(" in feeder and "concatenate" in text[text.index(
        "%" + re.search(r"calls=%([\w.\-]+)", feeder)[1] + " ("):][:4000]
    fed_by = re.findall(r"%([\w.\-]+)[,)]", feeder.split(" fusion(")[1])
    assert all("ps.compute" in ops[name] for name in fed_by if name in ops)
    # its rows: the scatter-add's own operand, no relayout between
    name = re.search(r"%(packed_lane_shift[\w.\-]*) = ", shifts[0])[1]
    users = [line for line in entry.splitlines()
             if re.search(rf"%{re.escape(name)}[,)]", line)]
    while len(users) == 1 and _is_a_view(users[0]):
        name = re.match(r"\s*%([\w.\-]+) = ", users[0])[1]
        users = [line for line in entry.splitlines()
                 if re.search(rf"%{re.escape(name)}[,)]", line)]
    assert len(users) == 1 and "/ps.push/scatter-add" in users[0], users
    assert f" = f32[{spec.rows_per_shard},128]" in users[0]
    wide = [line for line in entry.splitlines()
            if re.search(rf" = f32\[{n},128\]\S* (copy|fusion)\(", line)]
    # the pull's gather of physical rows is the one fusion that wide
    assert len(wide) == 1 and "/ps.pull/" in wide[0], wide
    assert not re.findall(rf" = f32\[{n},17\]\S* fusion\(", entry)
    calls = [
        line for line in entry.splitlines()
        if "tpu_custom_call" in line and "packed_lane_slice" in line
    ]
    assert len(calls) == 1 and "%packed_lane_slice_turned" in calls[0], calls
    assert f" = f32[17,{lanes_3d}]{{2,1,0:" in calls[0], calls[0]
    assert 'op_name="jit(step)/ps.pull/' in calls[0]
    # the gathered rows come to it as the gather's fusion leaves them
    assert re.search(rf"custom-call\(%[\w.\-]+, %fusion(\.\d+)?\)", calls[0])
    # and its result reaches the logic through bitcasts alone
    readers = _reaches_the_logic_through_bitcasts(text, calls[0], 17)
    if cell == "cell_2":
        assert all("ps.compute" in line for line in readers), readers
    assert f"f32[{n},17]{{1,0" not in text  # row-major, anywhere
    assert not re.findall(rf" = f32\[{n},17\][^ ]* copy\(", text)
    assert not re.search(
        rf"f32\[(17,{FM_BATCH},{FM_FIELDS}|{FM_BATCH},{FM_FIELDS},\d+)\]", text)
    collectives = [
        line for line in text.splitlines() if COLLECTIVE_OP.search(line)
    ]
    if cell == "cell_2":
        assert not collectives
    else:
        assert len(collectives) == 1, collectives
        assert f"%all-reduce = f32[{FM_FIELDS},{FM_BATCH},17]" in collectives[0]
        assert 'op_name="jit(step)/ps.pull/' in collectives[0]


def test_packing_fm_s_table_fits_the_chip_chunk_by_chunk(
        fm1, one_chip, no_compile_cache):
    """``ShardedParamStore._place`` packs cell 2's 49.1 M x 17 rows (4.72 GB
    as they lie on the chip) into the 3.59 GB table in one program that
    holds one chunk beside them (``core/store._pack_rows``).  Reshaped all
    at once the rows are first laid 128 lanes wide, 25 GB: the TPU compiler
    refuses that here as the chip would."""
    from flink_parameter_server_tpu.ops.packed import pack_table

    spec, _ = fm1
    values = _shape(one_chip, (FM1_ROWS, 17), jnp.float32)
    mem = store_mod._pack_rows(spec).lower(values).compile().memory_analysis()
    assert 3.59 * GB < mem.output_size_in_bytes < 3.6 * GB
    assert mem.temp_size_in_bytes < 0.5 * GB
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    ) < 9 * GB  # of the chip's 16
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        jax.jit(lambda v: pack_table(v, FM1_PHYS_ROWS)).lower(values).compile()


def test_unpacking_fm_s_table_fits_the_chip_chunk_by_chunk(
        fm1, one_chip, no_compile_cache):
    """``values()`` of cell 2's table on its one chip: the 3.59 GB table,
    the 4.72 GB of logical rows and one chunk (``core/store._unpack_rows``),
    where ``unpack_table`` at once asks for 25 GB as packing at once does."""
    from flink_parameter_server_tpu.ops.packed import unpack_table

    spec, _ = fm1
    table = _shape(one_chip, spec.table_shape(), jnp.float32)
    mem = store_mod._unpack_rows.lower(spec, table).compile().memory_analysis()
    assert 4.7 * GB < mem.output_size_in_bytes < 4.73 * GB
    assert mem.temp_size_in_bytes < 0.5 * GB
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    ) < 9 * GB
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        jax.jit(lambda t: unpack_table(t, FM1_ROWS, 17)).lower(table).compile()


def _table_made_at_once(spec, config):
    """What ``create_table`` ran for a packed spec before PR 31: every row
    initialised, then one ``pack_table``."""
    from flink_parameter_server_tpu.models import factorization_machine as fmm
    from flink_parameter_server_tpu.ops.packed import pack_table

    def build():
        rows = fmm.make_store(
            dataclasses.replace(config, num_features=spec.padded_capacity),
            layout="dense",
        ).table
        return pack_table(rows, spec.table_shape()[0])

    return build


def test_creating_cell_4_s_table_inits_and_packs_on_its_shards(
        ps4, no_compile_cache):
    """``make_store(config, mesh=...)`` at cell 4's size (``train_fm(mesh=
    ...)``; the cell itself places values): every chip initialises and
    packs its own 3.43 GB block chunk by chunk (``core/store.
    _create_packed``), one chunk of rows beside it (0.94 GB) and no
    collective.  All rows at once are laid 128 lanes wide first: 24 GB
    asked of each chip."""
    from flink_parameter_server_tpu.models import factorization_machine as fmm

    mesh, spec, logic = ps4
    compiled = jax.jit(
        lambda: fmm.make_store(logic.config, mesh=mesh).table
    ).lower().compile()
    assert not [
        line for line in compiled.as_text().splitlines()
        if COLLECTIVE_OP.search(line)
    ]
    mem = compiled.memory_analysis()
    assert 3.43 * GB < mem.output_size_in_bytes < 3.44 * GB
    assert mem.temp_size_in_bytes < 1.0 * GB
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        jax.jit(
            _table_made_at_once(spec, logic.config),
            out_shardings=spec.sharding(),
        ).lower().compile()


def test_creating_fm_s_table_fits_the_chip_chunk_by_chunk(
        fm1, one_chip, no_compile_cache):
    """``make_store(config)`` at cell 2's size on its one chip: the 3.59 GB
    table and one chunk of initialised rows (0.94 GB), where all 49.1 M
    rows at once ask for 25 GB."""
    from flink_parameter_server_tpu.models import factorization_machine as fmm

    spec, logic = fm1
    mem = jax.jit(
        lambda: fmm.make_store(logic.config).table, out_shardings=one_chip
    ).lower().compile().memory_analysis()
    assert 3.59 * GB < mem.output_size_in_bytes < 3.6 * GB
    assert mem.temp_size_in_bytes < 1.0 * GB
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        jax.jit(
            _table_made_at_once(spec, logic.config), out_shardings=one_chip
        ).lower().compile()




@pytest.fixture(scope="module")
def w2v():
    from flink_parameter_server_tpu.models import word2vec as w2vm

    spec = jax.eval_shape(
        lambda: w2vm.make_store(W2V_VOCAB, W2V_DIM, dtype=jnp.float32)
    ).spec
    return spec, w2vm.SkipGramNS(0.025), w2vm


@pytest.fixture(scope="module")
def w2v_step(w2v, one_chip):
    """``arm -> CellStep``: cell 5's step at full size for a described v5e,
    compiled once an arm.  ``kernels``: as the chip runs it (the mean
    combiner on, the store's own layout, the push's arm chosen as on a TPU);
    ``xla``: the summed logic on the store's own layout in the arms a CPU
    reads; ``dense``: that with the table left ``(vocab, 2, 300)``."""
    spec, summed, w2vm = w2v
    batch = {
        "center": _shape(one_chip, (W2V_BATCH,), jnp.int32),
        "context": _shape(one_chip, (W2V_BATCH,), jnp.int32),
        "negatives": _shape(one_chip, (W2V_BATCH, W2V_NEG), jnp.int32),
        "mask": _shape(one_chip, (W2V_BATCH,), jnp.bool_),
    }

    @functools.cache
    def built(arm):
        logic, laid, backend = summed, spec, None
        if arm == "kernels":
            logic = w2vm.SkipGramNS(
                0.025, dedup_scale=True, vocab_size=W2V_VOCAB)
            backend = "tpu"
            with _compiling_for_described_chips(backend):
                assert store_mod.arms(spec).push == "tile_add"
        elif arm == "dense":
            laid = dataclasses.replace(spec, layout="dense")
        return CellStep(
            make_train_step(logic, laid),
            _shape(one_chip, laid.table_shape(), jnp.float32), (), batch,
            backend=backend)

    return built


def test_w2v_step_holds_its_table_once_and_copies_no_table(w2v, w2v_step):
    """The layout ``make_store`` resolves by itself holds a ``(2, 300)`` row
    flat in 640 lanes, row-major (``f32[3000000,640]{1,0:T(8,128)}``, 7.68
    GB): the step updates it in place, gathers and scatter-adds whole rows
    of it, and no op yields a second table.  Left as ``(vocab, 2, 300)``
    the chip holds the table vocabulary-minor (``{0,1,2:T(2,128)}``, 7.2 GB,
    no padding) and the step copies all of it to a row-major table (9.2 GB,
    300 lanes padded to 384) for the gather and back after the scatter-add,
    9.6 GB of temporaries beside the table."""
    spec, _, _ = w2v
    assert spec.layout == "packed" and spec.pack == 1
    assert spec.table_shape() == (W2V_VOCAB, 640)
    mem = w2v_step("xla").memory
    assert mem.alias_size_in_bytes >= 7.68 * GB  # in place
    assert mem.temp_size_in_bytes < 1.0 * GB  # 0.65 GB here
    text = w2v_step("xla").text
    entry = text[text.index("ENTRY"):]
    tables = [
        line.strip() for line in entry.splitlines()
        if re.search(rf" = f32\[{W2V_VOCAB},", line)
    ]
    # the parameter and the scatter-add's fusion: nothing else is table-sized
    assert len(tables) == 2 and all(
        f"f32[{W2V_VOCAB},640]{{1,0:T(8,128)}}" in t for t in tables
    ), tables
    assert " parameter(" in tables[0] and "ps.push/scatter-add" in tables[1]
    dense = w2v_step("dense")
    assert dense.memory.temp_size_in_bytes > 9.0 * GB
    text = dense.text
    copies = re.findall(
        rf"= f32\[{W2V_VOCAB},2,300\]\{{[0-9,]+:T\(2,128\)\}} copy\(",
        text[text.index("ENTRY"):],
    )
    assert len(copies) >= 2, copies


def test_w2v_step_on_a_tpu_pushes_through_the_tile_kernel(w2v_step):
    """What cell 5 runs on the chip: asked for the backend, ``push`` takes
    ``ops/row_update``'s tile kernel for the 640-lane rows (no refusal is
    counted), two calls of it under ``ps.push`` are the only ops that yield
    a table, no XLA scatter is left on the table, nothing copies it, and
    the step's temporaries stay within 0.3 GB of the XLA arm's 0.65."""
    step = w2v_step("kernels")
    assert step.noted == 0
    mem = step.memory
    assert mem.alias_size_in_bytes >= 7.68 * GB  # in place
    assert mem.temp_size_in_bytes < 0.95 * GB  # 0.65 GB here
    text = step.text
    entry = text[text.index("ENTRY"):]
    tables = [
        line.strip() for line in entry.splitlines()
        if re.search(rf" = f32\[{W2V_VOCAB},", line)
    ]
    assert len(tables) == 3 and " parameter(" in tables[0], tables
    for call in tables[1:]:
        assert call.startswith("%sorted_row_update_tiles"), call
        assert "custom-call(" in call and "ps.push" in call, call
    assert " copy(" not in "".join(tables)
    assert "ps.push/scatter-add" not in text


def _ops_built_under(text, scope):
    """``(op, body)`` for every op of the compiled ENTRY whose innermost
    ``ps.*`` scope is ``scope``: the line without its metadata, the op's
    name (``.../scatter-add``) kept, and the text of the computation a
    fusion calls (what the fusion does: a scatter, a gather, a pad...)."""
    bodies = dict(re.findall(
        r"^(%[\w.\-]+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S
    ))
    found = []
    for line in text[text.index("ENTRY"):].splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        scopes = re.findall(r"ps\.[a-z_]+", name.group(1)) if name else []
        if not scopes or scopes[-1] != scope:
            continue
        called = re.search(r"calls=(%[\w.\-]+)", line)
        found.append((
            line.strip().split(" metadata=")[0] + " " + name.group(1),
            bodies.get(called.group(1), "") if called else "",
        ))
    return found


def test_w2v_step_assembles_its_deltas_without_a_zeroed_block(w2v_step):
    """Cell 5's deltas are written once: under ``ps.delta_build`` and
    ``ps.push`` no op of the compiled step yields a ``(B, 7, 2, 300)`` block
    (the parent filled a zeroed one, ``{3,2,1,0:T(2,128)}``: a ``pad``, two
    ``dynamic-update-slice`` and a multiply over it, 6 ms of 21.6 on the
    v5e).  The gradients are joined compact, relaid to rows on HALF the
    bytes (one ``reshape`` to ``f32[114688,300]``), and the 600-lane rows
    first exist in the push's own mask fusion: nothing relays a
    ``f32[114688,600]`` (the parent's ``reshape`` under ``ps.push``).  The
    temporaries were 0.65 GB where the block made them 0.78; since PR 57 the
    push permutes its 600-lane rows ONCE for both kernel calls, one 294 MB
    buffer where two of 147 MB packed into the heap's holes: the same live
    bytes take 0.117 GB more of heap (the buffer assignment's total 8.564 GB
    for 8.447) and ``temp_size_in_bytes`` reads 0.883 GB.  A permute a call
    without the pad compiles to 0.649 GB at this table's 3,000,000 rows and
    draws two ``remat_compressed`` copies of ``f32[57344,600]`` round the
    first call from 3,300,000 rows on (8.45 GB; compiled at 3.01, 3.025,
    3.04, 3.3, 3.6, 3.9, 4.2 and 5.0 M rows: PERF.md section 6, PR 57),
    the one permute none to 5.0 M: the bound is that form's reading.  The
    ops below are what holds the block off."""
    step = w2v_step("kernels")
    assert step.memory.temp_size_in_bytes < 0.90 * GB
    text = step.text
    build, push = (
        [op for op, _ in _ops_built_under(text, scope)]
        for scope in ("ps.delta_build", "ps.push")
    )
    assert len(build) >= 4 and len(push) >= 8  # the filter found both
    keys = W2V_BATCH * (W2V_NEG + 2)
    block = f"f32[{W2V_BATCH},{W2V_NEG + 2},2,{W2V_DIM}]"
    assert not [op for op in build + push if block in op]
    assert not [op for op in build if " dynamic-update-slice(" in op]

    def relaid(ops, lanes):
        return [
            op for op in ops
            if re.search(rf"= f32\[{keys},{lanes}\]\S* (reshape|copy)\(", op)
        ]

    assert not relaid(build + push, 2 * W2V_DIM)
    assert len(relaid(build, W2V_DIM)) == 1 and not relaid(push, W2V_DIM)


# -- cells 5, 7 and 13 push wide rows through the tile kernel: the tests that
# read their steps stand together (a worker that is dealt neighbours builds
# each fixture once) ---------------------------------------------------------
# fastText's released wiki.en model (chipbench/configs/ft-wiki-en-300.json):
# words, n-gram buckets and output vectors in one store of (300,) rows, and a
# batch of 4,096 pairs x (bag of 51, context, 5 negatives) = 233,472 lanes
FT_VOCAB, FT_BUCKETS, FT_DIM, FT_BATCH, FT_BAG = 2_519_370, 2_000_000, 300, 4_096, 51


@pytest.fixture(scope="module")
def ft_step(one_chip):
    """Cell 7's step at full size for a described v5e as the chip runs it
    (the store's own layout, the push's arm chosen as on a TPU), compiled
    ONCE for the tests that read it."""
    from flink_parameter_server_tpu.models import fasttext as ftm

    spec = jax.eval_shape(
        lambda: ftm.make_store(FT_VOCAB, FT_BUCKETS, FT_DIM, dtype=jnp.float32)
    ).spec
    assert spec.layout == "packed" and spec.pack == 1
    assert spec.table_shape() == (7_038_744, 384)
    with _compiling_for_described_chips("tpu"):
        assert store_mod.arms(spec).push == "tile_add"
    logic = ftm.FastTextSkipGram(0.05, FT_VOCAB, FT_BUCKETS, FT_BAG)
    batch = {
        "bag": _shape(one_chip, (FT_BATCH, FT_BAG), jnp.int32),
        "context": _shape(one_chip, (FT_BATCH,), jnp.int32),
        "negatives": _shape(one_chip, (FT_BATCH, 5), jnp.int32),
        "mask": _shape(one_chip, (FT_BATCH,), jnp.bool_),
    }
    return CellStep(
        make_train_step(logic, spec),
        _shape(one_chip, spec.table_shape(), jnp.float32), (), batch,
        backend="tpu")


# glove-840b-300 (chipbench/configs): cell 13's table and batch
GLOVE_VOCAB, GLOVE_DIM, GLOVE_BATCH = 2_196_017, 300, 32_768
GLOVE_PHYS_ROWS = 4_392_040


@pytest.fixture(scope="module")
def glove():
    from flink_parameter_server_tpu.models import glove as gl

    model = gl.GloVeConfig(GLOVE_VOCAB, GLOVE_DIM)
    assert model.num_rows == 4_392_034 and model.row_lanes == 602
    return model, gl


def test_glove_table_is_initialised_in_place_from_a_seed_argument(
        glove, one_chip, no_compile_cache):
    """4,392,034 x 602 f32 rule rows under a ``jit`` that takes the seed: the
    11.24 GB table ``f32[4392040,640]`` (a row flat in five registers) is the
    program's only output, initialised ``core/store._PACK_CHUNK`` rows a loop
    step: 0.67 GB of temporaries, where a second copy would not fit."""
    model, gl = glove
    compiled = jax.jit(lambda s: gl.make_store(model, seed=s).table).lower(
        _shape(one_chip, (), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == GLOVE_PHYS_ROWS * 640 * 4 == 11_243_622_400
    assert mem.temp_size_in_bytes < 1.0 * GB
    assert len(re.findall(r" while\(", compiled.as_text())) >= 1


@pytest.fixture(scope="module")
def glove_step(glove, one_chip):
    """``arm -> CellStep``: cell 13's step at full size for a described v5e,
    compiled once an arm: as the chip runs it (``kernels``: the arms chosen
    as on a TPU) or off it (``xla``)."""
    model, gl = glove
    spec = jax.eval_shape(lambda: gl.make_store(model)).spec
    assert spec.layout == "packed" and spec.pack == 1
    assert spec.table_shape() == (GLOVE_PHYS_ROWS, 640)
    assert store_mod.arms(spec) == store_mod.Arms(
        "packed_selects", "rule", "", "scatter_add", "xla_set", False)  # a CPU
    batch = {
        "word": _shape(one_chip, (GLOVE_BATCH,), jnp.int32),
        "context": _shape(one_chip, (GLOVE_BATCH,), jnp.int32),
        "count": _shape(one_chip, (GLOVE_BATCH,), jnp.float32),
        "mask": _shape(one_chip, (GLOVE_BATCH,), jnp.bool_),
    }

    @functools.cache
    def built(arm):
        backend = BACKEND[arm]
        if arm == "kernels":
            with _compiling_for_described_chips(backend):
                n0 = row_update.refusal_count()
                assert store_mod.arms(spec) == store_mod.Arms(
                    "packed_selects", "rule", "", "tile_kernel",
                    "tile_assign", False)
                assert row_update.refusal_count() == n0
        return CellStep(
            make_train_step(gl.GloVe(model), spec),
            _shape(one_chip, spec.table_shape(), jnp.float32), (), batch,
            backend=backend)

    return built


@pytest.mark.parametrize("cell", [5, 7])
def test_the_combiners_counts_hold_no_counter_and_touch_no_table(
        cell, request):
    """The mean combiner's counts come from a sort of the batch's keys
    (``ops/dedup.occurrence_counts``, PR 39): in cell 5's and cell 7's step
    as the chip runs them, no op under ``ps.delta_build`` yields a buffer
    as long as the vocabulary (the parent zeroed a ``f32[3000000]`` /
    ``f32[7038740]`` counter every step), none scatters or gathers (it
    scatter-added a one a lane into the counter and gathered the counter
    back: 7-9 ns a lane, 1.6 and 3.7 ms a step on the v5e), the two sorts
    are there, and the step's temporaries stay inside the bounds the
    parent's step was held to (0.883 / 0.767 GB here; cell 5's were 0.649
    until PR 57 permuted its push's rows once: the test above says why)."""
    if cell == 5:
        step = request.getfixturevalue("w2v_step")("kernels")
        rows, lanes, temp = W2V_VOCAB, W2V_BATCH * (W2V_NEG + 2), 0.90 * GB
    else:
        step = request.getfixturevalue("ft_step")
        rows, lanes = 2 * FT_VOCAB + FT_BUCKETS, FT_BATCH * (FT_BAG + 6)
        temp = 1.0 * GB
    assert step.memory.temp_size_in_bytes < temp
    build = _ops_built_under(step.text, "ps.delta_build")
    assert len(build) >= 10  # the filter found the scope
    sorts = [op for op, _ in build if re.search(r"\) sort\(", op)]
    assert len(sorts) == 2 and all(f"s32[{lanes}]" in op for op in sorts)
    for op, body in build:
        assert f"[{rows}]" not in op and f"[{rows}]" not in body, op
        assert not re.search(r" (scatter|gather)\(", op + body), op
        assert not re.search(r"/(scatter(-add)?|gather)$", op), op


@pytest.mark.parametrize("cell", [5, 7, 13])
def test_no_pass_over_the_pushed_rows_stands_in_front_of_the_tile_kernel(
        cell, request):
    """The tile kernel takes a wide row at the width the logic left it (PR
    57): in the compiled steps of cells 5, 7 and 13 every tile-kernel call
    reads its rows from the op that made them, at their LOGICAL width
    (``f32[114688,600]``, ``f32[233472,300]``: ONE permute for all the calls
    of an add push; ``f32[65536,301]``: the combine's permute of the worker's
    part, PR 59 (602 until then); ``f32[32768,602]``: the rule's own output,
    whole rows), and nothing between them
    pads, copies or "compresses" the batch's rows: no ``pad`` to 640 / 384
    lanes (the parent's ``pad.71/.73``, ``pad.75/.77/.79``, ``pad.38``, 0.37 to
    0.51 ms each on the v5e), no select over the pushed block in front of a
    rule's combine (``_zero_masked``), no merge of the new rows into the
    gathered ones (``fusion.53``), and no ``remat_compressed`` /
    ``remat_uncompressed`` pair, which is what the compiler makes of three
    permutes of ``f32[77824,300]`` that lie across cell 7's calls (a copy to
    ``{0,1}`` and back for two of them, and 0.952 GB of temporaries; of
    cell 5's ``f32[57344,600]`` too once its table has a tenth more rows:
    both compiled, PERF.md section 6, PR 57; why an add push narrower than
    its table permutes once).  The temporaries:
    cell 7's the parent's 0.767 GB, cell 13's 0.336 (0.437 until PR 59, 0.673
    until PR 57); cell 5's
    0.883 for its 0.649 (one 294 MB buffer where two of 147 packed better
    into the heap; the live bytes are the parent's)."""
    if cell == 5:
        step = request.getfixturevalue("w2v_step")("kernels")
        lanes, w, width = W2V_BATCH * (W2V_NEG + 2), 2 * W2V_DIM, 640
        rows_in, calls, temp = [(lanes, w)] * 2, 2, 0.90 * GB
    elif cell == 7:
        step = request.getfixturevalue("ft_step")
        lanes, w, width = FT_BATCH * (FT_BAG + 6), FT_DIM, 384
        rows_in, calls, temp = [(lanes, w)] * 3, 3, 0.77 * GB
    else:
        step = request.getfixturevalue("glove_step")("kernels")
        lanes, w, width = 2 * GLOVE_BATCH, "602|301", "640|384"
        rows_in, calls, temp = [(store_mod._RULE_CHUNK, 602), (lanes, 301)], 2, 0.45 * GB
    assert step.memory.temp_size_in_bytes < temp
    text = step.text
    assert "remat" not in text
    made = dict(re.findall(r"^\s+(%[\w.\-]+) = (\S+) ", text, re.M))
    kernels = re.findall(
        r"^\s+%sorted_row_(?:update|assign)_tiles[\w.\-]* = \S+ custom-call\("
        r"([^)]*)\)", text, re.M)
    assert len(kernels) == calls, kernels
    handed = sorted(
        made[call.split(", ")[3]].split("{")[0] for call in kernels)
    assert handed == sorted(f"f32[{n},{lanes_}]" for n, lanes_ in rows_in), handed
    # no pass of its own over the batch's rows (ops of the entry and of the
    # rule's loop; what a fusion does inside is the op that makes the rows):
    # at the physical width only the pull's gather is left, and in cell 13
    # the rule's read, the combine's zeroed block and its sums
    ops = "\n".join(
        body for head, body in re.findall(
            r"^(?:ENTRY )?(%[\w.\-]+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S)
        if not head.startswith(("%fused_computation", "%region")))
    assert "custom-call(" in ops and " fusion(" in ops  # the filter kept them
    for n in {n for n, _ in rows_in} | {lanes // calls}:
        assert not re.search(
            rf"= f32\[{n},({w}|{width})\]\S* (pad|copy)\(", ops), (cell, n)
    if cell == 13:  # `_zero_masked`'s select over the pushed block, the merge
        assert not re.search(
            rf"= f32\[\d+,({w}|{width})\]\S* fusion\([^\n]*jit\(_where\)", ops)


def test_ft_step_holds_cell_7_s_table_once_beside_three_tile_kernel_calls(
        ft_step):
    """Cell 7's step as the chip runs it: the 300-lane row lies flat in
    three registers (``f32[7038744,384]{1,0:T(8,128)}``, 10.81 GB, the
    tightest table the system has held), the step updates it in place, its
    push takes the tile kernel at three registers a row (no refusal) in
    three calls for its 233,472 lanes, dead ones included, and nothing else
    yields or copies a table; under 1 GB of temporaries beside it."""
    assert ft_step.noted == 0
    mem = ft_step.memory
    assert mem.alias_size_in_bytes >= 10.81 * GB  # in place
    assert mem.temp_size_in_bytes < 1.0 * GB  # 0.77 GB here
    text = ft_step.text
    entry = text[text.index("ENTRY"):]
    tables = [
        line.strip() for line in entry.splitlines()
        if re.search(r" = f32\[7038744,", line)
    ]
    assert len(tables) == 4 and " parameter(" in tables[0], tables
    for call in tables[1:]:
        assert call.startswith("%sorted_row_update_tiles"), call
        assert "custom-call(" in call and "ps.push" in call, call
        assert "f32[7038744,384]{1,0:T(8,128)}" in call
    assert " copy(" not in "".join(tables)
    assert "ps.push/scatter-add" not in text
    assert "ps.compute/ps.bag_pool" in text and "ps.compute/ps.delta_build" in text


@pytest.mark.parametrize("arm", ["kernels", "xla"])
def test_glove_step_holds_nothing_table_sized_beside_its_table(
        arm, glove_step):
    """Cell 13's step at full size for a described v5e: the donated 11.24 GB
    table is rewritten in place and never copied or transposed, with every
    scope the cell's metrics read.  As the chip runs it (``kernels``): under
    ``ps.pull`` ONE gather of whole physical rows ``f32[65536,640]`` (the
    TPU's gather moves whole rows: a window of the three registers that
    hold the worker's 301 lanes compiles to a loop over the ids, a slice of
    the table in front of it to a copy of 6.7 GB, PR 59) cut to the worker's
    part, ``f32[65536,301]``; under
    ``ps.push/ps.combine`` the permute of the batch's gradient rows, 301
    lanes wide, and ONE ``sorted_row_update_tiles`` call into a zeroed
    ``f32[65536,384]`` block of THREE registers;
    in the rule's loop ONE gather ``f32[32768,640]`` under ``ps.rule`` and
    ONE ``sorted_row_assign_tiles`` call on the table, the write-back.  What
    the step holds beside the table goes with the batch: 0.67 GB.  Off the
    TPU (``xla``) the same layout with one scatter-add for the sums and one
    row ``set`` of ``f32[32768,640]`` for the write-back, no kernel."""
    step = glove_step(arm)
    mem = step.memory
    assert 11.24 * GB < mem.alias_size_in_bytes < 11.25 * GB  # in place
    assert mem.temp_size_in_bytes < 1.0 * GB
    text = step.text
    assert not re.search(r"f32\[4392040,640\]\S* (copy|transpose)\(", text)
    assert "f32[4392034,602]" not in text and "f32[4392040,602]" not in text
    for scope in ("ps.pull", "ps.compute/ps.cooc_grad_rows", "ps.push/ps.combine",
                  "ps.push/while/body/ps.rule"):
        assert scope in text, scope
    lines = text.splitlines()
    pulls = [c for c in lines if re.search(r" gather\(", c)
             and " f32[65536,640]{1,0" in c and "ps.pull" in c]
    assert len(pulls) == 1 and "slice_sizes={1,640}" in pulls[0], pulls
    reads = [c for c in lines if re.search(r" gather\(", c)
             and " f32[32768,640]{1,0" in c]
    assert len(reads) == 1 and "ps.push/while/body/ps.rule" in reads[0], reads
    kernels = [line for line in lines if "tpu_custom_call" in line]
    scatters = [line for line in lines if re.search(r" scatter\(", line)]
    if arm == "xla":
        assert not kernels
        sets = [c for c in scatters if " f32[4392040,640]" in c]
        assert len(sets) == 1 and "ps.push/while/body" in sets[0], scatters
        # (the sums at the width pushed, the worker's 301 lanes: no pad in
        # front since PR 57, no accumulators' lanes since PR 59)
        sums = [c for c in scatters if " f32[65536,301]" in c]
        assert len(sums) == 1 and "ps.push/ps.combine" in sums[0], scatters
        return
    assert not scatters
    names = sorted(k.strip().split(" ", 1)[0].rstrip(".0123456789") for k in kernels)
    assert names == ["%sorted_row_assign_tiles", "%sorted_row_update_tiles"], names
    by_name = {k.strip().split(".", 1)[0]: k for k in kernels}
    assert " f32[4392040,640]{1,0" in by_name["%sorted_row_assign_tiles"]
    assert "ps.push/while/body" in by_name["%sorted_row_assign_tiles"]
    assert " f32[65536,384]{1,0" in by_name["%sorted_row_update_tiles"]
    assert "ps.push/ps.combine" in by_name["%sorted_row_update_tiles"]
    # the worker's part alone crosses: the 602-lane rows are the rule's
    assert not re.search(r"f32\[(65536|32768,2),60[02]\]", text)
    assert "f32[65536,301]" in text and "f32[32768,602]" in text


def test_w2v_table_is_initialised_in_place_from_a_seed_argument(
        one_chip, w2v, no_compile_cache):
    """``make_store`` under a ``jit`` that takes the seed: one program
    whatever the seed, the 7.68 GB table initialised block by block with no
    second table beside it."""
    _, _, w2vm = w2v
    compiled = jax.jit(
        lambda seed: w2vm.make_store(W2V_VOCAB, W2V_DIM, seed=seed)
    ).lower(_shape(one_chip, (), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert 7.68 * GB <= mem.output_size_in_bytes < 7.69 * GB
    assert mem.temp_size_in_bytes < 1.0 * GB  # 0.67 GB here: one block


# lr-ftrl-criteo-40m (chipbench/configs): cell 6's table, (w, z, n) f32 rows
# under a rule that is not "add", dense, the row held at FOUR lanes (the
# sublane tile the chip pads three to anyway) and whole tiles of 128 rows:
# f32[187767424,4]{0,1:T(4,128)}, 3.00 GB
LR_ROWS = 187_767_412
LR_TABLE = (187_767_424, 4)
LR_ROWS_HELD = LR_TABLE[0]


@pytest.fixture(scope="module")
def lr():
    from flink_parameter_server_tpu.models import logistic_ftrl as lf

    spec = jax.eval_shape(lambda: lf.make_store(LR_ROWS)).spec
    assert spec.layout == "dense" and spec.table_shape() == LR_TABLE
    return spec, lf.LogisticFTRL()


@pytest.fixture(scope="module")
def lr_step(lr, one_chip):
    """``arm -> CellStep``: cell 6's step at 187,767,412 rows for a described
    v5e, compiled ONCE an arm for the tests that read it: ``kernels``, as
    the chip runs it (code that asks for the backend still sees the CPU
    here: it is steered); ``xla``, the arms every other backend runs."""
    spec, logic = lr

    @functools.cache
    def built(arm):
        backend = BACKEND[arm]
        if arm == "kernels":
            with _compiling_for_described_chips(backend):
                assert store_mod.arms(spec) == store_mod.Arms(
                    "narrow_distinct", "rule", "", "sort", "tile_set", False)
        return CellStep(
            make_train_step(logic, spec),
            _shape(one_chip, spec.table_shape(), jnp.float32), (),
            _fm_batch(one_chip), backend=backend)

    return built


def test_lr_step_holds_nothing_table_sized_beside_its_table(lr_step):
    """Cell 6's step as the chip runs it (asked for the backend, the
    write-back takes ``ops/row_update.sorted_tile_set``): the donated table
    is rewritten in place through the rule arm's loop, the kernel under
    ``ps.push`` is the only op that yields a table, both transposes round it
    are bitcasts, nothing copies or transposes the table, no XLA scatter is
    left on it, both gathers read a 3-lane window of the 4-lane row, and
    what the step holds beside the table goes with the batch: 0.04 GB
    (0.105 since PR 70: the distinct rows the pull leaves for the rule and
    the sorts' operands)."""
    step = lr_step("kernels")
    text, mem = step.text, step.memory
    assert step.noted == 0
    assert 2.9 * GB < mem.alias_size_in_bytes < 3.1 * GB  # in place, 4 sublanes
    assert mem.temp_size_in_bytes < 0.2 * GB
    rows, lanes = LR_TABLE
    # (the kernel's view of it since PR 72: its tiles one after another)
    table = rf"f32\[({rows},{lanes}|{rows // 128},{lanes},128)\]"
    yields = [
        line.strip() for line in text.splitlines()
        if re.search(rf" = {table}\S* (?!parameter|get-tuple-element)", line)
    ]
    kernels = [y for y in yields if " custom-call(" in y]
    assert len(kernels) == 1 and kernels[0].startswith("%sorted_row_set_tiles")
    assert "ps.push/while/body" in kernels[0]
    # (PR 72) the spans' buffers are the kernel's own VMEM: three of 8 x 128
    # + 16 tiles of 2 KB, a span of eight tiles at 0.87 lanes a tile
    assert f'"size":"{3 * (8 * 128 + 16) * 2048}"' in kernels[0]
    # what else yields a table only names it anew
    assert all(" bitcast(" in y for y in yields if y not in kernels), yields
    assert not re.search(table + r"\S* (copy|transpose|scatter)\(", text)
    assert "scatter" not in "".join(
        line for line in text.splitlines() if "ps.push/while" in line
    )
    # the pull's loop over the distinct rows, and the rule's
    assert len(re.findall(r" while\(", text)) == 2
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert len(gathers) == 2 and all(
        "slice_sizes={1,3}" in g and " = f32[32768,3]" in g for g in gathers
    ), gathers


def test_lr_step_reads_each_distinct_row_once_for_the_logic_and_the_rule(
        lr_step):
    """Since PR 70 the pull reads the batch's DISTINCT rows once
    (``core/store._distinct_pull``): no gather yields ``f32[1277952,3]``;
    the two gathers left are a chunk of 32,768 rows each, the pull's inside
    its loop and the rule's own read below the BRANCH a step takes only
    where a masked or clipped lane left the push fewer distinct ids than
    the pull read: below the other branch, the one every step of cell 6
    takes, nothing gathers, the rule's rows are a ``dynamic-slice`` of what
    the pull left.  The sorts: three under ``ps.pull`` (the keys with their
    places; the distinct ids, ONE operand: unstable, it carries no iota; the
    way back, which carries ONE lane of the
    three, the compiler having dropped the two FTRL's worker never reads),
    the combine's two untouched, each with the three lanes of the deltas.
    The scopes ``ps.pull``, ``ps.push/ps.combine`` and ``ps.rule`` stand
    round what is left of each."""
    text = lr_step("kernels").text
    for scope in ("ps.pull", "ps.push/ps.combine",
                  "ps.push/while/body/ps.rule"):
        assert scope in text, scope
    lanes = FM_BATCH * FM_FIELDS
    sorts = sorted(
        (re.search(r'op_name="([^"]*)"', line).group(1),
         len(re.findall(rf"[sf]32\[{lanes}\]", line.split(" sort(")[0])))
        for line in text.splitlines()
        if re.search(rf" = \(?[sf]32\[{lanes}\]\S* .*sort\(", line))
    assert sorts == [
        ("jit(step)/ps.pull/sort", 1), ("jit(step)/ps.pull/sort", 2),
        ("jit(step)/ps.pull/sort", 2),
        ("jit(step)/ps.push/ps.combine/sort", 5),
        ("jit(step)/ps.push/ps.combine/sort", 5)], sorts
    assert not re.search(rf" = f32\[{lanes},\d\]\S* gather\(", text)
    bodies, _ = _computations(text)

    def below(name):
        # `name` and every computation it calls, however deep
        found, new = set(), {name}
        while new:
            found |= new
            new = {
                callee for n in new for line in bodies[n]
                for callee in re.findall(r"%[\w.\-]+", line.split(" = ", 1)[1])
                if callee in bodies} - found
        return found

    # (a gather inside the jitted `_narrow_pull` carries that name, not the
    # scope's: where it runs is read from who calls its computation)
    gathers = {name for name, lines in bodies.items()
               if any(" gather(" in line for line in lines)}
    assert len(gathers) == 2
    conditional = [
        line for line in text.splitlines() if " conditional(" in line]
    assert len(conditional) == 1 and "ps.rule/cond" in conditional[0]
    branches = re.search(
        r"branch_computations=\{([^}]*)\}", conditional[0]
    ).group(1).split(", ")
    reads = [b for b in branches if below(b) & gathers]
    slices = [b for b in branches if not below(b) & gathers]
    assert len(reads) == 1 and len(slices) == 1, branches
    assert any(
        " dynamic-slice(" in line
        for name in below(slices[0]) for line in bodies[name])
    # the other gather is the pull's, a chunk a trip of ITS loop
    loops = {
        re.search(r"body=(%[\w.\-]+)", line).group(1):
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in text.splitlines() if " while(" in line}
    assert sorted(loops.values()) == [
        "jit(step)/ps.pull/while", "jit(step)/ps.push/while"]
    pulls = [body for body, name in loops.items() if "ps.pull" in name]
    assert below(pulls[0]) & gathers == gathers - below(reads[0])
    assert len(below(pulls[0]) & gathers) == 1


def test_lr_step_off_the_tpu_keeps_xlas_row_set_in_place(lr, lr_step):
    """The arm every other backend runs, and a rule store the kernel does
    not take: XLA's row ``set`` of the padded rows into the same 4-lane
    table, in place, nothing table-sized beside it."""
    spec, _ = lr
    assert store_mod.arms(spec).write_back == "xla_set"  # this is a CPU
    step = lr_step("xla")
    mem = step.memory
    assert 2.9 * GB < mem.alias_size_in_bytes < 3.1 * GB
    assert mem.temp_size_in_bytes < 0.2 * GB
    text = step.text
    assert "sorted_row_set_tiles" not in text
    assert not re.search(r"f32\[%d,%d\]\S* copy\(" % LR_TABLE, text)


def test_placing_cell_6_s_values_pads_rows_and_lanes_in_one_pass(
        lr, one_chip, no_compile_cache):
    """``from_spec_values`` of the benchmark's seeded ``f32[187767412,3]``:
    the 12 rows and the fourth lane are padded in one program whose only
    output is the table, so set-up holds the values and the table (6.01 GB)
    and no third."""
    spec, _ = lr
    mem = store_mod._pad_to_tiles.lower(
        _shape(one_chip, (LR_ROWS, 3), jnp.float32), *LR_TABLE
    ).compile().memory_analysis()
    assert 3.0 * GB < mem.output_size_in_bytes < 3.01 * GB
    assert mem.temp_size_in_bytes < 0.01 * GB


@pytest.mark.parametrize("rows,lanes,width,pushed,span", [
    (LR_ROWS_HELD, 1, 1, None, 1), (LR_ROWS_HELD, 2, 2, None, 1),
    (LR_ROWS_HELD, 4, 3, None, 1), (LR_ROWS_HELD, 4, 4, None, 1),
    (LR_ROWS_HELD, 8, 5, None, 1), (LR_ROWS_HELD, 8, 8, None, 1),
    # as the cells' pushes call it (PR 72): cell 6's 1,277,952 lanes on
    # 1,466,933 tiles, cell 17's 851,968 on 212,992; the widest span at the
    # widest row
    (LR_ROWS_HELD, 4, 3, 1_277_952, 8), (27_262_976, 4, 3, 851_968, 32),
    (27_262_976, 8, 8, 851_968, 32),
])
def test_set_kernel_compiles_at_cell_6_s_size_for_every_row_it_takes(
        one_chip, no_compile_cache, rows, lanes, width, pushed, span):
    """A chunk of 32,768 sorted ids into 187,767,424 rows of 1 to 8 lanes
    (cell 17's 27,262,976 of 4): the table ``{0,1:T(L,128)}`` is the
    kernel's ``(rows / 128, L, 128)`` by bitcasts, aliased through its calls
    (two where a lane's scalars, 2 + 8 words, are too many for one call's
    SMEM), no temporary worth the name; a chunk alone is a short push into a
    long table and copies tile by tile, a chunk of a cell's push copies
    spans of the width ``set_span`` reads, whose three buffers (25 MB at 32
    tiles of 4 lanes, 50 at 8) Mosaic takes."""
    assert row_update.set_span(rows // 128, pushed or 32_768) == span
    compiled = jax.jit(
        lambda t, ids, new: row_update.sorted_tile_set(
            t, ids, new, of=pushed, interpret=False),
        donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (rows, lanes), jnp.float32),
        _shape(one_chip, (32_768,), jnp.int32),
        _shape(one_chip, (32_768, width), jnp.float32),
    ).compile()
    text = compiled.as_text()
    found = re.findall(r" custom-call\([^\n]*sorted_row_set_tiles", text)
    assert len(found) == (2 if width > 4 else 1), len(found)
    assert not re.search(
        r"f32\[(\d{8,9},\d|\d{6,7},\d,128)\]\S* (copy|transpose)\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= rows * lanes * 4
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


def test_mosaic_takes_no_tile_of_a_three_lane_table(
        one_chip, no_compile_cache, monkeypatch):
    """Why the physical row is four lanes and not three.  The chip lays
    ``f32[rows,3]`` ``{0,1:T(4,128)}`` too, four sublanes of which the array
    has three, and until PR 72 Mosaic refused the slice of a tile of the
    ``(3, rows)`` view ("aligned to tiling": ISSUE 35's probe).  The view
    ``(rows / 128, 3, 128)`` has no such slice (a copy takes whole tiles by
    their number) and compiles, in place, by bitcasts; but it saves nothing:
    the three-lane table holds the four-lane table's bytes, so the store's
    row stays the chip's sublane tile and ``set_refusal`` still names three
    lanes."""
    assert "(3,)" in row_update.set_refusal((LR_TABLE[0], 3), jnp.float32)
    monkeypatch.setattr(row_update, "SET_ROW_LANES", (1, 2, 3, 4, 8))
    compiled = jax.jit(
        lambda t, ids, new: row_update.sorted_tile_set(
            t, ids, new, interpret=False),
        donate_argnums=(0,),
    ).lower(
        _shape(one_chip, (LR_TABLE[0], 3), jnp.float32),
        _shape(one_chip, (32_768,), jnp.int32),
        _shape(one_chip, (32_768, 3), jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert "f32[187767424,3]{0,1:T(4,128)}" in text
    assert not re.search(r"f32\[\d{7,9},[\d,]+\]\S* (copy|transpose)\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes == (
        LR_TABLE[0] * 4 * 4)


def test_the_capacity_arm_holds_tables_beside_cell_6_s_table(
        lr, one_chip, no_compile_cache):
    """What ``push`` ran for a custom ``update`` until PR 34: duplicates
    combined into a zeroed TABLE, the rule over the whole table, a
    table-sized ``where``.  At cell 6's size that is table-sized
    temporaries beside the table (and a walk of 187.8 M rows to change
    355 k), where the batch-sized arm holds 0.04 GB."""
    spec, _ = lr

    def capacity_arm(table, ids, deltas):
        combined = jnp.zeros_like(table).at[ids].add(deltas, mode="drop")
        counts = jnp.zeros((table.shape[0],), jnp.int32).at[ids].add(
            1, mode="drop"
        )
        return jnp.where(
            (counts > 0)[:, None], spec.update(table, combined), table
        )

    lanes = FM_BATCH * FM_FIELDS
    try:
        mem = jax.jit(capacity_arm, donate_argnums=(0,)).lower(
            _shape(one_chip, (LR_ROWS + 4, 3), jnp.float32),  # as it lay then
            _shape(one_chip, (lanes,), jnp.int32),
            _shape(one_chip, (lanes, 3), jnp.float32),
        ).compile().memory_analysis()
    except Exception as e:  # refused outright
        assert "RESOURCE_EXHAUSTED" in str(e)
    else:
        assert mem.temp_size_in_bytes > 3.0 * GB  # a table, or more


# difacto-criteo-10m (chipbench/configs): cell 9's rule store, 36 f32 lanes a
# row (w, z, s, c, V[16], S[16]); since PR 47 packed three to a 128-lane
# physical row, 16,375,440 x 128 f32 = 8.384 GB (dense: rows-minor, 40 sublanes)
DF_ROWS, DF_LANES, DF_PHYS_ROWS = 49_126_310, 36, 16_375_440


@pytest.fixture(scope="module")
def difacto():
    """``difacto-criteo-10m`` as ``chipbench/families/difacto.py`` builds it:
    the configuration's rule, ``make_store``'s own layout."""
    from chipbench import spec as bench_spec
    from chipbench.families import difacto as fam
    from flink_parameter_server_tpu.models import difacto as dfm

    cfg = bench_spec.load_json("chipbench/configs/difacto-criteo-10m.json")
    rule = dfm.DiFactoUpdater(**{k: float(cfg[k]) for k in fam.RULE_KEYS})
    model = dfm.DiFactoConfig(DF_ROWS, 16)
    assert cfg["num_features"] == DF_ROWS and model.row_lanes == DF_LANES
    return cfg, rule, model, fam, dfm


@pytest.mark.parametrize("layout", ["auto", "dense"])
def test_difacto_table_is_initialised_in_place_block_by_block(
        layout, difacto, one_chip, no_compile_cache):
    """The seeded warm start of 49,126,310 x 36 f32 rows under a ``jit`` that
    takes the key.  As ``make_store`` resolves it (``auto``: packed) the
    8.384 GB table ``f32[16375440,128]`` is the program's only output,
    initialised and packed ``core/store._PACK_CHUNK`` physical rows a loop
    step, 0.20 GB of temporaries; pinned dense it is PR 45's 7.86 GB table
    (36 lanes down 40 sublanes), ``core/store._INIT_BLOCK`` rows a step (all
    rows at once the same init asked 11.8 GB of temporaries)."""
    cfg, rule, model, fam, dfm = difacto
    compiled = jax.jit(lambda key: dfm.make_store(
        model, rule, init_fn=fam.warm_rows(cfg, rule, key), dtype=jnp.float32,
        layout=layout,
    ).table).lower(_shape(one_chip, (2,), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    if layout == "auto":
        assert mem.output_size_in_bytes == DF_PHYS_ROWS * 128 * 4
        assert 8.38 * GB < mem.output_size_in_bytes < 8.39 * GB
    else:
        assert 7.85 * GB < mem.output_size_in_bytes < 7.87 * GB
    assert mem.temp_size_in_bytes < 0.5 * GB
    assert len(re.findall(r" while\(", compiled.as_text())) >= 1


@pytest.fixture(scope="module")
def difacto_step(difacto, one_chip):
    """``arm -> CellStep``: cell 9's step at full size for a
    described v5e, compiled once an arm (``kernels``: as the chip runs it,
    asked for the backend; ``xla``: what a CPU reads)."""
    _, rule, model, _, dfm = difacto
    spec = jax.eval_shape(
        lambda: dfm.make_store(model, rule, dtype=jnp.float32)
    ).spec

    @functools.cache
    def built(arm):
        return CellStep(
            make_train_step(dfm.DiFacto(model, rule), spec),
            _shape(one_chip, spec.table_shape(), jnp.float32), (),
            _fm_batch(one_chip), backend=BACKEND[arm],
            spec=spec)

    return built


@pytest.mark.parametrize("arm", ["kernels", "xla"])
def test_difacto_step_holds_nothing_table_sized_beside_its_table(
        arm, difacto_step, monkeypatch):
    """Cell 9's step at full size for a described v5e, the table packed
    three 36-lane rows to a physical row (``f32[16375440,128]{1,0:T(8,128)}``,
    8.384 GB): the donated table is rewritten in place and never copied or
    transposed, with every scope the cell's metrics read, and NO gather or
    scatter of 36-lane rows of the table anywhere.  As the chip runs it
    (asked for the backend: ``kernels``): under ``ps.pull`` ONE gather of
    whole physical rows (``slice_sizes={1,128}``) and ONE
    ``packed_lane_slice_turned`` call (PR 63: the logic takes its rows TURNED,
    ``models/factorization_machine.FieldLanes``; ``packed_lane_slice`` and
    ``f32[20,1277952]`` until then) that hands the logic
    ``f32[20,39,32768]``, its own layout of ``(K, B, 20)``, through bitcasts
    alone: the WORKER'S PART of the
    rows (``StoreSpec.worker_width``, PR 59: the 36-lane rows of ``S`` never
    leave the rule's loop; the logic's gradient rows are ``f32[1277952,20]``
    and no array of the step outside that loop is 36 lanes wide); under ``ps.push/
    ps.combine`` ``sorted_run_sums`` inside the loop over thirteen stretches
    (PR 62: the row kernel under the DENSE plan, 98,304 lanes a call into the
    zeroed ``f32[1277952,128]`` block, a row a lane, which the rule's loop
    reads in place: no slice of it; PR 46's ``sorted_row_update`` until
    then, with a second pipelined input of zeros and, since PR 54, a ``sort
    s32[384,256]`` a call that are gone); in the rule's loop ONE gather ``f32[32768,128]`` of the
    chunk's physical rows under ``ps.rule`` and ONE ``sorted_row_set`` call,
    the write-back of whole physical rows.  What the step holds beside the
    table goes with the batch: 1.33 GB (the padded rows and the zeroed block
    of the combine, 0.654 GB each; the pulled physical rows, 0.654 GB, are
    dead before them).  Off the TPU (``xla``) the same layout with XLA's
    selects, one scatter-add for the sums and one row ``set`` of
    ``f32[32768,128]`` for the write-back, no kernel."""
    step = difacto_step(arm)
    spec = step.spec
    assert spec.layout == "packed" and spec.pack == 3 and not spec.narrow_rule
    assert spec.table_shape() == (DF_PHYS_ROWS, 128)
    n = FM_BATCH * FM_FIELDS
    assert store_mod.arms(spec, pull_lanes=n) == store_mod.Arms(
        "packed_selects", "rule", "", "scatter_add", "xla_set", False)  # a CPU
    if arm == "kernels":
        # code that asks for the backend still sees the CPU here: steer it
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        n0 = row_update.refusal_count()
        kernels = store_mod.Arms(
            "packed_kernel", "rule", "", "row_kernel", "row_set", False)
        assert store_mod.arms(spec, pull_lanes=n) == kernels
        assert store_mod.arms(spec) == kernels  # the import is preloaded
        # (the step's logic declares its block: the slice a field at a time)
        assert store_mod.arms(
            spec, pull_lanes=n, fields=FM_FIELDS
        ).pull == "packed_kernel_by_field"
        assert row_update.refusal_count() == n0
    assert step.noted == 0
    mem = step.memory
    assert 8.38 * GB < mem.alias_size_in_bytes < 8.39 * GB  # in place
    text = step.text
    assert not re.search(r"f32\[16375440,128\]\S* (copy|transpose)\(", text)
    assert "f32[49126312,36]" not in text and "f32[16375440,36]" not in text
    for scope in ("ps.pull", "ps.compute/ps.gate", "ps.compute/ps.delta_build",
                  "ps.push/ps.combine", "ps.push/while/body/ps.rule"):
        assert scope in text, scope
    lines = text.splitlines()
    # every gather and scatter that names the table moves whole registers
    pulls = [c for c in lines if re.search(r" gather\(", c)
             and f" f32[{n},128]{{1,0" in c]
    assert len(pulls) == 1 and "slice_sizes={1,128}" in pulls[0], pulls
    assert 'op_name="jit(step)/ps.pull/' in pulls[0]
    reads = [c for c in lines if re.search(r" gather\(", c)
             and " f32[32768,128]{1,0" in c]
    assert len(reads) == 1 and "slice_sizes={1,128}" in reads[0], reads
    assert "ps.push/while/body/ps.rule" in reads[0]
    assert not any(re.search(r"f32\[\d+,36\]\S* (gather|scatter)\(", c)
                   and "16375440" in c for c in lines)
    kernels = [line for line in lines if "tpu_custom_call" in line]
    scatters = [line for line in lines if re.search(r" scatter\(", line)]
    if arm == "xla":
        assert mem.temp_size_in_bytes < 1.6 * GB
        assert not kernels
        sets = [c for c in scatters if "f32[16375440,128]" in c]
        assert len(sets) == 1 and "ps.push/while/body" in sets[0], scatters
        assert "ps.rule" not in sets[0] and "ps.combine" not in sets[0]
        return
    assert 1.2 * GB < mem.temp_size_in_bytes < 1.5 * GB  # 1.327 here
    assert not scatters
    names = sorted(k.strip().split(" ", 1)[0].rstrip(".0123456789") for k in kernels)
    assert names == [
        "%packed_lane_slice_turned", "%sorted_row_set", "%sorted_run_sums"]
    by_name = {k.strip().split(".", 1)[0]: k for k in kernels}
    slice_call = by_name["%packed_lane_slice_turned"]
    assert f" = f32[20,{FM_FIELDS},{FM_BATCH}]{{2,1,0:" in slice_call
    assert 'op_name="jit(step)/ps.pull/' in slice_call
    _reaches_the_logic_through_bitcasts(text, slice_call, 20)
    # the worker's part alone crosses: outside the rule's loop (its chunk
    # of 32,768 whole rows) nothing is 36 lanes wide
    assert not re.search(rf"f32\[(36,{n}|{n},36|36,{FM_BATCH},{FM_FIELDS}"
                         rf"|{FM_BATCH},{FM_FIELDS},36)\]", text)
    assert f"f32[{n},20]" in text
    set_call = by_name["%sorted_row_set"]
    assert " f32[16375440,128]{1,0" in set_call
    assert "ps.push/while/body" in set_call and "ps.rule" not in set_call
    assert "ps.combine" not in set_call
    sums_call = by_name["%sorted_run_sums"]
    assert " f32[1277952,128]{1,0" in sums_call
    assert "ps.push/ps.combine/while/body" in sums_call
    # the rule's loop cuts its chunks out of the block where it lies
    assert not re.search(rf"f32\[{n},20\]\S* slice\(", text)
    # the dense plan sorts nothing: the batch's two sorts are `_wide_runs`'
    assert not re.search(r"s32\[384,256\]\S* sort\(", text)
    # the rule's loop and the stretches': no flatten is a loop since PR 63
    assert len(re.findall(r" while\(", text)) == 2
    _no_block_of_the_batch_has_its_fields_minor(text)
    # both end with what is live: the rule's with the last distinct row,
    # since PR 54 the stretches' with the last live lane.  A `fori_loop` of
    # a constant trip count is traced as a `scan`; the combine's is a `while`
    from flink_parameter_server_tpu.ops import dedup

    jaxpr = jax.make_jaxpr(lambda i, v: dedup.combine_runs(
        i, v, spec.padded_capacity, "row_kernel", interpret=False)
    )(jax.ShapeDtypeStruct((FM_BATCH * FM_FIELDS,), jnp.int32),
      jax.ShapeDtypeStruct((FM_BATCH * FM_FIELDS, 36), jnp.float32))
    loops = [e.primitive.name for e in jaxpr.eqns
             if e.primitive.name in ("scan", "while")
             and "sorted_run_sums" in str(e.params)]
    assert loops == ["while"]


@pytest.fixture(scope="module")
def difacto_ps4_step(difacto, topo):
    """``arm -> CellStep``: cell 12's step at full size for four
    described chips, compiled once an arm (``kernels``: as the chips run it,
    asked for the backend; ``xla``: what a CPU reads)."""
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    cfg, rule, _, fam, dfm = difacto

    @functools.cache
    def built(arm):
        mesh = make_mesh(1, 4, devices=topo.devices)
        model = dfm.DiFactoConfig(FM_ROWS, 16)  # the 40 M record's rows
        spec = jax.eval_shape(
            lambda: dfm.make_store(model, rule, mesh=mesh, dtype=jnp.float32)
        ).spec
        assert spec.layout == "packed" and spec.pack == 3
        assert spec.table_shape() == (4 * 15_647_288, 128)
        assert store_mod.arms(spec).on_shards
        backend = BACKEND[arm]
        if arm == "kernels":
            with _compiling_for_described_chips(backend):
                n0 = row_update.refusal_count()
                assert store_mod.arms(spec) == store_mod.Arms(
                    "packed_kernel", "rule", "", "row_kernel", "row_set", True)
                assert store_mod.arms(
                    spec, pull_lanes=FM_BATCH * FM_FIELDS, fields=FM_FIELDS
                ).pull == "packed_kernel_by_field"
                assert row_update.refusal_count() == n0
        return CellStep(
            make_train_step(dfm.DiFacto(model, rule), spec),
            _shape(spec.sharding(), spec.table_shape(), jnp.float32), (),
            _fm_batch(NamedSharding(mesh, PartitionSpec())), backend=backend)

    return built


@pytest.mark.parametrize("arm", ["kernels", "xla"])
def test_difacto_step_on_four_chips_runs_its_rule_on_the_shard_that_owns_the_row(
        arm, difacto_ps4_step):
    """Cell 12's step at full size for four described chips: cell 9's rows
    at cell 4's record, 187,767,412 x 36 f32 packed three to a physical row,
    ``f32[15647288,128]`` (8.011 GB) a chip.  The push is ONE ``shard_map``
    (``core/store._push_rule_on_shards``): every chip's block rewritten in
    place by the calls a one-place packed store gets (``sorted_run_sums``
    under ``ps.combine``, ``sorted_row_set`` in the rule's loop; off the TPU
    XLA's scatter-add and row ``set`` ON THE BLOCK, nothing partitioned by
    GSPMD), 1.33 GB of temporaries a chip, and the step's only collectives
    are the pull's all-reduce of ``f32[39,32768,20]``, the worker's part of
    the rows (PR 59: 20 lanes of a row's 36) TURNED, the batch its minor
    axis (PR 63: 105 MB as tiled where ``f32[32768,39,20]``, 39 fields padded
    to 128 lanes, was 336), and the push's counts, a tile a shard: no row
    of the table and no key crosses chips.  The rule's loop and the stretches'
    are the step's two ``while``: no flatten is a loop since PR 63."""
    step = difacto_ps4_step(arm)
    text, mem = step.text, step.memory
    _every_transfer_carries_the_programs_name("cell_12", step)
    assert 8.01 * GB < mem.alias_size_in_bytes < 8.02 * GB  # in place, a chip
    assert mem.temp_size_in_bytes < 1.6 * GB  # 1.327 with the kernels
    lines = text.splitlines()
    assert not re.search(r"f32\[15647288,128\]\S* (copy|transpose)\(", text)
    assert "f32[62589152,128]" not in text  # no chip ever sees the whole table
    collectives = [c for c in lines if re.search(
        r" (all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)"
        r"(-start)?\(", c)]
    shapes = sorted(c.strip().split(" = ", 1)[1].split("{")[0] for c in collectives)
    # (six counts a shard since PR 54's `ps_combine_kernel_writes`, in the
    # first lanes of one whole tile a shard since PR 69, which keeps the
    # gather an `all-gather` with its `mesh.push_counts_gather` name: the 24
    # numbers alone it rewrote as `all-reduce s32[24]` under none)
    assert shapes == ["f32[39,32768,20]", "s32[4,8,128]"], collectives
    assert len(re.findall(r" while\(", text)) == 2
    for scope in ("ps.pull", "ps.push/shard_map/ps.combine",
                  "ps.push/shard_map/while/body/ps.rule"):
        assert scope in text, scope
    kernels = [line for line in lines if "tpu_custom_call" in line]
    scatters = [line for line in lines if re.search(r" scatter\(", line)]
    if arm == "xla":
        # the one-place arms on each shard's own block
        assert all(" f32[15647288,128]" in c or " f32[1277952,20]" in c
                   for c in scatters) and len(scatters) == 2, scatters
        assert not kernels
        return
    assert not scatters
    names = sorted(k.strip().split(" ", 1)[0].rstrip(".0123456789") for k in kernels)
    assert names == [
        "%packed_lane_slice_turned", "%sorted_row_set", "%sorted_run_sums"]
    # (the selects' arm turns what it sliced: `f32[20,32768,39]` is there)
    _no_block_of_the_batch_has_its_fields_minor(text)
    by_name = {k.strip().split(".", 1)[0]: k for k in kernels}
    assert " f32[15647288,128]{1,0" in by_name["%sorted_row_set"]
    assert "ps.push/shard_map/while/body" in by_name["%sorted_row_set"]
    assert " f32[1277952,128]{1,0" in by_name["%sorted_run_sums"]
    assert "ps.push/shard_map/ps.combine/while/body" in by_name["%sorted_run_sums"]


@pytest.mark.parametrize("rows, lanes, sorted_form", [
    (FM1_PHYS_ROWS, 1_277_952, True),  # cell 2: XLA keeps it, 21.8 ns a lane
    (FM1_PHYS_ROWS, 877_257, True),  # 8 lanes over the table's rows
    (FM1_PHYS_ROWS, 877_256, False),  # lanes x 8 = rows
    (FM1_PHYS_ROWS, 851_968, False),
    (6_815_744, 851_968, False),  # lanes x 8 = rows
    (24_563_152, 851_968, False),  # cell 10: the kernel, XLA's is 74.7 ns
    (24_563_152, 3_100_000, True),
])
def test_the_compilers_cut_that_SERIAL_SCATTER_ROWS_A_LANE_stands_on(
        one_chip, no_compile_cache, rows, lanes, sorted_form):
    """The COMPILER's cut that ``core/store._SERIAL_SCATTER_ROWS_A_LANE``
    stands on, on the plain op for a described v5e: the TPU compiler gives
    ``table.at[ids].add(deltas, mode="drop")`` (float32, 128 lanes) its
    sorted form, a ``sort`` of the ids and ``indices_are_sorted=true`` on
    the scatter (13-22 ns a lane on the chip), exactly when the batch has
    more than an eighth as many lanes as the operand has rows, and leaves it
    serial (74.7 ns a lane) at or under that.  ``core/store.arms`` sends
    a one-register push to the tile kernel on the serial side alone.  A
    libtpu that moves the cut fails HERE: then measure both arms on the new
    side (PERF.md section 6, PR 49) and move the constant."""
    assert sorted_form == (
        lanes * store_mod._SERIAL_SCATTER_ROWS_A_LANE > rows)
    text = jax.jit(
        lambda t, i, d: t.at[i].add(d, mode="drop"), donate_argnums=(0,)
    ).lower(
        _shape(one_chip, (rows, 128), jnp.float32),
        _shape(one_chip, (lanes,), jnp.int32),
        _shape(one_chip, (lanes, 128), jnp.float32),
    ).compile().as_text()
    scatters = [c for c in text.splitlines() if re.search(r" scatter\(", c)]
    assert len(scatters) == 1 and f"f32[{rows},128]" in scatters[0]
    assert ("indices_are_sorted=true" in scatters[0]) == sorted_form
    assert bool(re.search(r" sort\(", text)) == sorted_form


# dlrm-criteo-10m (chipbench/configs): cell 10's add-store, 49,126,297 rows of 64
# f32 lanes packed two to a 128-lane physical row: 24,563,152 x 128 f32, 12.58 GB
# of a 16 GB chip; the MLPs (762,177 f32) in the worker's state
DLRM_ROWS, DLRM_PHYS_ROWS, DLRM_FIELDS = 49_126_297, 24_563_152, 26


@pytest.fixture(scope="module")
def dlrm_cell():
    """``dlrm-criteo-10m`` as ``chipbench/families/dlrm.py`` builds it."""
    from chipbench import spec as bench_spec
    from flink_parameter_server_tpu.models import dlrm

    cfg = bench_spec.load_json("chipbench/configs/dlrm-criteo-10m.json")
    model = dlrm.DLRMConfig(tuple(cfg["field_cardinalities"]))
    assert model.num_rows == DLRM_ROWS and cfg["dim"] == model.dim == 64
    return cfg, model, dlrm


def test_dlrm_table_is_initialised_in_place_two_rows_to_a_physical_row(
        dlrm_cell, one_chip, no_compile_cache):
    """The seeded init of 49,126,297 x 64 f32 rows under a ``jit`` that takes
    the seed: the 12.58 GB table ``f32[24563152,128]`` is the program's only
    output, initialised and packed ``core/store._PACK_CHUNK`` physical rows a
    loop step, a quarter of a GB of temporaries (a second table does not fit
    the chip, nor do the logical rows laid 128 lanes wide: 25 GB)."""
    cfg, model, dlrm = dlrm_cell
    compiled = jax.jit(
        lambda seed: dlrm.make_store(model, seed=seed, dtype=jnp.float32).table
    ).lower(_shape(one_chip, (), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == DLRM_PHYS_ROWS * 128 * 4 == 12_576_333_824
    assert mem.temp_size_in_bytes < 0.5 * GB
    assert len(re.findall(r" while\(", compiled.as_text())) >= 1


@pytest.fixture(scope="module")
def dlrm_step(dlrm_cell, one_chip):
    """Cell 10's step at full size for a described v5e as the chip runs it
    (asked for the backend), compiled ONCE for the tests that read it."""
    _, model, dlrm = dlrm_cell
    spec = jax.eval_shape(lambda: dlrm.make_store(model, dtype=jnp.float32)).spec
    logic = dlrm.DLRM(model)
    state = {
        k: _shape(one_chip, v.shape, v.dtype) for k, v in jax.eval_shape(
            lambda: logic.init_state(jax.random.PRNGKey(0))).items()
    }
    batch = {
        "dense": _shape(one_chip, (FM_BATCH, 13), jnp.float32),
        "ids": _shape(one_chip, (FM_BATCH, DLRM_FIELDS), jnp.int32),
        "label": _shape(one_chip, (FM_BATCH,), jnp.float32),
        "mask": _shape(one_chip, (FM_BATCH,), jnp.bool_),
    }
    return CellStep(
        make_train_step(logic, spec),
        _shape(one_chip, spec.table_shape(), jnp.float32), state, batch,
        backend="tpu", spec=spec)


def test_dlrm_step_holds_nothing_table_sized_beside_its_12_58_gb_table(
        dlrm_cell, dlrm_step, monkeypatch):
    """Cell 10's step at full size for a described v5e, as the chip runs it
    (asked for the backend): the donated table is rewritten in place by the
    tile kernel, nine calls of one shape (851,968 lanes times 8 are under
    the table's 24,563,152 rows, where the TPU compiler would leave its own
    scatter-add serial: ``arms(spec, push_lanes=n)``), no XLA scatter, and
    never copied; under ``ps.pull`` ONE gather of whole physical rows and the
    lane slice kernel at two rows a register; the dense net's scopes on its
    products; 1.8 GB of temporaries, so table, step and the pool stay under
    the chip's 16 GB.  Since PR 51 the push's lane shift is the slice's
    mirror kernel here too, ONE call whose rows the nine permutes read as
    they lie.

    Since PR 65 the logic a step in one place traces keeps the batch the
    MINOR axis between the two kernels (``models/dlrm.DLRM`` mixes in
    ``FieldLanes``) and both move the batch a field at a time:
    ``packed_lane_slice_turned`` hands ``f32[64,26,32768]``, XLA's own layout
    of the logic's ``(26, 32768, 64)`` (and of its swap to ``(32768, 26,
    64)``, a bitcast), and ``packed_lane_shift_fielded``
    reads one fusion of that shape, the delta build fused into it.  Until
    then the logic computed on row-major ``(B, 26, 64)`` between two
    feature-major kernels (``packed_lane_slice`` handing ``f32[64,851968]``):
    ``copy.319 f32[851968,64]`` + ``reshape.45 f32[32768,26,64]`` behind the
    slice, ``reshape.47`` + ``copy.324 f32[851968,64]`` in front of the
    shift, 4.97 ms a step on the v5e; and the backward pass put the triangle
    of ``dZ`` back by a scatter, a ``while`` of 351 ``dynamic-update-slice``
    of one ``f32[32768]`` column (2.28 ms), where one product with a 0/1
    matrix does now (``dlrm.pair_tables``' ``both``; a static GATHER in its
    place hangs the chip beside the tile kernel, which no compile shows:
    PERF.md section 6, PR 65): no `` while(`` is left in the step.
    What stands in the crossing's place is one transposing copy each way
    round XLA's batched products, which want ``(B, 27, 64)`` row-major
    (PERF.md section 6, PR 65)."""
    (_, model, dlrm), step, spec = dlrm_cell, dlrm_step, dlrm_step.spec
    assert (spec.layout, spec.pack, spec.update) == ("packed", 2, "add")
    assert spec.table_shape() == (DLRM_PHYS_ROWS, 128)
    n = FM_BATCH * DLRM_FIELDS
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # (the tile kernel: the batch is under the compiler's cut)
    assert store_mod.arms(spec, pull_lanes=n, push_lanes=n) == store_mod.Arms(
        "packed_kernel", "tile_add", "kernel", "", "", False)
    assert store_mod.arms(
        spec, pull_lanes=n, push_lanes=n, fields=DLRM_FIELDS
    ) == store_mod.Arms(
        "packed_kernel_by_field", "tile_add", "kernel_by_field", "", "", False)
    assert n * store_mod._SERIAL_SCATTER_ROWS_A_LANE < DLRM_PHYS_ROWS
    logic = dlrm.DLRM(model)
    assert logic.for_workers(1).pulls_turned and not logic.pulls_turned
    mem = step.memory
    assert 12.57 * GB < mem.alias_size_in_bytes < 12.59 * GB  # in place, state too
    assert mem.temp_size_in_bytes < 2.0 * GB  # 1.82 here
    assert mem.alias_size_in_bytes + mem.temp_size_in_bytes < 14.5 * GB
    text = step.text
    assert not re.search(r"f32\[24563152,128\]\S* (copy|transpose)\(", text)
    assert "f32[49126297,64]" not in text and "f32[49126304,64]" not in text
    lines = text.splitlines()
    pulls = [c for c in lines if re.search(r" gather\(", c)
             and f" f32[{n},128]{{1,0" in c]
    assert len(pulls) == 1 and "slice_sizes={1,128}" in pulls[0], pulls
    assert 'op_name="jit(step)/ps.pull/' in pulls[0]
    kernels = [line for line in lines if "tpu_custom_call" in line]
    block = f"{DLRM_FIELDS},{FM_BATCH}"
    slices = [k for k in kernels if "packed_lane_slice" in k]
    assert len(slices) == 1 and "%packed_lane_slice_turned" in slices[0]
    assert f" = f32[64,{block}]{{2,1,0:" in slices[0]
    adds = [k for k in kernels if "sorted_row_update_tiles" in k]
    assert len(adds) == 9 == -(-n // row_update.MAX_LANES) and len(kernels) == 11
    for call in adds:
        assert " = f32[24563152,128]{1,0" in call and "ps.push/" in call
    shifts = [k for k in kernels if "packed_lane_shift" in k]
    assert len(shifts) == 1 and "%packed_lane_shift_fielded" in shifts[0]
    assert f" = f32[{block},128]{{2,1,0:" in shifts[0]
    assert 'op_name="jit(step)/ps.push/' in shifts[0]

    def users_of(call):
        name = re.search(r"%([\w.\-]+) = ", call)[1]
        return [c for c in lines if re.search(rf"%{re.escape(name)}[,)]", c)]

    # the logic takes the slice kernel's rows through a bitcast ...
    # (example-major to the logic's products, the batch still the buffer's
    # minor axis)
    turned, = users_of(slices[0])
    assert f" = f32[{FM_BATCH},{DLRM_FIELDS},64]{{0,1,2:" in turned
    assert " bitcast(" in turned
    # ... the shift kernel reads ONE fusion in its own layout (the delta
    # build in it), and the nine permutes its rows through a bitcast
    fed, = re.findall(r"custom-call\(%[\w.\-]+, %([\w.\-]+)\)", shifts[0])
    feeder, = [c for c in lines if re.search(rf"%{re.escape(fed)} = ", c)]
    assert f" = f32[64,{block}]{{2,1,0:" in feeder and " fusion(" in feeder
    flat, = users_of(shifts[0])
    assert f" = f32[{n},128]{{1,0:" in flat and " bitcast(" in flat
    users = users_of(flat)
    assert len(users) == 9 and all(  # the permutes, no relayout between
        " = f32[94720,128]" in c and "ps.push/jit(_take)/gather" in c
        for c in users), users
    # nothing turns the batch's rows round between the kernels and the logic,
    # and no loop puts the triangle back
    assert not re.search(rf" = f32\[{n},64\]\S* (copy|reshape)\(", text)
    assert not re.search(
        rf" = f32\[{FM_BATCH},{DLRM_FIELDS},64\]\S* reshape\(", text)
    assert not re.search(r" while\(", text)
    # the gather's fusion and the nine permutes' are the fusions that wide
    assert not re.search(rf" = f32\[{n},128\]\S* (copy|pad)\(", text)
    assert "pad_select_fusion" not in text
    assert not re.search(rf" = f32\[{n},64\]\S* fusion\(", text)  # the mask
    # calls of ONE shape (the sorted batch padded to nine times 370 blocks):
    # a process traces and lowers the kernel once
    assert "f32[94720,128]" in text and "f32[94208,128]" not in text
    assert not re.search(r" scatter\(", text)
    # the step's outputs carry what the plan counted
    assert {"ps_push_kernel_lanes", "ps_push_tile_rows", "ps_slice_kernel",
            "ps_shift_kernel", "ps_lanes_by_field"} <= set(step.outs)
    # (the delta build's multiply lies INSIDE the fusion that feeds the shift
    # kernel, which a trace names by its root, under `ps.push`)
    for scope in ("dense_bottom", "dense_interact", "dense_top", "delta_build"):
        assert f"jit(step)/ps.compute/ps.{scope}/" in text, scope
    assert "transpose(jvp(" not in text


# dlrm-criteo-40m-ps4 (chipbench/configs): cell 16's table over four chips
DLRM_PS4_ROWS, DLRM_PS4_SHARD_ROWS = 93_883_705, 23_470_928


@pytest.fixture(scope="module")
def dlrm_ps4(topo):
    """``dlrm-criteo-40m-ps4`` as ``chipbench/families/dlrm.py`` builds it,
    on the four described chips: its mesh, model, spec (nothing allocated)."""
    from chipbench import spec as bench_spec
    from flink_parameter_server_tpu.models import dlrm
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    cfg = bench_spec.load_json("chipbench/configs/dlrm-criteo-40m-ps4.json")
    mesh = make_mesh(cfg["mesh"]["dp"], cfg["mesh"]["ps"], devices=topo.devices)
    model = dlrm.DLRMConfig(
        tuple(cfg["field_cardinalities"]), dim=cfg["dim"],
        bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
        learning_rate=cfg["learning_rate"])
    assert model.num_rows == DLRM_PS4_ROWS and model.dim == 128
    spec = jax.eval_shape(
        lambda: dlrm.make_store(model, mesh=mesh, dtype=jnp.float32)).spec
    assert (spec.layout, spec.pack, spec.update) == ("dense", 1, "add")
    assert spec.rows_per_shard == DLRM_PS4_SHARD_ROWS
    assert spec.table_shape() == (4 * DLRM_PS4_SHARD_ROWS, 128)
    return mesh, model, spec, dlrm


def test_dlrm_ps4_table_is_initialised_on_its_shards(dlrm_ps4, no_compile_cache):
    """The seeded init of 93,883,705 x 128 f32 rows under a ``jit`` that takes
    the seed, over four described chips: every chip's 12.02 GB block is the
    program's only output there, 0.28 GB of temporaries beside it (the rows
    are one register wide as they are drawn, so the init fuses into the
    table's own write: no loop is needed where a packed table has one)."""
    mesh, model, spec, dlrm = dlrm_ps4
    compiled = jax.jit(
        lambda seed: dlrm.make_store(
            model, seed=seed, mesh=mesh, dtype=jnp.float32).table
    ).lower(_shape(NamedSharding(mesh, PartitionSpec()), (), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == DLRM_PS4_SHARD_ROWS * 128 * 4 == 12_017_115_136
    assert mem.temp_size_in_bytes < 0.5 * GB
    assert not COLLECTIVE_OP.search(compiled.as_text())


@pytest.fixture(scope="module")
def dlrm_ps4_step(dlrm_ps4):
    """Cell 16's step at full size compiled ONCE for four described chips,
    as the chips run it (asked for the backend)."""
    mesh, model, spec, dlrm = dlrm_ps4
    n = FM_BATCH * DLRM_FIELDS
    with _compiling_for_described_chips("tpu"):
        n0 = row_update.refusal_count()
        assert store_mod.arms(
            spec, pull_lanes=n, push_lanes=n, fields=DLRM_FIELDS
        ) == store_mod.Arms("take", "tile_add", "", "", "", True)
        assert row_update.refusal_count() == n0
        everywhere = NamedSharding(mesh, PartitionSpec())
        logic = dlrm.DLRM(model)
        state = {
            k: _shape(everywhere, v.shape, v.dtype) for k, v in jax.eval_shape(
                lambda: logic.init_state(jax.random.PRNGKey(0))).items()
        }
        assert sum(v.size for v in state.values()) == 2_368_897
        batch = {
            "dense": _shape(everywhere, (FM_BATCH, 13), jnp.float32),
            "ids": _shape(everywhere, (FM_BATCH, DLRM_FIELDS), jnp.int32),
            "label": _shape(everywhere, (FM_BATCH,), jnp.float32),
            "mask": _shape(everywhere, (FM_BATCH,), jnp.bool_),
        }
        return CellStep(
            make_train_step(logic, spec),
            _shape(spec.sharding(), spec.table_shape(), jnp.float32), state,
            batch, backend="tpu")


def _collectives(text):
    """``{channel id: (op, result shapes)}`` of a compiled step's
    collectives (an async one is spelled several times, one channel)."""
    found = {}
    for line in text.splitlines():
        op = COLLECTIVE_OP.search(line)
        if op:
            shapes = line.split(" = ", 1)[1].split(op.group(0))[0]
            found[re.search(r"channel_id=(\d+)", line).group(1)] = (
                op.group(1), tuple(re.findall(r"[a-z]+\d+\[[\d,]*\]", shapes)),
                line)
    return found


def test_dlrm_ps4_step_adds_on_the_shard_that_owns_the_row(dlrm_ps4_step):
    """Cell 16's step at full size for four described chips: the push is ONE
    ``shard_map`` (``core/store._push_add_on_shards``), every chip's
    ``f32[23470928,128]`` block (12.02 GB) rewritten in place by the tile
    kernel, one call in a loop over the batch's nine (851,968 lanes x 8 are
    under a SHARD's rows, where the TPU compiler would leave the scatter-add
    it partitions serial: 74.7 ns a lane), no XLA scatter, no copy of a
    block; no row of the table and no key crosses chips for the push: what
    crosses in front of it is ONE all-gather of the delta block
    ``f32[26,32768,128]``, each chip's quarter of the examples built where
    its dense net ran (PR 68), and behind it its counts, a tile a shard (the
    deltas' gather stays the async pair the parent's step has: with the
    counts in ONE register a shard the compiler ran it synchronous, 3.67 ms
    for 1.94 exposed, PERF.md section 6, PR 69).
    Table, MLPs and temporaries are 12.92 GB a chip (13.85 until PR 68),
    under the 15.0 GB that decided one host of two against one of three
    (``reduced_why``)."""
    text, mem, outs = (
        dlrm_ps4_step.text, dlrm_ps4_step.memory, dlrm_ps4_step.outs)
    _every_transfer_carries_the_programs_name("cell_16", dlrm_ps4_step)
    n = FM_BATCH * DLRM_FIELDS
    assert n * store_mod._SERIAL_SCATTER_ROWS_A_LANE <= DLRM_PS4_SHARD_ROWS
    lines = text.splitlines()
    assert 12.02 * GB < mem.alias_size_in_bytes < 12.04 * GB  # in place, a chip
    # (no more than the parent's 1.82: 0.89 here, a quarter of the
    # activations and one block of rows each side)
    assert mem.temp_size_in_bytes < 1.0 * GB
    assert mem.alias_size_in_bytes + mem.temp_size_in_bytes < 15.0 * GB
    assert not re.search(r"f32\[23470928,128\]\S* (copy|transpose)\(", text)
    assert "f32[93883712,128]" not in text  # no chip ever sees the whole table
    assert not re.search(r" scatter\(", text)
    found = _collectives(text)
    gathers = [c for c in found.values()
               if c[0] == "all-gather" and "ps.compute" not in c[2]
               and "push_counts_gather" not in c[2]]
    (_, shapes, line), = gathers
    assert shapes == (f"f32[{DLRM_FIELDS},{FM_BATCH},128]",), gathers
    assert "ps.push/mesh.push_deltas_gather/" in line
    assert re.search(
        r"%async-collective-done[.\d]* = f32\[26,32768,128\]", text), (
        "the deltas' all-gather no longer overlaps the push's sort")
    counts, = [c for c in found.values() if c[1] == ("s32[4,8,128]",)]
    assert counts[0] == "all-gather" and "mesh.push_counts_gather" in counts[2]
    # ONE kernel call in the step's text, in the body of the one `while`
    # that walks the nine equal calls of the sorted batch and ends with the
    # last that holds a lane of this shard's (PR 67: nine calls, unrolled,
    # were nine bodies traced, lowered and loaded by every warm set-up)
    assert -(-n // row_update.MAX_LANES) == 9
    call, = [line for line in lines if "tpu_custom_call" in line]
    assert "%sorted_row_update_tiles" in call
    assert " = f32[23470928,128]{1,0" in call
    assert "ps.push/shard_map/while/body/" in call
    loop, = re.findall(r"^.* while\(.*$", text, re.M)
    assert "f32[23470928,128]" in loop and "ps.push/shard_map/while" in loop
    assert {"ps_push_kernel_lanes", "ps_push_tile_rows",
            "ps_push_lanes_max_shard", "ps_push_tile_rows_max_shard"} <= set(outs)
    assert "ps_slice_kernel" not in outs  # a dense store has no lane kernel
    for scope in ("dense_bottom", "dense_interact", "dense_top", "delta_build"):
        assert f"jit(step)/ps.compute/ps.{scope}/" in text, scope


def test_dlrm_ps4_step_computes_its_dense_net_once(dlrm_ps4_step):
    """The same compiled step (PR 68): the minibatch's compute split over
    the servers' own axis.  The pull's all-reduce of ``f32[32768,26,128]``
    (436 MB handed whole to every chip) is gone: every shard gathers all
    851,968 lanes from its own block (``core/store._take_on_shards``) and
    the sum over ``ps`` is the TPU's fused all-reduce-and-scatter on the
    FLAT block, a chip left its quarter of the examples' rows (212,992 of
    them; a halo of 1,056 rows goes to the neighbour by one permute, the
    scatter's blocks being the padded block's quarters); every dense product
    and every pass of the interaction runs on 8,192 examples; the dense
    gradients cross as the 16 leaves' BLOCK sums, a chip's one block of four
    gathered leaf by leaf (4 x 9,475,588 bytes held a chip), and every chip adds
    the four in the batch's order (``models/dlrm._over_examples``): no sum
    of theirs is a collective's, so the MLPs are the one-place step's bit
    for bit; the rows' deltas are built on the quarter.  The step's outputs
    say 4 parts."""
    text, outs = dlrm_ps4_step.text, dlrm_ps4_step.outs
    quarter = FM_BATCH // 4
    assert "ps_compute_parts" in outs
    assert f"f32[{FM_BATCH},{DLRM_FIELDS},128]" not in text
    found = _collectives(text)
    assert sorted(c[0] for c in found.values()) == (
        ["all-gather"] * 17 + ["all-reduce"] * 2 + ["collective-permute"]
    ), found  # (the seventeenth gather: the push's counts)
    # the pull: an all-reduce inside the fusion the TPU runs as a
    # reduce-scatter, on the flat block, under `ps.pull`
    scattered, = [
        line for line in text.splitlines() if "calls=%all-reduce-scatter" in line]
    assert re.search(r" = f32\[2133\d\d,128\]", scattered), scattered
    assert "ps.pull" in scattered
    inside, = [c for c in found.values()
               if c[0] == "all-reduce" and c[1][0].startswith("f32[85")]
    assert inside[1] == ("f32[853376,128]",)  # 4 x 213,344: no field padded
    halo, = [c for c in found.values() if c[0] == "collective-permute"]
    assert set(halo[1]) == {"f32[1056,128]", "u32[]"}
    # the dense gradients: every leaf's four block sums gathered (the last
    # bias' four numbers each into a vector of zeros that an all-reduce
    # hands round: a sum of one number and three zeros), 4 x 2,368,897
    # float32 a chip, and no all-reduce of a leaf's own sum
    grads = [c for c in found.values()
             if c[0] == "all-gather" and "ps.compute" in c[2]]
    sizes = [
        int(np.prod([int(d) for d in re.findall(r"\d+", c[1][-1].split("[")[1])]))
        for c in grads]
    small, = [c for c in found.values()
              if c[0] == "all-reduce" and c[1][0].startswith("f32[")
              and not c[1][0].startswith("f32[85")]
    assert small[1] == ("f32[4]",)
    assert len(sizes) == 15 and sum(sizes) + 4 == 4 * 2_368_897 == 9_475_588
    # the dense net at a quarter of the examples, nowhere at all of them
    dense = [line for line in text.splitlines() if "/ps.compute/ps.dense_" in line]
    assert dense and not any(f"[{FM_BATCH}," in line.split(" = ", 1)[-1].split("(")[0]
                             for line in dense)
    for shape in (f"f32[{quarter},1024]", f"f32[{quarter},27,27]",
                  f"f32[{quarter},27,128]", f"f32[{quarter},479]"):
        assert shape in text, shape
    build, = [line for line in text.splitlines()
              if "ps.delta_build" in line and " fusion(" in line]
    assert f" = f32[{DLRM_FIELDS},{quarter},128]" in build, build


# pbg-freebase-d100-p16 (chipbench/configs): cell 14's table and batch
KGE_ROWS, KGE_RELATIONS, KGE_DIM = 15_152_092, 25_291, 100
KGE_CHUNKS, KGE_CHUNK, KGE_UNIFORM = 800, 50, 50
KGE_PHYS_ROWS, KGE_KEYS = 15_152_096, 160_000


@pytest.fixture(scope="module")
def kge():
    from flink_parameter_server_tpu.models import kge as kg

    model = kg.KGEConfig(KGE_ROWS, KGE_RELATIONS, KGE_DIM)
    assert model.row_lanes == 101
    return model, kg


def test_kge_table_is_initialised_in_place_from_a_seed_argument(
        kge, one_chip, no_compile_cache):
    """15,152,092 x 101 f32 rule rows under a ``jit`` that takes the seed:
    the 7.76 GB table ``f32[15152096,128]`` (a row alone in one register) is
    the program's only output, initialised ``core/store._PACK_CHUNK`` rows a
    loop step beside 0.07 GB of temporaries."""
    model, kg = kge
    compiled = jax.jit(lambda s: kg.make_store(model, seed=s).table).lower(
        _shape(one_chip, (), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == KGE_PHYS_ROWS * 128 * 4 == 7_757_873_152
    assert mem.temp_size_in_bytes < 0.2 * GB
    assert len(re.findall(r" while\(", compiled.as_text())) >= 1


@pytest.fixture(scope="module")
def kge_step(kge, one_chip):
    """``layout -> CellStep``: cell 14's step at full size for a described
    v5e with the arms chosen as on a TPU, compiled once a layout: as the
    chip runs it (``auto``) or with the row left dense (pinned)."""
    model, kg = kge
    logic = kg.ComplExNegatives(model)
    state = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: logic.init_state(jax.random.PRNGKey(0))))
    batch = {
        name: _shape(one_chip, (KGE_CHUNKS, n), jnp.int32)
        for name, n in (
            ("source", KGE_CHUNK), ("destination", KGE_CHUNK),
            ("relation", KGE_CHUNK), ("source_negatives", KGE_UNIFORM),
            ("destination_negatives", KGE_UNIFORM))
    }

    @functools.cache
    def built(layout):
        spec = jax.eval_shape(
            lambda: kg.make_store(model, layout=layout)).spec
        with _compiling_for_described_chips("tpu"):
            n0 = row_update.refusal_count()
            arm = store_mod.arms(
                spec, pull_lanes=KGE_KEYS, push_lanes=KGE_KEYS)
            assert row_update.refusal_count() == n0
        return CellStep(
            make_train_step(logic, spec),
            _shape(one_chip, spec.table_shape(), jnp.float32), state, batch,
            backend="tpu", spec=spec, arms=arm)

    return built


def test_kge_step_holds_its_one_register_table_once(kge_step):
    """Cell 14's step at full size for a described v5e, as ``"auto"`` lays a
    rule row of 101 lanes (PR 61): PACKED, one row to one 128-lane register,
    ``f32[15152096,128]{1,0}``.  The donated 7.76 GB table is rewritten in
    place and never copied or transposed.  Under ``ps.pull`` ONE gather of
    whole physical rows ``f32[160000,128]``, cut to the worker's 100 lanes;
    under ``ps.push/ps.combine`` the row kernel's sums of the batch's
    gradient rows, 100 lanes wide as they come (``sorted_run_sums``, the
    dense plan of PR 62: 80,128 lanes a call into a zeroed
    ``f32[160256,128]`` block); in the rule's loop ONE gather
    ``f32[32768,128]`` under ``ps.rule`` and ONE ``sorted_row_set`` call on
    the table, the write-back; no XLA scatter touches the table.  The
    chunked scores are batched ``50 x 100 x 100`` products under the logic's
    scopes, the written-out backward pass too.  Beside the table: 0.44 GB."""
    step = kge_step("auto")
    spec, arm = step.spec, step.arms
    assert spec.layout == "packed" and spec.pack == 1
    assert spec.table_shape() == (KGE_PHYS_ROWS, 128)
    assert arm == store_mod.Arms(
        "packed_selects", "rule", "", "row_kernel", "row_set", False)
    mem = step.memory
    assert 7.75 * GB < mem.alias_size_in_bytes < 7.81 * GB  # in place
    assert mem.temp_size_in_bytes < 0.6 * GB
    text = step.text
    assert not re.search(r"f32\[15152096,128\]\S* (copy|transpose)\(", text)
    assert "f32[15152096,101]" not in text and "f32[15152092," not in text
    for scope in ("ps.pull", "ps.compute/ps.kge_operator/",
                  "ps.compute/ps.kge_score/", "ps.compute/ps.kge_score_grad/",
                  "ps.compute/ps.kge_operator_update/", "ps.push/ps.combine",
                  "ps.push/while/body/ps.rule"):
        assert scope in text, scope
    assert "transpose(jvp(" not in text
    lines = text.splitlines()
    pulls = [c for c in lines if re.search(r" gather\(", c)
             and " f32[160000,128]{1,0" in c and "ps.pull" in c]
    assert len(pulls) == 1 and "slice_sizes={1,128}" in pulls[0], pulls
    reads = [c for c in lines if re.search(r" gather\(", c)
             and " f32[32768,128]{1,0" in c]
    assert len(reads) == 1 and "ps.push/while/body/ps.rule" in reads[0], reads
    kernels = [line for line in lines if "tpu_custom_call" in line]
    names = sorted(k.strip().split(" ", 1)[0].rstrip(".0123456789") for k in kernels)
    assert names == ["%sorted_row_set", "%sorted_run_sums"], names
    by_name = {k.strip().split(".", 1)[0]: k for k in kernels}
    assert " f32[15152096,128]{1,0" in by_name["%sorted_row_set"]
    assert "ps.push/while/body" in by_name["%sorted_row_set"]
    assert " f32[160256,128]{1,0" in by_name["%sorted_run_sums"]
    assert "ps.push/ps.combine/while/body" in by_name["%sorted_run_sums"]
    # the operators' segment sum is the one scatter left, in the worker
    scatters = [line for line in lines if re.search(r" scatter\(", line)]
    assert len(scatters) == 1 and " f32[25291,2,100]" in scatters[0], scatters
    assert "ps.kge_operator_update" in scatters[0]


def test_kge_step_with_its_row_left_dense_copies_the_whole_table(kge_step):
    """Why ``"auto"`` packs a rule row of 65 to 127 lanes: pinned dense, the
    TPU hands the step its ``f32[15152096,101]`` table capacity-minor
    (``{0,1}``) and the step copies it WHOLE to a row-major one for its
    gathers and back after XLA's row ``set``: 7.9 GB of temporaries beside
    a table that the chip pads to 7.76 GB all the same; they do not fit."""
    step = kge_step("dense")
    spec, arm = step.spec, step.arms
    assert spec.layout == "dense" and spec.table_shape() == (KGE_PHYS_ROWS, 101)
    assert (arm.pull, arm.combine, arm.write_back) == (
        "take", "row_kernel", "xla_set")
    assert step.memory.temp_size_in_bytes > 6.0 * GB
    text = step.text
    assert len(re.findall(r"f32\[15152096,101\]\S* copy\(", text)) >= 2
    assert re.search(r"f32\[15152096,101\]\{0,1", text)


# dlrm-dcnv2-mlperf-s32 (chipbench/configs): cell 15's tables and batch
DCN_SIZES = (
    40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 40000000, 3067956,
    405282, 10, 2209, 11938, 155, 4, 976, 14, 40000000, 40000000, 40000000,
    590152, 12973, 108, 36)
DCN_BAGS = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100,
            27, 10, 3, 1, 1)
DCN_ROWS, DCN_PHYS_ROWS, DCN_BATCH, DCN_KEYS = 6_380_781, 6_380_784, 2_048, 438_272


@pytest.fixture(scope="module")
def dcn():
    from flink_parameter_server_tpu.models import dlrm_dcnv2 as dc

    model = dc.DCNv2Config(
        tuple(-(-n // 32) for n in DCN_SIZES), DCN_BAGS, DCN_SIZES)
    assert model.num_rows == DCN_ROWS and DCN_BATCH * model.lookups == DCN_KEYS
    return model, dc


def test_dcn_table_is_initialised_in_place_from_a_seed_argument(
        dcn, one_chip, no_compile_cache):
    """6,380,781 x 256 f32 rule rows under a ``jit`` that takes the seed: the
    6.53 GB table ``f32[6380784,256]`` (a row flat in two whole registers,
    its weights and then their zeroed accumulators) is the program's only
    output, initialised a block of rows a loop step beside 0.14 GB of
    temporaries: no second array holds the rows' optimiser state."""
    model, dc = dcn
    compiled = jax.jit(lambda s: dc.make_store(model, seed=s).table).lower(
        _shape(one_chip, (), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == DCN_PHYS_ROWS * 256 * 4 == 6_533_922_816
    assert mem.temp_size_in_bytes < 0.2 * GB
    assert len(re.findall(r" while\(", compiled.as_text())) >= 1


@pytest.fixture(scope="module")
def dcn_step(dcn, one_chip):
    """Cell 15's step at full size for a described v5e as the chip runs it
    (asked for the backend), compiled ONCE for the tests that read it."""
    model, dc = dcn
    spec = jax.eval_shape(lambda: dc.make_store(model)).spec
    with _compiling_for_described_chips("tpu"):
        n0 = row_update.refusal_count()
        arm = store_mod.arms(spec, pull_lanes=DCN_KEYS, push_lanes=DCN_KEYS)
        assert row_update.refusal_count() == n0
    logic = dc.DLRMDCNv2(model)
    state = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: logic.init_state(jax.random.PRNGKey(0))))
    assert sum(x.size for x in jax.tree.leaves(state)) == 2 * 16_044_545
    batch = {
        "dense": _shape(one_chip, (DCN_BATCH, 13), jnp.float32),
        "ids": _shape(one_chip, (DCN_BATCH, 214), jnp.int32),
        "label": _shape(one_chip, (DCN_BATCH,), jnp.float32),
        "mask": _shape(one_chip, (DCN_BATCH,), jnp.bool_),
    }
    return CellStep(
        make_train_step(logic, spec),
        _shape(one_chip, spec.table_shape(), jnp.float32), state, batch,
        backend="tpu", spec=spec, arms=arm)


def test_dcn_step_fits_beside_its_two_register_table(dcn_step):
    """Cell 15's step at full size for a described v5e, as ``"auto"`` lays a
    rule row of 256 lanes: PACKED at ``k`` = 1, flat in two whole registers,
    ``f32[6380784,256]{1,0}``.  The donated 6.53 GB table (and the dense
    net's 128 MB of leaves and accumulators) is rewritten in place and never
    copied or transposed; beside it 0.70 GB of temporaries.  Under
    ``ps.pull`` ONE gather of whole rows ``f32[438272,256]`` cut to the
    worker's 128 lanes (the accumulators' half is gathered and dropped:
    PERF.md section 7); under ``ps.push/ps.combine`` the row kernel's sums of
    the batch's gradient rows at 128 lanes (``sorted_run_sums``); in the
    rule's loop ONE gather ``f32[32768,256]`` under ``ps.rule`` and ONE
    ``sorted_row_assign_tiles`` call on the table, the write-back; no XLA
    scatter touches the table.  The dense net's products are under the
    logic's scopes, the written-out backward pass too."""
    step, spec, arm = dcn_step, dcn_step.spec, dcn_step.arms
    assert spec.layout == "packed" and spec.pack == 1
    assert spec.table_shape() == (DCN_PHYS_ROWS, 256) and spec.worker_width == 128
    assert arm == store_mod.Arms(
        "packed_selects", "rule", "", "row_kernel", "tile_assign", False)
    mem = step.memory
    assert 6.65 * GB < mem.alias_size_in_bytes < 6.68 * GB  # in place
    assert mem.temp_size_in_bytes < 0.9 * GB
    text = step.text
    assert not re.search(r"f32\[6380784,256\]\S* (copy|transpose)\(", text)
    assert "f32[6380781," not in text
    for scope in ("ps.pull", "ps.compute/ps.bag_pool/",
                  "ps.compute/ps.dense_bottom/", "ps.compute/ps.dense_interact/",
                  "ps.compute/ps.dense_top/", "ps.compute/ps.bag_grad_spread/",
                  "ps.compute/ps.dense_adagrad/", "ps.push/ps.combine",
                  "ps.push/while/body/ps.rule"):
        assert scope in text, scope
    assert "transpose(jvp(" not in text
    lines = text.splitlines()
    pulls = [c for c in lines if re.search(r" gather\(", c)
             and " f32[438272,256]{1,0" in c and "ps.pull" in c]
    assert len(pulls) == 1 and "slice_sizes={1,256}" in pulls[0], pulls
    reads = [c for c in lines if re.search(r" gather\(", c)
             and " f32[32768,256]{1,0" in c]
    assert len(reads) == 1 and "ps.push/while/body/ps.rule" in reads[0], reads
    kernels = [line for line in lines if "tpu_custom_call" in line]
    names = sorted(k.strip().split(" ", 1)[0].rstrip(".0123456789") for k in kernels)
    assert names == ["%sorted_row_assign_tiles", "%sorted_run_sums"], names
    by_name = {k.strip().split(".", 1)[0]: k for k in kernels}
    assert " f32[6380784,256]{1,0" in by_name["%sorted_row_assign_tiles"]
    assert "ps.push/while/body" in by_name["%sorted_row_assign_tiles"]
    assert "ps.push/ps.combine/while/body" in by_name["%sorted_run_sums"]
    assert not [line for line in lines if re.search(r" scatter\(", line)]


# wdl-criteo-10m (chipbench/configs): cell 17's two tables, as they lie
WDL_DEEP_TABLE, WDL_WIDE_TABLE = (24_563_152, 128), (27_262_976, 4)
WDL_BATCH, WDL_FIELDS = 32_768, 26


@pytest.fixture(scope="module")
def wdl_tpu_step(one_chip):
    """Cell 17's step over its TWO stores at full size for a described v5e,
    as the chip runs it, compiled ONCE for the tests that read it."""
    from chipbench import spec as bench_spec
    from flink_parameter_server_tpu.models import wide_deep as wd

    cfg = bench_spec.resolve(
        bench_spec.load_benchmark(), "wdl-criteo-10m.train-fields-uniform",
        dry_run=False)["cfg"]
    model = wd.WideDeepConfig(
        tuple(cfg["field_cardinalities"]), dim=cfg["dim"],
        hidden=tuple(cfg["hidden"]), cross_buckets=cfg["cross_buckets"])
    with _compiling_for_described_chips("tpu"):
        spec = jax.eval_shape(lambda: wd.make_stores(model)).spec
        assert spec["deep"].table_shape() == WDL_DEEP_TABLE
        assert spec["wide"].table_shape() == WDL_WIDE_TABLE
        lanes = WDL_BATCH * WDL_FIELDS
        n0 = row_update.refusal_count()
        assert store_mod.arms(
            spec["wide"], pull_lanes=lanes, push_lanes=lanes
        ) == store_mod.Arms(
            "narrow_distinct", "rule", "", "sort", "tile_set", False)
        assert store_mod.arms(
            spec["deep"], pull_lanes=lanes, push_lanes=lanes
        ) == store_mod.Arms(
            "packed_kernel", "rule", "", "row_kernel", "row_set", False)
        assert row_update.refusal_count() == n0
        logic = wd.WideAndDeep(model)
        tables = {
            name: _shape(one_chip, spec[name].table_shape(), jnp.float32)
            for name in spec}
        state = jax.tree.map(
            lambda x: _shape(one_chip, x.shape, x.dtype),
            jax.eval_shape(lambda: logic.init_state(jax.random.PRNGKey(0))))
        batch = {
            "dense": _shape(one_chip, (WDL_BATCH, 13), jnp.float32),
            "ids": _shape(one_chip, (WDL_BATCH, WDL_FIELDS), jnp.int32),
            "label": _shape(one_chip, (WDL_BATCH,), jnp.float32),
            "mask": _shape(one_chip, (WDL_BATCH,), jnp.bool_),
        }
        return CellStep(
            make_train_step(logic, spec), tables, state, batch, backend="tpu")


def test_the_two_store_step_rewrites_both_tables_in_place(wdl_tpu_step):
    """ONE program over both stores, both tables donated: 13.02 GB aliased
    (12.58 + 0.44), under 1 GB of temporaries (they go with the batch), and
    of each table exactly ONE op yields a table: its write-back's kernel in
    its rule's loop, under its own store's label.  Nothing copies,
    transposes or scatters either table."""
    text, mem = wdl_tpu_step.text, wdl_tpu_step.memory
    assert 13.0 * GB < mem.alias_size_in_bytes < 13.05 * GB
    # PR 71's step held 0.934 GB; the set kernel's spans (PR 72) live in
    # VMEM (three buffers of 4,112 tiles, 25 MB) and its plan's scans are a
    # chunk's lanes: nothing more in HBM
    assert mem.temp_size_in_bytes < 0.94 * GB
    for shape, kernel, where in (
            (r"24563152,128", "%sorted_row_set.", "ps.push/store.deep/while/body"),
            (r"(27262976,4|212992,4,128)", "%sorted_row_set_tiles.",
             "ps.push/store.wide/while/body")):
        table = rf"f32\[{shape}\]"
        yields = [
            line.strip() for line in text.splitlines()
            if re.search(rf" = {table}\S* (?!parameter|get-tuple-element)",
                         line)]
        kernels = [y for y in yields if " custom-call(" in y]
        assert len(kernels) == 1 and kernels[0].startswith(kernel), yields
        assert where in kernels[0]
        # what else yields a table only names it anew
        assert all(" bitcast(" in y for y in yields if y not in kernels), yields
        assert not re.search(table + r"\S* (copy|transpose|scatter)\(", text)
    assert not [line for line in text.splitlines()
                if re.search(r" scatter\(", line)]


def test_the_two_store_step_holds_one_gather_and_one_write_back_a_store(
        wdl_tpu_step):
    """What reads a table: the deep pull's ONE gather of 851,968 whole
    physical rows and its rule's chunk of 32,768; the wide pull's chunk of
    32,768 distinct 3-lane rows in its loop and the rule's own under the
    branch a masked step takes (cell 6's pair).  The Pallas calls are the
    four the stores would take alone: the deep pull's lane slice, the deep
    combine's row sums, the two write-backs.  Four loops: the wide pull's
    and rule's, the deep combine's and rule's.  The hash stands under its
    own scope in front of the pulls, the net under the logic's."""
    text = wdl_tpu_step.text
    lines = text.splitlines()
    gathers = [line for line in lines if re.search(r" gather\(", line)]
    lanes = WDL_BATCH * WDL_FIELDS
    deep_pull = [g for g in gathers if f" = f32[{lanes},128]" in g]
    assert len(deep_pull) == 1 and "ps.pull/store.deep" in deep_pull[0]
    deep_rule = [g for g in gathers if " = f32[32768,128]" in g]
    assert len(deep_rule) == 1
    assert "ps.push/store.deep/while/body/ps.rule" in deep_rule[0]
    wide = [g for g in gathers if " = f32[32768,3]" in g]
    assert len(wide) == 2 and all("slice_sizes={1,3}" in g for g in wide)
    assert not [g for g in gathers if f" = f32[{lanes},3]" in g]
    kernels = [line for line in lines if "tpu_custom_call" in line]
    names = sorted(
        k.strip().split(" ", 1)[0].rstrip(".0123456789") for k in kernels)
    assert names == [
        "%packed_lane_slice", "%sorted_row_set", "%sorted_row_set_tiles",
        "%sorted_run_sums"], names
    assert len(re.findall(r" while\(", text)) == 4
    for scope in ("ps.cross_hash/", "ps.pull/store.wide", "ps.pull/store.deep",
                  "ps.compute/ps.dense_top/", "ps.compute/ps.dense_adagrad/",
                  "ps.compute/ps.delta_build/",
                  "ps.push/store.wide/ps.combine",
                  "ps.push/store.deep/ps.combine",
                  "ps.push/store.wide/while/body/ps.rule",
                  "ps.push/store.deep/while/body/ps.rule"):
        assert scope in text, scope
    # the backward product that ends at the pulled rows carries the net's
    # scope, the cut to the rows' lanes fused into it
    assert not [
        line for line in lines if " convolution(" in line
        and "ps.delta_build" in line]


# sage-papers100m-p8 (chipbench/configs): cell 18's three tables, as they lie
SAGE_TABLES = {
    "off": (108_464, 128), "nbr": (3_155_640, 128), "feat": (13_882_496, 128)}
SAGE_SEEDS = 1_000


@pytest.fixture(scope="module")
def sage_tpu_step(one_chip):
    """Cell 18's step over its THREE read-only stores at full size for a
    described v5e, seven rounds of pulls in one program, compiled ONCE for
    the tests that read it."""
    from chipbench import spec as bench_spec
    from chipbench.families import sage as family
    from flink_parameter_server_tpu.models import graphsage as gs

    cfg = bench_spec.resolve(
        bench_spec.load_benchmark(), "sage-papers100m-p8.train-seeds-uniform",
        dry_run=False)["cfg"]
    model = family._model(cfg)
    with _compiling_for_described_chips("tpu"):
        spec = jax.eval_shape(lambda: gs.make_stores(model)).spec
        assert {n: spec[n].table_shape() for n in spec} == SAGE_TABLES
        n0 = row_update.refusal_count()
        for name, pull in (("off", "packed_selects"),
                           ("nbr", "packed_selects"), ("feat", "take")):
            assert store_mod.arms(
                spec[name], pull_lanes=100_000, only_read=True
            ) == store_mod.Arms(pull, "", "", "", "", False)
        assert row_update.refusal_count() == n0
        logic = gs.GraphSage(model)
        tables = {
            name: _shape(one_chip, spec[name].table_shape(), spec[name].dtype)
            for name in spec}
        state = jax.tree.map(
            lambda x: _shape(one_chip, x.shape, x.dtype),
            jax.eval_shape(lambda: logic.init_state(jax.random.PRNGKey(0))))
        batch = {
            "seed": _shape(one_chip, (SAGE_SEEDS,), jnp.int32),
            "label": _shape(one_chip, (SAGE_SEEDS,), jnp.int32),
            "mask": _shape(one_chip, (SAGE_SEEDS,), jnp.bool_),
        }
        return CellStep(
            make_train_step(logic, spec), tables, state, batch, backend="tpu")


def test_the_chained_step_reads_its_three_tables_where_they_lie(sage_tpu_step):
    """ONE program for seven rounds of pulls: the three tables donated and
    aliased (8.78 GB: 7.11 of features, 1.62 of neighbour ids, 0.06 of row
    ends), the two large ones yielded by no op but a parameter (no copy, no
    scatter, no second features table), 0.80 GB of temporaries (the 413 MB
    of pulled features and the deepest hop's 384 MB of physical rows).  One
    gather a round, each under ``ps.pull``, its store's label and, from the
    second on, its round's number, at the lanes the fan-outs give; the draws
    between them under ``ps.sample``, the net under its two scopes."""
    text, mem = sage_tpu_step.text, sage_tpu_step.memory
    assert 8.78 * GB < mem.alias_size_in_bytes < 8.79 * GB
    assert mem.temp_size_in_bytes < 0.85 * GB
    lines = text.splitlines()
    # (the 55.5 MB of row ends the compiler itself stages in the chip's fast
    # memory, `S(1)`, for the three rounds that read it: its choice)
    for rows, lanes in (SAGE_TABLES["nbr"], SAGE_TABLES["feat"]):
        table = rf"[fs]32\[{rows},{lanes}\]"
        yields = [
            line.strip() for line in lines
            if re.search(rf" = {table}\S* (?!parameter|get-tuple-element)",
                         line)]
        assert all(" bitcast(" in y for y in yields), yields
    assert not [line for line in lines if re.search(r" scatter\(", line)]
    gathers = [line for line in lines if re.search(
        r" = [fs]32\[\d+,128\]\S* gather\(", line)]
    want = [
        (2_000, "ps.pull/store.off/"), (5_000, "ps.pull/round.1/store.nbr/"),
        (10_000, "ps.pull/round.2/store.off/"),
        (50_000, "ps.pull/round.3/store.nbr/"),
        (100_000, "ps.pull/round.4/store.off/"),
        (750_000, "ps.pull/round.5/store.nbr/"),
        (806_000, "ps.pull/round.6/store.feat/")]
    assert len(gathers) == len(want)
    for (rows, where), line in zip(want, gathers):
        assert f"32[{rows},128]" in line and where in line, line
    for scope in ("jit(step)/ps.sample/", "ps.compute/ps.sage_dense/",
                  "ps.compute/ps.dense_adam/"):
        assert scope in text, scope
    assert "tpu_custom_call" not in text and " while(" not in text
    assert sorted(k for k in sage_tpu_step.outs if k.startswith("ps_")) == [
        "ps_lanes_by_field@nbr", "ps_lanes_by_field@off", "ps_pull_lanes@feat",
        "ps_pull_lanes@nbr", "ps_pull_lanes@off", "ps_slice_kernel@nbr",
        "ps_slice_kernel@off"]


def test_the_chained_steps_feature_pull_fills_nothing_behind_its_gather(
        sage_tpu_step):
    """Cell 18's 806,000 feature rows leave their gather as they are: the
    parent's ``broadcast_select_fusion f32[806000,128]`` (1.26 ms of 13.17,
    a read and a write of 413 MB: PERF.md section 6, PR 76) is gone, and the
    temporaries stand where they stood (0.801 GB)."""
    _no_fill_pass_stands_behind_a_bounded_gather(
        sage_tpu_step, 806_000, 0.802 * GB)


def _step_text_sha(lowered_text):
    """sha256 of a step's lowered text, which carries no locations but in
    its Mosaic bodies: each stands as the hash of its print without them, so
    the text is the same from any checkout and a kernel that moves moves
    it."""
    import hashlib

    text = MOSAIC_BODY.sub(lambda m: _body_sha(m.group(0)), lowered_text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _lowered_off_the_tpu(step, *args):
    return jax.jit(step, donate_argnums=(0, 1)).lower(*args).as_text()


# every cell's step at full size AS THE CHIP RUNS IT (lowered for described
# chips, asked for the backend, the kernels in it): name -> the fixture that
# holds it, the key the fixture is asked for, and the hash of its text.  Read
# from the fixtures' lowered text: no compile, and a lowering (0.2-2 s) only
# where the worker has not built the fixture for a neighbour.
ON_THE_CHIP = {
    "mf_cells_1_3_11_on_a_tpu": (
        "mf_tpu_step", (BATCH,), "97006e03ec6c6b96"),
    "fm_cell_2_on_a_tpu": (
        "fm_step", ("cell_2", "kernels"), "54af7aba7d6f5d8a"),
    "fm_ps4_cell_4_on_four_tpus": (
        "fm_step", ("cell_4", "kernels"), "831429aecc471fca"),
    "w2v_cell_5_on_a_tpu": (
        "w2v_step", ("kernels",), "516e264b187449b7"),
    "lr_cell_6_on_a_tpu": (
        "lr_step", ("kernels",), "786ca19216aec82c"),
    "ft_cell_7_on_a_tpu": (
        "ft_step", None, "f760a7fb1f7744dc"),
    "keyed_mf_cell_8_on_four_tpus": (
        "mf_dp4_step", ("whole_on_every_chip",), "ac2183a2ec5f1c80"),
    "difacto_cell_9_on_a_tpu": (
        "difacto_step", ("kernels",), "303f9a9ea4e1a64e"),
    "dlrm_cell_10_on_a_tpu": (
        "dlrm_step", None, "ce6e9158095e29a1"),
    "difacto_ps4_cell_12_on_four_tpus": (
        "difacto_ps4_step", ("kernels",), "cd80a9212976c0a2"),
    "glove_cell_13_on_a_tpu": (
        "glove_step", ("kernels",), "15eb405c9fedc098"),
    "kge_cell_14_on_a_tpu": (
        "kge_step", ("auto",), "572a5cfb332f1ff5"),
    "dcn_cell_15_on_a_tpu": (
        "dcn_step", None, "5c6cdd0de7ad3c5e"),
    "dlrm_ps4_cell_16_on_four_tpus": (
        "dlrm_ps4_step", None, "1c69cbd63e3d9cfc"),
    "wdl_cell_17_on_a_tpu": (
        "wdl_tpu_step", None, "599b072726ea7c20"),
    "sage_cell_18_on_a_tpu": (
        "sage_tpu_step", None, "a313cf88871cff4a"),
}


@pytest.mark.parametrize("cell, want", [
    ("mf_cells_1_and_3", "213275654bdc84e9"),
    ("fm_cell_2", "715a8a5e1325631a"),
    ("fm_ps4_cell_4", "b1244432ae0312eb"),
    ("lr_cell_6", "9195922d2258936e"),
    ("keyed_mf_cell_8", "1865234b35f082f2"),
] + [(cell, held[2]) for cell, held in ON_THE_CHIP.items()])
def test_every_cells_step_text_is_the_parents(cell, want, request):
    """The lowered text of a step carries no locations, so a change that
    traces the same ops gives the same text: cells 1 and 3 (MF, dense 128
    lanes) and cell 2 (FM, seven 17-lane rows to a 128-lane row) run the
    step PR 30 to PR 32 ran (PERF.md section 6 records both hashes).  A
    change that means to move them brings its own: PR 42 gave FM's step the
    scalar ``ps_slice_kernel`` (which arm sliced the pulled rows; lowered
    here, off the TPU, the arm and every other op are the parent's:
    ``3913e9e902cfade8`` until then).  Since PR 46 also cell 4 (FM packed
    under ``ps = 4``), cell 6 (the narrow rule store: its combine, its
    counts and its outputs are what they were, no new count among them) and
    cell 8 (MF under four keyed workers), each as PR 46's parent lowers it
    here: that PR changed the combine of WIDE rule rows, which none of the
    five traces.  PR 51 gave the two FM steps one scalar more,
    ``ps_shift_kernel`` (which arm shifted the pushed deltas: here, off the
    TPU, a constant 0 and the parent's ops to the letter; ``62cb492a1f6ca10e``
    and ``0d16cc6091cddad1`` until then); the other three did not move.
    PR 57 took ``_zero_masked`` out of a RULE store's push (a masked lane goes
    to the sentinel in ``_push_rule``; its delta reaches no kept row): cell
    6's text lost the mask's ``reshape`` to ``(1277952, 1)``, a zero
    ``broadcast_in_dim`` and the ``_where`` over ``f32[1277952,3]``, nothing
    else (``aab60546ac40ed64`` until then); the four add stores kept theirs.
    PR 63 moved the two FM steps and meant to: the copy of their logic that
    a step in one place traces computes field-major, takes its rows turned
    (off the TPU, as here, the pull's answer with its axes swapped) and
    pushes ``(K, B)`` lanes (``models/factorization_machine.FieldLanes``;
    ``bc06381bf02bcde5`` and ``db02bf3de22f8a3e`` until then); the other
    three did not move, nor did
    the step of any cell that runs neither FM logic (every cell's lowered
    step at full size for a described v5e, hashed on both trees: PERF.md
    section 6, PR 63).  PR 65 gave the step of every store packed several
    rows to a physical row one scalar more, ``ps_lanes_by_field`` (whether
    its lane kernels move the batch a field at a time: here, off the TPU, a
    constant 0 behind the parent's ops to the letter; ``1a01b65060f0886d``
    and ``d240e3e5008700fb`` until then; at full size for a described v5e
    cells 2, 4, 9 and 12 hash equal to the parent's with that output left
    out, every other cell but cell 10 as it stands: PERF.md section 6, PR
    65); the other three did not move.  PR 67 (an add store's push on the
    shards that own its rows, the tile kernel's calls rolled into a loop
    there, DLRM's init one program) moved none of the five, and at full
    size for a described v5e every one of cells 1-15 hashes equal on its
    parent and on its tree (the sixteen hashes: PERF.md section 6, PR 67).
    PR 68 (the minibatch's compute split over the servers' own axis where a
    logic declares ``example_blocks`` under one worker group and ``ps`` > 1:
    cell 16 alone) moved none of the five: a step that computes the whole
    minibatch in every place hands out no new output and names no
    constraint, and at full size for a described v5e every one of cells
    1-15 BUT CELL 10 hashes equal on its parent and on its tree; cell 10's
    text moved and was meant to (its dense gradients are summed in the four
    blocks the chips of cell 16 hold, so that the MLPs do not depend on
    ``ps``: PERF.md section 6, PR 68).  PR 69 gave the two steps under a
    mesh one scalar more, ``ps_mesh_kib`` (what their ``mesh.*`` sites say
    crosses between chips, a constant of the
    trace): with that output left out their text is the parent's to
    the letter (``7c6eef68c7b9b162`` and ``47d256f2590f4bc0``, held below:
    the names themselves are locations, which this text does not carry);
    the three steps in one place did not move.  PR 70 (a narrow rule
    store pulls a batch's distinct rows once: cell 6 on a TPU, the arm
    ``narrow_distinct``) moved none of the five, cell 6's included: the arm
    is taken on a TPU alone, and lowered here, off it, the step calls
    ``pull_counted`` for ``pull`` and traces the parent's ops to the letter;
    the step as the chip runs it is held by
    ``test_lr_step_reads_each_distinct_row_once_for_the_logic_and_the_rule``
    (compiled for a described v5e), and every other cell's step hashes
    equal on the parent and on the tree at full size for a described v5e
    (PERF.md section 6, PR 70).
    PR 73 (the tests' clock; the explicit-collectives module that no step
    called, deleted) moved none of the five and renamed this test: it now
    also holds EVERY cell's step at full size as the chip runs it (the
    ``..._on_a_tpu`` / ``..._on_four_tpus`` cases, ``ON_THE_CHIP``: what
    every PR since PR 63 hashed by hand in a scratch script), read from the
    lowered text of the fixtures that compile those steps, a Mosaic body
    standing as the hash of its print without locations; each as PR 73's
    parent lowers it.  Cell 11 runs cells 1 and 3's step.  PR 74 moved the
    six steps that call ``ops/row_update._tile_kernel`` and meant to (cells
    5, 7, 10 and 16's add push, cell 13's combine and write-back, cell
    15's write-back: the kernel's adds hold a tile row in registers where
    the rows are one or five registers wide and added, cells 5, 10 and 16;
    its plan hands every one of the six five words a block for three, the
    only change to cells 7, 13 and 15, whose rows are added, or stored,
    lane by lane as before; until then
    ``16f834160caf82d7``, ``714998ad7272db94``, ``0ea613ea9d5a0e78``,
    ``c1ca125a2e7b3fc2``, ``04a7f054e433a5bc``, ``e0bb481e6420de82``);
    the other fourteen cases hash equal on the parent and on the tree.
    PR 75 (a step that pulls again from what it pulled,
    ``BatchedWorkerLogic.next_keys``; a store a step only reads; int32
    scalar rows) moved none of the twenty: a logic of one round runs through
    the rounds' seam (``core/transform._pull_rounds``) and traces the ops
    and the names it traced, and ``arms`` reads a float32 store as it did;
    the twenty-first case, cell 18's step, is new and as that PR's tree
    lowers it.  PR 76 (``mode="clip"`` on the ``take`` arm, ``_narrow_pull``
    and ``row_add``'s permutes: a gather whose ids the code has bounded
    traces no fill) moved eight cases and meant to, each losing in front of
    the gather the wrap of negative ids and the compares with the table's
    bounds, behind it the ``select`` against NaN, and nothing else (the
    gather clamps by itself): ``mf_cells_1_and_3`` (the item pull;
    ``467449ddc73eac39`` until then), ``lr_cell_6`` (``_narrow_pull``;
    ``f669bf2e1dddf416``), ``keyed_mf_cell_8`` (the item pull under ``dp``;
    ``878ead5a803a932d``, and ``47d256f2590f4bc0`` with the mesh's output
    left out), and as the
    chip runs them cells 1, 3 and 11 (the item pull and ``row_add``'s two
    permutes; ``d3dfc5c70ddec5b1``), cell 6 (``4316e6f312ec73b7``), cell 8
    (``4c18ed8a28ba52c5``), cell 17 (the wide store's ``_narrow_pull``;
    ``6c8f66f09bc7e7b3``) and cell 18 (``feat``'s pull;
    ``be7c94d5fa3142b2``); ``fm_cell_2``, ``fm_ps4_cell_4`` and the rest did
    not move, cell 14's among them (its pull is ``packed_selects``, which
    gathered with ``mode="clip"`` already)."""
    if cell in ON_THE_CHIP:
        fixture, key, _ = ON_THE_CHIP[cell]
        held = request.getfixturevalue(fixture)
        step = held(*key) if key is not None else held
        assert step.noted == 0
        assert _step_text_sha(step.lowered) == want
        return
    shape = jax.ShapeDtypeStruct

    def mf_batch(n, on=shape):
        return {"user": on((n,), jnp.int32), "item": on((n,), jnp.int32),
                "rating": on((n,), jnp.float32), "mask": on((n,), jnp.bool_)}

    if cell == "mf_cells_1_and_3":
        logic = mfm.OnlineMatrixFactorization(
            USERS, DIM, updater=mfm.SGDUpdater(2e-4))
        spec = jax.eval_shape(
            lambda: ShardedParamStore.create(ITEMS, (DIM,), dtype=jnp.float32)
        ).spec
        args = (shape((spec.padded_capacity, DIM), jnp.float32),
                shape((USERS, DIM), jnp.float32), mf_batch(BATCH))
    elif cell == "fm_cell_2":
        spec, logic = request.getfixturevalue("fm1")
        batch = {k: shape(v.shape, v.dtype) for k, v in _fm_batch(None).items()}
        args = (shape(spec.table_shape(), jnp.float32), (), batch)
    elif cell == "fm_ps4_cell_4":
        mesh, spec, logic = request.getfixturevalue("ps4")
        args = (_shape(spec.sharding(), spec.table_shape(), jnp.float32), (),
                _fm_batch(NamedSharding(mesh, PartitionSpec())))
    elif cell == "lr_cell_6":
        spec, logic = request.getfixturevalue("lr")
        args = (shape(spec.table_shape(), jnp.float32), (), _fm_batch(None))
    else:
        from flink_parameter_server_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(4, 1, devices=request.getfixturevalue("topo").devices)
        logic = mfm.OnlineMatrixFactorization(
            50_082_603, DIM, updater=mfm.SGDUpdater(5e-5), mesh=mesh)
        spec = jax.eval_shape(lambda: ShardedParamStore.create(
            ITEMS, (DIM,), dtype=jnp.float32, mesh=mesh)).spec

        def on(dims, dtype, *axes):
            return _shape(NamedSharding(mesh, PartitionSpec(*axes)), dims, dtype)

        args = (on((spec.padded_capacity, DIM), jnp.float32, "ps", None),
                on((logic.state_rows, DIM), jnp.float32, "dp", None),
                mf_batch(262_144, on))
    step = make_train_step(logic, spec)
    assert _step_text_sha(_lowered_off_the_tpu(step, *args)) == want
    before_the_mesh_names = {
        "fm_ps4_cell_4": "7c6eef68c7b9b162", "keyed_mf_cell_8": "cbe05fad0de5b5ad"}
    if cell in before_the_mesh_names:

        def without(*a):
            table, state, outs = step(*a)
            return table, state, {
                k: v for k, v in outs.items() if not k.startswith("ps_mesh_")}

        without.__name__ = "step"  # (the module's name is in its text)
        assert _step_text_sha(
            _lowered_off_the_tpu(without, *args)) == before_the_mesh_names[cell]
