"""Compile the main path's kernels for a DESCRIBED TPU v5e, at the benchmark's
real widths, with the TPU compiler installed here — no chip, nothing runs
(the ``on-chip-measurement`` guide, section 2).  What Mosaic or the TPU
compiler refuses costs a test failure instead of chip time.

All such compiles live in THIS file: the worker that runs it loads the TPU
library, inside a fixture, and keeps it until it exits."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from flink_parameter_server_tpu import ShardedParamStore
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import matrix_factorization as mfm
from flink_parameter_server_tpu.ops import row_update

# mf-hugewiki-k128 (chipbench/configs): the MF cells' shapes
USERS, ITEMS, DIM, BATCH = 5_008_260, 39_780, 128, 65_536


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a described device's executable cannot be read back from the cache
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("lanes", [BATCH, row_update.MAX_LANES])
def test_row_update_kernel_compiles_at_the_mf_cells_shapes(
        one_chip, no_compile_cache, lanes):
    """Single-row dynamic-offset DMAs into 5,008,260 rows (no multiple of
    8) of 128 f32 lanes, 65,536 sorted lanes: Mosaic takes it, and as many
    lanes as ``refusal`` lets through (their row ids fit SMEM)."""
    compiled = jax.jit(
        lambda st, ids, old, dl: row_update.sorted_row_update(
            st, ids, old, dl, interpret=False),
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


def _compiled_mf_step(one_chip, monkeypatch, batch_size):
    """The step the MF cells run (``OnlineMatrixFactorization`` as
    ``chipbench/families/mf.py`` builds it: no ``state_scatter``), compiled
    for the chip at the cells' tables and a batch of ``batch_size``."""
    # code that asks for the backend still sees the CPU here: steer it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
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
    return jax.jit(
        make_train_step(logic, spec), donate_argnums=(0, 1)
    ).lower(
        _shape(one_chip, (spec.padded_capacity, DIM), jnp.float32),
        _shape(one_chip, (USERS, DIM), jnp.float32),
        batch,
    ).compile()


def test_mf_step_with_a_batch_over_the_kernels_lanes_keeps_the_xla_arm(
        one_chip, no_compile_cache, monkeypatch):
    """131,072 row ids do not fit the kernel's SMEM (Mosaic: RESOURCE_
    EXHAUSTED): the default step says so once, counts, and compiles with
    the XLA scatter as the parent's did."""
    n0 = store_mod.pallas_fallback_count()
    with pytest.warns(RuntimeWarning, match="falling back.*131072 lanes"):
        compiled = _compiled_mf_step(one_chip, monkeypatch, 131_072)
    assert store_mod.pallas_fallback_count() == n0 + 1
    assert "sorted_row_update" not in compiled.as_text()


def test_mf_step_default_arm_on_tpu_is_the_row_kernel(
        one_chip, no_compile_cache, monkeypatch):
    """At the cells' batch the user state goes through the kernel, and no
    scatter over the state array is left under ``ps.state_push``."""
    n0 = store_mod.pallas_fallback_count()
    compiled = _compiled_mf_step(one_chip, monkeypatch, BATCH)
    assert store_mod.pallas_fallback_count() == n0
    text = compiled.as_text()
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
