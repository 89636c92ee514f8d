"""Round-4 regression tests: the advisor findings (ADVICE.md r3) stay
fixed.

Covers:
  * StoreSpec rejects unknown ``scatter_impl`` / ``layout`` values — a
    typo like 'xla-sorted' must never silently run the plain XLA
    scatter.
  * sorted_dedup_scatter_add rejects ``oob`` below the table (routed
    lanes would land on a REAL row) and int32 rep-id overflow.
"""
import jax.numpy as jnp
import pytest

from flink_parameter_server_tpu.core.store import ShardedParamStore, StoreSpec
from flink_parameter_server_tpu.ops.sorted_scatter import (
    sorted_dedup_scatter_add,
)


@pytest.mark.parametrize("bad", ["xla-sorted", "sorted", "Pallas", ""])
def test_store_spec_rejects_unknown_scatter_impl(bad):
    with pytest.raises(ValueError, match="scatter_impl"):
        StoreSpec(capacity=8, value_shape=(4,), scatter_impl=bad)


def test_store_spec_rejects_unknown_layout():
    with pytest.raises(ValueError, match="layout"):
        StoreSpec(capacity=8, value_shape=(4,), layout="auto")
    # create() resolves "auto" BEFORE the spec, so it stays accepted there
    store = ShardedParamStore.create(8, (4,), layout="auto")
    assert store.spec.layout in ("dense", "packed")


def test_sorted_scatter_rejects_low_oob():
    table = jnp.zeros((16, 4))
    ids = jnp.array([1, 2, 3], jnp.int32)
    deltas = jnp.ones((3, 4))
    with pytest.raises(ValueError, match="oob"):
        sorted_dedup_scatter_add(table, ids, deltas, oob=8)
    # oob == rows (the default) stays valid
    out = sorted_dedup_scatter_add(table, ids, deltas, oob=16)
    assert float(out.sum()) == 12.0


def test_sorted_scatter_rejects_int32_rep_overflow():
    table = jnp.zeros((16, 4))
    ids = jnp.array([1, 2, 3], jnp.int32)
    deltas = jnp.ones((3, 4))
    with pytest.raises(ValueError, match="int32"):
        sorted_dedup_scatter_add(
            table, ids, deltas, oob=jnp.iinfo(jnp.int32).max - 1
        )
