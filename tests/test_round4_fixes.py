"""Round-4 regression tests: the advisor findings (r3) stay
fixed.

Covers:
  * StoreSpec rejects unknown ``layout`` values.
"""
import pytest

from flink_parameter_server_tpu.core.store import ShardedParamStore, StoreSpec


def test_store_spec_rejects_unknown_layout():
    with pytest.raises(ValueError, match="layout"):
        StoreSpec(capacity=8, value_shape=(4,), layout="auto")
    # create() resolves "auto" BEFORE the spec, so it stays accepted there
    store = ShardedParamStore.create(8, (4,), layout="auto")
    assert store.spec.layout in ("dense", "packed")
