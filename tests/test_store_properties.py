"""Property-based store semantics (hypothesis).

The store is the framework's keyed-state heart; these properties pin the
reference semantics (SURVEY.md §2 #3) against arbitrary batches:
push-then-pull observation, permutation invariance of commutative
updates, and mask/OOB drop behavior.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip, not a collection error
from hypothesis import given, settings, strategies as st

from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.utils.initializers import zeros

CAP, DIM = 16, 3


def _store():
    return ShardedParamStore.create(CAP, (DIM,), init_fn=zeros((DIM,)))


def _batch(pairs):
    """(ids, deltas): each scalar delta broadcast across the DIM columns."""
    ids = jnp.asarray([i for i, _ in pairs], jnp.int32)
    col = np.array([d for _, d in pairs], np.float32)
    return ids, jnp.asarray(np.tile(col[:, None], (1, DIM)))


# A batch's LENGTH is a shape, and every new shape is some sixty XLA programs
# to compile: the properties hold for whatever ids, duplicates and deltas a
# batch has, so the examples draw those freely and their length from six.
LENGTHS = (1, 2, 3, 7, 16, 24)
ids_deltas = st.sampled_from(LENGTHS).flatmap(lambda n: st.lists(
    st.tuples(
        st.integers(min_value=-3, max_value=CAP + 3),
        st.floats(min_value=-5, max_value=5, allow_nan=False, width=32),
    ),
    min_size=n,
    max_size=n,
))


@settings(max_examples=25, deadline=None)
@given(ids_deltas)
def test_push_matches_sequential_oracle(pairs):
    ids, deltas = _batch(pairs)
    out = _store().push(ids, deltas)
    want = np.zeros((CAP, DIM), np.float32)
    for i, d in pairs:
        if 0 <= i < CAP:
            want[i] += d
    np.testing.assert_allclose(np.asarray(out.values()), want, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(ids_deltas, st.randoms(use_true_random=False))
def test_push_order_invariant(pairs, rnd):
    """Commutative add: any permutation of the batch yields the same
    table (the async-interleaving tolerance the reference relies on)."""
    shuffled = list(pairs)
    rnd.shuffle(shuffled)

    def run(ps):
        ids, deltas = _batch(ps)
        return np.asarray(_store().push(ids, deltas).values())

    np.testing.assert_allclose(run(pairs), run(shuffled), atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(ids_deltas)
def test_pull_after_push_roundtrip(pairs):
    ids, deltas = _batch(pairs)
    store = _store().push(ids, deltas)
    in_range = jnp.clip(ids, 0, CAP - 1)
    pulled = np.asarray(store.pull(in_range))
    table = np.asarray(store.values())
    np.testing.assert_allclose(pulled, table[np.asarray(in_range)], atol=1e-5)


# -- a packed rule store (rows of 9 to 64 lanes under a rule) ----------------
RULE_DIM = 36


def _rule(current, combined):
    return 0.5 * current + combined + 1.0


def _rule_stores():
    values = jnp.asarray(
        (np.arange(CAP * RULE_DIM, dtype=np.float32) % 11 - 5.0
         ).reshape(CAP, RULE_DIM))
    return [
        ShardedParamStore.from_values(values, update=_rule, layout=layout)
        for layout in ("auto", "dense")
    ]


def _rule_batch(pairs):
    ids = jnp.asarray([i for i, _ in pairs], jnp.int32)
    col = np.array([d for _, d in pairs], np.float32)
    lanes = np.arange(RULE_DIM, dtype=np.float32)[None, :]
    return ids, jnp.asarray(col[:, None] * (1.0 + lanes))


@settings(max_examples=25, deadline=None)
@given(ids_deltas)
def test_a_packed_rule_push_is_the_dense_rule_push(pairs):
    """Three 36-lane rows to a physical row against one row a row: whatever
    the batch, the same rows bit for bit, an untouched row left as it was
    and a pull of what was pushed."""
    packed, dense = _rule_stores()
    assert packed.spec.layout == "packed" and dense.spec.layout == "dense"
    ids, deltas = _rule_batch(pairs)
    got = packed.push(ids, deltas)
    want = np.asarray(dense.push(ids, deltas).values())
    assert np.asarray(got.values()).tobytes() == want.tobytes()
    touched = {i for i, _ in pairs if 0 <= i < CAP}
    before = np.asarray(dense.values())
    for row in range(CAP):
        assert (row in touched) != (want[row].tobytes() == before[row].tobytes())
    in_range = jnp.clip(ids, 0, CAP - 1)
    assert np.asarray(got.pull(in_range)).tobytes() == (
        want[np.asarray(in_range)].tobytes())


@settings(max_examples=25, deadline=None)
@given(ids_deltas, st.randoms(use_true_random=False))
def test_a_packed_rule_push_names_each_row_once_whatever_the_order(pairs, rnd):
    """The rule runs once a touched row on the SUM of its deltas: a shuffled
    batch gives the same rows to the sum's rounding, the same rows touched."""
    shuffled = list(pairs)
    rnd.shuffle(shuffled)

    def run(ps):
        return np.asarray(_rule_stores()[0].push(*_rule_batch(ps)).values())

    np.testing.assert_allclose(run(pairs), run(shuffled), rtol=1e-5, atol=1e-4)
