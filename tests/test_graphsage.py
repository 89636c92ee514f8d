"""GraphSAGE by sampled mini-batches (``models/graphsage.py``) and what it
forced: a step that pulls again from what it pulled
(``BatchedWorkerLogic.next_keys``), stores a step only reads, int32 scalar
rows.  Small graphs on the CPU; cell 18's full-size step is compiled in
``tests/test_tpu_compile.py``."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run, spec as bench_spec
from flink_parameter_server_tpu import ShardedParamStore
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.batched import (
    BatchedWorkerLogic, PushRequest)
from flink_parameter_server_tpu.core.store import StoreGroup
from flink_parameter_server_tpu.core.transform import (
    make_train_step, transform_batched)
from flink_parameter_server_tpu.models import graphsage as gs
from flink_parameter_server_tpu.models import matrix_factorization as mfm
from flink_parameter_server_tpu.models import wide_deep as wd

# a small share: the published depth, narrow widths
SMALL = dict(
    family="sage", num_nodes=700, num_edges=700 * 29, num_train_nodes=200,
    widths=[16, 32, 32, 7], fanouts=[4, 3, 2], dropout=0.5, dtype="float32",
    batch=24, learning_rate=0.003, beta1=0.9, beta2=0.999, eps=1e-8,
    degree_law={"exponent": 1.4515581061330063, "cap": 1000},
    reference={"file": "chipbench/references/sage.py", "batches": 2,
               "delta_rtol": 2e-5, "delta_atol": 1e-12, "row_ulps": 4,
               "relu_ulps": 16},
)
UNIFORM = {"keys": {"kind": "uniform"}}


def _small(seed=5):
    fam = bench_spec.family("sage")
    logic, stores = fam.build(SMALL, seed, None)
    return fam, bench_spec.reference(SMALL), logic, stores


def _graph(degrees, features=4, seed=0):
    """Three stores of a hand-made graph: node ``v`` has ``degrees[v]``
    neighbours, ``(v + 1 + j) mod n`` its ``j``-th (distinct)."""
    n = len(degrees)
    ends = np.concatenate([[0], np.cumsum(degrees)]).astype(np.int32)
    nbr = np.concatenate([
        (v + 1 + np.arange(d)) % n for v, d in enumerate(degrees)
    ] + [np.zeros(0)]).astype(np.int32)
    feat = np.random.default_rng(seed).uniform(-1, 1, (n, features))
    return StoreGroup.of({
        "off": ShardedParamStore.from_values(jnp.asarray(ends), layout="auto"),
        "nbr": ShardedParamStore.from_values(jnp.asarray(nbr), layout="auto"),
        "feat": ShardedParamStore.from_values(
            jnp.asarray(feat, jnp.float32), layout="auto"),
    }), ends, nbr


class _Spy(gs.GraphSage):
    """Hands the rows of every round out of the step."""

    def step(self, state, batch, pulled):
        new, reqs, out = super().step(state, batch, pulled)
        rows = {f"round{n}": next(iter(r.values()))
                for n, r in enumerate(pulled)}
        return new, reqs, {**out, **rows}


def test_two_steps_are_the_plain_references():
    """The net, Adam's moments and the loss of two steps against
    ``chipbench/references/sage.py`` (numpy, its own sampler arithmetic),
    under the benchmark's own check; a bfloat16 pass in the products fails
    it."""
    fam, ref, logic, stores = _small()
    batches = fam.host_batches(SMALL, UNIFORM, 11, 2)
    ids = ref.touched(batches)
    state = logic.init_state(jax.random.PRNGKey(0))
    before = fam.rows(stores, state, ids)
    step = jax.jit(make_train_step(logic, stores.spec))

    def train(state):
        table, losses = stores.table, []
        for b in batches:
            table, state, out = step(table, state, b)
            losses.append(np.asarray(out["loss"]))
        return fam.rows(type(stores)(stores.spec, table), state, ids), losses

    got, losses = train(state)
    want = ref.apply(SMALL, before, ids, batches)
    failures, worst = run._check_rows(SMALL["reference"], want, got, before)
    assert failures == [] and 0.0 < worst["share"] <= 1.0, (failures, worst)
    # the first step's loss, seed by seed, from the reference's forward pass
    leaves = {k: v for k, v in ref.unlaid(SMALL, before["parameters"]).items()
              if k in ref.leaf_shapes(SMALL)}
    cuts = np.cumsum([0] + ref.lanes_at(SMALL, SMALL["batch"]))
    live = [before["live"][0][a:b] for a, b in zip(cuts, cuts[1:])]
    _, logits = ref.forward(
        SMALL, leaves, before["features"][0], live, before["key"], 0)
    top = logits.max(axis=1)
    lse = top + np.log(np.exp(logits - top[:, None]).sum(axis=1))
    picked = logits[np.arange(logits.shape[0]), ids["label"][0]]
    np.testing.assert_allclose(losses[0], lse - picked, rtol=2e-5, atol=2e-6)
    # the same two steps with every product's operands rounded to bfloat16
    real = gs._dot
    gs._dot = lambda a, b: real(
        a.astype(jnp.bfloat16).astype(jnp.float32),
        b.astype(jnp.bfloat16).astype(jnp.float32))
    try:
        step = jax.jit(make_train_step(logic, stores.spec))
        coarse, _ = train(state)
    finally:
        gs._dot = real
    failures, worst = run._check_rows(SMALL["reference"], want, coarse, before)
    assert failures and worst["share"] > 100.0


def test_the_chained_step_pulls_what_separate_eager_pulls_pull():
    """Seven rounds inside ONE jitted step against the same rounds done by
    hand, a ``store.pull`` a round and ``next_keys`` between them: every
    round's rows bit for bit, and all three tables left as they were."""
    _, _, logic, stores = _small()
    spy = _Spy(logic.config, seed=logic.seed)
    state = spy.init_state(jax.random.PRNGKey(0))
    batch = {"seed": jnp.arange(24, dtype=jnp.int32) * 7 % 200,
             "label": jnp.zeros(24, jnp.int32),
             "mask": jnp.arange(24) % 5 != 0}
    tables, _, out = jax.jit(make_train_step(spy, stores.spec))(
        stores.table, state, batch)
    keys, pulled = spy.keys(batch), []
    while keys is not None:
        (name, block), = keys.items()
        pulled.append({name: stores[name].pull(block)})
        keys = spy.next_keys(state, batch, tuple(pulled))
    assert len(pulled) == 7
    for n, rows in enumerate(pulled):
        (eager,) = rows.values()
        assert np.array_equal(np.asarray(out[f"round{n}"]), np.asarray(eager))
    for name in stores.spec:
        assert np.array_equal(
            np.asarray(tables[name]), np.asarray(stores.table[name]))
    assert {k: int(v) for k, v in out.items() if k.startswith("ps_pull")} == {
        "ps_pull_lanes@off": 2 * 24 * (1 + 2 + 6),
        "ps_pull_lanes@nbr": 24 * (2 + 6 + 24),
        "ps_pull_lanes@feat": 24 * (1 + 2 + 6 + 24)}
    assert not [k for k in out if "push" in k or "rule" in k]


@pytest.mark.parametrize("what, want", [
    ("single_store", "83c76269c615ef58"), ("store_group", "2ab0e375249ed376")])
def test_a_one_round_logics_step_text_is_the_parents(what, want):
    """A logic that answers no ``next_keys`` lowers to the text PR 75's
    parent lowered (hashed there, commit 71290ba): the rounds' seam adds no
    op and no name to a step of one round.  PR 76 moved both and meant to
    (the arm ``take`` and ``_narrow_pull`` gather with ``mode="clip"``: the
    fill's wrap, compares and select gone, the gather clamping by itself;
    ``6096915f447dd6ab`` and ``22bde0b296d5fadc`` until then)."""
    shape = jax.ShapeDtypeStruct
    if what == "single_store":
        logic = mfm.OnlineMatrixFactorization(
            500, 16, updater=mfm.SGDUpdater(2e-4))
        spec = jax.eval_shape(lambda: ShardedParamStore.create(
            300, (16,), dtype=jnp.float32)).spec
        args = (shape(spec.table_shape(), jnp.float32),
                shape((500, 16), jnp.float32),
                {"user": shape((64,), jnp.int32),
                 "item": shape((64,), jnp.int32),
                 "rating": shape((64,), jnp.float32),
                 "mask": shape((64,), jnp.bool_)})
    else:
        model = wd.WideDeepConfig(
            (50, 7, 30), dim=8, hidden=(16, 8), cross_buckets=64)
        spec = jax.eval_shape(lambda: wd.make_stores(model)).spec
        logic = wd.WideAndDeep(model)
        args = ({n: shape(spec[n].table_shape(), jnp.float32) for n in spec},
                jax.eval_shape(lambda: logic.init_state(jax.random.PRNGKey(0))),
                {"dense": shape((32, 13), jnp.float32),
                 "ids": shape((32, 3), jnp.int32),
                 "label": shape((32,), jnp.float32),
                 "mask": shape((32,), jnp.bool_)})
    text = jax.jit(
        make_train_step(logic, spec), donate_argnums=(0, 1)).lower(*args)
    assert hashlib.sha256(
        text.as_text().encode()).hexdigest()[:16] == want


class _Chase(BatchedWorkerLogic):
    """ONE store, two rounds: a row's first lane names the next row."""

    def init_state(self, rng):
        return ()

    def keys(self, batch):
        return batch["at"]

    def next_keys(self, state, batch, pulled):
        if len(pulled) == 2:
            return None
        return pulled[-1][:, 0].astype(jnp.int32)

    def step(self, state, batch, pulled):
        first, second = pulled
        return state, PushRequest(
            batch["at"], jnp.ones_like(first), None), {"second": second}


def test_a_single_stores_step_takes_the_rounds_through_the_same_seam():
    values = np.zeros((50, 2), np.float32)
    values[:, 0] = (np.arange(50) * 3 + 1) % 50
    values[:, 1] = np.arange(50)
    store = ShardedParamStore.from_values(jnp.asarray(values))
    at = jnp.asarray([4, 9, 9, 40], jnp.int32)
    table, _, out = jax.jit(make_train_step(_Chase(), store.spec))(
        store.table, (), {"at": at})
    hop = (np.asarray(at) * 3 + 1) % 50
    assert np.array_equal(np.asarray(out["second"]), values[hop])
    assert int(out["ps_pull_lanes"]) == 8
    want = values.copy()
    np.add.at(want, np.asarray(at), 1.0)
    assert np.array_equal(np.asarray(table)[:50], want)
    lowered = jax.jit(make_train_step(_Chase(), store.spec)).lower(
        store.table, (), {"at": at}).as_text(debug_info=True)
    assert "ps.pull/round.1" in lowered and "round.0" not in lowered


def test_a_store_a_step_only_reads_leaves_it_aliased_and_untouched():
    """The donated step hands every read-only table back in the buffer it
    came in (no copy of 7.1 GB at cell 18's size), bit for bit, and says so
    in its counts: no output of a push."""
    _, _, logic, stores = _small()
    state = logic.init_state(jax.random.PRNGKey(0))
    batch = {"seed": jnp.arange(24, dtype=jnp.int32),
             "label": jnp.zeros(24, jnp.int32), "mask": jnp.ones(24, bool)}
    kept = {n: np.asarray(stores.table[n]) for n in stores.spec}
    step = jax.jit(make_train_step(logic, stores.spec), donate_argnums=(0, 1))
    lowered = step.lower(stores.table, state, batch).as_text()
    tables = {n: jnp.array(stores.table[n]) for n in stores.spec}
    where = {n: t.unsafe_buffer_pointer() for n, t in tables.items()}
    new, _, out = step(tables, state, batch)
    for name in stores.spec:
        assert np.array_equal(np.asarray(new[name]), kept[name])
        assert new[name].unsafe_buffer_pointer() == where[name], name
    assert lowered.count("tf.aliasing_output") >= 3
    assert not [k for k in out if "push" in k or "rule" in k]
    assert store_mod.arms(stores.spec["feat"], pull_lanes=100, only_read=True
                          ).push == ""


def test_every_draw_is_a_neighbour_and_dead_lanes_are_the_degree_zero_ones():
    degrees = [3, 0, 5, 1, 0, 7, 2, 4, 0, 6, 1, 9]
    stores, ends, nbr = _graph(degrees)
    config = gs.SageConfig(
        num_nodes=12, num_edges=int(ends[-1]), widths=(4, 8, 8, 3),
        fanouts=(4, 3, 2))
    spy = _Spy(config, seed=1)
    state = spy.init_state(jax.random.PRNGKey(0))
    seeds = np.arange(12, dtype=np.int32)
    batch = {"seed": jnp.asarray(seeds), "label": jnp.zeros(12, jnp.int32),
             "mask": jnp.ones(12, bool)}
    _, _, out = jax.jit(make_train_step(spy, stores.spec))(
        stores.table, state, batch)
    parents, live = seeds, np.ones(12, bool)
    dead = 0
    for depth, k in enumerate((2, 3, 4)):
        drawn = np.asarray(out[f"round{2 * depth + 1}"])  # (k, parents)
        has = live & (np.asarray(degrees)[parents] > 0)
        for j in range(k):
            for i, v in enumerate(parents):
                if has[i]:
                    assert drawn[j, i] in nbr[ends[v]:ends[v + 1]]
        dead += k * int((~has).sum())
        parents = np.where(has[None], drawn, 0).reshape(-1)
        live = np.broadcast_to(has, drawn.shape).reshape(-1)
    lanes = 12 * (2 + 6 + 24)
    assert int(out["sage_sampled_lanes"]) == lanes
    assert int(out["sage_live_lanes"]) == lanes - dead and dead > 0


def test_the_draws_of_one_row_are_uniform():
    """A chi-square on one adjacency row: 4,000 draws over a node's seven
    neighbours (the 99.9 % point of six degrees of freedom is 22.5)."""
    stores, ends, nbr = _graph([7] * 12)
    config = gs.SageConfig(
        num_nodes=12, num_edges=int(ends[-1]), widths=(4, 8, 8, 3),
        fanouts=(1, 1, 40))
    spy = _Spy(config, seed=2)
    state = spy.init_state(jax.random.PRNGKey(0))
    batch = {"seed": jnp.full((100,), 5, jnp.int32),
             "label": jnp.zeros(100, jnp.int32), "mask": jnp.ones(100, bool)}
    _, _, out = jax.jit(make_train_step(spy, stores.spec))(
        stores.table, state, batch)
    drawn = np.asarray(out["round1"]).reshape(-1)
    counts = np.asarray([(drawn == u).sum() for u in nbr[ends[5]:ends[6]]])
    assert counts.sum() == 4_000
    assert ((counts - 4_000 / 7) ** 2 / (4_000 / 7)).sum() < 22.5


def test_save_and_resume_carry_the_stream_of_draws(tmp_path):
    """Four steps in one run against two, a checkpoint of the group and the
    state (the key and Adam's count with it), and two more: every leaf, the
    moments and all three tables bit for bit."""
    from flink_parameter_server_tpu.training import checkpoint

    fam, _, logic, stores = _small()
    batches = fam.host_batches(SMALL, UNIFORM, 3, 4)
    whole = transform_batched(
        batches, logic, stores, rng=jax.random.PRNGKey(0))
    first = transform_batched(
        batches[:2], logic, stores, rng=jax.random.PRNGKey(0))
    checkpoint.save(
        str(tmp_path / "ckpt"), first.store, first.worker_state, step=2)
    store, state, meta = checkpoint.restore(
        str(tmp_path / "ckpt"), stores.spec)
    assert meta["step"] == 2 and int(state["t"]) == 2
    state = jax.tree.map(jnp.asarray, state)
    rest = transform_batched(batches[2:], logic, store, initial_state=state)
    for k, v in whole.worker_state.items():
        assert np.array_equal(np.asarray(v), np.asarray(rest.worker_state[k])), k
    for name in stores.spec:
        # (the rows a checkpoint keeps: a table's padding past its capacity
        # is the placement's, zeros after a restore)
        assert np.array_equal(np.asarray(rest.store[name].values()),
                              np.asarray(stores[name].values()))
        assert rest.store.table[name].dtype == stores.table[name].dtype
    assert [float(o["loss"].sum()) for o in rest.worker_outputs] == [
        float(o["loss"].sum()) for o in whole.worker_outputs[2:]]


@pytest.mark.parametrize("shape", [(), (1,)], ids=["rank0", "one_lane"])
@pytest.mark.parametrize("layout", ["auto", "dense", "packed"])
def test_int32_scalar_rows_come_back_through_pull(layout, shape):
    """A table of integers under every layout ``_resolve_layout`` gives it
    (``auto``: 128 scalars to a physical row): created in place, placed from
    values, dumped and restored, pulled by a block of keys, the dead key -1
    reading row 0."""
    n = 1_000
    values = ((np.arange(n, dtype=np.int64) * 7919 + 2**30) % (2**31 - 1)
              ).astype(np.int32).reshape((n,) + shape)
    made = ShardedParamStore.create(
        n, shape, dtype=jnp.int32, layout=layout,
        init_fn=lambda ids: ((ids.astype(jnp.uint32) * np.uint32(7919)
                              + np.uint32(2**30)) % np.uint32(2**31 - 1)
                             ).astype(jnp.int32).reshape(ids.shape + shape))
    placed = ShardedParamStore.from_values(jnp.asarray(values), layout=layout)
    back = made.spec.restored(np.asarray(made.portable()))
    assert made.spec.layout == ("dense" if layout == "dense" else "packed")
    ids = np.random.default_rng(0).integers(-1, n, (37, 3)).astype(np.int32)
    for store in (made, placed, back):
        got = np.asarray(store.pull(jnp.asarray(ids)))
        assert got.dtype == np.int32
        assert np.array_equal(got, values[np.maximum(ids, 0)])
        assert np.array_equal(np.asarray(store.values()), values)
