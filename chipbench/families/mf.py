"""Online matrix factorisation: ``OnlineMatrixFactorization`` (user factors
in worker state) + ``ShardedParamStore`` (item factors), built as
``chip_smoke.py``'s main path builds them."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import datagen

# the jitted program ``transform_batched`` dispatches once per microbatch
STEP_PROGRAM = "jit_step"


def build(cfg: dict, seed: int, mesh):
    """The item store's spec is ``ShardedParamStore.create``'s own (taken
    abstractly); its rows ~ N(0, init_scale) are made on the device in one
    jitted call that takes the seed as an ARGUMENT.  The program's per-id
    initialisers bake their seed into the program as a constant, so a new
    ``--seed`` would compile again: the worker state, which only the program
    can initialise (``logic.init_state``), therefore keeps the one seed of the
    configuration file, and ``--seed`` reaches the item table and the stream.
    """
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu import ShardedParamStore
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )

    dtype = jnp.dtype(cfg["dtype"])
    scale = float(cfg["init_scale"])
    shape = (cfg["num_items"], cfg["dim"])
    logic = OnlineMatrixFactorization(
        cfg["num_users"], cfg["dim"],
        updater=SGDUpdater(float(cfg["learning_rate"])),
        seed=int(cfg["worker_state_seed"]), dtype=dtype, mesh=mesh,
        init_low=-scale, init_high=scale,
    )
    spec = jax.eval_shape(lambda: ShardedParamStore.create(
        shape[0], shape[1:], dtype=dtype, mesh=mesh
    )).spec
    values = jax.jit(lambda key: scale * jax.random.normal(key, shape, dtype))(
        jax.random.PRNGKey(seed)
    )
    return logic, ShardedParamStore.from_spec_values(spec, values)


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    return datagen.rating_batches(
        cfg["num_users"], cfg["num_items"], cfg["batch"], n,
        item_keys=traffic["keys"], seed=seed,
    )


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The touched rows as float32 numpy: users from the worker state,
    items through the store's own pull."""
    import jax.numpy as jnp

    return {
        "user": np.asarray(
            jnp.take(state, jnp.asarray(ids["user"]), axis=0), np.float32
        ),
        "item": np.asarray(store.pull(jnp.asarray(ids["item"])), np.float32),
    }


def hbm_bytes_per_step(cfg: dict) -> float:
    """Bytes one step's gathers and scatters must move (the default-arm
    term of ``bench.py``'s ``hbm_bytes_per_step``): per side one row read
    for the gather and a read and a write for the scatter-add, for every
    record of the batch — user rows and item rows are ``dim`` wide."""
    el = np.dtype(cfg["dtype"]).itemsize
    return 3.0 * cfg["batch"] * (cfg["dim"] + cfg["dim"]) * el


def topk_check(cfg: dict, snapshot, answers, *, rtol: float, atol: float) -> int:
    """How many of ``answers`` (``(user, TopKResult)``) are NOT an exact
    top-K of the final snapshot, by the plain reference."""
    import jax.numpy as jnp

    from chipbench.references import mf as reference

    table = np.asarray(snapshot.store().values(), np.float32)
    users = np.asarray([u for u, _ in answers], np.int32)
    vecs = np.asarray(jnp.take(snapshot.aux, jnp.asarray(users), axis=0))
    return sum(
        not reference.topk_holds(
            vec, table, np.asarray(ans.item_ids), np.asarray(ans.scores),
            rtol=rtol, atol=atol,
        )
        for vec, (_, ans) in zip(vecs, answers)
    )
