"""Degree-2 factorisation machine: ``FactorizationMachine`` +
``models/factorization_machine.make_store`` with that function's default
layout and scatter arm (no arm is chosen for speed here)."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import datagen

STEP_PROGRAM = "jit_step"


def build(cfg: dict, seed: int, mesh):
    """The store's spec is ``make_store``'s own (taken abstractly, nothing
    allocated); its rows are made on the device in one jitted call that takes
    the seed as an ARGUMENT, in ``make_store``'s distribution (w = 0, v ~
    N(0, init_scale)).  ``make_store(seed=...)`` bakes its seed into the
    program as a constant: every new ``--seed`` would compile the 6 GiB init
    again (14 s of set-up, my chip run, PR 25)."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu import ShardedParamStore
    from flink_parameter_server_tpu.models.factorization_machine import (
        FactorizationMachine,
        FMConfig,
        make_store,
    )

    fm = FMConfig(
        num_features=cfg["num_features"], dim=cfg["dim"],
        learning_rate=float(cfg["learning_rate"]), loss="logistic",
    )
    dtype = jnp.dtype(cfg["dtype"])
    spec = jax.eval_shape(lambda: make_store(fm, mesh=mesh, dtype=dtype)).spec

    def rows(key):
        v = float(cfg["init_scale"]) * jax.random.normal(
            key, (fm.num_features, fm.dim), dtype
        )
        return jnp.concatenate(
            [jnp.zeros((fm.num_features, 1), dtype), v], axis=-1
        )

    values = jax.jit(rows, out_shardings=spec.sharding())(
        jax.random.PRNGKey(seed)
    )
    return FactorizationMachine(fm), ShardedParamStore.from_spec_values(
        spec, values
    )


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    return datagen.click_batches(
        cfg["field_cardinalities"], cfg["dense_fields"], cfg["batch"], n,
        feature_keys=traffic["keys"], seed=seed,
    )


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    import jax.numpy as jnp

    return {"feature": np.asarray(
        store.pull(jnp.asarray(ids["feature"])), np.float32
    )}


def hbm_bytes_per_step(cfg: dict) -> float:
    """One (1 + dim)-wide row read for the gather, one read and one write
    for the scatter-add, for each of the batch's ``batch * fields`` active
    features.  Lane padding of the narrow row is waste, not need."""
    el = np.dtype(cfg["dtype"]).itemsize
    return 3.0 * cfg["batch"] * cfg["fields"] * (1 + cfg["dim"]) * el
