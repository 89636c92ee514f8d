"""Sparse logistic regression with the FTRL-Proximal rule on the server:
``models/logistic_ftrl.LogisticFTRL`` + ``make_store`` with that function's
default layout; the rule's hyper-parameters are the configuration's."""
from __future__ import annotations

import numpy as np

# the record, its batches and the fetch of touched rows are fm-criteo's
from chipbench.families.fm import STEP_PROGRAM, host_batches, rows  # noqa: F401


def build(cfg: dict, seed: int, mesh):
    """The store's spec is ``make_store``'s own (taken abstractly, nothing
    allocated); its rows are the configuration's warm start, made on the
    device in one jitted call that takes the seed as an ARGUMENT (a seed
    baked into the program would compile the init again for every
    ``--seed``: ``families/fm.py``): ``z ~ N(0, z_std)``, ``n ~ U[0,
    n_max)``, ``w`` the rule's own weight of them, so that both branches of
    its threshold hold rows from the first step."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu import ShardedParamStore
    from flink_parameter_server_tpu.models import logistic_ftrl as lf

    rule = lf.FTRLProximal(**{k: float(cfg[k]) for k in ("alpha", "beta", "l1", "l2")})
    dtype = jnp.dtype(cfg["dtype"])
    rows_n = int(cfg["num_features"])
    spec = jax.eval_shape(
        lambda: lf.make_store(rows_n, rule, mesh=mesh, dtype=dtype)
    ).spec
    start = cfg["warm_start"]

    def rows(key):
        kz, kn = jax.random.split(key)
        z = float(start["z_std"]) * jax.random.normal(kz, (rows_n,), dtype)
        n = float(start["n_max"]) * jax.random.uniform(kn, (rows_n,), dtype)
        return jnp.stack([rule.weights(z, n), z, n], axis=-1)

    values = jax.jit(rows, out_shardings=spec.sharding())(
        jax.random.PRNGKey(seed)
    )
    return lf.LogisticFTRL(), ShardedParamStore.from_spec_values(spec, values)


def distinct_rows_per_step(cfg: dict) -> float:
    """Expected distinct rows a batch touches under uniform field keys: the
    integer fields' one row each, and of a field of ``c`` rows ``c (1 - (1 -
    1/c)^batch)``."""
    c = np.asarray(cfg["field_cardinalities"], np.float64)
    return cfg["dense_fields"] + float(
        (c * -np.expm1(cfg["batch"] * np.log1p(-1.0 / c))).sum()
    )


def hbm_bytes_per_step(cfg: dict) -> float:
    """What the step MUST move: the weight of every active feature for the
    pull (``batch x fields`` elements), and every DISTINCT row the batch
    touches read once and written once, three elements wide (the rule is
    applied once a row, so duplicates need no traffic of their own)."""
    el = np.dtype(cfg["dtype"]).itemsize
    return el * (
        cfg["batch"] * cfg["fields"] + 2 * 3 * distinct_rows_per_step(cfg)
    )
