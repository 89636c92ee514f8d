"""DiFacto, a factorisation machine with FTRL on ``w`` and AdaGrad on ``V`` as
the server's rule: ``models/difacto.DiFacto`` + ``make_store`` with that
function's default layout; the rule's hyper-parameters are the
configuration's."""
from __future__ import annotations

import numpy as np

# the record, its batches and the fetch of touched rows are fm-criteo's
from chipbench.families.fm import STEP_PROGRAM, host_batches, rows  # noqa: F401
from chipbench.families.lr import distinct_rows_per_step  # noqa: F401

RULE_KEYS = ("lr", "lr_beta", "l1", "l2", "V_lr", "V_lr_beta", "V_l2", "V_threshold")
# lanes of the row before V: w, z, s, c (the benchmark's own copy)
STATE_LANES = 4


def field_firsts(cfg: dict) -> np.ndarray:
    """First row of every categorical field (the integer fields' rows come
    before them, one each)."""
    cards = np.asarray(cfg["field_cardinalities"], np.int64)
    return cfg["dense_fields"] + np.concatenate([[0], np.cumsum(cards)[:-1]])


def warm_rows(cfg: dict, rule, key):
    """``init(ids) -> (n, 4 + 2 dim)``: the configuration's warm start
    (``assumed.warm_start``), every row from ``key`` and its own id alone:
    ``z ~ N(0, z_std)``, ``s ~ U[0, s_max)``, ``w`` the rule's own weight of
    them, ``V ~ N(0, V_init_scale)``, ``S ~ U[0, s_max)`` and the count ``c =
    floor(examples / C x e)``, ``e ~ Exp(1)``, ``C`` the cardinality of the
    row's field (1 for an integer field's row: every example names it)."""
    import jax
    import jax.numpy as jnp

    start, dim = cfg["warm_start"], int(cfg["dim"])
    dtype = jnp.dtype(cfg["dtype"])
    cards = [float(c) for c in cfg["field_cardinalities"]]
    firsts = [int(f) for f in field_firsts(cfg)]

    def one(i):
        kn, ku, ke = jax.random.split(jax.random.fold_in(key, i), 3)
        normal = jax.random.normal(kn, (1 + dim,), dtype)
        uniform = float(start["s_max"]) * jax.random.uniform(
            ku, (1 + dim,), dtype
        )
        return normal, uniform, jax.random.exponential(ke, (), dtype)

    def init(ids):
        normal, uniform, e = jax.vmap(one)(ids.astype(jnp.uint32))
        z, s = float(start["z_std"]) * normal[:, 0], uniform[:, 0]
        card = jnp.ones(ids.shape, dtype)
        for first, c in zip(firsts, cards):
            card = jnp.where(ids >= first, c, card)
        count = jnp.floor(float(start["examples"]) / card * e)
        return jnp.concatenate(
            [
                jnp.stack([rule.weights(z, s), z, s, count], axis=-1),
                float(cfg["V_init_scale"]) * normal[:, 1:],
                uniform[:, 1:],
            ],
            axis=-1,
        )

    return init


def build(cfg: dict, seed: int, mesh):
    """The store is ``make_store``'s own, built on the device in one jitted
    call that takes the seed as an ARGUMENT (a seed baked into the program
    would compile the init again for every ``--seed``: ``families/fm.py``)
    and initialised IN PLACE (``ShardedParamStore.create`` with an
    ``init_fn``): at 7.86 GB no second copy of the table fits beside it."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import difacto as df

    rule = df.DiFactoUpdater(**{k: float(cfg[k]) for k in RULE_KEYS})
    model = df.DiFactoConfig(int(cfg["num_features"]), int(cfg["dim"]))
    store = jax.jit(lambda key: df.make_store(
        model, rule, init_fn=warm_rows(cfg, rule, key), mesh=mesh,
        dtype=jnp.dtype(cfg["dtype"]),
    ))(jax.random.PRNGKey(seed))
    return df.DiFacto(model, rule), store


def _lanes(cfg: dict) -> tuple:
    """``(read by a worker, pushed with a gradient, of the whole row)``."""
    dim = int(cfg["dim"])
    return 2 + dim, 1 + dim, STATE_LANES + 2 * dim


def rule_path_bytes_per_step(cfg: dict) -> float:
    """What the SERVER side of a step (``ps.combine`` + ``ps.rule`` +
    ``ps.push``) must move, whatever implements it: the batch's gradients
    read once, the ``1 + dim`` lanes a key that can be other than zero (the
    lanes of ``z``, ``s``, ``c`` and ``S`` carry nothing), and every
    DISTINCT row the batch touches read once and written once at its whole
    ``4 + 2 dim`` lanes (the rule runs once a row, so duplicates need no
    traffic of their own).  No id, no sort, no padding of a lane or a tile
    is counted: a lower bound, so its share of the roofline cannot pass
    100 %, and a later kernel is held to the same work."""
    el = np.dtype(cfg["dtype"]).itemsize
    _, pushed, row = _lanes(cfg)
    return el * (
        cfg["batch"] * cfg["fields"] * pushed
        + 2 * row * distinct_rows_per_step(cfg)
    )


def hbm_bytes_per_step(cfg: dict) -> float:
    """What the whole step MUST move: for the pull the ``2 + dim`` lanes a
    key that a worker reads (``w``, ``c``, ``V``; it needs neither ``z`` nor
    the accumulators), and the server side's bytes
    (:func:`rule_path_bytes_per_step`): 92 + 87 + 2 x 50.7 = 280 MB at the
    configuration's sizes."""
    el = np.dtype(cfg["dtype"]).itemsize
    read, _, _ = _lanes(cfg)
    return el * cfg["batch"] * cfg["fields"] * read + rule_path_bytes_per_step(cfg)
