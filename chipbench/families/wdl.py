"""Wide & Deep: TWO stores in one step, the hashed crosses' ``(w, z, n)``
rows under FTRL-Proximal and the categorical values' ``(e, G)`` rows under
AdaGrad, the ReLU net and its accumulators in the worker's state:
``models/wide_deep.WideAndDeep`` + ``make_stores`` with that function's
default layouts (no arm is chosen here), through ``StreamingDriver`` as every
family; the record is cell 10's (13 dense values, 26 ids, a label)."""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np

# the record, its batches and its ids (each a row of the deep store) are
# dlrm-criteo-10m's, draw for draw
from chipbench.families.dlrm import STEP_PROGRAM, host_batches  # noqa: F401
from chipbench.references.wdl import CODE_BITS, _laid, leaf_shapes


def fields(cfg: dict) -> int:
    return len(cfg["field_cardinalities"])


def keys_per_step(cfg: dict) -> int:
    """Lanes of EACH store's pull and push a step: a cross a field."""
    return int(cfg["batch"]) * fields(cfg)


def build(cfg: dict, seed: int, mesh):
    """Both stores are ``make_stores``' own, built on the device in ONE
    jitted call that takes the seed as an ARGUMENT (a seed baked into the
    program would compile the init again for every ``--seed``:
    ``families/fm.py``) and initialised IN PLACE
    (``ShardedParamStore.create``: at 12.58 GB no second copy of the deep
    table fits beside it), warm as the configuration's ``warm_start`` says.
    The net is the logic's own ``init_state`` of the same seed."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import wide_deep as wd
    from flink_parameter_server_tpu.models.logistic_ftrl import FTRLProximal

    model = wd.WideDeepConfig(
        tuple(int(c) for c in cfg["field_cardinalities"]),
        dense_features=int(cfg["dense_fields"]), dim=int(cfg["dim"]),
        hidden=tuple(cfg["hidden"]), cross_buckets=int(cfg["cross_buckets"]),
        learning_rate=float(cfg["learning_rate"]), eps=float(cfg["eps"]),
        acc0=float(cfg["acc0"]), ftrl=FTRLProximal(
            **{k: float(cfg[k]) for k in ("alpha", "beta", "l1", "l2")}),
    )
    warm = {k: float(v) for k, v in cfg["warm_start"].items()}
    seed = np.uint32(seed % 2**32)
    stores = jax.jit(lambda s: wd.make_stores(
        model, seed=s, mesh=mesh, dtype=jnp.dtype(cfg["dtype"]), **warm,
    ))(seed)
    return wd.WideAndDeep(model, seed=seed), stores


@functools.lru_cache(maxsize=None)
def _keys_program(buckets: int):
    """``(cross, left id, right id) -> wide row``, ONE program (op by op,
    every shape of the hash is a program each process loads)."""
    import jax

    from flink_parameter_server_tpu.ops.hashing import pair_key

    return jax.jit(lambda cross, left, right: cross * buckets + pair_key(
        left, right, buckets))


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """ONE group as float32 numpy, a deep row's ``2 dim`` lanes wide
    (``chipbench/references/wdl.py`` says why one): the touched deep rows
    WHOLE (embedding and accumulators) through that store's own pull; the
    touched wide rows ``(w, z, n)`` through theirs, flat; then every dense
    leaf, ``bias`` and, in the same order, every accumulator from the
    worker's state, flat (zeros fill a part's last row).  A touched wide row
    comes as the CODE of its cross (``references/wdl.cross_codes``: which
    cross, and the two ids it joins) and is fetched under the PROGRAM's hash
    of it, ``ops/hashing.pair_key``, among that cross's own buckets (the
    wide store's rows over the crosses): the reference addresses the same
    rows by its own arithmetic."""
    import jax.numpy as jnp

    deep = np.asarray(store["deep"].pull(jnp.asarray(ids["deep"])), np.float32)
    lanes = deep.shape[1]
    codes, low = ids["wide"], (1 << CODE_BITS) - 1
    cross = codes >> (2 * CODE_BITS)
    buckets = store["wide"].spec.capacity // (int(cross.max()) + 1)
    keys = _keys_program(buckets)(
        cross.astype(np.int32), ((codes >> CODE_BITS) & low).astype(np.int32),
        (codes & low).astype(np.int32))
    wide = np.asarray(store["wide"].pull(keys), np.float32)
    names = sorted(k for k in state if not k.endswith("_acc"))
    dense = np.concatenate([
        np.asarray(state[k + tail], np.float32).reshape(-1)
        for tail in ("", "_acc") for k in names
    ])
    return {"parameters": np.concatenate(
        [deep, _laid(wide.reshape(-1), lanes), _laid(dense, lanes)])}


def dense_params(cfg: dict) -> int:
    return int(sum(np.prod(shape) for shape in leaf_shapes(cfg).values()))


def deep_distinct_rows(cfg: dict) -> float:
    """Expected distinct deep rows a batch touches under uniform keys: of a
    field of ``c`` rows ``c (1 - (1 - 1/c)^batch)``, no two fields sharing
    one."""
    c = np.asarray(cfg["field_cardinalities"], np.float64)
    return float((c * -np.expm1(cfg["batch"] * np.log1p(-1.0 / c))).sum())


def wide_distinct_rows(cfg: dict) -> float:
    """Expected distinct wide rows a batch touches under uniform keys.
    Cross ``j`` joins field ``j`` (``a`` rows) and field ``j + 1`` (``b``
    rows): the ``a b`` equally likely pairs, of which the hash, symmetric in
    its two ids' ORDER and blind to which field an id came from, sees every
    one as its own (ids of two fields never meet: each is a row of the deep
    store), ``D = a b (1 - (1 - 1 / (a b))^batch)`` distinct; those fall on
    the cross's ``m`` buckets as ``D`` balls, ``m (1 - (1 - 1/m)^D)`` of
    them hit.  A cross of two small fields (3 x 7,112 values) touches few."""
    c = np.asarray(cfg["field_cardinalities"], np.float64)
    pairs = c * np.roll(c, -1)
    distinct = pairs * -np.expm1(cfg["batch"] * np.log1p(-1.0 / pairs))
    m = float(cfg["cross_buckets"])
    return float((m * -np.expm1(distinct * np.log1p(-1.0 / m))).sum())


def distinct_rows_per_step(cfg: dict) -> float:
    """Rule rows a step rewrites, both stores'."""
    return deep_distinct_rows(cfg) + wide_distinct_rows(cfg)


def dense_flops_per_step(cfg: dict) -> float:
    """MODEL floating-point operations of the deep net a step: 2 a
    multiply-add of the forward pass (every layer: 1,520,896 multiply-adds
    an example at the source's sizes), the backward pass twice that.  The
    passes a float32 product takes on a bfloat16 MXU are not counted, nor
    the bias adds, ReLUs and the loss: a lower bound."""
    macs = sum(
        shape[0] * shape[1] for shape in leaf_shapes(cfg).values()
        if len(shape) == 2
    )
    return 2.0 * macs * 3.0 * cfg["batch"]


def hbm_bytes_per_step(cfg: dict) -> float:
    """What the two STORES must move a step, whatever implements them: for
    the pulls the ``dim`` lanes of an embedding and the ONE weight of a
    cross that a worker reads, a key; for the pushes the gradients read once
    (``dim`` lanes and one) and every DISTINCT row read once and written
    once at its whole width (``2 dim`` lanes; three), the rule running once
    a row.  No id, no sort, no hash; the net's 12 MB of leaves and
    accumulators and its activations are not the stores': a lower bound, so
    a share of the roofline made of it cannot pass 100 %."""
    el = np.dtype(cfg["dtype"]).itemsize
    dim = int(cfg["dim"])
    return el * (
        2 * keys_per_step(cfg) * (dim + 1)
        + 2 * 2 * dim * deep_distinct_rows(cfg)
        + 2 * 3 * wide_distinct_rows(cfg)
    )
