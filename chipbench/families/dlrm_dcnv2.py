"""MLPerf's DLRM-DCNv2 at one server's share of its tables: the rows of every
categorical field, a row its 128 weights and Adagrad's 128 accumulators, in
ONE rule store, the dense net and its accumulators in the worker's state:
``models/dlrm_dcnv2.DLRMDCNv2`` + ``make_store`` with that function's default
layout (a 256-lane rule row lies flat in two registers; no arm is chosen
here), and the record stream: 13 dense values, a label and 26 bags of ids,
every id drawn within its field's HELD rows."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import datagen
from chipbench.references.dlrm_dcnv2 import leaf_shapes

STEP_PROGRAM = "jit_step"


def held_rows(cfg: dict) -> List[int]:
    """Rows of every table that ONE of the deployment's ``servers`` holds:
    the published counts split row-wise and evenly, the last server short
    (``ceil``)."""
    servers = int(cfg["servers"])
    return [
        -(-int(n) // servers)
        for n in cfg["source_sizes"]["num_embeddings_per_feature"]
    ]


def lookups(cfg: dict) -> int:
    """Ids an example: the bags' sizes in all."""
    return int(sum(cfg["multi_hot_sizes"]))


def keys_per_step(cfg: dict) -> int:
    return int(cfg["batch"]) * lookups(cfg)


def dense_params(cfg: dict) -> int:
    return int(sum(np.prod(shape) for shape in leaf_shapes(cfg).values()))


def build(cfg: dict, seed: int, mesh):
    """The store is ``make_store``'s own, built on the device in one jitted
    call that takes the seed as an ARGUMENT (a seed baked into the program
    would compile the init again for every ``--seed``: ``families/fm.py``)
    and initialised IN PLACE (``ShardedParamStore.create``); a table starts
    as the source's does, by its PUBLISHED row count.  The dense net is the
    logic's own ``init_state`` of the same seed."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import dlrm_dcnv2 as dcn

    model = dcn.DCNv2Config(
        tuple(int(c) for c in cfg["field_cardinalities"]),
        tuple(int(s) for s in cfg["multi_hot_sizes"]),
        tuple(
            int(n) for n in cfg["source_sizes"]["num_embeddings_per_feature"]),
        dense_features=int(cfg["dense_fields"]), dim=int(cfg["dim"]),
        bottom_mlp=tuple(cfg["bottom_mlp"]),
        cross_layers=int(cfg["cross_layers"]),
        cross_rank=int(cfg["cross_rank"]), over_mlp=tuple(cfg["over_mlp"]),
        learning_rate=float(cfg["learning_rate"]), eps=float(cfg["eps"]),
    )
    seed = np.uint32(seed % 2**32)
    store = jax.jit(lambda s: dcn.make_store(
        model, seed=s, mesh=mesh, dtype=jnp.dtype(cfg["dtype"]),
    ))(seed)
    return dcn.DLRMDCNv2(model, seed=seed), store


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    """``n`` full batches.  An example's ids, field after field the ids of
    each field's bag, each drawn by the traffic's key law within its field's
    held rows, independently (a bag may name a row twice, as the source's
    synthetic multi-hot data may), offset by the fields before it; then its
    dense values uniform in [0, 1) and its label 0 / 1 with equal odds, as
    cell 10's records draw them (``datagen.click_batches``).  Batch ``i``
    draws from its own generator, so the stream is a function of the seed
    alone."""
    cards = np.asarray(cfg["field_cardinalities"], np.int64)
    sizes = np.asarray(cfg["multi_hot_sizes"])
    first = np.concatenate([[0], np.cumsum(cards)[:-1]])
    lane_first, lane_rows = np.repeat(first, sizes), np.repeat(cards, sizes)
    batch, dense = int(cfg["batch"]), int(cfg["dense_fields"])

    def one(i):
        rng = np.random.default_rng([seed, i + 1])
        ids = datagen.draw_keys(
            rng, traffic["keys"], (batch, lane_rows.size), lane_rows)
        return {
            "ids": (ids + lane_first).astype(np.int32),
            "dense": rng.random((batch, dense), np.float32),
            "label": rng.choice(np.array([0.0, 1.0], np.float32), batch),
            "mask": np.ones(batch, bool),
        }

    return datagen._batches(one, n)


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """ONE group as float32 numpy, a whole row's lanes wide: the touched
    rows WHOLE (weights and accumulators) through the store's own pull, then
    every dense leaf and then every leaf's accumulator from the worker's
    state, flat, the leaves by name (zeros fill the last row)."""
    import jax.numpy as jnp

    pulled = np.asarray(store.pull(jnp.asarray(ids["embedding"])), np.float32)
    order = sorted(k for k in state if not k.endswith("_acc"))
    flat = np.concatenate([
        np.asarray(state[k + tail], np.float32).reshape(-1)
        for tail in ("", "_acc") for k in order
    ])
    flat = np.pad(flat, (0, -flat.size % pulled.shape[1]))
    return {"parameters": np.concatenate(
        [pulled, flat.reshape(-1, pulled.shape[1])])}


def distinct_rows_per_step(cfg: dict) -> float:
    """Expected distinct rows a batch touches under uniform keys, in closed
    form: field ``f``'s ``batch x size_f`` keys fall on its ``m_f`` held
    rows, ``m (1 - (1 - 1/m)^keys)`` of them distinct (a table of one row:
    one), and no two fields share a row."""
    total = 0.0
    for m, size in zip(cfg["field_cardinalities"], cfg["multi_hot_sizes"]):
        keys = float(cfg["batch"]) * size
        total += 1.0 if m == 1 else m * -np.expm1(keys * np.log1p(-1.0 / m))
    return total


def dense_flops_per_step(cfg: dict) -> float:
    """MODEL floating-point operations of the dense net a step: 2 a
    multiply-add of the forward pass (every matrix: the two MLPs' layers and
    a cross layer's ``V`` and ``W``; 16,030,464 multiply-adds an example at
    the source's sizes), the backward pass twice that (the gradient of each
    product with respect to either operand).  The passes a float32 product
    takes on a bfloat16 MXU are not counted, nor the bias adds, ReLUs, the
    cross network's element-wise products and the loss: a lower bound."""
    macs = sum(
        shape[0] * shape[1] for shape in leaf_shapes(cfg).values()
        if len(shape) == 2
    )
    return 2.0 * macs * 3.0 * cfg["batch"]


def rule_path_bytes_per_step(cfg: dict) -> float:
    """What the SERVER side of a step (``ps.combine`` + ``ps.rule`` +
    ``ps.push``) must move, whatever implements it: the pushed gradients
    read once at their ``dim`` lanes a key, and every DISTINCT row the batch
    touches read once and written once at its whole ``2 dim`` lanes (the
    rule runs once a row).  No id, no sort: a lower bound, so its share of
    the roofline cannot pass 100 %, and a later kernel or layout is held to
    the same work."""
    el = np.dtype(cfg["dtype"]).itemsize
    dim = int(cfg["dim"])
    return el * (
        keys_per_step(cfg) * dim + 2 * 2 * dim * distinct_rows_per_step(cfg)
    )


def hbm_bytes_per_step(cfg: dict) -> float:
    """What the whole step MUST move: for the pull the ``dim`` lanes a key
    that a worker reads (it needs no accumulator), the server side's bytes
    (:func:`rule_path_bytes_per_step`) and the dense net's leaves and
    accumulators read once and written once by the worker's Adagrad (the
    products read the leaves from there too).  Activations are not counted:
    a lower bound."""
    el = np.dtype(cfg["dtype"]).itemsize
    return (
        el * keys_per_step(cfg) * int(cfg["dim"])
        + rule_path_bytes_per_step(cfg)
        + el * 2 * 2 * dense_params(cfg)
    )
