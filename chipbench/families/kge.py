"""Knowledge-graph embeddings as PyTorch-BigGraph trains one bucket of them:
``models/kge.ComplExNegatives`` + ``make_store`` with that function's default
layout (a 101-lane rule row, the embedding and row-wise AdaGrad's one
accumulator, in one register), and the record stream: chunks of edges ``(s,
r, o)`` with the chunk's uniform negatives of each side, every id drawn from
the bucket's two partitions."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import datagen

STEP_PROGRAM = "jit_step"


def partition_rows(cfg: dict) -> int:
    """Rows of ONE of the source's partitions: its entities split evenly,
    the last partition short (``ceil``)."""
    sizes = cfg["source_sizes"]
    return -(-int(sizes["num_entities"]) // int(cfg["num_partitions"]))


def bucket_rows(cfg: dict) -> int:
    """Rows of an off-diagonal bucket: the sources' partition and the
    destinations'."""
    return 2 * partition_rows(cfg)


def edges_per_bucket(cfg: dict) -> float:
    """The edges one bucket holds on average: the source's edges over its
    ``num_partitions`` squared buckets.  PBG keeps a bucket's edges in memory
    while it trains the bucket, and one pass over them is what it does
    between two swaps: the pool is that many edges (``pool_batches``)."""
    return int(cfg["source_sizes"]["num_edges"]) / int(cfg["num_partitions"]) ** 2


def chunks_per_step(cfg: dict) -> int:
    return int(cfg["batch"]) // int(cfg["chunk"])


def keys_per_step(cfg: dict) -> int:
    """A chunk's sources, destinations and uniform ids of each side."""
    return chunks_per_step(cfg) * 2 * (
        int(cfg["chunk"]) + int(cfg["uniform_negatives"]))


def build(cfg: dict, seed: int, mesh):
    """The store is ``make_store``'s own, built on the device in one jitted
    call that takes the seed as an ARGUMENT (a seed baked into the program
    would compile the init again for every ``--seed``: ``families/fm.py``)
    and initialised IN PLACE (``core/store.create_table``): at 7.76 GB no
    second copy of the table fits beside it and the step's temporaries."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import kge

    model = kge.KGEConfig(
        int(cfg["num_entities"]), int(cfg["num_relations"]), int(cfg["dim"]),
        lr_rel=float(cfg["lr_rel"]), eps=float(cfg["eps"]),
    )
    rule = kge.RowAdaGrad(float(cfg["lr"]), float(cfg["eps"]))
    store = jax.jit(lambda s: kge.make_store(
        model, rule, seed=s, mesh=mesh, dtype=jnp.dtype(cfg["dtype"])
    ))(np.uint32(seed % 2**32))
    return kge.ComplExNegatives(model), store


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    """``n`` full batches: ``batch / chunk`` chunks of ``chunk`` edges, a
    source from the bucket's first half of the rows, a destination from its
    second, both by the traffic's key law, a relation uniform over all of
    them; and ``uniform_negatives`` ids a chunk from each half, uniform
    whatever the law (PBG's ``num_uniform_negs``).  Batch ``i`` draws from
    its own generator, so the stream is a function of the seed alone."""
    half = int(cfg["num_entities"]) // 2
    shape = (chunks_per_step(cfg), int(cfg["chunk"]))
    negatives = (shape[0], int(cfg["uniform_negatives"]))
    uniform = {"kind": "uniform"}
    out = []
    for i in range(n):
        rng = np.random.default_rng([seed, i + 1])
        out.append({
            "source": datagen.draw_keys(rng, traffic["keys"], shape, half),
            "destination": np.int32(half) + datagen.draw_keys(
                rng, traffic["keys"], shape, half),
            "relation": datagen.draw_keys(
                rng, uniform, shape, int(cfg["num_relations"])),
            "source_negatives": datagen.draw_keys(
                rng, uniform, negatives, half),
            "destination_negatives": np.int32(half) + datagen.draw_keys(
                rng, uniform, negatives, half),
        })
    return out


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Two groups as float32 numpy: the touched entity rows WHOLE (the
    embedding and its accumulator) through the store's own pull, and every
    relation's ``(a, b, S_a, S_b)`` flat from the worker's state."""
    import jax.numpy as jnp

    relations = state["operators"].shape[0]
    return {
        "entity": np.asarray(
            store.pull(jnp.asarray(ids["entity"])), np.float32),
        "operator": np.concatenate([
            np.asarray(state[leaf], np.float32).reshape(relations, -1)
            for leaf in ("operators", "operator_acc")
        ], axis=1),
    }


def distinct_rows_per_step(cfg: dict) -> float:
    """Expected distinct rows a batch touches under uniform keys, in closed
    form: a side's keys fall on its partition's ``m`` rows, ``m (1 - (1 -
    1/m)^keys)`` of them distinct, and the two sides share no row."""
    m, keys = float(int(cfg["num_entities"]) // 2), keys_per_step(cfg) / 2
    return 2.0 * m * -np.expm1(keys * np.log1p(-1.0 / m))


def score_flops_per_step(cfg: dict) -> float:
    """MODEL floating-point operations of the scores and their gradients a
    step: a chunk's ``(chunk, dim) x (dim, chunk + uniform)`` product a side,
    2 a multiply-add, and the two transposed products of its backward pass
    (the gradient with respect to either operand).  The passes a float32
    product takes on a bfloat16 MXU are not counted, nor the operators'
    element-wise products and the softmax: a lower bound."""
    n, m = int(cfg["chunk"]), int(cfg["chunk"]) + int(cfg["uniform_negatives"])
    return 2.0 * n * m * int(cfg["dim"]) * 3 * 2 * chunks_per_step(cfg)


def rule_path_bytes_per_step(cfg: dict) -> float:
    """What the SERVER side of a step (``ps.combine`` + ``ps.rule`` +
    ``ps.push``) must move, whatever implements it: the pushed gradients
    read once at their ``dim`` lanes a key, and every DISTINCT row the batch
    touches read once and written once at its whole ``dim + 1`` lanes (the
    rule runs once a row).  No id, no sort, no padding to a register: a
    lower bound, so its share of the roofline cannot pass 100 %, and a later
    kernel or layout is held to the same work."""
    el = np.dtype(cfg["dtype"]).itemsize
    dim = int(cfg["dim"])
    return el * (
        keys_per_step(cfg) * dim + 2 * (dim + 1) * distinct_rows_per_step(cfg)
    )


def hbm_bytes_per_step(cfg: dict) -> float:
    """What the whole step MUST move: for the pull the ``dim`` lanes a key
    that a worker reads (it needs no accumulator), and the server side's
    bytes (:func:`rule_path_bytes_per_step`: ``dim`` lanes pushed a key,
    ``dim + 1`` read and written a distinct row).  The pad to 128 lanes and
    the 25,291 operators (20 MB, read whole by the worker's AdaGrad) are
    not counted: a lower bound."""
    el = np.dtype(cfg["dtype"]).itemsize
    return (
        el * keys_per_step(cfg) * int(cfg["dim"])
        + rule_path_bytes_per_step(cfg)
    )
