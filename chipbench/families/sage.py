"""GraphSAGE on a share of ogbn-papers100M: THREE stores a step only reads
(the adjacency lists' ends and neighbour ids, int32 scalar rows; the nodes'
128 float32 features) and a step that pulls seven ROUNDS from them, each
round's keys computed from the rows of the one before; the three-layer net
and Adam in the worker's state: ``models/graphsage.GraphSage`` +
``make_stores`` with that function's default layouts (no arm is chosen
here), through ``StreamingDriver`` as every family."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import datagen
from chipbench.references import sage as ref

STEP_PROGRAM = "jit_step"
# rows a single eager pull of the walk asks for (a block of the deepest hop)
_PULL_BLOCK = 1 << 18
# `rows` is handed the store, the state and the checked batches, not the
# configuration: the fan-outs of the stores `build` last made, for its walk
_BUILT: Dict[str, list] = {}


def _model(cfg: dict):
    from flink_parameter_server_tpu.models import graphsage as gs

    return gs.SageConfig(
        num_nodes=int(cfg["num_nodes"]), num_edges=int(cfg["num_edges"]),
        widths=tuple(int(w) for w in cfg["widths"]),
        fanouts=tuple(int(k) for k in cfg["fanouts"]),
        dropout=float(cfg["dropout"]),
        learning_rate=float(cfg["learning_rate"]), beta1=float(cfg["beta1"]),
        beta2=float(cfg["beta2"]), eps=float(cfg["eps"]),
        degree_exponent=float(cfg["degree_law"]["exponent"]),
        degree_cap=int(cfg["degree_law"]["cap"]),
    )


def build(cfg: dict, seed: int, mesh):
    """The three stores are ``make_stores``' own, built on the device in ONE
    jitted call that takes the seed as an ARGUMENT (a seed baked into the
    program would compile the build again for every ``--seed``:
    ``families/fm.py``), the features and the neighbour ids initialised IN
    PLACE (``ShardedParamStore.create``: 7.1 GB and 1.6 GB, no second copy).
    The net is the logic's own ``init_state`` of the same seed."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import graphsage as gs

    model = _model(cfg)
    _BUILT["fanouts"] = list(model.fanouts)
    seed = np.uint32(seed % 2**32)
    stores = jax.jit(lambda s: gs.make_stores(
        model, seed=s, mesh=mesh, dtype=jnp.dtype(cfg["dtype"])))(seed)
    return gs.GraphSage(model, seed=seed), stores


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    """A record is a seed node and its label: the seeds drawn by the traffic
    file's law over the share's ``num_train_nodes`` training nodes (its first
    ids), the label a hash of the node over the classes (a node keeps its
    label), every lane live."""
    train, classes = int(cfg["num_train_nodes"]), int(cfg["widths"][-1])
    batch = int(cfg["batch"])

    def one(i):
        rng = np.random.default_rng([seed, i])
        nodes = datagen.draw_keys(rng, traffic["keys"], batch, train)
        with np.errstate(over="ignore"):
            h = (nodes.astype(np.uint32) + np.uint32(seed % 2**32)) * np.uint32(
                0x9E3779B1)
            h ^= h >> np.uint32(15)
            h *= np.uint32(0x85EBCA6B)
            h ^= h >> np.uint32(13)
        return {
            "seed": nodes.astype(np.int32),
            "label": (h % np.uint32(classes)).astype(np.int32),
            "mask": np.ones(batch, bool),
        }

    return datagen._batches(one, n)


def _puller(store):
    """``pull(name, ids) -> rows`` through the stores' own pull, a block of
    ids at a time, every block one shape (a program a shape)."""
    import jax.numpy as jnp

    def pull(name: str, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int32).reshape(-1)
        out = []
        for at in range(0, ids.size, _PULL_BLOCK):
            part = ids[at:at + _PULL_BLOCK]
            padded = np.pad(part, (0, -part.size % min(_PULL_BLOCK, 4096)))
            out.append(np.asarray(
                store[name].pull(jnp.asarray(padded)))[:part.size])
        return np.concatenate(out)

    return pull


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """ONE group, 128 lanes wide (``chipbench/references/sage.py`` says why
    one): every leaf, Adam's moments, its step count and running powers from
    the worker's state, flat, then a SAMPLE of the read-only rows the checked
    batches touch, read through the stores' own pull (the first seeds' row
    ends, first neighbour and features, every word as two 16-bit halves:
    they must not move, not by a bit).  Beside the
    group, for the reference alone and only while the state is fresh: the
    sampler's ``key`` and the trees of the checked batches, walked by the
    REFERENCE's own arithmetic (``references/sage.walk``) over rows read
    through the stores' pull: ``features`` ``(batches, lanes, 128)`` and
    ``live`` ``(batches, lanes)``."""
    pull = _puller(store)
    names = sorted(k for k in state if k[0] in "wb" and k[1].isdigit())
    flat = [np.asarray(state[p + k], np.float32).reshape(-1)
            for p in ("", "m_", "v_") for k in names]
    flat.append(np.asarray([
        float(state["t"]), float(state["beta1_t"]), float(state["beta2_t"])],
        np.float32))
    first = ids["seed"][:, :ref.SAMPLE_SEEDS].reshape(-1).astype(np.int64)
    begin, end = pull("off", first), pull("off", first + 1)
    flat += [
        ref.halves(begin), ref.halves(end),
        ref.halves(pull("nbr", np.where(end > begin, begin, 0))),
        # (a feature by its WORD, so that one rounding of it shows)
        ref.halves(np.asarray(pull("feat", first), np.float32).view(np.uint32)),
    ]
    out = {"parameters": ref.laid(np.concatenate(flat)),
           "key": np.asarray(state["key"], np.uint32)}
    if int(state["t"]) == 0:
        trees = [
            ref.walk(_BUILT, out["key"], n, ids["seed"][n], ids["mask"][n],
                     pull)
            for n in range(ids["seed"].shape[0])]
        out["live"] = np.stack(
            [np.concatenate([a.reshape(-1) for a in t["live"]]) for t in trees])
        out["features"] = np.stack([
            np.asarray(pull("feat", np.concatenate(t["nodes"])), np.float32)
            for t in trees])
    return out


def keys_per_step(cfg: dict) -> Dict[str, int]:
    """Lanes a step pulls, store by store: two row ends a destination node
    of the three hops, a neighbour id a sampled lane, a feature row a node
    met (112,000 / 805,000 / 806,000 at 1,000 seeds and 15 / 10 / 5)."""
    lanes = ref.lanes_at(cfg, int(cfg["batch"]))
    return {"off": 2 * sum(lanes[:-1]), "nbr": sum(lanes[1:]),
            "feat": sum(lanes)}


def feature_pull_bytes_per_step(cfg: dict) -> float:
    """What the features' pull must move whatever implements it: every
    pulled row read once and written once at its 128 float32 lanes (no key,
    nothing of the gather's own): a lower bound."""
    lanes = keys_per_step(cfg)["feat"]
    return 2.0 * lanes * int(cfg["widths"][0]) * np.dtype(cfg["dtype"]).itemsize


def hbm_bytes_per_step(cfg: dict) -> float:
    """What the three STORES must move a step, whatever implements them: a
    key read and the 4 bytes of an id fetched for every lane of the graph's
    pulls, a key and a feature row read once and written once for every node
    met.  No physical row's other 127 ids, no random word, nothing of the
    net: a lower bound, so a share of the roofline made of it cannot pass
    100 %."""
    lanes = keys_per_step(cfg)
    return (8.0 * (lanes["off"] + lanes["nbr"]) + 4.0 * lanes["feat"]
            + feature_pull_bytes_per_step(cfg))


def dense_flops_per_step(cfg: dict) -> float:
    """MODEL floating-point operations of the net a step: 2 a multiply-add
    of the forward pass (a self and a neighbour product a layer over its
    destination lanes: 4.55 G multiply-adds at the source's sizes), the
    backward pass twice that less the first layer's input gradient, which
    nobody needs (the features are no parameter).  The passes a float32
    product takes on a bfloat16 MXU, the means, biases, ReLUs, dropout, the
    loss and Adam are not counted: a lower bound."""
    widths = [int(w) for w in cfg["widths"]]
    lanes = ref.lanes_at(cfg, int(cfg["batch"]))
    layers = len(cfg["fanouts"])
    macs = [2 * n * m * sum(lanes[:layers - i])
            for i, (n, m) in enumerate(zip(widths, widths[1:]))]
    return 2.0 * (3.0 * sum(macs) - macs[0])
