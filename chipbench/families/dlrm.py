"""DLRM: the embedding rows of every categorical field in ONE add-store, the
two MLPs in the worker's state: ``models/dlrm.DLRM`` + ``make_store`` with
that function's default layout (no arm is chosen for speed here)."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import datagen

STEP_PROGRAM = "jit_step"


def layer_shapes(cfg: dict) -> Dict[str, tuple]:
    """``{layer: (inputs, outputs)}`` from the configuration's widths: the
    bottom MLP on the dense fields, the top on ``[z0, the pairs below the
    diagonal of T T^t]`` (the benchmark's own copy)."""
    vectors = len(cfg["field_cardinalities"]) + 1
    top_in = int(cfg["dim"]) + vectors * (vectors - 1) // 2
    out = {}
    for name, first, widths in (
        ("bot", int(cfg["dense_fields"]), cfg["bottom_mlp"]),
        ("top", top_in, cfg["top_mlp"]),
    ):
        for i, (n, m) in enumerate(zip([first] + list(widths), widths)):
            out[f"{name}{i}"] = (int(n), int(m))
    return out


def build(cfg: dict, seed: int, mesh):
    """The store is ``make_store``'s own, built on the device in one jitted
    call that takes the seed as an ARGUMENT (a seed baked into the program
    would compile the init again for every ``--seed``: ``families/fm.py``)
    and initialised IN PLACE (``ShardedParamStore.create``): at 12.58 GB no
    second copy of the table fits beside it.  The MLPs are the logic's own
    ``init_state`` of the same seed."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import dlrm

    model = dlrm.DLRMConfig(
        tuple(int(c) for c in cfg["field_cardinalities"]),
        dense_features=int(cfg["dense_fields"]), dim=int(cfg["dim"]),
        bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
        learning_rate=float(cfg["learning_rate"]),
    )
    seed = np.uint32(seed % 2**32)
    store = jax.jit(lambda s: dlrm.make_store(
        model, seed=s, mesh=mesh, dtype=jnp.dtype(cfg["dtype"]),
    ))(seed)
    return dlrm.DLRM(model, seed=seed), store


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    """Cell 2's record from cell 2's generator, draw for draw: the 26
    categorical ids (less the 13 integer-field rows that the FM table holds
    and this one does not), the 13 dense values as ``x``, the label as 0/1."""
    dense = int(cfg["dense_fields"])
    return [
        {
            "dense": b["values"][:, :dense],
            "ids": b["ids"][:, dense:] - np.int32(dense),
            "label": (b["label"] > 0).astype(np.float32),
            "mask": b["mask"],
        }
        for b in datagen.click_batches(
            cfg["field_cardinalities"], dense, cfg["batch"], n,
            feature_keys=traffic["keys"], seed=seed,
        )
    ]


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """ONE group of rows ``dim`` lanes wide: the touched embedding rows
    through the store's own pull, then the MLPs WHOLE from the worker state,
    flat, ``dim`` lanes to a row (zeros fill the last): layer after layer, a
    layer's weights row by row, then its bias."""
    import jax.numpy as jnp

    pulled = np.asarray(store.pull(jnp.asarray(ids["embedding"])), np.float32)
    flat = np.concatenate([
        np.asarray(state[f"{name}_{leaf}"], np.float32).reshape(-1)
        for name in sorted(k[:-2] for k in state if k.endswith("_w"))
        for leaf in "wb"
    ])
    flat = np.pad(flat, (0, -flat.size % pulled.shape[1]))
    return {"parameters": np.concatenate(
        [pulled, flat.reshape(-1, pulled.shape[1])]
    )}


def dense_flops_per_step(cfg: dict) -> float:
    """MODEL floating-point operations of the dense net a step: 2 a
    multiply-add of the forward pass (every layer, and the whole ``T T^t``:
    806,720 multiply-adds an example at the source's sizes), the backward
    pass twice that (the gradient of each product with respect to either
    operand).  The passes a float32 product takes on a bfloat16 MXU are not
    counted, nor the bias adds, ReLUs and the loss: a lower bound."""
    vectors = len(cfg["field_cardinalities"]) + 1
    macs = sum(n * m for n, m in layer_shapes(cfg).values()) + (
        vectors * vectors * int(cfg["dim"])
    )
    return 2.0 * macs * 3.0 * cfg["batch"]


def hbm_bytes_per_step(cfg: dict) -> float:
    """One ``dim``-wide row read for the gather, one read and one write for
    the scatter-add, for each of the batch's ``batch * fields`` embedding
    rows.  The other half of the 128-lane physical row is waste, not need,
    and the dense net's 3 MB of weights are read from VMEM-sized arrays."""
    el = np.dtype(cfg["dtype"]).itemsize
    fields = len(cfg["field_cardinalities"])
    return 3.0 * cfg["batch"] * fields * int(cfg["dim"]) * el
