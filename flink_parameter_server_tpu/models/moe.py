"""Mixture-of-experts layer with expert parallelism over an ``ep`` axis.

The reference has no expert parallelism (SURVEY.md §2: "EP — NO"); like
pipeline parallelism this exists because distributed scale is first-class
in the rebuild: a sparse-expert FFN whose experts are sharded across the
``ep`` mesh axis, with token routing as ``all_to_all`` over ICI — the
canonical Switch-Transformer-style dispatch.

Semantics (top-1 switch routing with capacity):

  * gate: ``softmax(x @ w_gate)``; each token goes to its argmax expert,
    its output scaled by the gate probability,
  * each expert processes at most ``capacity`` tokens per device shard
    (first-come within the shard's token order); overflow tokens pass
    through the residual unchanged (standard switch behavior),
  * dispatch/return are two ``all_to_all``s over ``ep``: tokens bucketed
    per expert locally, regrouped so each device runs only its local
    experts' FFNs — one MXU batch per local expert.

The dense oracle (:func:`moe_reference`) replicates the identical
capacity/ordering semantics for parity tests.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    num_experts: int
    capacity: int  # max tokens PER EXPERT per device shard
    dtype: object = jnp.float32


def init_moe_params(rng: Array, cfg: MoEConfig, mesh: Optional[Mesh] = None,
                    ep_axis: str = "ep") -> Dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = cfg.d_model**-0.5
    scale_out = cfg.d_ff**-0.5
    params = {
        "w_gate": (
            scale_in * jax.random.normal(k1, (cfg.d_model, cfg.num_experts))
        ).astype(cfg.dtype),
        "w_up": (
            scale_in
            * jax.random.normal(k2, (cfg.num_experts, cfg.d_model, cfg.d_ff))
        ).astype(cfg.dtype),
        "w_down": (
            scale_out
            * jax.random.normal(k3, (cfg.num_experts, cfg.d_ff, cfg.d_model))
        ).astype(cfg.dtype),
    }
    if mesh is not None and ep_axis in mesh.axis_names:
        params["w_up"] = jax.device_put(
            params["w_up"], NamedSharding(mesh, P(ep_axis, None, None))
        )
        params["w_down"] = jax.device_put(
            params["w_down"], NamedSharding(mesh, P(ep_axis, None, None))
        )
    return params


def _route(x: Array, w_gate: Array, num_experts: int, capacity: int):
    """Top-1 routing with per-expert capacity, deterministic in token
    order.  Returns (expert_idx, slot, keep_mask, gate_prob) per token."""
    logits = x @ w_gate.astype(x.dtype)  # (N, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # (N,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    # slot of each token within its expert bucket = running count of
    # earlier tokens routed to the same expert
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)  # (N, E)
    slot = jnp.cumsum(onehot, axis=0) * onehot  # (N, E), 1-based
    slot = jnp.sum(slot, axis=-1) - 1  # (N,) 0-based
    keep = slot < capacity
    return expert, slot, keep, gate.astype(x.dtype)


def _expert_ffn(w_up_e: Array, w_down_e: Array, tokens: Array) -> Array:
    return jax.nn.gelu(tokens @ w_up_e) @ w_down_e


def moe_dense(params: Dict, x: Array, cfg: MoEConfig) -> Array:
    """Efficient single-device MoE (no collectives): bucket tokens per
    expert, one vmapped FFN batch per expert — 1× FLOPs (plus capacity
    padding), identical semantics to :func:`moe_apply` on one shard.
    This is the mesh-less path used by the transformer; the O(E·N)
    :func:`moe_reference` below stays as the independent test oracle."""
    E, C, d = cfg.num_experts, cfg.capacity, cfg.d_model
    expert, slot, keep, gate = _route(x, params["w_gate"], E, C)
    buckets = jnp.zeros((E, C, d), x.dtype)
    buckets = buckets.at[
        jnp.where(keep, expert, E - 1), jnp.clip(slot, 0, C - 1)
    ].add(jnp.where(keep[:, None], x, 0.0))
    y = jax.vmap(_expert_ffn)(params["w_up"], params["w_down"], buckets)
    out = y[jnp.where(keep, expert, E - 1), jnp.clip(slot, 0, C - 1)]
    return jnp.where(keep[:, None], out * gate[:, None], 0.0)


def moe_reference(params: Dict, x: Array, cfg: MoEConfig) -> Array:
    """Dense single-device oracle with identical routing semantics."""
    N = x.shape[0]
    expert, slot, keep, gate = _route(
        x, params["w_gate"], cfg.num_experts, cfg.capacity
    )
    out = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        sel = (expert == e) & keep
        y = _expert_ffn(params["w_up"][e], params["w_down"][e], x)
        out = out + jnp.where(sel[:, None], y, 0.0)
    return jnp.where(keep[:, None], out * gate[:, None], 0.0)


def moe_apply(
    params: Dict,
    x: Array,
    cfg: MoEConfig,
    *,
    mesh: Mesh,
    ep_axis: str = "ep",
    dp_axis: Optional[str] = "dp",
) -> Array:
    """Expert-parallel MoE FFN: ``x`` (N, d) with N sharded over ``dp``
    (if present), experts sharded over ``ep``.  Returns the gated expert
    outputs (0 for dropped tokens) — add to the residual stream.
    """
    E = cfg.num_experts
    ep = mesh.shape[ep_axis]
    assert E % ep == 0, (E, ep)
    e_local = E // ep
    C = cfg.capacity
    d = cfg.d_model

    if dp_axis is not None and dp_axis not in mesh.axis_names:
        dp_axis = None
    lead = (dp_axis,) if dp_axis else (None,)
    x_spec = P(*lead, None)

    def body(w_gate, w_up, w_down, x_loc):
        n_loc = x_loc.shape[0]
        expert, slot, keep, gate = _route(x_loc, w_gate, E, C)

        # bucket local tokens: (E, C, d); dropped tokens go nowhere
        buckets = jnp.zeros((E, C, d), x_loc.dtype)
        tok_idx = jnp.arange(n_loc)
        buckets = buckets.at[
            jnp.where(keep, expert, E - 1),
            jnp.clip(slot, 0, C - 1),
        ].add(jnp.where(keep[:, None], x_loc, 0.0))

        # dispatch: regroup expert buckets onto their owning ep shard:
        # (E, C, d) = (ep, e_local, C, d) -- all_to_all splits the ep dim
        # here and concatenates the arriving shards' buckets
        dispatched = jax.lax.all_to_all(
            buckets.reshape(ep, e_local, C, d),
            ep_axis,
            split_axis=0,
            concat_axis=0,
        )  # (ep, e_local, C, d): sender s's buckets for my experts
        # run my local experts on every sender's bucket
        y = jax.vmap(
            lambda wu, wd, toks: _expert_ffn(wu, wd, toks),
            in_axes=(0, 0, 1),
            out_axes=1,
        )(w_up, w_down, dispatched)  # (ep, e_local, C, d)

        # return trip: send each sender its processed buckets back
        returned = jax.lax.all_to_all(
            y, ep_axis, split_axis=0, concat_axis=0
        ).reshape(E, C, d)

        # un-bucket: token t reads (expert[t], slot[t])
        out = returned[
            jnp.where(keep, expert, E - 1), jnp.clip(slot, 0, C - 1)
        ]
        return jnp.where(keep[:, None], out * gate[:, None], 0.0)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, None),  # gate replicated
            P(ep_axis, None, None),
            P(ep_axis, None, None),
            x_spec,
        ),
        out_specs=x_spec,
        check_vma=False,
    )(params["w_gate"], params["w_up"], params["w_down"], x)


__all__ = [
    "MoEConfig",
    "init_moe_params",
    "moe_apply",
    "moe_dense",
    "moe_reference",
]
