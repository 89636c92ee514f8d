"""Pipeline parallelism — GPipe-style stage pipelining over a ``pp`` axis.

The reference has no pipeline parallelism (SURVEY.md §2: "PP — NO"); this
module exists because distributed scale is a first-class requirement of
the rebuild: models whose layer stack exceeds one chip's HBM shard layers
across a ``pp`` mesh axis, and microbatches stream through the stages over
the ICI ring.

Design (SPMD schedule inside one ``shard_map``):

  * stage ``s`` holds its block of layers (params stacked per stage,
    sharded ``P('pp', ...)``),
  * time ticks ``t = 0 .. S+M-2`` (S stages, M microbatches): at tick t,
    stage s computes microbatch ``t-s`` if it is in [0, M), then
    ``ppermute``s its activation to stage ``s+1``,
  * stage 0 injects microbatch t at tick t; the last stage accumulates
    outputs; a final masked ``psum`` over ``pp`` replicates them.

Every stage computes at every tick (idle ticks are masked, not skipped) —
the classic bubble cost ``(S-1)/(S+M-1)``; raise M to amortise.  The
schedule is fully differentiable (``ppermute`` transposes to the reverse
permutation), so ``jax.grad`` through a pipelined forward just works.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_apply(
    stage_params: Any,
    x: Array,
    block_fn: Callable[[Any, Array], Array],
    *,
    mesh: Mesh,
    pp_axis: str = "pp",
    dp_axis: str | None = "dp",
    num_microbatches: int,
    x_tail_spec: tuple | None = None,
) -> Array:
    """Run ``x`` through ``S = mesh.shape[pp_axis]`` pipelined stages.

    ``stage_params``: pytree whose leaves lead with the stage axis
    (shape ``(S, ...)``), sharded ``P(pp_axis, ...)``.
    ``x``: (B, ...), batch dim sharded over ``dp_axis`` (if the mesh has
    it) and replicated over ``pp`` — each dp row pipelines only its own
    batch shard.  ``block_fn(stage_local_params, x_mb) -> y_mb``: one
    stage's compute on one microbatch (same shape in/out).
    ``num_microbatches``: must divide the per-dp-shard batch.
    Returns (B, ...) sharded like ``x``.

    The tick schedule runs under ``lax.scan`` so ``block_fn`` is traced
    exactly once regardless of M (raise M freely to shrink the
    (S-1)/(S+M-1) bubble without blowing up compile time) and reverse
    -mode autodiff composes; the uniform loop body issues one (wasted)
    final-tick ppermute in exchange.
    """
    S = mesh.shape[pp_axis]
    M = num_microbatches
    if dp_axis is not None and dp_axis not in mesh.axis_names:
        dp_axis = None
    dp = mesh.shape[dp_axis] if dp_axis else 1
    B = x.shape[0]
    assert B % (M * dp) == 0, (B, M, dp)

    param_specs = jax.tree.map(lambda _: P(pp_axis), stage_params)
    # x_tail_spec shards the non-batch dims (e.g. (sp_axis, None) to keep
    # the sequence dim sp-sharded through the pipeline for ring attention)
    if x_tail_spec is None:
        x_tail_spec = (None,) * (x.ndim - 1)
    assert len(x_tail_spec) == x.ndim - 1, (x_tail_spec, x.ndim)
    x_spec = P(*((dp_axis,) + tuple(x_tail_spec)))

    def body(local_params, x_full):
        # local_params leaves: (1, ...) — this stage's block
        local_params = jax.tree.map(lambda l: l[0], local_params)
        s = jax.lax.axis_index(pp_axis)
        mb = x_full.shape[0] // M
        inputs = x_full.reshape((M, mb) + x_full.shape[1:])
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(t, state):
            carry, outputs = state
            # stage 0 injects microbatch t (clamped index is masked off
            # for t >= M by `active` below)
            inj = jax.lax.dynamic_index_in_dim(
                inputs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            x_in = jnp.where(s == 0, inj, carry)
            idx = t - s
            active = jnp.logical_and(idx >= 0, idx < M)
            y = block_fn(local_params, x_in)
            y = jnp.where(active, y, x_in)
            write = jnp.logical_and(active, s == S - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(
                    write,
                    y,
                    jax.lax.dynamic_index_in_dim(
                        outputs, jnp.clip(idx, 0, M - 1), 0, keepdims=False
                    ),
                ),
                jnp.clip(idx, 0, M - 1),
                axis=0,
            )
            carry = jax.lax.ppermute(y, pp_axis, perm)
            return carry, outputs

        carry = jnp.zeros_like(inputs[0])
        outputs = jnp.zeros_like(inputs)
        (carry, outputs), _ = jax.lax.scan(
            lambda state, t: (tick(t, state), None),
            (carry, outputs),
            jnp.arange(S + M - 1),
        )

        # outputs live on the last stage only; replicate via psum
        outputs = jax.lax.psum(outputs, pp_axis)
        return outputs.reshape(x_full.shape)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x)


def stack_stage_params(
    layer_params_list,
    num_stages: int,
    *,
    mesh: Mesh | None = None,
    pp_axis: str = "pp",
):
    """Group a list of per-layer param pytrees into ``num_stages`` stacked
    stage pytrees: leaves gain leading dims (num_stages, layers_per_stage).

    With ``mesh``, each *concrete* (eager/init-time) leaf is built
    shard-by-shard via ``jax.make_array_from_callback`` onto
    ``P(pp_axis, ...)`` — a device never materialises more than its own
    stage's layers, so the layer stack can exceed one chip's memory.
    Under a jit trace the host path can't run; leaves are stacked and
    sharding-constrained instead, and GSPMD decides the transient — for
    stacks that can't fit replicated, stack eagerly before jit.

    ``block_fn`` then scans its stage's (layers_per_stage, ...) leaves.
    """
    n = len(layer_params_list)
    assert n % num_stages == 0, (n, num_stages)
    per = n // num_stages

    def stack(*leaves):
        stacked = jnp.stack(leaves)  # (n, ...)
        return stacked.reshape((num_stages, per) + stacked.shape[1:])

    if mesh is None:
        return jax.tree.map(stack, *layer_params_list)

    traced = any(
        isinstance(l, jax.core.Tracer)
        for l in jax.tree.leaves(layer_params_list)
    )
    if traced:
        # under jit the host shard-by-shard path can't run; stack and let
        # GSPMD place the result via a sharding constraint
        def stack_constrained(*leaves):
            out = stack(*leaves)
            return jax.lax.with_sharding_constraint(
                out,
                jax.NamedSharding(mesh, P(pp_axis, *([None] * (out.ndim - 1)))),
            )

        return jax.tree.map(stack_constrained, *layer_params_list)

    import numpy as np

    def stack_sharded(*leaves):
        # host views of the per-layer leaves; each device's callback
        # assembles only the rows (stages) its shard owns
        host = [np.asarray(l) for l in leaves]
        shape = (num_stages, per) + host[0].shape
        sharding = jax.NamedSharding(
            mesh, P(pp_axis, *([None] * (len(shape) - 1)))
        )
        blocks = {}  # memoize per index: replica devices (dp) share blocks

        def cb(index):
            key = tuple(
                (sl.start, sl.stop, sl.step) if isinstance(sl, slice) else sl
                for sl in index
            )
            if key not in blocks:
                lo, hi, _ = index[0].indices(num_stages)
                block = np.stack(
                    [host[s * per + j]
                     for s in range(lo, hi) for j in range(per)]
                ).reshape((hi - lo, per) + host[0].shape)
                blocks[key] = block[(slice(None),) + tuple(index[1:])]
            return blocks[key]

        return jax.make_array_from_callback(shape, sharding, cb)

    return jax.tree.map(stack_sharded, *layer_params_list)


__all__ = ["pipeline_apply", "stack_stage_params"]
