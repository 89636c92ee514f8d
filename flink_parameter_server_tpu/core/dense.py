"""DenseParameterServer — the PS API stretched to dense model pytrees.

Reference parity: BASELINE.json config #5 ("Transformer-base LM
data-parallel — dense allreduce — stretch the PS API").  The keyed
``pull(id)/push(id, delta)`` protocol degenerates, for a dense model, to
"pull everything / push one gradient": the server is the full parameter
pytree plus an optimizer, and a push folds the (dp-allreduced) gradient
through the optimizer update.  The allreduce is not written anywhere —
jit + dp-sharded batch shardings make XLA insert the psum over ICI, the
collective-native replacement for the reference's per-key Netty routing.
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, List, Optional

import jax

from .transform import TransformResult, jnp_copy

# `optax` is imported where an update is applied: the import is 0.7 s (chex,
# absl, toolz) that every sparse job would else pay for importing the
# package (a warm set-up is 14-18 s, bounded at 10 %: PERF.md section 6, PR 33)

Array = jax.Array
PyTree = Any


class DenseParameterServer:
    """Functional (params, opt_state, optimizer) bundle with pull/push.

    ``pull()`` → the model pytree; ``push(grads)`` → new server with the
    optimizer update applied.  Same contract shape as
    :class:`ShardedParamStore`, with the id space collapsed to "all".
    """

    def __init__(
        self,
        params: PyTree,
        optimizer: optax.GradientTransformation,
        opt_state: Optional[PyTree] = None,
    ):
        self.params = params
        self.optimizer = optimizer
        self.opt_state = (
            opt_state if opt_state is not None else optimizer.init(params)
        )

    def pull(self) -> PyTree:
        return self.params

    def push(self, grads: PyTree) -> "DenseParameterServer":
        updates, new_opt_state = self.optimizer.update(
            grads, self.opt_state, self.params
        )
        import optax

        new_params = optax.apply_updates(self.params, updates)
        return DenseParameterServer(new_params, self.optimizer, new_opt_state)

    def values(self) -> PyTree:
        """Close-time model dump (reference §3.5)."""
        return self.params


def _merged_dp_specs(tree: PyTree, mesh, dp_axis: str) -> PyTree:
    """Per-leaf shardings merging ``dp`` into each CONCRETE leaf's
    existing spec on the first unsharded dp-divisible axis (None =
    leave the leaf alone: scalars, already-dp-sharded, no eligible
    axis).  Composes with tp/sp model-parallel layouts rather than
    clobbering them."""
    if dp_axis not in mesh.axis_names:
        raise ValueError(
            f"dp_axis={dp_axis!r} not in mesh axes {mesh.axis_names}"
        )
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = mesh.shape[dp_axis]

    def spec_for(x):
        if getattr(x, "ndim", 0) < 1:
            return None
        cur: tuple = ()
        sharding = getattr(x, "sharding", None)
        spec = getattr(sharding, "spec", None)
        if spec is not None:
            cur = tuple(spec)
        cur = cur + (None,) * (x.ndim - len(cur))
        used = set()
        for e in cur:
            if isinstance(e, str):
                used.add(e)
            elif isinstance(e, (tuple, list)):
                used.update(e)
        if dp_axis in used:
            return None  # already dp-sharded somewhere
        for i in range(x.ndim):
            if cur[i] is None and x.shape[i] % dp == 0:
                merged = cur[:i] + (dp_axis,) + cur[i + 1:]
                return NamedSharding(mesh, P(*merged))
        return None

    return jax.tree.map(spec_for, tree)


def opt_state_zero1_specs(
    opt_state: PyTree, mesh, dp_axis: str = "dp"
) -> PyTree:
    """Per-leaf ZeRO-1 shardings derived from a CONCRETE opt_state.

    Call this on the freshly-initialized (placed) optimizer state:
    ``optax``'s init builds m/v with ``zeros_like(params)``, so each
    leaf already carries the PARAMS' sharding (tp/sp model-parallel
    layouts included); ``dp`` merges into the first free divisible axis
    (forcing ``P(dp, ...)`` on a tp-sharded leaf would *replicate* it
    across tp and invert the memory win)."""
    return _merged_dp_specs(opt_state, mesh, dp_axis)


def fsdp_place(params: PyTree, mesh, dp_axis: str = "dp") -> PyTree:
    """FSDP (ZeRO-3 analogue) placement: re-shard CONCRETE params over
    ``dp`` (merged into each leaf's existing tp/sp spec on a free
    axis).  Nothing else changes: under jit, XLA all_gathers a weight
    right where a matmul consumes it and reduce_scatters its gradient —
    the per-layer gather/release schedule FSDP implementations hand-roll
    is GSPMD's normal propagation here.  ``optimizer.init`` on the
    returned params inherits the sharded layout (zeros_like), so
    optimizer state is 1/dp too: params + grads + opt state all scale
    down with the mesh, at the cost of per-use weight all_gathers.
    """
    specs = _merged_dp_specs(params, mesh, dp_axis)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s) if s is not None else x,
        params, specs,
    )


def shard_opt_state_constraint(
    opt_state: PyTree, mesh, dp_axis: str = "dp", specs: PyTree = None
) -> PyTree:
    """Cross-replica weight-update sharding (ZeRO-1 done the XLA way).

    Constrain optimizer-state leaves to dp-sharded layouts.  Under jit,
    XLA propagates the constraint backward/forward: the gradient
    allreduce becomes reduce_scatter, each replica runs the optimizer
    math only for its 1/dp parameter slice, and the updates rejoin the
    params — same collective bytes as the plain allreduce, but Adam's
    m/v (8 bytes/param fp32) stop being replicated.  Counted in bytes
    (tests/test_zero1_memory.py, a small LM, dp=8): GSPMD propagates
    the constraint through ``apply_updates`` to the params OUTPUT too,
    so post-step params come back dp-sharded — steady-state memory
    matches :func:`fsdp_place` (0.125x replicated), with the weight
    all_gather paid at the next step's consumption sites instead of at
    update time.  This is the
    sharding-annotation form of automatic cross-replica weight-update
    sharding; nothing here hand-schedules a collective.

    ``specs``: pytree from :func:`opt_state_zero1_specs` (None entries =
    leave the leaf alone).  Without it, specs are derived from the
    leaves in place — inside jit those are tracers with no sharding, so
    the derivation sees every axis as free and shards the first
    dp-divisible one.  That is correct ONLY on a pure-dp mesh; a
    multi-axis mesh without explicit ``specs`` is rejected (silently
    re-sharding a tp-sharded leaf to dp-only would replicate it across
    tp — the exact memory win inverted).
    """
    if dp_axis not in mesh.axis_names:
        raise ValueError(
            f"dp_axis={dp_axis!r} not in mesh axes {mesh.axis_names}"
        )
    if specs is None:
        if len(mesh.axis_names) > 1:
            raise ValueError(
                f"mesh has axes {mesh.axis_names}: pass "
                f"specs=opt_state_zero1_specs(initial_opt_state, mesh) "
                f"so dp merges with the model-parallel layout instead "
                f"of overwriting it"
            )
        specs = _merged_dp_specs(opt_state, mesh, dp_axis)
    return jax.tree.map(
        lambda x, s: (
            jax.lax.with_sharding_constraint(x, s) if s is not None
            else x
        ),
        opt_state, specs,
    )


def make_dense_train_step(
    loss_fn: Callable[[PyTree, Any], Array],
    optimizer: optax.GradientTransformation,
    *,
    mesh=None,
    dp_axis: str = "dp",
    shard_opt_state: bool = False,
    opt_specs: PyTree = None,
) -> Callable:
    """Fused pull → grad → push step (jit this).  ``loss_fn(params,
    batch) -> scalar``; gradients are averaged across the dp axis by XLA
    from the shardings alone.

    ``shard_opt_state=True`` (requires ``mesh``): optimizer state is
    dp-sharded via :func:`shard_opt_state_constraint` — ZeRO-1 memory
    scaling for the dense PS path.  For tp/sp-sharded models also pass
    ``opt_specs=opt_state_zero1_specs(server.opt_state, mesh)`` so dp
    merges into a free axis of each leaf instead of overwriting the
    model-parallel layout."""
    if shard_opt_state:
        if mesh is None:
            raise ValueError("shard_opt_state=True requires mesh")
        if dp_axis not in mesh.axis_names:
            raise ValueError(
                f"dp_axis={dp_axis!r} not in mesh axes {mesh.axis_names}"
            )
        if opt_specs is None and len(mesh.axis_names) > 1:
            raise ValueError(
                f"mesh has axes {mesh.axis_names}: pass "
                f"opt_specs=opt_state_zero1_specs(server.opt_state, mesh) "
                f"so dp merges with the model-parallel layout instead of "
                f"overwriting it"
            )

    import optax

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        if shard_opt_state:
            opt_state = shard_opt_state_constraint(
                opt_state, mesh, dp_axis, specs=opt_specs
            )
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def transform_dense(
    data: Iterable,
    loss_fn: Callable[[PyTree, Any], Array],
    server: DenseParameterServer,
    *,
    batch_sharding=None,
    on_step: Optional[Callable[[int, Array], None]] = None,
    steps_per_call: int = 1,
) -> TransformResult:
    """The ``transform`` loop for the dense case: one jitted
    pull→grad→push per microbatch; returns losses as worker outputs and
    the final model as the server dump.

    ``steps_per_call=K`` scans K microbatches inside one jitted dispatch
    (same dispatch-amortization as ``transform_batched``; decisive when
    host↔device latency rivals the step time).  Per-step losses and
    ``on_step`` calls are preserved by unstacking; a trailing group
    shorter than K runs the single-step program.
    """
    if steps_per_call < 1:
        raise ValueError(f"steps_per_call={steps_per_call}: must be >= 1")
    from .transform import scan_group_sharding, stack_group

    base = make_dense_train_step(loss_fn, server.optimizer)
    step = jax.jit(base, donate_argnums=(0, 1))
    scan_step = None
    scan_sharding = None
    if steps_per_call > 1:
        scan_sharding = scan_group_sharding(batch_sharding)

        def _scan(params, opt_state, batches):
            def body(carry, b):
                p, o = carry
                p, o, loss = base(p, o, b)
                return (p, o), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), batches
            )
            return params, opt_state, losses

        scan_step = jax.jit(_scan, donate_argnums=(0, 1))

    # The jitted step donates its (params, opt_state) arguments; start from
    # copies so the caller's server survives (it is a read-only input).
    params = jax.tree.map(jnp_copy, server.params)
    opt_state = jax.tree.map(jnp_copy, server.opt_state)
    losses: List[Any] = []

    def _run_one(params, opt_state, batch):
        if batch_sharding is not None:
            batch = jax.tree.map(
                lambda x: jax.device_put(x, batch_sharding), batch
            )
        params, opt_state, loss = step(params, opt_state, batch)
        if on_step is not None:
            on_step(len(losses), loss)
        losses.append(loss)
        return params, opt_state

    def _run_group(params, opt_state, group):
        stacked = stack_group(group, scan_sharding)
        params, opt_state, group_losses = scan_step(
            params, opt_state, stacked
        )
        for i in range(len(group)):
            loss = group_losses[i]
            if on_step is not None:
                on_step(len(losses), loss)
            losses.append(loss)
        return params, opt_state

    group: List[Any] = []
    for batch in data:
        if steps_per_call == 1:
            params, opt_state = _run_one(params, opt_state, batch)
            continue
        group.append(batch)
        if len(group) == steps_per_call:
            params, opt_state = _run_group(params, opt_state, group)
            group = []
    for batch in group:  # tail shorter than K
        params, opt_state = _run_one(params, opt_state, batch)

    final = DenseParameterServer(params, server.optimizer, opt_state)
    return TransformResult(
        worker_outputs=losses,
        server_outputs=[final.values()],
        store=None,
        worker_state=None,
    )


__all__ = [
    "DenseParameterServer",
    "fsdp_place",
    "make_dense_train_step",
    "opt_state_zero1_specs",
    "shard_opt_state_constraint",
    "transform_dense",
]
