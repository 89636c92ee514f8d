"""GraphSAGE by sampled mini-batches: a step whose KEYS come from what it
pulled.  Three hops of sampled neighbours out of a read-only graph store of
scalar rows, then the features of every node met out of a read-only features
store, a three-layer mean-aggregator net and Adam in the worker (Hamilton et
al., "Inductive Representation Learning on Large Graphs", NeurIPS 2017,
Algorithm 2 and its fixed-size draws; trained as DistDGL trains it, Zheng et
al., IA3 at SC 2020: the ``train_dist`` script of DGL's distributed GraphSAGE example,
``SAGEConv(aggregator_type="mean")``, the graph and the node features
partitioned over the machines' ``KVServer``s, samplers beside them).

Reference parity: the reference's worker is handed the client in BOTH hooks
(``onPullRecv(paramId, paramValue, ps)``, SURVEY.md §2 #2), so a Scala worker
pulls again from a pull's answer.  Here that is
``BatchedWorkerLogic.next_keys``: seven ROUNDS of pulls in one jitted step,
over three stores that the step only READS (``core/store.StoreGroup``; no
request names them, their tables leave the step as they came in):

- ``off``: ``num_nodes + 1`` int32 scalar rows, node ``v``'s neighbour ids
  are rows ``off[v] .. off[v + 1] - 1`` of
- ``nbr``: ``num_edges`` int32 scalar rows, a neighbour's node id each (the
  share's own edges, both directions);
- ``feat``: ``num_nodes`` rows of ``features`` float32 lanes.

One step, for seeds ``s`` (depth 0) and the fan-out ``k_d`` of depth ``d``
(the fan-out list read from its END, the seeds drawing the last entry, as
DGL's ``NeighborSampler`` walks from the output layer back):

    round 0      off[(v, v + 1)] of the seeds          -> first, degree
    round 1      nbr[first + r mod degree], k_0 draws a seed   -> depth 1
    rounds 2-5   the same for depths 1 and 2           -> depths 2 and 3
    round 6      feat[every node of depths 0..3]       (806 a seed at 5/10/15)

A node draws ONCE, ``k_d`` independent uniform draws WITH replacement from
its adjacency row, and every layer that needs its neighbours reads that one
draw (the original GraphSAGE's fixed-size tree; nothing is de-duplicated).
``r`` is ``jax.random.bits`` of ``fold_in(fold_in(key, t), d)``, ``key`` and
the step count ``t`` in the WORKER's state, so ``save`` / ``resume`` carries
the stream of draws (threefry: the same bits on the CPU and on the chip).  A
node of degree 0 has DEAD lanes below it: they pull row 0 (key -1, the
store's convention), count in no mean and reach no gradient.  The sampled
block of depth ``d + 1`` is ``(k_d, n_d)``, child ``j`` of parent ``i`` at
``[j, i]``: a parent's mean is a sum of ``k_d`` whole slabs.

The net, layer ``l`` = 1..3 over the depths ``0 .. 3 - l``:

    h^l_v = h^{l-1}_v W^l_self + mean_{live children u} h^{l-1}_u W^l_neigh + b^l

``h^0`` the features, a node with no live child taking a zero mean; ReLU then
dropout (the kept lanes doubled at 0.5; a mask word's top bit, from the same
key folded with ``2 + l``) after layers 1 and 2; the loss the mean over the
live seeds of softmax cross-entropy against the seed's label; Adam
(bias-corrected, the running powers of the betas carried as float32 products)
on every leaf, moments and step count in the worker's state.  Float32, every
product at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batched import BatchedWorkerLogic
from ..core.store import ShardedParamStore, StoreGroup
from ..ops.hashing import _GOLDEN, _fmix32
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor

Array = jax.Array
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class SageConfig:
    """``num_nodes`` nodes and ``num_edges`` neighbour ids (the rows of
    ``nbr``); ``widths`` the net's (features, hidden.., classes); ``fanouts``
    in DGL's order, the INPUT layer's first, so depth ``d`` draws
    ``fanouts[-1 - d]``; ``dropout`` after every layer but the last; Adam's
    ``learning_rate``, ``beta1``, ``beta2``, ``eps``.  The synthetic graph's
    degree law is ``P(d) ~ d ^ -degree_exponent`` on ``1 .. degree_cap``
    (:func:`make_stores`; the defaults' mean is ogbn-papers100M's 29.1)."""

    num_nodes: int
    num_edges: int
    widths: Tuple[int, ...] = (128, 256, 256, 172)
    fanouts: Tuple[int, ...] = (15, 10, 5)
    dropout: float = 0.5
    learning_rate: float = 0.003
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    degree_exponent: float = 1.4515581061330063
    degree_cap: int = 1000

    def __post_init__(self) -> None:
        if len(self.fanouts) != len(self.widths) - 1:
            raise ValueError(
                f"a fan-out a layer: {len(self.fanouts)} fan-outs for "
                f"{len(self.widths) - 1} layers")

    @property
    def layers(self) -> int:
        return len(self.fanouts)

    def draws(self, depth: int) -> int:
        """Neighbours a node of ``depth`` draws."""
        return self.fanouts[-1 - depth]

    def nodes_at(self, seeds: int) -> Tuple[int, ...]:
        """Lanes of every depth ``0 .. layers`` for ``seeds`` seeds."""
        lanes = [seeds]
        for depth in range(self.layers):
            lanes.append(lanes[-1] * self.draws(depth))
        return tuple(lanes)

    def leaf_shapes(self) -> Dict[str, Tuple[int, ...]]:
        shapes = {}
        for i, (n, m) in enumerate(zip(self.widths, self.widths[1:]), 1):
            shapes[f"w{i}_self"] = (n, m)
            shapes[f"w{i}_neigh"] = (n, m)
            shapes[f"b{i}"] = (m,)
        return shapes

    def macs_per_step(self, seeds: int) -> int:
        """Multiply-adds of one step's FORWARD pass: layer ``l`` over the
        lanes of the depths ``0 .. layers - l``, a self and a neighbour
        product each."""
        lanes = self.nodes_at(seeds)
        return sum(
            2 * n * m * sum(lanes[: self.layers - i])
            for i, (n, m) in enumerate(zip(self.widths, self.widths[1:])))


def _dot(a: Array, b: Array) -> Array:
    return jnp.dot(a, b, precision=_HIGHEST)


class GraphSage(BatchedWorkerLogic):
    """Batch: ``seed`` (B,) int node ids, ``label`` (B,) int classes,
    ``mask`` (B,) bool.  Seven rounds of keys (the module docstring); the
    state a dict: the leaves of :meth:`SageConfig.leaf_shapes`, ``m_<leaf>``
    and ``v_<leaf>``, Adam's moments, ``t`` its step count (int32),
    ``beta1_t`` / ``beta2_t`` the betas' running powers, ``key`` the
    sampler's (uint32[2]).  The outputs are ``loss`` (B,), a live seed's
    cross-entropy, and the step's counts ``sage_sampled_lanes``,
    ``sage_live_lanes`` and ``sage_feature_rows``."""

    def __init__(self, config: SageConfig, *, seed=0):
        self.config = config
        self.seed = seed

    # -- state -------------------------------------------------------------
    def init_state(self, rng: Array) -> Dict[str, Array]:
        """Glorot-uniform weights at ReLU's gain (DGL's ``SAGEConv``), zero
        biases, zero moments, step 0; the sampler's key folded from ``rng``
        and the logic's seed."""
        seed = self.seed
        if not isinstance(seed, jax.Array):  # a number: no program of its own
            seed = np.asarray(seed).astype(np.uint32)
        return _init_state(
            rng, seed, shapes=tuple(self.config.leaf_shapes().items()))

    # -- the rounds of pulls -------------------------------------------------
    def keys(self, batch: Dict[str, Array]) -> Dict[str, Array]:
        return {"off": _row_ends(batch["seed"].astype(jnp.int32))}

    def next_keys(self, state, batch, pulled) -> Optional[Dict[str, Array]]:
        cfg, n = self.config, len(pulled)
        depth = n // 2
        if n == 2 * cfg.layers + 1:
            return None
        if n == 2 * cfg.layers:
            # every node met, depth by depth: the seeds, then each sampled
            # block in its own C order (dead lanes pull row 0)
            nodes, live = self._frontiers(batch, pulled)
            return {"feat": jnp.concatenate([
                jnp.where(alive, at, -1).reshape(-1)
                for at, alive in zip(nodes, live)])}
        if n % 2:
            # the adjacency rows' ends of depth `depth` came back: draw
            with scope("ps.sample"):
                _, live = self._frontiers(batch, pulled)
                ends = pulled[-1]["off"]
                first, degree = ends[:, 0], ends[:, 1] - ends[:, 0]
                words = jax.random.bits(
                    self._key(state, depth),
                    (cfg.draws(depth), first.shape[0]), jnp.uint32)
                pick = (words % jnp.maximum(degree, 1).astype(jnp.uint32))
                has = live[depth].reshape(-1) & (degree > 0)
                return {"nbr": jnp.where(
                    has, first + pick.astype(jnp.int32), -1)}
        # the neighbours of depth `depth - 1` came back: their rows' ends
        return {"off": _row_ends(pulled[-1]["nbr"].reshape(-1))}

    def _key(self, state, fold: int) -> Array:
        """The step's key for the draw ``fold``: hops 0.., then dropout's
        masks at ``layers - 1 + l``."""
        return jax.random.fold_in(
            jax.random.fold_in(state["key"], state["t"]), fold)

    def _frontiers(self, batch, pulled):
        """``(nodes, live)`` of every depth the rounds so far have reached:
        depth 0 ``(B,)``, depth ``d + 1`` ``(k_d, n_d)``.  A sampled lane is
        live where its parent is and has a neighbour to draw."""
        nodes = [batch["seed"].astype(jnp.int32)]
        live = [batch["mask"]]
        for depth in range(len(pulled) // 2):
            ends = pulled[2 * depth]["off"]
            has = live[depth].reshape(-1) & (ends[:, 1] > ends[:, 0])
            drawn = pulled[2 * depth + 1]["nbr"]
            nodes.append(drawn)
            live.append(jnp.broadcast_to(has, drawn.shape))
        return nodes, live

    # -- the compute ---------------------------------------------------------
    def step(self, state, batch, pulled):
        cfg = self.config
        _, live = self._frontiers(batch, pulled)
        lanes = [alive.size for alive in live]
        x = pulled[-1]["feat"]
        leaves = {k: state[k] for k in cfg.leaf_shapes()}
        label = batch["label"].astype(jnp.int32)
        seeds_live = live[0]
        examples = jnp.maximum(jnp.sum(seeds_live, dtype=jnp.float32), 1.0)

        def loss_of(leaves):
            h = x
            for layer in range(1, cfg.layers + 1):
                # the destinations: depths 0 .. layers - layer, the first
                # lanes of `h`; each depth's children the block behind it
                depths = cfg.layers - layer + 1
                dest = sum(lanes[:depths])
                means, at = [], lanes[0]
                for depth in range(depths):
                    k, n = cfg.draws(depth), lanes[depth]
                    block = h[at:at + k * n].reshape(k, n, h.shape[1])
                    alive = live[depth + 1]
                    total = jnp.where(alive[..., None], block, 0.0).sum(axis=0)
                    # (a parent's children live or die together)
                    means.append(jnp.where(alive[0][:, None], total / k, 0.0))
                    at += k * n
                h = (_dot(h[:dest], leaves[f"w{layer}_self"])
                     + _dot(jnp.concatenate(means), leaves[f"w{layer}_neigh"])
                     + leaves[f"b{layer}"])
                if layer < cfg.layers:
                    h = jnp.maximum(h, 0.0)
                    if cfg.dropout:
                        word = jax.random.bits(
                            self._key(state, cfg.layers - 1 + layer),
                            h.shape, jnp.uint32)
                        keep = word < np.uint32(
                            round((1.0 - cfg.dropout) * 2.0**32))
                        h = jnp.where(keep, h / (1.0 - cfg.dropout), 0.0)
            lse = jax.nn.logsumexp(h, axis=1)
            picked = jnp.take_along_axis(h, label[:, None], axis=1)[:, 0]
            each = jnp.where(seeds_live, lse - picked, 0.0)
            return each.sum() / examples, each

        with scope("ps.sage_dense"):
            (_, each), grads = jax.value_and_grad(
                loss_of, has_aux=True)(leaves)
        with scope("ps.dense_adam"):
            new = dict(state)
            new["t"] = state["t"] + 1
            b1t = new["beta1_t"] = state["beta1_t"] * np.float32(cfg.beta1)
            b2t = new["beta2_t"] = state["beta2_t"] * np.float32(cfg.beta2)
            for k, g in grads.items():
                m = new[f"m_{k}"] = (
                    np.float32(cfg.beta1) * state[f"m_{k}"]
                    + np.float32(1.0 - cfg.beta1) * g)
                v = new[f"v_{k}"] = (
                    np.float32(cfg.beta2) * state[f"v_{k}"]
                    + np.float32(1.0 - cfg.beta2) * (g * g))
                new[k] = state[k] - np.float32(cfg.learning_rate) * (
                    m / (1.0 - b1t)) / (
                        jnp.sqrt(v / (1.0 - b2t)) + np.float32(cfg.eps))
        out = {
            "loss": each,
            "sage_sampled_lanes": jnp.asarray(sum(lanes[1:]), jnp.int32),
            "sage_live_lanes": sum(
                jnp.sum(alive, dtype=jnp.int32) for alive in live[1:]),
            "sage_feature_rows": jnp.asarray(sum(lanes), jnp.int32),
        }
        return new, {}, out

    def publish_counts(self, outs: dict, registry: Any, total, peak) -> None:
        if "sage_sampled_lanes" not in outs:
            return
        registry.gauge("sage_sampled_lanes", component="train").set(
            total(outs["sage_sampled_lanes"]))
        registry.gauge("sage_live_lanes", component="train").set(
            total(outs["sage_live_lanes"]))
        registry.gauge("sage_feature_rows", component="train").set(
            total(outs["sage_feature_rows"]))


def _row_ends(nodes: Array) -> Array:
    """``(n, 2)`` keys into ``off``: a node's row and the next."""
    return jnp.stack([nodes, nodes + 1], axis=-1)


def _init_state(rng: Array, seed, *, shapes) -> Dict[str, Array]:
    key = jax.random.fold_in(rng, seed)
    state: Dict[str, Array] = {}
    for i, (name, shape) in enumerate(shapes):
        if len(shape) == 2:
            bound = np.sqrt(2.0) * np.sqrt(6.0 / (shape[0] + shape[1]))
            state[name] = jax.random.uniform(
                jax.random.fold_in(key, i), shape, jnp.float32,
                -bound, bound)
        else:
            state[name] = jnp.zeros(shape, jnp.float32)
    for name, shape in shapes:
        state[f"m_{name}"] = jnp.zeros(shape, jnp.float32)
        state[f"v_{name}"] = jnp.zeros(shape, jnp.float32)
    state["t"] = jnp.zeros((), jnp.int32)
    state["beta1_t"] = jnp.ones((), jnp.float32)
    state["beta2_t"] = jnp.ones((), jnp.float32)
    state["key"] = jax.random.fold_in(key, len(shapes))
    return state


def degree_thresholds(config: SageConfig) -> np.ndarray:
    """The degree law's cumulative distribution as uint32 thresholds: a
    uniform word ``h`` draws the degree ``1 + searchsorted(thresholds, h,
    "right")``, integers throughout (the same graph from the same seed on
    any backend)."""
    d = np.arange(1, config.degree_cap + 1, dtype=np.float64)
    p = d ** -config.degree_exponent
    cdf = np.cumsum(p / p.sum())
    return np.minimum(np.floor(cdf[:-1] * 2.0**32), 2.0**32 - 1).astype(
        np.uint32)


def make_stores(
    config: SageConfig, *, seed=0, mesh=None, dtype=None,
) -> StoreGroup:
    """The three stores, built on the device from ``seed`` (which may be
    traced), their rows' place on the chip ``core/store._resolve_layout``'s
    to choose: ``off`` and ``nbr`` int32 scalar rows (128 to a physical
    row), ``feat`` ``features`` float32 lanes a row.  The graph is a
    CONTROL, not a law measured on a data set: node ``v``'s degree is drawn
    from ``P(d) ~ d ^ -degree_exponent`` on ``1 .. degree_cap`` by a
    hash of ``v``, the rows' ends are the running sum of the degrees cut at
    ``num_edges`` (the last rows end where the ids do: a node past that end
    has no neighbour here, and DEAD lanes below it), and neighbour ``e``
    is a hash of ``e`` over the nodes, uniform; a feature is ``U(-1, 1)``
    of the seed and the row's id.  ``nbr`` and ``feat`` are initialised in
    place (``ShardedParamStore.create``); ``off`` is a running sum, made
    whole (``num_nodes + 1`` words) and placed."""
    dtype = dtype or jnp.float32
    seed = jnp.asarray(seed).astype(jnp.uint32)
    nodes = np.uint32(config.num_nodes)
    thresholds = degree_thresholds(config)

    def neighbour(ids: Array) -> Array:
        h = _fmix32(ids.astype(jnp.uint32) * _GOLDEN + seed)
        return (h % nodes).astype(jnp.int32)

    ids = jnp.arange(config.num_nodes, dtype=jnp.uint32)
    words = _fmix32((ids + np.uint32(0x5A6E)) * _GOLDEN ^ (seed + np.uint32(1)))
    degree = 1 + jnp.searchsorted(
        jnp.asarray(thresholds), words, side="right").astype(jnp.int32)
    ends = jnp.minimum(jnp.cumsum(degree), config.num_edges)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return StoreGroup.of({
        "off": ShardedParamStore.from_values(
            offsets, mesh=mesh, layout="auto"),
        "nbr": ShardedParamStore.create(
            config.num_edges, (), dtype=jnp.int32, init_fn=neighbour,
            mesh=mesh, layout="auto"),
        "feat": ShardedParamStore.create(
            config.num_nodes, (config.widths[0],), dtype=dtype,
            init_fn=ranged_random_factor(
                seed, (config.widths[0],), low=-1.0, high=1.0, dtype=dtype),
            mesh=mesh, layout="auto"),
    })


__all__ = ["GraphSage", "SageConfig", "degree_thresholds", "make_stores"]
