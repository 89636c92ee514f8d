"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

The reference has nothing sequence-related (SURVEY.md §2: SP/CP "ABSENT"),
but long-context support is a first-class requirement of this framework:
the Transformer config (BASELINE.json #5) must scale past a single chip's
memory for long sequences.

Design (blockwise/ring attention): the sequence dimension is sharded over
``sp``; each device holds one Q/K/V block.  S−1 ``ppermute`` steps rotate
the K/V blocks around the ICI ring while every device accumulates its
queries' attention with the *online softmax* (running max/denominator), so
the full (T × T) score matrix never materialises and per-device memory is
O(T/S · T/S) per step.  Compute for step j overlaps with the DMA of step
j+1 under XLA's async collective scheduling.

Causality is enforced per block pair: the j-th rotation gives device ``i``
the K/V of global block ``(i − j) mod S``; blocks strictly in the future
are fully masked, the diagonal block gets the triangular mask, past blocks
are unmasked.  Step 0 is the self block, so every query row always has at
least one valid key (no -inf softmax rows).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _block_attention_update(q, k, v, scores_mask, m, l, o, scale):
    """One online-softmax accumulation step.

    q: (B, H, T, D), k/v: (B, H, T, D); scores_mask (T, T) bool (True =
    attend); m, l: (B, H, T) fp32; o: (B, H, T, D) fp32.

    Scores and all running accumulators are float32 regardless of the
    input dtype (the flash/ring-attention convention): bf16 running
    max/denominator compound ~1e-2 error per rescale chain over many ring
    steps.  Inputs may stay bf16 — the MXU reads bf16 operands and this
    einsum accumulates fp32 via ``preferred_element_type``.
    """
    scores = (
        jnp.einsum("bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32)
        * scale
    )  # (B,H,T,S) fp32
    scores = jnp.where(scores_mask[None, None], scores, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    # renormalise previous accumulators; exp(-inf - finite) == 0 is safe
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    # fully-masked rows produce p == 0 everywhere, contributing nothing
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhts,bhsd->bhtd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def ring_attention_inner(
    q_blk: Array,
    k_blk: Array,
    v_blk: Array,
    *,
    sp_axis: str,
    num_blocks: int,
    causal: bool = True,
) -> Array:
    """The ring schedule on LOCAL (B, T_local, H_local, D) blocks.

    Call this inside an *enclosing* ``shard_map`` whose mesh carries
    ``sp_axis`` (shard_maps don't nest) — e.g. from a pipeline stage body
    (:mod:`.pipeline`).  ``num_blocks`` must be the static ``sp`` size.
    """
    # (B_local, T_local, H, D) → (B, H, T, D)
    qh = jnp.moveaxis(q_blk, 2, 1)
    kh = jnp.moveaxis(k_blk, 2, 1)
    vh = jnp.moveaxis(v_blk, 2, 1)
    B, H, T, D = qh.shape
    scale = 1.0 / (D**0.5)
    my = jax.lax.axis_index(sp_axis)

    m = jnp.full((B, H, T), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, T), jnp.float32)
    o = jnp.zeros((B, H, T, D), jnp.float32)

    tri = jnp.tril(jnp.ones((T, T), bool))
    full = jnp.ones((T, T), bool)
    none = jnp.zeros((T, T), bool)

    def step(j, carry):
        m, l, o, kh, vh = carry
        src = (my - j) % num_blocks
        if causal:
            mask = jnp.where(src == my, tri, jnp.where(src < my, full, none))
        else:
            mask = full
        m, l, o = _block_attention_update(qh, kh, vh, mask, m, l, o, scale)
        if j < num_blocks - 1:  # final rotation's result is never read
            perm = [(i, (i + 1) % num_blocks) for i in range(num_blocks)]
            kh = jax.lax.ppermute(kh, sp_axis, perm)
            vh = jax.lax.ppermute(vh, sp_axis, perm)
        return m, l, o, kh, vh

    # unrolled python loop: num_blocks is static and small; lets XLA
    # pipeline each step's compute with the next ppermute
    carry = (m, l, o, kh, vh)
    for j in range(num_blocks):
        carry = step(j, carry)
    m, l, o, _, _ = carry

    out = (o / jnp.maximum(l[..., None], 1e-30)).astype(q_blk.dtype)
    return jnp.moveaxis(out, 1, 2)  # back to (B, T, H, D)


def ring_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    mesh: Mesh,
    sp_axis: str = "sp",
    dp_axis: Optional[str] = "dp",
    tp_axis: Optional[str] = None,
    causal: bool = True,
) -> Array:
    """Causal multi-head attention with the sequence sharded over ``sp``.

    q, k, v: (B, T_global, H, D) with T_global sharded over ``sp``, B over
    ``dp`` (if present) and heads over ``tp`` (if given — each device then
    runs the ring for its local heads only, composing SP×TP).  Returns
    same-shaped output, same sharding.
    """
    num_blocks = mesh.shape[sp_axis]

    lead = (dp_axis,) if dp_axis else (None,)
    spec = P(*lead, sp_axis, tp_axis, None)

    def body(q_blk, k_blk, v_blk):
        return ring_attention_inner(
            q_blk, k_blk, v_blk,
            sp_axis=sp_axis, num_blocks=num_blocks, causal=causal,
        )

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def reference_attention(q: Array, k: Array, v: Array, *, causal: bool = True) -> Array:
    """Unsharded causal attention — the parity oracle for ring_attention."""
    qh = jnp.moveaxis(q, 2, 1)
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bhtd,bhsd->bhts", qh, kh) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bhsd->bhtd", probs, vh)
    return jnp.moveaxis(out, 1, 2)


__all__ = ["ring_attention", "reference_attention"]
