"""Vectorised hash families for sketches and id load-balancing.

Reference parity: the reference's sketch package relies on families of
pairwise-independent hash functions for bloom/count and tug-of-war (AMS)
sketches (SURVEY.md §2 #10), and routes parameters to server subtasks by
``hash(paramId) % psParallelism`` (§2 "Model parallelism").

TPU-first: TPUs have no fast int64 path, so everything here is pure
**uint32** arithmetic with natural wraparound — multiply-xorshift mixing
(murmur3-finalizer style), branch-free, vmappable, fusable into one
elementwise kernel per microbatch.  Also works under ``jax_enable_x64=0``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_MIX1 = np.uint32(0x85EBCA6B)
_MIX2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B1)


def _fmix32(h: Array) -> Array:
    """murmur3 finalizer: full-avalanche uint32 mixing."""
    h = h ^ (h >> np.uint32(16))
    h = h * _MIX1
    h = h ^ (h >> np.uint32(13))
    h = h * _MIX2
    h = h ^ (h >> np.uint32(16))
    return h


def fmix32_np(h: np.ndarray) -> np.ndarray:
    """Host-side (numpy) mirror of :func:`_fmix32` — same constants,
    same avalanche, so routing decisions taken on the HOST (the cluster
    partitioner picking a shard before a network send,
    ``cluster/partition.py``) agree bit-for-bit with any device-side
    use of this family.  Input is coerced to uint32; wraparound is the
    hash, so the overflow warnings numpy would raise are suppressed
    locally."""
    with np.errstate(over="ignore"):
        h = np.asarray(h).astype(np.uint32)
        h ^= h >> np.uint32(16)
        h = (h * _MIX1).astype(np.uint32)
        h ^= h >> np.uint32(13)
        h = (h * _MIX2).astype(np.uint32)
        h ^= h >> np.uint32(16)
    return h


def hash_params(num_hashes: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Draw per-hash (a, b) uint32 constants (a odd), deterministic in
    ``seed``."""
    rng = np.random.default_rng(seed)
    a = rng.integers(1, 2**32, num_hashes, dtype=np.uint64).astype(np.uint32) | 1
    b = rng.integers(0, 2**32, num_hashes, dtype=np.uint64).astype(np.uint32)
    return a, b


def bucket_hash(x: Array, a: np.ndarray, b: np.ndarray, m: int) -> Array:
    """``h_i(x) = fmix32(a_i·x + b_i) mod m`` for every hash i.

    ``x``: (...,) non-negative int ids.  Returns (..., num_hashes) int32
    buckets in [0, m).
    """
    xu = x.astype(jnp.uint32)[..., None]
    h = _fmix32(jnp.asarray(a)[None, :] * xu + jnp.asarray(b)[None, :])
    return (h % jnp.uint32(m)).astype(jnp.int32)


def sign_hash(x: Array, a: np.ndarray, b: np.ndarray) -> Array:
    """±1 hash per (x, hash i) — the tug-of-war sketch's sign family.
    Returns (..., num_hashes) float32 in {-1, +1}."""
    xu = x.astype(jnp.uint32)[..., None]
    h = _fmix32(jnp.asarray(a)[None, :] * xu + jnp.asarray(b)[None, :])
    return jnp.where((h >> np.uint32(31)) == 0, 1.0, -1.0).astype(jnp.float32)


def pair_key(x: Array, y: Array, num_keys: int) -> Array:
    """Stable key for an unordered (x, y) co-occurrence pair, folded into
    [0, num_keys) — the bloom co-occurrence sketch's pair id."""
    lo = jnp.minimum(x, y).astype(jnp.uint32)
    hi = jnp.maximum(x, y).astype(jnp.uint32)
    k = _fmix32(hi * _GOLDEN + lo)
    return (k % jnp.uint32(num_keys)).astype(jnp.int32)


def permute_ids(ids: Array, capacity: int, seed: int = 0x5BD1) -> Array:
    """Bijective spreading of ids across [0, capacity): defeats
    block-sharding hotspots for Zipf-skewed ids (the rebuild's answer to
    the reference's mod-hash routing under skew; shard ``s`` owns rows
    ``[s*R, (s+1)*R)``: core/store.py's pull and push on the shards).

    ``capacity`` must be a power of two (the padded table capacity
    usually is): an odd-multiplier affine map mod 2^k is a permutation,
    and uint32 wraparound composes correctly with the final mask.
    """
    assert capacity & (capacity - 1) == 0, (
        f"permute_ids requires power-of-two capacity, got {capacity}"
    )
    a = np.uint32(((((seed << 1) | 1) * 0x9E3779B1) & 0xFFFFFFFF) | 1)
    h = ids.astype(jnp.uint32) * a + np.uint32(0x7F4A7C15)
    return (h & jnp.uint32(capacity - 1)).astype(jnp.int32)


__all__ = [
    "fmix32_np",
    "hash_params",
    "bucket_hash",
    "sign_hash",
    "pair_key",
    "permute_ids",
]
