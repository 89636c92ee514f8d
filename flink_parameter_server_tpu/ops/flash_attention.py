"""Flash attention for the dense transformer path (TPU splash kernel).

Reference parity: the reference has nothing sequence-related (SURVEY.md
§2 "Sequence/context parallelism": absent) — this is a beyond-reference
TPU-native component backing BASELINE config 5 (transformer-LM) and the
long-context story.  The O(T²) scores matrix of
:func:`..parallel.ring_attention.reference_attention` never touches HBM:
the splash kernel (JAX's production TPU flash attention,
``jax.experimental.pallas.ops.tpu.splash_attention``) streams K/V blocks
through VMEM with an online softmax, skipping fully-masked blocks of the
causal mask entirely (~2× fewer FLOPs at long T), with a custom VJP for
training.

Integration contract (matching ``reference_attention``):

  * layout ``(B, T, H, D)`` in, ``(B, T, H, D)`` out (the kernel's
    native layout is ``(H, T, D)``; batch is vmapped),
  * causal masking, ``1/sqrt(D)`` scaling applied to q (the kernel does
    NOT scale internally),
  * fp32 softmax accumulation regardless of input dtype (kernel-internal).

``supports_shape`` gates the compiled path conservatively (T a multiple
of 128 sublane-tiles, D a multiple of 64 lanes); ``chip_smoke.py``
compiles forward and gradient at T=512, D=64 on the chip.  Off-TPU the
caller should prefer ``reference_attention`` —
interpret mode exists for parity tests, not perf.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def supports_shape(seq_len: int, head_dim: int) -> bool:
    """True if the compiled splash kernel supports (T, D)."""
    return seq_len % 128 == 0 and head_dim % 64 == 0 and seq_len >= 128


def eligible(seq_len: int, head_dim: int, mesh=None) -> bool:
    """The 'auto' gate: compiled flash is used iff this holds.  ONE
    home for the predicate — the transformer's attention dispatch and
    the benchmarks' run-labeling both call it (a drifted copy would
    mislabel A/B rows)."""
    return (
        mesh is None
        and jax.default_backend() == "tpu"
        and supports_shape(seq_len, head_dim)
    )


def _dp_only_mesh(mesh, dp_axis: str) -> bool:
    return (
        mesh is not None
        and dp_axis in mesh.axis_names
        and all(
            size == 1
            for name, size in mesh.shape.items()
            if name != dp_axis
        )
    )


def eligible_dp(
    seq_len: int, head_dim: int, batch: int, mesh, dp_axis: str = "dp"
) -> bool:
    """The 'auto' gate for DATA-PARALLEL meshes: flash runs per dp shard
    under shard_map (attention is batch-elementwise, so a dp-only mesh
    needs no cross-shard traffic).  sp/tp/pp meshes stay on their ring /
    reference paths."""
    return (
        _dp_only_mesh(mesh, dp_axis)
        and jax.default_backend() == "tpu"
        and supports_shape(seq_len, head_dim)
        and batch % mesh.shape[dp_axis] == 0
    )


def flash_mha_dp(
    q: Array,
    k: Array,
    v: Array,
    *,
    mesh,
    dp_axis: str = "dp",
    interpret: Optional[bool] = None,
) -> Array:
    """Causal flash attention with the batch dim sharded over ``dp``:
    one kernel invocation per shard, no collectives (attention never
    mixes batch rows).  Inside a jit whose activations are already
    dp-sharded this is a sharding-preserving no-op wrapper around the
    kernel — the multi-chip deployment of BASELINE config 5."""
    from jax.sharding import PartitionSpec as P

    B = q.shape[0]
    dp = mesh.shape[dp_axis]
    if B % dp != 0:
        raise ValueError(
            f"flash_mha_dp needs batch {B} divisible by dp={dp}"
        )
    spec = P(dp_axis, None, None, None)
    fn = jax.shard_map(
        lambda a, b, c: flash_mha(a, b, c, interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


@functools.lru_cache(maxsize=32)
def _make_kernel(seq_len: int, num_heads: int, interpret: bool):
    """Kernel construction is Python-side work (mask metadata build) —
    cache per static shape so repeated traces reuse it.

    ``ensure_compile_time_eval``: the splash builder materialises small
    mask arrays; when the first call happens inside a jit trace those
    would be tracers, and caching a tracer-carrying kernel poisons every
    later trace (UnexpectedTracerError).  Forcing compile-time eval makes
    the cached kernel concrete regardless of caller context."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    with jax.ensure_compile_time_eval():
        mask = sm.MultiHeadMask(
            [sm.CausalMask((seq_len, seq_len)) for _ in range(num_heads)]
        )
        return sk.make_splash_mha_single_device(
            mask=mask, interpret=interpret
        )


def flash_mha(
    q: Array,
    k: Array,
    v: Array,
    *,
    interpret: Optional[bool] = None,
) -> Array:
    """Causal flash attention on ``(B, T, H, D)`` tensors.

    Drop-in for ``reference_attention(q, k, v)`` (causal=True) — parity
    asserted to kernel-accumulation tolerance in
    tests/test_flash_attention.py, gradients included.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, D = q.shape
    if not supports_shape(T, D):
        raise ValueError(
            f"flash_mha needs T % 128 == 0 and D % 64 == 0; got T={T}, "
            f"D={D}. Callers should gate on supports_shape() and fall "
            f"back to reference_attention."
        )
    kernel = _make_kernel(T, H, interpret)
    # scale q in f32 (a bf16 pre-scale would round before the kernel's
    # f32 accumulation even starts)
    scale = 1.0 / (D**0.5)
    q_scaled = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def one(qb, kb, vb):
        out = kernel(
            qb.transpose(1, 0, 2),  # (H, T, D)
            kb.transpose(1, 0, 2),
            vb.transpose(1, 0, 2),
        )
        return out.transpose(1, 0, 2)

    return jax.vmap(one)(q_scaled, k, v).astype(v.dtype)


__all__ = [
    "flash_mha",
    "flash_mha_dp",
    "supports_shape",
    "eligible",
    "eligible_dp",
]
