"""Where the persistent XLA compilation cache lives.

Entry points (``chip_smoke.py``, ``__graft_entry__.py``, the
benchmark's ``chipbench/run.py``, the ``examples/`` that jit) call
:func:`enable_compile_cache` before their first compile; the package
never calls it at import.  The directory is part of the cache key, so
it must not move between runs: it is either where
``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that variable itself —
nothing here touches the setting then) or ``<checkout>/.jax_cache``,
computed from this file's location.

Being every entry point's first call, it is also where the program's
compile ledger starts listening (``telemetry/compile_ledger.py``): every
trace, lowering, compile and cache load from here on is counted by program.
"""
from __future__ import annotations

import os

import jax

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(  # <checkout>
        os.path.dirname(  # flink_parameter_server_tpu/
            os.path.dirname(os.path.abspath(__file__))  # utils/
        )
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    from ..telemetry import compile_ledger

    compile_ledger.install()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # keep small programs too: the defaults skip anything that compiled
    # in under a second, which is most of a smoke run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # an executable carries its ops' names (the step's ``ps.*`` scopes,
    # which a device trace is reduced by): JAX's default key strips them,
    # so a cache shared with a checkout that names its ops otherwise would
    # hand back that checkout's names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir


__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]
