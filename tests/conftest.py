"""Test harness: 8 virtual CPU devices = the "MiniCluster equivalent".

The reference tests distributed behavior on Flink's in-JVM MiniCluster
(real operator parallelism, local channels — SURVEY.md §4).  Our analogue:
XLA's CPU backend with a forced host device count gives real pjit shardings
and real collectives without TPU hardware.

The suite is pinned to the CPU with ``jax.config.update`` rather than
the environment: a plugin or an earlier import may have loaded jax before
this file runs, and jax captures ``JAX_PLATFORMS`` when it is imported.
Set ``FPS_TPU_TESTS=1`` to run the suite on the backend jax finds instead.
"""
import os
import shutil
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# One compile a program a RUN.  The tests call the store eagerly, so every
# primitive at every new shape is an XLA program of its own (~60 ms), and
# under ``--dist load`` each xdist worker met the same ones and compiled them
# again.  The first process of a run (the xdist controller, which imports
# this file before it starts its workers; or the one process of a run
# without xdist) makes an EMPTY directory and names it in the environment,
# where its workers and every subprocess a test starts find it: a process
# that finds one named uses it and removes nothing.  Whoever made it removes
# it when the session ends: nothing outlives the run, so a second run is no
# faster than the first and no executable is loaded on another machine than
# compiled it.  ``tests/test_tpu_compile.py`` switches the cache off round
# its compiles (a described chip's executable cannot be read back).
_RUN_CACHE = None
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _RUN_CACHE = tempfile.mkdtemp(prefix="fps-tests-jax-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _RUN_CACHE
# (the defaults keep nothing that compiled in under a second: every entry here)
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

import jax  # noqa: E402

if os.environ.get("FPS_TPU_TESTS") != "1":
    jax.config.update("jax_platforms", "cpu")
# (as above: jax may have read the environment before this file ran)
jax.config.update(
    "jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_unconfigure(config):
    """The run's compile cache goes with the run (its maker removes it)."""
    if _RUN_CACHE is not None:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


@pytest.fixture()
def no_compile_cache():
    """For a test that must see the backend compile: no executable is read
    from (or written to) the run's cache until the test ends."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, devs
    return devs


@pytest.fixture(scope="session")
def mesh_devices():
    """The ≥8 virtual devices the mesh-store tests shard over.

    The XLA flag above applies only if THIS module ran before any jax
    backend initialized; when something imported jax first (a stray
    sitecustomize, an IDE runner collecting a single file), the flag
    cannot retroactively split the host — so skip with the remedy
    rather than failing on a 1-device "mesh"."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(
            "jax initialized without --xla_force_host_platform_device_"
            "count=8 (the flag cannot apply after backend init): run "
            "pytest from tests/ so conftest.py sets XLA_FLAGS before "
            "jax imports"
        )
    return devs


@pytest.fixture(scope="session")
def mesh():
    """2 workers (dp) x 4 ps shards — both reference parallelism knobs >1."""
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    return make_mesh(worker_parallelism=2, ps_parallelism=4)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def steer_arms(monkeypatch):
    """The ONE way a test reaches an arm its backend would not get:
    ``steer_arms(push="tile_add", write_back="row_set")`` replaces those
    fields of the record the REAL ``core/store.arms`` reads from the spec
    and the batch, for every store until the test ends (a kernel arm then
    runs interpreted on the CPU).  An arm the store does not have stays as
    read: an add store's ``combine`` and ``write_back``, a rule store's
    ``push`` and ``shift``, a dense store's ``shift``, a row a sort carries
    (``combine`` ``"sort"``: no other form takes such a row), a pull that is
    not packed (but a narrow rule store's: ``pull="narrow_distinct"`` has it
    read a batch's distinct rows once, ``pull="narrow"`` a row a lane).  A
    value may be a function of the spec.  Calls stack."""
    import dataclasses

    from flink_parameter_server_tpu.core import store as store_mod

    def steer(**fields):
        real = store_mod.arms

        def steered(spec, **lanes):
            arm = real(spec, **lanes)
            new = {}
            for name, value in fields.items():
                was = getattr(arm, name)
                if isinstance(was, str) and (
                        was in ("", "rule", "sort", "take")):
                    continue
                # a narrow pull is steered between its own two forms alone
                if str(was).startswith("narrow") and not (
                        str(value).startswith("narrow")):
                    continue
                new[name] = value(spec) if callable(value) else value
            return dataclasses.replace(arm, **new)

        monkeypatch.setattr(store_mod, "arms", steered)

    return steer


def pytest_collection_modifyitems(config, items):
    """Environment-gated marker skips.

    ``shmem``: hosts without usable POSIX shared memory (no /dev/shm,
    or not writable) — the shm transport itself falls back to TCP
    there, so there is nothing to test.

    ``meshstore``: sessions where jax initialized before this conftest
    could force 8 virtual CPU devices — the flag cannot apply
    post-init, and a 1-device run would test nothing the marker
    promises (deterministic ≥8-way mesh shardings)."""
    from flink_parameter_server_tpu.shmem import available

    if not available():
        skip = pytest.mark.skip(reason="no writable /dev/shm on this host")
        for item in items:
            if "shmem" in item.keywords:
                item.add_marker(skip)
    if jax.device_count() < 8:
        skip_mesh = pytest.mark.skip(
            reason=(
                "jax initialized without --xla_force_host_platform_"
                "device_count=8 (the flag cannot apply after backend "
                "init): run pytest so tests/conftest.py imports before "
                "jax does"
            )
        )
        for item in items:
            if "meshstore" in item.keywords:
                item.add_marker(skip_mesh)
