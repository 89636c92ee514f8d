"""Two-process jax.distributed smoke test (SURVEY.md §2 "Distributed
communication backend"; round-1 verdict item 8).

The reference proves its Netty/TaskManager scale-out on an in-JVM
MiniCluster; the analogue here is two *real* OS processes coordinated by
``jax.distributed`` on the CPU backend (2 virtual devices each → a
2-host × 2-device global mesh), running parallel/multihost.py end to
end: init, DCN/ICI-aware mesh layout, ingestion slicing, one
cross-process collective, and a ShardedParamStore whose ps axis spans
both processes driven by a jitted push+pull (the scatter/gather
collectives cross the process boundary — the reference's
"keyed routing spans TaskManagers" analogue).

The children get their environment from this test, ``JAX_PLATFORMS=cpu``
included: they must not reach for a chip the parent may hold.
"""
import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.skipif(
    os.environ.get("FPS_SKIP_MULTIHOST") == "1",
    reason="multihost smoke disabled by env",
)
@pytest.mark.slow
def test_two_process_distributed_smoke():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = os.path.join(repo, "tests", "_multihost_child.py")
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"

    env = {
        **os.environ,
        "PYTHONPATH": repo,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "JAX_ENABLE_X64": "0",
    }

    procs = [
        subprocess.Popen(
            [sys.executable, child, coordinator, "2", str(pid)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"multihost children timed out; partial: {outs}")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"MULTIHOST_OK {pid}" in out, out
