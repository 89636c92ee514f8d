"""The entry points that decide what a chip run may claim: ``chip_smoke.py``
refuses to run off the chip unless told to dry-run, the compile cache can
be placed from outside, and a shard child starts pinned to the CPU."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(**extra):
    """One CPU device, no inherited cache placement: what a user's shell
    on a chipless machine looks like."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _run(args, **kw):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=300, **kw,
    )


# -- chip_smoke.py ------------------------------------------------------------


def test_chip_smoke_dry_run_passes_and_says_what_it_is(tmp_path):
    r = _run(
        [os.path.join(REPO, "chip_smoke.py"), "--cpu-dry-run",
         "--out", str(tmp_path / "out")],
        env=_child_env(), cwd=str(tmp_path),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "platform: cpu" in r.stdout
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["ok"] is True
    assert summary["device"]["platform"] == "cpu"
    assert "control flow only" in summary["dry_run"]
    assert "main_path" in summary["stages"]
    assert any(s.startswith("kernel:") for s in summary["stages"])
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    # a dry run never prints the chip run's two-key result line
    lines = r.stdout.strip().splitlines()
    assert [ln for ln in lines if ln.startswith('{"ok"')] == lines[-1:]


def test_chip_smoke_result_line_has_exactly_the_contract_keys(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for ok in (True, False):
        line = chip_smoke.result_line(ok, device)
        assert "\n" not in line
        got = json.loads(line)
        assert list(got) == ["ok", "device"] and got["ok"] is ok
        assert got["device"] == device
        assert list(got["device"]) == ["platform", "kind", "count"]


def test_chip_smoke_without_a_chip_exits_nonzero_with_no_result(tmp_path):
    """No flag, no TPU: nonzero before anything is compiled (nothing is
    cached, no stage line, no JSON)."""
    cache = tmp_path / "cache"
    r = _run(
        [os.path.join(REPO, "chip_smoke.py"), "--out", str(tmp_path / "o")],
        env=_child_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
        cwd=str(tmp_path),
    )
    assert r.returncode not in (0, 1), (r.returncode, r.stderr[-2000:])
    assert r.stdout.strip() == ""
    assert "--cpu-dry-run" in r.stderr
    assert not cache.exists() or not os.listdir(cache)
    assert not (tmp_path / "o").exists()


# -- utils/compile_cache.py ---------------------------------------------------

_CACHE_PROBE = (
    "import json, jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "from flink_parameter_server_tpu.utils.compile_cache import "
    "enable_compile_cache\n"
    "got = enable_compile_cache()\n"
    "print(json.dumps([before, got, jax.config.jax_compilation_cache_dir]))\n"
)


def _cache_probe(cwd, **env):
    r = _run(
        ["-c", _CACHE_PROBE], cwd=str(cwd),
        env=_child_env(PYTHONPATH=REPO, **env),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_the_environment_untouched(tmp_path):
    placed = str(tmp_path / "placed")
    before, got, after = _cache_probe(
        tmp_path, JAX_COMPILATION_CACHE_DIR=placed
    )
    assert before == got == after == placed


def test_compile_cache_default_is_fixed_to_the_checkout(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    other = tmp_path / "elsewhere"
    other.mkdir()
    for cwd in (REPO, other):
        before, got, after = _cache_probe(cwd)
        assert before is None
        assert got == after == want


# -- cluster/procs.py ---------------------------------------------------------


def test_shard_child_is_started_pinned_to_the_cpu(monkeypatch):
    """The variable must be in the environment the child STARTS with —
    jax reads it at import, which in a spawned child precedes the
    child's own first line — and the parent's value comes back after."""
    from flink_parameter_server_tpu.cluster import procs

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with procs._cpu_pinned_child_env():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with procs._cpu_pinned_child_env():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in os.environ
