"""Parameters (ParameterTool analogue) + __graft_entry__ regression."""
import os
import sys

import jax
import pytest

from flink_parameter_server_tpu.utils.config import Parameters


class TestParameters:
    def test_args_forms(self):
        p = Parameters.from_args(
            ["--lr", "0.05", "--dim=16", "--use-ring", "--name", "mf"]
        )
        assert p.get_float("lr") == 0.05
        assert p.get_int("dim") == 16
        assert p.get_bool("use-ring") is True
        assert p.get("name") == "mf"
        assert p.get("missing", "d") == "d"

    def test_required_and_errors(self):
        p = Parameters.from_args([])
        with pytest.raises(KeyError, match="required parameter --lr"):
            p.required("lr")
        with pytest.raises(ValueError, match="expected --key"):
            Parameters.from_args(["lr", "0.1"])

    def test_env_and_merge(self, monkeypatch):
        monkeypatch.setenv("FPS_LR", "0.1")
        monkeypatch.setenv("FPS_DIM", "8")
        env = Parameters.from_env()
        argv = Parameters.from_args(["--lr", "0.2"])
        merged = env.merged_with(argv)
        assert merged.get_float("lr") == 0.2  # argv wins
        assert merged.get_int("dim") == 8


def _load_graft():
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__

    return __graft_entry__


def test_graft_entry_compiles():
    g = _load_graft()
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (256,)


@pytest.mark.slow
def test_graft_dryrun_multichip_8():
    g = _load_graft()
    g.dryrun_multichip(8)  # asserts internally; covers MF + transformer


def test_env_dash_normalization(monkeypatch):
    """FPS_USE_RING merges with the --use-ring argv convention."""
    monkeypatch.setenv("FPS_USE_RING", "1")
    env = Parameters.from_env()
    assert env.get_bool("use-ring") is True
    merged = env.merged_with(Parameters.from_args(["--use-ring=false"]))
    assert merged.get_bool("use-ring") is False  # argv overrides env


def test_numeric_errors_name_the_key():
    p = Parameters.from_args(["--dim", "abc"])
    with pytest.raises(ValueError, match="--dim"):
        p.get_int("dim")


def test_underscore_value_preserved_and_lookup_normalized():
    p = Parameters.from_args(["--checkpoint_dir=/tmp/my_run_1", "--use_ring"])
    # values keep their underscores; keys normalise on store AND lookup
    assert p.get("checkpoint-dir") == "/tmp/my_run_1"
    assert p.get("checkpoint_dir") == "/tmp/my_run_1"
    assert p.get_bool("use-ring") and p.get_bool("use_ring")
    assert "use_ring" in p
