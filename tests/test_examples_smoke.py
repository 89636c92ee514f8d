"""Smoke tests for the runnable examples (the reference's L5 apps).

Runs the examples as real subprocesses — the exact user surface — so
example bit-rot fails CI.  All examples are covered (the PA and
sketches examples twice: their single-process default AND their
``--serve`` registry/cluster path, workloads/); the slow one
(hybrid_migration, ~2.5 min on this 1-core host) stays behind
``FPS_ALL_EXAMPLES=1`` so per-commit cost stays low while the verify
workflow exercises the full set.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )


def test_passive_aggressive_example():
    r = _run([os.path.join("examples", "passive_aggressive_classification.py")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "train accuracy" in r.stdout


def test_mf_example_with_args():
    r = _run(
        [
            os.path.join("examples", "online_mf_movielens.py"),
            "--dim", "8", "--epochs", "1", "--batch", "8192",
        ]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "train RMSE" in r.stdout


def test_passive_aggressive_example_cluster_serve():
    """The registry path: --serve runs the PA workload on a live
    2-shard cluster (bitwise parity enforced in the example itself)
    and answers `predict` margins over the TCP verb endpoint."""
    r = _run(
        [
            os.path.join(
                "examples", "passive_aggressive_classification.py"
            ),
            "--serve", "--rounds", "10", "--batch", "64",
            "--features", "48",
        ]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "bitwise parity vs streaming: True" in r.stdout
    assert "served margins" in r.stdout


def test_streaming_sketches_example():
    r = _run([os.path.join("examples", "streaming_sketches.py")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "count-min hottest words" in r.stdout
    assert "F2 estimate" in r.stdout


def test_streaming_sketches_example_cluster_serve():
    """The registry path: --serve runs the count-min workload on a
    live 2-shard cluster (integer-exact counts enforced in the
    example) and answers query/topk over the TCP verb endpoint."""
    r = _run(
        [
            os.path.join("examples", "streaming_sketches.py"),
            "--serve", "--rounds", "12", "--batch", "256",
            "--vocab", "128",
        ]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "integer-exact vs ground truth: True" in r.stdout
    assert "served top-4" in r.stdout


def test_topk_recommendation_example():
    r = _run([os.path.join("examples", "topk_recommendation.py")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "top-10 items" in r.stdout


def test_word2vec_example():
    r = _run([os.path.join("examples", "word2vec_skipgram.py")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "neighbours" in r.stdout


@pytest.mark.slow
def test_transformer_lm_example():
    r = _run([os.path.join("examples", "transformer_lm.py")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout


def test_mf_example_from_socket():
    """The reference's canonical streaming demo shape: MF trained from a
    live newline-delimited TCP source until the producer closes."""
    import socketserver
    import threading

    import numpy as np

    # user count divisible by the 8-device dp mesh (worker state is
    # dp-sharded; the example's synthetic default 2000 divides too)
    rng = np.random.default_rng(0)
    payload = "".join(
        f"{rng.integers(0, 64)},{rng.integers(0, 96)},{rng.normal():.3f}\n"
        for _ in range(3000)
    ).encode()

    class H(socketserver.BaseRequestHandler):
        def handle(self):
            self.request.sendall(payload)

    class Srv(socketserver.TCPServer):
        allow_reuse_address = True

    srv = Srv(("127.0.0.1", 0), H)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        r = _run(
            [
                os.path.join("examples", "online_mf_movielens.py"),
                "--socket", f"127.0.0.1:{port}",
                "--num-users", "64", "--num-items", "96",
                "--dim", "8", "--batch", "512",
            ]
        )
    finally:
        srv.shutdown()
        srv.server_close()
    assert r.returncode == 0, r.stderr[-2000:]
    assert "socket stream ended" in r.stdout


def test_serve_recommendations_example():
    """Train-while-serve demo: in-process top-K queries mid-training,
    then a TCP round trip against the final model."""
    r = _run(
        [
            os.path.join("examples", "serve_recommendations.py"),
            "--num-users", "64", "--num-items", "96", "--dim", "8",
            "--ratings", "20000", "--batch", "1024", "--epochs", "1",
            "--queries", "4", "--k", "5",
        ]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "top-5" in r.stdout
    assert "steps stale" in r.stdout
    assert "tcp answer" in r.stdout
    assert "serving_qps" in r.stdout


def test_mf_example_socket_path_conflict_is_loud():
    """--socket with --path/--epochs must refuse, not silently ignore
    the bounded-file options (advisor finding, round 5)."""
    r = _run(
        [
            os.path.join("examples", "online_mf_movielens.py"),
            "--socket", "127.0.0.1:1", "--epochs", "2",
        ]
    )
    assert r.returncode != 0
    assert "incompatible" in (r.stderr + r.stdout)


def test_production_driver_example():
    r = _run(
        [
            os.path.join("examples", "production_driver.py"),
            "--batches", "24", "--steps-per-call", "4",
            "--checkpoint-every", "8",
        ]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "resumed at step" in r.stdout
    assert "resumed-run RMSE" in r.stdout


@pytest.mark.skipif(
    os.environ.get("FPS_ALL_EXAMPLES") != "1",
    reason="~2.5 min on a 1-core host; set FPS_ALL_EXAMPLES=1 "
           "(the verify workflow does) to include it",
)
def test_hybrid_migration_example():
    r = _run([os.path.join("examples", "hybrid_migration.py")], timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "-shard device store" in r.stdout
