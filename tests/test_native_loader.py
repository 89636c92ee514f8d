"""Native C++ loader tests: parse parity with the numpy loader, streaming
batcher correctness (all formats, shuffle, epochs, tail padding)."""
import os

import numpy as np
import pytest

from flink_parameter_server_tpu.data.movielens import load_movielens

native = pytest.importorskip(
    "flink_parameter_server_tpu.data.native_loader"
)

try:
    native.get_lib()
    HAVE_NATIVE = True
except native.NativeUnavailable:
    HAVE_NATIVE = False

pytestmark = pytest.mark.skipif(not HAVE_NATIVE, reason="no C++ toolchain")


@pytest.fixture(scope="module")
def ratings_file(tmp_path_factory):
    rng = np.random.default_rng(0)
    path = tmp_path_factory.mktemp("data") / "u.data"
    with open(path, "w") as f:
        for _ in range(1000):
            f.write(
                f"{rng.integers(1, 50)}\t{rng.integers(1, 80)}\t"
                f"{rng.integers(1, 6)}\t{rng.integers(1e8, 1e9)}\n"
            )
    return str(path)


def test_parse_matches_numpy_loader(ratings_file):
    a = native.load_ratings(ratings_file)
    b = load_movielens(ratings_file, normalize=False)
    np.testing.assert_array_equal(a["user"], b["user"])
    np.testing.assert_array_equal(a["item"], b["item"])
    np.testing.assert_allclose(a["rating"], b["rating"])


def test_parse_csv_and_dat_formats(tmp_path):
    csv = tmp_path / "ratings.csv"
    csv.write_text("userId,movieId,rating,timestamp\n1,10,4.5,0\n2,20,3.0,0\n")
    out = native.load_ratings(str(csv), compact_ids=False)
    np.testing.assert_array_equal(out["user"], [1, 2])
    np.testing.assert_array_equal(out["item"], [10, 20])
    np.testing.assert_allclose(out["rating"], [4.5, 3.0])

    dat = tmp_path / "ratings.dat"
    dat.write_text("7::99::5::0\n8::100::1::0\n")
    out = native.load_ratings(str(dat), compact_ids=False)
    np.testing.assert_array_equal(out["user"], [7, 8])
    np.testing.assert_array_equal(out["item"], [99, 100])


def test_stream_batches_covers_all_rows(ratings_file):
    batches = list(native.stream_batches(ratings_file, 256, epochs=2))
    total = sum(int(b["mask"].sum()) for b in batches)
    assert total == 2000
    # fixed shapes with padded tail
    assert all(b["user"].shape == (256,) for b in batches)


def test_stream_shuffle_changes_order_not_content(ratings_file):
    plain = list(native.stream_batches(ratings_file, 128))
    shuf = list(native.stream_batches(ratings_file, 128, shuffle_seed=7))
    cat = lambda bs, k: np.concatenate(
        [b[k][b["mask"]] for b in bs]
    )
    assert not np.array_equal(cat(plain, "user"), cat(shuf, "user"))
    assert sorted(cat(plain, "user").tolist()) == sorted(cat(shuf, "user").tolist())


def test_stream_feeds_training(ratings_file, tmp_path):
    """End-to-end: native stream -> batched MF step."""
    from flink_parameter_server_tpu.models.matrix_factorization import (
        ps_online_mf,
    )

    res = ps_online_mf(
        native.stream_batches(ratings_file, 256, epochs=1, shuffle_seed=0),
        num_users=64,
        num_items=128,
        dim=4,
        collect_outputs=False,
    )
    assert np.isfinite(np.asarray(res.store.values())).all()


def test_parse_crlf_and_no_trailing_newline(tmp_path):
    """Windows line endings and a file ending without newline parse fine."""
    p = tmp_path / "crlf.data"
    p.write_bytes(b"1\t10\t4.0\t0\r\n2\t20\t3.5\t0")  # CRLF + no final \n
    out = native.load_ratings(str(p), compact_ids=False)
    np.testing.assert_array_equal(out["user"], [1, 2])
    np.testing.assert_allclose(out["rating"], [4.0, 3.5])


def test_binary_is_keyed_on_source_hash(tmp_path, monkeypatch):
    """A binary is only loaded if it was built from the source beside
    it: the name carries the source's hash, so a library left over from
    another source (a copied tree, an older checkout) is never picked
    up by a coin-flip of copy times."""
    import hashlib

    with open(native._SRC, "rb") as f:
        src = f.read()
    digest = hashlib.sha256(src).hexdigest()[:16]
    assert os.path.basename(native._so_path()) == (
        f"libfps_loader-{digest}.so"
    )

    # another source -> another name, built on demand; a newer-looking
    # stale file under the old fixed name is ignored
    other = tmp_path / "fps_loader.cpp"
    other.write_bytes(src + b"\n// changed\n")
    build = tmp_path / "build"
    build.mkdir()
    (build / "libfps_loader.so").write_bytes(b"not a library")
    monkeypatch.setattr(native, "_SRC", str(other))
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    so = native._build()
    assert os.path.basename(so) != f"libfps_loader-{digest}.so"
    assert os.path.basename(so).startswith("libfps_loader-")
    assert os.path.getsize(so) > 1000
    assert native._build() == so  # second call: present, not rebuilt
    assert sorted(os.listdir(build)) == sorted(
        ["libfps_loader.so", os.path.basename(so)]
    )
