"""Socket text source + unbounded-record batcher (data/socket.py) —
the reference's ``socketTextStream`` ingestion edge, tested against a
real localhost TCP server and driven end-to-end into the compiled loop.
"""
import socket
import socketserver
import threading

import numpy as np
import pytest

from flink_parameter_server_tpu.data.socket import (
    batches_from_records,
    socket_text_stream,
)


class _OneShotServer(socketserver.TCPServer):
    allow_reuse_address = True


def _serve(payload: bytes):
    """Serve ``payload`` to the first client, then close.  Returns the
    bound port."""

    class H(socketserver.BaseRequestHandler):
        def handle(self):
            self.request.sendall(payload)

    srv = _OneShotServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.handle_request, daemon=True)
    t.start()
    return srv.server_address[1], srv


def test_socket_text_stream_lines_and_trailing_partial():
    port, srv = _serve(b"alpha\nbeta\ngamma")  # no trailing newline
    try:
        lines = list(socket_text_stream("127.0.0.1", port))
    finally:
        srv.server_close()
    assert lines == ["alpha", "beta", "gamma"]


def test_socket_text_stream_rejects_unbounded_line():
    port, srv = _serve(b"x" * 4096)  # no newline at all
    try:
        with pytest.raises(ValueError, match="newline"):
            list(socket_text_stream("127.0.0.1", port,
                                    max_line_bytes=1024))
    finally:
        srv.server_close()


def test_batches_from_records_pads_and_counts_drops():
    def parse(line):
        u, i, r = line.split(",")
        return {"user": np.int32(u), "item": np.int32(i),
                "rating": np.float32(r)}

    lines = ["1,2,0.5", "3,4,1.0", "garbage", "5,6,-0.5"]
    it = batches_from_records(iter(lines), 3, parse)
    batches = list(it)
    assert it.dropped == 1  # the garbage line was counted, not fatal
    (full,) = batches  # 3 valid records = exactly one full batch
    assert full["user"].tolist() == [1, 3, 5]
    assert full["rating"].dtype == np.float32
    assert full["mask"].all()


def test_batches_from_records_tail_mask():
    it = batches_from_records(
        iter(["7,8,0.25"]), 4,
        lambda ln: dict(zip(
            ("user", "item", "rating"),
            (np.int32(ln.split(",")[0]), np.int32(ln.split(",")[1]),
             np.float32(ln.split(",")[2])),
        )),
    )
    (b,) = list(it)
    assert b["mask"].tolist() == [True, False, False, False]
    assert b["user"][0] == 7 and b["user"][1] == 0  # zero-padded


def test_undecodable_bytes_drop_not_crash():
    """One corrupt byte mid-stream must not kill the job: the mangled
    line fails parse and lands in .dropped (docs/api.md contract)."""
    port, srv = _serve(b"1,2,0.5\n\xff\xfe,oops\n3,4,1.0\n")
    try:
        it = batches_from_records(
            socket_text_stream("127.0.0.1", port), 2,
            lambda ln: dict(zip(
                ("user", "item", "rating"),
                (np.int32(ln.split(",")[0]), np.int32(ln.split(",")[1]),
                 np.float32(ln.split(",")[2])),
            )),
        )
        (b,) = list(it)
    finally:
        srv.server_close()
    assert it.dropped == 1
    assert b["user"].tolist() == [1, 3]


def test_parse_reserved_mask_key_is_loud():
    it = batches_from_records(
        iter(["x"]), 1, lambda ln: {"mask": np.bool_(True)}
    )
    with pytest.raises(ValueError, match="reserved"):
        list(it)


def test_parse_reserved_mask_key_is_loud_on_any_row():
    """The reserved-name guard must fire per row, not just on rows[0]:
    a 'mask' appearing only mid-stream used to slip past the old
    rows[0]-only check (advisor finding, round 5)."""

    def parse(ln):
        if ln == "bad":
            return {"v": np.int32(0), "mask": np.bool_(True)}
        return {"v": np.int32(ln)}

    it = batches_from_records(iter(["1", "2", "bad"]), 8, parse)
    with pytest.raises(ValueError, match="reserved"):
        list(it)


def test_inconsistent_row_keys_drop_not_crash():
    """A parse() that returns different dict keys across records must
    not kill the unbounded job with a KeyError at stack time: rows
    whose key set differs from the first valid row's are counted as
    dropped (advisor finding, round 5)."""

    def parse(ln):
        if ln == "extra":
            return {"v": np.int32(7), "bonus": np.int32(1)}
        if ln == "missing":
            return {"w": np.int32(8)}
        return {"v": np.int32(ln)}

    records = ["1", "extra", "2", "missing", "3"]
    it = batches_from_records(iter(records), 2, parse)
    batches = list(it)
    assert it.dropped == 2  # 'extra' and 'missing', counted not fatal
    got = [
        int(v) for b in batches for v, m in zip(b["v"], b["mask"]) if m
    ]
    assert got == [1, 2, 3]  # the consistent rows all survived


def test_batcher_invariants_property():
    """Hypothesis: for ANY mix of valid/malformed records and any batch
    size — total masked-in lanes == valid records, .dropped == malformed
    records, every batch is exactly batch_size wide (static shapes),
    and record order/values survive."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(
        st.lists(
            st.one_of(
                st.integers(min_value=0, max_value=999),  # valid payload
                st.just(None),                            # malformed
            ),
            min_size=0, max_size=40,
        ),
        st.integers(min_value=1, max_value=9),
    )
    def prop(records, batch_size):
        def parse(rec):
            return {"v": np.int32(rec)}  # None -> TypeError -> dropped

        it = batches_from_records(iter(records), batch_size, parse)
        batches = list(it)
        valid = [r for r in records if r is not None]
        assert it.dropped == len(records) - len(valid)
        assert all(b["v"].shape == (batch_size,) for b in batches)
        assert sum(int(b["mask"].sum()) for b in batches) == len(valid)
        got = [
            int(v) for b in batches for v, m in zip(b["v"], b["mask"]) if m
        ]
        assert got == valid  # order and values survive the bridge

    prop()


def test_socket_stream_to_train_step_end_to_end():
    """Full edge: TCP lines -> parse -> microbatches -> jitted PS step.
    The padded tail's masked lanes (pad id 0) must not touch the table:
    row 0 stays at its zero init because every REAL record avoids it."""
    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.core.transform import transform_batched
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )

    rng = np.random.default_rng(0)
    n = 22  # deliberately not a multiple of the batch size (padded tail)
    payload = "".join(
        f"{rng.integers(0, 16)},{rng.integers(1, 32)},"  # items 1.. only
        f"{rng.normal():.4f}\n"
        for _ in range(n)
    ).encode()
    port, srv = _serve(payload)

    def parse(line):
        u, i, r = line.split(",")
        return {"user": np.int32(u), "item": np.int32(i),
                "rating": np.float32(r)}

    try:
        batches = batches_from_records(
            socket_text_stream("127.0.0.1", port), 8, parse
        )
        logic = OnlineMatrixFactorization(16, 4, updater=SGDUpdater(0.05))
        store = ShardedParamStore.create(32, (4,))  # zero-init table
        res = transform_batched(batches, logic, store, dump_model=False)
    finally:
        srv.server_close()
    assert len(res.worker_outputs) >= 3  # 22 records / 8 = 3 batches
    vals = np.asarray(res.store.values())
    assert np.isfinite(vals).all()
    # padding lanes carry item id 0 (pad_value) with mask False — a
    # mask leak would write row 0, which no real record targets
    np.testing.assert_array_equal(vals[0], np.zeros(4))
    assert np.abs(vals[1:]).sum() > 0  # real rows did train
