"""Native walcodec vs the pure-Python reference implementation:
byte-identical encode, identical scan semantics (torn tail, bit flip),
and the WAL/EngineWAL integration paths."""
import os
import struct
import zlib

import pytest

from etcd_tpu import native
from etcd_tpu.native import (_py_encode_records, _py_scan_records,
                             HAVE_NATIVE)

RECORDS = [(2, b"hello"), (3, b""), (2, b"x" * 10000), (7, bytes(range(256)))]


def test_python_roundtrip():
    buf, crc = _py_encode_records(RECORDS, 123)
    recs, crc2, consumed = _py_scan_records(buf, 123)
    assert recs == RECORDS
    assert crc2 == crc and consumed == len(buf)


@pytest.mark.skipif(not HAVE_NATIVE, reason="walcodec not built (./build)")
def test_native_matches_python_bytes():
    for seed in (0, 1, 0xDEADBEEF):
        py_buf, py_crc = _py_encode_records(RECORDS, seed)
        c_buf, c_crc = native.encode_records(RECORDS, seed)
        assert c_buf == py_buf
        assert c_crc == py_crc


@pytest.mark.skipif(not HAVE_NATIVE, reason="walcodec not built (./build)")
def test_native_scan_matches_python():
    buf, _ = _py_encode_records(RECORDS, 5)
    for data in (buf,
                 buf[:-3],                       # torn tail
                 buf[:20] + b"\xff" + buf[21:],  # bit flip mid-record
                 b""):
        py = _py_scan_records(data, 5)
        cc = native.scan_records(data, 5)
        assert cc == py, (len(data), py, cc)


def test_scan_stops_at_flip_keeps_prefix():
    buf, _ = _py_encode_records(RECORDS, 9)
    # flip a byte inside the THIRD record's payload
    off = sum(16 + len(p) for _, p in RECORDS[:2]) + 20
    bad = buf[:off] + bytes([buf[off] ^ 0xFF]) + buf[off + 1:]
    recs, _, consumed = native.scan_records(bad, 9)
    assert recs == RECORDS[:2]
    assert consumed == sum(16 + len(p) for _, p in RECORDS[:2])


def test_enginewal_replay_uses_codec(tmp_path):
    from etcd_tpu.server.enginewal import EngineWAL, RoundRecord
    w = EngineWAL(str(tmp_path / "w"), fsync=False)
    for i in range(5):
        rec = RoundRecord(round_no=i, entries=[(0, i + 1, 1, b"payload%d" % i)])
        w.append(rec)
    w.close()
    w2 = EngineWAL(str(tmp_path / "w"), fsync=False)
    got = list(w2.replay())
    assert [r.round_no for r in got] == list(range(5))
    assert got[3].entries == [(0, 4, 1, b"payload3")]
    # torn tail: truncate mid-record
    seg = [n for n in os.listdir(tmp_path / "w") if n.endswith(".wal")][0]
    p = tmp_path / "w" / seg
    p.write_bytes(p.read_bytes()[:-7])
    w3 = EngineWAL(str(tmp_path / "w"), fsync=False)
    got = list(w3.replay())
    assert [r.round_no for r in got] == list(range(4))


def test_pack_multi_byte_identical():
    """walcodec.pack_multi must produce exactly the Python reference
    packing of server/engine._pack_entry's multi branch — WAL payloads
    are replayed byte-for-byte and CRC-chained."""
    import struct

    from etcd_tpu.native.walcodec import pack_multi
    from etcd_tpu.server.engine import P_MULTI

    def py_pack(items):
        out = [bytes([P_MULTI]), struct.pack("<I", len(items))]
        for it in items:
            blob = it[1][1:]
            out.append(struct.pack("<I", len(blob)))
            out.append(blob)
        return b"".join(out)

    cases = [
        [(1, b"\x00" + b'{"id":1}')],
        [(1, b"\x00" + b'{"id":1}'), (2, b"\x00" + b'{"id":2,"v":"x"}')],
        [(i, b"\x00" + bytes([65 + (i % 26)]) * (i % 300 + 1), None)
         for i in range(512)],
        [(7, b"\x01")],                   # empty body after the tag
    ]
    for items in cases:
        assert pack_multi(items, P_MULTI) == py_pack(items)

    # And against the ACTUAL shipping fallback (not the copy above): a
    # framing change to engine._pack_entry must fail here, or built and
    # un-built trees would write divergent WAL entries.
    import etcd_tpu.server.engine as engine_mod
    saved = engine_mod._c_pack_multi
    try:
        engine_mod._c_pack_multi = None
        for items in cases:
            if len(items) > 1:
                assert engine_mod._pack_entry(items) == \
                    pack_multi(items, P_MULTI)
    finally:
        engine_mod._c_pack_multi = saved

    import pytest
    with pytest.raises(TypeError):
        pack_multi([(1, "not-bytes")], P_MULTI)
    with pytest.raises(TypeError):
        pack_multi([(1, b"")], P_MULTI)   # payload must carry a tag byte
    with pytest.raises(TypeError):
        pack_multi([1], P_MULTI)


# ---------------------------------------------------------------------------
# ingresscore: the ingress tier's HTTP scan/format hot loop
# ---------------------------------------------------------------------------

_SCAN_CASES = [
    b"",
    b"GET /health HTTP/1.1\r\n\r\n",
    (b"PUT /tenants/1/v2/keys/a?x=1 HTTP/1.1\r\n"
     b"Content-Length: 5\r\n"
     b"Content-Type: application/x-www-form-urlencoded\r\n"
     b"Authorization: Basic abc=\r\nConnection: close\r\n\r\nvalue"),
    # second request's body incomplete: only the first is emitted
    b"PUT /a HTTP/1.1\r\nContent-Length: 5\r\n\r\nvalueP"
    b"UT /b HTTP/1.1\r\nContent-Length: 5\r\n\r\nva",
    # two complete pipelined requests, case-insensitive close
    b"GET /a HTTP/1.1\r\n\r\nGET /b HTTP/1.1\r\nconnection: CLOSE\r\n\r\n",
    b"BADLINE\r\n\r\n",                                  # err: request line
    b"GET /a HTTP/1.1\r\nContent-Length: zz\r\n\r\n",    # err: bad length
    b"GET /a HTTP/1.1\r\nContent-Length: 99999999999\r\n\r\n",  # err: body
    b"GET /a HTTP/1.1\r\nContent-Length:\r\n\r\n",       # empty reads as 0
    b"X" * (65 * 1024),                                  # err: headers cap
    b"GET /a HTTP/1.1\r\nNo-colon-line junk\r\nAuthorization:  pad  \r\n\r\n",
]


def test_py_scan_requests_semantics():
    from etcd_tpu.native import (ING_EBADLINE, ING_OK, _py_scan_requests)
    reqs, consumed, err = _py_scan_requests(_SCAN_CASES[2])
    assert err == ING_OK and consumed == len(_SCAN_CASES[2])
    m, t, ctype, auth, close, body = reqs[0]
    assert (m, t) == ("PUT", "/tenants/1/v2/keys/a?x=1")
    assert ctype.startswith("application/x-www-form")
    assert auth == "Basic abc=" and close and body == b"value"
    # a bad request line consumes nothing past the last good request
    reqs, consumed, err = _py_scan_requests(_SCAN_CASES[5])
    assert err == ING_EBADLINE and reqs == [] and consumed == 0


@pytest.mark.skipif(not native.HAVE_NATIVE_INGRESS,
                    reason="ingresscore not built (./build)")
def test_native_scan_requests_matches_python():
    from etcd_tpu.native import _c_scan_requests, _py_scan_requests
    for case in _SCAN_CASES:
        assert _c_scan_requests(bytes(case)) == _py_scan_requests(case), case
    # bytearray input (the live rbuf shape) via the wrapper
    got = native.scan_requests(bytearray(_SCAN_CASES[4]))
    assert got == _py_scan_requests(_SCAN_CASES[4])


@pytest.mark.skipif(not native.HAVE_NATIVE_INGRESS,
                    reason="ingresscore not built (./build)")
def test_native_format_responses_matches_python():
    from etcd_tpu.native import _c_format_responses, _py_format_responses
    items = [(200, b'{"ok":1}\n'), (201, b""), (503, b"{}"),
             (412, b"precondition"), (777, b"unknown-status")]
    c = _c_format_responses(items)
    assert c == _py_format_responses(items)
    # parseable by the stdlib's strict parser
    import io
    from http.client import HTTPResponse

    class _FakeSock:
        def __init__(self, data):
            self._f = io.BytesIO(data)

        def makefile(self, *a, **k):
            return self._f

    r = HTTPResponse(_FakeSock(c[0]))  # type: ignore[arg-type]
    r.begin()
    assert r.status == 200 and r.read() == b'{"ok":1}\n'
    with pytest.raises(TypeError):
        _c_format_responses([(200, "not-bytes")])
    with pytest.raises(TypeError):
        _c_format_responses([200])


# -- frontcore: the HTTP front's batched socket calls -------------------------


def _socketpairs(n):
    import socket
    pairs = [socket.socketpair() for _ in range(n)]
    for a, b in pairs:
        a.setblocking(False)
        b.setblocking(False)
    return pairs


@pytest.mark.parametrize("impl", ["native", "python"])
def test_front_recv_many_and_send_many(impl):
    """One call, one result per descriptor: bytes (b"" at end of file) or
    -errno from recv_many, bytes taken or -errno from send_many; the C
    module and the os.read / os.write fallback give the same lists."""
    import errno
    if impl == "native":
        if not native.HAVE_NATIVE_FRONT:
            pytest.skip("frontcore not built")
        recv_many, send_many = native.recv_many, native.send_many
    else:
        recv_many, send_many = native._py_recv_many, native._py_send_many
    assert recv_many([], 4096) == [] and send_many([]) == []
    pairs = _socketpairs(4)
    try:
        (a0, b0), (a1, b1), (a2, b2), (a3, b3) = pairs
        # send: bytes and a bytearray (the front's wbuf), whole or in part
        big = bytearray(b"z" * (8 << 20))
        sent = send_many([(a0.fileno(), b"hello"),
                          (a1.fileno(), bytearray(b"wbuf")),
                          (a2.fileno(), big)])
        assert sent[:2] == [5, 4] and 0 < sent[2] < len(big)
        assert len(big) == 8 << 20      # the buffer is released, unchanged
        del big[:sent[2]]               # and can be resized again
        # a full socket takes nothing more: -EAGAIN, not an exception
        assert send_many([(a2.fileno(), big)])[0] in (
            -errno.EAGAIN, -errno.EWOULDBLOCK)
        a3.close()                      # b3 sees end of file
        got = recv_many([b0.fileno(), b1.fileno(), b3.fileno()], 16384)
        assert got == [b"hello", b"wbuf", b""]
        # nothing left to read: -EAGAIN; the read is capped by bufsize
        assert recv_many([b0.fileno()], 16384) == [-errno.EAGAIN]
        assert recv_many([b2.fileno()], 1000) == [b"z" * 1000]
        # writing to a peer that is gone is an errno, never a signal
        assert send_many([(b3.fileno(), b"late")]) == [-errno.EPIPE]
        with pytest.raises((TypeError, ValueError)):
            send_many([(a0.fileno(), "text")])
    finally:
        for a, b in pairs:
            a.close()
            b.close()


@pytest.mark.skipif(not native.HAVE_NATIVE_FRONT,
                    reason="frontcore not built")
def test_front_recv_many_shares_its_scratch_between_many_descriptors():
    """More descriptors than 4 MiB / bufsize: each read is capped lower
    and no byte is lost, the rest is there for the next call."""
    pairs = _socketpairs(100)
    try:
        for i, (a, _b) in enumerate(pairs):
            a.send(bytes([i]) * 60000)
        fds = [b.fileno() for _a, b in pairs]
        got = native.recv_many(fds, 65536)      # 100 x 64 KiB > 4 MiB
        assert all(0 < len(g) <= (4 << 20) // 100 for g in got)
        total = [len(g) for g in got]
        for _ in range(8):
            more = native.recv_many(fds, 65536)
            total = [t + (len(m) if isinstance(m, bytes) else 0)
                     for t, m in zip(total, more)]
        assert total == [60000] * 100
        assert all(set(g) == {i} for i, g in enumerate(got))
    finally:
        for a, b in pairs:
            a.close()
            b.close()


@pytest.mark.parametrize("impl", ["native", "python"])
def test_front_recv_many_takes_any_number_of_descriptors(impl):
    """More descriptors than share the scratch at 4 KiB each (1024): the
    call works them off in groups, one result per descriptor in order,
    and never refuses."""
    import errno
    if impl == "native":
        if not native.HAVE_NATIVE_FRONT:
            pytest.skip("frontcore not built")
        recv_many = native.recv_many
    else:
        recv_many = native._py_recv_many
    n = 2500
    a, b = _socketpairs(1)[0]
    c, d = _socketpairs(1)[0]
    try:
        a.send(b"x" * 100)              # b has 100 bytes, d has none
        fds = [b.fileno() if i % 1000 == 7 else d.fileno()
               for i in range(n)]
        got = recv_many(fds, 10)
        assert len(got) == n
        # b was read 10 bytes at a time, at 7, 1007 and 2007, in order
        assert [i for i, g in enumerate(got) if g == b"x" * 10] == [
            7, 1007, 2007]
        assert all(g == -errno.EAGAIN for i, g in enumerate(got)
                   if i % 1000 != 7)
        assert recv_many([b.fileno()], 4096) == [b"x" * 70]
    finally:
        for s in (a, b, c, d):
            s.close()
