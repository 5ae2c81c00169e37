"""The HTTP front's event loop (etcdhttp/web.py), against a real HttpServer
over sockets: the parser's corners (keep-alive, close, pipelining, HEAD,
100-continue, split segments, the stdlib handler's limits), one thread for
any number of request/response connections, the thread path for what may
block (watches, streams, hijacks) and the socket's way back, stop(), and
one wake per batch of completions.

The server behind the routes is a stub with a real Store: its `submit_pairs`
parks requests until the test releases them, so a test decides what is in
flight. The engine's own half (MultiEngine.submit_pairs) is pinned in
tests/test_engine.py and tests/test_read_plane.py.
"""
import itertools
import json
import os
import socket
import threading
import time
from types import SimpleNamespace

import pytest

from etcd_tpu import errors
from etcd_tpu.etcdhttp.client import ClientAPI
from etcd_tpu.etcdhttp.web import REPLIED, HttpServer, Router
from etcd_tpu.server import obs
from etcd_tpu.server.request import Request
from etcd_tpu.store import new_store
from etcd_tpu.utils.wait import Wait


class StubServer:
    """What ClientAPI drives, with a non-blocking submit the test
    releases by hand (hold=True) or that completes at once."""

    def __init__(self, hold=False, request_timeout=5.0):
        self.store = new_store(namespaces=("/0", "/1"))
        self.cluster = SimpleNamespace(cluster_id=7)
        self.clock = time.time
        self.stopped = False
        self.commit_index = 0
        self.term = 1
        self.request_timeout = request_timeout
        self.submitter = self
        self.hold = hold
        self.wait = Wait()
        self.pending = []
        self.submits = []           # items per submit call
        self.expired = []
        self._ids = itertools.count(1)
        self.new_id = itertools.count(1).__next__

    def submit_item(self, r):
        return r

    def _apply(self, r: Request):
        try:
            if r.method == "GET":
                return self.store.get(r.path, r.recursive, r.sorted)
            if r.method == "DELETE":
                return self.store.delete(r.path, is_dir=r.dir,
                                         recursive=r.recursive)
            return self.store.set(r.path, is_dir=r.dir, value=r.val,
                                  expire_time=r.expiration)
        except errors.EtcdError as e:
            return e

    def do(self, r: Request):           # the blocking path
        if r.method == "GET" and r.wait:
            return self.store.watch(r.path, r.recursive, r.stream, r.since)
        res = self._apply(r)
        if isinstance(res, errors.EtcdError):
            raise res
        return res

    # -- web.LoopOp's submitter -------------------------------------------

    def submit_pairs(self, items, sink):
        tokens = []
        for r in items:
            if r.path.endswith("/refused"):     # this one alone
                tokens.append(errors.EtcdError(errors.ECODE_INVALID_FORM,
                                               cause="refused"))
                continue
            rid = next(self._ids)
            self.wait.register(rid, sink)
            self.pending.append((rid, r))
            tokens.append(SimpleNamespace(rid=rid))
        self.submits.append(len(items))     # counted once they are pending
        if not self.hold:
            self.release()
        return tokens

    def release(self):
        pending, self.pending = self.pending, []
        self.wait.trigger_many([(rid, self._apply(r))
                                for rid, r in pending])

    def settle(self, toks, values):
        return values

    def expire(self, tok):
        self.wait.cancel(tok.rid)
        self.expired.append(tok.rid)
        return errors.EtcdError(errors.ECODE_RAFT_INTERNAL,
                                cause="request timed out")


def _serve(stub, extra=None):
    router = Router()
    api = ClientAPI(stub)
    router.add("/v2/keys", api.handle_keys, begin=api.begin_keys)
    for prefix, fn, begin in extra or ():
        router.add(prefix, fn, begin=begin)
    http = HttpServer("127.0.0.1", 0, router)
    http.start()
    return http


@pytest.fixture
def front():
    made = []

    def make(stub, extra=None):
        http = _serve(stub, extra)
        made.append(http)
        return http

    yield make
    for http in made:
        http.stop()


def _connect(http, timeout=10.0):
    s = socket.create_connection(("127.0.0.1", http.port), timeout=timeout)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def _put(key, value, extra=""):
    body = f"value={value}"
    return (f"PUT /v2/keys/{key} HTTP/1.1\r\nHost: t\r\n{extra}"
            "Content-Type: application/x-www-form-urlencoded\r\n"
            f"Content-Length: {len(body)}\r\n\r\n{body}").encode()


_unread = {}            # socket -> bytes received past the reply just read


def _read_reply(s, head_only=False):
    """One reply off a socket: (status, {header: value}, body)."""
    buf = _unread.pop(s, b"")
    while b"\r\n\r\n" not in buf:
        data = s.recv(65536)
        if not data:
            raise EOFError(buf)
        buf += data
    head, _, rest = buf.partition(b"\r\n\r\n")
    lines = head.decode("iso-8859-1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = dict(ln.split(": ", 1) for ln in lines[1:])
    n = (0 if head_only or status == 100
         else int(headers.get("Content-Length", 0)))
    while len(rest) < n:
        data = s.recv(65536)
        if not data:
            raise EOFError(rest)
        rest += data
    if rest[n:]:
        _unread[s] = rest[n:]
    return status, headers, rest[:n]


def _closed(s, timeout=5.0):
    s.settimeout(timeout)
    try:
        return s.recv(1) == b""
    except (ConnectionResetError, BrokenPipeError):
        return True


def _wait_for(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def test_keep_alive_reuse_and_connection_close(front):
    stub = StubServer()
    http = front(stub)
    s = _connect(http)
    for i in range(3):
        s.sendall(_put("a", i))
        status, headers, body = _read_reply(s)
        assert status == (201 if i == 0 else 200)
        assert json.loads(body)["node"]["value"] == str(i)
        assert headers["X-Etcd-Cluster-ID"] == "7"
        assert "Connection" not in headers
    s.sendall(_put("a", "last", extra="Connection: close\r\n"))
    status, headers, _ = _read_reply(s)
    assert status == 200 and headers["Connection"] == "close"
    assert _closed(s)
    # HTTP/1.0 closes unless it asks for keep-alive
    s = _connect(http)
    s.sendall(b"GET /v2/keys/a?quorum=true HTTP/1.0\r\n\r\n")
    status, headers, body = _read_reply(s)
    assert status == 200 and json.loads(body)["node"]["value"] == "last"
    assert _closed(s)
    s = _connect(http)
    s.sendall(b"GET /v2/keys/a?quorum=true HTTP/1.0\r\n"
              b"Connection: keep-alive\r\n\r\n")
    assert _read_reply(s)[0] == 200
    s.sendall(b"GET /v2/keys/a?quorum=true HTTP/1.0\r\n\r\n")
    assert _read_reply(s)[0] == 200
    assert _closed(s)


def test_pipelined_requests_are_answered_in_order_one_at_a_time(front):
    stub = StubServer(hold=True)
    http = front(stub)
    s = _connect(http)
    s.sendall(_put("p", 1) + _put("p", 2))
    assert _wait_for(lambda: stub.submits == [1])
    time.sleep(0.1)
    # the second is not parsed, let alone submitted, until the first is
    # answered
    assert stub.submits == [1] and len(stub.pending) == 1
    stub.release()
    status, _, body = _read_reply(s)
    assert status == 201 and json.loads(body)["node"]["value"] == "1"
    assert _wait_for(lambda: stub.submits == [1, 1])
    stub.release()
    status, _, body = _read_reply(s)
    assert status == 200 and json.loads(body)["node"]["value"] == "2"
    assert json.loads(body)["prevNode"]["value"] == "1"


def test_expect_100_continue(front):
    http = front(StubServer())
    s = _connect(http)
    body = b"value=big"
    s.sendall(b"PUT /v2/keys/e HTTP/1.1\r\nHost: t\r\n"
              b"Content-Type: application/x-www-form-urlencoded\r\n"
              b"Expect: 100-continue\r\n"
              b"Content-Length: %d\r\n\r\n" % len(body))
    status, _, rest = _read_reply(s)
    assert status == 100 and rest == b""
    s.sendall(body)
    status, _, reply = _read_reply(s)
    assert status == 201 and json.loads(reply)["node"]["value"] == "big"


def test_head_has_the_length_and_no_body(front):
    http = front(StubServer())
    s = _connect(http)
    s.sendall(_put("h", "v"))
    assert _read_reply(s)[0] == 201
    for target in ("/v2/keys/h?quorum=true",      # submitted
                   "/v2/keys/h",                  # served from the store
                   "/v2/keys/h?wait=true&waitIndex=1"):     # on a thread
        s.sendall(f"GET {target} HTTP/1.1\r\n\r\n".encode())
        _, headers, body = _read_reply(s)
        s.sendall(f"HEAD {target} HTTP/1.1\r\n\r\n".encode())
        status, h_headers, rest = _read_reply(s, head_only=True)
        assert status == 200 and rest == b""
        assert h_headers["Content-Length"] == headers["Content-Length"]
        assert int(headers["Content-Length"]) == len(body) > 0
    # nothing of a body is left on the wire: the next reply parses
    s.sendall(b"GET /v2/keys/h?quorum=true HTTP/1.1\r\n\r\n")
    assert _read_reply(s)[0] == 200


def test_header_byte_by_byte_and_body_in_segments(front):
    http = front(StubServer())
    s = _connect(http)
    req = _put("split", "x" * 3000)
    head, _, body = req.partition(b"\r\n\r\n")
    for b in head + b"\r\n\r\n":
        s.sendall(bytes([b]))
        time.sleep(0.0005)
    for i in range(0, len(body), 1000):
        time.sleep(0.02)
        s.sendall(body[i:i + 1000])
    status, _, reply = _read_reply(s)
    assert status == 201
    assert json.loads(reply)["node"]["value"] == "x" * 3000
    # bare LF line ends, as the stdlib handler took them
    s.sendall(b"GET /v2/keys/split?quorum=true HTTP/1.1\nHost: t\n\n")
    assert _read_reply(s)[0] == 200


@pytest.mark.parametrize("request_bytes,status", [
    (b"GET /" + b"a" * 70000 + b" HTTP/1.1\r\n\r\n", 414),
    (b"GET /" + b"a" * 70000, 414),                    # never finished
    (b"GET /v2/keys/a HTTP/1.1\r\n"
     + b"".join(b"X-H%d: v\r\n" % i for i in range(101)) + b"\r\n", 431),
    (b"GET /v2/keys/a HTTP/1.1\r\nX-Long: " + b"v" * 70000 + b"\r\n\r\n",
     431),
    (b"GARBAGE\r\n\r\n", 400),
    (b"GET /v2/keys/a HTTP/1.1\r\nContent-Length: nope\r\n\r\n", 400),
    (b"GET /v2/keys/a HTTP/3.0\r\n\r\n", 505),
], ids=["414", "414-partial", "431-count", "431-line", "400-syntax",
        "400-length", "505"])
def test_limits_are_answered_and_the_connection_closed(front, request_bytes,
                                                        status):
    http = front(StubServer())
    s = _connect(http)
    s.sendall(request_bytes)
    got, headers, _ = _read_reply(s)
    assert got == status and headers["Connection"] == "close"
    assert _closed(s)
    # the listener is none the worse
    s = _connect(http)
    s.sendall(_put("ok", 1))
    assert _read_reply(s)[0] == 201


def test_a_client_that_stops_reading_stalls_nobody(front):
    big = b"x" * (12 << 20)

    def blob(ctx, suffix):
        ctx.send(200, big)
        return REPLIED

    stub = StubServer()
    http = front(stub, extra=[("/blob", blob, blob)])
    deaf = _connect(http)
    deaf.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 18)
    deaf.sendall(b"GET /blob HTTP/1.1\r\n\r\n" + _put("later", 1))
    time.sleep(0.2)                     # 12 MB cannot fit the socket
    t0 = time.time()
    for i in range(20):
        s = _connect(http)
        s.sendall(_put(f"k{i}", i))
        assert _read_reply(s)[0] == 201
        s.close()
    assert time.time() - t0 < 5.0
    # the deaf client's next request waits behind its unread reply
    assert ("/1/later" not in
            [n.key for n in stub.store.get("/1", True, False).node.nodes])
    status, headers, body = _read_reply(deaf)       # now it reads
    assert status == 200 and body == big
    assert _read_reply(deaf)[0] == 201


def test_300_keep_alive_connections_on_one_thread(front):
    stub = StubServer(hold=True, request_timeout=60.0)
    http = front(stub)
    before = threading.active_count()
    socks = [_connect(http, timeout=30.0) for _ in range(300)]
    for i, s in enumerate(socks):
        s.sendall(_put(f"c{i}", i))
    assert _wait_for(lambda: sum(stub.submits) == 300, timeout=20.0)
    # 300 requests in flight: one loop thread, no thread per connection
    # (counted against what this process ran before: a test worker may
    # carry other files' daemon threads)
    assert threading.active_count() <= before + 1
    assert threading.active_count() - before + 2 < 20   # main + the loop
    stub.release()
    for s in socks:
        assert _read_reply(s)[0] == 201
    stub.hold = False
    for rnd in range(3):                 # and again over the same sockets
        for i, s in enumerate(socks):
            s.sendall(b"GET /v2/keys/c%d?quorum=true HTTP/1.1\r\n\r\n" % i)
        for i, s in enumerate(socks):
            status, _, body = _read_reply(s)
            assert status == 200
            assert json.loads(body)["node"]["value"] == str(i)
        assert threading.active_count() <= before + 1
    # far fewer submit calls than requests: a select pass is one submit
    assert len(stub.submits) < sum(stub.submits)
    for s in socks:
        s.close()


def test_long_poll_watch_holds_a_thread_and_hands_the_socket_back(front):
    stub = StubServer()
    http = front(stub)
    w = _connect(http)
    w.sendall(b"GET /v2/keys/w?wait=true HTTP/1.1\r\n\r\n")
    time.sleep(0.2)
    s = _connect(http)
    s.sendall(_put("w", "fired"))
    assert _read_reply(s)[0] == 201
    status, _, body = _read_reply(w)
    assert status == 200 and json.loads(body)["node"]["value"] == "fired"
    # the watch's socket is back on the loop and serves on
    w.sendall(_put("w", "again"))
    status, _, body = _read_reply(w)
    assert status == 200 and json.loads(body)["node"]["value"] == "again"
    # a watcher whose client goes away is released
    gone = _connect(http)
    gone.sendall(b"GET /v2/keys/never?wait=true HTTP/1.1\r\n\r\n")
    assert _wait_for(lambda: stub.store.watcher_hub.count == 1)
    gone.close()
    assert _wait_for(lambda: stub.store.watcher_hub.count == 0, timeout=10)


def test_stream_watch_is_chunked_and_closes_the_connection(front):
    stub = StubServer()
    http = front(stub)
    w = _connect(http)
    w.sendall(b"GET /v2/keys/s?wait=true&stream=true HTTP/1.1\r\n\r\n")
    buf = b""
    while b"\r\n\r\n" not in buf:
        buf += w.recv(65536)
    head, _, rest = buf.partition(b"\r\n\r\n")
    assert b"200 OK" in head and b"Transfer-Encoding: chunked" in head
    s = _connect(http)
    for v in ("one", "two"):
        s.sendall(_put("s", v))
        assert _read_reply(s)[0] in (200, 201)
    deadline = time.time() + 10
    while rest.count(b'"value"') < 2 and time.time() < deadline:
        rest += w.recv(65536)
    size, _, chunk = rest.partition(b"\r\n")
    assert json.loads(chunk[:int(size, 16)])["node"]["value"] == "one"
    w.close()
    assert _wait_for(lambda: stub.store.watcher_hub.count == 0, timeout=10)


def test_hijack_gets_the_bytes_read_past_the_request_and_is_closed(front):
    def upgrade(ctx, suffix):
        rfile, wfile = ctx.hijack()
        wfile.write(b"HTTP/1.1 101 Switching Protocols\r\n\r\n")
        wfile.flush()
        while True:
            frame = rfile.read(4)
            if len(frame) < 4:
                return
            wfile.write(frame.upper())

    http = front(StubServer(), extra=[("/up", upgrade, None)])
    s = _connect(http)
    # the first frames ride the same segment as the request
    s.sendall(b"POST /up HTTP/1.1\r\nContent-Length: 2\r\n\r\nhiabcdefgh")
    got = b""
    while not got.endswith(b"ABCDEFGH"):
        got += s.recv(65536)
    assert got.startswith(b"HTTP/1.1 101")
    s.sendall(b"ijkl")
    assert s.recv(4) == b"IJKL"
    s.shutdown(socket.SHUT_WR)
    assert _closed(s)                   # never re-parsed as HTTP


def test_stop_severs_idle_in_flight_and_lent_connections(front):
    stub = StubServer(hold=True)
    http = front(stub)
    idle = _connect(http)
    idle.sendall(b"GET /v2/keys/?quorum=true HTTP/1.1\r\n\r\n")
    assert _wait_for(lambda: stub.submits == [1])
    stub.release()
    assert _read_reply(idle)[0] == 200
    flying = _connect(http)
    flying.sendall(_put("f", 1))
    watching = _connect(http)
    watching.sendall(b"GET /v2/keys/x?wait=true HTTP/1.1\r\n\r\n")
    assert _wait_for(lambda: sum(stub.submits) == 2
                     and stub.store.watcher_hub.count == 1)
    http.stop()
    for s in (idle, flying, watching):
        assert _closed(s)
    assert len(stub.expired) == 1       # the in-flight one was called off
    with pytest.raises(OSError):
        _connect(http, timeout=1.0)


def test_one_wake_drains_a_hundred_completions(front):
    stub = StubServer(hold=True)
    http = front(stub)
    socks = [_connect(http) for _ in range(100)]
    for i, s in enumerate(socks):
        s.sendall(_put(f"w{i}", i))
    assert _wait_for(lambda: sum(stub.submits) == 100)
    wakes = obs.http_front_wakes.value
    done = obs.http_front_completions.value
    stub.release()                      # one ack batch
    for s in socks:
        assert _read_reply(s)[0] == 201
    assert obs.http_front_completions.value - done == 100
    assert obs.http_front_wakes.value - wakes == 1


def test_the_sweep_answers_what_outlived_its_time_out(front):
    stub = StubServer(hold=True, request_timeout=0.3)
    http = front(stub)
    s = _connect(http)
    t0 = time.time()
    s.sendall(_put("slow", 1))
    status, _, body = _read_reply(s)
    assert 0.3 <= time.time() - t0 < 3.0
    err = json.loads(body)
    assert status == 500 and err["errorCode"] == errors.ECODE_RAFT_INTERNAL
    assert err["cause"] == "request timed out"
    assert len(stub.expired) == 1
    stub.release()                      # too late: nobody is registered
    s.sendall(b"GET /v2/keys/slow?quorum=true HTTP/1.1\r\n\r\n")
    assert _wait_for(lambda: len(stub.pending) == 1)
    stub.release()
    assert _read_reply(s)[0] == 200     # the write did apply; no reply twice


def test_served_counters_tell_loop_from_thread(front):
    http = front(StubServer())

    def count():
        return {tuple(sorted(lbl.items())): v
                for _, lbl, v in obs.http_front_served.samples()}

    a = count()
    s = _connect(http)
    s.sendall(_put("m", 1))                                   # loop
    assert _read_reply(s)[0] == 201
    s.sendall(b"GET /v2/keys/m?quorum=true HTTP/1.1\r\n\r\n")  # loop
    assert _read_reply(s)[0] == 200
    s.sendall(b"GET /v2/keys/m HTTP/1.1\r\n\r\n")      # loop (local read)
    assert _read_reply(s)[0] == 200
    s.sendall(b"GET /v2/keys/m?wait=true&waitIndex=1 HTTP/1.1\r\n\r\n")
    assert _read_reply(s)[0] == 200                        # thread
    s.sendall(b"GET /nowhere HTTP/1.1\r\n\r\n")                # loop (404)
    assert _read_reply(s)[0] == 404
    assert _wait_for(lambda: count()[(("path", "thread"),)]
                     - a.get((("path", "thread"),), 0) == 1)
    # (a reply can be read before the loop has counted it)
    assert _wait_for(lambda: count()[(("path", "loop"),)]
                     - a.get((("path", "loop"),), 0) == 4)


def test_local_reads_are_served_on_the_loop_without_a_submit(front):
    """A GET with neither quorum nor wait waits for nothing: the loop
    answers it from the store, no worker thread and no submit, with the
    reply a blocking handle_keys writes (errors too)."""
    stub = StubServer()
    http = front(stub)
    s = _connect(http)
    s.sendall(_put("dir/a", 1) + _put("dir/b", 2))
    assert _read_reply(s)[0] == 201 and _read_reply(s)[0] == 201
    submits, before = list(stub.submits), threading.active_count()
    for _ in range(50):
        s.sendall(b"GET /v2/keys/dir?recursive=true&sorted=true "
                  b"HTTP/1.1\r\n\r\n")
        status, headers, body = _read_reply(s)
        assert status == 200 and headers["X-Etcd-Index"] == "2"
        assert [n["value"] for n in json.loads(body)["node"]["nodes"]] == [
            "1", "2"]
    s.sendall(b"GET /v2/keys/none HTTP/1.1\r\n\r\n")
    status, _, body = _read_reply(s)
    assert status == 404
    assert json.loads(body)["errorCode"] == errors.ECODE_KEY_NOT_FOUND
    assert stub.submits == submits
    assert threading.active_count() == before


def test_one_refused_request_is_answered_alone(front):
    """A request its submitter refuses gets its error; the other requests
    of the same select pass (other tenants', in the engine) are staged
    and answered as ever."""
    stub = StubServer(hold=True)
    http = front(stub)
    socks = [_connect(http) for _ in range(8)]
    for i, s in enumerate(socks):
        s.sendall(_put("refused" if i == 3 else f"r{i}", i))
    status, _, body = _read_reply(socks[3])
    assert status == 400 and json.loads(body)["cause"] == "refused"
    assert _wait_for(lambda: sum(stub.submits) == 8)
    assert len(stub.pending) == 7
    stub.release()
    for i, s in enumerate(socks):
        if i != 3:
            assert _read_reply(s)[0] == 201
    socks[3].sendall(_put("fine", 1))           # its connection serves on
    assert _wait_for(lambda: len(stub.pending) == 1)
    stub.release()
    assert _read_reply(socks[3])[0] == 201


@pytest.mark.parametrize("impl", ["native", "python"])
def test_more_than_1024_connections_readable_in_one_pass(front, impl,
                                                         monkeypatch):
    """However many connections a pass finds readable, they are read and
    served (recv_many's scratch holds 1024 at 4 KiB each): 1,100
    keep-alive connections send while the loop is held up, with the C
    module and with its fallback."""
    import resource

    from etcd_tpu import native
    from etcd_tpu.etcdhttp import web

    n = 1100
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard != resource.RLIM_INFINITY and hard < 3 * n:
        pytest.skip("descriptor limit too low")
    if soft != resource.RLIM_INFINITY and soft < 3 * n:
        resource.setrlimit(resource.RLIMIT_NOFILE, (3 * n, hard))
    if impl == "native":
        if not native.HAVE_NATIVE_FRONT:
            pytest.skip("frontcore not built")
        assert web.recv_many is native.recv_many
    else:
        monkeypatch.setattr(web, "recv_many", native._py_recv_many)
        monkeypatch.setattr(web, "send_many", native._py_send_many)
    held = threading.Event()

    def hold_up(ctx, suffix):           # a begin that (wrongly) blocks
        held.set()
        time.sleep(1.0)
        ctx.send(200, b"ok")
        return REPLIED

    stub = StubServer()
    http = front(stub, extra=[("/hold", hold_up, hold_up)])
    socks = [_connect(http, timeout=30.0) for _ in range(n)]
    try:
        for i, s in enumerate(socks):   # all accepted, all served once
            s.sendall(_put(f"n{i}", 0))
        for s in socks:
            assert _read_reply(s)[0] == 201
        holder = _connect(http)
        holder.sendall(b"GET /hold HTTP/1.1\r\n\r\n")
        assert held.wait(10)
        for i, s in enumerate(socks):   # readable together, next pass
            s.sendall(_put(f"n{i}", i))
        assert _read_reply(holder)[0] == 200
        for i, s in enumerate(socks):
            status, _, body = _read_reply(s)
            assert status == 200
            assert json.loads(body)["node"]["value"] == str(i)
        assert max(stub.submits) > 1024     # they did share one pass
        s = _connect(http)                  # and the listener lives
        s.sendall(_put("after", 1))
        assert _read_reply(s)[0] == 201
    finally:
        for s in socks:
            s.close()
        if soft != resource.getrlimit(resource.RLIMIT_NOFILE)[0]:
            resource.setrlimit(resource.RLIMIT_NOFILE, (soft, hard))


def test_a_batch_call_that_raises_does_not_take_the_loop_down(
        front, monkeypatch, caplog):
    """recv_many / send_many raising (not a per-descriptor -errno) costs
    that call, made again one descriptor at a time: never the listener."""
    from etcd_tpu.etcdhttp import web

    def broken(*args):
        raise ValueError("too many descriptors a call")

    http = front(StubServer())
    s = _connect(http)
    s.sendall(_put("b", 0))
    assert _read_reply(s)[0] == 201
    monkeypatch.setattr(web, "recv_many", broken)
    monkeypatch.setattr(web, "send_many", broken)
    for i in range(3):
        s.sendall(_put("b", i))
        assert _read_reply(s)[0] == 200
    s2 = _connect(http)
    s2.sendall(_put("b2", 1))
    assert _read_reply(s2)[0] == 201
    assert any("recv of" in r.getMessage() for r in caplog.records)
    assert any("send to" in r.getMessage() for r in caplog.records)


@pytest.mark.parametrize("code,survives", [
    ("EAGAIN", True), ("EINTR", True), ("ECONNRESET", False),
    ("ETIMEDOUT", False)])
def test_an_errno_from_recv_is_not_now_or_a_quiet_close(
        front, monkeypatch, caplog, code, survives):
    """A -errno in recv_many's list: EAGAIN / EINTR mean "not now" and the
    keep-alive connection serves on; any other (a reset by the client)
    closes the connection, with no traceback in the log."""
    import errno

    from etcd_tpu.etcdhttp import web

    real = web.recv_many
    inject = []

    def recv(fds, bufsize):
        if inject:                      # nothing is read: still readable
            return [inject.pop()] * len(fds)
        return real(fds, bufsize)

    monkeypatch.setattr(web, "recv_many", recv)
    http = front(StubServer())
    s = _connect(http)
    s.sendall(_put("e", 0))
    assert _read_reply(s)[0] == 201
    inject.append(-getattr(errno, code))
    s.sendall(_put("e", 1))
    if survives:
        assert _read_reply(s)[0] == 200
    else:
        assert _closed(s)
    assert not inject
    assert not [r for r in caplog.records if r.exc_info]
    s = _connect(http)
    s.sendall(_put("e2", 1))
    assert _read_reply(s)[0] == 201


def test_the_worker_pool_never_outgrows_the_requests_in_flight(front):
    """Requests of the thread path, one after the other on 40 keep-alive
    connections as fast as they are answered: never more worker threads
    than connections (a worker between two jobs is not a reason for a
    new one), and the workers go when the work does."""
    def slow(ctx, suffix):              # a handler that blocks a little
        time.sleep(0.001)
        ctx.send(200, b"ok")

    http = front(StubServer(), extra=[("/slow", slow, None)])
    before = threading.active_count()
    socks = [_connect(http) for _ in range(40)]
    req = b"GET /slow HTTP/1.1\r\n\r\n"
    for s in socks:
        s.sendall(req)
    most, served, end = 0, 0, time.time() + 1.5
    while time.time() < end:
        for s in socks:
            assert _read_reply(s)[0] == 200
            s.sendall(req)
            served += 1
        most = max(most, threading.active_count() - before)
    for s in socks:
        assert _read_reply(s)[0] == 200
    assert served > 400
    assert 1 <= most <= 40
    assert _wait_for(lambda: threading.active_count() <= before,
                     timeout=10.0)


def test_a_busy_connection_of_the_thread_path_keeps_its_worker(front):
    """Requests that take the thread path, one after the other on one
    connection: the worker that answered one waits a moment for the next
    (no hand-off a request, as a thread per connection had none). After a
    pause the socket is back on the loop and served as ever. The worker
    begins the next request as the loop would: one whose `begin` declines
    is its own too (a tenant with auth on), one that is submitted or
    answered at once goes back to the loop with the socket, in order."""
    def who(ctx, suffix):
        ctx.send(200, str(threading.get_ident()).encode())

    stub = StubServer(hold=True)
    http = front(stub, extra=[("/who", who, None),
                              ("/declined", who, lambda ctx, suffix: None)])
    s = _connect(http)
    get = b"GET /who HTTP/1.1\r\n\r\n"
    idents = []
    for i in range(20):
        s.sendall(get if i % 2 else b"GET /declined HTTP/1.1\r\n\r\n")
        idents.append(_read_reply(s)[2])
    assert len(set(idents)) == 1
    assert int(idents[0]) != http._thread.ident
    time.sleep(0.3)                     # well past the worker's wait
    assert _wait_for(lambda: not http._lent)
    # pipelined behind a thread-path request: a submit, then a 404
    s.sendall(get + _put("after", 1) + b"GET /nowhere HTTP/1.1\r\n\r\n")
    assert _read_reply(s)[0] == 200
    assert _wait_for(lambda: len(stub.pending) == 1)
    stub.release()
    status, _, body = _read_reply(s)
    assert status == 201 and json.loads(body)["node"]["key"] == "/after"
    assert _read_reply(s)[0] == 404
    s.sendall(get)                      # and the connection serves on
    assert _read_reply(s)[0] == 200
    # a client that goes away while the worker waits: nothing is left
    s.sendall(get)
    assert _read_reply(s)[0] == 200
    s.close()
    assert _wait_for(lambda: not http._lent and not http._conns)


# -- the same answers as the parent's front, on both paths ---------------------
#
# One table of request shapes, each sent as raw bytes to a fresh, seeded,
# deterministic server twice: on the loop path (the route has `begin`; the
# request is parsed by begin_keys and staged through submit_pairs) and on
# the thread path (handle_keys on a worker). What came back (status line,
# every header but Date in wire order, body bytes) and what the server was
# handed (the Request staged or done, and its encode() bytes: the WAL
# payload) must equal tests/front_loop_table.json, recorded from the parent
# commit of PR 30 with this same code (`python tests/test_front_loop.py
# --record` writes the file from whatever tree it runs in).

_TABLE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "front_loop_table.json")
_NOW = 4102444800.0             # 2100-01-01T00:00:00Z: no TTL runs out
_FORM = "Content-Type: application/x-www-form-urlencoded\r\n"


class TableServer:
    """What ClientAPI drives, the same at every run: a Python Store on a
    fixed clock with a few seeded keys, request ids from 1, and a record
    of every Request it is handed (staged by the loop, or done)."""

    def __init__(self, loop: bool):
        from etcd_tpu.store import Store
        self.store = Store(clock=lambda: _NOW, namespaces=("/0", "/1"))
        self.store.set("/1/seed/a", value="1")
        self.store.set("/1/seed/b", value="two words")
        self.store.set("/1/seed/dir/c", value="3")
        self.store.set("/1/seed/ttl", value="t", expire_time=_NOW + 100)
        self.cluster = SimpleNamespace(cluster_id=0x2a)
        self.clock = lambda: _NOW
        self.stopped = False
        self.commit_index = 12
        self.term = 3
        self.request_timeout = 5.0
        self.submitter = self if loop else None
        self.wait = Wait()
        self.seen = []
        self._ids = itertools.count(1)

    def new_id(self):
        return next(self._ids)

    def submit_item(self, r):
        return r

    def _apply(self, r: Request):
        """server/engine.py _apply_request's mapping, on this store."""
        st, exp = self.store, r.expiration
        if r.method == "GET":
            return st.get(r.path, r.recursive, r.sorted)
        if r.method == "POST":
            return st.create(r.path, is_dir=r.dir, value=r.val, unique=True,
                             expire_time=exp)
        if r.method == "DELETE":
            if r.prev_index or r.prev_value:
                return st.compare_and_delete(r.path, r.prev_value,
                                             r.prev_index)
            return st.delete(r.path, is_dir=r.dir, recursive=r.recursive)
        if r.refresh:
            return st.update(r.path, None, exp, refresh=True)
        if r.prev_exist:
            if r.prev_index or r.prev_value:
                return st.compare_and_swap(r.path, r.prev_value,
                                           r.prev_index, r.val, exp)
            return st.update(r.path, r.val, exp)
        if r.prev_exist is not None:
            return st.create(r.path, is_dir=r.dir, value=r.val,
                             expire_time=exp)
        if r.prev_index or r.prev_value:
            return st.compare_and_swap(r.path, r.prev_value, r.prev_index,
                                       r.val, exp)
        return st.set(r.path, is_dir=r.dir, value=r.val, expire_time=exp)

    def do(self, r: Request):
        self.seen.append(r)
        if r.method == "GET" and r.wait:
            return self.store.watch(r.path, r.recursive, r.stream, r.since)
        return self._apply(r)

    def submit_pairs(self, items, sink):
        done, tokens = [], []
        for r in items:
            if r.id == 0:           # as MultiEngine.submit_pairs does
                r = Request(**{**r.__dict__, "id": self.new_id()})
            self.seen.append(r)
            self.wait.register(r.id, sink)
            tokens.append(SimpleNamespace(rid=r.id))
            try:
                done.append((r.id, self._apply(r)))
            except errors.EtcdError as e:
                done.append((r.id, e))
        self.wait.trigger_many(done)
        return tokens

    def settle(self, toks, values):
        return values

    def expire(self, tok):
        self.wait.cancel(tok.rid)
        return errors.EtcdError(errors.ECODE_RAFT_INTERNAL,
                                cause="request timed out")


def _req(line, headers="", body=""):
    """Raw request bytes; a body gets its Content-Length."""
    if isinstance(body, str):
        body = body.encode("iso-8859-1")
    if body:
        headers += f"Content-Length: {len(body)}\r\n"
    return (f"{line}\r\n{headers}\r\n").encode("iso-8859-1") + body


def _form(line, body, headers=""):
    return _req(line, headers + _FORM, body)


_K = "/v2/keys"
# name -> request bytes, or (head, body) sent in two parts around the
# server's `100 Continue`
_SHAPES = {
    "put-form": _form(f"PUT {_K}/a/b HTTP/1.1", "value=v1"),
    "put-query-value": _req(f"PUT {_K}/q?value=fromquery HTTP/1.1"),
    "put-body-over-query": _form(
        f"PUT {_K}/bq?value=fromquery&ttl=7 HTTP/1.1", "value=frombody"),
    "put-percent-key-and-value": _form(
        f"PUT {_K}/p%20q%2Fr/%C3%A9 HTTP/1.1", "value=a%26b%3Dc%25+%C3%A9"),
    "put-plus": _form(f"PUT {_K}/plus+key HTTP/1.1", "value=a+b++c"),
    "put-repeated-parameter": _form(
        f"PUT {_K}/rep?value=q1&value=q2 HTTP/1.1", "value=b1&value=b2"),
    "put-blank-value": _form(f"PUT {_K}/blank HTTP/1.1", "value="),
    "put-blank-flags": _form(
        f"PUT {_K}/bf?dir=&recursive&sorted= HTTP/1.1", "value=x&&=y&"),
    "put-semicolon-is-no-separator": _form(
        f"PUT {_K}/semi HTTP/1.1", "value=a;ttl=5"),
    "put-non-utf8": _form(f"PUT {_K}/nu HTTP/1.1",
                          b"value=%ff%fe\xff\xfe&x=\xe9"),
    "put-double-slash-target": _form(f"PUT //v2/keys//ds///k HTTP/1.1",
                                     "value=ds"),
    "put-fragment": _form(f"PUT {_K}/frag?value=q#f?value=no HTTP/1.1", ""),
    "get-absolute-form": _req(
        f"GET http://example.com:2379{_K}/seed/a?quorum=true HTTP/1.1"),
    "put-dotdot-escapes": _form(f"PUT {_K}/a/../../0/x HTTP/1.1",
                                "value=no"),
    "put-dotdot-inside": _form(f"PUT {_K}/a/./b/../c HTTP/1.1", "value=in"),
    "put-root": _form(f"PUT {_K} HTTP/1.1", "value=root"),
    "head-quorum": _req(f"HEAD {_K}/seed/a?quorum=true HTTP/1.1"),
    "head-local": _req(f"HEAD {_K}/seed/a HTTP/1.1"),
    "get-local": _req(f"GET {_K}/seed/a HTTP/1.1"),
    "get-quorum": _req(f"GET {_K}/seed/a?quorum=true HTTP/1.1"),
    "get-quorum-false": _req(f"GET {_K}/seed/b?quorum=false HTTP/1.1"),
    "get-ttl-node": _req(f"GET {_K}/seed/ttl?quorum=true HTTP/1.1"),
    "get-recursive-sorted": _req(
        f"GET {_K}/seed?recursive=true&sorted=true HTTP/1.1"),
    "get-quorum-recursive": _req(
        f"GET {_K}/seed?recursive=true&quorum=true&sorted=true HTTP/1.1"),
    "get-keys-root": _req(f"GET {_K}/?quorum=true HTTP/1.1"),
    "get-missing": _req(f"GET {_K}/nope HTTP/1.1"),
    "get-quorum-missing": _req(f"GET {_K}/nope/deeper?quorum=true HTTP/1.1"),
    "post-in-order": _form(f"POST {_K}/queue HTTP/1.1", "value=job1"),
    "post-in-order-ttl": _form(f"POST {_K}/queue HTTP/1.1",
                               "value=job2&ttl=9"),
    "put-prevExist-false-new": _form(
        f"PUT {_K}/new?prevExist=false HTTP/1.1", "value=n"),
    "put-prevExist-false-there": _form(
        f"PUT {_K}/seed/a?prevExist=false HTTP/1.1", "value=n"),
    "put-prevExist-true": _form(f"PUT {_K}/seed/a HTTP/1.1",
                                "value=u&prevExist=true"),
    "put-prevExist-true-missing": _form(
        f"PUT {_K}/nope?prevExist=true HTTP/1.1", "value=u"),
    "put-prevExist-bad": _form(f"PUT {_K}/seed/a?prevExist=maybe HTTP/1.1",
                               "value=u"),
    "put-prevIndex": _form(f"PUT {_K}/seed/a?prevIndex=1 HTTP/1.1",
                           "value=cas"),
    "put-prevIndex-mismatch": _form(
        f"PUT {_K}/seed/a?prevIndex=99 HTTP/1.1", "value=cas"),
    "put-prevIndex-nan": _form(f"PUT {_K}/seed/a?prevIndex=abc HTTP/1.1",
                               "value=cas"),
    "put-prevIndex-negative": _form(
        f"PUT {_K}/seed/a?prevIndex=-1 HTTP/1.1", "value=cas"),
    "put-prevValue": _form(f"PUT {_K}/seed/b HTTP/1.1",
                           "value=cas&prevValue=two+words"),
    "put-prevValue-mismatch": _form(
        f"PUT {_K}/seed/b?prevValue=three HTTP/1.1", "value=cas"),
    "put-prevValue-empty": _form(f"PUT {_K}/seed/b?prevValue= HTTP/1.1",
                                 "value=cas"),
    "put-prevValue-and-index": _form(
        f"PUT {_K}/seed/a?prevValue=1&prevIndex=1&prevExist=true HTTP/1.1",
        "value=both"),
    "put-ttl": _form(f"PUT {_K}/t HTTP/1.1", "value=v&ttl=30"),
    "put-ttl-zero": _form(f"PUT {_K}/t0 HTTP/1.1", "value=v&ttl=0"),
    "put-ttl-nan": _form(f"PUT {_K}/t HTTP/1.1", "value=v&ttl=soon"),
    "put-ttl-negative": _form(f"PUT {_K}/t HTTP/1.1", "value=v&ttl=-5"),
    "put-ttl-blank": _form(f"PUT {_K}/tb HTTP/1.1", "value=v&ttl="),
    "put-refresh": _form(f"PUT {_K}/seed/ttl HTTP/1.1",
                         "refresh=true&ttl=60&prevExist=true"),
    "put-refresh-with-value": _form(f"PUT {_K}/seed/ttl HTTP/1.1",
                                    "refresh=true&ttl=60&value=x"),
    "put-refresh-with-blank-value": _form(f"PUT {_K}/seed/ttl HTTP/1.1",
                                          "refresh=true&ttl=60&value="),
    "put-refresh-no-ttl": _form(f"PUT {_K}/seed/ttl HTTP/1.1",
                                "refresh=true"),
    "put-dir": _form(f"PUT {_K}/d1?dir=true HTTP/1.1", ""),
    "put-dir-ttl": _form(f"PUT {_K}/d2 HTTP/1.1", "dir=true&ttl=20"),
    "put-over-dir": _form(f"PUT {_K}/seed/dir HTTP/1.1", "value=file"),
    "put-noValueOnSuccess": _form(
        f"PUT {_K}/seed/a?noValueOnSuccess=true HTTP/1.1", "value=quiet"),
    "put-noValueOnSuccess-false": _form(
        f"PUT {_K}/seed/a?noValueOnSuccess=false HTTP/1.1", "value=loud"),
    "put-noValueOnSuccess-bad": _form(
        f"PUT {_K}/seed/a?noValueOnSuccess=yes HTTP/1.1", "value=x"),
    "get-noValueOnSuccess": _req(
        f"GET {_K}/seed/a?noValueOnSuccess=true&quorum=true HTTP/1.1"),
    "put-bad-bool": _form(f"PUT {_K}/x?recursive=1 HTTP/1.1", "value=x"),
    "put-bad-bool-order": _form(
        f"PUT {_K}/x?dir=T&quorum=nope&prevExist=maybe HTTP/1.1", "value=x"),
    "put-every-flag-false": _form(
        f"PUT {_K}/ff?recursive=false&sorted=false&quorum=false&wait=false"
        "&stream=false&dir=false&refresh=false HTTP/1.1", "value=ff"),
    "delete": _req(f"DELETE {_K}/seed/a HTTP/1.1"),
    "delete-missing": _req(f"DELETE {_K}/nope HTTP/1.1"),
    "delete-dir-as-file": _req(f"DELETE {_K}/seed HTTP/1.1"),
    "delete-dir": _req(f"DELETE {_K}/seed/dir?dir=true HTTP/1.1"),
    "delete-recursive": _req(f"DELETE {_K}/seed?recursive=true HTTP/1.1"),
    "delete-prevValue": _req(f"DELETE {_K}/seed/a?prevValue=1 HTTP/1.1"),
    "delete-prevValue-mismatch": _req(
        f"DELETE {_K}/seed/a?prevValue=2 HTTP/1.1"),
    "delete-prevIndex": _req(f"DELETE {_K}/seed/b?prevIndex=2 HTTP/1.1"),
    "delete-with-body": _form(f"DELETE {_K}/seed/b HTTP/1.1",
                              "prevValue=two+words"),
    "get-wait-and-quorum": _req(
        f"GET {_K}/seed/a?wait=true&quorum=true HTTP/1.1"),
    "get-stream-without-wait": _req(f"GET {_K}/seed/a?stream=true HTTP/1.1"),
    "get-waitIndex-nan": _req(
        f"GET {_K}/seed/a?waitIndex=x&quorum=true HTTP/1.1"),
    "get-waitIndex": _req(f"GET {_K}/seed/a?waitIndex=4&quorum=true HTTP/1.1"),
    "patch-is-405": _form(f"PATCH {_K}/seed/a HTTP/1.1", "value=x"),
    "not-a-route": _req("GET /v2/nothing HTTP/1.1"),
    "connection-close": _form(f"PUT {_K}/cc HTTP/1.1", "value=bye",
                              "Connection: close\r\n"),
    "connection-close-error": _req(f"GET {_K}/nope?quorum=true HTTP/1.1",
                                   "Connection: Close\r\n"),
    "http-1.0": _req(f"GET {_K}/seed/a?quorum=true HTTP/1.0"),
    "http-1.0-keep-alive": _form(f"PUT {_K}/ka HTTP/1.0", "value=ka",
                                 "Connection: Keep-Alive\r\n"),
    "folded-header": _form(f"PUT {_K}/fold HTTP/1.1", "value=folded",
                           "X-Fold: a\r\n\tb\r\n  c\r\n"),
    "folded-content-type": _req(
        f"PUT {_K}/foldct HTTP/1.1",
        "Content-Type:\r\n application/x-www-form-urlencoded\r\n",
        "value=unseen"),
    "duplicate-headers": _req(
        f"PUT {_K}/dup HTTP/1.1",
        "Content-Type: text/plain\r\n" + _FORM
        + "Content-Length: 7\r\nContent-Length: 99\r\n"
        + "Connection: close\r\nConnection: keep-alive\r\n") + b"value=d",
    "header-case-and-parameters": _req(
        f"PUT {_K}/case HTTP/1.1",
        "content-type: application/x-www-form-urlencoded; charset=utf-8\r\n"
        "CONTENT-LENGTH: 10\r\nHOST: t\r\n") + b"value=case",
    "body-without-content-type": _req(f"PUT {_K}/noct HTTP/1.1", "",
                                      "value=unseen"),
    "bare-lf": f"PUT {_K}/lf?value=lf HTTP/1.1\nHost: t\n\n".encode(),
    "expect-100-continue": (
        (f"PUT {_K}/e HTTP/1.1\r\n{_FORM}Expect: 100-Continue\r\n"
         "Content-Length: 9\r\n\r\n").encode(), b"value=big"),
    "expect-on-http-1.0": _form(f"PUT {_K}/e10 HTTP/1.0", "value=no100",
                                "Expect: 100-continue\r\n"),
    "400-syntax": b"GARBAGE\r\n\r\n",
    "400-four-words": f"GET {_K}/a b HTTP/1.1\r\n\r\n".encode(),
    "400-version": f"GET {_K}/a HTTP/x.y\r\n\r\n".encode(),
    "400-version-no-http": f"GET {_K}/a FTP/1.1\r\n\r\n".encode(),
    "400-version-one-part": f"GET {_K}/a HTTP/1\r\n\r\n".encode(),
    "505": f"GET {_K}/a HTTP/2.0\r\n\r\n".encode(),
    "http-1.2-is-1.1": _req(f"GET {_K}/seed/a?quorum=true HTTP/1.2"),
    "431-count": (f"GET {_K}/a HTTP/1.1\r\n".encode()
                  + b"".join(b"X-H%d: v\r\n" % i for i in range(101))
                  + b"\r\n"),
    "100-headers-pass": (f"GET {_K}/seed/a?quorum=true HTTP/1.1\r\n".encode()
                         + b"".join(b"X-H%d: v\r\n" % i for i in range(100))
                         + b"\r\n"),
    "431-line": (f"GET {_K}/a HTTP/1.1\r\nX-Long: ".encode()
                 + b"v" * 70000 + b"\r\n\r\n"),
    "414": b"GET /" + b"a" * 70000 + b" HTTP/1.1\r\n\r\n",
    "400-header-line": f"GET {_K}/a HTTP/1.1\r\nNoColonHere\r\n\r\n".encode(),
    "400-header-no-name": f"GET {_K}/a HTTP/1.1\r\n: v\r\n\r\n".encode(),
    "400-fold-before-any-header": (
        f"GET {_K}/a HTTP/1.1\r\n folded\r\n\r\n".encode()),
    "501-transfer-encoding": (
        f"PUT {_K}/te HTTP/1.1\r\nTransfer-Encoding: chunked\r\n\r\n"
        "0\r\n\r\n").encode(),
    "400-length-nan": (
        f"GET {_K}/a HTTP/1.1\r\nContent-Length: nope\r\n\r\n").encode(),
    "400-length-negative": (
        f"GET {_K}/a HTTP/1.1\r\nContent-Length: -1\r\n\r\n").encode(),
    "blank-content-length": _req(f"GET {_K}/seed/a?quorum=true HTTP/1.1",
                                 "Content-Length:\r\n"),
}


def _observe(name: str, loop: bool) -> dict:
    """Send one shape to a fresh TableServer on one path; what came back
    and what the server saw, as JSON holds it."""
    srv = TableServer(loop)
    router = Router()
    api = ClientAPI(srv)
    router.add(_K, api.handle_keys, begin=api.begin_keys if loop else None)
    http = HttpServer("127.0.0.1", 0, router)
    http.start()
    try:
        s = _connect(http)
        shape = _SHAPES[name]
        raw = b""
        if isinstance(shape, tuple):
            s.sendall(shape[0])
            while not raw.endswith(b"\r\n\r\n"):
                raw += s.recv(65536)
            shape = shape[1]
        s.sendall(shape)
        s.settimeout(10.0)
        # whole replies: a head, and the body its Content-Length names
        # (none after a HEAD)
        no_body = shape.startswith(b"HEAD ")
        while True:
            head, sep, rest = raw.partition(b"\r\n\r\n")
            if sep and head.startswith(b"HTTP/1.1 100"):
                head, sep, rest = rest.partition(b"\r\n\r\n")
            if sep:
                m = [ln for ln in head.split(b"\r\n")
                     if ln.lower().startswith(b"content-length:")]
                if len(rest) >= (0 if no_body or not m
                                 else int(m[0].split(b":")[1])):
                    break
            data = s.recv(65536)
            if not data:
                break
            raw += data
        closed = b"Connection: close" in raw and _closed(s)
        s.close()
    finally:
        http.stop()
    lines = raw.decode("iso-8859-1").split("\r\n")
    return {
        "reply": [ln for ln in lines if not ln.startswith("Date: ")],
        "closed": bool(closed),
        "requests": [dict(r.__dict__) for r in srv.seen],
        "encoded": [r.encode().decode() for r in srv.seen],
    }


def _table():
    with open(_TABLE_PATH) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(_SHAPES))
def test_same_reply_and_same_staged_request_as_the_parent(name):
    want = _table()[name]
    got = {"loop": _observe(name, True), "thread": _observe(name, False)}
    for path in ("loop", "thread"):
        assert got[path]["reply"] == want[path]["reply"], path
        assert got[path]["closed"] == want[path]["closed"], path
        assert got[path]["encoded"] == want[path]["encoded"], path
        assert ([Request(**d) for d in got[path]["requests"]]
                == [Request(**d) for d in want[path]["requests"]]), path
    # and the two paths answer alike, as they did at the parent
    assert got["loop"]["reply"] == got["thread"]["reply"]
    assert ([dict(d, id=0) for d in got["loop"]["requests"]]
            == got["thread"]["requests"])


# -- the parsers against their references, on generated input -----------------

def _reference_parse_head(raw: bytes):
    """etcdhttp/web.py _parse_head as the parent commit of PR 30 had it
    (every header split into a list and a dict for every request), kept
    as the reference: (method, target, items, length, keep_alive,
    expect), or the (status, message) it refused with."""
    from etcd_tpu.etcdhttp.web import _MAX_HEADERS, _MAX_LINE
    lines = raw.decode("iso-8859-1").split("\n")
    first = lines[0]
    if len(first) > _MAX_LINE:
        return 414, "Request-URI Too Long"
    words = first.split()
    if len(words) != 3:
        return 400, f"Bad request syntax ({first[:64]!r})"
    method, target, version = words
    if version == "HTTP/1.1":
        vers = (1, 1)
    else:
        try:
            if not version.startswith("HTTP/"):
                raise ValueError
            major, minor = version[5:].split(".")
            vers = (int(major), int(minor))
        except ValueError:
            return 400, f"Bad request version ({version[:32]!r})"
        if vers >= (2, 0):
            return 505, f"Invalid HTTP version ({version[5:]})"
    if len(lines) - 1 > _MAX_HEADERS:
        return 431, "Too many headers"
    items = []
    for text in lines[1:]:
        if len(text) > _MAX_LINE:
            return 431, "Line too long"
        if text[:1] in (" ", "\t") and items:
            items[-1] = (items[-1][0], items[-1][1] + " " + text.strip())
            continue
        name, sep, value = text.partition(":")
        if not sep or not name:
            return 400, "Bad header line"
        items.append((name, value.strip()))
    first_of = {}
    for k, v in items:
        first_of.setdefault(k.lower(), v)
    get = first_of.get
    if get("transfer-encoding") is not None:
        return 501, "Transfer-Encoding is not supported"
    try:
        length = int(get("content-length") or 0)
        if length < 0:
            raise ValueError
    except ValueError:
        return 400, "Bad Content-Length"
    conn_hdr = get("connection")
    if vers >= (1, 1):
        keep_alive = conn_hdr is None or "close" not in conn_hdr.lower()
    else:
        keep_alive = conn_hdr is not None and \
            "keep-alive" in conn_hdr.lower()
    expect = (vers >= (1, 1) and
              (get("expect") or "").lower() == "100-continue")
    return method, target, items, length, keep_alive, expect


_HEAD_PARTS = [
    "Host: t", "host:t", "Content-Length: 5", "content-length:  7 ",
    "Content-Length: -1", "Content-Length: x", "Content-Length:",
    "CONTENT-LENGTH: 3", "Connection: close", "Connection: Keep-Alive",
    "connection: x, CLOSE", "Connection:", "Expect: 100-continue",
    "Expect: 100-Continue", "expect: nope", "Transfer-Encoding: chunked",
    "transfer-encoding:", "X-A: b", "X-A: c", " folded", "\tfolded too",
    " close", " 100-continue", "NoColon", ": noname", "", " ", ":", "a:",
    "X-expect-connection: content-length transfer-encoding",
    "Content-Length : 9", "Content-Type: application/x-www-form-urlencoded",
]
_LINES = ["GET /v2/keys/a HTTP/1.1", "PUT /v2/keys/content-length HTTP/1.0",
          "GET /expect?connection=close HTTP/1.1", "HEAD / HTTP/1.2",
          "GET / HTTP/0.9", "GET / HTTP/2.0", "GET / HTTP/1", "GET / http/1.1",
          "GET  /two-spaces  HTTP/1.1", "GET / HTTP/1.1 extra", "GET /",
          "POST /a\xa0b HTTP/1.1", ""]


@pytest.mark.parametrize("seed", range(12))
def test_generated_heads_parse_as_the_reference_parser(seed):
    import random

    from etcd_tpu.etcdhttp.web import _BadRequest, _parse_head
    rng = random.Random(seed)
    for _ in range(400):
        head = [rng.choice(_LINES)] + [
            rng.choice(_HEAD_PARTS) for _ in range(rng.randrange(0, 7))]
        raw = rng.choice(["\r\n", "\n"]).join(head).encode("iso-8859-1")
        want = _reference_parse_head(raw)
        try:
            h, expect = _parse_head(raw, 1.5)
        except _BadRequest as e:
            assert (e.status, e.message) == want, raw
            continue
        assert (h.method, h.target, h.headers.items(), h.length,
                h.keep_alive, expect) == want, raw
        assert h.t_in == 1.5
        for name, value in want[2]:
            first = [v for k, v in want[2] if k.lower() == name.lower()][0]
            assert h.headers.get(name.upper()) == first
        assert h.headers.get("X-Absent") is None
        assert h.headers.get("X-Absent", "d") == "d"


@pytest.mark.parametrize("seed", range(12))
def test_generated_parameters_parse_as_parse_qs(seed):
    import random
    from urllib.parse import parse_qs

    from etcd_tpu.etcdhttp.web import Ctx, _add_params, _Head, Headers
    rng = random.Random(seed)
    alphabet = ["&", "=", "+", "%", ";", "#", "?", "/", " ", "a", "b", "value",
                "ttl", "%20", "%2", "%zz", "%C3%A9", "%ff", "\xe9", "\u20ac",
                "true", "1", "&&", "=="]

    def some():
        return "".join(rng.choice(alphabet)
                       for _ in range(rng.randrange(0, 9)))
    for _ in range(400):
        qs, form = some(), some()
        got = {}
        _add_params(qs, got)
        assert got == parse_qs(qs, keep_blank_values=True), qs
        # a request's values: the form's first, then the query's, as the
        # parent's Ctx merged two parse_qs results
        want = parse_qs(qs.partition("#")[0], keep_blank_values=True)
        for k, v in parse_qs(form.encode().decode("utf-8", "replace"),
                             keep_blank_values=True).items():
            want[k] = v + want.get(k, [])
        headers = Headers(
            [("Content-Type", "application/x-www-form-urlencoded")])
        ctx = Ctx(None, _Head("PUT", "/p?" + qs, headers, 0, True, 0.0),
                  form.encode())
        assert ctx.path == "/p"
        assert ctx.params() == want, (qs, form)
        for k in list(want) + ["absent"]:
            assert ctx.has(k) == (k in want)
            assert ctx.value(k, "d") == (want[k][0] if k in want else "d")


@pytest.mark.parametrize("seed", range(6))
def test_a_leaf_node_is_written_as_json_dumps_writes_it(seed):
    """client._leaf_json against json.dumps(trim_prefix(to_dict())), which
    stays the code of every node that lists children."""
    import random

    from etcd_tpu.etcdhttp.client import _leaf_json, trim_prefix
    from etcd_tpu.store.event import Event, NodeExtern
    rng = random.Random(seed)
    texts = ["", "/", "x", "/1", "/1/", "/1/a b", "/10/a", "/2/security",
             'q"uo\\te', "tab\tnl\ncr\r\x00\x1f\x7f", "\u00e9\u20ac\U0001f600",
             "\ud800", "</script>", "ab" * 256]
    for _ in range(300):
        n = NodeExtern(
            key=rng.choice(["/1", "/1/", ""]) + rng.choice(texts),
            value=rng.choice([None] + texts), dir=rng.random() < 0.3,
            created_index=rng.randrange(0, 1 << 62),
            modified_index=rng.randrange(0, 1 << 62),
            expiration=rng.choice([None, 0.0, 1.5, _NOW + rng.random()]),
            ttl=rng.randrange(0, 99999))
        want = json.dumps(trim_prefix(Event("get", node=n).to_dict()))
        assert '{"action": "get", "node": ' + _leaf_json(n) + "}" == want


def test_head_starts_are_whole_under_many_threads():
    """web._head_start's table is shared by the loop and every worker
    thread: whoever formats a second's lines, each caller gets whole lines
    for its own status and a Date no more than a second off."""
    import sys
    from email.utils import parsedate_to_datetime

    from etcd_tpu.etcdhttp.web import _head_start
    bad, stop = [], time.time() + 1.5

    def hammer(status):
        while time.time() < stop and not bad:
            before = int(time.time())
            lines = _head_start(status, "t", "text/x").split(b"\r\n")
            when = parsedate_to_datetime(lines[2][6:].decode()).timestamp()
            if (len(lines) != 5 or not lines[0].startswith(
                    b"HTTP/1.1 %d " % status) or lines[3:] != [
                    b"Content-Type: text/x", b""]
                    or not before - 1 <= when <= time.time() + 1):
                bad.append(lines)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=hammer, args=(200 + i % 5,))
                   for i in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=20)
    finally:
        sys.setswitchinterval(was)
    assert not any(th.is_alive() for th in threads) and not bad, bad[:1]


@pytest.mark.parametrize("kind", ["histogram", "summary"])
def test_a_pass_observed_in_one_call_scrapes_as_one_call_each(kind):
    """observe_many (the loop's pass, settle's wake) leaves what observe()
    of each value in turn leaves: same cells, same count, same sum to the
    bit, same window."""
    import random

    from etcd_tpu.utils import metrics
    rng = random.Random(7)
    vs = [rng.choice([0.0, 1e-4, 0.005, 12.0, rng.random(),
                      rng.expovariate(50.0)]) for _ in range(3000)]
    reg = metrics.Registry()
    make = (metrics.Histogram if kind == "histogram" else
            lambda n, h, registry: metrics.Summary(n, h, window=64,
                                                   registry=registry))
    one, many = make("t_one", "h", registry=reg), make("t_many", "h",
                                                       registry=reg)
    for v in vs:
        one.observe(v)
    for i in range(0, len(vs), 127):
        many.observe_many(vs[i:i + 127])
    many.observe_many([])
    assert ([(lbl, v) for _, lbl, v in one.samples()]
            == [(lbl, v) for _, lbl, v in many.samples()])


def test_request_init_names_every_field_with_its_default():
    """server/request.py writes Request.__init__ by hand (one dict update
    instead of a setattr a field): it has to take every field of the
    dataclass, in order, with the field's default, or encode() (which
    reads __dict__) would lose the one that was forgotten."""
    import dataclasses
    import inspect
    params = list(inspect.signature(Request.__init__).parameters.values())[1:]
    fields = dataclasses.fields(Request)
    assert [p.name for p in params] == [f.name for f in fields]
    assert [p.default for p in params] == [f.default for f in fields]
    r = Request(*range(len(fields)))
    assert list(r.__dict__.values()) == list(range(len(fields)))
    assert list(r.__dict__) == [f.name for f in fields]
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.id = 1


if __name__ == "__main__":
    import sys
    if sys.argv[1:] == ["--record"]:
        table = {name: {"loop": _observe(name, True),
                        "thread": _observe(name, False)}
                 for name in sorted(_SHAPES)}
        with open(_TABLE_PATH, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"{len(table)} shapes recorded in {_TABLE_PATH}")
