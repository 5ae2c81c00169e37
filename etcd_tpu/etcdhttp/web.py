"""Event-loop HTTP routing core shared by the client and peer APIs.

The reference hangs its handlers off Go's net/http ServeMux
(etcdhttp/client.go:85-114): a goroutine per connection costs it next to
nothing. An OS thread per connection does not: 256 handler threads
sharing one interpreter with the engine's round thread were the member's
bottleneck (PERF.md, PR 24). So one thread, `etcd-http`, owns every
connection of a listener:

- It accepts, reads (non-blocking sockets under `selectors`, a read
  buffer per connection) and parses HTTP/1.1 itself: request line,
  headers, Content-Length bodies, keep-alive, `Connection: close`,
  HTTP/1.0, HEAD, `Expect: 100-continue`, with the limits the stdlib
  handler had (a 64 KiB line, 100 headers) answered 414 / 431 / 400.
  One request is in flight per connection: the next is not parsed until
  this one's reply is written, so pipelined requests keep their order.
- A route may offer `begin` beside its handler (Router.add). The loop
  calls it for a request it has parsed; `begin` must not block. It
  answers inline (REPLIED: a tenant's local read, a parse error), or
  returns a LoopOp (the request is submitted and answered later: every
  LoopOp of one select pass goes to its submitter in ONE submit_pairs
  call, and the results come back through a utils.wait.Sink that signals
  the loop's wake pipe once per batch of acks), or returns None.
- None, a route with no `begin`, and every connection of a TLS listener
  take the thread path: whatever may block (a long-poll or streaming
  watch, a hijack(), an admin route, a server with only a blocking
  `do`, a TLS handshake) runs the handler as written, on a worker thread
  against the blocking socket (_Workers: never more threads than
  requests on this path right now). When it returns and the connection
  was neither streamed nor hijacked, the socket goes back to the loop;
  bytes the loop had read past the request go with it either way
  (hijack()'s rfile starts with them).
- A reply is one buffer (status line, headers, body) and one send; what
  the socket does not take is kept and written when it is writable.
- The sockets that a pass finds readable are read in ONE call and the
  replies a pass has built are sent in ONE call (native/frontcore.c:
  recv_many / send_many, each releasing the interpreter lock once). A
  system call costs the loop 13-22 us on the chip's host; getting the
  interpreter back after each one, from the engine's round thread, cost
  it 120 us and more (PERF.md, PR 25).

Which path a request takes is decided by what the code can see (the
route, the request, whether the server has a submit), never by a setting.
"""
from __future__ import annotations

import errno
import io
import json
import logging
import os
import queue
import select
import selectors
import socket
import threading
import time
from email.utils import formatdate
from http import HTTPStatus
from types import MappingProxyType
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import unquote, urlsplit

from etcd_tpu.native import _py_recv_many as _recv_each
from etcd_tpu.native import _py_send_many as _send_each
from etcd_tpu.native import recv_many, send_many
from etcd_tpu.utils.wait import Sink

log = logging.getLogger("etcdhttp")

# The stdlib handler's limits (http.server / http.client), kept.
_MAX_LINE = 65536
_MAX_HEADERS = 100
_RECV = 65536
# Unsent reply bytes and unparsed request bytes a connection may hold;
# past either the connection is closed / not read until it has drained.
_WBUF_CAP = 16 << 20
_RBUF_CAP = 1 << 20
# The loop's time-out sweep over its in-flight table: a few times a second.
_SWEEP_S = 0.25
# An idle worker thread lingers this long for the next blocking request.
_IDLE_S = 2.0
# A worker that has answered a request waits this long on the connection
# for its next one before the socket goes back to the loop: a connection
# whose requests all take the thread path (a server with only a blocking
# `do`) keeps its thread while it is busy, and pays no hand-off a request.
_LINGER_S = 0.02
_REASONS = {s.value: s.phrase for s in HTTPStatus}
_CONTINUE = b"HTTP/1.1 100 Continue\r\n\r\n"
# -errno results of recv_many / send_many that mean "not now".
_AGAIN = (-errno.EAGAIN, -errno.EWOULDBLOCK, -errno.EINTR)


class Headers:
    """A request's headers: get() is case-insensitive and returns the
    first value (email.message.Message.get's contract, which the handlers
    were written against); items() keeps wire order and spelling. The
    look-up table is made at the first get(): for the request whose
    handler reads a header."""

    __slots__ = ("_items", "_first")

    def __init__(self, items: List[Tuple[str, str]]) -> None:
        self._items = items
        self._first: Optional[Dict[str, str]] = None

    def _table(self) -> Dict[str, str]:
        first = self._first
        if first is None:
            first = self._first = {}
            for k, v in self._items:
                first.setdefault(k.lower(), v)
        return first

    def get(self, name: str, default=None):
        return self._table().get(name.lower(), default)

    def items(self):
        return list(self._items)


class _BadRequest(Exception):
    def __init__(self, status: int, message: str) -> None:
        super().__init__(message)
        self.status = status
        self.message = message


class _Head:
    """A parsed request line and header block, waiting for its body."""

    __slots__ = ("method", "target", "headers", "length", "keep_alive",
                 "t_in")

    def __init__(self, method, target, headers, length, keep_alive, t_in):
        self.method = method
        self.target = target
        self.headers = headers
        self.length = length
        self.keep_alive = keep_alive
        self.t_in = t_in


def _parse_head(raw: bytes, t_in: float) -> Tuple[_Head, bool]:
    """Request line + headers (without the blank line) -> (_Head, whether
    the client waits for `100 Continue`). Raises _BadRequest."""
    # iso-8859-1 maps bytes to characters one to one: lengths are bytes.
    text = raw.decode("iso-8859-1")
    lines = text.split("\n")
    first = lines[0]
    if len(first) > _MAX_LINE:
        raise _BadRequest(414, "Request-URI Too Long")
    words = first.split()
    if len(words) != 3:
        raise _BadRequest(400, f"Bad request syntax ({first[:64]!r})")
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
            raise _BadRequest(400,
                              f"Bad request version ({version[:32]!r})")
        if vers >= (2, 0):
            raise _BadRequest(505, f"Invalid HTTP version ({version[5:]})")
    if len(lines) - 1 > _MAX_HEADERS:
        raise _BadRequest(431, "Too many headers")
    items: List[Tuple[str, str]] = []
    for i in range(1, len(lines)):
        line = lines[i]
        if len(line) > _MAX_LINE:
            raise _BadRequest(431, "Line too long")
        if line[:1] in (" ", "\t") and items:      # obsolete line folding
            items[-1] = (items[-1][0], items[-1][1] + " " + line.strip())
            continue
        name, sep, value = line.partition(":")
        if not sep or not name:
            raise _BadRequest(400, "Bad header line")
        items.append((name, value.strip()))
    headers = Headers(items)
    low = text.lower()
    if not ("content-length" in low or "connection" in low
            or "expect" in low or "transfer-encoding" in low):
        # The head spells none of the four headers the front itself
        # reads (a GET of two header lines): nothing to look up.
        return _Head(method, target, headers, 0, vers >= (1, 1), t_in), False
    get = headers._table().get
    if get("transfer-encoding") is not None:
        raise _BadRequest(501, "Transfer-Encoding is not supported")
    try:
        length = int(get("content-length") or 0)
        if length < 0:
            raise ValueError
    except ValueError:
        raise _BadRequest(400, "Bad Content-Length")
    conn_hdr = get("connection")
    if vers >= (1, 1):
        keep_alive = conn_hdr is None or "close" not in conn_hdr.lower()
    else:
        keep_alive = conn_hdr is not None and \
            "keep-alive" in conn_hdr.lower()
    expect = (vers >= (1, 1) and
              (get("expect") or "").lower() == "100-continue")
    return _Head(method, target, headers, length, keep_alive, t_in), expect


class _Conn:
    """One client connection. The loop thread owns it while `blocking` is
    False; a worker thread owns it while True (the thread path)."""

    __slots__ = ("server", "sock", "fd", "addr", "rbuf", "scan", "wbuf",
                 "head", "busy", "closing", "eof", "events", "blocking",
                 "closed", "queued", "reply")

    def __init__(self, server: "HttpServer", sock, addr,
                 blocking: bool = False) -> None:
        self.server = server
        self.sock = sock
        self.addr = addr
        self.rbuf = bytearray()
        self.scan = 0             # rbuf is known to hold no blank line before
        self.wbuf = bytearray()   # reply bytes the socket has not taken yet
        self.head: Optional[_Head] = None
        self.busy = False         # a request is in flight: parse no other
        self.closing = False      # close once wbuf is written
        self.eof = False          # the peer closed its sending half
        self.events = 0           # what the selector watches
        self.blocking = blocking
        self.closed = False
        self.fd = sock.fileno()
        self.queued = False       # in the loop's list of pending sends
        self.reply = None         # (t_in, kind, waited, op) until sent

    def write(self, data: bytes) -> None:
        """Hand reply bytes to the socket: all of them now on the thread
        path; on the loop they join this pass's one batch of sends
        (HttpServer._send_pending), the rest kept for EVENT_WRITE."""
        if self.blocking:
            if self.wbuf:
                data = bytes(self.wbuf) + data
                self.wbuf.clear()
            self.sock.sendall(data)
            return
        if self.closed:
            return
        self.wbuf += data
        if len(self.wbuf) > _WBUF_CAP:
            self.server._close(self)
        elif not self.queued:
            self.queued = True
            self.server._sends.append(self)


class _PrefixedRaw(io.RawIOBase):
    """A blocking socket as a raw stream that first yields the bytes the
    loop had already read past the request (hijack()'s rfile)."""

    def __init__(self, sock, prefix: bytes) -> None:
        self._sock = sock
        self._prefix = prefix

    def readable(self) -> bool:
        return True

    def readinto(self, b) -> int:
        if self._prefix:
            n = min(len(b), len(self._prefix))
            b[:n] = self._prefix[:n]
            self._prefix = self._prefix[n:]
            return n
        return self._sock.recv_into(b)


class _SockWriter:
    """The stdlib handler's unbuffered wfile: every write is a sendall."""

    def __init__(self, sock) -> None:
        self._sock = sock

    def write(self, data) -> int:
        self._sock.sendall(data)
        return len(data)

    def flush(self) -> None:
        pass


def _add_params(qs: str, into: Dict[str, List[str]]) -> None:
    """parse_qs(qs, keep_blank_values=True), added to `into`: `&` alone
    separates, `+` is a space, %XX a byte of UTF-8, repeats keep order."""
    for pair in qs.split("&"):
        if not pair:
            continue
        name, _, value = pair.partition("=")
        if "+" in pair or "%" in pair:
            name = unquote(name.replace("+", " "))
            value = unquote(value.replace("+", " "))
        if name in into:
            into[name].append(value)
        else:
            into[name] = [value]


class Ctx:
    """One request: query+form values (parsed when first asked for),
    response helpers, and a client-disconnect probe for long-polls."""

    # Extra headers of every response (CORS): set whole by the server.
    extra_headers: Dict[str, str] = MappingProxyType({})
    _values: Optional[Dict[str, List[str]]] = None
    _streaming = False

    def __init__(self, conn: _Conn, head: _Head, body: bytes) -> None:
        self._conn = conn
        self.method = head.method
        self.target = head.target      # as sent, query string included
        self.headers = head.headers
        self.body = body
        self.keep_alive = head.keep_alive
        self.t_in = head.t_in          # perf_counter at the parsed head
        target = head.target
        if target.startswith("//"):
            target = "/" + target.lstrip("/")
        if target[:1] == "/":
            # Origin form: no scheme or authority for urlsplit to find,
            # so it would drop the fragment and cut at the first `?`.
            if "#" in target:
                target = target[:target.index("#")]
            path, _, self._query = target.partition("?")
        else:
            parts = urlsplit(target)
            path, self._query = parts.path, parts.query
        self.path = unquote(path) if "%" in path else path

    # -- inputs -------------------------------------------------------------

    def params(self) -> Dict[str, List[str]]:
        """Every parameter's values (read only), the form's before the
        query's: body parameters take precedence over the URL query string
        (Go net/http Request.Form semantics the reference relies on)."""
        values = self._values
        if values is None:
            values = self._values = {}
            if self.body and (self.headers.get("Content-Type") or ""
                              ).startswith("application/x-www-form-urlencoded"):
                _add_params(self.body.decode("utf-8", "replace"), values)
            if self._query:
                _add_params(self._query, values)
        return values

    def has(self, key: str) -> bool:
        return key in self.params()

    def value(self, key: str, default: str = "") -> str:
        v = self.params().get(key)
        return v[0] if v else default

    def remote_addr(self) -> str:
        addr = self._conn.addr
        return f"{addr[0]}:{addr[1]}"

    # -- buffered responses ---------------------------------------------------

    def _head(self, status: int, content_type: str, rest: bytes,
              headers) -> bytes:
        """_head_start's lines, `rest` (length or transfer encoding), the
        CORS headers, the handler's: a dict, or lines made for the wire."""
        out = _head_start(status, self._conn.server.server_version,
                          content_type) + rest
        if self.extra_headers:
            out += _header_lines(self.extra_headers)
        if headers:
            out += (headers if type(headers) is bytes
                    else _header_lines(headers))
        return out + b"\r\n"

    def send(self, status: int, body: bytes = b"",
             content_type: str = "text/plain", headers=None) -> None:
        """The whole reply as one buffer and one send."""
        head = self._head(
            status, content_type,
            (b"Content-Length: %d\r\n" if self.keep_alive else
             b"Content-Length: %d\r\nConnection: close\r\n") % len(body),
            headers)
        self._conn.write(head + body if body and self.method != "HEAD"
                         else head)

    def send_json(self, status: int, obj, headers=None) -> None:
        self.send(status, json.dumps(obj).encode(), "application/json",
                  headers)

    # -- chunked streaming (watch streams) ------------------------------------

    def begin_stream(self, status: int, content_type: str,
                     headers=None) -> None:
        # A stream writer must never block forever on a stalled client:
        # with no socket timeout, a peer that stops reading (TCP buffers
        # full) would pin this handler thread inside sendall and its
        # watcher would never be released. timeout -> OSError subclass ->
        # write_chunk returns False -> the loop cleans up.
        try:
            self._conn.sock.settimeout(30.0)
        except OSError:
            pass
        self._streaming = True
        self._conn.write(self._head(
            status, content_type, b"Transfer-Encoding: chunked\r\n",
            headers))

    def write_chunk(self, data: bytes) -> bool:
        try:
            self._conn.write(b"%x\r\n%b\r\n" % (len(data), data))
            return True
        except (BrokenPipeError, ConnectionResetError, OSError):
            return False

    def end_stream(self) -> None:
        try:
            self._conn.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass

    # -- connection takeover (binary upgrade endpoints) -----------------------

    def hijack(self):
        """Take over the raw connection for a non-HTTP framed protocol
        (the batchframe channel's 101 upgrade): returns (rfile, wfile)
        positioned right after this request's body; rfile starts with
        whatever the loop had already read past it. The caller owns the
        socket until it returns from its handler; the server then closes
        the connection (keep-alive re-parse of binary frames as HTTP
        would be garbage)."""
        self._streaming = True      # the connection is closed after
        conn = self._conn
        prefix = bytes(conn.rbuf)
        conn.rbuf.clear()
        return (io.BufferedReader(_PrefixedRaw(conn.sock, prefix)),
                _SockWriter(conn.sock))

    def client_gone(self) -> bool:
        """True once the peer closed its half of the connection — the
        CloseNotify analogue that lets long-polls release their watcher
        (reference client.go:571-576)."""
        if self._conn.eof:
            return True
        try:
            sock = self._conn.sock
            r, _, _ = select.select([sock], [], [], 0)
            if not r:
                return False
            data = sock.recv(1, socket.MSG_PEEK)
            return len(data) == 0
        except (OSError, ValueError):
            return True


# (status, server version, content type) -> (second, the first lines)
_head_starts: Dict[tuple, Tuple[int, bytes]] = {}


def _head_start(status: int, server_version: str, content_type: str) -> bytes:
    """Status line, Server, Date, Content-Type: made once a second."""
    now = int(time.time())
    key = (status, server_version, content_type)
    hit = _head_starts.get(key)
    if hit is None or hit[0] != now:
        hit = _head_starts[key] = (now, (
            f"HTTP/1.1 {status} {_REASONS.get(status, '')}\r\n"
            f"Server: {server_version}\r\n"
            f"Date: {formatdate(now, usegmt=True)}\r\n"
            f"Content-Type: {content_type}\r\n").encode("iso-8859-1"))
    return hit[1]


def _header_lines(headers: Dict[str, str]) -> bytes:
    return "".join(f"{k}: {v}\r\n"
                   for k, v in headers.items()).encode("iso-8859-1")


# What a route's `begin` returns when it has answered the request itself.
REPLIED = object()


class LoopOp:
    """What a route's `begin` returns for a request that is answered
    later without a thread of its own. The loop gathers the LoopOps of one
    select pass and hands each submitter its `item`s in one call:

        submitter.submit_pairs(items, sink) -> one token per item, each
            with a `.rid` the sink will name, or an exception in the
            place of an item it refuses (answered at once, through
            `finish`, and that request only); returns at once
        submitter.settle(tokens, values) -> the values to answer with,
            for the completions of one wake
        submitter.expire(token) -> the value to answer a request with
            that outlived `timeout` (and must not be delivered later)
        submitter.tracer (optional) -> a server.obs.Tracer for the
            front_in and woke marks of a sampled rid, and its finish()
            where the reply is handed to the socket

    (MultiEngine is one: its items are (tenant, Request) pairs.)

    When (rid, value) comes out of the sink, `finish(value)` builds the
    reply through the request's Ctx, on the loop: it must not block."""

    __slots__ = ("submitter", "item", "finish", "kind", "timeout", "conn",
                 "ctx", "token", "deadline", "t_submit", "traced")

    def __init__(self, submitter, item, finish: Callable[[Any], None],
                 kind: str = "other", timeout: float = 5.0) -> None:
        self.submitter = submitter
        self.item = item
        self.finish = finish
        self.kind = kind
        self.timeout = timeout
        self.traced = False


Handler = Callable[[Ctx, str], None]
Route = Tuple[str, bool, Handler, Optional[Callable[[Ctx, str], Any]], str]


class Router:
    """Longest-prefix-wins routing. Handlers get (ctx, suffix) where suffix
    is the path remainder after the matched prefix."""

    def __init__(self) -> None:
        self._routes: List[Route] = []

    def add(self, prefix: str, fn: Handler, exact: bool = False,
            begin: Optional[Callable[[Ctx, str], Any]] = None) -> None:
        """`fn` is the handler as a thread runs it. `begin`, if given, is
        tried first, on the event loop, and must not block: it returns
        REPLIED (answered), a LoopOp (submitted; answered later) or None
        (run `fn` on a thread)."""
        self._routes.append((prefix, exact, fn, begin,
                             prefix if prefix.endswith("/") else prefix + "/"))
        self._routes.sort(key=lambda r: len(r[0]), reverse=True)

    def match(self, path: str):
        """(fn, begin, suffix) of the route that serves `path`, or None."""
        for prefix, exact, fn, begin, below in self._routes:
            if path == prefix:
                return fn, begin, ""
            if not exact and path.startswith(below):
                return fn, begin, path[len(prefix):]
        return None


class _Workers:
    """Threads for the requests that may block. There are never more
    workers than jobs handed in and not finished (at most one a
    connection): a job takes a worker that is free, and only when every
    worker has a job of its own does it get a new thread; a worker that
    finds nothing to do for _IDLE_S exits. So the count follows what is
    blocked right now (watches, streams, upgrades) and, for a server
    whose every request takes this path, the connections busy at once,
    as a thread per connection did. "Nobody is seen waiting" is no reason
    for a new thread: a worker between two jobs waits for the
    interpreter, and under load most of them do."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._threads = 0           # workers alive
        self._open = 0              # jobs handed in and not finished
        self._jobs: "queue.SimpleQueue" = queue.SimpleQueue()

    def run(self, job: Callable[[], Optional[Callable[[], None]]]) -> None:
        """`job` may return a callable, run once the job is counted done."""
        with self._lock:
            self._open += 1
            grow = self._threads < self._open
            if grow:
                self._threads += 1
        self._jobs.put(job)
        if grow:
            try:
                threading.Thread(target=self._work, daemon=True,
                                 name="etcd-http-worker").start()
            except RuntimeError:    # no more threads: the job waits its turn
                log.exception("http: cannot start a worker thread")
                with self._lock:
                    self._threads -= 1

    def _work(self) -> None:
        while True:
            try:
                job = self._jobs.get(timeout=_IDLE_S)
            except queue.Empty:
                with self._lock:
                    if self._threads > self._open:
                        self._threads -= 1
                        return
                continue            # a job is on its way to the queue
            if job is None:         # stop()
                return
            after = None
            try:
                after = job()
            except Exception:  # noqa: BLE001 — a worker outlives its job
                log.exception("http worker job failed")
            with self._lock:
                self._open -= 1
            if after is not None:
                # What lets the connection's next request in (the socket's
                # way back to the loop) comes after the job is off the
                # books, or that request would be counted beside it.
                after()

    def stop(self) -> None:
        with self._lock:
            n = self._threads
        for _ in range(n):
            self._jobs.put(None)


_ACCEPT = object()
_WAKE = object()


class HttpServer:
    """One listener served by one event-loop thread (module docstring);
    worker threads are daemons so watches never block shutdown."""

    def __init__(self, host: str, port: int, router: Router,
                 server_version: str = "etcd-tpu",
                 cors: Optional[set] = None, tls_context=None,
                 thread_cpu=None) -> None:
        self.router = router
        # An obs.ThreadCpu for the loop thread's CPU clock (class "loop",
        # read at scrape time only), where the server behind keeps one.
        self._thread_cpu = thread_cpu
        self.server_version = server_version
        # CORS origin whitelist ("*" = any); None disables CORS handling
        # (reference pkg/cors/cors.go CORSInfo + CORSHandler).
        self.cors = set(cors) if cors else None
        # The front's span and self time and where requests were served
        # (server/obs.py); off under ETCD_TPU_OBS=off. Imported here, not
        # at the top: the server package imports this one.
        from etcd_tpu.server import obs
        self._obs_on = obs.obs_enabled()
        self._front = obs.front
        self._h_request = {k: obs.http_request.labels(k)
                           for k in obs.FRONT_KINDS}
        self._h_self = {k: obs.http_front_self.labels(k)
                        for k in obs.FRONT_KINDS}
        self._c_loop, self._c_thread = (
            obs.http_front_served.labels(p) for p in obs.FRONT_PATHS)
        self._c_wakes = obs.http_front_wakes
        self._c_completions = obs.http_front_completions

        self._tls = tls_context
        self._scheme = "https" if tls_context is not None else "http"
        lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            lsock.bind((host, port))
            # A short backlog resets connections under concurrent client
            # bursts (reference etcd serves 256+ concurrent clients in
            # its benchmarks).
            lsock.listen(1024)
            lsock.setblocking(False)
        except OSError:
            lsock.close()
            raise
        self._lsock = lsock
        self._addr = lsock.getsockname()[:2]
        self._sel = selectors.DefaultSelector()
        self._wake_r, self._wake_w = os.pipe()
        self._wake_lock = threading.Lock()
        os.set_blocking(self._wake_r, False)
        os.set_blocking(self._wake_w, False)
        self._sink = Sink(self._wake)
        self._conns: set = set()            # owned by the loop
        self._lent: set = set()             # owned by a worker thread
        self._lent_lock = threading.Lock()
        self._returned: "queue.SimpleQueue" = queue.SimpleQueue()
        self._inflight: Dict[int, LoopOp] = {}
        self._batch: List[LoopOp] = []
        self._sends: List[_Conn] = []       # reply bytes to write this pass
        self._workers = _Workers()
        self._stopping = False
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._addr[1]

    @property
    def url(self) -> str:
        return f"{self._scheme}://{self._addr[0]}:{self._addr[1]}"

    def start(self) -> None:
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="etcd-http")
        self._thread.start()

    def stop(self) -> None:
        """Stop listening and sever every connection, idle, in flight or
        held by a worker: shutting the listener alone would leave old
        keep-alive connections served — a stopped member would otherwise
        keep answering peers as a zombie."""
        self._stopping = True
        self._wake()
        if self._thread is not None:
            self._thread.join(timeout=5)
        else:
            self._shutdown()
        with self._lent_lock:
            lent = list(self._lent)
        for conn in lent:
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        while True:                 # handed back after the loop had gone
            try:
                self._unlend(self._returned.get_nowait()[0], close=True)
            except queue.Empty:
                break
        self._workers.stop()

    # -- the loop ---------------------------------------------------------------

    def _wake(self) -> None:
        # Under a lock with _shutdown's close: a late completion must
        # never write to a descriptor number that was closed and reused.
        with self._wake_lock:
            if self._wake_w < 0:
                return              # stopped: nobody to wake
            try:
                os.write(self._wake_w, b"\0")
            except (BlockingIOError, InterruptedError):
                pass                # the pipe is full: it will wake

    def _serve(self) -> None:
        sel = self._sel
        if self._thread_cpu is not None:
            self._thread_cpu.register("loop")
        sel.register(self._lsock, selectors.EVENT_READ, _ACCEPT)
        sel.register(self._wake_r, selectors.EVENT_READ, _WAKE)
        next_sweep = time.monotonic() + _SWEEP_S
        try:
            while not self._stopping:
                woken = False
                events = sel.select(_SWEEP_S if self._inflight else None)
                readable = []
                for key, mask in events:
                    conn = key.data
                    if conn is _WAKE:
                        woken = True
                    elif conn is _ACCEPT:
                        self._accept()
                    else:
                        if mask & selectors.EVENT_WRITE and not conn.queued:
                            conn.queued = True
                            self._sends.append(conn)
                        if mask & selectors.EVENT_READ:
                            readable.append(conn)
                if readable:
                    self._recv_ready(readable)
                if self._batch:
                    self._submit()
                if woken:
                    self._woken()
                if self._inflight:
                    now = time.monotonic()
                    if now >= next_sweep:
                        next_sweep = now + _SWEEP_S
                        self._sweep(now)
                while self._sends:
                    self._send_pending()
                if self._batch:     # pipelined behind a reply just written
                    self._submit()
        except Exception:  # noqa: BLE001 — the listener must say why it died
            log.exception("http event loop failed")
        finally:
            self._shutdown()

    def _shutdown(self) -> None:
        for op in list(self._inflight.values()):
            try:
                op.submitter.expire(op.token)
            except Exception:  # noqa: BLE001
                log.exception("http: expire at shutdown failed")
        self._inflight.clear()
        for conn in list(self._conns):
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            self._close(conn)
        try:
            self._sel.close()
        except OSError:
            pass
        self._lsock.close()
        with self._wake_lock:
            fds, self._wake_r, self._wake_w = (self._wake_r,
                                               self._wake_w), -1, -1
        for fd in fds:
            if fd >= 0:
                os.close(fd)

    def _accept(self) -> None:
        for _ in range(256):
            try:
                sock, addr = self._lsock.accept()
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
            if self._tls is not None:
                # A handshake may block on a slow client: never here. A
                # TLS connection keeps its thread for its lifetime.
                conn = _Conn(self, sock, addr, blocking=True)
                self._lend(conn)
                self._workers.run(lambda c=conn: self._serve_tls(c))
                continue
            sock.setblocking(False)
            conn = _Conn(self, sock, addr)
            self._conns.add(conn)
            self._watch(conn, selectors.EVENT_READ)

    def _watch(self, conn: _Conn, events: int) -> None:
        """Set what the selector watches of a loop-owned connection."""
        if events == conn.events or conn.closed:
            return
        if not conn.events:
            self._sel.register(conn.sock, events, conn)
        elif not events:
            self._sel.unregister(conn.sock)
        else:
            self._sel.modify(conn.sock, events, conn)
        conn.events = events

    def _close(self, conn: _Conn) -> None:
        if conn.closed:
            return
        if conn.events:
            try:
                self._sel.unregister(conn.sock)
            except (KeyError, ValueError, OSError):
                pass
            conn.events = 0
        conn.closed = True
        self._conns.discard(conn)
        try:
            conn.sock.close()
        except OSError:
            pass

    def _recv_ready(self, conns: List[_Conn]) -> None:
        """One recv on each readable connection, all in one call that
        releases the interpreter lock once (native/frontcore.c)."""
        conns = [c for c in conns if not c.closed]
        fds = [c.fd for c in conns]
        try:
            got = recv_many(fds, _RECV)
        except Exception:  # noqa: BLE001 — a batch's fault is not the loop's
            log.exception("http: recv of %d connections failed", len(fds))
            got = _recv_each(fds, _RECV)
        for conn, data in zip(conns, got):
            try:
                self._received(conn, data)
            except Exception:  # noqa: BLE001 — one connection's
                log.exception("http: connection %s failed", conn.addr)
                self._close(conn)

    def _received(self, conn: _Conn, data) -> None:
        if isinstance(data, int):       # -errno
            if data not in _AGAIN:      # reset by the client, most often
                self._close(conn)
            return
        if not data:                    # end of file
            if conn.busy or conn.wbuf:
                # The reply in flight still goes out (a client may half-
                # close after its request); stop watching a socket that
                # stays readable for ever.
                conn.eof = True
                self._watch(conn, conn.events & ~selectors.EVENT_READ)
            else:
                self._close(conn)
            return
        conn.rbuf += data
        if conn.busy or conn.wbuf:
            if len(conn.rbuf) > _RBUF_CAP:
                self._watch(conn, conn.events & ~selectors.EVENT_READ)
            return
        self._advance(conn)

    def _send_pending(self) -> None:
        """One send on each connection with reply bytes to write, all in
        one call that releases the interpreter lock once. What a socket
        does not take stays in its wbuf for EVENT_WRITE; a connection
        whose reply is out goes on with the bytes it has read."""
        conns, self._sends = self._sends, []
        live = []
        for conn in conns:
            conn.queued = False
            if conn.wbuf and not conn.closed:
                live.append(conn)
        items = [(c.fd, c.wbuf) for c in live]
        try:
            sent = send_many(items)
        except Exception:  # noqa: BLE001 — a batch's fault is not the loop's
            log.exception("http: send to %d connections failed", len(items))
            sent = _send_each(items)
        now = time.perf_counter()
        handed = []
        for conn, n in zip(live, sent):
            if n < 0:
                if n not in _AGAIN:
                    self._close(conn)
                    continue
                n = 0
            del conn.wbuf[:n]
            if conn.reply is not None:
                handed.append(conn.reply)
                conn.reply = None
            if conn.wbuf:
                self._watch(conn, conn.events | selectors.EVENT_WRITE)
                continue
            if conn.events & selectors.EVENT_WRITE:
                self._watch(conn, conn.events & ~selectors.EVENT_WRITE)
            try:
                self._advance(conn)
            except Exception:  # noqa: BLE001 — one connection's
                log.exception("http: connection %s failed", conn.addr)
                self._close(conn)
        self._handed(handed, now)

    def _advance(self, conn: _Conn) -> None:
        """Serve what the read buffer holds, one request at a time, until
        one is in flight, its reply is not written yet, or bytes run out."""
        while not (conn.busy or conn.wbuf or conn.closed):
            if conn.closing or (conn.eof and not conn.rbuf):
                self._close(conn)
                return
            if not conn.rbuf:
                break                   # nothing read: no request to find
            ctx = self._next_request(conn)
            if ctx is not None:
                self._dispatch(conn, ctx)
            elif conn.eof:
                conn.closing = True     # half a request and no more to come
            elif not conn.closing:
                break                   # more bytes needed
        if not (conn.events & selectors.EVENT_READ or conn.closed
                or conn.eof or conn.blocking):
            self._watch(conn, conn.events | selectors.EVENT_READ)

    def _next_request(self, conn: _Conn) -> Optional[Ctx]:
        """The next whole request in conn.rbuf as a Ctx, or None (more
        bytes needed, or the request was refused and the connection is
        closing). Used by the loop and by a TLS connection's thread."""
        buf = conn.rbuf
        if conn.head is None:
            end, skip = buf.find(b"\r\n\r\n", conn.scan), 4
            if end < 0:
                end, skip = buf.find(b"\n\n", conn.scan), 2
            if end < 0:
                conn.scan = max(0, len(buf) - 3)
                if len(buf) > _MAX_LINE:
                    self._check_partial(conn)
                return None
            raw = bytes(buf[:end])
            del buf[:end + skip]
            conn.scan = 0
            try:
                conn.head, expect = _parse_head(raw, time.perf_counter())
            except _BadRequest as e:
                self._refuse(conn, e.status, e.message)
                return None
            if expect and len(buf) < conn.head.length:
                conn.write(_CONTINUE)
        head = conn.head
        if len(buf) < head.length:
            return None
        body = bytes(buf[:head.length]) if head.length else b""
        if head.length:
            del buf[:head.length]
        conn.head = None
        return Ctx(conn, head, body)

    def _check_partial(self, conn: _Conn) -> None:
        """More than a line's worth of bytes and no blank line yet: hold
        the unfinished head to the limits a finished one is held to."""
        lines = bytes(conn.rbuf).split(b"\n")
        if len(lines[0]) > _MAX_LINE:
            self._refuse(conn, 414, "Request-URI Too Long")
        elif len(lines) - 1 > _MAX_HEADERS:
            self._refuse(conn, 431, "Too many headers")
        elif max(map(len, lines)) > _MAX_LINE:
            self._refuse(conn, 431, "Line too long")

    def _refuse(self, conn: _Conn, status: int, message: str) -> None:
        """Answer a request the front cannot serve (the stdlib handler's
        send_error) and close the connection."""
        body = (f"<html><head><title>Error response</title></head><body>"
                f"<h1>Error response</h1><p>Error code: {status}</p>"
                f"<p>Message: {message}.</p></body></html>\n").encode(
                    "utf-8", "replace")
        head = (_head_start(status, self.server_version,
                            "text/html;charset=utf-8")
                + b"Connection: close\r\nContent-Length: %d\r\n\r\n"
                % len(body))
        conn.closing = True
        conn.rbuf.clear()
        try:
            conn.write(head + body)
        except OSError:
            pass

    def _route(self, ctx: Ctx):
        """CORS, OPTIONS and the route look-up, for both paths: (fn,
        begin, suffix), or None when the request is answered already."""
        if self.cors is not None:
            # reference CORSHandler.ServeHTTP: header on every
            # allowed-origin response; OPTIONS answered 200.
            if "*" in self.cors:
                allow = "*"
            else:
                origin = ctx.headers.get("Origin", "")
                allow = origin if origin in self.cors else None
            if allow is not None:
                ctx.extra_headers = {
                    "Access-Control-Allow-Methods":
                        "POST, GET, OPTIONS, PUT, DELETE",
                    "Access-Control-Allow-Origin": allow,
                    "Access-Control-Allow-Headers": "accept, content-type",
                }
            if ctx.method == "OPTIONS":
                ctx.send(200)
                return None
        hit = self.router.match(ctx.path)
        if hit is None:
            ctx.send(404, b"404 page not found\n")
        return hit

    def _begin(self, conn: _Conn, ctx: Ctx):
        """Route one parsed request and call its route's `begin`, on the
        loop or on a worker that holds the connection: (hit, op), with
        hit None once the request is answered (a 404, an OPTIONS,
        REPLIED) and op a LoopOp to submit; or None, refused with 500."""
        try:
            hit = self._route(ctx)
            op = None
            if hit is not None and hit[1] is not None:
                op = hit[1](ctx, hit[2])
                if op is REPLIED:
                    hit = op = None
        except Exception as e:  # noqa: BLE001 — last resort, as the handler's
            log.exception("http: %s %s failed in begin", ctx.method,
                          ctx.path)
            self._refuse(conn, 500, str(e))
            return None
        return hit, op

    def _dispatch(self, conn: _Conn, ctx: Ctx, begun=None) -> None:
        """One parsed request, on the loop: answer it, submit it, or lend
        the connection to a worker thread. `begun` is _begin's result
        where a worker has called it already."""
        if begun is None:
            begun = self._begin(conn, ctx)
            if begun is None:
                return
        hit, op = begun
        if hit is None:
            self._replied(conn, ctx, "other", 0.0)
            return
        if op is not None:
            op.conn, op.ctx = conn, ctx
            conn.busy = True
            self._batch.append(op)
            return
        # The thread path: the handler as written, on a blocking socket.
        conn.busy = True
        self._watch(conn, 0)
        self._conns.discard(conn)
        conn.sock.setblocking(True)
        conn.blocking = True
        self._lend(conn)
        fn, _begin, suffix = hit
        self._workers.run(lambda: self._serve_lent(conn, ctx, fn, suffix))

    def _replied(self, conn: _Conn, ctx: Ctx, kind: str, waited: float,
                 op: Optional[LoopOp] = None) -> None:
        """A reply was built on the loop and waits in conn.wbuf for this
        pass's sends; _handed observes it when the socket has it."""
        reply = (ctx.t_in, kind, waited, op)
        conn.busy = False
        if not ctx.keep_alive:
            conn.closing = True
        if conn.wbuf:
            conn.reply = reply
        else:                       # the handler wrote nothing to wait for
            self._handed([reply], time.perf_counter())

    def _handed(self, replies: list, now: float) -> None:
        """Handed to the socket at `now`, the front's span of each ends: a
        pass's spans in one call per kind, a sampled request's span
        folded on its own."""
        spans: Dict[str, Tuple[list, list]] = {}
        for t_in, kind, waited, op in replies:
            if op is not None and op.traced:
                op.submitter.tracer.finish(op.token.rid, kind, now)
            if self._obs_on:
                whole, own = spans.setdefault(kind, ([], []))
                whole.append(now - t_in)
                own.append(now - t_in - waited)
        for kind, (whole, own) in spans.items():
            self._h_request[kind].observe_many(whole)
            self._h_self[kind].observe_many(own)
        if spans:
            self._c_loop.inc(len(replies))

    def _submit(self) -> None:
        """Every LoopOp of this select pass, one call per submitter."""
        batch, self._batch = self._batch, []
        groups: Dict[int, List[LoopOp]] = {}
        for op in batch:
            groups.setdefault(id(op.submitter), []).append(op)
        for ops in groups.values():
            sub = ops[0].submitter
            t = time.perf_counter()
            try:
                tokens = sub.submit_pairs([op.item for op in ops],
                                          self._sink)
            except Exception as e:  # noqa: BLE001 — answer, do not die
                log.exception("http: submit of %d requests failed", len(ops))
                for op in ops:
                    op.conn.busy = False
                    self._refuse(op.conn, 500, str(e))
                continue
            tr = getattr(sub, "tracer", None)
            every = tr.every if tr is not None else 0
            deadline = time.monotonic()
            for op, tok in zip(ops, tokens):
                op.t_submit = t
                if isinstance(tok, Exception):
                    self._finish(op, tok, t)    # refused, this one alone
                    continue
                op.token = tok
                op.deadline = deadline + op.timeout
                self._inflight[tok.rid] = op
                if every and tok.rid % every == 0:
                    op.traced = True
                    tr.mark(tok.rid, "front_in", t=op.ctx.t_in)

    def _woken(self) -> None:
        """The wake pipe was written: completions in the sink,
        connections a worker has handed back, or stop()."""
        try:
            os.read(self._wake_r, 4096)
        except (BlockingIOError, InterruptedError):
            pass
        done = self._sink.drain()
        if done:
            if self._obs_on:
                self._c_wakes.inc()
                self._c_completions.inc(len(done))
            t_woke = time.perf_counter()
            pop = self._inflight.pop
            groups: Dict[int, list] = {}
            for rid, value in done:
                op = pop(rid, None)
                if op is not None:        # else: expired a moment ago
                    groups.setdefault(id(op.submitter), []).append(
                        (op, value))
            for pairs in groups.values():   # one settle per submitter
                settled = pairs[0][0].submitter.settle(
                    [op.token for op, _ in pairs], [v for _, v in pairs])
                for (op, _), value in zip(pairs, settled):
                    self._finish(op, value, t_woke)
        while True:
            try:
                conn, back = self._returned.get_nowait()
            except queue.Empty:
                break
            self._take_back(conn, back)

    def _finish(self, op: LoopOp, value: Any, t_woke: float) -> None:
        conn, ctx = op.conn, op.ctx
        tr = op.submitter.tracer if op.traced else None
        if tr is not None:
            tr.mark(op.token.rid, "woke", t=t_woke)
        try:
            op.finish(value)
        except Exception as e:  # noqa: BLE001 — last resort
            log.exception("http: reply to %s %s failed", ctx.method,
                          ctx.path)
            conn.busy = False
            self._refuse(conn, 500, str(e))
            return
        self._replied(conn, ctx, op.kind, t_woke - op.t_submit, op)
        if not conn.wbuf:
            self._advance(conn)

    def _sweep(self, now: float) -> None:
        """Answer what outlived its time-out (cfg.request_timeout for the
        engine) with the submitter's own error."""
        late = [op for op in self._inflight.values() if op.deadline <= now]
        for op in late:
            del self._inflight[op.token.rid]
            self._finish(op, op.submitter.expire(op.token),
                         time.perf_counter())

    # -- the thread path --------------------------------------------------------

    def _lend(self, conn: _Conn) -> None:
        with self._lent_lock:
            self._lent.add(conn)

    def _unlend(self, conn: _Conn, close: bool) -> None:
        with self._lent_lock:
            self._lent.discard(conn)
        if close:
            conn.closed = True
            try:
                conn.sock.close()
            except OSError:
                pass

    def _serve_lent(self, conn: _Conn, ctx: Ctx, fn: Handler, suffix: str):
        """Worker thread: one request of a loop-owned connection and,
        while they arrive within _LINGER_S, its next ones that take this
        path too; then the socket goes back to the loop (or is closed),
        with the request the worker has begun and the loop must finish
        (a LoopOp to submit, an answer to account for), if there is one."""
        back = None
        try:
            keep = self._run_handler(conn, ctx, fn, suffix)
            while keep and not self._stopping:
                ctx = self._await_request(conn, _LINGER_S)
                if ctx is None:
                    keep = not (conn.eof or conn.closing)
                    break
                begun = self._begin(conn, ctx)
                if begun is None:
                    keep = False
                elif begun[0] is None or begun[1] is not None:
                    back = (ctx, begun)
                    break
                else:
                    keep = self._run_handler(conn, ctx, begun[0][0],
                                             begun[0][2])
        except OSError:
            keep = False
        if not keep or self._stopping:
            self._unlend(conn, close=True)
            return None
        return lambda: self._hand_back(conn, back)

    def _hand_back(self, conn: _Conn, back) -> None:
        self._returned.put((conn, back))    # still lent until the loop has
        self._wake()                # it: stop() severs what it finds there

    def _take_back(self, conn: _Conn, back) -> None:
        self._unlend(conn, close=self._stopping)
        if self._stopping:
            return
        conn.sock.setblocking(False)
        conn.blocking = False
        conn.busy = False
        self._conns.add(conn)
        if back is not None:
            self._dispatch(conn, *back)
        self._advance(conn)

    def _await_request(self, conn: _Conn,
                       linger: Optional[float]) -> Optional[Ctx]:
        """Worker thread: the next whole request of a blocking connection,
        waiting for its bytes up to `linger` seconds (None: for ever).
        None: nothing whole came in time, the peer has closed (conn.eof)
        or the request was refused (conn.closing)."""
        deadline = None if linger is None else time.monotonic() + linger
        while True:
            ctx = self._next_request(conn)
            if ctx is not None or conn.closing:
                return ctx
            if deadline is not None:
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                conn.sock.settimeout(left)
            try:
                data = conn.sock.recv(_RECV)
            except TimeoutError:
                return None
            finally:
                if deadline is not None:
                    conn.sock.settimeout(None)
            if not data:
                conn.eof = True
                return None
            conn.rbuf += data

    def _serve_tls(self, conn: _Conn) -> None:
        """Worker thread: a TLS connection's whole life (reference
        pkg/transport NewTLSListener, listener.go:60-80): handshake, then
        request after request on this thread."""
        try:
            conn.sock = self._tls.wrap_socket(
                conn.sock, server_side=True, do_handshake_on_connect=False)
            conn.sock.do_handshake()
            while not self._stopping:
                ctx = self._await_request(conn, None)
                if ctx is None:
                    return
                hit = self._route(ctx)
                if hit is None:
                    if not ctx.keep_alive:
                        return
                elif not self._run_handler(conn, ctx, hit[0], hit[2]):
                    return
        except OSError:             # ssl.SSLError is one
            pass
        finally:
            self._unlend(conn, close=True)

    def _run_handler(self, conn: _Conn, ctx: Ctx, fn: Handler,
                     suffix: str) -> bool:
        """Run one handler on this thread against the blocking socket;
        True if the connection may serve another request."""
        front = self._front if self._obs_on else None
        if front is not None:
            # From the parsed request line to the response written. What
            # the engine learned on this thread meanwhile (its own clock
            # on the wait for the ack, the request's kind, a sampled rid)
            # comes back through obs.front.
            front.blocked, front.kind, front.trace = 0.0, "other", None
            front.t_in = ctx.t_in
        keep = ctx.keep_alive
        try:
            fn(ctx, suffix)
        except (BrokenPipeError, ConnectionResetError):
            keep = False
        except Exception as e:  # pragma: no cover - last resort
            log.exception("http: %s %s failed", ctx.method, ctx.path)
            if not ctx._streaming:
                self._refuse(conn, 500, str(e))
            keep = False
        if ctx._streaming:
            return False            # a watch stream, a hijacked connection
        if front is not None:
            self._c_thread.inc()
            kind = front.kind
            if kind in self._h_request:         # not a watch
                dt = time.perf_counter() - ctx.t_in
                self._h_request[kind].observe(dt)
                self._h_self[kind].observe(dt - front.blocked)
                if front.trace is not None:
                    tracer, rid = front.trace
                    tracer.finish(rid, kind, ctx.t_in + dt)
        return keep and not conn.closing
