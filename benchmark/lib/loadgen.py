#!/usr/bin/env python3
"""The one general load generator. Stdlib only, never JAX.

A traffic mix is a data file (benchmark/traffic/<mix>.json); this file
interprets its keys and refuses the ones it does not implement yet:

  loop            "closed": each client sends its next request when the
                  previous one is answered. "open": requests are due on a
                  schedule drawn from the seed, whatever the member does;
                  needs `rate` and `arrival`
  rate            open loop: operations a second, all workers together
  arrival         open loop: "poisson" (exponential gaps; each worker draws
                  its own stream at rate / gen_procs, and a superposition
                  of Poisson streams is one)
  clients         connections (persistent HTTP/1.1 keep-alive): the closed
                  loop's clients, the open loop's pool
  gen_procs       worker processes the clients are spread over; each worker
                  drives its connections from ONE thread with a selector, so
                  the generator is neither one GIL nor a crowd of threads
  write_share     1.0 = all PUTs, 0 = all reads (a share between is drawn
                  per operation from the seed)
  read            "quorum" (?quorum=true); needed where write_share < 1
  value_bytes     bytes of a written (and preloaded) value
  keys_per_client a write goes to /c{client}/k{j}, j uniform
  tenant_dist     {"kind": "uniform"}, or {"kind": "zipf", "theta": t}: rank
                  r of the G tenants with probability ~ 1 / r^t, ranks
                  scattered over the tenants by a seeded permutation (YCSB's
                  scrambled zipfian)
  preload         {"keys_per_tenant": 1}: /pre/k0 in every tenant, written
                  in set-up; reads go to preloaded keys only. A write mix
                  may ask for it too: the front builds a tenant's API objects
                  at the tenant's first request, and a deployment that has
                  run for a minute has built them all
  readback_keys   size of the seeded sample read back after the window
  warmup_seconds  the cell's own traffic before the window (set-up)
  start_spread_ms closed loop only: client c sends its first request
                  c/clients of this many milliseconds after the start of the
                  warm-up and of the window (absent: all at once). Clients
                  of a deployment do not start in one millisecond, and a
                  closed loop against a server that acknowledges a round's
                  writes together keeps the phase it was started with: a
                  burst leaves it to chance how the clients fall into
                  rounds (PERF.md, PR 23)
  via             "direct" or absent; "ingress" is refused

The open loop's timing rule: every latency is taken from the request's DUE
time, not from its send, so a stall of the member counts for every request
that fell due while it stood still (a closed loop hides it: nobody sends).
At its due time a request takes a free connection of the worker's pool; if
none is free it waits, in due order, for the next that frees. A key keeps
one writer (the connection's id is in the key) and a connection one request
in flight, which is what the checker rests on. The 5 s client timeout counts
from the send; a request that found no connection within 5 s of its due
time is given up unsent. Both count as failed. No request is due after the
window's end. The worker also records send - due ("how late the generator
ran") per operation and how many requests found the pool dry.

Every choice comes from --seed: tenant and key per operation from
random.Random(seed, client), values from checker.value_for, an open loop's
due times from random.Random(seed, worker, run). Client timeout
5 s, no resend: a timeout, a refused or severed connection or a non-2xx
answer counts as failed.

The parent (`Generator`) starts the workers as `python loadgen.py
<spec.json>`, speaks one JSON line per command over their pipes and merges
what they dump. CLOCK_MONOTONIC is one clock for all processes of a Linux
host, so the window's start and end are given as monotonic times.
"""
from __future__ import annotations

import json
import os
import random
import selectors
import socket
import subprocess
import sys
import time
from bisect import bisect_left
from collections import deque
from itertools import accumulate

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from checker import ABSENT, Reference, value_for  # noqa: E402

CLIENT_TIMEOUT_S = 5.0
PRELOAD_TRIES = 4
CONNECT_BATCH = 16          # the front's listen backlog is 128 (web.py)
SUPPORTED = {"loop", "rate", "arrival", "clients", "gen_procs",
             "write_share", "read", "value_bytes", "keys_per_client",
             "tenant_dist", "preload", "readback_keys", "warmup_seconds",
             "start_spread_ms", "via", "why"}


class MixError(ValueError):
    pass


def _number(mix: dict, key: str, least: float = 0.0) -> bool:
    v = mix.get(key)
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and v > least)


def validate_mix(mix: dict) -> None:
    """Refuse, with a clear message, what this generator does not do."""
    unknown = set(mix) - SUPPORTED
    if unknown:
        raise MixError(f"traffic mix has keys this generator does not "
                       f"implement: {sorted(unknown)}")
    loop = mix.get("loop")
    if loop == "open":
        if not _number(mix, "rate"):
            raise MixError("loop='open' needs a rate (operations a second, "
                           "over 0)")
        if mix.get("arrival") != "poisson":
            raise MixError(f"arrival={mix.get('arrival')!r}: only 'poisson' "
                           "is implemented")
        if "start_spread_ms" in mix:
            raise MixError("start_spread_ms belongs to a closed loop: an "
                           "open loop's requests are due on its schedule")
    elif loop == "closed":
        for k in ("rate", "arrival"):
            if k in mix:
                raise MixError(f"{k} belongs to loop='open': a closed "
                               "loop's rate is what the member gives it")
    else:
        raise MixError(f"loop={loop!r}: 'closed' or 'open'")
    if mix.get("via", "direct") != "direct":
        raise MixError(f"via={mix['via']!r}: only 'direct' is implemented")
    dist = mix.get("tenant_dist", {})
    kind = dist.get("kind") if isinstance(dist, dict) else None
    if kind == "zipf":
        if set(dist) != {"kind", "theta"} or not _number(dist, "theta"):
            raise MixError("tenant_dist of kind 'zipf' takes theta, a "
                           "number over 0, and nothing else")
    elif kind != "uniform" or set(dist) != {"kind"}:
        raise MixError(f"tenant_dist={dist!r}: kind 'uniform' (no "
                       "parameter) or 'zipf'")
    ws = mix.get("write_share")
    if not isinstance(ws, (int, float)) or not 0 <= ws <= 1:
        raise MixError("write_share must be a number in 0..1")
    if ws < 1:
        if mix.get("read") != "quorum":
            raise MixError(f"read={mix.get('read')!r}: only 'quorum' is "
                           "implemented")
        if mix.get("preload", {}).get("keys_per_tenant") != 1:
            raise MixError("a mix with reads needs preload.keys_per_tenant "
                           "= 1")
    spread = mix.get("start_spread_ms", 0)
    if not isinstance(spread, (int, float)) or spread < 0:
        raise MixError("start_spread_ms must be a number, 0 or more")
    for k in ("clients", "gen_procs", "value_bytes"):
        if not isinstance(mix.get(k), int) or mix[k] < 1:
            raise MixError(f"{k} must be a positive whole number")
    if ws > 0 and (not isinstance(mix.get("keys_per_client"), int)
                   or mix["keys_per_client"] < 1):
        raise MixError("keys_per_client must be a positive whole number")


# ---------------------------------------------------------------------------
# Requests and answers on a raw keep-alive socket
# ---------------------------------------------------------------------------

def put_bytes(tenant: int, key: str, value: str) -> bytes:
    body = "value=" + value          # values are hex: nothing to escape
    return (f"PUT /tenants/{tenant}/v2/keys{key} HTTP/1.1\r\nHost: b\r\n"
            "Content-Type: application/x-www-form-urlencoded\r\n"
            f"Content-Length: {len(body)}\r\n\r\n{body}").encode()


def get_bytes(tenant: int, key: str) -> bytes:
    return (f"GET /tenants/{tenant}/v2/keys{key}?quorum=true HTTP/1.1\r\n"
            "Host: b\r\n\r\n").encode()


def parse_response(buf: bytearray):
    """(status, body bytes, bytes consumed) or None while incomplete."""
    end = buf.find(b"\r\n\r\n")
    if end < 0:
        return None
    head = bytes(buf[:end]).decode("latin-1")
    status = int(head.split(" ", 2)[1])
    length = 0
    for line in head.split("\r\n")[1:]:
        name, _, val = line.partition(":")
        if name.strip().lower() == "content-length":
            length = int(val)
    total = end + 4 + length
    if len(buf) < total:
        return None
    return status, bytes(buf[end + 4:total]), total


def node_value(status: int, body: bytes):
    """The value a keys answer carries; ABSENT for 'key not found'."""
    doc = json.loads(body or b"{}")
    if status == 404 and doc.get("errorCode") == 100:
        return ABSENT
    return doc["node"]["value"]


# ---------------------------------------------------------------------------
# Sources: who decides a connection's next request
# ---------------------------------------------------------------------------

W, R = "w", "r"


class ZipfTenants:
    """tenant_dist of kind "zipf": rank r (1..G) with probability
    ~ 1 / r^theta, by a cumulative table and bisection on the client's own
    rng.random(); ranks go to tenants through a permutation drawn from the
    seed, the same in every worker."""

    def __init__(self, seed: int, groups: int, theta: float) -> None:
        self.cum = list(accumulate(1.0 / r ** theta
                                   for r in range(1, groups + 1)))
        self.perm = list(range(groups))
        random.Random(f"{seed}/zipf-permutation").shuffle(self.perm)

    def pick(self, rng: random.Random) -> int:
        # (random() < 1, so the rank is inside the table; rank 0 = hottest)
        return self.perm[bisect_left(self.cum, rng.random() * self.cum[-1])]


def tenant_dist(seed: int, mix: dict, groups: int):
    """None for uniform (the client draws rng.randrange(groups) itself, as
    it always did), else the worker's one ZipfTenants."""
    dist = mix["tenant_dist"]
    if dist["kind"] == "uniform":
        return None
    return ZipfTenants(seed, groups, dist["theta"])


class MixClient:
    """One connection's source of requests: a client of the closed loop, a
    connection of the open loop's pool (the schedule is the worker's; what
    is sent on this connection is drawn here)."""

    def __init__(self, seed: int, cid: int, mix: dict, groups: int,
                 ref: Reference, tenants: ZipfTenants | None = None) -> None:
        self.seed, self.cid, self.mix, self.groups = seed, cid, mix, groups
        self.rng = random.Random(seed * 1_000_003 + cid)
        self.ref = ref
        self.tenants = tenants
        self.seq = 0
        self.recording = False
        # (kind, t_from, t_reply or None, ok): t_from is the send in a
        # closed loop and the due time in an open one
        self.ops: list = []
        self.think: list = []      # closed loop: reply -> next send, seconds
        self.stale = 0             # in-window reads that differ from ref
        self.t_last_reply = None

    def next(self):
        rng, mix = self.rng, self.mix
        tenant = (rng.randrange(self.groups) if self.tenants is None
                  else self.tenants.pick(rng))
        if rng.random() < mix["write_share"]:
            key = f"/c{self.cid}/k{rng.randrange(mix['keys_per_client'])}"
            value = value_for(self.seed, self.cid, self.seq,
                              mix["value_bytes"])
            self.seq += 1
            self.ref.sent(tenant, key, value)
            return put_bytes(tenant, key, value), (W, tenant, key, value)
        return get_bytes(tenant, "/pre/k0"), (R, tenant, "/pre/k0", None)

    def sent(self, t_send: float) -> None:
        if self.recording and self.t_last_reply is not None:
            self.think.append(t_send - self.t_last_reply)

    def done(self, token, status, body, t_from, t_reply) -> None:
        """status None: no answer (timeout, severed connection)."""
        kind, tenant, key, value = token
        ok = False
        if status is not None and 200 <= status < 300:
            try:
                got = node_value(status, body)
            except (ValueError, KeyError):
                got = object()
            if kind == W:
                ok = got == value
                if ok:
                    self.ref.acked(tenant, key, value)
            else:
                ok = True
                if got not in self.ref_allowed_read(tenant):
                    self.stale += 1
        if self.recording:
            self.ops.append((kind, t_from, t_reply, ok))
        self.t_last_reply = t_reply

    def ref_allowed_read(self, tenant: int) -> list:
        return [value_for(self.seed, "pre", tenant, self.mix["value_bytes"])]


class ListSource:
    """A fixed list of requests shared by the connections that drain it
    (preload, read-back)."""

    def __init__(self, requests) -> None:
        self.todo = deque(requests)     # (bytes, tag)
        self.results: list = []         # (tag, status, body)

    def next(self):
        return self.todo.popleft() if self.todo else None

    def sent(self, t_send: float) -> None:
        pass

    def done(self, token, status, body, t_send, t_reply) -> None:
        self.results.append((token, status, body))


# ---------------------------------------------------------------------------
# The selector loop
# ---------------------------------------------------------------------------

class Conn:
    __slots__ = ("sock", "buf", "source", "token", "t_send", "t_from")

    def __init__(self, source) -> None:
        self.sock = None
        self.buf = bytearray()
        self.source = source
        self.token = None
        self.t_send = 0.0       # the client timeout counts from here
        self.t_from = 0.0       # the latency counts from here: the send in
                                # a closed loop, the due time in an open one


def open_schedule(seed: int, worker: int, run_id: int, rate: float,
                  seconds: float) -> list:
    """One worker's due times for one run of `seconds`, as offsets from the
    run's start: a Poisson stream at `rate` a second, from the seed. run_id
    tells the runs of one process apart (0 is the window, 1 the warm-up)."""
    rng = random.Random(f"{seed}/open/{worker}/{run_id}")
    out, t = [], rng.expovariate(rate)
    while t < seconds:
        out.append(t)
        t += rng.expovariate(rate)
    return out


class Loop:
    """N keep-alive connections to one member, driven from one thread."""

    def __init__(self, host: str, port: int, fine_timer: bool = False) -> None:
        self.addr = (host, port)
        # fine_timer (the open loop, which sleeps until a due time):
        # select(2) takes its timeout in microseconds, where epoll_wait
        # rounds it up to the next millisecond
        self.sel = (selectors.SelectSelector() if fine_timer
                    else selectors.DefaultSelector())
        self.conns: list = []
        self.max_gap = 0.0      # longest time between two turns of run():
                                # over ~0.1 s, this process (or the whole
                                # machine) stood still, not the member

    def _connect(self, conn: Conn, deadline: float) -> bool:
        while True:
            try:
                s = socket.create_connection(self.addr, timeout=5.0)
            except OSError:
                if time.monotonic() > deadline:
                    return False
                time.sleep(0.05)
                continue
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.settimeout(CLIENT_TIMEOUT_S)
            conn.sock = s
            conn.buf.clear()
            self.sel.register(s, selectors.EVENT_READ, conn)
            return True

    def _drop(self, conn: Conn) -> None:
        if conn.sock is not None:
            try:
                self.sel.unregister(conn.sock)
            except (KeyError, ValueError):
                pass
            conn.sock.close()
            conn.sock = None

    def open(self, sources, connect_deadline_s: float = 30.0) -> None:
        """One connection per source, a few at a time, with retry."""
        deadline = time.monotonic() + connect_deadline_s
        for i, src in enumerate(sources):
            conn = Conn(src)
            if not self._connect(conn, deadline):
                raise ConnectionError(f"cannot connect to {self.addr}")
            self.conns.append(conn)
            if (i + 1) % CONNECT_BATCH == 0:
                time.sleep(0.02)

    def rebind(self, sources) -> None:
        for conn, src in zip(self.conns, sources):
            conn.source = src
        for conn in self.conns[len(sources):]:
            conn.source = ListSource([])

    def close(self) -> None:
        for conn in self.conns:
            self._drop(conn)
        self.conns = []
        self.sel.close()

    def _send(self, conn: Conn, t_from: float | None) -> bool:
        """The source's next request on `conn`; its latency counts from
        t_from (None: from the send)."""
        nxt = conn.source.next()
        if nxt is None:
            return False
        data, conn.token = nxt
        if conn.sock is None and not self._connect(
                conn, time.monotonic() + 1.0):
            conn.source.done(conn.token, None, b"",
                             time.monotonic() if t_from is None else t_from,
                             None)
            conn.token = None
            return False
        conn.t_send = time.monotonic()
        if t_from is None:
            conn.t_from = conn.t_send
            conn.source.sent(conn.t_send)
        else:
            conn.t_from = t_from
        try:
            conn.sock.sendall(data)
        except OSError:
            self._fail(conn)
            return False
        return True

    def _send_next(self, conn: Conn, t_end: float) -> bool:
        return time.monotonic() < t_end and self._send(conn, None)

    def _fail(self, conn: Conn) -> None:
        """No answer for the request in flight: count it, drop the
        connection (an answer arriving later must not be taken for the
        next request's)."""
        token, conn.token = conn.token, None
        self._drop(conn)
        conn.source.done(token, None, b"", conn.t_from, None)

    def _readable(self, conn: Conn) -> bool:
        """Take what the socket holds; True where that ended the request in
        flight (its answer is whole, or the connection is gone)."""
        try:
            data = conn.sock.recv(65536)
        except OSError:
            data = b""
        if not data:
            if conn.token is not None:
                self._fail(conn)
                return True
            self._drop(conn)
            return False
        conn.buf += data
        parsed = parse_response(conn.buf)
        if parsed is None or conn.token is None:
            return False
        status, body, used = parsed
        t_reply = time.monotonic()
        del conn.buf[:used]
        token, conn.token = conn.token, None
        conn.source.done(token, status, body, conn.t_from, t_reply)
        return True

    def _turn(self, wait: float, turn: float) -> tuple:
        """One select; (the connections that are readable, now)."""
        events = self.sel.select(timeout=wait)
        now = time.monotonic()
        self.max_gap = max(self.max_gap, now - turn)
        return [key.data for key, _ in events], now

    def run(self, t_start: float, t_end: float, offsets=None) -> None:
        """Closed loop on every connection from t_start (connection i from
        t_start + offsets[i]); no request is sent after t_end; returns when
        every answer is in or timed out."""
        due = deque(sorted(
            ((t_start + (offsets[i] if offsets else 0.0), i)
             for i in range(len(self.conns)))))
        delay = due[0][0] - time.monotonic() if due else 0.0
        if delay > 0:
            time.sleep(delay)
        busy = 0
        turn = time.monotonic()
        while busy or due:
            while due and due[0][0] <= time.monotonic():
                busy += self._send_next(self.conns[due.popleft()[1]], t_end)
            wait = 0.05
            if due:
                wait = max(0.0, min(wait, due[0][0] - time.monotonic()))
            ready, turn = self._turn(wait, turn)
            for conn in ready:
                if conn.sock is not None and self._readable(conn):
                    busy -= 1
                    busy += self._send_next(conn, t_end)
            now = time.monotonic()
            for conn in self.conns:
                if (conn.token is not None
                        and now - conn.t_send > CLIENT_TIMEOUT_S):
                    self._fail(conn)
                    busy -= 1
                    busy += self._send_next(conn, t_end)

    def run_open(self, due: list) -> dict:
        """Open loop: request i is due at due[i] (monotonic, ascending) and
        takes a free connection then, or the next that frees, in due order
        (connections are taken in turn, so every one of the pool stays in
        use). A request still unsent CLIENT_TIMEOUT_S after its due time is
        given up. Returns when every request is answered, timed out or
        given up, with send - due of every request sent ("late", seconds),
        the number that found the pool dry and the due times given up."""
        free = deque(self.conns)
        late, given_up = [], []
        i = marked = busy = dry = 0     # due[:i] are sent or given up;
        n = len(due)                    # due[i:marked] found the pool dry
        delay = due[0] - time.monotonic() if due else 0.0
        if delay > 0:
            time.sleep(delay)
        turn = sweep = time.monotonic()
        while i < n or busy:
            now = time.monotonic()
            while i < n and due[i] <= now and free:
                conn = free.popleft()
                if self._send(conn, due[i]):
                    busy += 1
                    late.append(conn.t_send - due[i])
                else:
                    free.append(conn)   # failed at once: counted by done()
                i += 1
                now = time.monotonic()
            marked = max(marked, i)
            wait = 0.02
            if i < n and free:
                wait = max(0.0, min(wait, due[i] - now))
            elif i < n:
                while marked < n and due[marked] <= now:
                    marked += 1
                    dry += 1
            ready, turn = self._turn(wait, turn)
            for conn in ready:
                if conn.sock is not None and self._readable(conn):
                    busy -= 1
                    free.append(conn)
            if turn - sweep >= 0.25:
                sweep = turn
                for conn in self.conns:
                    if (conn.token is not None
                            and turn - conn.t_send > CLIENT_TIMEOUT_S):
                        self._fail(conn)
                        busy -= 1
                        free.append(conn)
                while i < n and turn - due[i] > CLIENT_TIMEOUT_S:
                    given_up.append(due[i])
                    i += 1
        return {"late": late, "pool_dry": dry, "given_up": given_up}


def drive(port: int, requests, conns: int = 32) -> list:
    """Send a fixed list of requests over a few connections and return
    [(tag, status, body)] (the parent's preload-free helper for read-backs
    and probes)."""
    src = ListSource(requests)
    loop = Loop("127.0.0.1", port)
    try:
        loop.open([src] * max(1, min(conns, len(src.todo))))
        loop.run(time.monotonic(), float("inf"))
    finally:
        loop.close()
    return src.results


# ---------------------------------------------------------------------------
# Worker process
# ---------------------------------------------------------------------------

def worker_main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    seed, mix, groups = spec["seed"], spec["mix"], spec["groups"]
    ref = Reference()
    tenants = tenant_dist(seed, mix, groups)
    clients = [MixClient(seed, cid, mix, groups, ref, tenants)
               for cid in spec["client_ids"]]
    is_open = mix["loop"] == "open"
    loop = Loop("127.0.0.1", spec["port"], fine_timer=is_open)
    gen = {"late": [], "pool_dry": 0, "given_up": []}   # open loop's own

    def say(**kw) -> None:
        sys.stdout.write(json.dumps(kw) + "\n")
        sys.stdout.flush()

    try:
        for line in sys.stdin:
            cmd = json.loads(line)
            op = cmd["cmd"]
            if op == "open":
                loop.open(clients)
                say(ok=True)
            elif op == "preload":
                # Idempotent sets: one that got no answer (the member can
                # stall for seconds, e.g. at a checkpoint) is sent again.
                nbytes = mix["value_bytes"]
                todo, written = list(cmd["tenants"]), 0
                for _ in range(PRELOAD_TRIES):
                    src = ListSource(
                        (put_bytes(t, "/pre/k0",
                                   value_for(seed, "pre", t, nbytes)), t)
                        for t in todo)
                    loop.rebind([src] * len(loop.conns))
                    loop.run(time.monotonic(), float("inf"))
                    todo = [t for t, status, _ in src.results
                            if status not in (200, 201)]
                    written += len(src.results) - len(todo)
                    if not todo:
                        break
                loop.rebind(clients)
                say(ok=not todo, written=written, failed=todo[:5])
            elif op == "run":
                record = cmd["record"]
                for c in clients:
                    c.recording = record
                    c.t_last_reply = None
                loop.max_gap = 0.0
                if is_open:
                    sched = open_schedule(
                        seed, spec["worker"], cmd["run_id"],
                        mix["rate"] / spec["workers"], cmd["t1"] - cmd["t0"])
                    res = loop.run_open([cmd["t0"] + o for o in sched])
                    if record:
                        gen = res
                else:
                    spread = mix.get("start_spread_ms", 0) / 1e3
                    loop.run(cmd["t0"], cmd["t1"],
                             [spread * c.cid / mix["clients"]
                              for c in clients])
                say(ok=True)
            elif op == "dump":
                kind = W if mix["write_share"] >= 0.5 else R
                with open(cmd["path"], "w") as f:
                    json.dump({
                        "ops": [o for c in clients for o in c.ops]
                        + [(kind, t, None, False) for t in gen["given_up"]],
                        "think": [t for c in clients for t in c.think],
                        "late": gen["late"],
                        "pool_dry": gen["pool_dry"],
                        "given_up": len(gen["given_up"]),
                        "stale_reads": sum(c.stale for c in clients),
                        "max_loop_gap_s": loop.max_gap,
                        "reference": ref.dump()}, f)
                say(ok=True)
            elif op == "quit":
                break
    finally:
        loop.close()
    return 0


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------

class Generator:
    """The worker processes of one run."""

    def __init__(self, work: str, seed: int, mix: dict, groups: int,
                 port: int) -> None:
        validate_mix(mix)
        self.work, self.mix, self.groups = work, mix, groups
        n = mix["gen_procs"]
        self.procs: list = []
        for w in range(n):
            spec = {"seed": seed, "mix": mix, "groups": groups, "port": port,
                    "worker": w, "workers": n,
                    "client_ids": list(range(w, mix["clients"], n))}
            path = os.path.join(work, f"gen{w}.spec.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                bufsize=1))

    def _all(self, cmds: list) -> list:
        for p, cmd in zip(self.procs, cmds):
            p.stdin.write(json.dumps(cmd) + "\n")
            p.stdin.flush()
        out = []
        for p in self.procs:
            line = p.stdout.readline()
            if not line:
                raise RuntimeError(f"load generator worker died "
                                   f"(rc={p.poll()})")
            out.append(json.loads(line))
        return out

    def open(self) -> None:
        self._all([{"cmd": "open"}] * len(self.procs))

    def preload(self) -> int:
        n = len(self.procs)
        res = self._all([{"cmd": "preload",
                          "tenants": list(range(w, self.groups, n))}
                         for w in range(n)])
        bad = [r for r in res if not r["ok"]]
        if bad:
            raise RuntimeError(f"preload: writes failed: {bad}")
        return sum(r["written"] for r in res)

    def run(self, t0: float, t1: float, record: bool,
            run_id: int = 0) -> None:
        """The mix's traffic from t0 to t1. An open loop draws its schedule
        for (seed, worker, run_id)."""
        self._all([{"cmd": "run", "t0": t0, "t1": t1, "record": record,
                    "run_id": run_id}] * len(self.procs))

    def dump(self) -> dict:
        """Merged records: ops, think times, the open loop's lateness and
        counts, stale-read count, reference."""
        paths = [os.path.join(self.work, f"gen{w}.dump.json")
                 for w in range(len(self.procs))]
        self._all([{"cmd": "dump", "path": p} for p in paths])
        ops, think, late, stale, ref, gap = [], [], [], 0, Reference(), 0.0
        dry = given_up = 0
        for p in paths:
            with open(p) as f:
                d = json.load(f)
            ops += d["ops"]
            think += d["think"]
            late += d["late"]
            dry += d["pool_dry"]
            given_up += d["given_up"]
            stale += d["stale_reads"]
            gap = max(gap, d["max_loop_gap_s"])
            ref.merge(Reference.load(d["reference"]))
        return {"ops": ops, "think": think, "late": late, "pool_dry": dry,
                "given_up": given_up, "stale_reads": stale,
                "reference": ref, "max_loop_gap_s": gap}

    def close(self) -> None:
        """Stop every worker and wait until each has ended."""
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.stdin.write('{"cmd": "quit"}\n')
                    p.stdin.close()
                except (OSError, ValueError):
                    pass
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            if p.stdout:
                p.stdout.close()
        self.procs = []


if __name__ == "__main__":
    sys.exit(worker_main(sys.argv[1]))
