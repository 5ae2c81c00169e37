"""Coalescing ingress tier (server/ingress.py): correctness of the
batching proxy between shallow clients and the engine.

Pins the tier's contracts: per-client FIFO survives coalescing (a
client's writes apply in submission order even when they ride different
flush windows); ack/error demultiplexing routes each slot's outcome to
exactly its own client (a failing CAS never poisons batch-mates); an
ingress SIGKILL never loses an ACKED write (acks forward only after the
upstream's fsync-gated ack — proven against a real kill); the watch hub
fans one upstream stream out to N downstream watchers with the same
events in the same order as a direct engine watch; and the event-driven
front actually holds thousands of connections within the fd budget.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from etcd_tpu.server.cluster import STORE_KEYS_PREFIX
from etcd_tpu.server.engine import EngineConfig, MultiEngine
from etcd_tpu.server.ingress import Ingress, IngressConfig
from etcd_tpu.server.request import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G, P = 4, 3  # one kernel shape for the module => one XLA compile


def make_engine(tmp, **kw):
    kw.setdefault("groups", G)
    kw.setdefault("peers", P)
    kw.setdefault("window", 16)
    kw.setdefault("max_ents", 4)
    kw.setdefault("heartbeat_tick", 3)
    kw.setdefault("request_timeout", 30.0)
    kw.setdefault("fsync", False)  # tmpdirs; durability logic unchanged
    kw.setdefault("checkpoint_rounds", 1 << 30)
    return MultiEngine(EngineConfig(data_dir=str(tmp), **kw))


class stack:
    """engine + EngineHttp front + in-process Ingress, torn down in
    reverse order."""

    def __init__(self, tmp, **ingress_kw):
        from etcd_tpu.etcdhttp.tenants import EngineHttp
        self.eng = make_engine(tmp)
        self.front = EngineHttp(self.eng)
        self.front.start()
        self.eng.start()
        assert self.eng.wait_leaders(60.0)
        self.ing = Ingress(IngressConfig(upstream=self.front.url,
                                         **ingress_kw))
        self.ing.start()
        self.base = f"http://127.0.0.1:{self.ing.port}"

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.ing.stop()
        self.front.stop()
        self.eng.stop()


def _put(base, t, key, val, timeout=30, headers=None, **params):
    q = "&".join(f"{k}={v}" for k, v in params.items())
    req = urllib.request.Request(
        f"{base}/tenants/{t}/v2/keys{key}" + (f"?{q}" if q else ""),
        data=f"value={val}".encode(), method="PUT")
    req.add_header("Content-Type", "application/x-www-form-urlencoded")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


def _get_json(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _scrape(base, name):
    with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
        text = r.read().decode()
    for ln in text.splitlines():
        if ln.startswith(name) and " " in ln:
            return float(ln.rsplit(" ", 1)[1])
    return None


def test_per_client_fifo_through_coalescing(tmp_path):
    """24 depth-1 clients × 12 sequential writes each, through small
    flush windows: every client's writes apply in its submission order
    (monotone modifiedIndex AND the store's per-key event history shows
    its values in sequence), and the lanes really coalesced (flushes <
    requests)."""
    with stack(tmp_path, flush_max_requests=8) as s:
        n0 = _scrape(s.base, "etcd_ingress_coalesce_batch_requests_count")
        s0 = _scrape(s.base, "etcd_ingress_coalesce_batch_requests_sum")
        N, W = 24, 12
        fails = []
        indexes = {c: [] for c in range(N)}

        def client(c):
            for seq in range(W):
                st, body = _put(s.base, c % G, f"/c{c}", f"{c}:{seq}")
                if st != 201 and st != 200:
                    fails.append((c, seq, st, body))
                    return
                indexes[c].append(body["node"]["modifiedIndex"])

        ths = [threading.Thread(target=client, args=(c,)) for c in range(N)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert all(not t.is_alive() for t in ths), "clients hung"
        assert not fails, fails[:3]
        for c in range(N):
            ix = indexes[c]
            assert len(ix) == W and ix == sorted(ix) and \
                len(set(ix)) == W, (c, ix)
            _, body = _put(s.base, c % G, f"/c{c}", "final",
                           prevValue=f"{c}:{W-1}")
            assert body.get("action") == "compareAndSwap", (c, body)
        # The windows actually batched: strictly fewer upstream flushes
        # than requests (mean batch depth > 1).
        n1 = _scrape(s.base, "etcd_ingress_coalesce_batch_requests_count")
        s1 = _scrape(s.base, "etcd_ingress_coalesce_batch_requests_sum")
        flushes, reqs = n1 - n0, s1 - s0
        assert reqs >= N * W and flushes < reqs, (flushes, reqs)


def test_error_fanback_routing(tmp_path):
    """Failing CAS writes share flush windows with valid writes: each
    client gets exactly its own outcome — 412/101 for the CAS losers,
    201 for the writers — and every valid write lands."""
    with stack(tmp_path, flush_max_requests=16) as s:
        assert _put(s.base, 0, "/cas", "base")[0] == 201
        outcomes = {}

        def loser(i):
            st, body = _put(s.base, 0, "/cas", f"steal{i}",
                            prevValue="wrong")
            outcomes[("l", i)] = (st, body.get("errorCode"))

        def writer(i):
            st, _ = _put(s.base, 0, f"/ok{i}", f"v{i}")
            outcomes[("w", i)] = (st, None)

        ths = [threading.Thread(target=loser, args=(i,)) for i in range(8)]
        ths += [threading.Thread(target=writer, args=(i,)) for i in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
        assert all(not t.is_alive() for t in ths)
        for i in range(8):
            assert outcomes[("l", i)] == (412, 101), outcomes[("l", i)]
            assert outcomes[("w", i)] == (201, None), outcomes[("w", i)]
        assert _get_json(f"{s.base}/tenants/0/v2/keys/cas"
                         )["node"]["value"] == "base"
        for i in range(8):
            assert _get_json(f"{s.base}/tenants/0/v2/keys/ok{i}"
                             )["node"]["value"] == f"v{i}"


def _spawn_ingress(upstream):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    p = subprocess.Popen(
        [sys.executable, "-m", "etcd_tpu.server.ingress",
         "--upstream", upstream],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, cwd=REPO)
    info = json.loads(p.stdout.readline())
    return p, info["port"]


def test_sigkill_loses_no_acked_write(tmp_path):
    """The durability hand-off, against a real crash: depth-1 clients
    count a write only after the ingress relayed the upstream ack;
    SIGKILL the ingress mid-stream; every counted write must be in the
    engine. (In-flight unacked writes may die with the proxy — that is
    the contract.)"""
    import http.client

    from etcd_tpu.etcdhttp.tenants import EngineHttp
    eng = make_engine(tmp_path)
    front = EngineHttp(eng)
    front.start()
    eng.start()
    proc = None
    try:
        assert eng.wait_leaders(60.0)
        proc, port = _spawn_ingress(front.url)
        NC = 8
        acked = [-1] * NC
        stop = threading.Event()

        def client(cid):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=15)
            seq = 0
            while not stop.is_set():
                try:
                    conn.request(
                        "PUT", f"/tenants/{cid % G}/v2/keys/s{cid}",
                        body=f"value={cid}:{seq}",
                        headers={"Content-Type":
                                 "application/x-www-form-urlencoded"})
                    r = conn.getresponse()
                    r.read()
                    if not 200 <= r.status < 300:
                        return
                except (OSError, http.client.HTTPException):
                    return          # killed mid-request: seq stays unacked
                acked[cid] = seq    # ONLY after the relayed ack
                seq += 1
            conn.close()

        ths = [threading.Thread(target=client, args=(c,))
               for c in range(NC)]
        for t in ths:
            t.start()
        deadline = time.time() + 60
        while time.time() < deadline and min(acked) < 5:
            time.sleep(0.05)
        assert min(acked) >= 5, f"clients never got going: {acked}"
        proc.send_signal(signal.SIGKILL)   # mid-batch, mid-relay
        proc.wait(timeout=30)
        for t in ths:
            t.join(timeout=30)
        assert all(not t.is_alive() for t in ths), "client hung after kill"

        for cid in range(NC):
            ev = eng.do(cid % G, Request(
                method="GET", path=f"{STORE_KEYS_PREFIX}/s{cid}"))
            stored = int(ev.node.value.split(":")[1])
            assert stored >= acked[cid], \
                f"client {cid}: acked seq {acked[cid]} but engine has " \
                f"{stored} — an acked write was lost"

        # A fresh ingress over the same engine resumes service.
        proc2, port2 = _spawn_ingress(front.url)
        try:
            st, body = _put(f"http://127.0.0.1:{port2}", 0, "/s0",
                            "after-restart")
            assert st in (200, 201), (st, body)
        finally:
            proc2.kill()
            proc2.wait(timeout=30)
    finally:
        stop_ev = locals().get("stop")
        if stop_ev is not None:
            stop_ev.set()
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        front.stop()
        eng.stop()


def test_watch_hub_differential_vs_direct(tmp_path):
    """Three downstream stream watchers + one long-poll through the hub
    vs a direct engine watch: identical events in identical order, over
    ONE upstream stream."""
    import http.client
    with stack(tmp_path) as s:
        st0 = s.eng.store(0)
        since = st0.current_index + 1
        direct = st0.watch(f"{STORE_KEYS_PREFIX}/hub", recursive=True,
                           stream=True, since_index=since)

        watchers = []
        for _ in range(3):
            c = http.client.HTTPConnection("127.0.0.1", s.ing.port,
                                           timeout=30)
            c.request("GET", "/tenants/0/v2/keys/hub"
                             "?wait=true&stream=true&recursive=true")
            watchers.append((c, c.getresponse()))   # headers up => live

        poll_got = {}

        def long_poll():
            try:
                poll_got["event"] = _get_json(
                    f"{s.base}/tenants/0/v2/keys/hub"
                    f"?wait=true&recursive=true")
            except Exception as e:  # noqa: BLE001 — asserted below
                poll_got["error"] = e

        th = threading.Thread(target=long_poll, daemon=True)
        th.start()
        time.sleep(0.5)   # let all four watchers register on the hub
        assert _scrape(s.base, "etcd_ingress_hub_streams") == 1.0
        assert _scrape(s.base, "etcd_ingress_hub_watchers") == 4.0

        assert _put(s.base, 0, "/hub/a", "1")[0] == 201
        assert _put(s.base, 0, "/hub/b", "2")[0] == 201
        assert _put(s.base, 0, "/hub/a", "3", prevValue="1")[0] == 200
        req = urllib.request.Request(
            f"{s.base}/tenants/0/v2/keys/hub/b", method="DELETE")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
        assert _put(s.base, 0, "/hub/c", "4")[0] == 201
        NEV = 5

        def sig(d):
            n = d.get("node") or d.get("prevNode") or {}
            return (d["action"], n.get("key"),
                    (d.get("node") or {}).get("value"),
                    n.get("modifiedIndex"))

        want = []
        for _ in range(NEV):
            e = direct.next_event(timeout=30)
            assert e is not None, "direct watch starved"
            d = e.to_dict()
            n = d.get("node") or d.get("prevNode") or {}
            key = n.get("key", "")
            if key.startswith(STORE_KEYS_PREFIX):
                n["key"] = key[len(STORE_KEYS_PREFIX):]
            want.append(sig(d))

        for c, resp in watchers:
            got = []
            for _ in range(NEV):
                line = resp.readline()
                assert line, "hub stream ended early"
                got.append(sig(json.loads(line)))
            assert got == want, (got, want)
            c.close()
        th.join(timeout=30)
        assert sig(poll_got.get("event", {})) == want[0], poll_got
        # Last watcher gone => the hub drops the upstream stream.
        deadline = time.time() + 10
        while time.time() < deadline and \
                _scrape(s.base, "etcd_ingress_hub_streams") != 0.0:
            time.sleep(0.1)
        assert _scrape(s.base, "etcd_ingress_hub_streams") == 0.0


def _req_json(url, method="PUT", payload=None, headers=None, timeout=30):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    req.add_header("Content-Type", "application/json")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


def test_malformed_input_does_not_kill_loop(tmp_path):
    """Client-controlled garbage — a non-numeric Content-Length, a
    non-numeric waitIndex, a mangled request line — must cost that ONE
    connection a 400/close, never the shared event loop (one loop thread
    owns every connection on the ingress)."""
    with stack(tmp_path) as s:
        # Non-numeric Content-Length: 400 on this connection only.
        sk = socket.create_connection(("127.0.0.1", s.ing.port),
                                      timeout=10)
        sk.sendall(b"PUT /tenants/0/v2/keys/x HTTP/1.1\r\n"
                   b"Host: t\r\nContent-Length: banana\r\n\r\n")
        sk.settimeout(10)
        assert b" 400 " in sk.recv(4096)
        sk.close()
        # Non-numeric waitIndex: 400, not an unhandled ValueError.
        try:
            urllib.request.urlopen(
                f"{s.base}/tenants/0/v2/keys/x?wait=true&waitIndex=abc",
                timeout=10)
            raise AssertionError("bad waitIndex was accepted")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert json.loads(e.read())["errorCode"] == 203
        # Mangled request line: connection dropped, loop unharmed.
        sk2 = socket.create_connection(("127.0.0.1", s.ing.port),
                                       timeout=10)
        sk2.sendall(b"\x00\xff GARBAGE\r\n\r\n")
        sk2.settimeout(10)
        try:
            sk2.recv(4096)
        except OSError:
            pass
        sk2.close()
        # The loop survived all three: normal service continues.
        assert _put(s.base, 0, "/alive", "1")[0] == 201
        assert _get_json(f"{s.base}/tenants/0/v2/keys/alive"
                         )["node"]["value"] == "1"


def test_recursive_delete_through_ingress(tmp_path):
    """`DELETE ?recursive=true` must stay recursive through the
    coalesced batch path — dropping the flag silently turns it into a
    non-recursive delete (different result than the direct engine)."""
    with stack(tmp_path) as s:
        assert _put(s.base, 0, "/rd/a", "1")[0] == 201
        assert _put(s.base, 0, "/rd/sub/b", "2")[0] == 201
        req = urllib.request.Request(
            f"{s.base}/tenants/0/v2/keys/rd?recursive=true",
            method="DELETE")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
            body = json.loads(r.read())
        assert body["action"] == "delete", body
        try:
            urllib.request.urlopen(f"{s.base}/tenants/0/v2/keys/rd/a",
                                   timeout=10)
            raise AssertionError("recursive delete left children behind")
        except urllib.error.HTTPError as e:
            assert e.code == 404
        # The flag genuinely travels (it is not a default): the same
        # delete WITHOUT recursive refuses a non-empty dir, as direct.
        assert _put(s.base, 0, "/rd2/a", "1")[0] == 201
        req = urllib.request.Request(
            f"{s.base}/tenants/0/v2/keys/rd2", method="DELETE")
        try:
            urllib.request.urlopen(req, timeout=30)
            raise AssertionError("non-recursive delete of a dir passed")
        except urllib.error.HTTPError as e:
            assert e.code in (400, 403), e.code


def test_watch_waitindex_history_ring_and_cleared(tmp_path):
    """waitIndex semantics must match the direct path: an index older
    than the hub ring's coverage replays from upstream event history
    (never silently skipped), an index older than upstream history
    answers 401 EventIndexCleared, and an index inside the ring is
    served from the ring."""
    import http.client
    with stack(tmp_path) as s:
        _, b1 = _put(s.base, 0, "/wi/a", "1")
        i1 = b1["node"]["modifiedIndex"]
        assert _put(s.base, 0, "/wi/b", "2")[0] == 201

        # 1. Long-poll with a pre-hub waitIndex: the ring (empty — no
        # hub stream exists) cannot cover it; upstream history replays.
        ev = _get_json(f"{s.base}/tenants/0/v2/keys/wi"
                       f"?wait=true&recursive=true&waitIndex={i1}")
        assert ev["node"]["modifiedIndex"] == i1, ev

        # 2. Stream watch with an old waitIndex through the dedicated
        # proxy: the FIRST matching history event replays, then the
        # stream goes live — exactly the direct path's (reference v2)
        # stream-watch semantics, which scan history once per watch.
        c = http.client.HTTPConnection("127.0.0.1", s.ing.port,
                                       timeout=30)
        c.request("GET", f"/tenants/0/v2/keys/wi?wait=true&stream=true"
                         f"&recursive=true&waitIndex={i1}")
        resp = c.getresponse()
        assert resp.status == 200
        assert json.loads(resp.readline())["node"]["modifiedIndex"] == i1
        _, b3 = _put(s.base, 0, "/wi/c", "3")
        assert (json.loads(resp.readline())["node"]["modifiedIndex"]
                == b3["node"]["modifiedIndex"])
        c.close()

        # 3. Ring replay: a live hub stream's ring covers indexes it has
        # seen; a long-poll inside that coverage is served immediately.
        ch = http.client.HTTPConnection("127.0.0.1", s.ing.port,
                                        timeout=30)
        ch.request("GET", "/tenants/0/v2/keys/wi"
                          "?wait=true&stream=true&recursive=true")
        hub_resp = ch.getresponse()   # hub stream now live
        time.sleep(0.3)
        _, b4 = _put(s.base, 0, "/wi/d", "4")
        i4 = b4["node"]["modifiedIndex"]
        assert json.loads(hub_resp.readline()
                          )["node"]["modifiedIndex"] == i4
        ev = _get_json(f"{s.base}/tenants/0/v2/keys/wi"
                       f"?wait=true&recursive=true&waitIndex={i4}",
                       timeout=10)
        assert ev["node"]["modifiedIndex"] == i4, ev
        ch.close()

        # 4. waitIndex beyond upstream event history: 401
        # EventIndexCleared passes through — never a silent hang.
        from etcd_tpu.store.event import DEFAULT_HISTORY_CAPACITY
        roll = [Request(method="PUT",
                        path=f"{STORE_KEYS_PREFIX}/roll/{i}",
                        val=str(i))
                for i in range(DEFAULT_HISTORY_CAPACITY + 64)]
        for i in range(0, len(roll), 64):
            s.eng.do_many(0, roll[i:i + 64])
        try:
            urllib.request.urlopen(
                f"{s.base}/tenants/0/v2/keys/wi"
                f"?wait=true&recursive=true&waitIndex={i1}", timeout=30)
            raise AssertionError("cleared index did not error")
        except urllib.error.HTTPError as e:
            # Reference mapping: HTTP 400 carrying errorCode 401.
            assert e.code == 400
            assert json.loads(e.read())["errorCode"] == 401


def test_auth_identity_survives_coalescing(tmp_path):
    """With tenant security enabled, writes coalesced through the
    ingress must be authorized as THEIR client, not as the ingress's
    anonymous upstream connection: each batch slot carries its own
    client's credentials."""
    with stack(tmp_path, flush_max_requests=16) as s:
        fb = s.front.url
        auth = {"Authorization": "Basic " +
                __import__("base64").b64encode(b"root:pw").decode()}
        st, body = _req_json(fb + "/tenants/0/v2/security/users/root",
                             payload={"user": "root", "password": "pw"})
        assert st == 201, body
        st, body = _req_json(
            fb + "/tenants/0/v2/security/roles/guest",
            payload={"role": "guest", "permissions":
                     {"kv": {"read": ["/*"], "write": []}}})
        assert st == 201, body
        st, body = _req_json(fb + "/tenants/0/v2/security/enable")
        assert st == 200, body

        # Anonymous write through the ingress: denied in-slot.
        st, body = _put(s.base, 0, "/sec/anon", "x")
        assert st == 401 and body["errorCode"] == 110, (st, body)
        # Authenticated write through the SAME coalescing lane: commits.
        st, body = _put(s.base, 0, "/sec/root", "ok", headers=auth)
        assert st == 201, (st, body)
        # Interleaved in shared flush windows, each slot keeps its own
        # identity: all root writes land, all anonymous writes 401.
        outcomes = {}

        def anon(i):
            outcomes[("a", i)] = _put(s.base, 0, f"/sec/a{i}", "x")[0]

        def rootw(i):
            outcomes[("r", i)] = _put(s.base, 0, f"/sec/r{i}", "v",
                                      headers=auth)[0]

        ths = [threading.Thread(target=anon, args=(i,)) for i in range(6)]
        ths += [threading.Thread(target=rootw, args=(i,))
                for i in range(6)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
        assert all(not t.is_alive() for t in ths)
        for i in range(6):
            assert outcomes[("a", i)] == 401, outcomes
            assert outcomes[("r", i)] == 201, outcomes
        # Guest reads stay open; credentials also survive the GET
        # passthrough (the fetcher forwards Authorization).
        assert _get_json(f"{s.base}/tenants/0/v2/keys/sec/root"
                         )["node"]["value"] == "ok"
        st, body = _req_json(f"{s.base}/tenants/0/v2/security/users",
                             method="GET")
        assert st == 401, (st, body)
        st, body = _req_json(f"{s.base}/tenants/0/v2/security/users",
                             method="GET", headers=auth)
        assert st == 200 and "root" in body.get("users", []), (st, body)


def test_slow_client_wbuf_cap(tmp_path, monkeypatch):
    """A stalled reader must not grow the ingress write buffer without
    bound: past the cap the connection is dropped and counted."""
    from etcd_tpu.server import ingress as ing_mod
    from etcd_tpu.server import obs
    ing = Ingress(IngressConfig(upstream="http://127.0.0.1:1"))
    a, b = socket.socketpair()
    try:
        a.setblocking(False)
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
        conn = ing_mod._Conn(a)
        monkeypatch.setattr(ing_mod, "_MAX_WBUF", 64 * 1024)
        n0 = obs.ingress_slow_clients.value
        conn.wbuf += b"x" * (1 << 20)   # 1 MB backlog, peer never reads
        ing._flush_wbuf(conn)
        assert not conn.open, "slow client kept its connection"
        assert obs.ingress_slow_clients.value == n0 + 1
    finally:
        b.close()
        ing._lsock.close()
        ing._wake_r.close()
        ing._wake_w.close()
        ing.sel.close()


@pytest.mark.slow
def test_many_connections_fd_smoke(tmp_path):
    """The event-driven front holds INGRESS_SMOKE_CONNS (default 10k)
    concurrent client connections — thread-per-connection would need 10k
    stacks — and stays inside the process fd limit, while still serving
    writes."""
    from etcd_tpu.etcdhttp.tenants import EngineHttp
    N = int(os.environ.get("INGRESS_SMOKE_CONNS", "10000"))
    eng = make_engine(tmp_path)
    front = EngineHttp(eng)
    front.start()
    eng.start()
    proc = None
    conns = []
    try:
        assert eng.wait_leaders(60.0)
        proc, port = _spawn_ingress(front.url)
        base = f"http://127.0.0.1:{port}"
        t0 = time.time()
        while len(conns) < N:
            assert time.time() - t0 < 180, \
                f"connect stalled at {len(conns)}/{N}"
            for _ in range(min(200, N - len(conns))):
                s = socket.socket()
                try:
                    s.connect(("127.0.0.1", port))
                except OSError:
                    s.close()
                    time.sleep(0.05)
                    break
                conns.append(s)
        connect_s = time.time() - t0
        assert len(conns) == N

        used = _scrape(base, "process_open_fds")
        limit = _scrape(base, "process_max_fds")
        assert used is not None and limit is not None
        assert used >= N, (used, N)
        assert used < limit, \
            f"ingress at {used}/{limit} fds with {N} conns"

        # Still serving: a write on every 1000th held connection.
        body = b"value=alive"
        head = ("PUT /tenants/0/v2/keys/smoke HTTP/1.1\r\n"
                "Host: t\r\nContent-Type: application/"
                "x-www-form-urlencoded\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        for s in conns[::1000]:
            s.settimeout(60)
            s.sendall(head + body)
            resp = s.recv(1)
            assert resp == b"H", resp
        st, _ = _put(base, 0, "/post-smoke", "ok")
        assert st in (200, 201)
        assert connect_s < 120, f"connect phase too slow: {connect_s:.1f}s"
    finally:
        for s in conns:
            try:
                s.close()
            except OSError:
                pass
        if proc is not None:
            proc.kill()
            proc.wait(timeout=30)
        front.stop()
        eng.stop()


# ---------------------------------------------------------------------------
# binary upstream channel: pipelining, ack demux, sever semantics
# ---------------------------------------------------------------------------

class _FakeFrameUpstream:
    """A scriptable stand-in for the engine's upstream surface: each
    accepted connection's first request head is handed to `script`
    (along with the raw socket + buffered reader) on its own thread, so
    tests can ack out of order, sever mid-window, or refuse the
    batchframe handshake."""

    def __init__(self, script):
        self.script = script
        self.frames = []       # (conn_idx, flush_id, [item dict, ...])
        self.accepted = 0
        self.lsock = socket.socket()
        self.lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.lsock.bind(("127.0.0.1", 0))
        self.lsock.listen(16)
        self.port = self.lsock.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        threading.Thread(target=self._accept_loop, daemon=True).start()

    def _accept_loop(self):
        while True:
            try:
                sock, _ = self.lsock.accept()
            except OSError:
                return
            idx, self.accepted = self.accepted, self.accepted + 1
            threading.Thread(target=self._serve, args=(idx, sock),
                             daemon=True).start()

    def _serve(self, idx, sock):
        rfile = sock.makefile("rb")
        try:
            head = self._read_head(rfile)
            if head is not None:
                self.script(self, idx, sock, rfile, head)
        except OSError:
            pass
        finally:
            for f in (rfile, sock):
                try:
                    f.close()
                except OSError:
                    pass

    @staticmethod
    def _read_head(rfile):
        lines = []
        while True:
            line = rfile.readline(8192)
            if not line:
                return None if not lines else lines
            if line in (b"\r\n", b"\n"):
                return lines
            lines.append(line.rstrip(b"\r\n"))

    def read_frame(self, idx, rfile):
        from etcd_tpu.server import batchframe
        from etcd_tpu.server.engine import _unpack_multi
        frame = batchframe.read_request_frame(rfile)
        if frame is None:
            return None
        fid, _auth, payload = frame
        items = [json.loads(b) for b in _unpack_multi(payload)]
        self.frames.append((idx, fid, items))
        return fid, items

    @staticmethod
    def ack(sock, fid, slots):
        from etcd_tpu.server import batchframe
        sock.sendall(batchframe.pack_response_frame(fid, slots))

    def close(self):
        try:
            self.lsock.close()
        except OSError:
            pass


def _raw_put(port, t, key, val, timeout=30):
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    body = f"value={val}".encode()
    s.sendall((f"PUT /tenants/{t}/v2/keys{key} HTTP/1.1\r\nHost: t\r\n"
               "Content-Type: application/x-www-form-urlencoded\r\n"
               f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    return s


def _read_http_response(s, timeout=30):
    s.settimeout(timeout)
    buf = b""
    while b"\r\n\r\n" not in buf:
        d = s.recv(4096)
        if not d:
            raise OSError("connection closed before response head")
        buf += d
    head, _, rest = buf.partition(b"\r\n\r\n")
    lines = head.decode().split("\r\n")
    status = int(lines[0].split()[1])
    clen = 0
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        if k.strip().lower() == "content-length":
            clen = int(v)
    while len(rest) < clen:
        d = s.recv(4096)
        if not d:
            raise OSError("connection closed mid-body")
        rest += d
    return status, rest[:clen]


def _wait_frames(srv, n, timeout=15):
    t0 = time.time()
    while len(srv.frames) < n:
        assert time.time() - t0 < timeout, \
            f"upstream saw {len(srv.frames)}/{n} frames"
        time.sleep(0.01)


def test_out_of_order_ack_demux():
    """Two pipelined flushes acked in REVERSE order: each client still
    receives exactly its own slot's response (demux is by flush id, not
    arrival order)."""
    from etcd_tpu.server import batchframe
    done = threading.Event()

    def script(srv, idx, sock, rfile, head):
        sock.sendall(batchframe.handshake_response())
        f1 = srv.read_frame(idx, rfile)
        f2 = srv.read_frame(idx, rfile)
        for fid, items in (f2, f1):          # reverse order on purpose
            srv.ack(sock, fid, [
                (200, json.dumps({"echo": it["path"]}).encode() + b"\n")
                for it in items])
        done.wait(30)

    srv = _FakeFrameUpstream(script)
    ing = Ingress(IngressConfig(upstream=srv.url, flush_max_requests=1,
                                flush_window=2, upstream_mode="frame"))
    ing.start()
    c1 = c2 = None
    try:
        c1 = _raw_put(ing.port, 0, "/ooo/a", "1")
        _wait_frames(srv, 1)     # flush 1 is in flight before flush 2
        c2 = _raw_put(ing.port, 0, "/ooo/b", "2")
        _wait_frames(srv, 2)
        st2, body2 = _read_http_response(c2)
        st1, body1 = _read_http_response(c1)
        assert (st1, json.loads(body1)["echo"]) == (200, "/ooo/a")
        assert (st2, json.loads(body2)["echo"]) == (200, "/ooo/b")
        assert [fid for _, fid, _ in srv.frames] == [1, 2]
    finally:
        done.set()
        for c in (c1, c2):
            if c is not None:
                c.close()
        ing.stop()
        srv.close()


def test_midwindow_sever_503s_exactly_inflight():
    """The upstream dies with two flushes in the window, having acked
    only the first: the acked client keeps its 200, the unacked one
    gets a 503, and after reconnect the next flush carries ONLY new
    writes — the severed flush is never re-sent (double-apply/CAS
    hazard)."""
    from etcd_tpu.server import batchframe, obs

    def script(srv, idx, sock, rfile, head):
        sock.sendall(batchframe.handshake_response())
        if idx == 0:
            f1 = srv.read_frame(idx, rfile)
            srv.read_frame(idx, rfile)       # flush 2: never acked
            srv.ack(sock, f1[0], [(200, b'{"ok": 1}\n')])
            time.sleep(0.1)                  # let the ack land first
            return                           # abrupt close = sever
        while True:                          # the reconnect channel
            f = srv.read_frame(idx, rfile)
            if f is None:
                return
            srv.ack(sock, f[0], [
                (200, b'{"ok": 2}\n') for _ in f[1]])

    srv = _FakeFrameUpstream(script)
    ing = Ingress(IngressConfig(upstream=srv.url, flush_max_requests=1,
                                flush_window=2, upstream_mode="frame"))
    ing.start()
    conns = []
    try:
        n_sev = obs.ingress_upstream_severed.value
        n_rec = obs.ingress_upstream_reconnects.value
        c1 = _raw_put(ing.port, 0, "/sev/a", "1")
        conns.append(c1)
        _wait_frames(srv, 1)
        c2 = _raw_put(ing.port, 0, "/sev/b", "2")
        conns.append(c2)
        _wait_frames(srv, 2)
        st1, body1 = _read_http_response(c1)
        assert st1 == 200 and json.loads(body1)["ok"] == 1
        st2, body2 = _read_http_response(c2)
        assert st2 == 503, (st2, body2)
        assert "severed" in json.loads(body2)["cause"]
        assert obs.ingress_upstream_severed.value == n_sev + 1

        time.sleep(0.3)          # past the 0.05s reconnect backoff
        c3 = _raw_put(ing.port, 0, "/sev/c", "3")
        conns.append(c3)
        st3, _body3 = _read_http_response(c3)
        assert st3 == 200
        assert obs.ingress_upstream_reconnects.value > n_rec
        # The reconnect channel saw ONLY the new write: no retry of the
        # severed flush.
        replayed = [it["path"] for cidx, _, items in srv.frames
                    if cidx == 1 for it in items]
        assert replayed == ["/sev/c"], replayed
    finally:
        for c in conns:
            c.close()
        ing.stop()
        srv.close()


def test_auto_mode_falls_back_to_json_path():
    """An upstream that routes /batch but refuses the batchframe
    handshake (e.g. an older router): the lane flips to the round-10
    JSON path — the SAME batch commits there, no client-visible error,
    and the fallback is counted."""
    from etcd_tpu.server import obs

    def script(srv, idx, sock, rfile, head):
        target = head[0].split(b" ")[1]
        if b"batchframe" in target:
            sock.sendall(b"HTTP/1.1 404 Not Found\r\n"
                         b"Content-Length: 0\r\n\r\n")
            return
        # Minimal JSON /tenants/{t}/batch server (connection reuse).
        while True:
            clen = 0
            for ln in head:
                k, _, v = ln.partition(b":")
                if k.strip().lower() == b"content-length":
                    clen = int(v)
            reqs = json.loads(rfile.read(clen))["reqs"]
            results = [{"status": 201, "event":
                        {"action": "set",
                         "node": {"key": r["path"], "value": r["value"]}}}
                       for r in reqs]
            data = json.dumps({"results": results}).encode()
            sock.sendall(b"HTTP/1.1 200 OK\r\n"
                         b"Content-Type: application/json\r\n" +
                         f"Content-Length: {len(data)}\r\n\r\n".encode()
                         + data)
            head = srv._read_head(rfile)
            if head is None:
                return

    srv = _FakeFrameUpstream(script)
    ing = Ingress(IngressConfig(upstream=srv.url,
                                upstream_mode="auto"))
    ing.start()
    try:
        n_fb = obs.ingress_upstream_fallbacks.value
        c = _raw_put(ing.port, 0, "/fb/a", "1")
        st, body = _read_http_response(c)
        c.close()
        assert st == 201, (st, body)
        assert json.loads(body)["node"]["value"] == "1"
        assert obs.ingress_upstream_fallbacks.value == n_fb + 1
    finally:
        ing.stop()
        srv.close()


def test_frame_fifo_across_flush_window(tmp_path):
    """Per-client FIFO with flush_window > 1 against a REAL engine:
    tiny flush caps force each client's sequential writes across many
    pipelined flushes; every client must still observe monotone
    modifiedIndex and its own value sequence in the store history."""
    with stack(tmp_path, flush_max_requests=2, flush_window=4,
               upstream_mode="frame") as s:
        N_CLIENTS, N_WRITES = 12, 10
        results = {}

        def client(c):
            t = c % G
            out = []
            for i in range(N_WRITES):
                st, body = _put(s.base, t, f"/fifo/c{c}", f"v{c}_{i}")
                out.append((st, body["node"]["modifiedIndex"]))
            results[c] = out

        ths = [threading.Thread(target=client, args=(c,))
               for c in range(N_CLIENTS)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert all(not t.is_alive() for t in ths)
        for c, out in results.items():
            sts = [st for st, _ in out]
            assert sts[0] == 201 and all(x == 200 for x in sts[1:]), sts
            idxs = [i for _, i in out]
            assert idxs == sorted(idxs) and len(set(idxs)) == len(idxs), \
                (c, idxs)
        # The channel really pipelined (frames went up) and nothing fell
        # back to JSON.
        sent = _scrape(s.base,
                       'etcd_ingress_upstream_frames_total'
                       '{direction="sent"}')
        assert sent is not None and sent > 0
        # Each client's final value survives.
        for c in range(N_CLIENTS):
            v = _get_json(f"{s.base}/tenants/{c % G}/v2/keys/fifo/c{c}"
                          )["node"]["value"]
            assert v == f"v{c}_{N_WRITES - 1}", (c, v)


def test_pure_python_fallback_leg(tmp_path):
    """use_native=False serves identically through the reference scan /
    format path (the leg CI pins so the C extension never becomes
    load-bearing): pipelined requests on one socket, then a real write."""
    with stack(tmp_path, use_native=False) as s:
        assert s.ing.use_native is False
        # Two pipelined PUTs on one connection parse + dispatch in order.
        c = socket.create_connection(("127.0.0.1", s.ing.port), timeout=30)
        reqs = b""
        for i in range(2):
            body = f"value=p{i}".encode()
            reqs += ((f"PUT /tenants/0/v2/keys/pyfb{i} HTTP/1.1\r\n"
                      "Host: t\r\nContent-Type: "
                      "application/x-www-form-urlencoded\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n"
                      ).encode() + body)
        c.sendall(reqs)
        for i in range(2):
            st, body = _read_http_response(c)
            assert st == 201, (i, st, body)
        c.close()
        assert _scrape(s.base, "etcd_ingress_native_enabled") == 0.0
