"""Batched write surface (MultiEngine.do_many + POST /tenants/{t}/batch):
the upstream half of the coalescing ingress tier.

Pins the demux contract the ingress relies on: results come back one per
request IN ORDER, application errors (failed CAS, missing key) occupy
their slot without poisoning batch-mates, and — the WAL-compat pin — a
workload shipped as do_many batches replays IDENTICALLY to the same
workload as N single do() calls (store dump, index, event history, watch
replay), because do_many feeds the same P_MULTI packing the round loop
already applies to concurrent do() traffic.
"""
import json
import threading
import urllib.error
import urllib.request

import pytest

from etcd_tpu import errors
from etcd_tpu.server.engine import EngineConfig, MultiEngine
from etcd_tpu.server.request import Request

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


def ev_sig(e):
    def nd(x):
        if x is None:
            return None
        return (x.key, x.value, x.dir, x.created_index, x.modified_index,
                x.expiration)
    return (e.action, nd(e.node), nd(e.prev_node), e.etcd_index)


def history_replay(st):
    hist = st.watcher_hub.event_history
    out = []
    i = hist.start_index
    while i <= hist.last_index:
        e = hist.scan("/", True, i)
        if e is None:
            break
        out.append(ev_sig(e))
        i = e.etcd_index + 1
    return out


def watch_replay(st, since):
    w = st.watch("/", recursive=True, stream=True, since_index=since)
    out = []
    while True:
        e = w.next_event(timeout=0.05)
        if e is None:
            return out
        out.append(ev_sig(e))


def test_do_many_in_slot_errors_and_order(tmp_path):
    """One batch mixing successes with a failing CAS and a DELETE of a
    missing key: every slot answers, errors stay in their slot, and the
    successful writes apply in submission order (monotone modifiedIndex
    along the batch)."""
    eng = make_engine(tmp_path)
    eng.start()
    try:
        assert eng.wait_leaders(60.0)
        reqs = [
            Request(method="PUT", path="/a", val="1"),
            Request(method="PUT", path="/a", val="2"),
            Request(method="PUT", path="/a", val="nope",
                    prev_value="wrong"),          # CAS fails: 101
            Request(method="PUT", path="/b", val="1"),
            Request(method="DELETE", path="/missing"),  # 100
            Request(method="PUT", path="/c", val="1"),
        ]
        out = eng.do_many(0, reqs)
        assert len(out) == len(reqs)
        assert isinstance(out[2], errors.EtcdError)
        assert out[2].code == errors.ECODE_TEST_FAILED
        assert isinstance(out[4], errors.EtcdError)
        assert out[4].code == errors.ECODE_KEY_NOT_FOUND
        oks = [out[i] for i in (0, 1, 3, 5)]
        assert all(not isinstance(e, errors.EtcdError) for e in oks)
        idxs = [e.node.modified_index for e in oks]
        assert idxs == sorted(idxs) and len(set(idxs)) == len(idxs)
        # The CAS failure didn't poison batch-mates: /a kept slot 1's
        # value, /b and /c exist.
        assert eng.do(0, Request(method="GET", path="/a")).node.value == "2"
        assert eng.do(0, Request(method="GET", path="/c")).node.value == "1"
    finally:
        eng.stop()


def test_do_many_rejects_read_methods(tmp_path):
    """Plain GETs never belong in a write batch (the ingress proxies
    them); do_many refuses the whole call before enqueueing anything."""
    eng = make_engine(tmp_path / "m")
    try:
        with pytest.raises(errors.EtcdError, match="bad batch method"):
            eng.do_many(0, [Request(method="GET", path="/x")])
    finally:
        eng.stop()


def _workload(g):
    """The event-producing shapes, parameterized per group."""
    return [
        Request(method="PUT", path="/k0", val=f"v{g}_0"),
        Request(method="PUT", path="/k1", val=f"v{g}_1"),
        Request(method="PUT", path="/k0", val="swapped",
                prev_value=f"v{g}_0"),
        Request(method="POST", path="/q", val="job"),
        Request(method="PUT", path="/new", val="n", prev_exist=False),
        Request(method="DELETE", path="/k1"),
        Request(method="PUT", path="/k0", val="nope",
                prev_value="wrong"),              # fails: 101
        Request(method="PUT", path="/k2", val=f"v{g}_2"),
    ]


def _result_sig(r):
    if isinstance(r, errors.EtcdError):
        return ("err", r.code, r.cause)
    return ev_sig(r)


def _state_after_restart(tmp):
    eng2 = make_engine(tmp)   # restart: state = WAL replay only
    try:
        state = {}
        for g in range(G):
            st = eng2.store(g)
            dump = st.get("/", recursive=True, want_sorted=True)
            state[g] = {"dump": ev_sig(dump),
                        "index": st.current_index,
                        "history": history_replay(st),
                        "watch": watch_replay(st, 1)}
        return state
    finally:
        eng2.stop()


def test_wal_replay_do_many_matches_singles(tmp_path):
    """WAL-compat pin: the same per-group workload shipped (a) as N
    sequential do() calls and (b) as do_many batches must be observably
    identical after a restart — the batch path writes the same P_MULTI
    entries the single path coalesces into, so replay cannot tell them
    apart."""
    d_single, d_batch = tmp_path / "single", tmp_path / "batch"

    eng = make_engine(d_single)
    eng.start()
    r_single = {}
    try:
        assert eng.wait_leaders(60.0)

        def client(g):
            out = []
            for r in _workload(g):
                try:
                    out.append(ev_sig(eng.do(g, r, timeout=30)))
                except errors.EtcdError as e:
                    out.append(("err", e.code, e.cause))
            r_single[g] = out

        ths = [threading.Thread(target=client, args=(g,)) for g in range(G)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert all(not t.is_alive() for t in ths)
    finally:
        eng.stop()

    eng = make_engine(d_batch)
    eng.start()
    r_batch = {}
    try:
        assert eng.wait_leaders(60.0)

        def bclient(g):
            # Two flush windows per group, like the ingress would ship.
            w = _workload(g)
            out = [_result_sig(r) for r in eng.do_many(g, w[:5])]
            out += [_result_sig(r) for r in eng.do_many(g, w[5:])]
            r_batch[g] = out

        ths = [threading.Thread(target=bclient, args=(g,))
               for g in range(G)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert all(not t.is_alive() for t in ths)
    finally:
        eng.stop()

    assert r_single == r_batch, "client-visible results diverged"
    s1, s2 = _state_after_restart(d_single), _state_after_restart(d_batch)
    for g in range(G):
        assert s1[g]["index"] == s2[g]["index"], g
        assert s1[g]["dump"] == s2[g]["dump"], g
        assert s1[g]["history"] == s2[g]["history"], g
        assert s1[g]["watch"] == s2[g]["watch"], g


def test_batch_http_route(tmp_path):
    """POST /tenants/{t}/batch: slot-aligned results with mixed outcomes,
    201 vs 200 status mapping, tenant isolation, and the refusals (wrong
    verb, malformed body, path escape)."""
    from etcd_tpu.etcdhttp.tenants import EngineHttp

    def post(url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(), method="POST")
        req.add_header("Content-Type", "application/json")
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"null")

    eng = make_engine(tmp_path)
    front = EngineHttp(eng)
    front.start()
    eng.start()
    base = front.url
    try:
        assert eng.wait_leaders(60.0)
        st, body = post(f"{base}/tenants/0/batch", {"reqs": [
            {"method": "PUT", "path": "/a", "value": "1"},
            {"method": "PUT", "path": "/a", "value": "2"},
            {"method": "PUT", "path": "/a", "value": "x",
             "prevValue": "wrong"},
            {"method": "DELETE", "path": "/missing"},
            {"method": "POST", "path": "/q", "value": "job"},
        ]})
        assert st == 200
        rs = body["results"]
        assert [r["status"] for r in rs] == [201, 200, 412, 404, 201]
        assert rs[0]["event"]["node"]["value"] == "1"
        assert rs[1]["event"]["action"] == "set"
        assert rs[2]["error"]["errorCode"] == 101
        # Error causes are tenant-relative (no internal store prefix).
        assert not rs[3]["error"]["cause"].startswith("/_etcd")
        # Batch writes are tenant-scoped like every other route.
        st, body = post(f"{base}/tenants/1/batch",
                        [{"method": "PUT", "path": "/a", "value": "t1"}])
        assert st == 200 and body["results"][0]["status"] == 201
        with urllib.request.urlopen(
                f"{base}/tenants/1/v2/keys/a", timeout=15) as r:
            assert json.loads(r.read())["node"]["value"] == "t1"
        with urllib.request.urlopen(
                f"{base}/tenants/0/v2/keys/a", timeout=15) as r:
            assert json.loads(r.read())["node"]["value"] == "2"
        # Refusals.
        st, _ = post(f"{base}/tenants/0/batch", {"reqs": []})
        assert st == 200
        st, _ = post(f"{base}/tenants/0/batch", {"reqs": "nope"})
        assert st == 400
        st, body = post(f"{base}/tenants/0/batch",
                        [{"method": "GET", "path": "/a"}])
        assert st == 400 or body.get("results") is None
        st, body = post(f"{base}/tenants/0/batch",
                        [{"method": "PUT", "path": "/../../escape",
                          "value": "x"}])
        assert st in (400, 403)
        req = urllib.request.Request(f"{base}/tenants/0/batch",
                                     method="GET")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=15)
        assert ei.value.code == 405
    finally:
        front.stop()
        eng.stop()


def test_batch_per_slot_auth(tmp_path):
    """Each batch slot is authorized under ITS OWN forwarded credentials
    ("auth" field), not the carrying connection's: the ingress coalesces
    many clients' writes onto one upstream socket, so without per-slot
    identity every ACL would evaluate against one anonymous peer."""
    import base64

    from etcd_tpu.etcdhttp.tenants import EngineHttp

    def post(url, payload, headers=None):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(), method="POST")
        req.add_header("Content-Type", "application/json")
        for k, v in (headers or {}).items():
            req.add_header(k, v)
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"null")

    def put_json(url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(), method="PUT")
        req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read() or b"null")

    eng = make_engine(tmp_path)
    front = EngineHttp(eng)
    front.start()
    eng.start()
    base = front.url
    try:
        assert eng.wait_leaders(60.0)
        st, _ = put_json(f"{base}/tenants/0/v2/security/users/root",
                         {"user": "root", "password": "pw"})
        assert st == 201
        st, _ = put_json(f"{base}/tenants/0/v2/security/roles/guest",
                         {"role": "guest", "permissions":
                          {"kv": {"read": ["/*"], "write": []}}})
        assert st == 201
        st, _ = put_json(f"{base}/tenants/0/v2/security/enable", {})
        assert st == 200

        root = "Basic " + base64.b64encode(b"root:pw").decode()
        # One batch, mixed identities, anonymous carrier connection:
        # the authed slot commits, the anonymous slot 401s IN-SLOT.
        st, body = post(f"{base}/tenants/0/batch", {"reqs": [
            {"method": "PUT", "path": "/mix/anon", "value": "x"},
            {"method": "PUT", "path": "/mix/root", "value": "ok",
             "auth": root},
        ]})
        assert st == 200, body
        rs = body["results"]
        assert rs[0]["status"] == 401, rs
        assert rs[0]["error"]["errorCode"] == 110, rs
        assert rs[1]["status"] == 201, rs
        # A malformed auth field fails the whole batch loudly (400).
        st, body = post(f"{base}/tenants/0/batch", {"reqs": [
            {"method": "PUT", "path": "/mix/bad", "value": "x",
             "auth": 42}]})
        assert st == 400, body
    finally:
        front.stop()
        eng.stop()


# ---------------------------------------------------------------------------
# the binary upstream channel (POST /tenants/{t}/batchframe + upgrade)
# ---------------------------------------------------------------------------

def test_p_multi_tag_pin():
    """batchframe.P_MULTI is a mirror (the ingress process must not
    import the engine): pin it to the engine's authoritative value."""
    from etcd_tpu.server import batchframe, engine
    assert batchframe.P_MULTI == engine.P_MULTI


def _item(r):
    """Request -> the item-dict JSON of the /batch(frame) slot schema."""
    d = {"method": r.method, "path": r.path}
    if r.val is not None:
        d["value"] = r.val
    if r.prev_value is not None:
        d["prevValue"] = r.prev_value
    if r.prev_exist is not None:
        d["prevExist"] = r.prev_exist
    if r.prev_index:
        d["prevIndex"] = r.prev_index
    return d


def _open_channel(port, tenant):
    import socket

    from etcd_tpu.server import batchframe
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    sock.sendall(batchframe.handshake_request(tenant, "t"))
    rfile = sock.makefile("rb")
    assert batchframe.read_handshake_status(rfile) == 101
    return sock, rfile


def test_batchframe_route_and_wal_parity(tmp_path):
    """The binary channel is observably the JSON /batch route: the same
    per-group workload shipped as PIPELINED request frames (both frames
    on the wire before the first response is read) returns the same
    slot statuses, and after a restart the store state is identical to
    a JSON-batch twin — both transports feed the same P_MULTI entries,
    so WAL replay cannot tell them apart."""
    from etcd_tpu import native
    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server import batchframe

    d_frame, d_batch = tmp_path / "frame", tmp_path / "batch"

    eng = make_engine(d_frame)
    front = EngineHttp(eng)
    front.start()
    eng.start()
    frame_status = {}
    try:
        assert eng.wait_leaders(60.0)
        for g in range(G):
            w = _workload(g)
            sock, rfile = _open_channel(front.http.port, g)
            try:
                for fid, part in ((7, w[:5]), (8, w[5:])):
                    payload = native.pack_multi(
                        [(0, b"\x00" + json.dumps(_item(r)).encode())
                         for r in part], batchframe.P_MULTI)
                    sock.sendall(batchframe.pack_request_frame(
                        fid, b"", payload))
                sts = []
                for fid in (7, 8):
                    rid, slots, err = batchframe.read_response_frame(rfile)
                    assert rid == fid and err == (), (rid, err)
                    sts += [s for s, _ in slots]
                frame_status[g] = sts
                # Slot bodies are final client-facing JSON.
                assert json.loads(slots[-1][1])["node"]["key"] == "/k2"
            finally:
                sock.close()
        # Mixed outcomes land in their slots: CAS fail 412, rest applied.
        for g in range(G):
            assert frame_status[g] == [201, 201, 200, 201, 201,
                                       200, 412, 201], frame_status[g]
    finally:
        front.stop()
        eng.stop()

    eng = make_engine(d_batch)
    front = EngineHttp(eng)
    front.start()
    eng.start()
    try:
        assert eng.wait_leaders(60.0)
        for g in range(G):
            w = _workload(g)
            for part in (w[:5], w[5:]):
                req = urllib.request.Request(
                    f"{front.url}/tenants/{g}/batch",
                    data=json.dumps(
                        {"reqs": [_item(r) for r in part]}).encode(),
                    method="POST")
                req.add_header("Content-Type", "application/json")
                with urllib.request.urlopen(req, timeout=30) as r:
                    assert r.status == 200
    finally:
        front.stop()
        eng.stop()

    s1, s2 = _state_after_restart(d_frame), _state_after_restart(d_batch)
    for g in range(G):
        assert s1[g]["index"] == s2[g]["index"], g
        assert s1[g]["dump"] == s2[g]["dump"], g
        assert s1[g]["history"] == s2[g]["history"], g
        assert s1[g]["watch"] == s2[g]["watch"], g


def test_batchframe_error_frame_and_handshake_refusals(tmp_path):
    """Channel input failures answer as FRAME-LEVEL errors (the flush
    fails loudly, the channel survives), and the handshake refuses
    non-upgrade requests with 426."""
    import urllib.request

    from etcd_tpu import native
    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server import batchframe

    eng = make_engine(tmp_path)
    front = EngineHttp(eng)
    front.start()
    eng.start()
    try:
        assert eng.wait_leaders(60.0)
        sock, rfile = _open_channel(front.http.port, 0)
        try:
            # Garbage payload -> error frame with FRAME_ERROR marker.
            sock.sendall(batchframe.pack_request_frame(3, b"", b"junk"))
            fid, slots, err = batchframe.read_response_frame(rfile)
            assert fid == 3 and slots is None and err[0] == 400, (fid, err)
            # The channel still works after the bad frame.
            payload = native.pack_multi(
                [(0, b"\x00" + json.dumps(
                    {"method": "PUT", "path": "/alive", "value": "1"}
                  ).encode())], batchframe.P_MULTI)
            sock.sendall(batchframe.pack_request_frame(4, b"", payload))
            fid, slots, err = batchframe.read_response_frame(rfile)
            assert fid == 4 and err == () and slots[0][0] == 201
        finally:
            sock.close()
        # No Upgrade header -> 426, connection stays HTTP.
        req = urllib.request.Request(
            f"{front.url}/tenants/0/batchframe", data=b"", method="POST")
        try:
            urllib.request.urlopen(req, timeout=15)
            assert False, "expected 426"
        except urllib.error.HTTPError as e:
            assert e.code == 426
    finally:
        front.stop()
        eng.stop()

def test_batchframe_sever_midflight_collects_staged_flushes(tmp_path):
    """A channel severed with flushes still staged (the ingress
    SIGKILL) must not leak them: the engine-side collector keeps
    draining its queue and COLLECTS every staged flush even though the
    responses have nowhere to go — otherwise each abandoned slot pins
    etcd_server_pending_proposal_total forever (a drain barrier on
    that gauge then hangs after the kill)."""
    import socket
    import struct
    import time

    from etcd_tpu import native
    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server import batchframe
    from etcd_tpu.utils import metrics

    eng = make_engine(tmp_path)
    front = EngineHttp(eng)
    front.start()
    eng.start()
    try:
        assert eng.wait_leaders(60.0)
        base = metrics.propose_pending.value
        sock, rfile = _open_channel(front.http.port, 0)
        for fid in range(1, 4):
            payload = native.pack_multi(
                [(0, b"\x00" + json.dumps(
                    {"method": "PUT", "path": f"/sv/{fid}_{i}",
                     "value": "x"}).encode()) for i in range(3)],
                batchframe.P_MULTI)
            sock.sendall(batchframe.pack_request_frame(fid, b"", payload))
        # RST the channel without reading a single response — the
        # collector's frame writes fail mid-queue.
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
        rfile.close()
        sock.close()
        deadline = time.time() + 30
        while time.time() < deadline:
            if metrics.propose_pending.value <= base:
                break
            time.sleep(0.1)
        assert metrics.propose_pending.value <= base, \
            metrics.propose_pending.value
        # The endpoint survives the sever: a fresh channel works. (How
        # many of the severed flushes committed is NOT asserted — the
        # RST may have cut frames the engine had not read yet; the
        # invariant is that whatever WAS staged got collected.)
        sock2, rfile2 = _open_channel(front.http.port, 0)
        try:
            payload = native.pack_multi(
                [(0, b"\x00" + json.dumps(
                    {"method": "PUT", "path": "/sv/after",
                     "value": "y"}).encode())], batchframe.P_MULTI)
            sock2.sendall(batchframe.pack_request_frame(9, b"", payload))
            fid, slots, err = batchframe.read_response_frame(rfile2)
            assert fid == 9 and err == () and slots[0][0] == 201
        finally:
            sock2.close()
    finally:
        front.stop()
        eng.stop()
