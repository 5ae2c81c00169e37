"""HTTP API + HTTP peer-transport integration tests (§4 T4 analogue over
real listeners): a localhost cluster of embed.Etcd members exercising the
/v2/keys matrix, headers, watches, members/stats/version/health endpoints —
modeled on reference integration/v2_http_kv_test.go and cluster_test.go.
"""
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from etcd_tpu.embed import Etcd, EtcdConfig


def free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def req(method, url, body=None, headers=None, timeout=10.0):
    """Returns (status, headers, parsed-json-or-text)."""
    r = urllib.request.Request(url, data=body, method=method,
                               headers=headers or {})
    try:
        resp = urllib.request.urlopen(r, timeout=timeout)
        status, hdrs, data = resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        status, hdrs, data = e.code, dict(e.headers), e.read()
    try:
        parsed = json.loads(data) if data else None
    except json.JSONDecodeError:
        parsed = data.decode()
    return status, hdrs, parsed


def form(d):
    from urllib.parse import urlencode
    return urlencode(d).encode()


FORM_HDR = {"Content-Type": "application/x-www-form-urlencoded"}


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("httpcluster")
    n = 3
    ports = free_ports(2 * n)
    peer_urls = {f"m{i}": [f"http://127.0.0.1:{ports[i]}"] for i in range(n)}
    members = []
    for i in range(n):
        name = f"m{i}"
        cfg = EtcdConfig(
            name=name, data_dir=str(tmp / name),
            initial_cluster=peer_urls,
            listen_client_urls=[f"http://127.0.0.1:{ports[n + i]}"],
            tick_ms=10, request_timeout=5.0)
        members.append(Etcd(cfg))
    for m in members:
        m.start()
    assert all(m.wait_leader(10) for m in members)
    assert settled(members)
    yield members
    for m in members:
        m.stop()


def settled(members, timeout=20.0):
    """Every member names the same leader: after boot, and again after the
    cluster lost one (at tick_ms=10 an election is 100 ms of silence away,
    and six test workers on one machine give a thread that much)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        leads = {m.server.leader_id for m in members}
        if len(leads) == 1 and 0 not in leads:
            return True
        time.sleep(0.02)
    return False


def curl(cluster, method, path, body=None, headers=None, member=0):
    """One request to one member, sent again across a leader change: a
    proposal answered 301 ("no leader") was dropped, never logged, so the
    client sends it again once there is a leader, as real etcd clients
    do. Every other answer is returned as it came."""
    base = cluster[member].client_urls[0]
    deadline = time.time() + 30
    while True:
        st, hdrs, parsed = req(method, base + path, body, headers)
        dropped = (st == 500 and isinstance(parsed, dict)
                   and parsed.get("errorCode") == 301)
        if not dropped or time.time() > deadline:
            return st, hdrs, parsed
        settled(cluster)


class TestKeys:
    def test_set_get_roundtrip(self, cluster):
        st, hd, body = curl(cluster, "PUT", "/v2/keys/foo",
                            form({"value": "bar"}), FORM_HDR)
        # A set that creates answers 201 (reference store/event.go IsCreated
        # + client.go writeKeyEvent:546).
        assert st == 201 and body["action"] == "set"
        assert body["node"]["key"] == "/foo"
        assert body["node"]["value"] == "bar"
        assert int(hd["X-Etcd-Index"]) >= 1
        assert "X-Etcd-Cluster-ID" in hd

        st, hd, body = curl(cluster, "GET", "/v2/keys/foo")
        assert st == 200 and body["action"] == "get"
        assert body["node"]["value"] == "bar"

    def test_get_missing_404(self, cluster):
        st, hd, body = curl(cluster, "GET", "/v2/keys/nope")
        assert st == 404
        assert body["errorCode"] == 100
        assert body["message"] == "Key not found"

    def test_create_in_order_post(self, cluster):
        st, _, b1 = curl(cluster, "POST", "/v2/keys/queue",
                         form({"value": "a"}), FORM_HDR)
        assert st == 201 and b1["action"] == "create"
        st, _, b2 = curl(cluster, "POST", "/v2/keys/queue",
                         form({"value": "b"}), FORM_HDR)
        k1 = int(b1["node"]["key"].rsplit("/", 1)[1])
        k2 = int(b2["node"]["key"].rsplit("/", 1)[1])
        assert k2 > k1
        st, _, body = curl(cluster, "GET",
                           "/v2/keys/queue?recursive=true&sorted=true")
        vals = [n["value"] for n in body["node"]["nodes"]]
        assert vals == ["a", "b"]

    def test_cas(self, cluster):
        curl(cluster, "PUT", "/v2/keys/cas", form({"value": "one"}),
             FORM_HDR)
        st, _, body = curl(cluster, "PUT",
                           "/v2/keys/cas?prevValue=two",
                           form({"value": "three"}), FORM_HDR)
        assert st == 412 or st == 400  # compare failed
        assert body["errorCode"] == 101
        st, _, body = curl(cluster, "PUT",
                           "/v2/keys/cas?prevValue=one",
                           form({"value": "two"}), FORM_HDR)
        assert st == 200 and body["action"] == "compareAndSwap"
        assert body["prevNode"]["value"] == "one"

    def test_cad(self, cluster):
        curl(cluster, "PUT", "/v2/keys/cad", form({"value": "x"}), FORM_HDR)
        st, _, body = curl(cluster, "DELETE",
                           "/v2/keys/cad?prevValue=wrong")
        assert body["errorCode"] == 101
        st, _, body = curl(cluster, "DELETE", "/v2/keys/cad?prevValue=x")
        assert st == 200 and body["action"] == "compareAndDelete"

    def test_prev_exist_create(self, cluster):
        st, _, body = curl(cluster, "PUT",
                           "/v2/keys/pe?prevExist=false",
                           form({"value": "v"}), FORM_HDR)
        assert st == 201 and body["action"] == "create"
        st, _, body = curl(cluster, "PUT",
                           "/v2/keys/pe?prevExist=false",
                           form({"value": "v2"}), FORM_HDR)
        assert body["errorCode"] == 105  # already exists
        st, _, body = curl(cluster, "PUT",
                           "/v2/keys/pe?prevExist=true",
                           form({"value": "v2"}), FORM_HDR)
        assert st == 200 and body["action"] == "update"

    def test_dir_and_recursive_delete(self, cluster):
        curl(cluster, "PUT", "/v2/keys/d/a", form({"value": "1"}), FORM_HDR)
        curl(cluster, "PUT", "/v2/keys/d/b", form({"value": "2"}), FORM_HDR)
        st, _, body = curl(cluster, "GET", "/v2/keys/d")
        assert body["node"]["dir"] is True
        st, _, body = curl(cluster, "DELETE", "/v2/keys/d")
        assert body["errorCode"] == 102  # not a file
        st, _, body = curl(cluster, "DELETE", "/v2/keys/d?dir=true")
        assert body["errorCode"] == 108  # dir not empty
        st, _, body = curl(cluster, "DELETE",
                           "/v2/keys/d?recursive=true")
        assert st == 200 and body["action"] == "delete"

    def test_ttl_visible(self, cluster):
        st, _, body = curl(cluster, "PUT", "/v2/keys/ttlkey",
                           form({"value": "v", "ttl": "100"}), FORM_HDR)
        assert st == 201
        assert body["node"]["ttl"] >= 99
        assert "expiration" in body["node"]

    def test_refresh_keeps_value_extends_ttl(self, cluster):
        curl(cluster, "PUT", "/v2/keys/rfr",
             form({"value": "keepme", "ttl": "5"}), FORM_HDR)
        st, _, body = curl(cluster, "PUT",
                           "/v2/keys/rfr?refresh=true",
                           form({"ttl": "500"}), FORM_HDR)
        assert st == 200, body
        assert body["node"]["value"] == "keepme"
        assert body["node"]["ttl"] > 400
        st, _, body = curl(cluster, "GET", "/v2/keys/rfr")
        assert body["node"]["value"] == "keepme"
        assert body["node"]["ttl"] > 400
        # refresh without a TTL is rejected (code 213)
        st, _, body = curl(cluster, "PUT", "/v2/keys/rfr?refresh=true")
        assert body["errorCode"] == 213
        # refresh with a value is rejected (code 212)
        st, _, body = curl(cluster, "PUT",
                           "/v2/keys/rfr?refresh=true",
                           form({"value": "x", "ttl": "5"}), FORM_HDR)
        assert body["errorCode"] == 212

    def test_path_escape_rejected(self, cluster):
        # ".." must not reach the internal /0 cluster tree.
        st, _, body = curl(cluster, "GET", "/v2/keys/%2e%2e/0")
        assert st == 400 and body["errorCode"] == 210
        st, _, body = curl(cluster, "DELETE",
                           "/v2/keys/../0?recursive=true")
        assert st == 400 and body["errorCode"] == 210
        # Membership survived.
        st, _, body = curl(cluster, "GET", "/v2/members")
        assert len(body["members"]) == 3

    def test_no_value_on_success(self, cluster):
        st, _, body = curl(cluster, "PUT",
                           "/v2/keys/nv?noValueOnSuccess=true",
                           form({"value": "big"}), FORM_HDR)
        assert st in (200, 201)
        assert "node" not in body and "prevNode" not in body
        st, _, body = curl(cluster, "GET", "/v2/keys/nv")
        assert body["node"]["value"] == "big"

    def test_quorum_get(self, cluster):
        curl(cluster, "PUT", "/v2/keys/qg", form({"value": "q"}), FORM_HDR)
        st, _, body = curl(cluster, "GET", "/v2/keys/qg?quorum=true",
                           member=1)
        assert st == 200 and body["node"]["value"] == "q"

    def test_bad_field_values(self, cluster):
        st, _, body = curl(cluster, "GET", "/v2/keys/foo?recursive=bogus")
        assert body["errorCode"] == 209
        st, _, body = curl(cluster, "PUT", "/v2/keys/foo?prevIndex=nan",
                           form({"value": "v"}), FORM_HDR)
        assert body["errorCode"] == 203
        st, _, body = curl(cluster, "PUT", "/v2/keys/foo",
                           form({"value": "v", "ttl": "bogus"}), FORM_HDR)
        assert body["errorCode"] == 202
        st, _, body = curl(cluster, "GET",
                           "/v2/keys/foo?wait=true&quorum=true")
        assert body["errorCode"] == 209

    def test_follower_serves_writes(self, cluster):
        # Any member takes writes; consensus routes to the leader.
        for i in range(3):
            st, _, body = curl(cluster, "PUT", f"/v2/keys/via{i}",
                               form({"value": str(i)}), FORM_HDR, member=i)
            assert st in (200, 201)
        for i in range(3):
            # a plain GET is served from the member's own store, which a
            # follower applies to a moment after the leader acknowledged
            deadline = time.time() + 10
            while True:
                st, _, body = curl(cluster, "GET", f"/v2/keys/via{i}",
                                   member=(i + 1) % 3)
                if st == 200 or time.time() > deadline:
                    break
                time.sleep(0.02)
            assert body["node"]["value"] == str(i)


class TestWatch:
    def test_longpoll_watch(self, cluster):
        results = {}

        def watcher():
            results["resp"] = curl(cluster, "GET",
                                   "/v2/keys/watched?wait=true", member=1)

        th = threading.Thread(target=watcher)
        th.start()
        time.sleep(0.3)
        curl(cluster, "PUT", "/v2/keys/watched", form({"value": "now"}),
             FORM_HDR)
        th.join(timeout=10)
        assert not th.is_alive()
        st, hd, body = results["resp"]
        assert st == 200 and body["action"] == "set"
        assert body["node"]["value"] == "now"

    def test_wait_index_history(self, cluster):
        st, _, body = curl(cluster, "PUT", "/v2/keys/hist",
                           form({"value": "h1"}), FORM_HDR)
        idx = body["node"]["modifiedIndex"]
        # waitIndex in the past replays from the event history ring.
        st, _, body = curl(cluster, "GET",
                           f"/v2/keys/hist?wait=true&waitIndex={idx}")
        assert st == 200 and body["node"]["value"] == "h1"

    def test_stream_watch(self, cluster):
        base = cluster[0].client_urls[0]
        got = []
        done = threading.Event()

        def streamer():
            r = urllib.request.Request(
                base + "/v2/keys/s?wait=true&stream=true&recursive=true")
            with urllib.request.urlopen(r, timeout=15) as resp:
                for _ in range(2):
                    line = resp.readline()
                    got.append(json.loads(line))
            done.set()

        th = threading.Thread(target=streamer, daemon=True)
        th.start()
        time.sleep(0.3)
        curl(cluster, "PUT", "/v2/keys/s/1", form({"value": "a"}), FORM_HDR)
        curl(cluster, "PUT", "/v2/keys/s/2", form({"value": "b"}), FORM_HDR)
        assert done.wait(15)
        assert [e["node"]["value"] for e in got] == ["a", "b"]


class TestMeta:
    def test_members_list(self, cluster):
        st, _, body = curl(cluster, "GET", "/v2/members")
        assert st == 200
        assert len(body["members"]) == 3
        m = body["members"][0]
        assert set(m) == {"id", "name", "peerURLs", "clientURLs"}
        assert all(mm["clientURLs"] for mm in body["members"])

    def test_member_add_conflict(self, cluster):
        taken = cluster[0].peer_urls[0]
        st, _, body = curl(cluster, "POST", "/v2/members",
                           json.dumps({"peerURLs": [taken]}).encode(),
                           {"Content-Type": "application/json"})
        assert st == 409

    def test_machines(self, cluster):
        st, _, body = curl(cluster, "GET", "/v2/machines")
        assert st == 200 and "http://" in body

    def test_stats(self, cluster):
        st, _, body = curl(cluster, "GET", "/v2/stats/self")
        assert st == 200
        assert body["state"] in ("StateLeader", "StateFollower")
        leader = next(i for i, m in enumerate(cluster)
                      if m.server.is_leader())
        st, _, body = curl(cluster, "GET", "/v2/stats/leader",
                           member=leader)
        assert st == 200
        assert len(body["followers"]) == 2
        for f in body["followers"].values():
            assert f["counts"]["success"] > 0
        st, _, body = curl(cluster, "GET", "/v2/stats/store")
        assert st == 200 and "watchers" in body

    def test_version_and_health(self, cluster):
        st, _, body = curl(cluster, "GET", "/version")
        assert st == 200 and body["etcdserver"].startswith("2.")
        st, _, body = curl(cluster, "GET", "/health")
        assert st == 200 and body["health"] == "true"

    def test_metrics_endpoint(self, cluster):
        """Prometheus text format with the reference's metric families
        (etcdserver/wal/snap/rafthttp metrics.go)."""
        curl(cluster, "PUT", "/v2/keys/metric-poke", form({"value": "x"}),
             FORM_HDR)
        st, hd, body = curl(cluster, "GET", "/metrics")
        assert st == 200
        assert hd["Content-Type"].startswith("text/plain")
        for family in ("etcd_server_proposal_durations_milliseconds",
                       "etcd_server_pending_proposal_total",
                       "etcd_server_proposal_failed_total",
                       "etcd_server_file_descriptors_used_total",
                       "etcd_wal_fsync_durations_microseconds",
                       "etcd_wal_last_index_saved"):
            assert f"# TYPE {family}" in body, family
        # real observations flowed in: the proposal count is > 0
        for line in body.splitlines():
            if line.startswith(
                    "etcd_server_proposal_durations_milliseconds_count"):
                assert float(line.split()[-1]) > 0
                break
        else:
            raise AssertionError("proposal count series missing")

    def test_debug_vars(self, cluster):
        st, _, body = curl(cluster, "GET", "/debug/vars")
        assert st == 200
        assert body["file_descriptor_limit"] > 0
        rs = body["raft.status"]
        assert rs["raftState"] in ("LEADER", "FOLLOWER", "CANDIDATE")
        assert int(rs["lead"], 16) != 0

    def test_404_paths(self, cluster):
        st, _, _ = curl(cluster, "GET", "/v2/bogus")
        assert st == 404


# -- CORS (reference pkg/cors/cors.go via the client-listener wrap) ----------

def test_cors_enforced(tmp_path):
    pport, cport = free_ports(2)
    cfg = EtcdConfig(
        name="c0", data_dir=str(tmp_path / "c0"),
        initial_cluster={"c0": [f"http://127.0.0.1:{pport}"]},
        listen_client_urls=[f"http://127.0.0.1:{cport}"],
        tick_ms=10, cors=["http://allowed.example"])
    m = Etcd(cfg)
    m.start()
    try:
        assert m.wait_leader(10)
        base = m.client_urls[0]
        # Allowed origin: headers present.
        st, hdrs, _ = req("GET", base + "/version",
                          headers={"Origin": "http://allowed.example"})
        assert st == 200
        assert hdrs.get("Access-Control-Allow-Origin") == \
            "http://allowed.example"
        assert "POST" in hdrs.get("Access-Control-Allow-Methods", "")
        # Disallowed origin: no CORS headers (the browser blocks it).
        st, hdrs, _ = req("GET", base + "/version",
                          headers={"Origin": "http://evil.example"})
        assert st == 200
        assert "Access-Control-Allow-Origin" not in hdrs
        # Preflight answers 200 immediately.
        st, hdrs, _ = req("OPTIONS", base + "/v2/keys/x",
                          headers={"Origin": "http://allowed.example"})
        assert st == 200
        assert hdrs.get("Access-Control-Allow-Origin") == \
            "http://allowed.example"
    finally:
        m.stop()


def test_cors_wildcard(tmp_path):
    pport, cport = free_ports(2)
    cfg = EtcdConfig(
        name="cw", data_dir=str(tmp_path / "cw"),
        initial_cluster={"cw": [f"http://127.0.0.1:{pport}"]},
        listen_client_urls=[f"http://127.0.0.1:{cport}"],
        tick_ms=10, cors=["*"])
    m = Etcd(cfg)
    m.start()
    try:
        assert m.wait_leader(10)
        st, hdrs, _ = req("GET", m.client_urls[0] + "/version")
        assert st == 200
        assert hdrs.get("Access-Control-Allow-Origin") == "*"
    finally:
        m.stop()


# -- continuous cluster-version negotiation (reference monitorVersions
#    server.go:933-973 + decideClusterVersion cluster_util.go:142-186) ------

def test_version_monitor_decides_min_and_upgrades(cluster):
    """A live cluster negotiates the min member version; when every member
    reports a higher version the monitor proposes the upgrade; it never
    downgrades."""
    import time as _t
    lead = next(m for m in cluster if m.server.is_leader())
    srv = lead.server
    deadline = _t.time() + 10
    while _t.time() < deadline and srv.cluster.version() is None:
        _t.sleep(0.05)
    assert srv.cluster_version() == "2.1.0"  # all members run 2.1.0

    # Mixed versions: one member reports older -> decided = min = 2.0.x ->
    # but 2.1.0 is already set and the monitor never downgrades.
    orig = srv._get_versions
    try:
        srv._get_versions = lambda: {1: "2.1.0", 2: "2.0.5", 3: "2.1.0"}
        assert srv._decide_cluster_version() == "2.0.5"
        srv._force_version_ev.set()
        _t.sleep(0.3)
        assert srv.cluster_version() == "2.1.0"  # no downgrade

        # Everyone upgraded to 2.2 -> cluster version rises.
        srv._get_versions = lambda: {1: "2.2.1", 2: "2.2.0", 3: "2.2.3"}
        srv._force_version_ev.set()
        deadline = _t.time() + 10
        while _t.time() < deadline and srv.cluster_version() != "2.2.0":
            _t.sleep(0.05)
        assert srv.cluster_version() == "2.2.0"

        # An unreachable member blocks any further decision.
        srv._get_versions = lambda: {1: "2.3.0", 2: None, 3: "2.3.0"}
        assert srv._decide_cluster_version() is None
        srv._force_version_ev.set()
        _t.sleep(0.3)
        assert srv.cluster_version() == "2.2.0"
    finally:
        srv._get_versions = orig


def _wait_peer_urls(api, hexid, want, timeout=10.0):
    """Poll the members API until the member's peer URLs equal `want`."""
    import time as _t
    deadline = _t.time() + timeout
    while _t.time() < deadline:
        info = [m for m in api.list() if hexid ==
                (m.id if isinstance(m.id, str) else f"{m.id:x}")]
        if info and sorted(info[0].peer_urls) == sorted(want):
            return True
        _t.sleep(0.1)
    return False


def test_member_update_peer_urls(cluster):
    """PUT /v2/members/{id} updates a member's advertised peer URLs through
    consensus (reference UPDATE_NODE ConfChange, client.go:252-286)."""
    import sys as _sys

    from etcd_tpu.client import Client, MembersAPI

    m1 = cluster[1]
    mid = f"{m1.server.id:x}"
    current = list(m1.peer_urls)
    extra = current + ["http://127.0.0.1:1"]    # unused alternate URL
    api = MembersAPI(Client(list(cluster[0].client_urls)))
    api.update(mid, extra)
    try:
        assert _wait_peer_urls(api, mid, extra), \
            "peer URL update never became visible"
    finally:
        # Always restore and WAIT for visibility: the module-scoped cluster
        # serves later tests. Only raise if the try body succeeded — a
        # restore raise here would mask the primary failure.
        api.update(mid, current)
        restored = _wait_peer_urls(api, mid, current)
        if not restored and _sys.exc_info()[0] is None:
            raise AssertionError("peer URL restore never became visible")
    st, _, body = req("GET", cluster[0].client_urls[0] + "/v2/members")
    assert st == 200
