"""The reference v2 HTTP KV conformance matrix, ported table-for-table from
integration/v2_http_kv_test.go (1,039 lines; SURVEY §4 Tier 4): CreateUpdate,
CAS, Delete, CAD, Unique (in-order POST), Get/QuorumGet tree shapes,
WatchWithIndex, WatchKeyInDir (TTL-dir expiry), and HEAD.

Absolute store indices in the reference tables (e.g. modifiedIndex 4/5)
depend on bootstrap-entry counts, so the port captures indices from earlier
responses instead of hard-coding them; everything else (status codes, error
codes, cause strings, tree shapes, actions) matches the reference verbatim.
"""
import threading
import time

import pytest

from etcd_tpu.embed import Etcd, EtcdConfig

from tests.test_http import FORM_HDR, form, free_ports, req


@pytest.fixture(scope="module",
                params=["member", "tenant"])
def member(tmp_path_factory, request):
    """The same conformance tables run against BOTH serving surfaces:
    a classic single-member cluster (the reference's NewCluster(t, 1))
    and one tenant keyspace of the batched multi-tenant engine at
    /tenants/{g} — the engine's v2 surface must be semantically
    indistinguishable from the reference member's."""
    tmp = tmp_path_factory.mktemp("v2matrix")
    if request.param == "member":
        pp, cp = free_ports(2)
        cfg = EtcdConfig(
            name="m0", data_dir=str(tmp / "m0"),
            initial_cluster={"m0": [f"http://127.0.0.1:{pp}"]},
            listen_client_urls=[f"http://127.0.0.1:{cp}"],
            tick_ms=10, request_timeout=5.0)
        m = Etcd(cfg)
        m.start()
        assert m.wait_leader(10)
        yield m
        m.stop()
        return
    from types import SimpleNamespace

    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server.engine import EngineConfig, MultiEngine

    (cp,) = free_ports(1)
    eng = MultiEngine(EngineConfig(
        groups=4, peers=3, data_dir=str(tmp / "eng"), window=16,
        max_ents=4, heartbeat_tick=3, fsync=False, request_timeout=15.0))
    http = EngineHttp(eng, port=cp)
    eng.start()
    http.start()
    deadline = time.time() + 120
    while time.time() < deadline:
        if all(eng.leader_slot(g) >= 0 for g in range(4)):
            break
        time.sleep(0.05)
    else:
        raise AssertionError("engine elections failed")
    # The first quorum read compiles the read-step variant — tens of
    # seconds on a cold compile cache, longer than a table row's 10 s
    # client timeout. Pay it here (the engine answers 500/300 at its own
    # request timeout while the round loop compiles; ask again).
    while time.time() < deadline:
        st, _, _ = req("GET", http.url + "/tenants/2/v2/keys/?quorum=true",
                       timeout=60.0)
        if st == 200:
            break
    else:
        raise AssertionError("first quorum read never served")
    yield SimpleNamespace(client_urls=[http.url + "/tenants/2"])
    http.stop()
    eng.stop()


def curl(member, method, path, data=None):
    return req(method, member.client_urls[0] + path,
               form(data) if data is not None else None,
               FORM_HDR if data is not None else None)


def test_create_update_table(member):
    """TestV2CreateUpdate (v2_http_kv_test.go:88-193)."""
    # key with ttl
    st, _, b = curl(member, "PUT", "/v2/keys/ttl/foo",
                    {"value": "XXX", "ttl": "20"})
    assert st == 201 and b["node"]["value"] == "XXX"
    assert b["node"]["ttl"] == 20
    # bad ttl
    st, _, b = curl(member, "PUT", "/v2/keys/ttl/foo",
                    {"value": "XXX", "ttl": "bad_ttl"})
    assert st == 400 and b["errorCode"] == 202
    assert b["message"] == "The given TTL in POST form is not a number"
    # create
    st, _, b = curl(member, "PUT", "/v2/keys/create/foo",
                    {"value": "XXX", "prevExist": "false"})
    assert st == 201 and b["node"]["value"] == "XXX"
    # create conflict
    st, _, b = curl(member, "PUT", "/v2/keys/create/foo",
                    {"value": "XXX", "prevExist": "false"})
    assert st == 412 and b["errorCode"] == 105
    assert b["message"] == "Key already exists"
    assert b["cause"] == "/create/foo"
    # update with ttl
    st, _, b = curl(member, "PUT", "/v2/keys/create/foo",
                    {"value": "YYY", "prevExist": "true", "ttl": "20"})
    assert st == 200 and b["action"] == "update"
    assert b["node"]["value"] == "YYY" and b["node"]["ttl"] == 20
    # update clears the ttl
    st, _, b = curl(member, "PUT", "/v2/keys/create/foo",
                    {"value": "ZZZ", "prevExist": "true"})
    assert st == 200 and b["action"] == "update"
    assert b["node"]["value"] == "ZZZ" and "ttl" not in b["node"]
    # update on a non-existing key
    st, _, b = curl(member, "PUT", "/v2/keys/nonexist",
                    {"value": "XXX", "prevExist": "true"})
    assert st == 404 and b["errorCode"] == 100
    assert b["message"] == "Key not found" and b["cause"] == "/nonexist"


def test_cas_table(member):
    """TestV2CAS (v2_http_kv_test.go:195-318) — incl. the exact cause-string
    forms: index-only, value-only, and combined mismatches."""
    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo", {"value": "XXX"})
    assert st == 201
    mi = b["node"]["modifiedIndex"]

    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "YYY", "prevIndex": str(mi)})
    assert st == 200 and b["action"] == "compareAndSwap"
    assert b["node"]["modifiedIndex"] == mi + 1
    mi += 1

    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "YYY", "prevIndex": str(mi + 100)})
    assert st == 412 and b["errorCode"] == 101
    assert b["message"] == "Compare failed"
    assert b["cause"] == f"[{mi + 100} != {mi}]"
    assert b["index"] >= mi

    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "YYY", "prevIndex": "bad_index"})
    assert st == 400 and b["errorCode"] == 203
    assert b["message"] == "The given index in POST form is not a number"

    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "ZZZ", "prevValue": "YYY"})
    assert st == 200 and b["action"] == "compareAndSwap"
    assert b["node"]["value"] == "ZZZ"
    mi = b["node"]["modifiedIndex"]

    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "XXX", "prevValue": "bad_value"})
    assert st == 412 and b["errorCode"] == 101
    assert b["cause"] == "[bad_value != ZZZ]"

    # prevValue present but empty -> 201 invalid form
    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "XXX", "prevValue": ""})
    assert st == 400 and b["errorCode"] == 201

    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "XXX", "prevValue": "bad_value",
                     "prevIndex": str(mi + 100)})
    assert st == 412 and b["errorCode"] == 101
    assert b["cause"] == f"[bad_value != ZZZ] [{mi + 100} != {mi}]"

    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "XXX", "prevValue": "ZZZ",
                     "prevIndex": str(mi + 100)})
    assert st == 412 and b["errorCode"] == 101
    assert b["cause"] == f"[{mi + 100} != {mi}]"

    st, _, b = curl(member, "PUT", "/v2/keys/cas/foo",
                    {"value": "XXX", "prevValue": "bad_value",
                     "prevIndex": str(mi)})
    assert st == 412 and b["errorCode"] == 101
    assert b["cause"] == "[bad_value != ZZZ]"


def test_delete_table(member):
    """TestV2Delete (v2_http_kv_test.go:320-414)."""
    curl(member, "PUT", "/v2/keys/del/foo", {"value": "XXX"})
    curl(member, "PUT", "/v2/keys/del/emptydir?dir=true", {})
    curl(member, "PUT", "/v2/keys/del/foodir/bar?dir=true", {})

    st, _, b = curl(member, "DELETE", "/v2/keys/del/foo")
    assert st == 200 and b["action"] == "delete"
    assert b["node"]["key"] == "/del/foo"
    assert b["prevNode"]["key"] == "/del/foo"
    assert b["prevNode"]["value"] == "XXX"

    st, _, b = curl(member, "DELETE", "/v2/keys/del/emptydir")
    assert st == 403 and b["errorCode"] == 102
    assert b["message"] == "Not a file" and b["cause"] == "/del/emptydir"

    st, _, b = curl(member, "DELETE", "/v2/keys/del/emptydir?dir=true")
    assert st == 200

    st, _, b = curl(member, "DELETE", "/v2/keys/del/foodir?dir=true")
    assert st == 403 and b["errorCode"] == 108
    assert b["message"] == "Directory not empty"
    assert b["cause"] == "/del/foodir"

    st, _, b = curl(member, "DELETE", "/v2/keys/del/foodir?recursive=true")
    assert st == 200 and b["action"] == "delete"
    assert b["node"]["dir"] is True and b["prevNode"]["dir"] is True


def test_cad_table(member):
    """TestV2CAD (v2_http_kv_test.go:416-510)."""
    st, _, b = curl(member, "PUT", "/v2/keys/cad/foo", {"value": "XXX"})
    mi = b["node"]["modifiedIndex"]
    curl(member, "PUT", "/v2/keys/cad/foovalue", {"value": "XXX"})

    st, _, b = curl(member, "DELETE",
                    f"/v2/keys/cad/foo?prevIndex={mi + 100}")
    assert st == 412 and b["errorCode"] == 101
    assert b["cause"] == f"[{mi + 100} != {mi}]"

    st, _, b = curl(member, "DELETE", "/v2/keys/cad/foo?prevIndex=bad_index")
    assert st == 400 and b["errorCode"] == 203
    assert b["message"] == "The given index in POST form is not a number"

    st, _, b = curl(member, "DELETE", f"/v2/keys/cad/foo?prevIndex={mi}")
    assert st == 200 and b["action"] == "compareAndDelete"
    assert b["node"]["key"] == "/cad/foo"

    st, _, b = curl(member, "DELETE", "/v2/keys/cad/foovalue?prevValue=YYY")
    assert st == 412 and b["errorCode"] == 101
    assert b["cause"] == "[YYY != XXX]"

    st, _, b = curl(member, "DELETE", "/v2/keys/cad/foovalue?prevValue=")
    assert st == 400 and b["errorCode"] == 201
    assert b["cause"] == '"prevValue" cannot be empty'

    st, _, b = curl(member, "DELETE", "/v2/keys/cad/foovalue?prevValue=XXX")
    assert st == 200 and b["action"] == "compareAndDelete"


def test_unique_in_order_table(member):
    """TestV2Unique (v2_http_kv_test.go:512-573): POST creates in-order keys
    numbered by the store index, monotonic ACROSS directories."""
    st, _, b = curl(member, "POST", "/v2/keys/unique/foo", {"value": "XXX"})
    assert st == 201 and b["action"] == "create"
    k1 = int(b["node"]["key"].rsplit("/", 1)[1])
    st, _, b = curl(member, "POST", "/v2/keys/unique/foo", {"value": "XXX"})
    assert st == 201
    k2 = int(b["node"]["key"].rsplit("/", 1)[1])
    assert k2 == k1 + 1
    st, _, b = curl(member, "POST", "/v2/keys/unique/bar", {"value": "XXX"})
    assert st == 201
    k3 = int(b["node"]["key"].rsplit("/", 1)[1])
    assert k3 == k2 + 1


@pytest.mark.parametrize("quorum", [False, True], ids=["serial", "quorum"])
def test_get_tree_shapes(member, quorum):
    """TestV2Get + TestV2QuorumGet (v2_http_kv_test.go:575-763): directory
    GET shows children (dirs WITHOUT grandchildren), recursive GET nests."""
    pfx = "getq" if quorum else "get"
    st, _, b = curl(member, "PUT", f"/v2/keys/{pfx}/foo/bar/zar",
                    {"value": "XXX"})
    assert st == 201
    mi = b["node"]["modifiedIndex"]
    qs = "?quorum=true" if quorum else ""

    st, hd, b = curl(member, "GET", f"/v2/keys/{pfx}/foo/bar/zar" + qs)
    assert st == 200 and b["action"] == "get"
    assert hd["Content-Type"].startswith("application/json")
    assert b["node"]["key"] == f"/{pfx}/foo/bar/zar"
    assert b["node"]["value"] == "XXX"

    st, _, b = curl(member, "GET", f"/v2/keys/{pfx}/foo" + qs)
    assert st == 200
    n = b["node"]
    assert n["dir"] is True and n["key"] == f"/{pfx}/foo"
    assert len(n["nodes"]) == 1
    child = n["nodes"][0]
    assert child["key"] == f"/{pfx}/foo/bar" and child["dir"] is True
    assert child["createdIndex"] == mi and child["modifiedIndex"] == mi
    assert "nodes" not in child, "non-recursive GET must hide grandchildren"

    st, _, b = curl(member, "GET",
                    f"/v2/keys/{pfx}/foo?recursive=true" + (
                        "&quorum=true" if quorum else ""))
    assert st == 200
    child = b["node"]["nodes"][0]
    assert child["dir"] is True
    leaf = child["nodes"][0]
    assert leaf["key"] == f"/{pfx}/foo/bar/zar" and leaf["value"] == "XXX"
    assert leaf["createdIndex"] == mi and leaf["modifiedIndex"] == mi


def test_watch_with_index(member):
    """TestV2WatchWithIndex (v2_http_kv_test.go:794-849): a watch at a
    future index must NOT fire for earlier writes, then fires with the
    event AT that index."""
    st, _, b = curl(member, "PUT", "/v2/keys/wwi/probe", {"value": "p"})
    base = b["node"]["modifiedIndex"]
    target = base + 2   # the SECOND write below

    out = {}

    def watch():
        out["resp"] = curl(member, "GET",
                           f"/v2/keys/wwi/bar?wait=true&waitIndex={target}")

    t = threading.Thread(target=watch, daemon=True)
    t.start()
    time.sleep(0.2)
    assert t.is_alive(), "watch fired before any write"

    st, _, b = curl(member, "PUT", "/v2/keys/wwi/bar", {"value": "XXX"})
    assert b["node"]["modifiedIndex"] == target - 1
    time.sleep(0.3)
    assert t.is_alive(), "watch fired for a write below waitIndex"

    st, _, b = curl(member, "PUT", "/v2/keys/wwi/bar", {"value": "XXX"})
    assert b["node"]["modifiedIndex"] == target
    t.join(timeout=5.0)
    assert not t.is_alive(), "watch never fired"
    wst, _, wb = out["resp"]
    assert wst == 200 and wb["action"] == "set"
    assert wb["node"]["key"] == "/wwi/bar"
    assert wb["node"]["modifiedIndex"] == target


def test_watch_key_in_expiring_dir(member):
    """TestV2WatchKeyInDir (v2_http_kv_test.go:851-900): watching a key
    inside a TTL directory delivers the DIRECTORY's expire event."""
    st, _, b = curl(member, "PUT", "/v2/keys/keyindir",
                    {"dir": "true", "ttl": "1"})
    assert st == 201 and b["node"]["ttl"] == 1
    st, _, b = curl(member, "PUT", "/v2/keys/keyindir/bar", {"value": "XXX"})
    assert st == 201

    out = {}

    def watch():
        out["resp"] = curl(member, "GET", "/v2/keys/keyindir/bar?wait=true")

    t = threading.Thread(target=watch, daemon=True)
    t.start()
    t.join(timeout=6.0)   # 1s ttl + SYNC tick + margin
    assert not t.is_alive(), "expire event never delivered"
    wst, _, wb = out["resp"]
    assert wst == 200 and wb["action"] == "expire"
    assert wb["node"]["key"] == "/keyindir"


def test_head(member):
    """TestV2Head (v2_http_kv_test.go:902-934): HEAD answers like GET —
    status + Content-Length — with an empty body."""
    import urllib.error
    import urllib.request

    url = member.client_urls[0] + "/v2/keys/head/foo"
    r = urllib.request.Request(url, method="HEAD")
    try:
        resp = urllib.request.urlopen(r, timeout=10.0)
        st, hd, data = resp.status, resp.headers, resp.read()
    except urllib.error.HTTPError as e:
        st, hd, data = e.code, e.headers, e.read()
    assert st == 404
    assert int(hd["Content-Length"]) > 0
    assert data == b"", "HEAD must not carry a body"

    st_put, _, _ = curl(member, "PUT", "/v2/keys/head/foo", {"value": "XXX"})
    assert st_put == 201
    resp = urllib.request.urlopen(
        urllib.request.Request(url, method="HEAD"), timeout=10.0)
    assert resp.status == 200
    assert int(resp.headers["Content-Length"]) > 0
    assert resp.read() == b""
