"""Per-tenant auth + stats on the engine's /tenants/{g}/... surface
(VERDICT r2 item 6): the v2 security matrix's auth cases against one
tenant, independence of the others, and restart survival — auth state
rides each tenant's OWN replicated keyspace."""
import base64
import json
import time
import urllib.error
import urllib.request

import pytest

from etcd_tpu.etcdhttp.tenants import EngineHttp
from etcd_tpu.server.engine import EngineConfig, MultiEngine


def _req(method, url, body=None, headers=None):
    r = urllib.request.Request(url, body, headers or {}, method=method)
    try:
        resp = urllib.request.urlopen(r, timeout=20)
        raw = resp.read()
        return resp.status, (json.loads(raw) if raw else {})
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw)
        except (ValueError, TypeError):
            return e.code, {}


def _auth(user, pw):
    cred = base64.b64encode(f"{user}:{pw}".encode()).decode()
    return {"Authorization": f"Basic {cred}"}


JH = {"Content-Type": "application/json"}
FH = {"Content-Type": "application/x-www-form-urlencoded"}


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tenant-sec")
    eng = MultiEngine(EngineConfig(
        groups=3, peers=3, data_dir=str(tmp / "e"), fsync=False,
        request_timeout=30.0))
    eng.start()
    http = EngineHttp(eng)
    http.start()
    assert eng.wait_leaders(60)
    yield eng, http.url, str(tmp / "e")
    http.stop()
    eng.stop()


def test_tenant_auth_matrix(cluster):
    eng, base, _ = cluster
    t1 = f"{base}/tenants/1"

    # Enable refused without a root user (reference security.go:358-403).
    st, body = _req("PUT", t1 + "/v2/security/enable")
    assert st == 400 and "root" in body["message"]

    # Root + restricted guest + a scoped role/user, then enable.
    st, body = _req("PUT", t1 + "/v2/security/users/root",
                    json.dumps({"user": "root",
                                "password": "rpw"}).encode(), JH)
    assert st == 201, body
    st, _ = _req("PUT", t1 + "/v2/security/roles/guest",
                 json.dumps({"role": "guest", "permissions": {
                     "kv": {"read": ["/*"], "write": []}}}).encode(), JH)
    assert st == 201
    st, _ = _req("PUT", t1 + "/v2/security/roles/appRole",
                 json.dumps({"role": "appRole", "permissions": {
                     "kv": {"read": ["/app/*"],
                            "write": ["/app/*"]}}}).encode(), JH)
    assert st == 201
    st, _ = _req("PUT", t1 + "/v2/security/users/alice",
                 json.dumps({"user": "alice",
                             "password": "apw"}).encode(), JH)
    assert st == 201
    st, body = _req("PUT", t1 + "/v2/security/users/alice",
                    json.dumps({"user": "alice",
                                "grant": ["appRole"]}).encode(), JH)
    assert st == 200 and body["roles"] == ["appRole"]
    st, _ = _req("PUT", t1 + "/v2/security/enable")
    assert st == 200

    # Security endpoints now need root.
    st, _ = _req("GET", t1 + "/v2/security/users")
    assert st == 401
    st, body = _req("GET", t1 + "/v2/security/users",
                    headers=_auth("root", "rpw"))
    assert st == 200 and set(body["users"]) == {"alice", "root"}
    st, _ = _req("GET", t1 + "/v2/security/users",
                 headers=_auth("root", "WRONG"))
    assert st == 401

    # Guest: read yes, write no (code 110).
    st, _ = _req("GET", t1 + "/v2/keys/")
    assert st == 200
    st, body = _req("PUT", t1 + "/v2/keys/app/x", b"value=1", FH)
    assert st == 401 and body.get("errorCode") == 110

    # Scoped user: writes inside its prefix, refused outside.
    st, _ = _req("PUT", t1 + "/v2/keys/app/x", b"value=1",
                 {**FH, **_auth("alice", "apw")})
    assert st == 201
    st, _ = _req("PUT", t1 + "/v2/keys/other/x", b"value=1",
                 {**FH, **_auth("alice", "apw")})
    assert st == 401
    # Root writes anywhere.
    st, _ = _req("PUT", t1 + "/v2/keys/other/x", b"value=1",
                 {**FH, **_auth("root", "rpw")})
    assert st == 201

    # Membership mutation (conf) needs root once security is on.
    st, _ = _req("POST", t1 + "/conf",
                 json.dumps({"op": "remove", "slot": 2}).encode(), JH)
    assert st == 401
    st, _ = _req("POST", t1 + "/conf",
                 json.dumps({"op": "add", "slot": 2}).encode(),
                 {**JH, **_auth("root", "rpw")})
    assert st != 401   # authenticated: passes the gate (slot already
    #                    active, so the engine answers its own error)

    # TENANT INDEPENDENCE: tenant 0 never enabled auth — writes are open,
    # and its security state is empty.
    st, _ = _req("PUT", f"{base}/tenants/0/v2/keys/app/x", b"value=1", FH)
    assert st == 201
    st, body = _req("GET", f"{base}/tenants/0/v2/security/enable")
    assert st == 200 and body["enabled"] is False


def test_tenant_stats(cluster):
    eng, base, _ = cluster
    st, body = _req("GET", f"{base}/tenants/0/v2/stats/store")
    assert st == 200 and "setsSuccess" in body
    st, body = _req("GET", f"{base}/tenants/0/v2/stats/self")
    assert st == 200 and body["id"] == "0" and "raftTerm" in body
    st, body = _req("GET", f"{base}/tenants/0/v2/stats/leader")
    assert st == 200 and "followers" in body


def test_the_auth_gate_is_asked_at_every_request(cluster, monkeypatch):
    """The front's loop serves a tenant's keys requests only while the
    tenant's own replicated keyspace says auth is off, and asks it at
    every request (etcdhttp/client.py begin_keys): on ONE keep-alive
    connection the request after `enable` is already on the thread path
    and refused without credentials, the one after `disable` is back on
    the loop, and a store that cannot answer denies."""
    import socket
    from urllib.parse import urlsplit

    from etcd_tpu import errors
    from etcd_tpu.server import obs
    eng, base, _ = cluster
    t2 = f"{base}/tenants/2"
    u = urlsplit(base)
    s = socket.create_connection((u.hostname, u.port), timeout=30)

    def served():
        return {lbl["path"]: v
                for _, lbl, v in obs.http_front_served.samples()}

    def put(value, path):
        """One write on the connection: (status, body, the path it took)."""
        before = served()
        body = f"value={value}".encode()
        s.sendall(b"PUT /tenants/2/v2/keys/gate HTTP/1.1\r\nHost: t\r\n"
                  b"Content-Type: application/x-www-form-urlencoded\r\n"
                  b"Content-Length: %d\r\n\r\n%b" % (len(body), body))
        raw = b""
        while b"\r\n\r\n" not in raw:
            raw += s.recv(65536)
        head, _, rest = raw.partition(b"\r\n\r\n")
        n = int([ln for ln in head.split(b"\r\n")
                 if ln.startswith(b"Content-Length:")][0].split(b":")[1])
        while len(rest) < n:
            rest += s.recv(65536)
        deadline = time.time() + 5      # counted once the reply is out
        while served().get(path, 0) == before.get(path, 0) \
                and time.time() < deadline:
            time.sleep(0.01)
        took = [k for k, v in served().items() if v != before.get(k, 0)]
        return int(head.split()[1]), json.loads(rest), took

    def admin(method, path, body=None, headers=None):
        """A security request on a connection of its own (thread path),
        returned once it is counted: the front counts a request after
        the reply is out, and put() must not see that count as its own."""
        before = served().get("thread", 0)
        st, _ = _req(method, t2 + path, body, headers)
        deadline = time.time() + 5
        while served().get("thread", 0) == before and time.time() < deadline:
            time.sleep(0.01)
        return st

    st, body, took = put("open", "loop")
    assert st == 201 and took == ["loop"]
    assert admin("PUT", "/v2/security/users/root", json.dumps(
        {"user": "root", "password": "rpw"}).encode(), JH) == 201
    assert admin("PUT", "/v2/security/roles/guest", json.dumps(
        {"role": "guest", "permissions": {
            "kv": {"read": ["/*"], "write": []}}}).encode(), JH) == 201
    assert admin("PUT", "/v2/security/enable") == 200
    st, body, took = put("shut", "thread")
    assert st == 401 and body["errorCode"] == 110 and took == ["thread"]
    assert admin("DELETE", "/v2/security/enable",
                 headers=_auth("root", "rpw")) == 200
    st, body, took = put("open again", "loop")
    assert st == 200 and took == ["loop"]
    assert body["prevNode"]["value"] == "open"
    # a store error that is not "absent" denies: it is not read as "off"
    sec = eng.store(2)

    def broken(path):
        raise errors.EtcdError(errors.ECODE_RAFT_INTERNAL, cause="broken")
    monkeypatch.setattr(sec, "value", broken)
    st, body, took = put("never", "loop")
    assert st == 500 and body["errorCode"] == 300 and took == ["loop"]
    monkeypatch.undo()
    st, body, _ = put("mended", "loop")
    assert st == 200 and body["prevNode"]["value"] == "open again"
    s.close()


def test_tenant_auth_survives_restart(cluster, tmp_path):
    eng, base, data_dir = cluster
    # (uses the module cluster's data dir written by the matrix test)
    st, _ = _req("GET", f"{base}/tenants/1/v2/security/enable")
    assert st == 200

    eng._stop_ev.set()
    eng._thread.join(10)
    eng.wal.close()
    eng2 = MultiEngine(EngineConfig(
        groups=3, peers=3, data_dir=data_dir, fsync=False,
        request_timeout=30.0))
    eng2.start()
    http2 = EngineHttp(eng2)
    http2.start()
    try:
        assert eng2.wait_leaders(60)
        b2 = http2.url
        st, body = _req("GET", f"{b2}/tenants/1/v2/security/enable")
        assert st == 200 and body["enabled"] is True
        st, body = _req("PUT", f"{b2}/tenants/1/v2/keys/app/y",
                        b"value=2", FH)
        assert st == 401 and body.get("errorCode") == 110
        st, _ = _req("PUT", f"{b2}/tenants/1/v2/keys/app/y", b"value=2",
                     {**FH, **_auth("alice", "apw")})
        assert st == 201
    finally:
        http2.stop()
        eng2.stop()


@pytest.fixture()
def lifecycle_cluster(tmp_path):
    """Fresh small engine for lifecycle-security tests (ADVICE r3 high:
    unauthenticated tenant deletion), with an operator credential on the
    HTTP frontend."""
    eng = MultiEngine(EngineConfig(
        groups=3, peers=3, data_dir=str(tmp_path / "e"), fsync=False,
        request_timeout=30.0))
    eng.start()
    http = EngineHttp(eng, admin_credentials=("op", "opsecret"))
    http.start()
    assert eng.wait_leaders(60)
    yield eng, http.url
    http.stop()
    eng.stop()


def _enable_tenant_auth(base, g, root_pw="rpw"):
    t = f"{base}/tenants/{g}"
    st, body = _req("PUT", t + "/v2/security/users/root",
                    json.dumps({"user": "root",
                                "password": root_pw}).encode(), JH)
    assert st == 201, body
    st, _ = _req("PUT", t + "/v2/security/enable",
                 headers=_auth("root", root_pw))
    assert st == 200


def test_tenant_delete_requires_credentials(lifecycle_cluster):
    eng, base = lifecycle_cluster
    _enable_tenant_auth(base, 1)

    # Unauthenticated deletion of an auth-enabled tenant: refused.
    st, _ = _req("DELETE", f"{base}/tenants/1")
    assert st == 401
    assert eng.tenant_active(1)
    # Wrong credential: refused.
    st, _ = _req("DELETE", f"{base}/tenants/1",
                 headers=_auth("root", "WRONG"))
    assert st == 401
    # The tenant's own root may delete it.
    st, body = _req("DELETE", f"{base}/tenants/1",
                    headers=_auth("root", "rpw"))
    assert st == 200 and body["removed"] == 1
    assert not eng.tenant_active(1)

    # With an operator credential configured, even an UNAUTHENTICATED
    # tenant's lifecycle needs it.
    st, _ = _req("DELETE", f"{base}/tenants/0")
    assert st == 401
    st, _ = _req("DELETE", f"{base}/tenants/0",
                 headers=_auth("op", "opsecret"))
    assert st == 200
    # Create likewise.
    st, _ = _req("PUT", f"{base}/tenants/0")
    assert st == 401
    st, _ = _req("PUT", f"{base}/tenants/0",
                 headers=_auth("op", "opsecret"))
    assert st == 201

    # The operator credential also overrides a tenant root (pool-wide
    # admin), so a lost tenant root cannot strand a slot.
    _enable_tenant_auth(base, 2, root_pw="zzz")
    st, _ = _req("DELETE", f"{base}/tenants/2",
                 headers=_auth("op", "opsecret"))
    assert st == 200


def test_tenant_recreate_gets_fresh_security_state(lifecycle_cluster):
    """ADVICE r3: per-tenant handler caches are keyed on the engine's
    lifecycle generation — a slot removed and recreated VIA THE ENGINE
    API (not HTTP DELETE) must not be served through the stale cached
    SecurityHandler of the previous generation."""
    eng, base = lifecycle_cluster
    _enable_tenant_auth(base, 1)
    # Restrict the auto-created permissive guest role to read-only so the
    # enabled state is observable from an unauthenticated client.
    st, _ = _req("PUT", f"{base}/tenants/1/v2/security/roles/guest",
                 json.dumps({"role": "guest", "revoke": {"kv": {
                     "read": [], "write": ["*"]}}}).encode(),
                 {**JH, **_auth("root", "rpw")})
    assert st == 200
    st, _ = _req("PUT", f"{base}/tenants/1/v2/keys/x", b"value=1", FH)
    assert st == 401   # guest writes refused; handler now cached

    # Recycle the slot straight through the engine (bypasses the HTTP
    # DELETE cache-invalidation path).
    eng.remove_tenant(1)
    eng.create_tenant(1)
    assert eng.wait_leaders(60, groups=[1])

    # The fresh generation has auth disabled: writes are open again and
    # the security store is empty.
    st, body = _req("GET", f"{base}/tenants/1/v2/security/enable")
    assert st == 200 and body["enabled"] is False
    st, _ = _req("PUT", f"{base}/tenants/1/v2/keys/x", b"value=1", FH)
    assert st == 201
