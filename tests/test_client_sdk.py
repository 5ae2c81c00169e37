"""Client SDK tests against a real HTTP cluster (reference client/ tests +
integration usage patterns)."""
import threading
import time

import pytest

from etcd_tpu.client import Client, KeysAPI, KeysError, MembersAPI
from etcd_tpu.embed import Etcd, EtcdConfig
from tests.test_http import free_ports


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("sdkcluster")
    n = 3
    ports = free_ports(2 * n)
    peer_urls = {f"m{i}": [f"http://127.0.0.1:{ports[i]}"] for i in range(n)}
    members = []
    for i in range(n):
        cfg = EtcdConfig(
            name=f"m{i}", data_dir=str(tmp / f"m{i}"),
            initial_cluster=peer_urls,
            listen_client_urls=[f"http://127.0.0.1:{ports[n + i]}"],
            tick_ms=10, request_timeout=5.0)
        members.append(Etcd(cfg))
    for m in members:
        m.start()
    assert all(m.wait_leader(10) for m in members)
    yield members
    for m in members:
        m.stop()


@pytest.fixture()
def kapi(cluster):
    c = Client([cluster[0].client_urls[0]])
    return KeysAPI(c)


def test_set_get_delete(kapi):
    r = kapi.set("/sdk/a", "1")
    assert r.action == "set" and r.node.value == "1"
    r = kapi.get("/sdk/a")
    assert r.node.value == "1" and r.index > 0
    r = kapi.delete("/sdk/a")
    assert r.action == "delete"
    with pytest.raises(KeysError) as ei:
        kapi.get("/sdk/a")
    assert ei.value.code == 100


def test_create_update_cas(kapi):
    r = kapi.create("/sdk/c", "v0")
    assert r.action == "create"
    with pytest.raises(KeysError) as ei:
        kapi.create("/sdk/c", "again")
    assert ei.value.code == 105
    r = kapi.update("/sdk/c", "v1")
    assert r.action == "update"
    r = kapi.set("/sdk/c", "v2", prev_value="v1")
    assert r.action == "compareAndSwap"
    r = kapi.set("/sdk/c", "v3", prev_index=r.node.modified_index)
    assert r.action == "compareAndSwap"


def test_create_in_order(kapi):
    r1 = kapi.create_in_order("/sdk/q", "one")
    r2 = kapi.create_in_order("/sdk/q", "two")
    assert r1.node.key < r2.node.key
    r = kapi.get("/sdk/q", recursive=True, sorted=True)
    assert [n.value for n in r.node.nodes] == ["one", "two"]


def test_quorum_get(kapi):
    kapi.set("/sdk/qr", "qv")
    assert kapi.get("/sdk/qr", quorum=True).node.value == "qv"


def test_dir_ttl(kapi):
    r = kapi.set("/sdk/ttldir", dir=True, ttl=100)
    assert r.node.dir and r.node.ttl >= 99


def test_watcher_follows_changes(kapi):
    kapi.set("/sdk/w", "w0")
    w = kapi.watcher("/sdk/w")
    got = []

    def run():
        for _ in range(2):
            got.append(w.next(timeout=10).node.value)

    th = threading.Thread(target=run)
    th.start()
    time.sleep(0.3)
    kapi.set("/sdk/w", "w1")
    # Watcher must pick up w2 even though it was written between polls.
    kapi.set("/sdk/w", "w2")
    th.join(timeout=15)
    assert not th.is_alive() and got == ["w1", "w2"]


def test_failover_and_sync(cluster):
    c = Client(["http://127.0.0.1:1", cluster[1].client_urls[0]],
               timeout=2.0)
    kapi = KeysAPI(c)
    assert kapi.set("/sdk/fo", "x").node.value == "x"  # dead endpoint skipped
    c.sync()
    assert len(c.endpoints) == 3


def test_members_api(cluster):
    c = Client([cluster[0].client_urls[0]])
    mapi = MembersAPI(c)
    ms = mapi.list()
    assert len(ms) == 3 and all(m.client_urls for m in ms)
    lead = mapi.leader()
    assert lead is not None
    lead_srv = next(m for m in cluster if m.server.is_leader())
    assert int(lead.id, 16) == lead_srv.server.id


def test_sdk_and_etcdctl_against_tenant_endpoint(tmp_path):
    """Existing etcd clients are DROP-IN against a tenant keyspace: the
    SDK (incl. the long-poll watcher) and etcdctl work unmodified when
    pointed at the engine's /tenants/{g} base URL — multi-tenant
    etcd-as-a-service without client changes."""
    import os
    import subprocess
    import sys as _sys

    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server.engine import EngineConfig, MultiEngine

    (cp,) = free_ports(1)
    eng = MultiEngine(EngineConfig(
        groups=2, peers=3, data_dir=str(tmp_path), window=16, max_ents=4,
        heartbeat_tick=3, fsync=False, request_timeout=15.0))
    http = EngineHttp(eng, port=cp)
    eng.start()
    http.start()
    try:
        deadline = time.time() + 120
        while time.time() < deadline and not all(
                eng.leader_slot(g) >= 0 for g in range(2)):
            time.sleep(0.05)
        kapi = KeysAPI(Client([f"{http.url}/tenants/1"]))
        r = kapi.set("/sdkkey", "hello")
        assert r.action == "set"
        g = kapi.get("/sdkkey")
        assert g.node.value == "hello"
        w = kapi.watcher("/sdkkey", after_index=g.node.modified_index)
        res = {}
        t = threading.Thread(target=lambda: res.update(ev=w.next(10)),
                             daemon=True)
        t.start()
        time.sleep(0.3)
        kapi.set("/sdkkey", "v2")
        t.join(12)
        assert res.get("ev") is not None and res["ev"].node.value == "v2"
        # Tenant isolation through the SDK: same key, other group.
        k0 = KeysAPI(Client([f"{http.url}/tenants/0"]))
        with pytest.raises(KeysError):
            k0.get("/sdkkey")

        env = dict(os.environ, PYTHONPATH=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            JAX_PLATFORMS="cpu")
        peers = f"{http.url}/tenants/0"

        def ctl(*args):
            return subprocess.run(
                [_sys.executable, "-m", "etcd_tpu.etcdctl.main",
                 "--peers", peers, *args],
                env=env, capture_output=True, text=True, timeout=60)

        assert ctl("set", "ck", "cv").returncode == 0
        out = ctl("get", "ck")
        assert out.returncode == 0 and out.stdout.strip() == "cv"
        out = ctl("ls", "/")
        assert out.returncode == 0 and "/ck" in out.stdout
    finally:
        http.stop()
        eng.stop()
