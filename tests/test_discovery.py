"""Discovery bootstrap tests: a real etcd_tpu member doubles as the
discovery service (the reference's public service is itself an etcd
cluster; a custom discovery endpoint is exactly
`http://host:port/v2/keys/<registry-path>` — Documentation/clustering.md).
Covers checkCluster/createSelf/waitNodes, full-cluster and duplicate-id
errors, GetCluster for proxies, and DNS SRV synthesis with a fake resolver
(reference discovery/discovery_test.go, srv.go)."""
import threading

import pytest

from etcd_tpu.client import Client, KeysAPI
from etcd_tpu.discovery import (DuplicateIDError, FullClusterError,
                                SizeNotFoundError, get_cluster, join_cluster,
                                srv_cluster)
from etcd_tpu.embed import Etcd, EtcdConfig
from etcd_tpu.etcdmain.config import parse_initial_cluster

from test_http import free_ports


@pytest.fixture(scope="module")
def disco(tmp_path_factory):
    """(member, base discovery URL maker) — each test gets its own token."""
    tmp = tmp_path_factory.mktemp("disco")
    pport, cport = free_ports(2)
    cfg = EtcdConfig(
        name="d0", data_dir=str(tmp / "d0"),
        initial_cluster={"d0": [f"http://127.0.0.1:{pport}"]},
        listen_client_urls=[f"http://127.0.0.1:{cport}"],
        tick_ms=10, request_timeout=5.0)
    m = Etcd(cfg)
    m.start()
    assert m.wait_leader(10)
    yield m
    m.stop()


def _setup_token(member, token, size):
    kapi = KeysAPI(Client(list(member.client_urls)))
    kapi.set(f"_etcd/registry/{token}/_config/size", str(size))
    return f"{member.client_urls[0]}/v2/keys/_etcd/registry/{token}"


def test_join_three_members(disco):
    durl = _setup_token(disco, "tok3", 3)
    results = {}

    def join(i):
        results[i] = join_cluster(durl, f"m{i}",
                                  [f"http://127.0.0.1:1238{i}"],
                                  max_retries=2)

    threads = [threading.Thread(target=join, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "discovery join deadlocked"

    # All three see the same 3-member cluster.
    parsed = parse_initial_cluster(results[0])
    assert len(parsed) == 3
    assert parsed["m1"] == ["http://127.0.0.1:12381"]
    assert len({tuple(sorted(v)) for v in results.values()}) == 1


def test_full_cluster_rejected_and_get_cluster(disco):
    durl = _setup_token(disco, "tokfull", 1)
    s = join_cluster(durl, "first", ["http://127.0.0.1:23801"],
                     max_retries=2)
    assert parse_initial_cluster(s) == {"first": ["http://127.0.0.1:23801"]}
    with pytest.raises(FullClusterError):
        join_cluster(durl, "second", ["http://127.0.0.1:23802"],
                     max_retries=2)
    # Latecomers (proxies) can still fetch the formed cluster.
    s2 = get_cluster(durl, max_retries=2)
    assert parse_initial_cluster(s2) == {"first": ["http://127.0.0.1:23801"]}


def test_duplicate_id_rejected(disco):
    from etcd_tpu.discovery.discovery import _Discovery
    from etcd_tpu.server.cluster import compute_member_id

    durl = _setup_token(disco, "tokdup", 3)
    join_peer = ["http://127.0.0.1:23811"]
    # Register "a" synchronously (createSelf half of joinCluster) so the
    # duplicate attempt below deterministically loses the create.
    mid = compute_member_id(join_peer, durl)
    d1 = _Discovery(durl, mid, max_retries=2)
    d1.check_cluster()
    d1.create_self(f"a={join_peer[0]}")

    # Same advertised URLs + same durl → same computed member ID → rejected.
    with pytest.raises(DuplicateIDError):
        join_cluster(durl, "a-again", join_peer, max_retries=2)

    # Fill the remaining slots concurrently so every joiner can complete.
    t2 = threading.Thread(
        target=lambda: join_cluster(durl, "b", ["http://127.0.0.1:23812"],
                                    max_retries=2))
    t2.start()
    join_cluster(durl, "c", ["http://127.0.0.1:23813"], max_retries=2)
    t2.join(timeout=30)
    assert not t2.is_alive()
    # ...and "a" itself can still complete its join.
    nodes, size, index = d1.check_cluster()
    assert len(nodes) == 3 and size == 3


def test_size_key_missing(disco):
    durl = f"{disco.client_urls[0]}/v2/keys/_etcd/registry/nosuchtok"
    with pytest.raises(SizeNotFoundError):
        join_cluster(durl, "x", ["http://127.0.0.1:23899"], max_retries=2)


def test_srv_cluster_fake_resolver():
    def lookup(service, proto, domain):
        assert proto == "tcp" and domain == "example.com"
        if service == "etcd-server-ssl":
            return []
        return [("infra0.example.com", 2380), ("infra1.example.com", 2380),
                ("infra2.example.com", 2380)]

    s = srv_cluster("example.com", "infra0",
                    ["http://infra0.example.com:2380"], lookup=lookup)
    parsed = parse_initial_cluster(s)
    assert parsed["infra0"] == ["http://infra0.example.com:2380"]
    assert len(parsed) == 3  # two others got ordinal names


def test_srv_cluster_no_records():
    with pytest.raises(RuntimeError):
        srv_cluster("example.com", "x", [], lookup=lambda *a: [])


@pytest.mark.slow
def test_discovery_hosted_on_a_tenant_keyspace(tmp_path):
    """The engine is its own discovery service: seed the registry size
    key in a TENANT keyspace, then bootstrap a classic 3-member cluster
    with --discovery pointed at the tenant URL (the reference's
    discovery.etcd.io is itself just an etcd; here one tenant of the
    batched engine plays that role). Subprocess members exercise the
    full etcdmain discovery path against the tenant surface."""
    import json
    import os
    import subprocess
    import sys
    import time
    import urllib.error
    import urllib.request

    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server.engine import EngineConfig, MultiEngine

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def put(url, body):
        req = urllib.request.Request(
            url, body, {"Content-Type": "application/x-www-form-urlencoded"},
            method="PUT")
        try:
            with urllib.request.urlopen(req, timeout=20) as r:
                return r.status
        except urllib.error.HTTPError as e:
            e.read()
            return e.code

    (ep,) = free_ports(1)
    eng = MultiEngine(EngineConfig(
        groups=2, peers=3, data_dir=str(tmp_path / "eng"), window=16,
        max_ents=4, heartbeat_tick=3, fsync=False, request_timeout=15.0))
    http = EngineHttp(eng, port=ep)
    eng.start()
    http.start()
    procs = []
    try:
        deadline = time.time() + 120
        while time.time() < deadline and not all(
                eng.leader_slot(g) >= 0 for g in range(2)):
            time.sleep(0.05)
        disc = f"{http.url}/tenants/1/v2/keys/_etcd/registry/tok1"
        assert put(f"{disc}/_config/size", b"value=3") in (200, 201)

        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        ports = [free_ports(2) for _ in range(3)]
        for i, (pp, cp) in enumerate(ports):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "etcd_tpu", "--name", f"m{i}",
                 "--data-dir", str(tmp_path / f"m{i}"),
                 "--listen-peer-urls", f"http://127.0.0.1:{pp}",
                 "--initial-advertise-peer-urls", f"http://127.0.0.1:{pp}",
                 "--listen-client-urls", f"http://127.0.0.1:{cp}",
                 "--advertise-client-urls", f"http://127.0.0.1:{cp}",
                 "--discovery", disc,
                 "--heartbeat-interval", "20",
                 "--election-timeout", "200"],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))
        deadline = time.time() + 150
        n = 0
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{ports[0][1]}/v2/members",
                        timeout=3) as r:
                    n = len(json.loads(r.read())["members"])
                if n == 3:
                    break
            except Exception:  # noqa: BLE001 — members still booting
                pass
            time.sleep(1)
        assert n == 3, f"cluster formed with {n} members"
        # 301 during election windows: retry like a real client.
        ok = False
        for _ in range(30):
            if put(f"http://127.0.0.1:{ports[1][1]}/v2/keys/bootok",
                   b"value=1") in (200, 201):
                ok = True
                break
            time.sleep(1)
        assert ok, "bootstrapped cluster never served a write"
        # The registry in the tenant recorded all three members.
        with urllib.request.urlopen(f"{disc}?recursive=true",
                                    timeout=10) as r:
            reg = json.loads(r.read())
        slots = [nd for nd in reg["node"].get("nodes", [])
                 if not nd["key"].endswith("_config")]
        assert len(slots) == 3, reg
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
        http.stop()
        eng.stop()
