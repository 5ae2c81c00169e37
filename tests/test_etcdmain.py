"""etcdmain config parsing/validation + data-dir identification + proxy mode
(reference etcdmain/config.go Parse validations, etcd.go identifyDataDirOrDie,
proxy/ director+reverse tests)."""
import json
import os
import threading
import time

import pytest

from etcd_tpu.embed import Etcd, EtcdConfig
from etcd_tpu.etcdmain import ConfigError, parse_args
from etcd_tpu.etcdmain.config import MainConfig, parse_initial_cluster
from etcd_tpu.etcdmain.etcd import (DIR_EMPTY, DIR_MEMBER, DIR_PROXY,
                                    ProxyServer, identify_data_dir)
from etcd_tpu.proxy import Director, ReverseProxy, readonly
from etcd_tpu.etcdhttp.web import HttpServer, Router

from test_http import free_ports, req, form, FORM_HDR


# -- flag/env parsing ---------------------------------------------------------

def test_parse_defaults():
    cfg = parse_args([], env={})
    assert cfg.name == "default"
    assert cfg.initial_cluster == {"default": ["http://localhost:2380"]}
    assert cfg.listen_client_urls == ("http://localhost:2379",)
    assert cfg.heartbeat_interval == 100 and cfg.election_timeout == 1000
    assert not cfg.is_proxy


def test_parse_initial_cluster_multi_url():
    ic = parse_initial_cluster(
        "a=http://1.1.1.1:2380,b=http://2.2.2.2:2380,a=http://1.1.1.1:7001")
    assert ic == {"a": ["http://1.1.1.1:2380", "http://1.1.1.1:7001"],
                  "b": ["http://2.2.2.2:2380"]}
    with pytest.raises(ConfigError):
        parse_initial_cluster("no-equals-sign")


def test_initial_cluster_defaults_from_name():
    cfg = parse_args(["--name", "infra0"], env={})
    assert cfg.initial_cluster == {"infra0": ["http://localhost:2380"]}


def test_env_fallback_and_flag_precedence():
    env = {"ETCD_NAME": "fromenv", "ETCD_SNAPSHOT_COUNT": "42",
           "ETCD_FORCE_NEW_CLUSTER": "true"}
    cfg = parse_args([], env=env)
    assert cfg.name == "fromenv"
    assert cfg.snapshot_count == 42
    assert cfg.force_new_cluster is True
    # Command line wins over env (pkg/flags/flag.go:68-77).
    cfg = parse_args(["--name", "fromflag"], env=env)
    assert cfg.name == "fromflag"


def test_conflicting_bootstrap_flags():
    with pytest.raises(ConfigError):
        parse_args(["--initial-cluster", "a=http://x:1",
                    "--discovery", "http://disc/tok"], env={})
    with pytest.raises(ConfigError):
        parse_args(["--discovery-srv", "example.com",
                    "--discovery", "http://disc/tok"], env={})


def test_advertise_required_with_listen():
    with pytest.raises(ConfigError):
        parse_args(["--listen-client-urls", "http://127.0.0.1:9999"], env={})
    # but fine for proxies, and fine when advertise is given
    parse_args(["--listen-client-urls", "http://127.0.0.1:9999",
                "--proxy", "on"], env={})
    parse_args(["--listen-client-urls", "http://127.0.0.1:9999",
                "--advertise-client-urls", "http://127.0.0.1:9999"], env={})


def test_election_timeout_validation():
    with pytest.raises(ConfigError):
        parse_args(["--heartbeat-interval", "300"], env={})
    cfg = parse_args(["--heartbeat-interval", "50",
                      "--election-timeout", "500"], env={})
    assert cfg.election_ticks == 10


# -- data dir identification --------------------------------------------------

def test_identify_data_dir(tmp_path):
    assert identify_data_dir(str(tmp_path / "nope")) == DIR_EMPTY
    d = tmp_path / "m"
    (d / "member").mkdir(parents=True)
    assert identify_data_dir(str(d)) == DIR_MEMBER
    p = tmp_path / "p"
    (p / "proxy").mkdir(parents=True)
    assert identify_data_dir(str(p)) == DIR_PROXY
    (p / "member").mkdir()
    with pytest.raises(ConfigError):
        identify_data_dir(str(p))


# -- proxy mode ---------------------------------------------------------------

@pytest.fixture(scope="module")
def one_member(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("proxytgt")
    pport, cport = free_ports(2)
    cfg = EtcdConfig(
        name="m0", data_dir=str(tmp / "m0"),
        initial_cluster={"m0": [f"http://127.0.0.1:{pport}"]},
        listen_client_urls=[f"http://127.0.0.1:{cport}"],
        advertise_client_urls=[f"http://127.0.0.1:{cport}"],
        tick_ms=10, request_timeout=5.0)
    m = Etcd(cfg)
    m.start()
    assert m.wait_leader(10)
    yield m
    m.stop()


def _proxy_for(one_member, tmp_path, extra=None):
    cfg = MainConfig()
    cfg.data_dir = str(tmp_path / "pxy")
    cfg.proxy = "on" if extra is None else extra
    cfg.initial_cluster = {"m0": list(one_member.peer_urls)}
    cfg.listen_client_urls = ("http://127.0.0.1:0",)
    p = ProxyServer(cfg)
    p.start()
    # force a synchronous endpoint refresh so the test never races the
    # 30s director cycle
    p.director.refresh()
    return p


def test_proxy_forwards_kv(one_member, tmp_path):
    p = _proxy_for(one_member, tmp_path)
    try:
        base = p.client_urls[0]
        st, hdrs, body = req("PUT", base + "/v2/keys/pfoo",
                             form({"value": "bar"}), FORM_HDR)
        assert st == 201 and body["node"]["value"] == "bar"
        assert "X-Etcd-Index" in hdrs
        st, _, body = req("GET", base + "/v2/keys/pfoo")
        assert st == 200 and body["node"]["value"] == "bar"
        # cluster file got persisted with the member's peer URLs
        with open(os.path.join(cfg_dir(p), "cluster")) as f:
            assert json.load(f)["PeerURLs"] == list(one_member.peer_urls)
    finally:
        p.stop()


def cfg_dir(p):
    return os.path.join(p.cfg.data_dir, "proxy")


def test_readonly_proxy_rejects_writes(one_member, tmp_path):
    p = _proxy_for(one_member, tmp_path, extra="readonly")
    try:
        base = p.client_urls[0]
        st, _, _ = req("PUT", base + "/v2/keys/rofoo",
                       form({"value": "x"}), FORM_HDR)
        assert st == 501
        st, _, _ = req("GET", base + "/v2/keys/")
        assert st == 200
    finally:
        p.stop()


def test_proxy_no_endpoints_503():
    d = Director(lambda: [], refresh_interval=3600)
    rp = ReverseProxy(d)
    router = Router()
    router.add("/", rp.handle)
    h = HttpServer("127.0.0.1", 0, router)
    h.start()
    try:
        st, _, body = req("GET", h.url + "/v2/keys/x")
        assert st == 503
    finally:
        d.stop()
        h.stop()


def test_env_bad_int_is_config_error():
    with pytest.raises(ConfigError):
        parse_args([], env={"ETCD_SNAPSHOT_COUNT": "abc"})


def test_member_dir_refuses_proxy_mode(tmp_path):
    from etcd_tpu.etcdmain.etcd import main
    d = tmp_path / "was-member"
    (d / "member").mkdir(parents=True)
    assert main(["--proxy", "on", "--data-dir", str(d)]) == 1
    # No proxy/ dir was planted beside member/.
    assert identify_data_dir(str(d)) == DIR_MEMBER


def test_proxy_passes_watch_longpoll(one_member, tmp_path):
    """A wait=true long-poll parks at the proxy until the member answers
    (the reference proxy has no response deadline — reverse.go)."""
    p = _proxy_for(one_member, tmp_path)
    try:
        base = p.client_urls[0]
        got = {}

        def watch():
            got["resp"] = req("GET", base + "/v2/keys/lpk?wait=true",
                              timeout=30)

        t = threading.Thread(target=watch, daemon=True)
        t.start()
        time.sleep(0.5)  # let the long-poll park
        st, _, _ = req("PUT", base + "/v2/keys/lpk", form({"value": "now"}),
                       FORM_HDR)
        assert st == 201
        t.join(timeout=15)
        assert not t.is_alive(), "watch through proxy never completed"
        st, _, body = got["resp"]
        assert st == 200 and body["node"]["value"] == "now"
        # the member was never quarantined by the parked poll
        assert len(p.director.endpoints()) >= 1
    finally:
        p.stop()


def test_proxy_fails_over_dead_endpoint(one_member, tmp_path):
    (dead,) = free_ports(1)
    urls = [f"http://127.0.0.1:{dead}"] + list(one_member.client_urls)
    d = Director(lambda: urls, refresh_interval=3600, failure_wait=60)
    # deterministic order: dead endpoint first
    d._eps.sort(key=lambda ep: ep.url != f"http://127.0.0.1:{dead}")
    rp = ReverseProxy(d)
    router = Router()
    router.add("/", rp.handle)
    h = HttpServer("127.0.0.1", 0, router)
    h.start()
    try:
        st, _, body = req("GET", h.url + "/v2/keys/")
        assert st == 200
        # the dead endpoint is now quarantined
        assert len(d.endpoints()) == len(urls) - 1
    finally:
        d.stop()
        h.stop()


# -- engine mode --------------------------------------------------------------

def test_engine_flags_validation():
    with pytest.raises(ConfigError):
        parse_args(["--engine-groups", "4", "--proxy", "on"])
    with pytest.raises(ConfigError):
        parse_args(["--engine-groups", "4", "--discovery", "http://x"])
    cfg = parse_args(["--engine-groups", "8", "--engine-peers", "3",
                      "--listen-client-urls", "http://127.0.0.1:0"])
    assert cfg.is_engine and cfg.engine_groups == 8 and cfg.engine_peers == 3


def test_engine_mode_serves_and_restarts(tmp_path):
    """The CLI engine mode end-to-end in process: tenants served over
    HTTP, data dir identified as engine/, restart keeps data."""
    import json as _json
    import urllib.request

    from etcd_tpu.etcdmain.etcd import DIR_ENGINE, EngineServer

    def put(base, g, key, val):
        r = urllib.request.Request(
            f"{base}/tenants/{g}/v2/keys/{key}",
            data=f"value={val}".encode(), method="PUT",
            headers={"Content-Type": "application/x-www-form-urlencoded"})
        with urllib.request.urlopen(r, timeout=30) as resp:
            return resp.status, _json.loads(resp.read())

    cfg = MainConfig()
    cfg.data_dir = str(tmp_path / "eng")
    cfg.engine_groups, cfg.engine_peers = 4, 3
    cfg.listen_client_urls = ("http://127.0.0.1:0",)
    s = EngineServer(cfg)
    s.start()
    try:
        assert s.engine.wait_leaders(60.0)
        base = s.client_urls[0]
        st, b = put(base, 2, "cli", "fromflags")
        assert st == 201 and b["node"]["value"] == "fromflags"
    finally:
        s.stop()
    assert identify_data_dir(cfg.data_dir) == DIR_ENGINE

    s2 = EngineServer(cfg)
    s2.start()
    try:
        base = s2.client_urls[0]
        with urllib.request.urlopen(f"{base}/tenants/2/v2/keys/cli",
                                    timeout=30) as resp:
            b = _json.loads(resp.read())
        assert b["node"]["value"] == "fromflags"
    finally:
        s2.stop()


def test_engine_mode_refuses_member_dir(tmp_path):
    from etcd_tpu.etcdmain.etcd import main as etcd_main
    d = tmp_path / "was-member"
    (d / "member").mkdir(parents=True)
    rc = etcd_main(["--engine-groups", "2", "--data-dir", str(d)])
    assert rc == 1


def test_engine_flag_ranges():
    for bad in (["--engine-groups", "-1"],
                ["--engine-groups", "4", "--engine-peers", "0"],
                ["--engine-groups", "4", "--engine-window", "2"],
                ["--engine-groups", "4", "--engine-mesh-peers-axis", "-1"]):
        with pytest.raises(ConfigError):
            parse_args(bad)


def test_engine_geometry_mismatch_refused(tmp_path):
    from etcd_tpu.server.engine import EngineConfig, MultiEngine
    d = str(tmp_path / "geo")
    eng = MultiEngine(EngineConfig(groups=4, peers=3, window=16,
                                   data_dir=d, fsync=False))
    eng.stop()
    # Peer/window changes and pool SHRINKS refuse; growth is allowed
    # (tenant lifecycle: the pool may be enlarged across restarts).
    with pytest.raises(ValueError, match="geometry"):
        MultiEngine(EngineConfig(groups=4, peers=5, window=16,
                                 data_dir=d, fsync=False))
    with pytest.raises(ValueError, match="geometry"):
        MultiEngine(EngineConfig(groups=2, peers=3, window=16,
                                 data_dir=d, fsync=False))
    # Same geometry reopens fine; a grown pool also reopens fine.
    eng2 = MultiEngine(EngineConfig(groups=4, peers=3, window=16,
                                    data_dir=d, fsync=False))
    eng2.stop()
    eng3 = MultiEngine(EngineConfig(groups=8, peers=3, window=16,
                                    data_dir=d, fsync=False))
    eng3.stop()


def test_engine_member_sizes_the_young_generations(tmp_path):
    """The engine member's entry sets the collector's thresholds so that a
    request does not outlive a young collection (etcd.py
    ENGINE_GC_THRESHOLD): with CPython's 700 every request is promoted and
    the O(G) heap is traversed, all threads stopped, every few seconds."""
    import gc

    from etcd_tpu.etcdmain.etcd import ENGINE_GC_THRESHOLD, EngineServer

    cfg = MainConfig()
    cfg.data_dir = str(tmp_path / "gceng")
    cfg.engine_groups, cfg.engine_peers = 4, 3
    cfg.listen_client_urls = ("http://127.0.0.1:0",)
    was = gc.get_threshold()
    try:
        s = EngineServer(cfg)
        s.start()
        try:
            assert gc.get_threshold() == ENGINE_GC_THRESHOLD
            assert ENGINE_GC_THRESHOLD[0] >= 10_000
        finally:
            s.stop()
    finally:
        gc.set_threshold(*was)


def test_engine_mesh_flag_serves(tmp_path):
    """--engine-mesh-peers-axis shards the CLI engine over all visible
    devices (the 8-device CPU mesh under conftest) and still serves."""
    import json as _json
    import urllib.request

    from etcd_tpu.etcdmain.etcd import EngineServer

    cfg = MainConfig()
    cfg.data_dir = str(tmp_path / "mesheng")
    cfg.engine_groups, cfg.engine_peers = 8, 4
    cfg.engine_mesh_peers_axis = 2
    cfg.listen_client_urls = ("http://127.0.0.1:0",)
    s = EngineServer(cfg)
    s.start()
    try:
        assert s.engine.cfg.mesh is not None
        assert len(s.engine.st.term.devices()) == 8
        assert s.engine.wait_leaders(60.0)
        base = s.client_urls[0]
        r = urllib.request.Request(
            f"{base}/tenants/1/v2/keys/meshflag", data=b"value=on",
            method="PUT",
            headers={"Content-Type": "application/x-www-form-urlencoded"})
        with urllib.request.urlopen(r, timeout=30) as resp:
            assert resp.status == 201
            assert _json.loads(resp.read())["node"]["value"] == "on"
    finally:
        s.stop()


def test_engine_mesh_divisibility_errors(tmp_path):
    from etcd_tpu.etcdmain.etcd import EngineServer, main as etcd_main

    cfg = MainConfig()
    cfg.data_dir = str(tmp_path / "bad")
    cfg.engine_groups, cfg.engine_peers = 5, 4   # 5 % 8 != 0
    cfg.engine_mesh_peers_axis = 1
    cfg.listen_client_urls = ("http://127.0.0.1:0",)
    with pytest.raises(ConfigError, match="divisible"):
        EngineServer(cfg)
    # And via main(): clean exit code, no traceback.
    rc = etcd_main(["--engine-groups", "5", "--engine-peers", "4",
                    "--engine-mesh-peers-axis", "1",
                    "--data-dir", str(tmp_path / "bad2")])
    assert rc == 1
