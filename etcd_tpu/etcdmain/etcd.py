"""Process entry for `etcd-tpu` (python -m etcd_tpu).

Behavioral equivalent of reference etcdmain/etcd.go Main(): parse
flags/env, default the data dir from the member name (etcd.go:96-99),
identify whether the data dir was previously a member or a proxy
(identifyDataDirOrDie etcd.go:376-404) and start the matching mode;
discovery full-cluster errors fall back to proxy mode when configured
(etcd.go:99-107).
"""
from __future__ import annotations

import gc
import json
import logging
import os
import signal
import sys
import threading
from typing import List, Optional, Sequence

from etcd_tpu.embed import Etcd, EtcdConfig
from etcd_tpu.etcdhttp.web import HttpServer, Router
from etcd_tpu.utils.tlsutil import TLSInfo
from etcd_tpu.etcdmain.config import (ConfigError, MainConfig,
                                      PROXY_READONLY, parse_args)
from etcd_tpu.proxy import (Director, ReverseProxy, fetch_cluster_urls,
                            readonly, write_cluster_file)
from etcd_tpu.proxy.director import PROXY_DIR_NAME

log = logging.getLogger("etcdmain")

DIR_MEMBER, DIR_PROXY, DIR_ENGINE, DIR_EMPTY = ("member", PROXY_DIR_NAME,
                                                "engine", "empty")

# The engine member's collector thresholds (CPython's default: 700, 10, 10).
# A request lives a few rounds (tens to hundreds of ms) and a round stages a
# hundred of them, so with a young collection every 700 net container
# allocations every request survives a gen-0 and a gen-1 collection and is
# counted as long-lived; once those counts reach a quarter of the heap
# CPython traverses ALL of it with every thread stopped: O(G) tenant stores
# and API objects (1.1 M objects at G=50,000) for garbage that reference
# counts had freed long before. Young collections rarer than a request's
# life leave the old generation to what really is long-lived; a gen-1
# collection every second gen-0 keeps any one of them under ~100,000 objects.
ENGINE_GC_THRESHOLD = (50_000, 2, 10)


def identify_data_dir(dir_: str) -> str:
    """Which mode this data dir was used for (reference etcd.go:376-404;
    engine/ is this framework's multi-tenant mode)."""
    try:
        names = os.listdir(dir_)
    except FileNotFoundError:
        return DIR_EMPTY
    present = [d for d in (DIR_MEMBER, DIR_PROXY, DIR_ENGINE)
               if d in names]
    if len(present) > 1:
        raise ConfigError(
            f"invalid datadir: {' and '.join(present)} directories both "
            "exist")
    return present[0] if present else DIR_EMPTY


def start_etcd(cfg: MainConfig) -> Etcd:
    """Launch a consensus member (reference startEtcd etcd.go:127-231)."""
    initial_cluster = dict(cfg.initial_cluster)
    token = cfg.initial_cluster_token
    if cfg.discovery or cfg.discovery_srv:
        from etcd_tpu.discovery import (join_cluster, srv_cluster)
        if not os.path.isdir(os.path.join(cfg.data_dir, "member")):
            if cfg.discovery:
                s = join_cluster(cfg.discovery, cfg.name,
                                 cfg.initial_advertise_peer_urls,
                                 proxy_url=cfg.discovery_proxy)
            else:
                s = srv_cluster(cfg.discovery_srv, cfg.name,
                                cfg.initial_advertise_peer_urls)
            from etcd_tpu.etcdmain.config import parse_initial_cluster
            initial_cluster = parse_initial_cluster(s)
            token = cfg.discovery or cfg.discovery_srv

    ecfg = EtcdConfig(
        name=cfg.name,
        data_dir=cfg.data_dir,
        initial_cluster=initial_cluster,
        listen_peer_urls=cfg.listen_peer_urls,
        listen_client_urls=cfg.listen_client_urls,
        advertise_client_urls=cfg.advertise_client_urls,
        cluster_token=token,
        snap_count=cfg.snapshot_count,
        tick_ms=cfg.heartbeat_interval,
        election_ticks=cfg.election_ticks,
        initial_cluster_state=cfg.initial_cluster_state,
        force_new_cluster=cfg.force_new_cluster,
        cors=cfg.cors,
        client_tls=TLSInfo(cert_file=cfg.cert_file, key_file=cfg.key_file,
                           ca_file=cfg.ca_file,
                           client_cert_auth=cfg.client_cert_auth),
        peer_tls=TLSInfo(cert_file=cfg.peer_cert_file,
                         key_file=cfg.peer_key_file,
                         ca_file=cfg.peer_ca_file,
                         client_cert_auth=cfg.peer_client_cert_auth),
    )
    e = Etcd(ecfg)
    e.start()
    log.info("etcd-tpu member %s listening: client=%s peer=%s",
             cfg.name, e.client_urls, e.peer_urls)
    return e


class EngineServer:
    """Multi-tenant engine mode: G consensus groups served from one
    batched kernel at /tenants/{g}/v2/keys (docs/deployment.md §2)."""

    def __init__(self, cfg: MainConfig) -> None:
        import jax

        from etcd_tpu.etcdhttp.tenants import EngineHttp
        from etcd_tpu.server.engine import EngineConfig, MultiEngine
        from etcd_tpu.utils.platform import enable_compile_cache

        gc.set_threshold(*ENGINE_GC_THRESHOLD)
        # The process entry owns the compile cache: a served member must
        # not recompile its step variants on every boot, and must not
        # need a script's help to avoid it.
        cache_dir = enable_compile_cache()
        devs = jax.devices()
        n = len(devs)
        log.info("engine: %d %s device(s) (%s); compile cache %s",
                 n, devs[0].platform, devs[0].device_kind, cache_dir)
        mesh = None
        if cfg.engine_mesh_peers_axis > 0:
            from etcd_tpu.parallel.mesh import make_mesh
            pa = cfg.engine_mesh_peers_axis
            # Fail with a flag-level message, not an opaque sharding error
            # from deep inside device placement.
            if n < 2:
                raise ConfigError(
                    f"-engine-mesh-peers-axis {pa} asks for a device mesh "
                    f"but only {n} {devs[0].platform} device is visible "
                    "(a 1x1 mesh shards nothing); drop the flag to run "
                    "on one device")
            if n % pa != 0:
                raise ConfigError(
                    f"-engine-mesh-peers-axis {pa} does not divide the "
                    f"{n} visible devices")
            if cfg.engine_peers % pa != 0:
                raise ConfigError(
                    f"-engine-peers {cfg.engine_peers} must be divisible "
                    f"by -engine-mesh-peers-axis {pa}")
            if cfg.engine_groups % (n // pa) != 0:
                raise ConfigError(
                    f"-engine-groups {cfg.engine_groups} must be "
                    f"divisible by the groups mesh axis ({n // pa} = "
                    f"{n} devices / peers-axis {pa})")
            mesh = make_mesh(devs, peers_axis=pa)
            log.info("engine: sharding over mesh %s",
                     dict(zip(mesh.axis_names, mesh.devices.shape)))
        self.engine = MultiEngine(EngineConfig(
            groups=cfg.engine_groups, peers=cfg.engine_peers,
            window=cfg.engine_window,
            data_dir=os.path.join(cfg.data_dir, DIR_ENGINE),
            applier_shards=cfg.engine_applier_shards,
            wal_shards=cfg.engine_wal_shards,
            lag_share=cfg.engine_lag_share,
            lag_hold_rounds=cfg.engine_lag_hold_rounds,
            lag_seed=cfg.engine_lag_seed,
            churn_down_rounds=cfg.engine_churn_down_rounds,
            churn_period_rounds=cfg.engine_churn_period_rounds,
            churn_seed=cfg.engine_churn_seed,
            mesh=mesh))
        client_tls = TLSInfo(cert_file=cfg.cert_file, key_file=cfg.key_file,
                             ca_file=cfg.ca_file,
                             client_cert_auth=cfg.client_cert_auth)
        self.http = []
        from etcd_tpu.embed import _listen_addr
        for url in cfg.listen_client_urls:
            host, port = _listen_addr(url)
            self.http.append(EngineHttp(
                self.engine, host, port,
                cors=set(cfg.cors) if cfg.cors else None,
                tls_context=(client_tls.server_context()
                             if not client_tls.empty() else None)))

    @property
    def client_urls(self):
        return [h.url for h in self.http]

    def start(self) -> None:
        for h in self.http:
            h.start()
        self.engine.start()
        log.info("engine: %d tenant groups x %d peers listening on %s",
                 self.engine.cfg.groups, self.engine.cfg.peers,
                 self.client_urls)

    def stop(self) -> None:
        self.engine.stop()
        for h in self.http:
            h.stop()


class ProxyServer:
    """Proxy mode: stateless fan-out to cluster members, endpoint view
    persisted in <data-dir>/proxy/cluster (reference startProxy
    etcdmain/etcd.go:234-335)."""

    def __init__(self, cfg: MainConfig) -> None:
        self.cfg = cfg
        proxy_dir = os.path.join(cfg.data_dir, DIR_PROXY)
        os.makedirs(proxy_dir, exist_ok=True)
        self._clusterfile = os.path.join(proxy_dir, "cluster")

        if os.path.exists(self._clusterfile):
            with open(self._clusterfile) as f:
                self._peer_urls = json.load(f)["PeerURLs"]
            log.info("proxy: using peer urls %s from cluster file",
                     self._peer_urls)
        else:
            self._peer_urls = [u for urls in cfg.initial_cluster.values()
                               for u in urls]
            if cfg.discovery:
                from etcd_tpu.discovery import get_cluster
                from etcd_tpu.etcdmain.config import parse_initial_cluster
                s = get_cluster(cfg.discovery, proxy_url=cfg.discovery_proxy)
                self._peer_urls = [u for urls in
                                   parse_initial_cluster(s).values()
                                   for u in urls]

        # The proxy honors the same TLS + CORS flags as a member: the
        # client TLSInfo secures its listener AND its outbound transport to
        # the cluster (reference startProxy, etcdmain/etcd.go:234-335);
        # the peer TLSInfo authenticates the /members refresh against
        # mutual-TLS peer listeners.
        client_tls = TLSInfo(cert_file=cfg.cert_file, key_file=cfg.key_file,
                             ca_file=cfg.ca_file,
                             client_cert_auth=cfg.client_cert_auth)
        peer_tls = TLSInfo(cert_file=cfg.peer_cert_file,
                           key_file=cfg.peer_key_file,
                           ca_file=cfg.peer_ca_file,
                           client_cert_auth=cfg.peer_client_cert_auth)
        self._out_ctx = (client_tls.client_context()
                         if not client_tls.empty() else None)
        self._peer_ctx = (peer_tls.client_context()
                          if not peer_tls.empty() else None)
        self.director = Director(self._refresh_urls)
        rp = ReverseProxy(self.director, tls_context=self._out_ctx)
        handler = readonly(rp.handle) if cfg.is_readonly_proxy else rp.handle
        self.http: List[HttpServer] = []
        for url in cfg.listen_client_urls:
            from etcd_tpu.embed import _listen_addr
            host, port = _listen_addr(url)
            router = Router()
            router.add("/", handler)
            self.http.append(HttpServer(
                host, port, router,
                cors=set(cfg.cors) if cfg.cors else None,
                tls_context=(client_tls.server_context()
                             if not client_tls.empty() else None)))

    def _refresh_urls(self) -> List[str]:
        client_urls, peer_urls = fetch_cluster_urls(
            self._peer_urls, tls_context=self._peer_ctx)
        if peer_urls:
            self._peer_urls = peer_urls
            write_cluster_file(self.cfg.data_dir, peer_urls)
        return client_urls

    @property
    def client_urls(self) -> List[str]:
        return [h.url for h in self.http]

    def start(self) -> None:
        for h in self.http:
            h.start()
        log.info("proxy: listening on %s", self.client_urls)

    def stop(self) -> None:
        self.director.stop()
        for h in self.http:
            h.stop()


def main(argv: Optional[Sequence[str]] = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s: %(message)s")
    try:
        cfg = parse_args(sys.argv[1:] if argv is None else argv)
    except ConfigError as e:
        print(f"error verifying flags, {e}. See 'etcd-tpu --help'.",
              file=sys.stderr)
        return 1
    if cfg.debug:
        logging.getLogger().setLevel(logging.DEBUG)

    if not cfg.data_dir:
        cfg.data_dir = f"{cfg.name}.etcd"
        log.info("no data-dir provided, using default data-dir ./%s",
                 cfg.data_dir)

    try:
        which = identify_data_dir(cfg.data_dir)
    except ConfigError as e:
        print(str(e), file=sys.stderr)
        return 1
    if which != DIR_EMPTY:
        log.info("already initialized as %s before, starting as etcd %s...",
                 which, which)

    stop_ev = threading.Event()

    def wait_for_stop() -> None:
        # A TIMED wait: the kernel may hand SIGTERM to any thread (JAX
        # starts dozens), CPython only runs the handler on the main
        # thread, and an untimed Event.wait() there is woken by nothing
        # else — the member then ignores SIGTERM for good (seen ~1 in 7
        # boots under load). Waking twice a second lets it run.
        while not stop_ev.wait(0.5):
            pass

    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            signal.signal(sig, lambda *_: stop_ev.set())
        except ValueError:
            pass  # not the main thread (tests)

    if cfg.is_proxy and which == DIR_MEMBER:
        # Refuse rather than plant a proxy/ dir beside member/ — that would
        # make the data dir permanently unidentifiable.
        print(f"cannot start as proxy: data dir {cfg.data_dir} was "
              f"previously initialized as a member", file=sys.stderr)
        return 1
    if cfg.is_engine != (which == DIR_ENGINE) and which != DIR_EMPTY:
        requested = ("engine" if cfg.is_engine
                     else "proxy" if cfg.is_proxy else "member")
        print(f"cannot start as {requested}: data dir {cfg.data_dir} was "
              f"previously initialized as {which}", file=sys.stderr)
        return 1

    if cfg.is_engine:
        try:
            runner = EngineServer(cfg)
        except (ConfigError, ValueError) as e:
            # Flag/geometry-level refusals answer like other config
            # errors, not with a traceback.
            print(str(e), file=sys.stderr)
            return 1
        runner.start()
        try:
            wait_for_stop()
        finally:
            runner.stop()
        return 0

    runner = None
    should_proxy = cfg.is_proxy or which == DIR_PROXY
    if not should_proxy:
        try:
            runner = start_etcd(cfg)
        except Exception as e:
            from etcd_tpu.discovery import FullClusterError
            if (isinstance(e, FullClusterError) and
                    cfg.should_fallback_to_proxy):
                log.info("discovery cluster full, falling back to proxy")
                should_proxy = True
            else:
                print(str(e), file=sys.stderr)
                return 1
    if should_proxy:
        runner = ProxyServer(cfg)
        runner.start()

    try:
        wait_for_stop()
    finally:
        runner.stop()
    return 0
