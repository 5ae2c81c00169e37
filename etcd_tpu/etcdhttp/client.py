"""The public v2 HTTP API.

Behavioral equivalent of reference etcdserver/etcdhttp/client.go: the full
/v2/keys matrix (CRUD, CAS/CAD, in-order POST, TTL, long-poll + streaming
watch — parseKeyRequest client.go:390-534, writeKeyEvent client.go:536-551,
handleKeyWatch client.go:553-597), /v2/members admin (client.go:180-286),
/v2/machines, /v2/stats/{self,leader,store}, /version and /health, with the
X-Etcd-Cluster-ID / X-Etcd-Index / X-Raft-Index / X-Raft-Term header
contract and the numeric-error JSON bodies of error/error.go.
"""
from __future__ import annotations

import json
import posixpath
from typing import Optional

from etcd_tpu import errors, version as ver
from etcd_tpu.utils import metrics
from etcd_tpu.server.cluster import Member, STORE_KEYS_PREFIX
from etcd_tpu.server.request import (METHOD_DELETE, METHOD_GET, METHOD_POST,
                                     METHOD_PUT, Request)
from etcd_tpu.etcdhttp.web import REPLIED, Ctx, LoopOp, Router
from etcd_tpu.store.event import Event, format_expiration

KEYS_PREFIX = "/v2/keys"
MEMBERS_PREFIX = "/v2/members"
MACHINES_PREFIX = "/v2/machines"
STATS_PREFIX = "/v2/stats"

_BOOL_FIELDS = ("recursive", "sorted", "quorum", "wait", "stream", "dir",
                "refresh", "noValueOnSuccess")
_BOOL_SET = frozenset(_BOOL_FIELDS)
_NO_FLAGS = (False,) * len(_BOOL_FIELDS)

_KEYS_METHODS = ("GET", "PUT", "POST", "DELETE", "HEAD")

# Actions whose successful response is 201 Created (reference
# store/event.go IsCreated: create, or set with prevExist=false).
_CREATED_ACTIONS = {"create"}

# A str as json.dumps writes it.
_json_str = json.encoder.encode_basestring_ascii


def _bool_of(get, field: str) -> bool:
    """A flag among a request's parameters (`get` is Ctx.params().get)."""
    v = get(field)
    if v is None or v[0] in ("", "false"):
        return False
    if v[0] == "true":
        return True
    raise errors.EtcdError(errors.ECODE_INVALID_FIELD,
                           cause=f'invalid value for "{field}"')


def _count_of(get, field: str, code: int) -> int:
    """A non-negative integer parameter, 0 where absent or blank."""
    v = get(field)
    if not v or not v[0]:
        return 0
    try:
        n = int(v[0])
        if n < 0:
            raise ValueError
    except ValueError:
        raise errors.EtcdError(code, cause=f'invalid value for "{field}"')
    return n


def trim_prefix(d: dict, prefix: str = STORE_KEYS_PREFIX) -> dict:
    """Strip the internal keys prefix from every node key in a response body
    (reference trimEventPrefix / trimNodeExternPrefix client.go:600-625),
    in place: `d` is an Event.to_dict() of the caller's own."""
    def trim_node(n: dict) -> None:
        k = n.get("key", "")
        if k.startswith(prefix):
            n["key"] = k[len(prefix):] or "/"
        for c in n.get("nodes") or ():
            trim_node(c)

    for field in ("node", "prevNode"):
        if d.get(field) is not None:
            trim_node(d[field])
    return d


def _leaf_json(n, prefix: str = STORE_KEYS_PREFIX) -> str:
    """json.dumps(trim_prefix(...)) of a NodeExtern that lists no
    children, straight from its fields in NodeExtern.to_dict's order."""
    k = n.key
    if k.startswith(prefix):
        k = k[len(prefix):] or "/"
    out = '{"key": ' + _json_str(k)
    if n.dir:
        out += ', "dir": true'
    if n.value is not None:
        out += ', "value": ' + _json_str(n.value)
    if n.expiration is not None:
        out += ', "expiration": "%s", "ttl": %d' % (
            format_expiration(n.expiration), n.ttl)
    return out + ', "modifiedIndex": %d, "createdIndex": %d}' % (
        n.modified_index, n.created_index)


def _event_json(e: Event, bare: bool = False) -> bytes:
    """json.dumps(trim_prefix(e.to_dict())) and a newline (`bare`: no
    node, no prevNode); only a listing goes through the dicts."""
    node, prev = (None, None) if bare else (e.node, e.prev_node)
    if (node is None or node.nodes is None) and \
            (prev is None or prev.nodes is None):
        body = '{"action": ' + _json_str(e.action)
        if node is not None:
            body += ', "node": ' + _leaf_json(node)
        if prev is not None:
            body += ', "prevNode": ' + _leaf_json(prev)
        return body.encode() + b"}\n"
    return json.dumps(trim_prefix(e.to_dict())).encode() + b"\n"


class ClientAPI:
    """Routes for one EtcdServer's client listener. `security` is wired in by
    the security module when auth is enabled (hasKeyPrefixAccess gate)."""

    def __init__(self, server, security=None) -> None:
        self.server = server
        self.security = security

    # -- routing --------------------------------------------------------------

    def install(self, router: Router) -> None:
        router.add(KEYS_PREFIX, self.handle_keys)
        router.add(MEMBERS_PREFIX, self.handle_members)
        router.add(MACHINES_PREFIX, self.handle_machines, exact=True)
        router.add(STATS_PREFIX + "/self", self.handle_stats_self, exact=True)
        router.add(STATS_PREFIX + "/leader", self.handle_stats_leader,
                   exact=True)
        router.add(STATS_PREFIX + "/store", self.handle_stats_store,
                   exact=True)
        router.add("/version", self.handle_version, exact=True)
        router.add("/health", self.handle_health, exact=True)
        router.add("/metrics", self.handle_metrics, exact=True)
        router.add("/debug/vars", self.handle_debug_vars, exact=True)

    # -- shared helpers -------------------------------------------------------

    def _headers(self, etcd_index: Optional[int] = None) -> bytes:
        """A reply's X-Etcd-* / X-Raft-* header lines, made for the wire."""
        s = self.server
        if etcd_index is None:
            return b"X-Etcd-Cluster-ID: %x\r\n" % s.cluster.cluster_id
        return (b"X-Etcd-Cluster-ID: %x\r\nX-Etcd-Index: %d\r\n"
                b"X-Raft-Index: %d\r\nX-Raft-Term: %d\r\n"
                % (s.cluster.cluster_id, etcd_index, s.commit_index, s.term))

    def _error(self, ctx: Ctx, err: errors.EtcdError) -> None:
        if not err.index:
            err.index = self.server.store.current_index
        # The internal store prefix must not leak into user-visible causes
        # (reference trimErrorPrefix, client.go:142,622-626).
        if err.cause.startswith(STORE_KEYS_PREFIX):
            err.cause = err.cause[len(STORE_KEYS_PREFIX):]
        ctx.send(err.status_code, err.to_json().encode() + b"\n",
                 "application/json", self._headers(err.index))

    # -- /v2/keys -------------------------------------------------------------

    def handle_keys(self, ctx: Ctx, suffix: str) -> None:
        if ctx.method not in _KEYS_METHODS:
            ctx.send(405, b"Method Not Allowed",
                     headers={"Allow": "GET, PUT, POST, DELETE, HEAD"})
            return
        try:
            r, no_value = self._parse_key_request(ctx, suffix)
            if self.security is not None:
                self.security.check_key_access(ctx, r)
            result = self.server.do(r)
        except errors.EtcdError as e:
            self._error(ctx, e)
            return
        if isinstance(result, Event):
            self._write_key_event(ctx, result, no_value=no_value)
        else:  # a Watcher from store.watch
            self._handle_watch(ctx, r, result)

    def begin_keys(self, ctx: Ctx, suffix: str):
        """handle_keys cut in two at `self.server.do(r)`, for the front's
        event loop (web.Router.add's `begin`; must not block). Where the
        server offers a non-blocking submit (the engine's _TenantServer;
        the single-cluster EtcdServer has only a blocking do), a write or
        a `?quorum=true` read is parsed here and comes back as a LoopOp:
        the loop submits it and calls the op's `finish` with the result
        the ack path released, which writes the reply handle_keys would
        have written. A local read (no quorum, no wait) waits for nothing:
        it is served from the store here and now, REPLIED. None sends the
        request down the thread path to handle_keys as written: watches
        (a watch holds its connection), a tenant with auth on (checking
        a password hashes it: milliseconds), any other method."""
        submitter = getattr(self.server, "submitter", None)
        if submitter is None or ctx.method not in _KEYS_METHODS:
            return None
        if ctx.method in ("GET", "HEAD") and ctx.has("wait"):
            return None
        try:
            if self.security is not None and self.security.enabled():
                return None
            # (the id is given here: the submitter stages this object)
            r, no_value = self._parse_key_request(ctx, suffix,
                                                  self.server.new_id)
            if r.method == METHOD_GET and not r.quorum:
                self._write_key_event(ctx, self.server.do(r),
                                      no_value=no_value)
                return REPLIED
        except errors.EtcdError as e:
            self._error(ctx, e)
            return REPLIED

        def finish(result) -> None:
            if isinstance(result, errors.EtcdError):
                self._error(ctx, result)
            else:
                self._write_key_event(ctx, result, no_value=no_value)

        return LoopOp(submitter, self.server.submit_item(r), finish,
                      kind="qread" if r.method == METHOD_GET else "write",
                      timeout=self.server.request_timeout)

    def _parse_key_request(self, ctx: Ctx, suffix: str, new_id=None):
        """reference parseKeyRequest client.go:390-534: (the Request,
        noValueOnSuccess). `new_id` names a write or a quorum read that
        will be submitted; without it the id stays 0 for do() to give."""
        method = "GET" if ctx.method == "HEAD" else ctx.method
        if method not in (METHOD_GET, METHOD_PUT, METHOD_POST, METHOD_DELETE):
            raise errors.EtcdError(errors.ECODE_INVALID_FORM,
                                   cause=f"bad method {method}")
        p = posixpath.normpath(STORE_KEYS_PREFIX + "/" + suffix.lstrip("/"))
        if p != STORE_KEYS_PREFIX and \
                not p.startswith(STORE_KEYS_PREFIX + "/"):
            # ".." segments must not escape the keys namespace into the
            # internal /0 cluster-metadata tree.
            raise errors.EtcdError(errors.ECODE_INVALID_FORM,
                                   cause=f"invalid key path {suffix!r}")
        params = ctx.params()
        get = params.get
        (recursive, sorted_, quorum, wait, stream, dir_, refresh,
         no_value) = ([_bool_of(get, f) for f in _BOOL_FIELDS]
                      if not _BOOL_SET.isdisjoint(params) else _NO_FLAGS)

        prev_value = get("prevValue")
        if prev_value is not None:
            prev_value = prev_value[0]
            if prev_value == "":
                raise errors.EtcdError(errors.ECODE_PREV_VALUE_REQUIRED,
                                       cause='"prevValue" cannot be empty')
        prev_index = _count_of(get, "prevIndex", errors.ECODE_INDEX_NAN)

        prev_exist = get("prevExist")
        if prev_exist is not None:
            if prev_exist[0] not in ("true", "false"):
                raise errors.EtcdError(errors.ECODE_INVALID_FIELD,
                                       cause='invalid value for "prevExist"')
            prev_exist = prev_exist[0] == "true"

        since = _count_of(get, "waitIndex", errors.ECODE_INDEX_NAN)
        ttl = _count_of(get, "ttl", errors.ECODE_TTL_NAN)
        expiration = self.server.clock() + ttl if ttl > 0 else None
        value = get("value")

        if wait and quorum:
            raise errors.EtcdError(
                errors.ECODE_INVALID_FIELD,
                cause='"quorum" is incompatible with "wait"')
        if stream and not wait:
            raise errors.EtcdError(
                errors.ECODE_INVALID_FIELD,
                cause='"stream" requires "wait"')
        if refresh:
            if value is not None:
                raise errors.EtcdError(
                    errors.ECODE_REFRESH_VALUE,
                    cause="A value was provided on a refresh")
            if expiration is None:
                raise errors.EtcdError(
                    errors.ECODE_REFRESH_TTL_REQUIRED,
                    cause="No TTL value set")

        rid = 0
        if new_id is not None and (method != METHOD_GET or quorum):
            rid = new_id()
        return Request(
            id=rid, method=method, path=p, val=value[0] if value else "",
            dir=dir_, prev_value=prev_value or "", prev_index=prev_index,
            prev_exist=prev_exist, expiration=expiration,
            wait=wait, since=since, recursive=recursive,
            sorted=sorted_, quorum=quorum, stream=stream,
            refresh=refresh), no_value

    def _write_key_event(self, ctx: Ctx, e: Event,
                         no_value: bool = False) -> None:
        """reference writeKeyEvent client.go:536-551."""
        # IsCreated (reference store/event.go:48-58): an explicit create, or
        # a set that made a new node (no prevNode), answers 201.
        created = (e.action in _CREATED_ACTIONS or
                   (e.action == "set" and e.prev_node is None))
        status = 201 if created else 200
        # noValueOnSuccess strips the payload echo (reference
        # writeKeyEvent noValueOnSuccess handling).
        bare = no_value and e.action in ("set", "update", "create",
                                         "compareAndSwap", "compareAndDelete")
        ctx.send(status, _event_json(e, bare), "application/json",
                 self._headers(e.etcd_index))

    def _handle_watch(self, ctx: Ctx, r: Request, watcher) -> None:
        """Long-poll or chunked stream (reference handleKeyWatch
        client.go:553-597). The watcher is released on client disconnect."""
        headers = self._headers(getattr(watcher, "start_index",
                                        self.server.store.current_index))
        try:
            if not r.stream:
                while True:
                    e = watcher.next_event(timeout=0.5)
                    if e is not None:
                        ctx.send(200, _event_json(e), "application/json",
                                 headers)
                        return
                    if watcher.removed or ctx.client_gone() or \
                            self.server.stopped:
                        ctx.send(200, b"", "application/json", headers)
                        return
            else:
                ctx.begin_stream(200, "application/json", headers)
                while True:
                    e = watcher.next_event(timeout=0.5)
                    if e is not None:
                        if not ctx.write_chunk(_event_json(e)):
                            return
                    elif watcher.removed or ctx.client_gone() or \
                            self.server.stopped:
                        ctx.end_stream()
                        return
        finally:
            watcher.remove()

    # -- /v2/members ----------------------------------------------------------

    def handle_members(self, ctx: Ctx, suffix: str) -> None:
        s = self.server
        h = self._headers()
        # Mutations need root once security is on (reference client.go:184-187
        # hasWriteRootAccess).
        if (self.security is not None and
                not self.security.check_members_access(ctx)):
            ctx.send_json(401, {"message": "Insufficient credentials"}, h)
            return
        try:
            if ctx.method == "GET" and suffix in ("", "/"):
                body = {"members": [self._member_dict(m)
                                    for m in s.cluster.members()]}
                ctx.send_json(200, body, h)
            elif ctx.method == "POST" and suffix in ("", "/"):
                req = self._parse_member_body(ctx)
                m = Member.new(req.get("name", ""), req["peerURLs"],
                               s.cluster.token)
                s.add_member(m)
                ctx.send_json(201, self._member_dict(m), h)
            elif ctx.method == "DELETE" and suffix.startswith("/"):
                mid = self._parse_member_id(suffix)
                if s.cluster.is_id_removed(mid):
                    ctx.send(410, b"Member permanently removed\n",
                             headers=h)
                    return
                s.remove_member(mid)
                ctx.send(204, headers=h)
            elif ctx.method == "PUT" and suffix.startswith("/"):
                mid = self._parse_member_id(suffix)
                req = self._parse_member_body(ctx)
                old = s.cluster.member(mid)
                m = Member(id=mid, name=old.name if old else "",
                           peer_urls=tuple(req["peerURLs"]),
                           client_urls=old.client_urls if old else ())
                s.update_member(m)
                ctx.send(204, headers=h)
            else:
                ctx.send(405, b"Method Not Allowed",
                         headers={"Allow": "GET, POST, DELETE, PUT"})
        except errors.EtcdError as e:
            code = 500 if e.code in (errors.ECODE_RAFT_INTERNAL,
                                     errors.ECODE_LEADER_ELECT) else 409
            if e.code == errors.ECODE_KEY_NOT_FOUND:
                code = 404
            ctx.send_json(code, {"message": e.cause or e.message}, h)
        except (KeyError, ValueError, json.JSONDecodeError) as e:
            ctx.send_json(400, {"message": f"bad member request: {e}"}, h)

    @staticmethod
    def _member_dict(m: Member) -> dict:
        return {"id": f"{m.id:x}", "name": m.name,
                "peerURLs": list(m.peer_urls),
                "clientURLs": list(m.client_urls)}

    @staticmethod
    def _parse_member_body(ctx: Ctx) -> dict:
        d = json.loads(ctx.body.decode() or "{}")
        urls = d.get("peerURLs")
        if not urls or not isinstance(urls, list):
            raise ValueError("peerURLs required")
        for u in urls:
            if not (u.startswith("http://") or u.startswith("https://")):
                raise ValueError(f"invalid peer URL {u!r}")
        return d

    @staticmethod
    def _parse_member_id(suffix: str) -> int:
        return int(suffix.strip("/"), 16)

    # -- misc surfaces --------------------------------------------------------

    def handle_machines(self, ctx: Ctx, suffix: str) -> None:
        urls = self.server.cluster.client_urls()
        ctx.send(200, ", ".join(urls).encode(), "text/plain",
                 self._headers())

    def handle_stats_self(self, ctx: Ctx, suffix: str) -> None:
        ctx.send_json(200, self.server.stats.to_dict(), self._headers())

    def handle_stats_leader(self, ctx: Ctx, suffix: str) -> None:
        s = self.server
        if not s.is_leader():
            e = errors.EtcdError(errors.ECODE_RAFT_INTERNAL,
                                 cause="not current leader")
            ctx.send(403, e.to_json().encode() + b"\n", "application/json",
                     self._headers())
            return
        ctx.send_json(200, s.lstats.to_dict(), self._headers())

    def handle_stats_store(self, ctx: Ctx, suffix: str) -> None:
        ctx.send_json(200, self.server.store.stats.to_dict(),
                      self._headers())

    def handle_version(self, ctx: Ctx, suffix: str) -> None:
        ctx.send_json(200, {"etcdserver": ver.VERSION,
                            "etcdcluster": self.server.cluster_version()})

    def handle_health(self, ctx: Ctx, suffix: str) -> None:
        healthy = (self.server.leader_id != 0 and not self.server.stopped
                   and not getattr(self.server, "_fatal", False))
        ctx.send_json(200 if healthy else 503,
                      {"health": "true" if healthy else "false"})

    def handle_metrics(self, ctx: Ctx, suffix: str) -> None:
        """Prometheus text exposition (reference client.go:53,102 wiring
        prometheus.Handler(); metric set per */metrics.go)."""
        used, _ = metrics.fd_usage()
        metrics.file_descriptors_used.set(used)
        ctx.send(200, metrics.REGISTRY.expose().encode(),
                 "text/plain; version=0.0.4")

    def handle_debug_vars(self, ctx: Ctx, suffix: str) -> None:
        """expvar-style JSON (reference client.go:317-331 serveVars:
        file_descriptor_limit + live raft.status)."""
        _, limit = metrics.fd_usage()
        st = self.server.raft_status()
        ctx.send_json(200, {"file_descriptor_limit": limit,
                            "raft.status": st})
