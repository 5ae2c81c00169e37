"""The replicated command (reference etcdserverpb.Request).

Every client mutation becomes one of these, is serialized into a raft entry,
and is applied deterministically on every member (reference
etcdserver/server.go:766-820 applyRequest). Encoding is canonical JSON
(sorted keys, no whitespace) — deterministic and debuggable; the consensus
hot path never touches these bytes (they ride the host log store).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Optional

METHOD_GET = "GET"
METHOD_PUT = "PUT"
METHOD_POST = "POST"
METHOD_DELETE = "DELETE"
METHOD_QGET = "QGET"
METHOD_SYNC = "SYNC"
METHOD_V3 = "V3"        # v3 op (the `v3` field) through the same log


@dataclass(frozen=True)
class Request:
    id: int = 0
    method: str = METHOD_GET
    path: str = ""
    val: str = ""
    dir: bool = False
    prev_value: str = ""
    prev_index: int = 0
    prev_exist: Optional[bool] = None   # tri-state (reference *bool)
    expiration: Optional[float] = None  # absolute unix seconds; None = keep forever
    wait: bool = False
    since: int = 0
    recursive: bool = False
    sorted: bool = False
    quorum: bool = False
    stream: bool = False
    time: float = 0.0                   # SYNC: the leader's cutoff timestamp
    refresh: bool = False               # TTL refresh without value change
    v3: Optional[dict] = None           # METHOD_V3 payload (server/v3.py)

    def __init__(self, id=0, method=METHOD_GET, path="", val="", dir=False,
                 prev_value="", prev_index=0, prev_exist=None,
                 expiration=None, wait=False, since=0, recursive=False,
                 sorted=False, quorum=False, stream=False, time=0.0,
                 refresh=False, v3=None) -> None:
        # The fields above, in one dict update: the __init__ a frozen
        # dataclass writes itself goes through object.__setattr__ once a
        # field (2.4 against 0.9 us), and one is built for every request.
        self.__dict__.update(
            id=id, method=method, path=path, val=val, dir=dir,
            prev_value=prev_value, prev_index=prev_index,
            prev_exist=prev_exist, expiration=expiration, wait=wait,
            since=since, recursive=recursive, sorted=sorted, quorum=quorum,
            stream=stream, time=time, refresh=refresh, v3=v3)

    def encode(self) -> bytes:
        # self.__dict__ instead of dataclasses.asdict: asdict deep-copies
        # recursively (19 internal calls per request) and was the single
        # hottest host function in the serving profile; the fields here are
        # all scalars except `v3` (a dict the apply path treats as opaque
        # JSON), so a shallow copy is equivalent.
        d = {k: v for k, v in self.__dict__.items()
             if v not in (None, "", 0, 0.0, False)}
        d["id"] = self.id
        d["method"] = self.method
        if self.prev_exist is not None:
            d["prev_exist"] = self.prev_exist
        return json.dumps(d, sort_keys=True, separators=(",", ":")).encode()

    @staticmethod
    def decode(data: bytes) -> "Request":
        d = json.loads(data.decode())
        return Request(**d)
