"""The propose→apply rendezvous registry (reference pkg/wait/wait.go:21-58).

A proposer registers a request id and blocks on the returned queue; the apply
loop triggers the id with the result once the entry commits and applies.
Thread-safe: proposers are HTTP handler threads, the trigger side is the
single run-loop thread.

A proposer that must not block (the HTTP front's event loop,
etcdhttp/web.py) registers the id with a Sink instead: trigger() then
appends (id, result) to the sink and signals its owner once, however many
results arrive before the owner drains them.
"""
from __future__ import annotations

import queue
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple


class Sink:
    """Where results go when nobody parks a thread for them: a deque the
    owner drains, and `wake`, called when a result arrives and the owner
    has not been signalled since its last drain (one write to the event
    loop's wake pipe per batch of acks, not one per ack).

    deliver() appends before it tests the flag and drain() clears the flag
    before it pops, so a result appended while the owner drains is either
    popped by that drain or signals the next one; a spare signal costs an
    empty drain, a lost one never happens. Appends and pops are GIL-atomic
    deque operations: any thread may deliver, one thread drains."""

    __slots__ = ("_items", "_wake", "_signalled")

    def __init__(self, wake: Callable[[], None]) -> None:
        self._items: deque = deque()
        self._wake = wake
        self._signalled = False

    def deliver(self, wid: int, value: Any) -> None:
        self.deliver_many(((wid, value),))

    def deliver_many(self, items: Iterable[Tuple[int, Any]]) -> None:
        self._items.extend(items)
        if not self._signalled:
            self._signalled = True
            self._wake()

    def drain(self) -> List[Tuple[int, Any]]:
        self._signalled = False
        items, out = self._items, []
        while items:
            out.append(items.popleft())
        return out


class Wait:
    """Lock-free on the hot path: CPython dict setdefault/pop are
    GIL-atomic, and trigger() sits on the apply loop's per-request path
    (profiled), so the registry rides the GIL instead of a Lock."""

    def __init__(self) -> None:
        self._waiters: Dict[int, Any] = {}

    def register(self, wid: int,
                 sink: Optional[Sink] = None) -> "queue.Queue[Any]":
        """A one-slot queue the caller blocks on or, with `sink`, nothing
        to block on: the result is delivered to the sink."""
        w = sink if sink is not None else queue.Queue(maxsize=1)
        if self._waiters.setdefault(wid, w) is not w:
            raise ValueError(f"duplicate wait id {wid:x}")
        return w

    def trigger(self, wid: int, value: Any) -> bool:
        w = self._waiters.pop(wid, None)
        if w is None:
            return False
        if type(w) is Sink:
            w.deliver(wid, value)
        else:
            w.put(value)
        return True

    def trigger_many(self, items: Iterable[Tuple[int, Any]]) -> None:
        """trigger() for every (id, result) of one ack batch; the results
        bound for one sink reach it together, under one signal."""
        pop = self._waiters.pop
        sinks: Dict[int, Tuple[Sink, list]] = {}
        for wid, value in items:
            w = pop(wid, None)
            if w is None:
                continue
            if type(w) is Sink:
                hit = sinks.get(id(w))
                if hit is None:
                    sinks[id(w)] = (w, [(wid, value)])
                else:
                    hit[1].append((wid, value))
            else:
                w.put(value)
        for sink, batch in sinks.values():
            sink.deliver_many(batch)

    def is_registered(self, wid: int) -> bool:
        return wid in self._waiters

    def cancel(self, wid: int) -> None:
        self._waiters.pop(wid, None)
