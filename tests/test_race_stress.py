"""Concurrency stress under an amplified thread scheduler — the Python
analogue of the reference's `go test --race` tier (SURVEY §5: race
detection; reference test:46-48 runs every package under the race
detector).

Python has no data-race sanitizer, so this does the next-best thing:
`sys.setswitchinterval(1e-5)` forces ~100x more preemption points, then
hammers every structure shared between the engine round thread and client
threads (Wait rendezvous, _pending/_dirty proposal queues, lazy tenant
store creation, watch hub) and asserts the externally visible invariants:

  * every ACKED write is readable afterwards (no lost updates),
  * modifiedIndex is unique per tenant (no double-apply),
  * watch streams see every event exactly once, in index order,
  * the Wait registry never leaks a waiter or delivers twice.

The single-writer invariant these tests guard is the design's whole
concurrency story (divergences.md "Synchronous Ready/Advance"): only the
engine thread touches consensus state; client threads only enqueue + block.
"""
import queue
import sys
import threading
import time

import pytest

from etcd_tpu import errors
from etcd_tpu.server.engine import EngineConfig, MultiEngine
from etcd_tpu.server.request import Request
from etcd_tpu.utils.wait import Wait


@pytest.fixture(autouse=True)
def fast_switches():
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    yield
    sys.setswitchinterval(old)


def test_wait_registry_storm():
    """register/trigger/cancel from many threads: a value is delivered to
    exactly one consumer exactly once, and the registry drains to empty."""
    w = Wait()
    N_THREADS, N_IDS = 8, 400
    delivered = [0] * (N_THREADS * N_IDS)
    errors_seen = []

    def producer(base):
        for i in range(N_IDS):
            wid = base * N_IDS + i
            q = w.register(wid)
            t = threading.Thread(target=w.trigger, args=(wid, wid))
            t.start()
            try:
                got = q.get(timeout=5.0)
                if got != wid:
                    errors_seen.append((wid, got))
                delivered[wid] += 1
            except queue.Empty:
                errors_seen.append((wid, "empty"))
            t.join()

    threads = [threading.Thread(target=producer, args=(b,))
               for b in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors_seen, errors_seen[:5]
    assert all(d == 1 for d in delivered)
    assert not w._waiters, "registry leaked waiters"


def test_engine_concurrent_clients_no_lost_updates(tmp_path):
    """16 writer threads × unique keys across 4 tenants against the live
    engine thread; concurrently, reader threads poll and a watcher consumes
    the event stream. Every acked write must be readable, and every applied
    event must carry a unique modifiedIndex per tenant."""
    eng = MultiEngine(EngineConfig(
        groups=4, peers=5, data_dir=str(tmp_path / "race"), window=16,
        max_ents=4, heartbeat_tick=3, request_timeout=60.0, fsync=False))
    eng.start()
    acked = {}           # key -> (group, modifiedIndex)
    failures = []
    lock = threading.Lock()
    try:
        assert eng.wait_leaders(60.0)

        # Watcher on tenant 0: stream from index 1, dedupe check below.
        stream = eng.do(0, Request(method="GET", path="/", wait=True,
                                   recursive=True, stream=True, since=1))

        stop_readers = threading.Event()

        def reader(g):
            while not stop_readers.is_set():
                try:
                    eng.do(g, Request(method="GET", path="/",
                                      recursive=True))
                except errors.EtcdError:
                    pass
                time.sleep(0.001)

        readers = [threading.Thread(target=reader, args=(g,), daemon=True)
                   for g in range(4)]
        for r in readers:
            r.start()

        def writer(w):
            for i in range(12):
                g = (w + i) % 4
                key = f"/w{w}/k{i}"
                try:
                    ev = eng.do(g, Request(method="PUT", path=key,
                                           val=f"{w}.{i}"), timeout=60.0)
                except errors.EtcdError as e:
                    with lock:
                        failures.append((key, str(e)))
                    continue
                with lock:
                    acked[key] = (g, ev.node.modified_index)

        writers = [threading.Thread(target=writer, args=(w,))
                   for w in range(16)]
        for t in writers:
            t.start()
        for t in writers:
            t.join(timeout=120.0)
        assert not any(t.is_alive() for t in writers), "writer hung"
        stop_readers.set()

        # The invariants below are vacuous if most writes never acked —
        # mass timeout under load would be its own engine bug.
        assert len(acked) >= 150, (len(acked), failures[:3])

        # No lost updates: every acked write readable with its value.
        for key, (g, _) in acked.items():
            w, i = key[2:].split("/k")
            ev = eng.do(g, Request(method="GET", path=key))
            assert ev.node.value == f"{w}.{i}", key

        # No double-apply: modifiedIndex unique per tenant.
        for g in range(4):
            idxs = [mi for (gg, mi) in acked.values() if gg == g]
            assert len(idxs) == len(set(idxs)), f"tenant {g} reused an index"

        # Watcher saw tenant 0's events exactly once, in order.
        seen = []
        deadline = time.time() + 10.0
        want = {k for k, (g, _) in acked.items() if g == 0}
        while time.time() < deadline and len(seen) < len(want) + 2:
            ev = stream.next_event(timeout=0.5)
            if ev is None:
                if {e.node.key for e in seen
                        if e.node.key in want} >= want:
                    break
                continue
            seen.append(ev)
        indices = [e.node.modified_index for e in seen]
        assert indices == sorted(indices), "watch events out of order"
        assert len(indices) == len(set(indices)), "watch delivered twice"
        got = {e.node.key for e in seen}
        missing = want - got
        assert not missing, f"watch missed events: {sorted(missing)[:5]}"
    finally:
        eng.stop()


def test_engine_lazy_store_creation_race(tmp_path):
    """First-touch of a tenant store races the apply thread (the
    check-then-set engine.store() guards); hammer first-touch from many
    threads while writes land in the same tenants."""
    eng = MultiEngine(EngineConfig(
        groups=8, peers=3, data_dir=str(tmp_path / "lazy"), window=16,
        max_ents=4, heartbeat_tick=3, request_timeout=60.0, fsync=False,
        initial_peers=3))
    eng.start()
    try:
        assert eng.wait_leaders(60.0)
        stores_seen = [[] for _ in range(8)]

        def toucher():
            for g in range(8):
                stores_seen_g = eng.store(g)
                stores_seen[g].append(id(stores_seen_g))

        def writer(g):
            ev = eng.do(g, Request(method="PUT", path="/lazy", val="x"),
                        timeout=60.0)
            assert ev.node.value == "x"

        ts = [threading.Thread(target=toucher) for _ in range(8)]
        ws = [threading.Thread(target=writer, args=(g,)) for g in range(8)]
        for t in ts + ws:
            t.start()
        for t in ts + ws:
            t.join(timeout=120.0)
        # One Store instance per tenant ever existed — a lost instance
        # would have discarded applied writes.
        for g in range(8):
            assert len(set(stores_seen[g])) == 1, f"tenant {g} store raced"
            assert eng.do(g, Request(method="GET", path="/lazy")
                          ).node.value == "x"
    finally:
        eng.stop()


def test_frames_plane_concurrent_clients_race(tmp_path):
    """The frames data plane's thread cast — per-host round thread,
    frames rx threads (append _rx/_meta_rx while the round thread
    drains), send loops, and client threads blocking in do() — under
    the amplified scheduler. Invariants: every acked write readable at
    the acking host with its exact value, modifiedIndex unique per
    tenant per host, no engine thread dies."""
    from etcd_tpu.server.hostengine import HostEngine, HostEngineConfig
    from etcd_tpu.tools.functional_tester import _free_ports

    G_, N_ = 4, 3
    ports = _free_ports(N_)
    engines = [HostEngine(HostEngineConfig(
        groups=G_, peers=N_,
        data_dir=str(tmp_path / f"host{r}"), host_id=r,
        frame_listen=("127.0.0.1", ports[r]),
        frame_peers={h: ("127.0.0.1", ports[h]) for h in range(N_)},
        window=8, max_ents=2, fsync=False, stagger=True,
        request_timeout=60.0, data_plane="frames"))
        for r in range(N_)]
    for e in engines:
        e.start()
    acked = {}           # (host, key) -> (g, modifiedIndex, val)
    failures = []
    lock = threading.Lock()
    try:
        deadline = time.time() + 90
        while time.time() < deadline:
            if all(any(e.leader_slot(g) >= 0 for e in engines)
                   for g in range(G_)):
                break
            time.sleep(0.05)

        def writer(w):
            h = w % N_
            e = engines[h]
            for i in range(10):
                g = (w + i) % G_
                key = f"/1/w{w}k{i}"
                try:
                    ev = e.do(g, Request(method="PUT", path=key,
                                         val=f"{w}.{i}"), timeout=60.0)
                except errors.EtcdError as exc:
                    with lock:
                        failures.append((key, str(exc)))
                    continue
                with lock:
                    acked[(h, key)] = (g, ev.node.modified_index,
                                       f"{w}.{i}")

        writers = [threading.Thread(target=writer, args=(w,))
                   for w in range(9)]
        for t in writers:
            t.start()
        for t in writers:
            t.join(timeout=180.0)
        assert not any(t.is_alive() for t in writers), "writer hung"
        for e in engines:
            assert e.failed is None, e.failed

        assert len(acked) >= 60, (len(acked), failures[:3])
        # Acked-at-host h => readable at host h's own store (the
        # durability contract each host's WAL backs).
        for (h, key), (g, _, val) in acked.items():
            node = engines[h].store(g).get(key, False, False)
            assert node.node.value == val, (h, key)
        # No double-apply anywhere.
        for h in range(N_):
            for g in range(G_):
                idxs = [mi for (hh, _), (gg, mi, _) in acked.items()
                        if hh == h and gg == g]
                assert len(idxs) == len(set(idxs)), (h, g)
    finally:
        for e in engines:
            try:
                e.stop()
            except Exception:  # noqa: BLE001
                pass


def test_view_batches_over_shared_cores_race_readers_and_each_other():
    """storecore.set_many_multi from more threads than cores, over core
    sets that overlap and come in opposite orders (the call takes every
    mutex before it mutates: a ring of waits would hang here), short
    calls that keep the interpreter beside long ones that release it,
    with readers walking the same trees meanwhile: every op lands
    exactly once (index = ops applied, a core
    at a time) and the history ring ends on the last index."""
    storecore = pytest.importorskip("etcd_tpu.native.storecore")
    import os

    cores = [storecore.Core(namespaces=("/0", "/1"), history_capacity=64)
             for _ in range(12)]
    applied = [0] * len(cores)
    tally = threading.Lock()
    stop = threading.Event()
    failures = []

    def batcher(tid, order, per_core):
        try:
            for b in range(40):
                mine = [cores[k] for k in order]
                n = per_core * len(mine)
                paths = [f"/1/t{tid}/k{(b + j) % 5}" for j in range(n)]
                done, descs, spans = storecore.set_many_multi(
                    mine, [per_core] * len(mine), paths, ["v" * 32] * n,
                    1.0, [0, n - 1])
                assert done == n and len(descs) == 2, (done, descs)
                for k, (first, last) in zip(order, spans):
                    assert last - first + 1 == per_core, (first, last)
                with tally:
                    for k in order:
                        applied[k] += per_core
        except Exception as e:  # noqa: BLE001 - reported by the test
            failures.append(e)

    def reader():
        try:
            while not stop.is_set():
                for c in cores:
                    # an index seen (read without the mutex) is in the
                    # ring by the time a locked reader gets in
                    seen = c.index
                    lo, hi, n = c.ring_bounds()
                    assert seen <= hi <= c.index and n <= 64, (seen, lo, hi)
                    c.get("/", True, False)
        except Exception as e:  # noqa: BLE001
            failures.append(e)

    fwd, rev = list(range(12)), list(range(11, -1, -1))
    workers = [threading.Thread(target=batcher, args=a) for a in (
        (0, fwd, 4), (1, rev, 4),           # 48 ops: the GIL is released
        (2, fwd[:6], 1), (3, rev[:6], 1),   # 6 ops: it is kept
        (4, fwd[3:9], 6), (5, [8, 2, 5, 0], 2))]
    readers = [threading.Thread(target=reader)
               for _ in range(max(2, (os.cpu_count() or 2)))]
    for t in readers + workers:
        t.start()
    for t in workers:
        t.join(timeout=120)
    stop.set()
    for t in readers:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in workers + readers), "hung"
    assert not failures, failures[:3]
    assert [c.index for c in cores] == applied
