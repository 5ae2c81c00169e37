"""Compartmentalized applier pool (engine.EngineConfig.applier_shards).

Pins the contract the pool restructure must keep: K=1 and K=4 produce
identical store state, event history and watch replays on a seeded mixed
workload (per-group FIFO + cross-shard watch/history semantics); a dead
applier worker surfaces as an engine error at the next seam, never a
hang; apply_queue_rounds bounds the DEEPEST shard's backlog; and the
ack path hands waiters raw C descriptors (LazyWriteEvent) without
materializing Event/NodeExtern objects at apply time.
"""
import threading
import time

import pytest

from etcd_tpu import errors
from etcd_tpu.server.engine import EngineConfig, MultiEngine
from etcd_tpu.server.request import Request
from etcd_tpu.store.event import LazyWriteEvent

G, P = 8, 3  # one kernel shape for the module => one XLA compile


def make_engine(tmp, shards, **kw):
    kw.setdefault("groups", G)
    kw.setdefault("peers", P)
    kw.setdefault("window", 16)
    kw.setdefault("max_ents", 4)
    kw.setdefault("heartbeat_tick", 3)
    kw.setdefault("request_timeout", 30.0)
    kw.setdefault("fsync", False)
    kw.setdefault("sync_interval", 0.0)  # no background SYNC entries
    kw.setdefault("checkpoint_rounds", 1 << 30)
    return MultiEngine(EngineConfig(data_dir=str(tmp),
                                    applier_shards=shards, **kw))


def inject(eng, g, r):
    """Queue a request WITHOUT registering a waiter (the waiterless
    batched fast path)."""
    if r.id == 0:
        r = Request(**{**r.__dict__, "id": eng.reqid.next()})
    with eng._lock:
        eng._pending[g].append((r.id, b"\x00" + r.encode(), r))
        eng._dirty.add(g)
    return r.id


def ev_sig(e):
    def nd(x):
        if x is None:
            return None
        return (x.key, x.value, x.dir, x.created_index, x.modified_index,
                x.expiration)  # ttl excluded: it is scan-time-dependent
    return (e.action, nd(e.node), nd(e.prev_node), e.etcd_index)


def history_replay(st):
    """Every event the tenant's history ring retains, oldest first."""
    hist = st.watcher_hub.event_history
    out = []
    i = hist.start_index
    while i <= hist.last_index:
        e = hist.scan("/", True, i)
        if e is None:
            break
        out.append(ev_sig(e))
        i = e.etcd_index + 1
    return out


def watch_replay(st, since):
    """What a watcher joining at `since` sees, via the hub's replay."""
    w = st.watch("/", recursive=True, stream=True, since_index=since)
    out = []
    while True:
        e = w.next_event(timeout=0.05)
        if e is None:
            return out
        out.append(ev_sig(e))


def run_workload(tmp, shards):
    """Seeded mixed workload: 20 waiterless plain PUTs per group (the
    batched fast path), then a fixed per-group sequence of waiter-held
    requests covering every scalar apply shape — overwrite chains, CAS,
    in-order POST, conditional create, delete, TTL put + refresh, and a
    failing CAS — issued sequentially per group (per-group FIFO is the
    invariant under test)."""
    eng = make_engine(tmp, shards)
    eng.start()
    try:
        assert eng.wait_leaders(60), "no leaders"
        for g in range(G):
            for i in range(20):
                inject(eng, g, Request(method="PUT",
                                       path=f"/bulk/{i % 7}",
                                       val=f"b{g}_{i}"))
        results = {}

        def client(g):
            out = []

            def do(r):
                try:
                    return ev_sig(eng.do(g, r, timeout=30))
                except errors.EtcdError as e:
                    return ("err", e.code, e.cause)

            for i in range(4):
                out.append(do(Request(method="PUT", path=f"/k{i % 2}",
                                      val=f"v{g}_{i}")))
            out.append(do(Request(method="PUT", path="/k0",
                                  val="swapped", prev_value=f"v{g}_2")))
            out.append(do(Request(method="POST", path="/q", val="job")))
            out.append(do(Request(method="PUT", path="/new", val="n",
                                  prev_exist=False)))
            out.append(do(Request(method="DELETE", path="/k1")))
            out.append(do(Request(method="PUT", path="/ttl", val="t",
                                  expiration=4e9)))
            out.append(do(Request(method="PUT", path="/ttl",
                                  refresh=True, expiration=5e9)))
            out.append(do(Request(method="PUT", path="/k0", val="nope",
                                  prev_value="wrong")))   # fails: 101
            results[g] = out

        ths = [threading.Thread(target=client, args=(g,))
               for g in range(G)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert all(not t.is_alive() for t in ths), "client writes hung"
        assert len(results) == G

        # Settle everything before reading stores.
        deadline = time.time() + 30
        while time.time() < deadline:
            with eng._lock:
                if not any(eng._pending[g] for g in range(G)):
                    break
            time.sleep(0.01)
        eng._drain_applies()

        shard_acks = [sh.acct.acked for sh in eng._appliers]
        state = {}
        for g in range(G):
            st = eng.store(g)
            dump = st.get("/", recursive=True, want_sorted=True)
            state[g] = {"dump": ev_sig(dump),
                        "index": st.current_index,
                        "history": history_replay(st),
                        "watch": watch_replay(st, 1)}
        return results, state, shard_acks
    finally:
        eng.stop()


def test_differential_k1_vs_k4(tmp_path):
    """The pool restructure's pin: K=4 must be observably identical to
    the single applier — waiter results, final store state, event
    history, and watch replays, per tenant."""
    r1, s1, acks1 = run_workload(tmp_path / "k1", shards=1)
    r4, s4, acks4 = run_workload(tmp_path / "k4", shards=4)
    assert len(acks1) == 1 and len(acks4) == 4
    assert r1 == r4, "waiter-visible results diverged"
    for g in range(G):
        assert s1[g]["index"] == s4[g]["index"], g
        assert s1[g]["dump"] == s4[g]["dump"], g
        assert s1[g]["history"] == s4[g]["history"], g
        assert s1[g]["watch"] == s4[g]["watch"], g
    # Every compartment actually applied its range (nothing fell back
    # to the synchronous path behind the pool's back).
    assert all(a > 0 for a in acks4), acks4
    assert sum(acks1) == sum(acks4)


def _poison_store(eng, g, exc_factory):
    st = eng.store(g)
    def boom(*a, **kw):
        raise exc_factory()
    for name in ("set_applied_many", "set_applied", "set_applied_lazy",
                 "set"):
        if hasattr(st, name):
            setattr(st, name, boom)


def test_worker_crash_surfaces_engine_error(tmp_path):
    """A dying applier worker must fail the engine at the next seam
    (enqueue/drain re-raise), not hang the round loop or silently skip
    its shard's entries."""
    eng = make_engine(tmp_path / "crash", shards=4)
    try:
        for _ in range(400):
            eng.run_round()
            if eng.wait_leaders(0.0):
                break
        assert eng.wait_leaders(5.0)
        _poison_store(eng, 0, lambda: RuntimeError("shard-0 store died"))
        inject(eng, 0, Request(method="PUT", path="/x", val="v"))
        with pytest.raises(RuntimeError, match="shard-0 store died"):
            for _ in range(200):
                eng.run_round()
            eng._drain_applies()
        # The failed shard halted for good: its worker exits, is NOT
        # respawned (that would re-apply the failed view from the top),
        # and every later seam re-raises the same terminal error.
        broken = [sh for sh in eng._appliers if sh.exc is not None]
        assert len(broken) == 1, broken
        broken[0].thread.join(timeout=5)
        assert not broken[0].thread.is_alive(), "halted worker lived on"
        eng._ensure_appliers()
        assert not broken[0].thread.is_alive(), "halted worker respawned"
        with pytest.raises(RuntimeError, match="shard-0 store died"):
            eng._drain_applies()
        # stop() swallows the (already-surfaced) applier error into
        # .failed instead of raising out of shutdown.
        eng.stop()
        assert isinstance(eng.failed, RuntimeError)
    finally:
        eng.stop()


def test_backpressure_bounds_deepest_shard(tmp_path):
    """apply_queue_rounds bounds the DEEPEST shard's backlog: a slow
    shard's queue tops out at the cap (observed from inside its own
    apply calls) while the round loop keeps serving the fast shard."""
    eng = make_engine(tmp_path / "bp", shards=2, apply_queue_rounds=1)
    try:
        for _ in range(400):
            eng.run_round()
            if eng.wait_leaders(0.0):
                break
        assert eng.wait_leaders(5.0)
        slow = eng._appliers[0]
        seen = []
        st0 = eng.store(0)
        orig = st0.set_applied_many

        def slow_many(paths, values, need=None):
            seen.append(len(slow.q))
            time.sleep(0.02)
            return orig(paths, values, need)

        st0.set_applied_many = slow_many
        for r in range(25):
            inject(eng, 0, Request(method="PUT", path="/s", val=f"a{r}"))
            inject(eng, G - 1, Request(method="PUT", path="/f",
                                       val=f"b{r}"))
            eng.run_round()
        eng._drain_applies()
        cap = eng.cfg.apply_queue_rounds
        assert seen, "slow shard never applied"
        assert max(seen) <= cap, seen
        assert max(seen) == cap, "backpressure never engaged"
        # both shards fully applied despite the asymmetry
        assert eng.store(0).get("/s").node.value == "a24"
        assert eng.store(G - 1).get("/f").node.value == "b24"
    finally:
        eng.stop()


def test_ack_path_is_lazy_for_native_store(tmp_path):
    """Acceptance pin: the apply-time ack path materializes NO
    Event/NodeExtern for plain-file PUTs — waiterless ones produce
    nothing, waiter-held ones a LazyWriteEvent of raw C descriptors that
    the consuming thread resolves. Event construction inside
    native_store during the apply window is a hard failure."""
    pytest.importorskip("etcd_tpu.native.storecore")
    from etcd_tpu.store import native_store

    eng = make_engine(tmp_path / "lazy", shards=2)
    try:
        for _ in range(400):
            eng.run_round()
            if eng.wait_leaders(0.0):
                break
        assert eng.wait_leaders(5.0)

        captured = []

        class Cap:   # waiter: records exactly what the applier delivers
            def put(self, v):
                captured.append(v)

        def boom(*a, **kw):
            raise AssertionError("Event materialized on the apply path")

        rid = eng.reqid.next()
        eng.wait._waiters[rid] = Cap()
        real_event, real_extern = native_store.Event, native_store._extern
        native_store.Event = native_store._extern = boom
        try:
            # waiterless (batched fast path) + waiter-held in one entry
            inject(eng, 1, Request(method="PUT", path="/w", val="quiet"))
            inject(eng, 1, Request(method="PUT", path="/w", val="loud",
                                   id=rid))
            for _ in range(200):
                eng.run_round()
                if captured:
                    break
            eng._drain_applies()
        finally:
            native_store.Event, native_store._extern = (real_event,
                                                        real_extern)
        assert captured, "waiter never triggered"
        lw = captured[0]
        assert isinstance(lw, LazyWriteEvent), type(lw)
        e = lw.resolve()   # HTTP-thread materialization (engine.do)
        assert e.action == "set"
        assert e.node.key == "/w" and e.node.value == "loud"
        assert e.prev_node.value == "quiet"
        # do() resolves transparently for real clients
        from tests.test_engine import put_async, settle
        t, out = put_async(eng, 2, "/z", "zz")
        res = settle(eng, t, out)
        assert res.node.key == "/z" and res.node.value == "zz"
    finally:
        eng.stop()
