"""Compartmentalized applier pool (engine.EngineConfig.applier_shards).

Pins the contract the pool restructure must keep: K=1 and K=4 produce
identical store state, event history and watch replays on a seeded mixed
workload (per-group FIFO + cross-shard watch/history semantics), and so
do the view batch (one native call over the tenants of a commit view)
and the per-request path; a dead applier worker surfaces as an engine
error at the next seam, never a hang, with the cursors standing behind
exactly what was applied; apply_queue_rounds bounds the DEEPEST shard's
backlog; nothing is acked before its view's fsync; and the ack path
hands waiters raw C descriptors (LazyWriteEvent) without materializing
Event/NodeExtern objects at apply time.
"""
import contextlib
import threading
import time

import pytest

from etcd_tpu import errors
from etcd_tpu.server import engine as engine_mod
from etcd_tpu.server import obs as obs_mod
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


@contextlib.contextmanager
def per_request_path():
    """No store passes for native, so no group joins the view batch:
    every request takes _apply_request."""
    real = engine_mod.NativeStore
    engine_mod.NativeStore = type("NoStoreIsNative", (), {})
    try:
        yield
    finally:
        engine_mod.NativeStore = real


def applied_by_path():
    return {labels["path"]: v
            for _, labels, v in obs_mod.apply_requests.samples()}


def boot(eng):
    for _ in range(400):
        eng.run_round()
        if eng.wait_leaders(0.0):
            break
    assert eng.wait_leaders(5.0)


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


WATCHED = 2     # the tenant that holds a live stream watcher throughout


def run_workload(tmp, shards):
    """Seeded mixed workload: 20 waiterless plain PUTs per group (entries
    of several requests: the view batch), then a fixed per-group sequence
    of waiter-held requests covering every apply shape — overwrite
    chains, CAS, in-order POST, conditional create, delete, TTL put +
    refresh, a failing CAS, a directory and a PUT onto it — issued
    sequentially per group (per-group FIFO is the invariant under test).
    Tenant WATCHED holds a live stream watcher from the start, so all of
    its writes take the per-request path."""
    eng = make_engine(tmp, shards)
    eng.start()
    try:
        assert eng.wait_leaders(60), "no leaders"
        live = eng.store(WATCHED).watch("/", recursive=True, stream=True,
                                        since_index=0)
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
            out.append(do(Request(method="PUT", path="/dir", dir=True)))
            out.append(do(Request(method="PUT", path="/dir/x", val="in")))
            out.append(do(Request(method="PUT", path="/dir",
                                  val="onto")))           # fails: 102
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
        seen = []
        while (e := live.next_event(timeout=0.05)) is not None:
            seen.append(ev_sig(e))
        state[WATCHED]["live"] = seen
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
    assert s1[WATCHED]["live"] == s4[WATCHED]["live"]
    assert s1[WATCHED]["live"] == s1[WATCHED]["history"][-len(
        s1[WATCHED]["live"]):], "the live watcher missed an event"
    # Every compartment actually applied its range (nothing fell back
    # to the synchronous path behind the pool's back).
    assert all(a > 0 for a in acks4), acks4
    assert sum(acks1) == sum(acks4)


@pytest.mark.parametrize("shards", [1, 4])
def test_differential_view_batch_vs_per_request_path(tmp_path, shards):
    """The view batch is semantically invisible: the same workload with
    every request forced down the per-request path leaves identical
    waiter results, store state, event history, watch replays and live
    watch events, per tenant."""
    pytest.importorskip("etcd_tpu.native.storecore")
    before = applied_by_path()
    rv, sv, acks_v = run_workload(tmp_path / "view", shards)
    mid = applied_by_path()
    with per_request_path():
        rs, ss, acks_s = run_workload(tmp_path / "scalar", shards)
    after = applied_by_path()
    # the batch engaged in the first run and not at all in the second
    assert mid["view"] - before["view"] >= 20 * (G - 1)
    assert after["view"] == mid["view"]
    assert (after["scalar"] - mid["scalar"]
            == (mid["view"] - before["view"])
            + (mid["scalar"] - before["scalar"]))
    assert rv == rs, "waiter-visible results diverged"
    assert sum(acks_v) == sum(acks_s)
    for g in range(G):
        assert sv[g] == ss[g], g


@pytest.mark.parametrize("shards", [1, 4])
def test_watcher_registering_mid_view_sees_the_views_events(tmp_path,
                                                             shards):
    """A watcher that registers between the pass's quiet check and the
    native call is notified of its tenant's part of the view from the
    ring, in order, and of every later write as a live watcher."""
    pytest.importorskip("etcd_tpu.native.storecore")
    eng = make_engine(tmp_path / "race", shards)
    real = engine_mod.set_applied_view
    raced = []

    def racing(stores, counts, paths, vals, need=None):
        st = eng.store(3)
        if st in stores and not raced:
            raced.append(st.watch("/", recursive=True, stream=True,
                                  since_index=0))
        return real(stores, counts, paths, vals, need)

    engine_mod.set_applied_view = racing
    try:
        boot(eng)
        for g in (1, 3, 5):
            for i in range(3):
                inject(eng, g, Request(method="PUT", path=f"/r{i}",
                                       val=f"a{g}_{i}"))
        for _ in range(200):
            eng.run_round()
            if raced:
                break
        eng._drain_applies()
        assert raced, "tenant 3 never joined a view batch"
        inject(eng, 3, Request(method="PUT", path="/later", val="l"))
        for _ in range(50):
            eng.run_round()
        eng._drain_applies()
        got = []
        while (e := raced[0].next_event(timeout=0.05)) is not None:
            got.append(ev_sig(e))
        assert [(a, n[0], n[1]) for a, n, _, _ in got] == [
            ("set", "/r0", "a3_0"), ("set", "/r1", "a3_1"),
            ("set", "/r2", "a3_2"), ("set", "/later", "l")]
        assert got == history_replay(eng.store(3))
        # the neighbours' parts of the same view landed too
        for g in (1, 5):
            assert eng.store(g).get("/r2").node.value == f"a{g}_2"
    finally:
        engine_mod.set_applied_view = real
        eng.stop()


def test_cursor_is_exact_after_a_mid_batch_failure(tmp_path):
    """The native call gives out mid-batch (its out-of-memory path,
    injected): the cursors stand behind exactly the entries applied, no
    result of the batch is handed out, the shard HALTs, and replay from
    the WAL applies every entry exactly once."""
    pytest.importorskip("etcd_tpu.native.storecore")
    from etcd_tpu.store import native_store

    eng = make_engine(tmp_path / "cut", shards=1)
    real = native_store.set_many_multi
    CUT = 3
    calls = []

    def cut_short(cores, counts, paths, vals, now, need=None):
        if len(paths) < 6:
            return real(cores, counts, paths, vals, now, need)
        calls.append(list(counts))
        kept, left = [], CUT
        for n in counts:
            kept.append(min(n, left))
            left -= kept[-1]
        _, descs, spans = real(cores, kept, paths[:CUT], vals[:CUT], now,
                               [p for p in need or () if p < CUT] or None)
        return CUT, descs, spans

    captured = []

    class Cap:
        def put(self, v):
            captured.append(v)

    try:
        boot(eng)
        base = eng.applied.copy()
        native_store.set_many_multi = cut_short
        # one entry a group: 2, 3, 1, 1 requests in groups 0..3; the cut
        # falls inside group 1's entry
        rids = []
        for g, n in ((0, 2), (1, 3), (2, 1), (3, 1)):
            for i in range(n):
                rid = eng.reqid.next()
                eng.wait._waiters[rid] = Cap()
                rids.append(rid)
                inject(eng, g, Request(method="PUT", path=f"/c{i}",
                                       val=f"v{g}_{i}", id=rid))
        with pytest.raises(MemoryError, match="request 3 of 7"):
            for _ in range(200):
                eng.run_round()
            eng._drain_applies()
        assert calls == [[2, 3, 1, 1]]
        sh = eng._appliers[0]
        sh.thread.join(timeout=5)
        assert isinstance(sh.exc, MemoryError)
        assert not sh.thread.is_alive(), "the shard did not HALT"
        assert not captured, "a result of the failed batch was handed out"
        # group 0's entry is applied whole: its cursor moved; group 1's
        # was cut inside (one request of three landed): it stands before
        # the entry; groups 2 and 3 were never reached
        moved = eng.applied - base
        assert moved[0] == 1 and not moved[1:4].any(), moved[:4]
        assert eng.store(0).current_index == 2
        assert eng.store(1).current_index == 1
        assert eng.store(2).current_index == eng.store(3).current_index == 0
    finally:
        native_store.set_many_multi = real
        eng.stop()
    assert isinstance(eng.failed, MemoryError)
    # never a skipped entry, never a second apply: the restart replays
    # the journalled round over stores rebuilt from nothing
    eng2 = make_engine(tmp_path / "cut", shards=1)
    try:
        for g, n in ((0, 2), (1, 3), (2, 1), (3, 1)):
            st = eng2.store(g)
            assert st.current_index == n, (g, st.current_index)
            assert [st.get(f"/c{i}").node.value for i in range(n)] == [
                f"v{g}_{i}" for i in range(n)]
    finally:
        eng2.wal.close()


@pytest.mark.parametrize("done", [0, 1, 2, 3, 4, 5, 6])
def test_one_cursor_a_group_with_several_entries_in_a_view(tmp_path, done):
    """A group with several entries in one view (a hot tenant, replay's
    deep span) is listed once an entry: its cursor stands at the LAST
    entry the native call applied whole, wherever the call stopped, and
    a group the call never reached does not move."""
    eng = make_engine(tmp_path / f"multi{done}", shards=1)
    real = engine_mod.set_applied_view
    try:
        eng.applied[:3] = (10, 20, 30)
        vb = engine_mod._ViewBatch()
        # group 0: entries 11 (2 requests), 12 (1), 13 (2, then a
        # trailing no-op: the cursor goes to 14); group 2: entry 31 (1)
        vb.cur_g[:] = [0, 0, 0, 2]
        vb.cur_i[:] = [11, 12, 14, 31]
        vb.cur_end[:] = [2, 3, 5, 6]
        vb.paths[:] = [f"/k{i}" for i in range(6)]
        vb.vals[:] = ["v"] * 6
        engine_mod.set_applied_view = lambda *a: (done, None, 0.0)
        if done < 6:
            with pytest.raises(MemoryError, match=f"request {done} of 6"):
                eng._apply_view(vb, True, eng._acks, None)
        else:
            eng._apply_view(vb, True, eng._acks, None)
        want0 = {0: 10, 1: 10, 2: 11, 3: 12, 4: 12, 5: 14, 6: 14}[done]
        assert eng.applied[:3].tolist() == [want0, 20,
                                            31 if done == 6 else 30]
    finally:
        engine_mod.set_applied_view = real
        eng.stop()


@pytest.mark.parametrize("shards", [1, 4])
def test_nothing_is_acked_before_wait_durable_returns(tmp_path, shards):
    """Hold the WAL's durability gate: a view's writes over many tenants
    are applied (the stores run ahead of the WAL pipeline) and no waiter
    of theirs hears of it; release the gate and every one is acked."""
    eng = make_engine(tmp_path / "gate", shards)
    gate, entered = threading.Event(), threading.Event()
    real = eng.wal.wait_durable

    def held(ticket):
        entered.set()
        gate.wait(30)
        return real(ticket)

    captured = {}

    class Cap:
        def __init__(self, rid):
            self.rid = rid

        def put(self, v):
            captured[self.rid] = v

    stop = threading.Event()

    def drive():            # blocks on the appliers' queue cap meanwhile
        while not stop.is_set():
            eng.run_round()

    driver = threading.Thread(target=drive, daemon=True)
    try:
        boot(eng)
        eng.wal.wait_durable = held
        rids = {}
        for g in range(G):
            rid = eng.reqid.next()
            eng.wait._waiters[rid] = Cap(rid)
            rids[rid] = g
            inject(eng, g, Request(method="PUT", path="/d", val=f"v{g}",
                                   id=rid))
        driver.start()
        assert entered.wait(30), "no ack batch reached the gate"

        def applied():
            try:
                return [eng.store(g).get("/d").node.value
                        for g in range(G)]
            except errors.EtcdError:
                return None

        deadline = time.time() + 30
        while applied() is None and time.time() < deadline:
            time.sleep(0.01)
        assert applied() == [f"v{g}" for g in range(G)]
        time.sleep(0.3)
        assert not captured, "acked ahead of the fsync"
        assert eng.acked_requests == 0
        gate.set()
        deadline = time.time() + 30
        while len(captured) < G and time.time() < deadline:
            time.sleep(0.01)
        assert set(captured) == set(rids)
        for rid, g in rids.items():
            assert captured[rid].resolve().node.value == f"v{g}"
    finally:
        gate.set()
        stop.set()
        if driver.is_alive():
            driver.join(30)
        eng.stop()


def _poison_store(eng, g, exc_factory):
    st = eng.store(g)
    def boom(*a, **kw):
        raise exc_factory()
    # every apply path of a native store reads its clock first, the view
    # batch among them; the Python Store's applies go through set
    for name in ("clock", "set_applied", "set_applied_lazy", "set"):
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
        orig = st0.clock

        def slow_clock():   # read once by every apply of this store
            seen.append(len(slow.q))
            time.sleep(0.02)
            return orig()

        st0.clock = slow_clock
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
